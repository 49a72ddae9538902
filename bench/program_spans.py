#!/usr/bin/env python3
"""The program's own spans (``bigroots.*``, written by ``repro.spans``)
in a traced run, and a check of what they cost.

The per-layer metrics that split ingest and the sweep read them, per
thousand rows (backlogged cells) or per tick (open-loop cells).
``run.py`` writes a traced run's profile under
``<checkout>/bench_out/trace`` and reduces it to its own ``bench.*``
spans; :func:`load` reads the ``bigroots.*`` spans of the same profile,
clipped to the window.  A profile is read only if its ``bench.window``
span gives exactly the window ``run.py`` measured, so a stale profile is
never taken for the run's own.  A program without spans gives none, and
its readers return None.

Run as a script, it makes the measurements the spans are judged by, in
one process on the chip::

    python3 bench/program_spans.py --workload megascale_12k.incident \\
        --seed <n> --seconds 30

- a traced run with the program's spans on (they follow the profiler, as
  in the benchmark's traced runs): every per-layer metric of the cell,
  the program's counters over the window, the packed stragglers beside
  the reference's, and the ten longest device-idle gaps labelled by the
  innermost span of either kind;
- the same run with the program's spans switched off, for the cost of
  the spans as ``ingest_ms_per_krow`` on against off;
- the cost of a span, off (a shared null context) and on (under a
  trace), timed on this host, times the spans a tick enters.

It prints one JSON line last.
"""
from __future__ import annotations

import bisect
import sys
from pathlib import Path

PREFIX = "bigroots."
WINDOW = "bench.window"


def load(run: dict, root: Path) -> dict[str, list[tuple[int, int]]]:
    """``{name: [(start_ns, end_ns), ...]}`` of the program's spans in the
    run's traced window (empty without them); kept in
    ``run["program_spans"]`` so that the readers of one run read the
    profile once."""
    if "program_spans" not in run:
        window_s = (run.get("trace") or {}).get("window_s")
        run["program_spans"] = _read(root / "bench_out" / "trace", window_s)
    return run["program_spans"]


def _read(trace_dir: Path, window_s: float | None) -> dict:
    import trace_reduce
    from jax.profiler import ProfileData

    try:
        path = trace_reduce.find_trace(trace_dir)
    except FileNotFoundError:
        return {}
    window, spans = None, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW or e.name.startswith(PREFIX):
                    s = int(e.start_ns)
                    iv = (s, int(e.start_ns + e.duration_ns))
                    if e.name == WINDOW:
                        window = window or iv
                    else:
                        spans.setdefault(e.name, []).append(iv)
    if window is None or (window[1] - window[0]) / 1e9 != window_s:
        return {}
    w0, w1 = window
    return {name: [(max(s, w0), min(e, w1)) for s, e in ivs
                   if e > w0 and s < w1]
            for name, ivs in spans.items()}


def self_seconds(spans: dict, name: str, children=()) -> float:
    """Seconds inside spans ``name``, less the ``children`` spans nested
    in them (children do not overlap one another)."""
    inner = sorted(iv for c in children for iv in spans.get(c, ()))
    starts = [s for s, _ in inner]
    total = 0
    for s, e in spans.get(name, ()):
        total += e - s
        i = bisect.bisect_left(starts, s)
        while i < len(inner) and inner[i][0] < e:
            if inner[i][1] <= e:
                total -= inner[i][1] - inner[i][0]
            i += 1
    return total / 1e9


def ms_per(run: dict, root: Path, name: str, per: str,
           children=()) -> float | None:
    """Milliseconds of span ``name`` (its self time, without
    ``children``) per thousand rows (``per="krow"``) or per tick
    (``per="tick"``) of the window, or None where the run has no such
    span."""
    spans = load(run, root)
    n = run["rows"] / 1e3 if per == "krow" else run["ticks"]
    if not spans.get(name) or not n:
        return None
    return self_seconds(spans, name, children) * 1e3 / n


def reader(path: str, name: str, per: str, children=()):
    """The ``read(run)`` of the metric file at ``path``, which lies under
    ``<checkout>/bench/metrics``: :func:`ms_per` on that checkout's
    profile."""
    root = Path(path).parents[2]
    return lambda run: ms_per(run, root, name, per, children)


def _span_ns(trace_dir: Path) -> dict[str, float]:
    """Nanoseconds to enter and leave a span (best of five timings): a
    leaf and a root span while spans are off, and a leaf span while on
    under a trace collected as ``run.py`` collects its own."""
    import timeit

    import jax

    from repro import spans

    def best(stmt: str, n: int) -> float:
        return min(timeit.repeat(stmt, globals=vars(spans), number=n,
                                 repeat=5)) / n * 1e9

    out = {"leaf_off": best('with span("bigroots.decode"): pass', 200_000),
           "root_off": best('with root("bigroots.ingest"): pass', 200_000)}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        spans.enable(True)
        out["leaf_on"] = best('with span("bigroots.decode"): pass', 20_000)
    finally:
        spans.enable(None)
        jax.profiler.stop_trace()
    return out


def main(argv: list[str] | None = None) -> int:
    import argparse
    import json

    import run as bench
    import trace_reduce

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    _spec, cell, _cfg, traffic = bench.load_cell(args.workload)
    sys.path.insert(0, str(bench.ROOT / "src"))
    try:
        device, peaks = bench.check_device(int(cell["chips"]))
    except bench.BenchError as e:
        print(f"program_spans: {e}", file=sys.stderr)
        return 2
    from repro import spans

    n_warm = int(traffic["warm_ticks"])
    seen: dict = {}

    def watch(svc):
        """Counters at the window's start and end."""
        seen["agg"], tick, calls = svc.agg, svc.diag.tick, [0]

        def counted(*a, **kw):
            out = tick(*a, **kw)
            calls[0] += 1
            if calls[0] == n_warm:
                seen["start"] = svc.agg.counters()
            return out
        svc.diag.tick = counted

    on = bench.run_cell(args.workload, args.seed, args.seconds, True,
                        peaks=peaks, device=device, hooks={"watch": watch})
    end = seen["agg"].counters()
    start = seen.get("start", {})
    counters = {k: end[k] - start.get(k, 0) for k in end}
    # The idle gaps again, labelled by the program's spans as well.
    trace_reduce.SPAN_PREFIX = ("bench.", PREFIX)
    relabelled = trace_reduce.reduce(trace_reduce.find_trace(
        bench.ROOT / "bench_out" / "trace"))
    trace_reduce.SPAN_PREFIX = "bench."
    entries = sum(len(v) for k, v in relabelled["spans"].items()
                  if k.startswith(PREFIX))

    def off_hook(_svc):
        spans.enable(False)
    try:
        off = bench.run_cell(args.workload, args.seed, args.seconds, True,
                             peaks=peaks, device=device,
                             hooks={"off": off_hook})
    finally:
        spans.enable(None)
    cost_ns = _span_ns(bench.ROOT / "bench_out" / "span_cost")
    per_tick = entries / max(counters.get("ticks", 0), 1)
    roots = (counters["deltas_ingested"] + counters["ticks"]) / max(
        counters["ticks"], 1)
    off_ms = ((per_tick - roots) * cost_ns["leaf_off"]
              + roots * cost_ns["root_off"]) / 1e6
    ingest = {k: r["metrics"]["ingest_ms_per_krow"]["value"]
              for k, r in (("on", on), ("off", off))}
    out = {
        "device": on["device"], "correct": [on["correct"], off["correct"]],
        "metrics_spans_on": {k: v["value"] for k, v in on["metrics"].items()},
        "metrics_spans_off": {k: v["value"] for k, v in off["metrics"].items()},
        "ingest_on_over_off": ingest["on"] / ingest["off"] - 1.0,
        "counters": counters,
        "stragglers_check": {"packed": counters["stragglers_packed"],
                             "reference": on["info"]["stragglers_window"]},
        "idle_gaps": relabelled["breakdown"]["idle_gaps"],
        "span_entries_per_tick": per_tick,
        "span_ns": cost_ns,
        "spans_off_ms_per_tick": off_ms,
        "spans_on_ms_per_tick": per_tick * cost_ns["leaf_on"] / 1e6,
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
