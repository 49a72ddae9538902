#!/usr/bin/env python3
"""Chip benchmark of the fleet diagnosis tick: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on one chip.  It sets up (device, compile cache, generator
workers, the pre-filled windows, warm-up of every shape the cell's ticks
use, warm ticks), measures ticks of the real entry points for
``--seconds`` (``FleetAggregator.ingest`` for each host payload, then
``Diagnosis.fleet(agg).tick``), then checks every tick's causes against
the configuration's plain reference and prints one JSON line last.
``--trace 1`` traces the window with the JAX profiler and reports the
cell's per-layer metrics instead of its end-to-end ones.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, ``bench/traffic/<traffic>.json`` and one reader
per metric in ``bench/metrics/<metric>.py``.  The configuration names
the rest:

- ``fleet``: the generator's fleet, ``bench/fleets/<fleet>.py`` (see
  ``gen.py``);
- ``schema``: the program's feature schema,
  ``repro.core.features.get_schema(schema)``, whose columns must be the
  configuration's ``features``;
- ``reference`` (``"reference"`` where the key is absent): the plain
  reference and the comparison, ``bench/<reference>.py``.  It holds
  ``Reference(cfg, precision=)`` (``"float64"``, or ``"control"`` for the
  lower-precision control) with ``prefill(blocks)``, ``ingest(blocks)``
  (one host payload), ``step()`` (one tick: fresh causes and per-window
  records with ``stragglers``), ``straggler_counts()`` (a tick's change
  detection alone: the straggler count of each changed window that has
  any), ``rows_accepted``, ``live_rows`` and ``names``; and
  ``compare(got, want, records, names)`` with the ``LIMITS`` of the
  numbers it returns;
- ``analyzer_options`` and ``aggregator_options``: keyword arguments
  passed as they are to ``BigRootsAnalyzer`` and ``FleetAggregator``,
  beside those this file sets, which they may not repeat.

Off a TPU, or on a device kind missing from ``bench/peaks.json``, it
exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: A run that has not ended by then dumps its threads and exits (the
#: first run of a cell in a checkout compiles, and may take 1,200 s).
WATCHDOG_S = 1100
#: The program pads a gate launch's rows to a multiple of this (the row
#: bucket of ``repro.core.fleet.pack_windows``), so a launch's compiled
#: shape is (windows, padded rows, features).
ROW_BUCKET = 256
#: Where a cell's window holds an unknown number of ticks (backlog), the
#: warm-up looks ahead until this many ticks in a row bring no new shape.
SETTLED_TICKS = 16
#: JAX's event for a program traced (a jit cache miss): counted inside
#: the window, where nothing may compile.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_traced = [0, 0]                 # traces counted, listener registered
#: Seconds that the device check spent importing JAX and finding the
#: devices (the TPU runtime's start): the first parts of set-up.
_setup_marks: dict[str, float] = {}
#: The TPU runtime pins a host staging buffer for transfers when it
#: starts; the gate launches move a few MB, so a small one serves.
os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 << 20))


def _count_trace(event: str, _secs: float, **_kw) -> None:
    if event == TRACE_EVENT:
        _traced[0] += 1


def log(msg: str) -> None:
    print(f"bench [{time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


class BenchError(RuntimeError):
    """The run cannot measure: no result is printed."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """(benchmark spec, cell, configuration, traffic) of cell ``name``."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(root / entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, cfg, traffic


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: end-to-end without trace,
    per-layer with it."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of metric ``name``."""
    import gen

    path = root / "bench" / "metrics" / f"{name}.py"
    return gen.load_module(path, f"bench_metric_{name}").read


def load_reference(cfg: dict, root: Path):
    """The plain reference module that configuration ``cfg`` names."""
    import gen

    name = cfg.get("reference", "reference")
    path = root / "bench" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"the configuration's reference {path} is missing")
    return gen.load_module(path, f"bench_reference_{name}")


def check_device(chips: int) -> tuple[dict, dict]:
    """The device as JAX reports it and its peaks; raises BenchError off
    a TPU, short of chips, or on a device kind with no peaks."""
    t = time.perf_counter()
    import jax

    _setup_marks["jax_import"] = time.perf_counter() - t
    t = time.perf_counter()
    devs = jax.devices()
    _setup_marks["device_init"] = time.perf_counter() - t
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        raise BenchError(f"JAX found no TPU (platform {device['platform']!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks = load_json(BENCH / "peaks.json")
    if device["kind"] not in peaks:
        raise BenchError(f"device kind {device['kind']!r} is not in peaks.json")
    return device, peaks[device["kind"]]


def cause_record(c) -> dict:
    a = c.attribution
    return {"stage": c.stage_id, "task_id": c.task_id, "feature": c.feature,
            "groups": tuple(c.peer_groups), "value": float(c.value),
            "severity": int(c.severity),
            "recovery": (float("nan") if a is None
                         else float(a.estimated_recovery_s))}


def _options(cfg: dict, key: str, fixed: dict) -> dict:
    """``fixed`` keyword arguments and the configuration's ``key`` dict,
    which may add arguments but not repeat one of ``fixed``."""
    extra = cfg.get(key, {})
    clash = sorted(fixed.keys() & extra.keys())
    if clash:
        raise BenchError(f"the configuration's {key} repeat {clash}, "
                         "which the benchmark sets")
    return {**fixed, **extra}


class Service:
    """The system under test, built as the configuration states."""

    def __init__(self, cfg: dict, *, interpret: bool) -> None:
        from repro.core import BigRootsAnalyzer
        from repro.core.analyzer import BigRootsThresholds
        from repro.core.features import get_schema
        from repro.serve import Diagnosis
        from repro.serve.fleet import FleetAggregator
        from repro.telemetry.events import StepTelemetry

        try:
            schema = get_schema(cfg["schema"])
        except KeyError as e:
            raise BenchError(str(e))
        stated = [(n, k) for n, k in cfg["features"].items()]
        actual = [(s.name, s.kind.value) for s in schema]
        if stated != actual:
            raise BenchError(f"schema {cfg['schema']!r} is {actual}, the "
                             f"configuration states {stated}")
        th = cfg["thresholds"]
        thresholds = BigRootsThresholds(
            quantile=th["quantile"],
            peer_mean=th["peer_mean"],
            time_floor=th["time_floor"], straggler=th["straggler"])
        self.analyzer = BigRootsAnalyzer(**_options(cfg, "analyzer_options", {
            "schema": schema, "thresholds": thresholds, "backend": "pallas",
            "kernel_interpret": interpret,
            "window_exact_quantiles": cfg["lambda_q"] == "exact"}))
        self.agg = FleetAggregator(**_options(cfg, "aggregator_options", {
            "schema": schema, "analyzer": self.analyzer,
            "attribution": bool(cfg["attribution"]),
            "max_stages": int(cfg["max_stages"]),
            "decay_steps": int(cfg["decay_steps"])}))
        self.diag = Diagnosis.fleet(self.agg)
        self.idle = StepTelemetry("diagnosis", schema=schema, wire=True,
                                  boot=1)

    def prefill(self, blocks: list[dict]) -> None:
        """Fill windows through the columnar bulk path."""
        for b in blocks:
            self.agg.store.add_rows(
                b["stage"], b["task_ids"], b["nodes"], b["starts"], b["ends"],
                b["locality"], feature_columns=b["features"])

    def warm_gates(self, shapes: list[tuple[int, int]]) -> None:
        """Compile (or load from the persistent cache) the gate kernel at
        each (windows, padded rows) launch shape, through the analyzer's
        own dispatch."""
        import numpy as np
        from repro.core.fleet import FleetGateBatch

        F = len(self.analyzer.schema)
        for W, R in shapes:
            rows, per_row = np.zeros((W, R, F)), np.ones((W, R, 1))
            per_win = np.zeros((W, 1, F))
            self.analyzer._eval_gates_batch(FleetGateBatch(
                rows, rows, per_row, per_row, per_row, per_win, per_win,
                per_win, np.zeros((1, 1, F)), np.full(W, R)))


def launch_shapes(reference, cfg: dict, tr, prefill: list[dict],
                  ticks: int | None) -> list[tuple[int, int]]:
    """(windows, padded rows) of every gate launch of ticks 0, 1, ...:
    the change detection and straggler counts of the ``reference``
    module on the generator's rows, for ``ticks`` ticks or, where that is
    unknown, until ``SETTLED_TICKS`` ticks in a row bring no new shape."""
    ref = reference.Reference(cfg)
    ref.prefill(prefill)
    shapes: set[tuple[int, int]] = set()
    k = settled = 0
    while k < ticks if ticks is not None else settled < SETTLED_TICKS:
        for *_, blocks in tr.tick(k):
            ref.ingest(blocks)
        counts = ref.straggler_counts()
        k += 1
        settled += 1
        if counts:
            rows = max(ROW_BUCKET, -(-max(counts) // ROW_BUCKET) * ROW_BUCKET)
            if (len(counts), rows) not in shapes:
                shapes.add((len(counts), rows))
                settled = 0
    return sorted(shapes)


def lag_growth(lat_ms: list[float]) -> float | None:
    """Median latency of the window's last fifth of ticks minus its first
    fifth: near 0 below the sustained rate, growing with the run above."""
    fifth = len(lat_ms) // 5
    if not fifth:
        return None
    import statistics

    return (statistics.median(lat_ms[-fifth:])
            - statistics.median(lat_ms[:fifth]))


def span(name: str, on: bool):
    if not on:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             interpret: bool = False, control: bool = False,
             root: Path = ROOT, overrides: dict | None = None,
             trace_dir: Path | None = None, t_start: float = T_START,
             peaks: dict | None = None, device: dict | None = None,
             hooks: dict | None = None) -> dict:
    """Run one cell and return its result line (a dict).

    ``interpret``, ``overrides`` (``{"config": {...}, "traffic": {...}}``
    merged into the files' contents) and ``hooks`` exist for the CPU
    rehearsal tests; ``control`` replaces the service's causes by the
    lower-precision control's before the comparison."""
    sys.path.insert(0, str(root / "src"))
    sys.path.insert(0, str(BENCH))
    import gen
    import kernels

    spec, cell, cfg, traffic = load_cell(cell_name, root)
    cfg = dict(cfg, **(overrides or {}).get("config", {}))
    traffic = dict(traffic, **(overrides or {}).get("traffic", {}))
    reference = load_reference(cfg, root)
    hooks = hooks or {}
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise BenchError(f"the program is not in this checkout: {e}")

    import jax
    from repro.launch.cache import use_compile_cache

    if not interpret:
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _traced[1]:
        jax.monitoring.register_event_duration_secs_listener(_count_trace)
        _traced[1] = 1

    tr = gen.make_traffic(cfg, traffic, seed, root)
    n_warm = int(traffic["warm_ticks"])
    open_loop = traffic["arrival"] == "open"
    n_window = math.ceil(seconds / cfg["tick_s"]) if open_loop else None
    # Open loop: one worker buffers the whole run ahead.  Backlog: two
    # workers a few ticks ahead, since the service never waits for a due
    # time.
    workers, ahead = (1, n_warm + n_window) if open_loop else (2, 4)
    generator = gen.Generator(cfg, traffic, seed, workers=workers,
                              ahead=ahead, root=root)
    got: list[list[dict]] = []
    parts = dict(_setup_marks, start=time.perf_counter() - t_start)
    try:
        svc = Service(cfg, interpret=interpret)
        for name, fn in hooks.items():
            fn(svc)
        mark = time.perf_counter()
        prefill = tr.prefill_blocks()
        svc.prefill(prefill)
        parts["prefill"] = time.perf_counter() - mark
        # Every gate launch shape of the run compiles (or loads from the
        # persistent cache) here, not in the window.
        mark = time.perf_counter()
        shapes = launch_shapes(reference, cfg, tr, prefill,
                               n_warm + n_window if open_loop else None)
        parts["shape_lookahead"] = time.perf_counter() - mark
        mark = time.perf_counter()
        svc.warm_gates(shapes)
        parts["shape_warmup"] = time.perf_counter() - mark
        mark = time.perf_counter()
        for k in range(n_warm):
            payloads, _rows = generator.get(k)
            for p in payloads:
                svc.agg.ingest(p)
            got.append([cause_record(c) for c in svc.diag.tick(svc.idle)])
        parts["warm_ticks"] = time.perf_counter() - mark
        log(f"set-up done: {parts}")

        log_dir = trace_dir or (root / "bench_out" / "trace")
        if trace:
            import shutil

            shutil.rmtree(log_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        lat_ms: list[float] = []
        rows = 0
        late = 0
        k = n_warm
        window = span("bench.window", trace)
        window.__enter__()
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        cpu0 = time.process_time()
        traced0 = _traced[0]
        while True:
            if open_loop:
                if k - n_warm >= n_window:
                    break
                due = t0 + tr.due_s(k - n_warm)
                now = time.perf_counter()
                if now < due:
                    with span("bench.schedule", trace):
                        time.sleep(due - now)
            ready = generator.ready(k)
            with span("bench.generator", trace):
                payloads, n_rows = generator.get(k)
            if not ready:
                late += 1
                print(f"generator: tick {k} was not ready when due",
                      file=sys.stderr)
            start = due if open_loop else time.perf_counter()
            with span("bench.tick", trace):
                for p in payloads:
                    with span("bench.ingest", trace):
                        svc.agg.ingest(p)
                with span("bench.sweep", trace):
                    fresh = svc.diag.tick(svc.idle)
            end = time.perf_counter()
            lat_ms.append((end - start) * 1e3)
            rows += n_rows
            got.append([cause_record(c) for c in fresh])
            k += 1
            if not open_loop and end - t0 >= seconds:
                break
        cpu_s = time.process_time() - cpu0
        window_s = end - t0
        traced = _traced[0] - traced0
        window.__exit__(None, None, None)
        if trace:
            jax.profiler.stop_trace()
    finally:
        generator.close()
    log(f"window done: {k - n_warm} ticks, {rows} rows in {window_s:.3f} s")
    if traced:
        log(f"warning: {traced} programs traced inside the window")
    if late and not open_loop:
        raise BenchError(f"the service waited on the generator in {late} "
                         "ticks of a backlogged run: the run is invalid")
    stats = jax.devices()[0].memory_stats() or {}
    program = {"rows_ingested": svc.agg.rows_ingested,
               "live_rows": svc.agg.num_live_rows}
    del svc
    ticks = k

    # The check, after the window, on the rows the window used.
    t_check = time.perf_counter()
    ref = reference.Reference(cfg)
    ref.prefill(prefill)
    ctl = reference.Reference(cfg, precision="control") if control else None
    if ctl is not None:
        ctl.prefill(prefill)
    want, records, ctl_out = [], [], []
    for t in range(ticks):
        payloads = tr.tick(t)
        for *_, blocks in payloads:
            ref.ingest(blocks)
            if ctl is not None:
                ctl.ingest(blocks)
        fresh, recs = ref.step()
        want.append(fresh)
        records.append(recs)
        if ctl is not None:
            ctl_out.append(ctl.step()[0])
    log("reference replayed")
    judged = reference.compare(ctl_out if control else got, want, records,
                               ref.names)
    checks = {
        "rows_lost": abs(ref.rows_accepted - program["rows_ingested"]),
        "live_rows_diff": abs(ref.live_rows - program["live_rows"]),
        "cause_flips": judged["cause_flips"],
        "value_rel_err": judged["value_rel_err"],
        "recovery_err_s": judged["recovery_err_s"],
    }
    correct = all(checks[k] <= lim for k, lim in reference.LIMITS.items())
    info = {"ticks_compared": ticks, "causes_compared": judged["compared"],
            "exempt": judged["exempt"], "examples": judged["examples"],
            "stragglers_last_tick": sum(r["stragglers"] for r in records[-1]),
            "stragglers_window": sum(r["stragglers"] for recs in
                                     records[n_warm:] for r in recs),
            "causes_window": sum(len(x) for x in want[n_warm:]),
            "late_ticks": late,
            "lag_growth_ms": lag_growth(lat_ms) if open_loop else None,
            "launch_shapes": len(shapes),
            "traced_in_window": traced,
            "setup_parts_s": parts,
            "check_s": time.perf_counter() - t_check}

    run = {"setup_s": setup_s, "window_s": window_s, "rows": rows,
           "ticks": ticks - n_warm, "tick_latency_ms": lat_ms,
           "cpu_s": cpu_s, "peaks": peaks or {}}
    out_device = dict(device or {"platform": jax.devices()[0].platform,
                                 "kind": jax.devices()[0].device_kind,
                                 "count": len(jax.devices())})
    out_device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    breakdown = None
    if trace:
        import trace_reduce

        t = trace_reduce.reduce(trace_reduce.find_trace(log_dir))
        run["trace"] = t
        run["spans"] = t["spans"]
        width = len(cfg["features"])
        run["gate_bytes"] = sum(
            kernels.gate_useful_bytes([r["stragglers"] for r in recs], width)
            for recs in records[n_warm:] if recs)
        out_device["busy_s"] = t["busy_s"]
        out_device["window_s"] = t["window_s"]
        breakdown = t["breakdown"]
    metrics = {}
    for m in cell_metrics(spec, cell_name, trace):
        value = load_reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": ticks,
              "failed": judged["failed_ticks"] + (checks["rows_lost"] > 0),
              "metrics": metrics, "device": out_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["info"] = info
    result["checks"] = {k: {"value": checks[k], "limit": lim}
                        for k, lim in reference.LIMITS.items()}
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="judge the lower-precision control in the "
                         "service's place (it must come out not correct)")
    args = ap.parse_args(argv)
    # A run that hangs shows where, and ends: tracebacks on SIGTERM, and
    # after WATCHDOG_S a dump and exit.
    faulthandler.register(signal.SIGTERM, all_threads=True)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    try:
        spec, cell, _cfg, _traffic = load_cell(args.workload)
        sys.path.insert(0, str(ROOT / "src"))
        device, peaks = check_device(int(cell["chips"]))
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), control=args.control,
                          peaks=peaks, device=device)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
