#!/usr/bin/env python3
"""Smoke run of the fleet diagnosis tick on one TPU chip, end to end.

    python chip_smoke.py [--seed N] [--ticks N]

Eight in-process hosts, each a ``StepTelemetry(wire=True)`` producer, emit
their rows of eight stage windows per tick: ``incident_columns`` rows, so
every window holds 16384 live rows with injected incidents, as in the
``scale/fleet_gates_8x16384`` and ``fleet_merge_8hosts_16384`` bench rows.
Each host ships one StepDelta v2 payload per tick.  The identical
payloads feed three ``Diagnosis.fleet`` tick paths:

- the path under test: Pallas gate kernel compiled by Mosaic (float32)
  and the jax forecaster;
- the same path with the pure-jnp gates (float64, emulated on a TPU);
- the host reference: numpy gates and the numpy forecaster (float64).

The run fails unless, on every tick, the confirmed cause sets agree
except for knife-edge flips (``repro.core.fleet.knife_edge``: the row's
value lies within float32 rounding of a threshold it crossed), the
forecaster's risks agree with the reference within ``FORECAST_TOL``, and
the gate program that ran was a Mosaic kernel (``tpu_custom_call``).
Tick seconds are a smoke reading, not a benchmark number.

Everything runs in this one process: a chip belongs to one process.  The
last line of standard output is a JSON object with ``ok`` and the device
as JAX reports it.  Off a TPU it prints ``"ok": false`` and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: Largest |risk difference| allowed between the jax forecaster (float64,
#: emulated on a TPU) and the numpy reference.  Risks lie in [0, 1]; a
#: float32 evaluation would already miss this by ~1e-7 relative per op.
FORECAST_TOL = 1e-6


#: Step numbers of stage j start at j × this, so each host's steps of one
#: stage land in one StepTelemetry stage window tick after tick.
_STAGE_STRIDE = 1 << 20


class _ScriptedClock:
    """Hands ``StepTelemetry`` each row's start, then its end."""

    def __init__(self) -> None:
        self.times: list[float] = []

    def __call__(self) -> float:
        return self.times.pop()


def _hosts(n_hosts: int, seed: int, rows_per_tick: int):
    from repro.core import JAX_FEATURES
    from repro.telemetry.events import StepTelemetry

    hosts = []
    for h in range(n_hosts):
        clock = _ScriptedClock()
        telem = StepTelemetry(
            f"host{h}", window=_STAGE_STRIDE, clock=clock,
            schema=JAX_FEATURES, wire=True, boot=seed * 1000 + h,
            wire_pending_cap=rows_per_tick,
        )
        hosts.append((telem, clock))
    return hosts


def _idle_producer(seed: int):
    """The diagnosing process's own producer: it runs no job steps, so
    each tick drains an empty delta while the hosts' rows arrive as
    payload bytes."""
    from repro.core import JAX_FEATURES
    from repro.telemetry.events import StepTelemetry

    return StepTelemetry("diagnosis", schema=JAX_FEATURES, wire=True,
                         boot=seed * 1000 + 999)


def _emit_tick(hosts, tick: int, seed: int, stages: int,
               rows_per_host: int) -> list[bytes]:
    """Record one tick of rows on every host; return their v2 payloads."""
    from repro.anomaly.incident import incident_columns

    n_hosts = len(hosts)
    for j in range(stages):
        cols = incident_columns(n_hosts * rows_per_host,
                                seed=seed * 100_000 + tick * 100 + j)
        t0 = 1000.0 * tick
        starts = t0 + cols["starts"]
        ends = t0 + cols["ends"]
        feats = list(cols["features"].items())
        for h, (telem, clock) in enumerate(hosts):
            for r in range(h * rows_per_host, (h + 1) * rows_per_host):
                clock.times = [float(ends[r]), float(starts[r])]
                step = j * _STAGE_STRIDE + tick * rows_per_host + r % rows_per_host
                with telem.step(step) as s:
                    for name, col in feats:
                        s.add(name, float(col[r]))
    return [telem.drain_delta().to_bytes(version=2) for telem, _ in hosts]


def _diagnosis(backend: str, *, stages: int, rows: int, interpret: bool,
               forecaster=None):
    from repro.core import BigRootsAnalyzer, JAX_FEATURES
    from repro.serve import Diagnosis
    from repro.serve.fleet import FleetAggregator

    an = BigRootsAnalyzer(JAX_FEATURES, backend=backend, backend_min_rows=0,
                          kernel_interpret=interpret)
    agg = FleetAggregator(JAX_FEATURES, an, attribution=True,
                          max_rows=rows, max_stages=stages)
    return Diagnosis.fleet(agg, forecaster=forecaster)


def _forecaster(backend: str, seed: int):
    from repro.core import JAX_FEATURES
    from repro.core.forecast import Forecaster
    from repro.models.forecast_ssd import ForecastConfig, forecast_init

    class Recording(Forecaster):
        """Keeps each tick's risks for the comparison."""

        def step_scores(self, rows, h, update):
            h_new, risks = super().step_scores(rows, h, update)
            self.risks.append(risks)
            return h_new, risks

    cfg = ForecastConfig(features=len(JAX_FEATURES))
    fc = Recording(forecast_init(cfg, seed=seed), cfg, JAX_FEATURES,
                   backend=backend)
    fc.risks = []
    return fc


def _reference_batch(an, windows):
    """The reference analyzer's packed gate batch over ``windows``, and
    where each (stage, task) straggler row sits in it."""
    from repro.core import pack_windows

    entries, where = [], {}
    for w in windows:
        pre = an._window_prelude(w)
        if not isinstance(pre, tuple):
            continue
        n, _, s_rows, ids, _ = pre
        for r, tid in enumerate(ids):
            where[(w.stage_id, tid)] = (len(entries), r)
        entries.append((w, s_rows, n, w.v[s_rows],
                        w.quantiles(an.thresholds.quantile,
                                    exact=an.window_exact_quantiles)))
    batch = pack_windows(entries, an.schema, an.thresholds.time_floor)
    return batch, where


def _cause_flips(want, got, where, knife, names):
    """(knife-edge flips, other flips) between two confirmed cause sets."""
    edge, other = 0, []
    for stage_id, task_id, feature in sorted(want ^ got):
        i, r = where.get((stage_id, task_id), (None, None))
        if i is not None and knife[i, r, names.index(feature)]:
            edge += 1
        else:
            other.append((stage_id, task_id, feature))
    return edge, other


def _gate_program_text(batch, peer_mean: float) -> tuple[str, float]:
    """Compile the gate kernel for the batch's shapes, as ``eval_gates``
    runs it on a TPU; return its compiled text and the seconds taken."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.bigroots_gates import _gates_pallas

    arrays = (batch.v, batch.peer_vsum, batch.inter_cnt, batch.intra_cnt,
              batch.rowmask, batch.vsum, batch.q, batch.numok, batch.floor)
    specs = [jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in arrays]
    t = time.perf_counter()
    compiled = _gates_pallas.lower(
        *specs, peer_mean=float(peer_mean), block_r=256, interpret=False,
    ).compile()
    return compiled.as_text(), time.perf_counter() - t


def run_smoke(*, seed: int = 0, ticks: int = 3, hosts: int = 8,
              rows_per_host: int = 2048, stages: int = 8,
              interpret: bool = False) -> dict:
    """Drive ``ticks`` fleet ticks through the three tick paths and compare
    them.  ``interpret=True`` runs the Pallas kernel in the interpreter
    (a CPU rehearsal at a small size) and skips the Mosaic check.
    Returns the report; ``report["failures"]`` lists what failed."""
    import numpy as np
    from repro.core import JAX_FEATURES, eval_gates_np
    from repro.core.fleet import knife_edge
    from repro.core.forecast import PREDICTED_STRAGGLER
    from repro.kernels.bigroots_gates import eval_gates

    rows = hosts * rows_per_host
    producers = _hosts(hosts, seed, stages * rows_per_host)
    paths = {
        "numpy": _diagnosis("numpy", stages=stages, rows=rows,
                            interpret=interpret,
                            forecaster=_forecaster("numpy", seed)),
        "pallas": _diagnosis("pallas", stages=stages, rows=rows,
                             interpret=interpret,
                             forecaster=_forecaster("jax", seed)),
        "jax": _diagnosis("jax", stages=stages, rows=rows,
                          interpret=interpret),
    }
    idle = {k: _idle_producer(seed) for k in paths}

    def tick_path(k: str, payloads) -> tuple[float, set]:
        """Ingest the payloads and tick; (seconds, confirmed cause keys)."""
        diag = paths[k]
        t = time.perf_counter()
        for p in payloads:
            diag.aggregator.ingest(p)
        fresh = diag.tick(idle[k])
        return time.perf_counter() - t, {
            (c.stage_id, c.task_id, c.feature) for c in fresh
            if c.feature != PREDICTED_STRAGGLER}

    names = JAX_FEATURES.names
    failures: list[str] = []
    report: dict = {}
    flips = {k: {"knife_edge": 0, "other": 0, "gbits": 0}
             for k in ("pallas", "jax")}
    tick_s: list[float] = []
    forecast_diff = 0.0
    stragglers = causes = 0
    for tick in range(ticks):
        payloads = _emit_tick(producers, tick, seed, stages, rows_per_host)
        _, want = tick_path("numpy", payloads)
        ref = paths["numpy"].aggregator
        batch, where = _reference_batch(ref.analyzer, list(ref.store.stages()))
        pm = ref.analyzer.thresholds.peer_mean
        if tick == 0 and not interpret:
            # Compile the kernel at the shapes the tick will use before
            # the tick runs it, so the compile is timed cold.
            text, report["gate_kernel_compile_s"] = _gate_program_text(
                batch, pm)
            report["mosaic"] = ("tpu_custom_call" in text and not
                                paths["pallas"].aggregator.analyzer
                                .kernel_interpret)
            if not report["mosaic"]:
                failures.append("the gate program is not a Mosaic kernel")
        secs, got_pallas = tick_path("pallas", payloads)
        tick_s.append(secs)
        got = {"pallas": got_pallas, "jax": tick_path("jax", payloads)[1]}
        want_bits = eval_gates_np(batch, pm)
        knife = knife_edge(batch, pm)
        stragglers = int(batch.counts.sum())
        causes = len(want)
        if not causes:
            failures.append(f"tick {tick}: the reference confirmed no cause")
        for k in ("pallas", "jax"):
            got_bits = eval_gates(
                batch.v, batch.peer_vsum, batch.inter_cnt, batch.intra_cnt,
                batch.rowmask, batch.vsum, batch.q, batch.numok, batch.floor,
                peer_mean=pm, backend=k, interpret=interpret)
            bit_flips = got_bits != want_bits
            flips[k]["gbits"] += int(bit_flips.sum())
            if (bit_flips & ~knife).any():
                failures.append(f"tick {tick}: {k} gate bits differ from the "
                                "f64 oracle away from any knife edge")
            edge, other = _cause_flips(want, got[k], where, knife, names)
            flips[k]["knife_edge"] += edge
            flips[k]["other"] += len(other)
            if other:
                failures.append(f"tick {tick}: {k} cause flips off the knife "
                                f"edge: {other[:5]}")
        got_r = paths["pallas"].forecaster.risks[-1]
        want_r = paths["numpy"].forecaster.risks[-1]
        if got_r.shape != want_r.shape:
            failures.append(f"tick {tick}: forecaster scored {got_r.shape} "
                            f"rows, reference {want_r.shape}")
        else:
            forecast_diff = max(forecast_diff,
                                float(np.max(np.abs(got_r - want_r))))
        print(f"tick {tick}: stragglers={stragglers} causes={causes} "
            f"pallas_flips={flips['pallas']} jax_flips={flips['jax']} "
            f"tick_s={tick_s[-1]!r}")
    if forecast_diff > FORECAST_TOL:
        failures.append(f"forecaster risks differ by {forecast_diff!r} "
                        f"> {FORECAST_TOL!r}")
    report.update({
        "ticks": ticks, "windows": stages, "rows_per_window": rows,
        "stragglers_last_tick": stragglers, "causes_last_tick": causes,
        "flips": flips, "forecast_max_diff": forecast_diff,
        "forecast_tol": FORECAST_TOL,
        # The cold tick compiles the forecaster step; the gate kernel's
        # compile is gate_kernel_compile_s.
        "cold_tick_s": tick_s[0],
        "warm_tick_s": statistics.median(tick_s[1:]) if ticks > 1 else None,
        "failures": failures,
    })
    return report


def _device() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ticks", type=int, default=3)
    args = ap.parse_args(argv)
    if args.ticks < 3:
        ap.error("--ticks must be at least 3")
    from repro.launch.cache import use_compile_cache

    cache = Path(use_compile_cache())
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"compile cache: {cache} ({entries} entries at start)")
    device = _device()
    print(f"device: {device}")
    if device["platform"] != "tpu":
        print(json.dumps({"ok": False, "device": device,
                          "error": "JAX found no TPU"}))
        return 1
    try:
        report = run_smoke(seed=args.seed, ticks=args.ticks)
    except Exception as e:  # noqa: BLE001 - any phase failing fails the run
        import traceback

        traceback.print_exc()
        print(json.dumps({"ok": False, "device": device,
                          "error": f"{type(e).__name__}: {e}"}))
        return 1
    print("smoke report (tick seconds are a smoke reading, not a benchmark "
          "number): " + json.dumps(report))
    if report["failures"]:
        print(json.dumps({"ok": False, "device": device,
                          "failures": report["failures"]}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
