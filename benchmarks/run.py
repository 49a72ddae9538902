"""Benchmark harness: one function per paper table/figure + beyond-paper
scale/kernel benches.  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run table3 fig8
    PYTHONPATH=src python -m benchmarks.run --check    # regression gate

``--check`` compares the produced rows against the committed
``BENCH_baseline.json`` (same directory) and exits non-zero if any
baselined row regresses more than ``_tolerance``× (default 2×) — the CI
gate for the hot analyzer paths (``scale/analyzer_16384_hosts`` and the
streaming ``scale/stream_step_analyze_16384``).  With no bench names
given, ``--check`` runs the benches the baseline covers and a baseline
row the run failed to produce is itself a failure (loud gate
misconfiguration); with explicit bench names, only the baseline rows
those benches produced are compared.

Every ``--check`` run also writes a machine-readable
``BENCH_current.json`` (override the path with the ``BENCH_CURRENT_OUT``
env var) with all produced rows and per-row verdicts; CI uploads it as a
build artifact so the perf trajectory accumulates per commit.  Deliberate
re-baselining (new hardware) = copy ``BENCH_current.json`` rows into
``BENCH_baseline.json``.
"""
from __future__ import annotations

import json
import os
import sys

from . import paper_tables, scale_bench

BENCHES = {
    "table3": paper_tables.table3,
    "fig7": paper_tables.fig7,
    "fig8": paper_tables.fig8,
    "fig9": paper_tables.fig9,
    "table5": paper_tables.table5,
    "table6": paper_tables.table6,
    "table7": paper_tables.table7,
    "analyzer_scale": scale_bench.analyzer_scale,
    "streaming_scale": scale_bench.streaming_scale,
    "fleet_gates": scale_bench.fleet_gates,
    "fleet_merge": scale_bench.fleet_merge,
    "tree_merge": scale_bench.tree_merge,
    "wire_transport": scale_bench.wire_transport,
    "policy_eval": scale_bench.policy_eval,
    "whatif_replay": scale_bench.whatif_replay,
    "forecast": scale_bench.forecast,
    "scenario_fleet": scale_bench.scenario_fleet,
    "kernels": scale_bench.kernel_bench,
    "e2e_train": scale_bench.e2e_train_bench,
}

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "BENCH_baseline.json")
CURRENT_PATH = os.environ.get(
    "BENCH_CURRENT_OUT",
    os.path.join(os.path.dirname(__file__), "BENCH_current.json"),
)


def _load_baseline() -> tuple[dict[str, float], float]:
    with open(BASELINE_PATH) as f:
        obj = json.load(f)
    tolerance = float(obj.pop("_tolerance", 2.0))
    rows = {k: float(v) for k, v in obj.items() if not k.startswith("_")}
    return rows, tolerance


def _check(rows: dict[str, float], require_all: bool) -> int:
    baseline, tolerance = _load_baseline()
    failures = 0
    verdicts: dict[str, str] = {}
    for name, base_us in sorted(baseline.items()):
        got = rows.get(name)
        if got is None:
            if require_all:
                print(f"CHECK,{name},MISSING (bench did not produce this row)")
                verdicts[name] = "MISSING"
                failures += 1
            continue
        ratio = got / base_us if base_us > 0 else float("inf")
        verdict = "OK" if ratio <= tolerance else "REGRESSION"
        verdicts[name] = verdict
        print(f"CHECK,{name},{verdict} got={got:.1f}us "
              f"baseline={base_us:.1f}us ratio={ratio:.2f}x limit={tolerance:.1f}x")
        if verdict != "OK":
            failures += 1
    _write_current(rows, verdicts, tolerance)
    return failures


def _write_current(rows: dict[str, float], verdicts: dict[str, str],
                   tolerance: float) -> None:
    """Persist this run's rows for the per-commit perf trajectory (CI
    uploads the file as an artifact; re-baselining copies rows from it)."""
    out = {
        "_comment": "us_per_call rows produced by the last `--check` run; "
                    "see BENCH_baseline.json for the gated subset.",
        "_tolerance": tolerance,
        "_verdicts": verdicts,
    }
    out.update({k: round(v, 1) for k, v in sorted(rows.items())})
    with open(CURRENT_PATH, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"CHECK,_artifact,wrote {CURRENT_PATH}")


def main() -> None:
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    argv = list(sys.argv[1:])
    check = "--check" in argv
    if check:
        argv.remove("--check")
    if argv:
        wanted = argv
    elif check:
        wanted = ["analyzer_scale", "streaming_scale", "fleet_gates",
                  "fleet_merge", "tree_merge", "wire_transport",
                  "policy_eval", "whatif_replay", "forecast",
                  "scenario_fleet"]
    else:
        wanted = list(BENCHES)

    print("name,us_per_call,derived")
    failures = 0
    rows: dict[str, float] = {}
    for name in wanted:
        fn = BENCHES[name]
        try:
            _rows, csv_rows = fn()
            for row_name, us, derived in csv_rows:
                rows[row_name] = us
                print(f"{row_name},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0,ERROR={type(e).__name__}:{e}")
    if check:
        failures += _check(rows, require_all=not argv)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
