#!/usr/bin/env python3
"""Rate sweep of an open-loop cell: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2000 3000 4000

Each rate is one run of the cell (``run.py``'s path, its traffic's
``rows_per_s`` replaced), each in a process of its own, one after
another: this process never touches JAX, so each child has the chip to
itself.  One JSON line per rate: tick latency p50/p90, the lag growth
(median latency of the window's last fifth of ticks minus its first
fifth: near 0 while the rate is sustained, growing with the run above
it), CPU per row and whether the run was correct.  The cell's traffic
file then takes 4/5 of the highest rate that sustains.
"""
import argparse
import json
import subprocess
import sys


def one_rate(workload: str, seed: int, seconds: float, rate: float) -> dict:
    import run

    spec, cell, _cfg, _traffic = run.load_cell(workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    device, peaks = run.check_device(int(cell["chips"]))
    res = run.run_cell(workload, seed, seconds, False, peaks=peaks,
                       device=device,
                       overrides={"traffic": {"rows_per_s": rate}})
    m = {k: v["value"] for k, v in res["metrics"].items()}
    return {"rows_per_s": rate, "correct": res["correct"],
            "tick_ms_p50": m.get("tick_ms_p50"),
            "tick_ms_p90": m.get("tick_ms_p90"),
            "lag_growth_ms": res["info"]["lag_growth_ms"],
            "cpu_ms_per_krow": m.get("cpu_ms_per_krow"),
            "setup_s": m.get("setup_s"),
            "traced_in_window": res["info"]["traced_in_window"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--one", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.one:
        print(json.dumps(one_rate(args.workload, args.seed, args.seconds,
                                  args.rates[0])))
        return 0
    rc = 0
    for rate in args.rates:
        p = subprocess.run(
            [sys.executable, __file__, "--one", "--workload", args.workload,
             "--seed", str(args.seed), "--seconds", str(args.seconds),
             "--rates", str(rate)], stdout=subprocess.PIPE, text=True)
        lines = p.stdout.strip().splitlines()
        print(lines[-1] if p.returncode == 0 and lines
              else json.dumps({"rows_per_s": rate, "exit": p.returncode}),
              flush=True)
        rc = rc or p.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
