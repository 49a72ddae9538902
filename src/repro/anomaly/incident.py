"""Seeded fleet-incident telemetry for the gate-sweep workloads.

The fleet benchmarks (``scale/fleet_gates_*``, ``scale/fleet_merge_*``)
and ``chip_smoke.py`` diagnose stage windows made by
:func:`incident_columns`: a contended-rack step window in which the Mantri
λs threshold flags about a fifth of the rows as stragglers while only a
small injected hot set carries a real feature signal.
"""
from __future__ import annotations

import numpy as np


def incident_columns(n_hosts: int, seed: int = 0) -> dict:
    """A fleet-incident step window of ``n_hosts`` rows, from ``seed``.

    The Mantri λs threshold flags ~20% of rows as stragglers (contended
    rack / hot shard storm) while only a small attributable subset
    carries a real feature signal.  This is the gate-dominated regime the
    fleet sweep batches: Eq. 5 algebra runs over every straggler row, but
    emission stays small."""
    rng = np.random.default_rng(seed)
    dur = rng.lognormal(mean=0.0, sigma=0.18, size=n_hosts) * 10.0
    slow = rng.choice(n_hosts, size=n_hosts // 5, replace=False)
    dur[slow] *= 1.9
    cpu = rng.uniform(0.1, 0.3, n_hosts)
    cpu[slow[: n_hosts // 500]] = 0.95  # the attributable hot set (~0.2%)
    return {
        "task_ids": [f"h{i}/s0" for i in range(n_hosts)],
        "nodes": [f"h{i % 512}" for i in range(n_hosts)],
        "starts": np.zeros(n_hosts),
        "ends": dur,
        # Tight feature spreads: the 1.5× peer-mean gate rejects organic
        # variation, so only the injected hot set emits causes.
        "features": {
            "cpu": cpu,
            "disk": rng.uniform(0.15, 0.2, n_hosts),
            "network": rng.uniform(5e5, 6e5, n_hosts),
            "read_bytes": rng.uniform(0.95, 1.05, n_hosts) * 64e6,
            "gc_time": rng.uniform(0, 0.05, n_hosts),
            "data_load_time": rng.uniform(0, 0.4, n_hosts),
            "h2d_time": rng.uniform(0, 0.1, n_hosts),
        },
    }
