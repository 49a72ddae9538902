"""Milliseconds in the program's ``bigroots.gates`` spans per thousand
rows in the traced window: the gate evaluation as the host sees it
(padding, host-to-device copies, the launch and the wait for its
result)."""
from pathlib import Path

import program_spans

ROOT = Path(__file__).parents[2]


def read(run: dict) -> float | None:
    return program_spans.ms_per_krow(run, ROOT, "bigroots.gates")
