"""Milliseconds in the program's ``bigroots.decode`` spans per thousand
rows in the traced window: ``StepDelta.from_bytes`` on each host payload
(wire decode)."""
from pathlib import Path

import program_spans

ROOT = Path(__file__).parents[2]


def read(run: dict) -> float | None:
    return program_spans.ms_per_krow(run, ROOT, "bigroots.decode")
