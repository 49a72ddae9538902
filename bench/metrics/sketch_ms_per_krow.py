"""Milliseconds in the program's ``bigroots.sketch`` spans per thousand
rows in the traced window: the P² λq sketch: per-row adds of small blocks
and exact re-anchors, wherever they run."""
from pathlib import Path

import program_spans

ROOT = Path(__file__).parents[2]


def read(run: dict) -> float | None:
    return program_spans.ms_per_krow(run, ROOT, "bigroots.sketch")
