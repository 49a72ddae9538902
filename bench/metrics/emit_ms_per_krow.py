"""Milliseconds in the program's ``bigroots.emit`` spans per thousand rows
in the traced window: the Eq. 6/7 finish and cause building of each
window, and the stream's emit-once, decay and forget passes."""
from pathlib import Path

import program_spans

ROOT = Path(__file__).parents[2]


def read(run: dict) -> float | None:
    return program_spans.ms_per_krow(run, ROOT, "bigroots.emit")
