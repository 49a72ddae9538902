"""Milliseconds in the program's ``bigroots.prep`` spans per thousand rows
in the traced window: the sweep's preparation: changed-window selection,
straggler detection and packing of the gate batch."""
from pathlib import Path

import program_spans

ROOT = Path(__file__).parents[2]


def read(run: dict) -> float | None:
    return program_spans.ms_per_krow(run, ROOT, "bigroots.prep")
