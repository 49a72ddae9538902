"""Milliseconds in the program's ``bigroots.whatif`` spans per thousand
rows in the traced window: what-if attribution of the tick's fresh causes
(``RootCauseStream._attribute``)."""
from pathlib import Path

import program_spans

ROOT = Path(__file__).parents[2]


def read(run: dict) -> float | None:
    return program_spans.ms_per_krow(run, ROOT, "bigroots.whatif")
