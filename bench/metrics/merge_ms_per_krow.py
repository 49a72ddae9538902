"""Self time of the program's ``bigroots.merge`` spans per thousand rows
in the traced window: ``StepDelta.apply_to`` (the window merge), less the
P² sketch work inside it."""
from pathlib import Path

import program_spans

ROOT = Path(__file__).parents[2]


def read(run: dict) -> float | None:
    return program_spans.ms_per_krow(run, ROOT, "bigroots.merge",
                                     ("bigroots.sketch",))
