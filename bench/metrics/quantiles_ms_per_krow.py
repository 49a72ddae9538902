"""Self time of the program's ``bigroots.quantiles`` spans per thousand
rows in the traced window: the λq thresholds of each swept window
(``SlidingStageWindow.quantiles``), less any sketch re-anchor inside."""
from pathlib import Path

import program_spans

ROOT = Path(__file__).parents[2]


def read(run: dict) -> float | None:
    return program_spans.ms_per_krow(run, ROOT, "bigroots.quantiles",
                                     ("bigroots.sketch",))
