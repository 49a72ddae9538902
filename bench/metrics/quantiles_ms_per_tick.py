"""Self time of the program's ``bigroots.quantiles`` spans per tick of the
traced window: the λq thresholds of each swept window
(``SlidingStageWindow.quantiles``), less any sketch re-anchor inside."""
import program_spans

read = program_spans.reader(__file__, "bigroots.quantiles", "tick",
                            ("bigroots.sketch",))
