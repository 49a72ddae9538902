"""Self time of the program's ``bigroots.merge`` spans per tick of the
traced window: ``StepDelta.apply_to`` (the window merge), less the
P² sketch work inside it."""
import program_spans

read = program_spans.reader(__file__, "bigroots.merge", "tick",
                            ("bigroots.sketch",))
