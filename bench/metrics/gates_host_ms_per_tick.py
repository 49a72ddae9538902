"""Milliseconds in the program's ``bigroots.gates`` spans per tick of the
traced window: the gate evaluation as the host sees it (padding,
host-to-device copies, the launch and the wait for its result)."""
import program_spans

read = program_spans.reader(__file__, "bigroots.gates", "tick")
