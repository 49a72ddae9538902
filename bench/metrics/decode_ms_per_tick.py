"""Milliseconds in the program's ``bigroots.decode`` spans per tick of
the traced window: ``StepDelta.from_bytes`` on each host payload
(wire decode)."""
import program_spans

read = program_spans.reader(__file__, "bigroots.decode", "tick")
