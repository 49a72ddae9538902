"""Milliseconds in the program's ``bigroots.prep`` spans per tick of the
traced window: the sweep's preparation: changed-window selection,
straggler detection and packing of the gate batch."""
import program_spans

read = program_spans.reader(__file__, "bigroots.prep", "tick")
