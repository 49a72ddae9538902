"""Milliseconds in the program's ``bigroots.prep`` spans per thousand rows
in the traced window: the sweep's preparation: changed-window selection,
straggler detection and packing of the gate batch."""
import program_spans

read = program_spans.reader(__file__, "bigroots.prep", "krow")
