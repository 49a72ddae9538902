"""Milliseconds in the program's ``bigroots.emit`` spans per thousand rows
in the traced window: the Eq. 6/7 finish and cause building of each
window, and the stream's emit-once, decay and forget passes."""
import program_spans

read = program_spans.reader(__file__, "bigroots.emit", "krow")
