"""Milliseconds in the program's ``bigroots.whatif`` spans per thousand
rows in the traced window: what-if attribution of the tick's fresh causes
(``RootCauseStream._attribute``)."""
import program_spans

read = program_spans.reader(__file__, "bigroots.whatif", "krow")
