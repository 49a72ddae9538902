"""``gates_roofline`` in the open-loop cells, where it moves the tick
latency: the same reader."""
import run

read = run.load_reader("gates_roofline")
