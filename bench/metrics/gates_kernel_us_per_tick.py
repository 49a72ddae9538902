"""Device microseconds of the gate kernel per tick: the summed durations
of its events on the device, found by the kernel's name, in the traced
window."""

#: The kernel's name as the device trace shows it.
KERNEL = "_gates_pallas"


def read(run: dict) -> float | None:
    t = run["trace"]
    secs = t["op_seconds"].get(KERNEL)
    return secs * 1e6 / run["ticks"] if secs and run["ticks"] else None
