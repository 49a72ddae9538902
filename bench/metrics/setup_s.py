"""Seconds from process start to the first measured tick: device start-up,
the compile cache, the generator's start, the pre-filled windows and the
warm ticks."""


def read(run: dict) -> float | None:
    return run["setup_s"]
