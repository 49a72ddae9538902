"""Rows of every tick completed in the window over the window's seconds."""


def read(run: dict) -> float | None:
    return run["rows"] / run["window_s"] if run["window_s"] > 0 else None
