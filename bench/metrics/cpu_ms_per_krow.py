"""Host CPU seconds of the service's process over the window
(``time.process_time``, every thread) per thousand rows ingested."""


def read(run: dict) -> float | None:
    return run["cpu_s"] * 1e3 / (run["rows"] / 1e3) if run["rows"] else None
