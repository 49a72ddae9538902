"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, in percent."""


def read(run: dict) -> float | None:
    t = run["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
