"""Milliseconds inside ``Diagnosis.tick`` (the fleet sweep: prelude, pack,
gate kernel, Eq. 6/7 finish, what-if) per thousand rows ingested, from the
benchmark's span around each tick call in the traced window."""


def read(run: dict) -> float | None:
    ms = sum(e - s for s, e in run["spans"].get("bench.sweep", ())) / 1e6
    return ms / (run["rows"] / 1e3) if run["rows"] else None
