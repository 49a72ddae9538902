"""Milliseconds inside ``FleetAggregator.ingest`` (wire decode, window
merge, λq sketch) per thousand rows, from the benchmark's spans around each
call in the traced window."""


def read(run: dict) -> float | None:
    ms = sum(e - s for s, e in run["spans"].get("bench.ingest", ())) / 1e6
    return ms / (run["rows"] / 1e3) if run["rows"] else None
