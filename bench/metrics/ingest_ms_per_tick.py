"""Milliseconds inside ``FleetAggregator.ingest`` per tick, from the
benchmark's spans in the traced window."""


def read(run: dict) -> float | None:
    ms = sum(e - s for s, e in run["spans"].get("bench.ingest", ())) / 1e6
    return ms / run["ticks"] if run["ticks"] else None
