"""Median tick latency: from the tick's due time to the return of its
causes, over every tick of the window."""
import statistics


def read(run: dict) -> float | None:
    lat = run["tick_latency_ms"]
    return statistics.median(lat) if lat else None
