"""90th percentile of the tick latency over every tick of the window
(``statistics.quantiles``, exclusive method)."""
import statistics


def read(run: dict) -> float | None:
    lat = run["tick_latency_ms"]
    return statistics.quantiles(lat, n=10)[-1] if len(lat) >= 10 else None
