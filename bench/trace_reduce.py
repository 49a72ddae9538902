"""The one reduction from a profiler trace (``.xplane.pb``) to device busy
time, per-op device time, the benchmark's host spans and the breakdown.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation that ran.  The host plane ``/host:CPU`` holds the
benchmark's own spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``), on the same clock.  The window is the
``bench.window`` span, or else from the first to the last event.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"\.\d+$")


def find_trace(log_dir: str | Path) -> str:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """Short name of an XLA op event: ``%_gates_pallas.1 = s8[...] ...``
    gives ``_gates_pallas``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(path: str | Path, top: int = 10) -> dict:
    """Busy and window seconds, per-op device seconds, host spans and the
    breakdown of one trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans: dict[str, list[tuple[int, int]]] = {}
    devices: list[list[tuple[int, int, str]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                             op_name(e.name)) for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.setdefault(e.name, []).append(
                            (int(e.start_ns), int(e.start_ns + e.duration_ns)))
    win = spans.get("bench.window")
    if win:
        w0, w1 = win[0]
    else:
        every = [(s, e) for ops in devices for s, e, _ in ops] + [
            x for v in spans.values() for x in v]
        w0 = min((s for s, _ in every), default=0)
        w1 = max((e for _, e in every), default=0)
    op_seconds: dict[str, float] = {}
    busy = 0.0
    gaps: list[tuple[int, int]] = []
    for ops in devices:
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                  if e > w0 and s < w1]
        for s, e, n in inside:
            op_seconds[n] = op_seconds.get(n, 0.0) + (e - s) / 1e9
        merged = _union([(s, e) for s, e, _ in inside])
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(len(devices), 1)
    host = [(s, e, name) for name, v in spans.items() if name != "bench.window"
            for s, e in v]

    def doing(s: int, e: int) -> str:
        """The innermost benchmark span open at the middle of a gap."""
        mid = (s + e) // 2
        open_ = [(hs, name) for hs, he, name in host if hs <= mid < he]
        return max(open_)[1] if open_ else "no span"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy / n_dev,
        "window_s": (w1 - w0) / 1e9,
        "devices": len(devices),
        "op_seconds": op_seconds,
        "spans": {k: v for k, v in spans.items() if k != "bench.window"},
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(
                op_seconds.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[doing(s, e), (e - s) / 1e9] for s, e in gaps[:top]],
        },
    }
