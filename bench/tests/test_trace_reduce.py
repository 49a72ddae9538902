"""The trace reduction on a small trace recorded on a TPU v5e: three
``bench.tick`` spans, each around a ``bench.ingest`` span and one gate
kernel launch on a [2, 512, 14] batch."""
from pathlib import Path

import kernels
import trace_reduce

TRACE = Path(__file__).parent / "data" / "gates_small.xplane.pb"


def test_reduce_recorded_trace():
    t = trace_reduce.reduce(TRACE)
    assert t["devices"] == 1
    assert len(t["spans"]["bench.tick"]) == 3
    assert len(t["spans"]["bench.ingest"]) == 3
    kernel = t["op_seconds"]["_gates_pallas"]
    assert 0 < kernel < t["busy_s"] <= t["window_s"]
    ops = dict(t["breakdown"]["device_ops"])
    assert ops["_gates_pallas"] == kernel
    assert len(t["breakdown"]["device_ops"]) <= 10
    gaps = t["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert all(s > 0 for _, s in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    # The device idles through the host's 2 ms ingest spans.
    assert "bench.ingest" in {name for name, _ in gaps}


def test_op_name():
    assert trace_reduce.op_name(
        "%_gates_pallas.1 = s8[1,8704,14]{2,1,0} custom-call(...)") == "_gates_pallas"
    assert trace_reduce.op_name("%copy-start.12 = (f32[2]) copy-start(x)") == "copy-start"
    assert trace_reduce.op_name("fusion") == "fusion"


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_gate_useful_bytes_hand_count():
    # Two windows of 3 and 5 real rows, 2 features: per row two float32
    # rows of 2 (16 B), three float32 scalars (12 B) and 2 int8 bits
    # (2 B) = 30 B; per window three float32 vectors of 2 = 24 B; one
    # float32 floor vector = 8 B.
    assert kernels.gate_useful_bytes([3, 5], 2) == 8 * 30 + 2 * 24 + 8
    assert kernels.gate_useful_bytes([], 14) == 14 * 4
