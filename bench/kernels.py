"""Bytes the gate kernel needs, from the real packed rows.

The Eq. 5 gate kernel reads, for every packed straggler row, its
gate-space values and node sums (``F`` float32 each) and three per-row
scalars (inter and intra peer counts, the row mask), and writes ``F``
int8 gate bits; per window it reads the running sums, the λq thresholds
and the NUMERICAL guard (``F`` float32 each), and once the TIME floor.
Padding rows are not useful work and are not counted.
"""
from __future__ import annotations

F32, I8 = 4, 1


def gate_useful_bytes(rows_per_window: list[int], width: int) -> int:
    """HBM bytes one gate launch must move for windows of the given real
    (unpadded) straggler counts and ``width`` features."""
    rows = sum(rows_per_window)
    per_row = 2 * width * F32 + 3 * F32 + width * I8
    per_window = 3 * width * F32
    return rows * per_row + len(rows_per_window) * per_window + width * F32

