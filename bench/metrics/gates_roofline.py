"""Share of the gate kernel's roofline: the least time the chip could take
for the kernel's useful bytes (the real packed rows, not the padding) at
the chip's HBM bandwidth, over the kernel's device time.  The kernel is
elementwise, so its bytes bound it, not its operations."""

KERNEL = "_gates_pallas"


def read(run: dict) -> float | None:
    secs = run["trace"]["op_seconds"].get(KERNEL)
    if not secs or not run.get("gate_bytes"):
        return None
    least = run["gate_bytes"] / run["peaks"]["hbm_bytes_s"]
    return 100.0 * least / secs
