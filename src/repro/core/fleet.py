"""Fleet-sweep batching: pack many sliding windows into one gate launch.

An always-on diagnosis service runs BigRoots once per step per stage
window; a *fleet sweep* runs it for every live window on the cluster (all
jobs, all stages) in the same tick — the "spatio-temporal, whole-fleet"
regime.  The Eq. 5 gate algebra is identical for every window, so instead
of W sequential numpy passes the sweep packs all windows into padded
``[n_windows, max_rows, F]`` device arrays and evaluates the gates in a
single :mod:`repro.kernels.bigroots_gates` launch
(``BigRootsAnalyzer.analyze_fleet``).

What gets packed (per window, straggler rows only — the gates are only
ever *emitted* for straggler rows, so packing the full window would do
~100× the work for identical output):

- the gate-space ``v`` rows of the stragglers,
- their per-row node aggregates (``node_vsums[code]`` and the derived
  inter/intra peer counts) gathered from the window's running sums,
- the window scalars: running ``Σv``, the λq thresholds from the window's
  P² sketch (or exact quantiles in reference mode), and the NUMERICAL
  stage-mean>0 guard,
- schema-constant column vectors: the TIME significance floor
  (−inf on non-TIME columns so the comparison is vacuous).

Rows are zero-padded to the widest window; ``rowmask`` marks real rows so
padding can never fire a gate.  :func:`eval_gates_np` is the numpy oracle
over the same packed layout — the ``backend="numpy"`` path of
``analyze_fleet`` and the ground truth the kernel equivalence suite pins
both accelerated backends against.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import numpy as np

from .features import FeatureKind, FeatureSchema
from .window import SlidingStageWindow


@dataclass
class FleetGateBatch:
    """Padded gate-kernel inputs for a fleet sweep (see module docstring)."""

    v: np.ndarray          # [W, R, F] gate-space straggler rows
    peer_vsum: np.ndarray  # [W, R, F] per-row node Σv
    inter_cnt: np.ndarray  # [W, R, 1] n - count(node)
    intra_cnt: np.ndarray  # [W, R, 1] count(node) - 1
    rowmask: np.ndarray    # [W, R, 1] 1.0 real row / 0.0 padding
    vsum: np.ndarray       # [W, 1, F] running Σv per window
    q: np.ndarray          # [W, 1, F] λq thresholds per window
    numok: np.ndarray      # [W, 1, F] NUMERICAL mean>0 guard
    floor: np.ndarray      # [1, 1, F] TIME floor (−inf elsewhere)
    counts: np.ndarray     # [W] real (unpadded) rows per window

    @property
    def shape(self) -> tuple[int, int, int]:
        return self.v.shape


def column_floor(schema: FeatureSchema, time_floor: float) -> np.ndarray:
    """Per-column TIME significance floor: ``time_floor`` on TIME columns,
    −inf elsewhere (``v > −inf`` is vacuously true for finite v)."""
    floor = np.full(len(schema), -np.inf, dtype=np.float64)
    tcols = schema.cols_of_kind(FeatureKind.TIME)
    if tcols.size:
        floor[tcols] = time_floor
    return floor


def pack_windows(
    entries: Sequence[tuple[SlidingStageWindow, np.ndarray, int, np.ndarray, np.ndarray]],
    schema: FeatureSchema,
    time_floor: float,
    scratch: FleetGateBatch | None = None,
    row_bucket: int = 256,
) -> FleetGateBatch:
    """Stack per-window straggler gate inputs into one padded batch.

    ``entries`` holds ``(window, s_rows, n, V, q)`` per window: the
    straggler row indices into the window buffers, the live count, the
    pre-gathered gate-space rows ``V = window.v[s_rows]`` and the λq
    threshold vector (sketch or exact — the caller's choice is what the
    batch becomes).

    The row dimension is rounded up to a ``row_bucket`` multiple (the
    kernel's default row block): the straggler count drifts every tick,
    and bucketing both keeps the downstream jit cache to one entry per
    bucket and stabilizes the batch shape so ``scratch`` actually hits.
    ``scratch`` (a batch from a previous pack) is reused in place when its
    shape still matches: an always-on sweep packs every tick, and
    re-faulting fresh multi-MB pages each time costs more than the gate
    evaluation.  The returned batch aliases the scratch in that case —
    callers must not hold onto a previous tick's batch across packs.
    """
    W = len(entries)
    F = len(schema)
    R = max((e[3].shape[0] for e in entries), default=0)
    if row_bucket > 1:
        R = max(row_bucket, ((R + row_bucket - 1) // row_bucket) * row_bucket)
    num = schema.cols_of_kind(FeatureKind.NUMERICAL)

    if scratch is not None and scratch.shape == (W, R, F):
        v, peer_vsum = scratch.v, scratch.peer_vsum
        inter_cnt, intra_cnt = scratch.inter_cnt, scratch.intra_cnt
        rowmask = scratch.rowmask
        vsum, qa, numok = scratch.vsum, scratch.q, scratch.numok
        numok[:] = 1.0
        counts = scratch.counts
        counts[:] = 0
    else:
        # np.empty + per-window tail zeroing: the padded tail is usually a
        # sliver of the batch, and fresh zeroed pages for multi-MB buffers
        # cost more than the gate evaluation itself.
        v = np.empty((W, R, F), dtype=np.float64)
        peer_vsum = np.empty((W, R, F), dtype=np.float64)
        inter_cnt = np.empty((W, R, 1), dtype=np.float64)
        intra_cnt = np.empty((W, R, 1), dtype=np.float64)
        rowmask = np.empty((W, R, 1), dtype=np.float64)
        vsum = np.zeros((W, 1, F), dtype=np.float64)
        qa = np.zeros((W, 1, F), dtype=np.float64)
        numok = np.ones((W, 1, F), dtype=np.float64)
        counts = np.zeros(W, dtype=np.int64)

    for i, (w, s_rows, n, V, q) in enumerate(entries):
        cnt = V.shape[0]
        counts[i] = cnt
        # Padding: zero values, benign counts of 1.0 (divisions stay
        # finite) and rowmask 0.0 so padded rows can never fire.
        v[i, cnt:] = 0.0
        peer_vsum[i, cnt:] = 0.0
        inter_cnt[i, cnt:] = 1.0
        intra_cnt[i, cnt:] = 1.0
        rowmask[i, cnt:] = 0.0
        if cnt == 0:
            continue
        codes = w.node_codes[s_rows]
        cnt_i = w.node_counts[codes]
        v[i, :cnt] = V
        peer_vsum[i, :cnt] = w.node_vsums[codes]
        inter_cnt[i, :cnt, 0] = n - cnt_i
        intra_cnt[i, :cnt, 0] = cnt_i - 1.0
        rowmask[i, :cnt, 0] = 1.0
        vsum[i, 0] = w.vsum
        qa[i, 0] = q
        if num.size:
            numok[i, 0, num] = (w.vsum[num] / n) > 0

    floor = column_floor(schema, time_floor).reshape(1, 1, F)
    return FleetGateBatch(v, peer_vsum, inter_cnt, intra_cnt, rowmask,
                          vsum, qa, numok, floor, counts)


@dataclass
class ForecastBatch:
    """Padded per-node telemetry sequences for one forecast launch.

    The forecasting hop rides the same sweep that packs
    :class:`FleetGateBatch`: per live window, per node, the last
    ``length`` gate-space rows become one sequence, *left*-padded (mask
    0.0) when a node's history is shorter — so the batched launch scores
    exactly what a per-node call over the unpadded tail would.
    """

    x: np.ndarray      # [S, L, F] gate-space rows, newest step last
    mask: np.ndarray   # [S, L] 1.0 real step / 0.0 left padding
    nodes: list        # [S] node name per sequence
    stage_ids: list    # [S] owning window's stage_id per sequence
    task_ids: list     # [S] newest task_id per sequence (the anchor row)
    count: int         # real (unpadded) sequences; rows >= count are all-pad

    @property
    def shape(self) -> tuple[int, int, int]:
        return self.x.shape


def pack_sequences(
    windows: Sequence[SlidingStageWindow],
    schema: FeatureSchema,
    length: int,
    seq_bucket: int = 256,
) -> ForecastBatch:
    """Gather per-node trailing sequences from live windows → one batch.

    Within a window, a node's live rows are taken in insertion order
    (ring order == time order for a sliding window) and the trailing
    ``length`` of them form its sequence.  The sequence dimension is
    rounded up to a ``seq_bucket`` multiple for the same reason
    :func:`pack_windows` buckets rows: one jit cache entry per bucket,
    stable shapes tick to tick.  Bucket-padding sequences are all-pad
    (mask 0.0 everywhere) and are dropped by ``count`` before emission.
    """
    F = len(schema)
    seqs: list[tuple[np.ndarray, int, str, str, str]] = []
    for w in windows:
        live = w.live_index()
        if live.size == 0:
            continue
        codes = w.node_codes[live]
        for code in np.unique(codes):
            rows = live[codes == code]
            tail = rows[-length:]
            V = w.v[tail]
            seqs.append(
                (V, V.shape[0], w.node_name(int(code)), w.stage_id,
                 w.task_id(int(tail[-1])))
            )
    S = len(seqs)
    S_pad = S
    if seq_bucket > 1:
        S_pad = max(seq_bucket, ((S + seq_bucket - 1) // seq_bucket) * seq_bucket)
    x = np.zeros((S_pad, length, F), dtype=np.float64)
    mask = np.zeros((S_pad, length), dtype=np.float64)
    nodes, stage_ids, task_ids = [], [], []
    for i, (V, n, node, stage_id, task_id) in enumerate(seqs):
        x[i, length - n :] = V
        mask[i, length - n :] = 1.0
        nodes.append(node)
        stage_ids.append(stage_id)
        task_ids.append(task_id)
    return ForecastBatch(x, mask, nodes, stage_ids, task_ids, S)


def eval_gates_np(batch: FleetGateBatch, peer_mean: float) -> np.ndarray:
    """Numpy oracle for the packed gate pipeline → ``gbits [W, R, F]``.

    Bit-for-bit the same comparisons (and operand order) as the kernel;
    used as the ``backend="numpy"`` fleet path and as the ground truth in
    the kernel equivalence tests.
    """
    with np.errstate(invalid="ignore", divide="ignore"):
        inter = (batch.vsum - batch.peer_vsum) / batch.inter_cnt
        intra = (batch.peer_vsum - batch.v) / batch.intra_cnt
        gate_inter = (batch.v > inter * peer_mean) & (batch.inter_cnt > 0.0)
        gate_intra = (batch.v > intra * peer_mean) & (batch.intra_cnt > 0.0)
        fired = (
            (batch.rowmask > 0.0)
            & (batch.v > batch.q)
            & (gate_inter | gate_intra)
            & (batch.numok > 0.0)
            & (batch.v > batch.floor)
        )
    gbits = gate_inter.astype(np.int8) + 2 * gate_intra.astype(np.int8)
    return np.where(fired, gbits, np.int8(0))


def knife_edge(batch: FleetGateBatch, peer_mean: float) -> np.ndarray:
    """Elements whose gate outcome float32 evaluation may flip → bool
    ``[W, R, F]``.

    The compiled gate kernel runs in float32 (Mosaic has no f64), while
    :func:`eval_gates_np` is the float64 reference.  An element is on the
    knife edge when its value ``v`` lies within float32 rounding of a
    threshold it is compared against:

    - λq and the TIME floor: ``|v - t| <= tol · (|v| + |t|)``;
    - ``λp × inter`` with ``inter = (Σv - node Σv) / inter_cnt``: the f32
      subtraction can be off by a rounding of each operand, so the scale
      is ``|v| + λp · (|Σv| + |node Σv|) / inter_cnt``;
    - ``λp × intra`` with ``intra = (node Σv - v) / intra_cnt``: likewise
      with ``(|node Σv| + |v|) / intra_cnt``.

    ``tol`` is four float32 epsilons — a few roundings of each operand
    with room for the TPU's division — plus the smallest normal f32 as an
    absolute term (TPUs flush denormals to zero).  Infinite thresholds
    (the −inf floor of non-TIME columns, empty peer groups) are never
    knife edges.  Every other element must agree bit for bit.
    """
    f32 = np.finfo(np.float32)
    tol = 4.0 * float(f32.eps)
    v = batch.v
    av = np.abs(v)

    def near(t: np.ndarray, scale: np.ndarray) -> np.ndarray:
        with np.errstate(invalid="ignore"):
            return np.isfinite(t) & (
                np.abs(v - t) <= tol * scale + float(f32.tiny)
            )

    with np.errstate(invalid="ignore", divide="ignore"):
        inter_cnt = np.where(batch.inter_cnt > 0, batch.inter_cnt, np.nan)
        intra_cnt = np.where(batch.intra_cnt > 0, batch.intra_cnt, np.nan)
        inter = peer_mean * (batch.vsum - batch.peer_vsum) / inter_cnt
        intra = peer_mean * (batch.peer_vsum - v) / intra_cnt
        inter_scale = av + peer_mean * (
            np.abs(batch.vsum) + np.abs(batch.peer_vsum)) / inter_cnt
        intra_scale = av + peer_mean * (np.abs(batch.peer_vsum) + av) / intra_cnt
    return (
        near(np.broadcast_to(batch.q, v.shape), av + np.abs(batch.q))
        | near(inter, inter_scale)
        | near(intra, intra_scale)
        | near(np.broadcast_to(batch.floor, v.shape), av + np.abs(batch.floor))
    )
