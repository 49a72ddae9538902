"""Pallas TPU kernel: batched BigRoots Eq. 5 gate pipeline for fleet sweeps.

The §III-B gate algebra — the λq quantile gate, the inter-/intra-node
peer-mean gates (the paper's observations 1 & 2), the TIME significance
floor ``F > 0.2`` and the NUMERICAL stage-mean ≤ 0 guard — is a pure
elementwise pipeline over ``[rows, F]`` gate-space matrices.  One
always-on diagnosis step evaluates it per stage window; a *fleet sweep*
evaluates it for every stage window of every job on the cluster.  This
kernel batches that sweep: ``repro.core.fleet.pack_windows`` stacks the
straggler rows of many :class:`~repro.core.window.SlidingStageWindow`\\ s
(their gate-space ``v`` rows, gathered per-row node aggregates, running
``Σv`` and sketch quantiles) into padded ``[n_windows, max_rows, F]``
device arrays, and a single launch returns the fired-gate bits for the
whole fleet.

Inputs (all per packed batch; see :class:`repro.core.fleet.FleetGateBatch`):

==============  ===================  =========================================
``v``           ``[W, R, F]``        gate-space values of the packed rows
``peer_vsum``   ``[W, R, F]``        per-row node Σv (``node_vsums[code]``)
``inter_cnt``   ``[W, R, 1]``        ``n - count(node)`` per row
``intra_cnt``   ``[W, R, 1]``        ``count(node) - 1`` per row
``rowmask``     ``[W, R, 1]``        1.0 for real rows, 0.0 for padding
``vsum``        ``[W, 1, F]``        window running Σv
``q``           ``[W, 1, F]``        per-column λq thresholds (sketch/exact)
``numok``       ``[W, 1, F]``        NUMERICAL mean>0 guard (1.0 = pass)
``floor``       ``[1, 1, F]``        TIME floor per column (−inf elsewhere)
==============  ===================  =========================================

Output: ``gbits [W, R, F]`` int8 — 0 where no gate fired; else bit 0 set
when the inter-node observation fired and bit 1 for intra-node, matching
the analyzer's peer-group emission table.

Precision: gate math runs in the operand dtype.  Interpret mode (the
CPU path) and the jnp twin run under ``jax.enable_x64`` in float64 (on a
TPU, XLA emulates the twin's f64), so on the CPU their gbits are
bit-identical to the numpy oracle.  The compiled kernel runs in float32,
because Mosaic has no f64: :func:`eval_gates` casts the batch for it.  A
row whose value lies within float32 rounding of a threshold it is
compared against (λq, ``λp × inter``, ``λp × intra`` or the TIME floor)
may then fire differently from the f64 oracle;
:func:`repro.core.fleet.knife_edge` marks exactly those elements, and
the tests and ``chip_smoke.py`` hold every other element to bit
identity.  Division by an empty peer group's zero count produces
NaN/±inf that the explicit ``cnt > 0`` masks neutralize, mirroring the
numpy path's ``isnan`` guards.

The gate bits are summed in int32 and cast to int8 only at the store:
Mosaic has no i8 vector arithmetic.  Off-TPU the kernel runs only in
interpret mode, and only when the caller asks for it (``interpret=True``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import numpy as np


def _gates_kernel(v_ref, pv_ref, icnt_ref, acnt_ref, m_ref, vs_ref, q_ref,
                  nok_ref, fl_ref, o_ref, *, peer_mean: float):
    v = v_ref[0]            # [Br, F]
    pv = pv_ref[0]          # [Br, F]
    icnt = icnt_ref[0]      # [Br, 1]
    acnt = acnt_ref[0]      # [Br, 1]
    mask = m_ref[0]         # [Br, 1]
    vsum = vs_ref[0]        # [1, F]
    q = q_ref[0]            # [1, F]
    numok = nok_ref[0]      # [1, F]
    floor = fl_ref[0]       # [1, F]

    # Peer means from the running aggregates (identical operand order to the
    # numpy path so float comparisons round the same way).
    inter = (vsum - pv) / icnt
    intra = (pv - v) / acnt
    gate_inter = (v > inter * peer_mean) & (icnt > 0.0)
    gate_intra = (v > intra * peer_mean) & (acnt > 0.0)
    fired = (
        (mask > 0.0)
        & (v > q)                       # λq quantile gate
        & (gate_inter | gate_intra)     # Eq. 5 peer-mean observations
        & (numok > 0.0)                 # NUMERICAL stage-mean ≤ 0 guard
        & (v > floor)                   # TIME significance floor
    )
    gbits = gate_inter.astype(jnp.int32) + 2 * gate_intra.astype(jnp.int32)
    o_ref[0] = jnp.where(fired, gbits, 0).astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("peer_mean", "block_r", "interpret")
)
def _gates_pallas(v, peer_vsum, inter_cnt, intra_cnt, rowmask, vsum, q,
                  numok, floor, *, peer_mean: float, block_r: int,
                  interpret: bool):
    W, R, F = v.shape
    n_rt = R // block_r
    kernel = functools.partial(_gates_kernel, peer_mean=peer_mean)
    row_spec = pl.BlockSpec((1, block_r, F), lambda w, r: (w, r, 0))
    cnt_spec = pl.BlockSpec((1, block_r, 1), lambda w, r: (w, r, 0))
    col_spec = pl.BlockSpec((1, 1, F), lambda w, r: (w, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(W, n_rt),
        in_specs=[
            row_spec,                                       # v
            row_spec,                                       # peer_vsum
            cnt_spec,                                       # inter_cnt
            cnt_spec,                                       # intra_cnt
            cnt_spec,                                       # rowmask
            col_spec,                                       # vsum
            col_spec,                                       # q
            col_spec,                                       # numok
            pl.BlockSpec((1, 1, F), lambda w, r: (0, 0, 0)),  # floor
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((W, R, F), jnp.int8),
        interpret=interpret,
    )(v, peer_vsum, inter_cnt, intra_cnt, rowmask, vsum, q, numok, floor)


@functools.partial(jax.jit, static_argnames=("peer_mean",))
def _gates_jnp(v, peer_vsum, inter_cnt, intra_cnt, rowmask, vsum, q, numok,
               floor, *, peer_mean: float):
    """Pure-jnp reference of the kernel (the XLA-fused fallback backend)."""
    inter = (vsum - peer_vsum) / inter_cnt
    intra = (peer_vsum - v) / intra_cnt
    gate_inter = (v > inter * peer_mean) & (inter_cnt > 0.0)
    gate_intra = (v > intra * peer_mean) & (intra_cnt > 0.0)
    fired = (
        (rowmask > 0.0) & (v > q) & (gate_inter | gate_intra)
        & (numok > 0.0) & (v > floor)
    )
    gbits = gate_inter.astype(jnp.int8) + 2 * gate_intra.astype(jnp.int8)
    return jnp.where(fired, gbits, jnp.int8(0))


def eval_gates(
    v: np.ndarray,
    peer_vsum: np.ndarray,
    inter_cnt: np.ndarray,
    intra_cnt: np.ndarray,
    rowmask: np.ndarray,
    vsum: np.ndarray,
    q: np.ndarray,
    numok: np.ndarray,
    floor: np.ndarray,
    *,
    peer_mean: float,
    backend: str = "pallas",
    block_r: int = 256,
    interpret: bool = False,
) -> np.ndarray:
    """Evaluate the Eq. 5 gate pipeline for a packed fleet batch.

    ``backend="pallas"`` launches the kernel: compiled by Mosaic in
    float32 on a TPU, or in the Pallas interpreter in float64 when
    ``interpret=True``.  Off-TPU without ``interpret=True`` it raises
    rather than run anything in the chip's place.  ``backend="jax"`` runs
    the jit'd pure-jnp twin in float64 on any backend.  For BOTH backends
    rows are zero-padded to a ``block_r`` multiple: the kernel grid needs
    it, and the jnp path needs the shape *bucketing* — an always-on loop
    sees a drifting straggler count every step, and without padding each
    distinct count would retrace and recompile the jit cache (tens of ms)
    instead of hitting one entry per bucket.  ``block_r`` is rounded up
    to a multiple of 32, the sublane tile of the int8 output.  Padding is
    masked by construction (``rowmask`` padding is 0).  Returns ``gbits``
    as a numpy int8 array of the unpadded shape.
    """
    if backend not in ("jax", "pallas"):
        raise ValueError(f"unknown gate backend: {backend!r}")
    compiled = backend == "pallas" and not interpret
    if compiled and jax.default_backend() != "tpu":
        raise RuntimeError(
            "the Pallas gate kernel compiles for TPU only, and JAX's "
            f"backend here is {jax.default_backend()!r}; pass interpret=True "
            "to run it in the Pallas interpreter"
        )
    W, R, F = v.shape
    block_r = _round_up(max(1, min(int(block_r), R)), 32)
    R_pad = _round_up(R, block_r)
    if R_pad != R:
        pad = ((0, 0), (0, R_pad - R), (0, 0))
        v = np.pad(v, pad)
        peer_vsum = np.pad(peer_vsum, pad)
        # Padded counts are 1 (not 0) so the kernel's divisions stay
        # finite noise-free; rowmask padding stays 0 and masks them.
        inter_cnt = np.pad(inter_cnt, pad, constant_values=1.0)
        intra_cnt = np.pad(intra_cnt, pad, constant_values=1.0)
        rowmask = np.pad(rowmask, pad)
    dtype = np.float32 if compiled else np.float64
    with jax.enable_x64(not compiled):
        args = [jnp.asarray(a, dtype=dtype) for a in (
            v, peer_vsum, inter_cnt, intra_cnt, rowmask, vsum, q, numok,
            floor)]
        if backend == "jax":
            out = _gates_jnp(*args, peer_mean=float(peer_mean))
        else:
            out = _gates_pallas(
                *args, peer_mean=float(peer_mean), block_r=block_r,
                interpret=bool(interpret),
            )
        return np.asarray(out)[:, :R]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
