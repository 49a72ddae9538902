"""Public jit'd wrappers over the Pallas kernels.

These are the entry points models call when ``cfg.attention_impl="pallas"``
etc.  Off-TPU the kernels run with ``interpret=True``; on a TPU the same
call sites compile the Mosaic kernels.  :func:`_interpret` picks the
default per platform when the wrapper is called, never at import.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention
from .flash_attention import flash_attention
from .moe_gmm import grouped_matmul
from .ssd_scan import ssd_intra_chunk

def _interpret(interpret: bool | None) -> bool:
    """``interpret`` if given, else interpret off-TPU."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


# ---------------------------------------------------------------------------
# attention entry points in model layout ([B, S, H, D])
# ---------------------------------------------------------------------------
def mha_flash(q, k, v, *, causal=True, block_q=128, block_k=128,
              interpret: bool | None = None):
    """q [B,S,H,D]; k/v [B,S,KV,D] → [B,S,H,D] (GQA folded into the kernel)."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    n_rep = H // KV
    q2 = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    k2 = k.transpose(0, 2, 1, 3).reshape(B * KV, k.shape[1], D)
    v2 = v.transpose(0, 2, 1, 3).reshape(B * KV, v.shape[1], D)
    out = flash_attention(
        q2, k2, v2, causal=causal, block_q=block_q, block_k=block_k,
        n_rep=n_rep, interpret=_interpret(interpret),
    )
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


def mha_decode(q, k_cache, v_cache, cache_len, *, block_k=512,
               interpret: bool | None = None):
    """q [B,1,H,D]; caches [B,S,KV,D] → [B,1,H,D]."""
    B, _, H, D = q.shape
    KV = k_cache.shape[2]
    n_rep = H // KV
    q2 = q[:, 0].transpose(0, 1, 2).reshape(B * H, D)
    k2 = k_cache.transpose(0, 2, 1, 3).reshape(B * KV, k_cache.shape[1], D)
    v2 = v_cache.transpose(0, 2, 1, 3).reshape(B * KV, v_cache.shape[1], D)
    out = decode_attention(
        q2, k2, v2, cache_len, block_k=block_k, n_rep=n_rep,
        interpret=_interpret(interpret),
    )
    return out.reshape(B, 1, H, D)


# ---------------------------------------------------------------------------
# SSD: full chunked layer (kernel intra-chunk + XLA inter-chunk recurrence)
# ---------------------------------------------------------------------------
def ssd_chunked_pallas(x, dt, A, Bm, Cm, chunk: int, h0=None,
                       interpret: bool | None = None):
    """Same contract as models.ssd.ssd_chunked, Pallas intra-chunk path.
    x [B,S,H,P]; dt [B,S,H]; A [H]; Bm/Cm [B,S,G,N]."""
    Bb, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Q = min(chunk, S)
    assert S % Q == 0
    Nc = S // Q
    interp = _interpret(interpret)

    # [B,S,H,*] → [B,H,Nc,Q,*]
    xc = x.reshape(Bb, Nc, Q, H, P).transpose(0, 3, 1, 2, 4)
    dtc = dt.reshape(Bb, Nc, Q, H).transpose(0, 3, 1, 2)
    Bc = jnp.repeat(Bm, rep, axis=2).reshape(Bb, Nc, Q, H, N).transpose(0, 3, 1, 2, 4)
    Cc = jnp.repeat(Cm, rep, axis=2).reshape(Bb, Nc, Q, H, N).transpose(0, 3, 1, 2, 4)

    y_intra, s_c, seg = ssd_intra_chunk(xc, dtc, A, Bc, Cc, interpret=interp)

    # inter-chunk recurrence in XLA (cheap): h advances chunk by chunk
    chunk_sum = seg[..., -1]                                # [B,H,Nc]
    chunk_decay = jnp.exp(chunk_sum)
    h_init = (
        jnp.zeros((Bb, H, P, N), jnp.float32) if h0 is None
        else h0.astype(jnp.float32)
    )

    def step(h, inp):
        dec, s = inp                                        # [B,H], [B,H,N,P]
        h_new = h * dec[:, :, None, None] + s.transpose(0, 1, 3, 2)
        return h_new, h

    h_final, h_before = jax.lax.scan(
        step, h_init,
        (chunk_decay.transpose(2, 0, 1), s_c.transpose(2, 0, 1, 3, 4)),
    )
    h_before = h_before.transpose(1, 2, 0, 3, 4)            # [B,H,Nc,P,N]

    in_decay = jnp.exp(seg)                                 # [B,H,Nc,Q]
    y_inter = jnp.einsum(
        "bhcqn,bhcpn->bhcqp", Cc * in_decay[..., None], h_before
    )
    y = (y_intra.astype(jnp.float32) + y_inter)             # [B,H,Nc,Q,P]
    y = y.transpose(0, 2, 3, 1, 4).reshape(Bb, S, H, P)
    return y.astype(x.dtype), h_final


# ---------------------------------------------------------------------------
# MoE: sorted+padded grouped FFN (kernel path of models.moe)
# ---------------------------------------------------------------------------
def moe_gmm_ffn(xs, group_sizes, w_gate, w_up, w_down, *, capacity_tile=128,
                interpret: bool | None = None):
    """xs [T, d] tokens sorted by expert; group_sizes [E].
    Returns [T, d] expert-FFN outputs (same order).  Pads each group to the
    capacity tile, runs three grouped matmuls, then unpads."""
    interp = _interpret(interpret)
    T, d = xs.shape
    E = w_gate.shape[0]
    cap = max(capacity_tile, ((T + E - 1) // E + capacity_tile - 1)
              // capacity_tile * capacity_tile)
    # scatter sorted tokens into [E, cap, d] (rows past group size stay zero)
    starts = jnp.concatenate([jnp.zeros((1,), group_sizes.dtype),
                              jnp.cumsum(group_sizes)[:-1]])
    token_expert = jnp.repeat(jnp.arange(E), 1)  # placeholder, computed below
    idx = jnp.arange(T)
    expert_of = jnp.searchsorted(jnp.cumsum(group_sizes), idx, side="right")
    slot = idx - starts[expert_of]
    ok = slot < cap
    xpad = jnp.zeros((E, cap, d), xs.dtype)
    xpad = xpad.at[expert_of, jnp.where(ok, slot, 0)].set(
        jnp.where(ok[:, None], xs, 0.0)
    )
    g = grouped_matmul(xpad, w_gate, interpret=interp)
    u = grouped_matmul(xpad, w_up, interpret=interp)
    h = jax.nn.silu(g) * u
    y = grouped_matmul(h, w_down, interpret=interp)         # [E, cap, d]
    out = y[expert_of, jnp.where(ok, slot, 0)]
    return jnp.where(ok[:, None], out, 0.0)
