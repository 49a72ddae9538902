"""Decoder-only LM assembly (dense / GQA / MoE / SSM / hybrid / VLM backbone).

Layers execute as a ``lax.scan`` over *pattern blocks* (config.pattern()
repeated n_blocks times) with per-slot stacked parameters — the lowered HLO
contains one block body regardless of depth, which keeps the 80-cell
dry-run compilable and mirrors production JAX LMs (MaxText-style).

Entry points:
  init_params(key, cfg)                  → params pytree
  forward(params, cfg, tokens, ...)      → (logits, MoeAux)      (train/eval)
  loss_fn(params, cfg, batch)            → (loss, metrics)
  init_cache(cfg, batch, max_len)        → decode cache pytree
  prefill(params, cfg, tokens, cache, ...)→ (logits, cache)
  decode_step(params, cfg, tokens, cache)→ (logits, cache)
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import (
    attention_apply,
    attention_decode,
    attention_init,
    mlp_apply,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
    rope,
    _project_qkv,
)
from .moe import MoeAux, moe_apply, moe_init
from .ssd import SsmState, ssm_apply, ssm_decode, ssm_init

Params = dict[str, Any]

ZERO_AUX = MoeAux(jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0))


def _slot_keys(cfg: ModelConfig) -> list[tuple[str, str, str]]:
    """[(key, kind, role)] per pattern slot: mixer then ffn."""
    out = []
    for i, slot in enumerate(cfg.pattern()):
        out.append((f"L{i}_{slot.mixer}", slot.mixer, "mixer"))
        if slot.ffn:
            out.append((f"L{i}_{slot.ffn}", slot.ffn, "ffn"))
    return out


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 4)
    pdtype = jnp.dtype(cfg.param_dtype)
    params: Params = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_padded, cfg.d_model))
                  * 0.02).astype(pdtype),
        "final_norm": rmsnorm_init(cfg.d_model, pdtype),
        "blocks": {},
    }
    if not cfg.tie_embeddings:
        params["head"] = (
            jax.random.normal(keys[1], (cfg.d_model, cfg.vocab_padded)) * 0.02
        ).astype(pdtype)

    init_by_kind = {
        "attn": lambda k: attention_init(k, cfg),
        "ssm": lambda k: ssm_init(k, cfg),
        "mlp": lambda k: mlp_init(k, cfg),
        "moe": lambda k: moe_init(k, cfg),
    }
    slot_key_root = keys[2]
    for si, (skey, kind, _role) in enumerate(_slot_keys(cfg)):
        block_keys = jax.random.split(
            jax.random.fold_in(slot_key_root, si), cfg.n_blocks
        )
        params["blocks"][skey] = jax.vmap(init_by_kind[kind])(block_keys)
    return params


# ---------------------------------------------------------------------------
# full-sequence forward (training / evaluation)
# ---------------------------------------------------------------------------
def _block_body(cfg: ModelConfig):
    slots = _slot_keys(cfg)

    # §Perf hc1 iteration 2: without the barrier, GSPMD hoists the next
    # norm's f32 convert above the tensor-parallel partial-sum all-reduce,
    # doubling every TP collective's payload (f32 instead of bf16).  The
    # barrier pins the residual stream dtype at the collective boundary.
    def _pin(x):
        return jax.lax.optimization_barrier(x)

    def body(carry, block_params):
        x, aux, positions = carry
        for skey, kind, _role in slots:
            p = block_params[skey]
            h = rmsnorm(x, p["norm_scale"], cfg.norm_eps)
            if kind == "attn":
                x = _pin(x + attention_apply(p, h, cfg, positions=positions))
            elif kind == "ssm":
                x = _pin(x + ssm_apply(p, h, cfg))
            elif kind == "mlp":
                x = _pin(x + mlp_apply(p, h))
            elif kind == "moe":
                y, a = moe_apply(p, h, cfg)
                x = _pin(x + y)
                aux = MoeAux(
                    aux.load_balance_loss + a.load_balance_loss,
                    aux.router_z_loss + a.router_z_loss,
                    aux.expert_load + a.expert_load,
                )
        return (x, aux, positions), None

    return body


def head_logits(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Final projection; padded vocab columns are masked to -1e30."""
    head = (
        params["embed"].T if cfg.tie_embeddings else params["head"]
    ).astype(x.dtype)
    logits = x @ head
    if cfg.vocab_padded != cfg.vocab:
        col = jnp.arange(cfg.vocab_padded)
        logits = jnp.where(
            col >= cfg.vocab, jnp.asarray(-1e30, logits.dtype), logits
        )
    return logits


def embed_inputs(
    params: Params, cfg: ModelConfig, tokens: jax.Array,
    embeds: jax.Array | None = None,
) -> jax.Array:
    """Token embedding; modality frontends prepend precomputed embeddings."""
    cdt = jnp.dtype(cfg.dtype)
    # The rows follow the tokens' sharding: with explicit mesh axes a
    # vocab-sharded table and batch-sharded tokens leave the gather's
    # output sharding ambiguous, so it is named here.
    tok = jax.typeof(tokens).sharding
    x = params["embed"].astype(cdt).at[tokens].get(
        out_sharding=tok.update(spec=P(*tok.spec, None)))
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(cdt), x], axis=1)
    return x


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,                 # [B, S_text]
    embeds: jax.Array | None = None,   # [B, P, d] modality prefix (VLM/audio)
) -> tuple[jax.Array, MoeAux]:
    x = embed_inputs(params, cfg, tokens, embeds)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    aux0 = MoeAux(jnp.float32(0.0), jnp.float32(0.0),
                  jnp.zeros((max(cfg.moe_experts, 1),), jnp.float32))
    body = _block_body(cfg)
    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if cfg.scan_blocks:
        (x, aux, _), _ = jax.lax.scan(body, (x, aux0, positions), params["blocks"])
    else:  # unrolled (dry-run cost extraction)
        carry = (x, aux0, positions)
        for i in range(cfg.n_blocks):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            carry, _ = body(carry, bp)
        x, aux, _ = carry
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = head_logits(params, cfg, x)
    n_moe = sum(1 for s in cfg.pattern() if s.ffn == "moe") * cfg.n_blocks
    if n_moe:
        aux = MoeAux(aux.load_balance_loss / n_moe, aux.router_z_loss / n_moe,
                     aux.expert_load / n_moe)
    return logits, aux


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Sharding-friendly CE: per-position nll without take_along_axis.

    ``take_along_axis`` on vocab-sharded logits makes GSPMD materialize /
    all-reduce activation-sized f32 gathers (§Perf hc1 iteration 1 — ~1 GB
    per op on glm4).  The iota-select form keeps every term a fused
    elementwise+reduce over the local vocab shard; the only cross-shard
    traffic is the [B, S] partial-reduction combine.
    """
    logits32 = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits32, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits32 - m), axis=-1)) + m[..., 0]
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    label_logit = jnp.sum(
        jnp.where(col == labels[..., None], logits32, 0.0), axis=-1
    )
    return lse - label_logit


def loss_fn(
    params: Params, cfg: ModelConfig, batch: dict,
    lb_coef: float = 0.01, z_coef: float = 1e-3,
) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy; labels < 0 are ignored (modality prefixes)."""
    logits, aux = forward(
        params, cfg, batch["tokens"], embeds=batch.get("embeds")
    )
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:   # modality prefix positions
        pad = jnp.full(
            (labels.shape[0], logits.shape[1] - labels.shape[1]), -1, labels.dtype
        )
        labels = jnp.concatenate([pad, labels], axis=1)
    valid = labels >= 0
    safe_labels = jnp.maximum(labels, 0)
    nll = cross_entropy(logits, safe_labels)
    denom = jnp.maximum(valid.sum(), 1)
    ce = jnp.where(valid, nll, 0.0).sum() / denom
    loss = ce + lb_coef * aux.load_balance_loss + z_coef * aux.router_z_loss
    metrics = {
        "loss": loss,
        "ce": ce,
        "lb_loss": aux.load_balance_loss,
        "z_loss": aux.router_z_loss,
        "expert_load_max": (
            aux.expert_load.max() if cfg.moe_experts else jnp.float32(0.0)
        ),
    }
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch_size: int, max_len: int) -> dict:
    cdt = jnp.dtype(cfg.dtype)
    cache: dict = {"len": jnp.zeros((), jnp.int32), "slots": {}}
    nb = cfg.n_blocks
    for skey, kind, role in _slot_keys(cfg):
        if kind == "attn":
            shape = (nb, batch_size, max_len, cfg.n_kv_heads, cfg.head_dim)
            cache["slots"][skey] = {
                "k": jnp.zeros(shape, cdt), "v": jnp.zeros(shape, cdt)
            }
        elif kind == "ssm":
            gn2 = 2 * cfg.ssm_groups * cfg.ssm_state
            cache["slots"][skey] = {
                "conv_x": jnp.zeros(
                    (nb, batch_size, cfg.ssm_conv - 1, cfg.d_inner), cdt
                ),
                "conv_bc": jnp.zeros(
                    (nb, batch_size, cfg.ssm_conv - 1, gn2), cdt
                ),
                "ssm": jnp.zeros(
                    (nb, batch_size, cfg.ssm_heads, cfg.ssm_head_dim,
                     cfg.ssm_state), jnp.float32,
                ),
            }
    return cache


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    cache: dict,
    embeds: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """Run the prompt through the model, filling the cache. Returns logits of
    the last position and the updated cache."""
    x = embed_inputs(params, cfg, tokens, embeds)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    slots = _slot_keys(cfg)
    max_len = next(
        (v["k"].shape[2] for v in cache["slots"].values() if "k" in v), S
    )

    def body(carry, block_params):
        x, positions = carry
        new_slots = {}
        for skey, kind, _role in slots:
            p = block_params[skey]
            h = rmsnorm(x, p["norm_scale"], cfg.norm_eps)
            if kind == "attn":
                q, k, v = _project_qkv(p, h, h, cfg)
                q = rope(q, positions, cfg.rope_theta)
                k = rope(k, positions, cfg.rope_theta)
                from .layers import _repeat_kv, blocked_attention, dense_attention

                n_rep = cfg.n_heads // cfg.n_kv_heads
                kk, vv = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
                if cfg.attention_impl == "dense":
                    out = dense_attention(q, kk, vv, causal=True)
                else:
                    out = blocked_attention(q, kk, vv, causal=True,
                                            unroll=cfg.attention_unroll)
                out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
                x = x + out @ p["wo"].astype(out.dtype)
                pad = max_len - S
                kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                new_slots[skey] = {"k": kc, "v": vc}
            elif kind == "ssm":
                out, st = ssm_apply(p, h, cfg, return_state=True)
                x = x + out
                new_slots[skey] = {
                    "conv_x": st.conv_x, "conv_bc": st.conv_bc, "ssm": st.ssm
                }
            elif kind == "mlp":
                x = x + mlp_apply(p, h)
            elif kind == "moe":
                y, _ = moe_apply(p, h, cfg)
                x = x + y
        return (x, positions), new_slots

    if cfg.scan_blocks:
        (x, _), slot_caches = jax.lax.scan(body, (x, positions), params["blocks"])
    else:
        carry = (x, positions)
        per_block = []
        for i in range(cfg.n_blocks):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            carry, ys = body(carry, bp)
            per_block.append(ys)
        x, _ = carry
        slot_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *per_block)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = head_logits(params, cfg, x[:, -1:, :])
    new_cache = {"len": jnp.full((), S, jnp.int32), "slots": {}}
    for skey, kind, _role in slots:
        if skey in slot_caches:
            new_cache["slots"][skey] = slot_caches[skey]
    return logits, new_cache


def decode_step(
    params: Params, cfg: ModelConfig, tokens: jax.Array, cache: dict
) -> tuple[jax.Array, dict]:
    """One decode step. tokens: [B, 1] → logits [B, 1, V], updated cache."""
    x = embed_inputs(params, cfg, tokens)
    B = x.shape[0]
    cache_len = cache["len"]
    slots = _slot_keys(cfg)

    def body(x, block_inputs):
        block_params, block_cache = block_inputs
        new_slots = {}
        for skey, kind, _role in slots:
            p = block_params[skey]
            h = rmsnorm(x, p["norm_scale"], cfg.norm_eps)
            if kind == "attn":
                c = block_cache[skey]
                out, kc, vc = attention_decode(
                    p, h, cfg, c["k"], c["v"], cache_len
                )
                x = x + out
                new_slots[skey] = {"k": kc, "v": vc}
            elif kind == "ssm":
                c = block_cache[skey]
                out, st = ssm_decode(
                    p, h, cfg,
                    SsmState(conv_x=c["conv_x"], conv_bc=c["conv_bc"],
                             ssm=c["ssm"]),
                )
                x = x + out
                new_slots[skey] = {
                    "conv_x": st.conv_x, "conv_bc": st.conv_bc, "ssm": st.ssm
                }
            elif kind == "mlp":
                x = x + mlp_apply(p, h)
            elif kind == "moe":
                y, _ = moe_apply(p, h, cfg)
                x = x + y
        return x, new_slots

    if cfg.scan_blocks:
        x, new_slot_caches = jax.lax.scan(
            body, x, (params["blocks"], cache["slots"])
        )
    else:
        per_block = []
        for i in range(cfg.n_blocks):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            bc = jax.tree.map(lambda a: a[i], cache["slots"])
            x, ys = body(x, (bp, bc))
            per_block.append(ys)
        new_slot_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *per_block)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = head_logits(params, cfg, x)
    return logits, {"len": cache_len + 1, "slots": new_slot_caches}


def abstract_params(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct pytree of the parameters (no allocation) — the
    dry-run path."""
    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
