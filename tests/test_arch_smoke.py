"""Per-architecture smoke tests: reduced config of the same family runs one
forward + train step + decode step on CPU; shapes come out right, no NaNs.
The FULL configs are exercised only via the dry-run (ShapeDtypeStruct)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, shapes_for
from repro.models import Model, smoke_variant

# Per-arch forward+train+decode sweeps: the heaviest suite — out of the CI
# fast lane, still in the full tier-1 run.
pytestmark = pytest.mark.slow

B, S = 2, 32


def make_batch(cfg, rng=None):
    rng = rng or np.random.default_rng(0)
    if cfg.enc_layers:
        t_enc = S // 4
        return {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
            "enc_embeds": jnp.asarray(
                rng.normal(0, 1, (B, t_enc, cfg.d_model)), jnp.float32
            ),
        }
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
    }
    if cfg.frontend_tokens:
        batch["embeds"] = jnp.asarray(
            rng.normal(0, 1, (B, cfg.frontend_tokens, cfg.d_model)), jnp.float32
        )
        # labels must cover prefix + text in loss handling (prefix is padded)
    return batch


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch_setup(request):
    cfg = smoke_variant(get_config(request.param))
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    return request.param, cfg, model, params


class TestArchSmoke:
    def test_forward_shapes_and_finite(self, arch_setup):
        arch, cfg, model, params = arch_setup
        batch = make_batch(cfg)
        logits, _aux = jax.jit(model.forward)(params, batch)
        expect_s = S + (cfg.frontend_tokens or 0)
        assert logits.shape == (B, expect_s, cfg.vocab)
        assert bool(jnp.isfinite(logits).all())

    def test_train_step_decreases_nothing_nan(self, arch_setup):
        arch, cfg, model, params = arch_setup
        batch = make_batch(cfg)

        @jax.jit
        def step(p):
            (loss, metrics), grads = jax.value_and_grad(
                model.loss, has_aux=True
            )(p, batch)
            p2 = jax.tree.map(lambda w, g: w - 1e-3 * g, p, grads)
            return loss, p2

        loss, params2 = step(params)
        assert bool(jnp.isfinite(loss))
        # gradients actually changed the parameters
        changed = jax.tree.map(
            lambda a, b: bool(jnp.any(a != b)), params, params2
        )
        assert any(jax.tree.leaves(changed))
        loss2, _ = step(params2)
        assert bool(jnp.isfinite(loss2))

    def test_decode_matches_forward(self, arch_setup):
        """Greedy logits from step-by-step decode ≡ full forward (causality)."""
        arch, cfg, model, params = arch_setup
        if cfg.enc_layers:
            pytest.skip("enc-dec decode covered in test_encdec_decode")
        if cfg.frontend_tokens:
            pytest.skip("vlm decode covered in test_vlm_prefill_decode")
        rng = np.random.default_rng(1)
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, 8)), jnp.int32)
        full_logits, _ = model.forward(params, {"tokens": toks})

        cache = model.init_cache(params, {"tokens": toks}, max_len=16)
        decode = jax.jit(model.decode)
        outs = []
        for i in range(8):
            logits, cache = decode(params, toks[:, i : i + 1], cache)
            outs.append(logits[:, 0])
        step_logits = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(step_logits), np.asarray(full_logits),
            rtol=2e-2, atol=2e-2,
        )

    def test_prefill_then_decode_consistent(self, arch_setup):
        arch, cfg, model, params = arch_setup
        if cfg.enc_layers or cfg.frontend_tokens:
            pytest.skip("covered elsewhere")
        rng = np.random.default_rng(2)
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, 8)), jnp.int32)
        full_logits, _ = model.forward(params, {"tokens": toks})

        cache = model.init_cache(params, {"tokens": toks[:, :6]}, max_len=16)
        pf_logits, cache = jax.jit(model.prefill)(
            params, {"tokens": toks[:, :6]}, cache
        )
        np.testing.assert_allclose(
            np.asarray(pf_logits[:, 0]), np.asarray(full_logits[:, 5]),
            rtol=2e-2, atol=2e-2,
        )
        logits6, cache = jax.jit(model.decode)(params, toks[:, 6:7], cache)
        np.testing.assert_allclose(
            np.asarray(logits6[:, 0]), np.asarray(full_logits[:, 6]),
            rtol=2e-2, atol=2e-2,
        )


class TestEncDec:
    def test_encdec_decode(self):
        cfg = smoke_variant(get_config("seamless_m4t_medium"))
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        batch = make_batch(cfg)
        full_logits, _ = model.forward(params, batch)

        cache = model.init_cache(params, batch, max_len=16)
        decode = jax.jit(model.decode)
        outs = []
        for i in range(8):
            logits, cache = decode(params, batch["tokens"][:, i : i + 1], cache)
            outs.append(logits[:, 0])
        step_logits = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(step_logits), np.asarray(full_logits[:, :8]),
            rtol=2e-2, atol=2e-2,
        )


class TestVLM:
    def test_vlm_prefill_decode(self):
        cfg = smoke_variant(get_config("internvl2_26b"))
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        batch = make_batch(cfg)
        full_logits, _ = model.forward(params, batch)
        P = cfg.frontend_tokens
        assert full_logits.shape[1] == S + P

        cache = model.init_cache(params, batch, max_len=S + P + 8)
        pf_logits, cache = jax.jit(model.prefill)(params, batch, cache)
        np.testing.assert_allclose(
            np.asarray(pf_logits[:, 0]), np.asarray(full_logits[:, -1]),
            rtol=2e-2, atol=2e-2,
        )
        nxt = jnp.argmax(pf_logits[:, 0], -1).astype(jnp.int32)[:, None]
        logits, cache = jax.jit(model.decode)(params, nxt, cache)
        assert bool(jnp.isfinite(logits).all())


class TestConfigs:
    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_full_config_validates(self, arch):
        cfg = get_config(arch)
        assert cfg.n_blocks >= 1
        assert cfg.param_count() > 0

    def test_param_counts_plausible(self):
        # Advertised sizes (±25%: vocab/tie variations are real).
        expect = {
            "codeqwen1_5_7b": 7.25e9,
            "glm4_9b": 9.4e9,
            "granite_8b": 8.1e9,
            "olmoe_1b_7b": 6.9e9,
            "jamba_v0_1_52b": 52e9,
            "mamba2_130m": 0.13e9,
        }
        for arch, n in expect.items():
            got = get_config(arch).param_count()
            assert 0.7 * n < got < 1.35 * n, f"{arch}: {got:.3e} vs {n:.3e}"

    def test_active_params_moe(self):
        cfg = get_config("olmoe_1b_7b")
        active = cfg.param_count(active_only=True)
        total = cfg.param_count()
        assert active < total / 3  # 8/64 experts active

    def test_shapes_for(self):
        assert len(shapes_for("mamba2_130m")) == 4
        assert len(shapes_for("glm4_9b")) == 3  # long_500k skipped
