"""Tests for the §Perf optimization paths: sharded CE, EP MoE, cache specs."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model, smoke_variant
from repro.models.lm import cross_entropy


class TestShardedCrossEntropy:
    def test_matches_take_along_axis(self):
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(0, 3, (4, 16, 37)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 37, (4, 16)), jnp.int32)
        got = cross_entropy(logits, labels)
        logp = jax.nn.log_softmax(logits, axis=-1)
        want = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_grad_matches(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.normal(0, 2, (2, 8, 11)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 11, (2, 8)), jnp.int32)
        g1 = jax.grad(lambda l: cross_entropy(l, labels).sum())(logits)

        def ref(l):
            logp = jax.nn.log_softmax(l, axis=-1)
            return -jnp.take_along_axis(logp, labels[..., None], -1).sum()

        g2 = jax.grad(ref)(logits)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-6)

    def test_extreme_logits_stable(self):
        logits = jnp.asarray([[[1e4, -1e4, 0.0]]], jnp.float32)
        labels = jnp.asarray([[0]], jnp.int32)
        out = cross_entropy(logits, labels)
        assert bool(jnp.isfinite(out).all())
        assert float(out[0, 0]) == pytest.approx(0.0, abs=1e-3)


class TestEpMoe:
    def test_ep_matches_ragged_on_virtual_mesh(self):
        """Run in-process guard: covered properly in test_parallel via
        subprocess; here we validate the capacity-drop behavior shape."""
        import os
        import subprocess
        import sys
        import textwrap

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = os.path.join(repo, "src")
        script = textwrap.dedent("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from dataclasses import replace
            from repro.configs import get_config
            from repro.models import smoke_variant
            from repro.models.moe import moe_init, moe_apply_ragged
            from repro.parallel import ep_moe

            cfg = replace(smoke_variant(get_config("olmoe_1b_7b")),
                          moe_experts=8, moe_top_k=2)
            mesh = make_mesh((2, 4), ("data", "model"))
            ep_moe.set_mesh(mesh)
            p = moe_init(jax.random.key(0), cfg)
            x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model))
            y_ref, _ = moe_apply_ragged(p, x, cfg)
            with mesh:
                y_ep, _ = jax.jit(lambda p, x: ep_moe.ep_moe_apply(
                    p, x, cfg, capacity_factor=8.0))(p, x)
            np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                                       rtol=1e-4, atol=1e-5)
            # tight capacity still runs (drops tokens, stays finite)
            with mesh:
                y_tight, _ = jax.jit(lambda p, x: ep_moe.ep_moe_apply(
                    p, x, cfg, capacity_factor=0.5))(p, x)
            assert np.isfinite(np.asarray(y_tight)).all()
            print("EP_OK")
        """)
        out = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, env=env,
                             timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "EP_OK" in out.stdout


class TestCacheSpecs:
    def test_head_dim_sharding_when_kv_misaligned(self):
        from jax.sharding import PartitionSpec as P

        from repro.parallel.sharding import cache_spec_for_kv

        class FakeMesh:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")

        # glm4: kv=2 (misaligned), hd=128 (divisible) → head_dim sharded
        cfg = get_config("glm4_9b")
        spec = cache_spec_for_kv(cfg, FakeMesh(), batch_size=128)
        assert spec == P(None, ("data",), None, None, "model")
        # batch=1 long-context: seq over dp, hd over model
        cfg2 = get_config("jamba_v0_1_52b")
        spec2 = cache_spec_for_kv(cfg2, FakeMesh(), batch_size=1)
        assert spec2 == P(None, None, ("data",), None, "model")
        # kv-aligned arch keeps head sharding
        cfg3 = get_config("olmoe_1b_7b")  # kv=16
        spec3 = cache_spec_for_kv(cfg3, FakeMesh(), batch_size=128)
        assert spec3 == P(None, ("data",), None, "model", None)


class TestGatheredMoe:
    def test_matches_ragged(self):
        from repro.models.moe import moe_apply_gathered, moe_apply_ragged, moe_init

        cfg = smoke_variant(get_config("granite_moe_1b_a400m"))
        p = moe_init(jax.random.key(2), cfg)
        x = jax.random.normal(jax.random.key(3), (1, 1, cfg.d_model))
        y1, _ = moe_apply_ragged(p, x, cfg)
        y2, _ = moe_apply_gathered(p, x, cfg)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=2e-5, atol=1e-6)
