"""Distribution tests.  Mesh-dependent cases run in a subprocess with 8
virtual devices (the main test process must keep seeing 1 device — the
dry-run is the only place 512 devices are forced)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import Model, smoke_variant
from repro.parallel.sharding import param_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_virtual(script: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


class TestParamSpecRules:
    def setup_method(self):
        self.mesh = make_mesh((1, 1), ("data", "model"))

    def test_attention_specs(self):
        cfg = get_config("granite_8b")
        assert param_spec(["blocks", "L0_attn", "wq"], 3, cfg, self.mesh) == \
            jax.sharding.PartitionSpec(None, None, "model")
        # kv=8 does not divide model=1? (divides) — use a 16-way mesh check below
        assert param_spec(["embed"], 2, cfg, self.mesh) == \
            jax.sharding.PartitionSpec("model", None)

    def test_kv_replication_rule(self):
        mesh16 = make_mesh((1, 1), ("data", "model"))
        cfg = get_config("glm4_9b")  # kv=2

        class FakeMesh:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")

        spec = param_spec(["blocks", "L0_attn", "wk"], 3, cfg, FakeMesh())
        assert spec == jax.sharding.PartitionSpec(None, None, None)
        cfg2 = get_config("olmoe_1b_7b")  # kv=16 divides 16
        spec2 = param_spec(["blocks", "L0_attn", "wk"], 3, cfg2, FakeMesh())
        assert spec2 == jax.sharding.PartitionSpec(None, None, "model")

    def test_moe_expert_parallel(self):
        cfg = get_config("olmoe_1b_7b")
        spec = param_spec(["blocks", "L0_moe", "w_gate"], 4, cfg, self.mesh)
        assert spec == jax.sharding.PartitionSpec(None, "model", None, None)

    def test_norms_replicated(self):
        cfg = get_config("granite_8b")
        assert param_spec(["final_norm"], 1, cfg, self.mesh) == \
            jax.sharding.PartitionSpec(None)


@pytest.mark.slow
class TestVirtualMesh:
    def test_sharded_train_step_matches_single_device(self):
        """2×4 mesh train step ≡ single-device train step (same loss)."""
        run_virtual("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.configs import get_config
            from repro.models import Model, smoke_variant
            from repro.train import AdamWConfig, init_state, make_train_step
            from repro.train.step import abstract_state, state_shardings
            from repro.data.pipeline import DataConfig, HostDataLoader

            cfg = smoke_variant(get_config("granite_8b"))
            model = Model(cfg)
            opt = AdamWConfig(lr=1e-3)
            loader = HostDataLoader(
                DataConfig(vocab=cfg.vocab, seq_len=16, batch_per_host=8), 0, 1)
            batch, _ = loader.batch_at(0)
            batch = jax.tree.map(jnp.asarray, batch)

            # single device
            s0 = init_state(model, jax.random.key(0), opt)
            step = make_train_step(model, opt)
            _, m_single = jax.jit(step)(s0, batch)

            # 2x4 mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            st = abstract_state(model, opt)
            sh = state_shardings(st, cfg, mesh)
            s1 = init_state(model, jax.random.key(0), opt)
            s1 = jax.tree.map(jax.device_put, s1, sh)
            b_sh = {k: NamedSharding(mesh, P("data", *([None] * (v.ndim - 1))))
                    for k, v in batch.items()}
            batch_sharded = jax.tree.map(jax.device_put, batch, b_sh)
            with mesh:
                step_sharded = jax.jit(step, in_shardings=(sh, b_sh),
                                       out_shardings=(sh, None))
                _, m_mesh = step_sharded(s1, batch_sharded)
            np.testing.assert_allclose(float(m_single["loss"]),
                                       float(m_mesh["loss"]), rtol=2e-4)
            print("LOSS_MATCH", float(m_single["loss"]), float(m_mesh["loss"]))
        """)

    def test_compressed_allreduce_shardmap(self):
        run_virtual("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from functools import partial
            from jax.sharding import PartitionSpec as P
            from repro.parallel.compress import compressed_allreduce_mean

            mesh = make_mesh((8,), ("data",))
            x = jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64) / 100.0

            @partial(jax.shard_map, mesh=mesh, in_specs=P("data", None),
                     out_specs=P("data", None), check_vma=False)
            def f(xs):
                return compressed_allreduce_mean(xs[0], "data")[None]

            got = f(x)
            want = x.mean(axis=0)
            np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                       atol=np.abs(want).max() / 100)
            # int8 payload on the wire
            hlo = jax.jit(f).lower(x).compile().as_text()
            assert "s8[" in hlo, "expected int8 all-gather in HLO"
            print("COMPRESSED_OK")
        """)

    def test_pipeline_parallel(self):
        run_virtual("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.parallel.pipeline import pipeline_apply, stage_split

            assert stage_split(10, 4) == [3, 3, 2, 2]
            mesh = make_mesh((4,), ("pipe",))
            n_stages, n_micro, mb, d = 4, 8, 2, 16
            keys = jax.random.split(jax.random.key(0), n_stages)
            ws = jnp.stack([
                jax.random.normal(k, (d, d)) * 0.3 for k in keys])

            def stage_fn(w, x):
                return jnp.tanh(x @ w)

            x = jax.random.normal(jax.random.key(1), (n_micro, mb, d))
            got = pipeline_apply(stage_fn, ws, x, mesh, axis="pipe")

            ref = x
            for i in range(n_stages):
                ref = jnp.tanh(ref @ ws[i])
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-4, atol=1e-5)
            print("PIPELINE_OK")
        """)

    def test_small_dryrun_cell_on_8_devices(self):
        """End-to-end lower+compile of a reduced arch on a 2x4 mesh."""
        run_virtual("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.configs import get_config, SHAPES
            from repro.launch.mesh import make_mesh
            from repro.models import Model, smoke_variant
            from repro.parallel.sharding import param_shardings
            from dataclasses import replace

            cfg = replace(smoke_variant(get_config("granite_moe_1b_a400m")),
                          moe_impl="dense")
            model = Model(cfg)
            mesh = make_mesh((2, 4), ("data", "model"))
            params = model.abstract_params()
            p_sh = param_shardings(params, cfg, mesh)
            tokens = jax.ShapeDtypeStruct((8, 32), jax.numpy.int32)
            t_sh = NamedSharding(mesh, P("data", None))

            def fwd(params, tokens):
                return model.forward(params, {"tokens": tokens})[0]

            with mesh:
                compiled = jax.jit(fwd, in_shardings=(p_sh, t_sh)).lower(
                    params, tokens).compile()
            assert compiled.cost_analysis()["flops"] > 0
            print("DRYRUN8_OK")
        """)
