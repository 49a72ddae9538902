"""Substrate tests: data pipeline, checkpointing, fault tolerance, gradient
compression, optimizer, mitigation planning, roofline parsing."""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.configs import get_config
from repro.core.analyzer import RootCause
from repro.core.features import FeatureKind
from repro.data.pipeline import DataConfig, HostDataLoader, Prefetcher
from repro.ft import (
    FailureDetector,
    HeartbeatWriter,
    MitigationAction,
    MitigationPlanner,
    RestartBudgetExceeded,
    Supervisor,
    plan_mesh_shape,
    reshard_plan,
)
from repro.models import Model, smoke_variant
from repro.parallel.compress import (
    dequantize,
    ef_compress,
    ef_init,
    quantize,
)
from repro.train import (
    AdamWConfig,
    abstract_state,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    init_state,
    make_schedule,
    make_train_step,
)


class TestDataPipeline:
    def test_deterministic_per_step(self):
        cfg = DataConfig(vocab=100, seq_len=16, batch_per_host=2, seed=3)
        a = HostDataLoader(cfg, 0, 4).batch_at(7)[0]
        b = HostDataLoader(cfg, 0, 4).batch_at(7)[0]
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_hosts_get_different_shards(self):
        cfg = DataConfig(vocab=100, seq_len=16, batch_per_host=2)
        a = HostDataLoader(cfg, 0, 4).batch_at(0)[0]
        b = HostDataLoader(cfg, 1, 4).batch_at(0)[0]
        assert not np.array_equal(a["tokens"], b["tokens"])

    def test_labels_shifted(self):
        cfg = DataConfig(vocab=100, seq_len=16, batch_per_host=2)
        batch, _ = HostDataLoader(cfg, 0, 1).batch_at(0)
        np.testing.assert_array_equal(
            batch["labels"][:, :-1], batch["tokens"][:, 1:]
        )

    def test_skew_inflates_bytes(self):
        base = DataConfig(vocab=100, seq_len=16, batch_per_host=2)
        skew = DataConfig(vocab=100, seq_len=16, batch_per_host=2,
                          skew_host=0, skew_factor=4.0)
        _, m0 = HostDataLoader(base, 0, 2).batch_at(0)
        _, m1 = HostDataLoader(skew, 0, 2).batch_at(0)
        _, m2 = HostDataLoader(skew, 1, 2).batch_at(0)
        assert m1.read_bytes > 3 * m0.read_bytes
        assert m2.read_bytes == pytest.approx(m0.read_bytes)

    def test_prefetcher(self):
        cfg = DataConfig(vocab=100, seq_len=8, batch_per_host=1)
        loader = HostDataLoader(cfg, 0, 1)
        with Prefetcher(loader, depth=2) as pf:
            b0, _ = pf.next()
            b1, _ = pf.next()
        want0, _ = loader.batch_at(0)
        np.testing.assert_array_equal(b0["tokens"], want0["tokens"])
        assert not np.array_equal(b0["tokens"], b1["tokens"])


class TestCheckpointManager:
    def _tree(self, x=1.0):
        return {"a": jnp.full((4, 3), x), "b": {"c": jnp.arange(5)}}

    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=3)
        tree = self._tree(2.5)
        mgr.save(10, tree)
        out = mgr.restore(jax.eval_shape(lambda: tree))
        np.testing.assert_array_equal(out["a"], tree["a"])
        np.testing.assert_array_equal(out["b"]["c"], tree["b"]["c"])

    def test_latest_and_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for step in (1, 2, 3, 4):
            mgr.save(step, self._tree(step))
        assert mgr.steps() == [3, 4]
        assert mgr.latest_step() == 4

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        mgr.save(5, self._tree(), blocking=False)
        mgr.wait()
        assert mgr.latest_step() == 5

    def test_atomicity_no_tmp_dirs_visible(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        mgr.save(1, self._tree())
        assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]

    def test_shape_mismatch_rejected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, self._tree())
        bad_template = {"a": jax.ShapeDtypeStruct((9, 9), jnp.float32),
                        "b": {"c": jax.ShapeDtypeStruct((5,), jnp.int32)}}
        with pytest.raises(ValueError, match="shape"):
            mgr.restore(bad_template)

    def test_restore_train_state_roundtrip(self, tmp_path):
        cfg = smoke_variant(get_config("granite_8b"))
        model = Model(cfg)
        opt = AdamWConfig()
        state = init_state(model, jax.random.key(0), opt)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(3, state)
        out = mgr.restore(abstract_state(model, opt))
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFaultTolerance:
    def test_heartbeat_detector(self, tmp_path):
        clock = [100.0]
        hw = HeartbeatWriter(str(tmp_path), "hostA", clock=lambda: clock[0])
        hw.beat()
        det = FailureDetector(str(tmp_path), timeout=5.0, clock=lambda: clock[0])
        assert det.alive() == ["hostA"] and det.dead() == []
        clock[0] += 10.0
        assert det.dead() == ["hostA"]

    def test_supervisor_restarts_then_succeeds(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        template = {"x": jax.ShapeDtypeStruct((2,), jnp.float32)}
        attempts = []

        def body(start, state):
            attempts.append(start)
            if state is None:
                state = {"x": jnp.zeros(2)}
            mgr.save(5, state)
            if len(attempts) < 3:
                raise RuntimeError("boom")
            return state

        sup = Supervisor(mgr, template, max_restarts=3)
        sup.run(body)
        assert attempts == [0, 6, 6]
        assert sup.restarts == 2

    def test_supervisor_budget(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))

        def body(start, state):
            raise RuntimeError("always")

        sup = Supervisor(mgr, {}, max_restarts=1)
        with pytest.raises(RestartBudgetExceeded):
            sup.run(body)

    def test_elastic_plan(self):
        assert plan_mesh_shape(256) == (16, 16)
        assert plan_mesh_shape(240) == (15, 16)
        assert plan_mesh_shape(512, pod_axis=2) == (2, 16, 16)
        plan = reshard_plan((16, 16), [f"h{i}" for i in range(28)],
                            [f"h{i}" for i in range(32)], chips_per_host=8)
        assert plan.new_shape == (14, 16)
        assert plan.dropped_hosts == ("h28", "h29", "h30", "h31")

    def test_elastic_too_few(self):
        with pytest.raises(ValueError):
            plan_mesh_shape(8, model_axis=16)


class TestMitigation:
    def _cause(self, feature, node="h0", task="h0/step1"):
        return RootCause(task_id=task, stage_id="s", node=node,
                         feature=feature, kind=FeatureKind.RESOURCE,
                         value=0.9, peer_groups=("inter",))

    def test_quarantine_threshold(self):
        planner = MitigationPlanner(quarantine_threshold=3)
        causes = [self._cause("cpu", "h7", f"h7/s{i}") for i in range(3)]
        plans = planner.plan(causes)
        assert any(
            p.action is MitigationAction.QUARANTINE_HOST and p.target == "h7"
            for p in plans
        )

    def test_below_threshold_no_quarantine(self):
        planner = MitigationPlanner(quarantine_threshold=3)
        plans = planner.plan([self._cause("cpu", "h7")])
        assert not plans

    def test_feature_action_mapping(self):
        planner = MitigationPlanner(min_findings=1)
        plans = planner.plan([self._cause("ckpt_time")])
        assert plans[0].action is MitigationAction.ASYNC_CKPT
        plans = planner.plan([self._cause("locality")])
        assert any(p.action is MitigationAction.REPLICATE_SHARDS for p in plans)


class TestCompression:
    def test_quantize_roundtrip_error_bounded(self):
        x = jnp.asarray(np.random.default_rng(0).normal(0, 2, (1000,)),
                        jnp.float32)
        qt = quantize(x)
        deq = dequantize(qt, x.shape)
        # per-block max/127 quantization: error ≤ scale/2 per element
        assert float(jnp.max(jnp.abs(x - deq))) <= float(qt.scale.max()) / 2 + 1e-6
        assert qt.q.dtype == jnp.int8

    def test_error_feedback_reduces_bias(self):
        """With EF, the *accumulated* quantized sum tracks the true sum."""
        rng = np.random.default_rng(1)
        g_true = jnp.asarray(rng.normal(0, 1, (512,)), jnp.float32)
        grads = {"w": g_true}
        residual = ef_init(grads)
        acc_q = jnp.zeros_like(g_true)
        for _ in range(20):
            deq, residual = ef_compress(grads, residual)
            acc_q = acc_q + deq["w"]
        err = float(jnp.max(jnp.abs(acc_q - 20 * g_true)))
        scale = float(quantize(g_true).scale.max())
        assert err <= 2 * scale  # bias does not accumulate across steps

    @pytest.mark.slow
    def test_compressed_train_step_converges(self):
        cfg = smoke_variant(get_config("mamba2_130m"))
        model = Model(cfg)
        opt = AdamWConfig(lr=1e-3, total_steps=10)
        state = init_state(model, jax.random.key(0), opt, compress=True)
        step = jax.jit(make_train_step(model, opt, compress=True),
                       donate_argnums=(0,))
        loader = HostDataLoader(
            DataConfig(vocab=cfg.vocab, seq_len=16, batch_per_host=2), 0, 1
        )
        batch, _ = loader.batch_at(0)
        batch = jax.tree.map(jnp.asarray, batch)
        losses = []
        for _ in range(6):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]


class TestOptimizer:
    def test_schedule_shapes(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          schedule="cosine", min_lr_ratio=0.1)
        sched = make_schedule(cfg)
        assert float(sched(jnp.asarray(0))) == pytest.approx(0.0)
        assert float(sched(jnp.asarray(10))) == pytest.approx(1.0)
        assert float(sched(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-6)

    def test_grad_clip(self):
        grads = {"a": jnp.full((4,), 10.0)}
        clipped, norm = clip_by_global_norm(grads, 1.0)
        assert float(norm) == pytest.approx(20.0)
        assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0)

    def test_adamw_decays_weights_not_norms(self):
        params = {"w": jnp.ones((3, 3)), "norm_scale": jnp.ones((3,))}
        grads = jax.tree.map(jnp.zeros_like, params)
        st = adamw_init(params)
        cfg = AdamWConfig(lr=0.1, weight_decay=0.5, warmup_steps=0,
                          schedule="constant", grad_clip=1e9)
        new_params, _, _ = adamw_update(grads, st, params, cfg)
        assert float(new_params["w"][0, 0]) < 1.0       # decayed
        assert float(new_params["norm_scale"][0]) == 1.0  # exempt

    @pytest.mark.slow
    def test_accum_matches_full_batch(self):
        cfg = smoke_variant(get_config("mamba2_130m"))
        model = Model(cfg)
        opt = AdamWConfig(lr=1e-3)
        loader = HostDataLoader(
            DataConfig(vocab=cfg.vocab, seq_len=16, batch_per_host=4), 0, 1
        )
        batch, _ = loader.batch_at(0)
        batch = jax.tree.map(jnp.asarray, batch)
        s0 = init_state(model, jax.random.key(0), opt)
        s1 = init_state(model, jax.random.key(0), opt)
        full = make_train_step(model, opt, accum=1)
        micro = make_train_step(model, opt, accum=2)
        out_full, m_full = full(s0, batch)
        out_micro, m_micro = micro(s1, batch)
        np.testing.assert_allclose(
            float(m_full["loss"]), float(m_micro["loss"]), rtol=1e-5
        )
        for a, b in zip(jax.tree.leaves(out_full["params"]),
                        jax.tree.leaves(out_micro["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)


class TestRooflineParser:
    def test_collective_stats_symbol_table(self):
        from repro.launch.roofline import collective_stats

        hlo = """
HloModule m
ENTRY e {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ag = f32[2048,256]{1,0} all-gather(%p0), replica_groups={}, dimensions={0}
  %ar = f32[2048,256]{1,0} all-reduce(%ag), to_apply=%sum
  ROOT %t = (f32[2048,256]{1,0}) tuple(%ar)
}
"""
        stats = collective_stats(hlo)
        assert stats.count_by_kind == {"all-gather": 1, "all-reduce": 1}
        assert stats.bytes_by_kind["all-gather"] == 128 * 256 * 4
        assert stats.bytes_by_kind["all-reduce"] == 2048 * 256 * 4

    def test_roofline_terms(self):
        from repro.launch.roofline import HBM_BW, LINK_BW, PEAK_FLOPS, Roofline

        r = Roofline.build(flops=PEAK_FLOPS, bytes_=HBM_BW,
                           coll_bytes=LINK_BW * 2, chips=256,
                           model_flops=PEAK_FLOPS * 256 * 0.5)
        assert r.compute_s == pytest.approx(1.0)
        assert r.memory_s == pytest.approx(1.0)
        assert r.collective_s == pytest.approx(2.0)
        assert r.dominant == "collective"
        assert r.useful_ratio == pytest.approx(0.5)
