"""Equivalence suite for the batched Eq. 5 gate kernel.

The jax and Pallas gate backends (``repro.kernels.bigroots_gates``, fed by
the ``repro.core.fleet`` packer) must produce *byte-identical* RootCause
sets to the numpy path on the CPU — gates are float64 comparisons end to
end (the kernel runs under ``jax.enable_x64``; Pallas in interpret mode,
asked for explicitly), so there is no tolerance to hide behind.  The
compiled TPU kernel runs in float32; its contract — only knife-edge
elements may flip — is pinned here by casting batches to float32.  Covers the randomized analyzer
path, the corner cases (empty inter/intra peer groups, NaN values,
stage-mean ≤ 0 numerical columns, TIME floor), padded-row masking in the
fleet batch, and ``analyze_fleet`` ≡ per-window analysis.
"""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import (  # noqa: E402
    BigRootsAnalyzer,
    BigRootsThresholds,
    SPARK_FEATURES,
    SlidingStageWindow,
    StageRecord,
    TaskRecord,
    eval_gates_np,
    found_set,
    pack_windows,
)
from repro.core.fleet import (  # noqa: E402
    FleetGateBatch,
    column_floor,
    knife_edge,
)
from repro.kernels.bigroots_gates import _gates_pallas, eval_gates  # noqa: E402
from repro.telemetry import ResourceTimeline  # noqa: E402

METRICS = ("cpu", "disk", "network")


def random_stage(rng, n=None, n_nodes=None):
    n = n if n is not None else int(rng.integers(2, 41))
    n_nodes = n_nodes if n_nodes is not None else int(rng.integers(1, 7))
    tasks = []
    for i in range(n):
        start = float(rng.uniform(0.0, 30.0))
        dur = float(rng.uniform(0.5, 60.0))
        feats = {
            "cpu": float(rng.uniform(0, 1)),
            "disk": float(rng.uniform(0, 1)),
            "network": float(rng.uniform(0, 1e8)),
            "read_bytes": float(rng.uniform(0, 1e9)),
            "shuffle_read_bytes": float(rng.uniform(0, 1e9)),
            "jvm_gc_time": float(rng.uniform(0, dur)),
        }
        if rng.random() < 0.2:
            del feats[list(feats)[int(rng.integers(len(feats)))]]
        tasks.append(TaskRecord(
            task_id=f"t{i}", stage_id="s",
            node=f"n{int(rng.integers(n_nodes))}",
            start=start, end=start + dur,
            locality=int(rng.choice([0, 0, 0, 1, 2])),
            features=feats,
        ))
    return StageRecord("s", tasks)


def random_timeline(rng, stage):
    tl = ResourceTimeline()
    t_hi = max(t.end for t in stage.tasks) + 10.0
    for node in {t.node for t in stage.tasks}:
        for metric in METRICS:
            if rng.random() < 0.2:
                continue
            ts = np.arange(-10.0, t_hi, float(rng.uniform(0.7, 2.0)))
            keep = rng.random(ts.size) > 0.3
            samples = [(float(t), float(rng.uniform(0, 1))) for t in ts[keep]]
            rng.shuffle(samples)
            tl.record_many(node, metric, samples)
    return tl


def random_thresholds(rng):
    return BigRootsThresholds(
        quantile=float(rng.choice([0.5, 0.7, 0.8, 0.9, 0.95])),
        peer_mean=float(rng.choice([1.0, 1.25, 1.5, 2.0])),
        edge_filter=float(rng.choice([0.3, 0.5, 0.8])),
        edge_width=float(rng.choice([1.0, 3.0, 5.0])),
    )


def fill_window(stage, rng, quantile, stage_id="s"):
    w = SlidingStageWindow(stage_id, SPARK_FEATURES, quantile=quantile)
    for i in rng.permutation(len(stage.tasks)):
        t = stage.tasks[i]
        w.add_row(t.task_id, t.node, t.start, t.end, t.locality, t.features)
    return w


def analyzers(th=BigRootsThresholds(), timelines=None, exact=True):
    """(numpy, jax, pallas) analyzers with the kernel forced on
    (backend_min_rows=0) and exact λq so results must be byte-identical."""
    mk = lambda backend: BigRootsAnalyzer(  # noqa: E731
        SPARK_FEATURES, th, timelines=timelines,
        window_exact_quantiles=exact, backend=backend, backend_min_rows=0,
        kernel_interpret=True,
    )
    return mk("numpy"), mk("jax"), mk("pallas")


def causes_sorted(sa):
    return sorted(sa.root_causes, key=lambda c: (c.task_id, c.feature))


def random_batch(rng, W=None, R=None, F=None):
    W = W or int(rng.integers(1, 5))
    R = R or int(rng.integers(1, 40))
    F = F or int(rng.integers(1, 15))
    counts = rng.integers(0, R + 1, size=W)
    v = rng.normal(1.0, 2.0, (W, R, F))
    peer_vsum = rng.normal(2.0, 4.0, (W, R, F))
    inter_cnt = rng.integers(0, 6, (W, R, 1)).astype(np.float64)
    intra_cnt = rng.integers(0, 6, (W, R, 1)).astype(np.float64)
    rowmask = np.zeros((W, R, 1))
    for i, c in enumerate(counts):
        rowmask[i, :c, 0] = 1.0
    vsum = rng.normal(0.0, 8.0, (W, 1, F))
    q = rng.normal(0.5, 1.0, (W, 1, F))
    numok = rng.choice([0.0, 1.0], (W, 1, F))
    floor = np.where(rng.random((1, 1, F)) < 0.3, 0.2, -np.inf)
    return FleetGateBatch(v, peer_vsum, inter_cnt, intra_cnt, rowmask,
                          vsum, q, numok, floor, counts)


class TestRawBatchEquivalence:
    """Kernel vs jnp vs numpy oracle on raw packed batches (no analyzer)."""

    @pytest.mark.parametrize("backend", ["jax", "pallas"])
    def test_randomized_bit_identical(self, backend):
        for seed in range(20):
            rng = np.random.default_rng(seed)
            b = random_batch(rng)
            want = eval_gates_np(b, peer_mean=1.5)
            got = eval_gates(b.v, b.peer_vsum, b.inter_cnt, b.intra_cnt,
                             b.rowmask, b.vsum, b.q, b.numok, b.floor,
                             peer_mean=1.5, backend=backend, interpret=True)
            np.testing.assert_array_equal(got, want, err_msg=f"seed={seed}")

    @pytest.mark.parametrize("backend", ["jax", "pallas"])
    def test_nan_values_and_zero_counts_never_fire(self, backend):
        """NaN gate-space values and empty peer groups (cnt 0 → 0/0 NaN
        peer means) must produce gbits 0 on every backend."""
        rng = np.random.default_rng(99)
        b = random_batch(rng, W=2, R=16, F=6)
        b.v[0, :4] = np.nan
        b.inter_cnt[:, ::2] = 0.0
        b.intra_cnt[:, 1::2] = 0.0
        # empty groups in the packed layout have peer_vsum == vsum (inter)
        # or == v (intra) → 0/0; emulate the worst case: both zeroed rows
        b.peer_vsum[0, ::2] = b.vsum[0]
        want = eval_gates_np(b, peer_mean=1.5)
        got = eval_gates(b.v, b.peer_vsum, b.inter_cnt, b.intra_cnt,
                         b.rowmask, b.vsum, b.q, b.numok, b.floor,
                         peer_mean=1.5, backend=backend, interpret=True)
        np.testing.assert_array_equal(got, want)
        assert (got[0, :4] == 0).all()                       # NaN rows dark
        assert (got[:, ::2] & 1).sum() == 0                  # no inter fires
        assert (got[:, 1::2] & 2).sum() == 0                 # no intra fires

    @pytest.mark.parametrize("backend", ["jax", "pallas"])
    def test_padded_rows_masked(self, backend):
        """gbits beyond each window's real row count must be zero even when
        the padded values would trivially pass every gate."""
        W, R, F = 3, 24, 5
        counts = np.array([5, 0, 24])
        v = np.full((W, R, F), 100.0)
        peer_vsum = np.zeros((W, R, F))
        inter_cnt = np.ones((W, R, 1))
        intra_cnt = np.ones((W, R, 1))
        rowmask = np.zeros((W, R, 1))
        for i, c in enumerate(counts):
            rowmask[i, :c, 0] = 1.0
        vsum = np.full((W, 1, F), 1.0)
        q = np.zeros((W, 1, F))
        numok = np.ones((W, 1, F))
        floor = np.full((1, 1, F), -np.inf)
        b = FleetGateBatch(v, peer_vsum, inter_cnt, intra_cnt, rowmask,
                           vsum, q, numok, floor, counts)
        got = eval_gates(b.v, b.peer_vsum, b.inter_cnt, b.intra_cnt,
                         b.rowmask, b.vsum, b.q, b.numok, b.floor,
                         peer_mean=1.5, backend=backend, interpret=True)
        np.testing.assert_array_equal(got, eval_gates_np(b, peer_mean=1.5))
        for i, c in enumerate(counts):
            assert (got[i, :c] > 0).all()    # real rows fire (by construction)
            assert (got[i, c:] == 0).all()   # padding never fires

    def test_pallas_row_blocking_consistent(self):
        """Different block_r tilings of the same batch agree (the grid
        decomposition is an implementation detail)."""
        rng = np.random.default_rng(7)
        b = random_batch(rng, W=2, R=70, F=9)
        outs = [
            eval_gates(b.v, b.peer_vsum, b.inter_cnt, b.intra_cnt, b.rowmask,
                       b.vsum, b.q, b.numok, b.floor, peer_mean=1.5,
                       backend="pallas", block_r=br, interpret=True)
            for br in (32, 64, 256)
        ]
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], outs[2])

    def test_unknown_backend_raises(self):
        rng = np.random.default_rng(1)
        b = random_batch(rng, W=1, R=4, F=2)
        with pytest.raises(ValueError, match="unknown gate backend"):
            eval_gates(b.v, b.peer_vsum, b.inter_cnt, b.intra_cnt, b.rowmask,
                       b.vsum, b.q, b.numok, b.floor, peer_mean=1.5,
                       backend="tpuv9")
        with pytest.raises(ValueError, match="unknown backend"):
            BigRootsAnalyzer(SPARK_FEATURES, backend="cuda")


def gates_f32_interpreted(b, peer_mean):
    """The compiled kernel's float32 arithmetic, run in the interpreter."""
    args = [jax.numpy.asarray(a, dtype=np.float32) for a in (
        b.v, b.peer_vsum, b.inter_cnt, b.intra_cnt, b.rowmask, b.vsum, b.q,
        b.numok, b.floor)]
    return np.asarray(_gates_pallas(*args, peer_mean=peer_mean,
                                    block_r=b.v.shape[1], interpret=True))


def plant_knife_edges(b, rng, peer_mean, frac=0.3):
    """Move a fraction of values a billionth above λq or above λp×inter:
    float64 sees them above the threshold, float32 rounds onto it."""
    q = np.broadcast_to(b.q, b.v.shape)
    with np.errstate(invalid="ignore", divide="ignore"):
        inter = peer_mean * (b.vsum - b.peer_vsum) / b.inter_cnt
    pick = rng.random(b.v.shape) < frac
    on_q = pick & (rng.random(b.v.shape) < 0.5)
    on_inter = pick & ~on_q & np.isfinite(inter) & (b.inter_cnt > 0)
    b.v[on_q] = q[on_q] + 1e-9 * np.abs(q[on_q])
    b.v[on_inter] = inter[on_inter] + 1e-9 * np.abs(inter[on_inter])


class TestFloat32Contract:
    """On a TPU the kernel runs in float32 (Mosaic has no f64): only
    knife-edge elements (``repro.core.fleet.knife_edge``) may differ from
    the float64 oracle, and ``backend="pallas"`` never quietly becomes
    the interpreter."""

    def test_f32_flips_are_knife_edges(self):
        flips = 0
        for seed in range(10):
            rng = np.random.default_rng(300 + seed)
            b = random_batch(rng, W=3, R=64, F=14)
            b.rowmask[:] = 1.0
            plant_knife_edges(b, rng, peer_mean=1.5)
            diff = gates_f32_interpreted(b, 1.5) != eval_gates_np(b, 1.5)
            off_edge = diff & ~knife_edge(b, 1.5)
            assert not off_edge.any(), f"seed={seed}: {np.argwhere(off_edge)}"
            flips += int(diff.sum())
        assert flips > 0  # the planted knife edges did flip

    def test_knife_edge_is_narrow(self):
        """Random values are almost never knife edges, so a wrong gate
        bit cannot hide behind the classifier."""
        rng = np.random.default_rng(5)
        b = random_batch(rng, W=4, R=256, F=14)
        b.rowmask[:] = 1.0
        knife = knife_edge(b, 1.5)
        assert knife.mean() < 1e-3
        want = eval_gates_np(b, 1.5)
        np.testing.assert_array_equal(gates_f32_interpreted(b, 1.5)[~knife],
                                      want[~knife])

    def test_compiled_kernel_needs_a_tpu(self):
        """Off-TPU, backend="pallas" without interpret mode raises instead
        of running the interpreter (or numpy) in the chip's place."""
        rng = np.random.default_rng(1)
        b = random_batch(rng, W=1, R=8, F=3)
        with pytest.raises(RuntimeError, match="interpret=True"):
            eval_gates(b.v, b.peer_vsum, b.inter_cnt, b.intra_cnt, b.rowmask,
                       b.vsum, b.q, b.numok, b.floor, peer_mean=1.5)
        tasks = [TaskRecord(f"t{i}", "s", f"n{i % 3}", 0.0,
                            30.0 if i == 0 else 10.0,
                            features={"read_bytes": 100.0 + i})
                 for i in range(12)]
        an = BigRootsAnalyzer(SPARK_FEATURES, backend="pallas",
                              backend_min_rows=0)
        w = fill_window(StageRecord("s", tasks), rng, 0.9)
        with pytest.raises(RuntimeError, match="interpret=True"):
            an.analyze_stage(w)


class TestAnalyzerBackendEquivalence:
    """Full-analyzer equivalence: backend="jax"/"pallas" must emit the same
    RootCause objects (ids, values, peer groups) as backend="numpy"."""

    def test_randomized_with_timelines(self):
        for seed in range(25):
            rng = np.random.default_rng(seed)
            stage = random_stage(rng)
            tl = random_timeline(rng, stage)
            th = random_thresholds(rng)
            an_np, an_jax, an_pl = analyzers(th, timelines=tl)
            w = fill_window(stage, rng, th.quantile)
            want = causes_sorted(an_np.analyze_stage(w))
            assert causes_sorted(an_jax.analyze_stage(w)) == want, f"seed={seed}"
            assert causes_sorted(an_pl.analyze_stage(w)) == want, f"seed={seed}"

    def test_single_node_empty_inter_peers(self):
        for seed in range(8):
            rng = np.random.default_rng(2000 + seed)
            stage = random_stage(rng, n_nodes=1)
            th = random_thresholds(rng)
            an_np, an_jax, an_pl = analyzers(th)
            w = fill_window(stage, rng, th.quantile)
            want = causes_sorted(an_np.analyze_stage(w))
            assert causes_sorted(an_jax.analyze_stage(w)) == want
            assert causes_sorted(an_pl.analyze_stage(w)) == want

    def test_lonely_node_straggler_empty_intra_peers(self):
        tasks = [TaskRecord(f"t{i}", "s", f"n{i % 3}", 0.0, 10.0,
                            features={"read_bytes": 100.0}) for i in range(12)]
        tasks.append(TaskRecord("t99", "s", "lonely", 0.0, 30.0,
                                features={"read_bytes": 900.0}))
        rng = np.random.default_rng(5)
        an_np, an_jax, an_pl = analyzers()
        w = fill_window(StageRecord("s", tasks), rng, 0.9)
        want = causes_sorted(an_np.analyze_stage(w))
        assert causes_sorted(an_jax.analyze_stage(w)) == want
        assert causes_sorted(an_pl.analyze_stage(w)) == want
        hit = [c for c in want if c.key == ("t99", "read_bytes")]
        assert hit and hit[0].peer_groups == ("inter",)

    def test_nonpositive_numerical_mean_guard(self):
        """A numerical column whose stage mean is ≤ 0 must not fire on any
        backend (the kernel's numok guard ≡ the numpy means guard)."""
        tasks = [TaskRecord(f"t{i}", "s", f"n{i % 2}", 0.0,
                            30.0 if i == 0 else 10.0,
                            features={"read_bytes": -100.0,
                                      "jvm_gc_time": 8.0 if i == 0 else 0.1})
                 for i in range(10)]
        rng = np.random.default_rng(6)
        an_np, an_jax, an_pl = analyzers()
        w = fill_window(StageRecord("s", tasks), rng, 0.9)
        want = causes_sorted(an_np.analyze_stage(w))
        assert causes_sorted(an_jax.analyze_stage(w)) == want
        assert causes_sorted(an_pl.analyze_stage(w)) == want
        assert not any(c.feature == "read_bytes" for c in want)
        # ... while the TIME feature still passes its floor and fires.
        assert any(c.feature == "jvm_gc_time" for c in want)

    def test_backend_min_rows_keeps_small_windows_on_numpy(self, monkeypatch):
        rng = np.random.default_rng(11)
        stage = random_stage(rng, n=20)
        an = BigRootsAnalyzer(SPARK_FEATURES, window_exact_quantiles=True,
                              backend="pallas", backend_min_rows=10_000)
        calls = []
        orig = an._eval_gates_batch
        monkeypatch.setattr(
            an, "_eval_gates_batch",
            lambda batch: (calls.append(1), orig(batch))[1],
        )
        w = fill_window(stage, rng, 0.9)
        an.analyze_stage(w)
        assert calls == []  # below threshold → numpy gates, no kernel launch


class TestFleetSweep:
    def test_fleet_matches_per_window_all_backends(self):
        rng = np.random.default_rng(21)
        windows = []
        for k in range(6):
            # deliberately varied sizes → varied straggler counts → padding
            stage = random_stage(rng, n=int(rng.integers(3, 60)))
            windows.append(fill_window(stage, rng, 0.9, stage_id=f"s{k}"))
        an_np, an_jax, an_pl = analyzers()
        want = [causes_sorted(an_np.analyze_stage(w)) for w in windows]
        for an in (an_np, an_jax, an_pl):
            got = an.analyze_fleet(windows)
            assert [sa.stage_id for sa in got] == [w.stage_id for w in windows]
            assert [causes_sorted(sa) for sa in got] == want

    def test_fleet_mixed_sources_fall_back(self):
        """Non-window stages and no-straggler windows inside a sweep take
        the per-stage fallback but keep their slot order."""
        rng = np.random.default_rng(22)
        stage = random_stage(rng, n=30)
        w = fill_window(stage, rng, 0.9, stage_id="win")
        flat = SlidingStageWindow("flat", SPARK_FEATURES)
        for i in range(8):
            flat.add_row(f"t{i}", "n0", 0.0, 1.0, features={"cpu": 0.5})
        frame_stage = random_stage(rng, n=12)
        an_np, _, an_pl = analyzers()
        got = an_pl.analyze_fleet([w, flat, StageRecord("rec", frame_stage.tasks)])
        assert [sa.stage_id for sa in got] == ["win", "flat", "rec"]
        assert got[1].root_causes == []
        want = an_np.analyze_stage(StageRecord("rec", frame_stage.tasks))
        assert found_set(got[2].root_causes) == found_set(want.root_causes)

    def test_fleet_sketch_mode_matches_per_window(self):
        """Default sketch-λq mode: the packed q comes from the same P²
        sketch the per-window path reads, so fleet ≡ per-window holds in
        production mode too (not just exact reference mode)."""
        rng = np.random.default_rng(23)
        windows = [
            fill_window(random_stage(rng, n=int(rng.integers(30, 80))),
                        rng, 0.9, stage_id=f"s{k}")
            for k in range(4)
        ]
        for backend in ("jax", "pallas"):
            an = BigRootsAnalyzer(SPARK_FEATURES, backend=backend,
                                  backend_min_rows=0, kernel_interpret=True)
            ref = BigRootsAnalyzer(SPARK_FEATURES)
            want = [found_set(ref.analyze_stage(w).root_causes)
                    for w in windows]
            got = [found_set(sa.root_causes) for sa in an.analyze_fleet(windows)]
            assert got == want

    def test_column_floor_layout(self):
        from repro.core import FeatureKind

        floor = column_floor(SPARK_FEATURES, 0.2)
        tcols = set(SPARK_FEATURES.cols_of_kind(FeatureKind.TIME).tolist())
        for j in range(len(SPARK_FEATURES)):
            assert floor[j] == (0.2 if j in tcols else -np.inf)

    def test_pack_windows_padding_and_aggregates(self):
        rng = np.random.default_rng(31)

        def with_stragglers(stage_id, n, n_slow):
            w = SlidingStageWindow(stage_id, SPARK_FEATURES, quantile=0.9)
            for i in range(n):
                dur = 30.0 if i < n_slow else float(rng.uniform(8.0, 12.0))
                w.add_row(f"t{i}", f"n{i % 3}", 0.0, dur,
                          features={"cpu": float(rng.random()),
                                    "read_bytes": float(rng.uniform(0, 1e9))})
            return w

        w1 = with_stragglers("a", 40, 4)
        w2 = with_stragglers("b", 6, 1)
        entries = []
        an = BigRootsAnalyzer(SPARK_FEATURES, window_exact_quantiles=True)
        for w in (w1, w2):
            pre = an._window_prelude(w)
            assert isinstance(pre, tuple)  # stragglers guaranteed above
            n, _, s_rows, _, _ = pre
            entries.append((w, s_rows, n, w.v[s_rows],
                            w.quantiles(0.9, exact=True)))
        batch = pack_windows(entries, SPARK_FEATURES, 0.2, row_bucket=8)
        W, R, F = batch.shape
        assert W == 2 and F == len(SPARK_FEATURES)
        # R is bucketed (stable shape across ticks → scratch + jit hits)
        assert R % 8 == 0 and R >= max(batch.counts)
        for i, (w, s_rows, n, V, q) in enumerate(entries):
            c = batch.counts[i]
            assert c == V.shape[0]
            np.testing.assert_array_equal(batch.v[i, :c], V)
            assert (batch.rowmask[i, :c, 0] == 1.0).all()
            assert (batch.rowmask[i, c:, 0] == 0.0).all()
            # padded peer counts are benign (1.0), never zero
            assert (batch.inter_cnt[i, c:, 0] == 1.0).all()
            np.testing.assert_array_equal(batch.vsum[i, 0], w.vsum)

    def test_pack_windows_scratch_reuse_no_stale_state(self):
        """Packing into a reused scratch (the always-on sweep path) must be
        indistinguishable from a fresh pack — stale tails, numok, vsum or
        q from the previous tick may not leak into the gates."""
        rng = np.random.default_rng(77)

        def mk(stage_id, n, n_slow, seed):
            r = np.random.default_rng(seed)
            w = SlidingStageWindow(stage_id, SPARK_FEATURES, quantile=0.9)
            for i in range(n):
                dur = 30.0 if i < n_slow else float(r.uniform(8.0, 12.0))
                w.add_row(f"t{i}", f"n{i % 3}", 0.0, dur,
                          features={"cpu": float(r.random()),
                                    "read_bytes": float(r.uniform(0, 1e9)),
                                    "jvm_gc_time": float(r.uniform(0, dur))})
            return w

        an = BigRootsAnalyzer(SPARK_FEATURES, window_exact_quantiles=True)

        def entries_for(windows):
            out = []
            for w in windows:
                pre = an._window_prelude(w)
                assert isinstance(pre, tuple)
                n, _, s_rows, _, _ = pre
                out.append((w, s_rows, n, w.v[s_rows],
                            w.quantiles(0.9, exact=True)))
            return out

        tick1 = entries_for([mk("a", 50, 8, 1), mk("b", 40, 3, 2)])
        tick2 = entries_for([mk("c", 60, 5, 3), mk("d", 30, 2, 4)])
        scratch = pack_windows(tick1, SPARK_FEATURES, 0.2, row_bucket=8)
        reused = pack_windows(tick2, SPARK_FEATURES, 0.2, scratch=scratch,
                              row_bucket=8)
        fresh = pack_windows(tick2, SPARK_FEATURES, 0.2, row_bucket=8)
        assert reused.shape == fresh.shape
        assert reused.v is scratch.v  # the reuse actually happened
        for name in ("v", "peer_vsum", "inter_cnt", "intra_cnt", "rowmask",
                     "vsum", "q", "numok", "floor", "counts"):
            np.testing.assert_array_equal(
                getattr(reused, name), getattr(fresh, name), err_msg=name
            )
        np.testing.assert_array_equal(
            eval_gates_np(reused, 1.5), eval_gates_np(fresh, 1.5)
        )
