"""Unit tests for the streaming substrate: P² sketches, sliding-window
retirement/compaction semantics, the incremental analyzer path, and the
timeline query cursor.

Randomized streaming-vs-batch *equivalence* lives in
``test_frame_equivalence.py`` (``TestStreamingReplay``); this module pins
the window's own contracts.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import (
    BigRootsAnalyzer,
    MIN_SKETCH_SAMPLES,
    P2ColumnSketch,
    P2Quantile,
    RootCauseStream,
    SPARK_FEATURES,
    SlidingStageWindow,
    StageFrame,
    StageRecord,
    StreamingTraceStore,
    TaskRecord,
    found_set,
)
from repro.core.sketch import exact_quantile, exact_quantiles
from repro.telemetry import ResourceTimeline


def _mk_task(i, node="n0", start=0.0, end=1.0, locality=0, **features):
    return TaskRecord(f"t{i}", "s", node, start, end, locality=locality,
                      features={k: float(v) for k, v in features.items()})


class TestP2Quantile:
    def test_tracks_exact_quantile_within_tolerance(self):
        rng = np.random.default_rng(0)
        for name, data in [
            ("uniform", rng.random(4000)),
            ("lognormal", rng.lognormal(0.0, 1.0, 4000)),
            ("normal", rng.normal(10.0, 3.0, 4000)),
        ]:
            for q in (0.5, 0.9, 0.95):
                sk = P2Quantile(q)
                for x in data:
                    sk.add(float(x))
                exact = float(np.quantile(data, q))
                rel = abs(sk.value() - exact) / (abs(exact) + 1e-12)
                assert rel < 0.05, (name, q, rel)

    def test_exact_below_min_samples(self):
        """Satellite regression: below MIN_SKETCH_SAMPLES the sketch must
        answer bit-for-bit like np.quantile (tiny stages keep seed-identical
        λq gates)."""
        rng = np.random.default_rng(1)
        for n in range(1, MIN_SKETCH_SAMPLES):
            for q in (0.5, 0.8, 0.9, 0.95):
                data = rng.random(n)
                sk = P2Quantile(q)
                for x in data:
                    sk.add(float(x))
                assert sk.value() == float(np.quantile(data, q)), (n, q)

    def test_rejects_degenerate_quantile(self):
        with pytest.raises(ValueError):
            P2Quantile(0.0)
        with pytest.raises(ValueError):
            P2Quantile(1.0)

    def test_empty_sketch_is_nan(self):
        assert np.isnan(P2Quantile(0.9).value())


class TestP2ColumnSketch:
    def test_columns_match_independent_scalars(self):
        rng = np.random.default_rng(2)
        data = rng.random((2000, 4)) * np.array([1.0, 10.0, 100.0, 1000.0])
        cs = P2ColumnSketch(0.9, 4)
        for row in data:
            cs.add(row)
        for j in range(4):
            sk = P2Quantile(0.9)
            for x in data[:, j]:
                sk.add(float(x))
            assert cs.values()[j] == pytest.approx(sk.value())

    def test_reset_from_anchors_exactly_then_keeps_tracking(self):
        rng = np.random.default_rng(3)
        data = rng.lognormal(0.0, 1.0, (3000, 3))
        cs = P2ColumnSketch(0.9, 3)
        cs.reset_from(data[:500])
        np.testing.assert_allclose(cs.values(),
                                   np.quantile(data[:500], 0.9, axis=0))
        for row in data[500:]:
            cs.add(row)
        exact = np.quantile(data, 0.9, axis=0)
        assert (np.abs(cs.values() - exact) / exact < 0.05).all()

    def test_reset_from_small_n_positions_stay_within_n(self):
        """Marker positions after a small-n re-anchor must stay in [1, n]
        (a rank beyond n claims order statistics that don't exist and
        permanently biases later estimates)."""
        cs = P2ColumnSketch(0.9, 2)
        cs.reset_from(np.arange(10.0).reshape(5, 2))
        assert cs._pos[0, 0] == 1.0 and cs._pos[4, 0] == 5.0
        assert (np.diff(cs._pos[:, 0]) >= 1.0).all()
        # and streaming onward from the anchor tracks the true quantile
        rng = np.random.default_rng(12)
        data = rng.random((3000, 2))
        for row in data:
            cs.add(row)
        exact = np.quantile(np.vstack([np.arange(10.0).reshape(5, 2), data]),
                            0.9, axis=0)
        assert (np.abs(cs.values() - exact) / exact < 0.05).all()

    def test_exact_quantile_helpers_bit_equal_numpy(self):
        rng = np.random.default_rng(4)
        v = rng.random((999, 7))
        for q in (0.1, 0.5, 0.9, 0.937):
            assert (exact_quantile(v, q) == np.quantile(v, q, axis=0)).all()
        qs = np.array([0.0, 0.45, 0.9, 0.95, 1.0])
        assert (exact_quantiles(v, qs) == np.quantile(v, qs, axis=0)).all()


class TestWindowRetirement:
    def test_straddling_rows_stay_live(self):
        """A task that started before the watermark but is still running
        (end > watermark) must stay in the window; only tasks that finished
        at or before the watermark retire."""
        w = SlidingStageWindow("s", SPARK_FEATURES, span=10.0)
        w.add_row("old", "n0", 0.0, 5.0)        # ends long before
        w.add_row("straddle", "n0", 2.0, 21.0)  # starts early, still running
        w.add_row("new", "n0", 20.0, 25.0)
        w.advance(25.0)                          # watermark = 15.0
        live = {w.task_id(int(i)) for i in w.live_index()}
        assert live == {"straddle", "new"}
        assert w.retired_total == 1

    def test_late_arrival_behind_watermark_is_dropped(self):
        w = SlidingStageWindow("s", SPARK_FEATURES, span=10.0)
        w.add_row("a", "n0", 0.0, 30.0)
        w.advance(30.0)                          # watermark = 20.0
        assert not w.add_row("late", "n0", 1.0, 5.0)
        assert w.late_drops == 1
        assert w.live_count == 1

    def test_out_of_order_arrivals_retire_by_end_time(self):
        """Arrival order ≠ time order: retirement must still retire exactly
        the rows whose end is at or behind the watermark."""
        w = SlidingStageWindow("s", SPARK_FEATURES, span=5.0)
        w.add_row("c", "n0", 20.0, 22.0)
        w.add_row("a", "n0", 0.0, 18.0)   # out-of-order, retires first
        w.add_row("b", "n0", 10.0, 21.0)
        w.advance(25.0)                   # watermark = 20.0
        live = {w.task_id(int(i)) for i in w.live_index()}
        assert live == {"b", "c"}
        # Aggregates must match a recompute over survivors.
        np.testing.assert_allclose(w.vsum, w.live_v().sum(axis=0), atol=1e-12)

    def test_max_rows_cap_retires_oldest_and_sets_watermark(self):
        w = SlidingStageWindow("s", SPARK_FEATURES, max_rows=3)
        for i in range(5):
            w.add_row(f"t{i}", "n0", float(i), float(i) + 1.0)
        assert w.live_count == 3
        live = {w.task_id(int(i)) for i in w.live_index()}
        assert live == {"t2", "t3", "t4"}
        # The cap implies a watermark: re-adding an already-retired-age row
        # must be refused, not silently re-admitted.
        assert not w.add_row("zombie", "n0", 0.0, 1.0)

    def test_max_rows_tied_ends_retire_as_a_cohort(self):
        """Tied end timestamps at the cap boundary must retire together:
        no live row may violate end > watermark, and which rows survive is
        never an arbitrary tie-break (the window may dip below max_rows)."""
        w = SlidingStageWindow("s", SPARK_FEATURES, max_rows=2)
        for i in range(3):
            w.add_row(f"t{i}", "n0", 0.0, 5.0)   # all tied at end=5.0
        assert w.watermark == 5.0
        idx = w.live_index()
        assert (w.ends[idx] > w.watermark).all()  # invariant holds exactly
        assert w.live_count == 0                  # whole cohort retired
        w.add_row("t3", "n0", 0.0, 6.0)
        assert w.live_count == 1

    def test_add_rows_routes_unknown_features_to_extras(self):
        """Bulk ingest must accept non-schema feature columns the same way
        add_row does (kept per-row as extras, not a KeyError)."""
        w = SlidingStageWindow("s", SPARK_FEATURES)
        w.add_rows(["a", "b"], ["n0", "n1"], np.zeros(2), np.ones(2),
                   feature_columns={"cpu": np.array([0.1, 0.2]),
                                    "loss": np.array([1.5, 2.5])})
        tasks = {t.task_id: t for t in w.tasks}
        assert tasks["a"].features == {"cpu": 0.1, "loss": 1.5}
        assert tasks["b"].features == {"cpu": 0.2, "loss": 2.5}

    def test_window_unbounded_without_span_or_cap(self):
        w = SlidingStageWindow("s", SPARK_FEATURES)
        for i in range(100):
            w.add_row(f"t{i}", "n0", 0.0, float(i + 1))
        assert w.advance() == 0
        assert w.live_count == 100


class TestWindowAggregates:
    def _fill(self, w, n, seed=0, nodes=4):
        rng = np.random.default_rng(seed)
        for i in range(n):
            start = float(rng.uniform(0, 50))
            w.add_row(f"t{i}", f"n{i % nodes}", start,
                      start + float(rng.uniform(0.5, 10)),
                      int(rng.choice([0, 1, 2])),
                      {"cpu": float(rng.random()),
                       "read_bytes": float(rng.uniform(0, 1e9)),
                       "jvm_gc_time": float(rng.uniform(0, 5))})
        return rng

    def test_aggregates_match_recompute_through_churn(self):
        w = SlidingStageWindow("s", SPARK_FEATURES, span=20.0)
        self._fill(w, 300, seed=5)
        w.advance()
        idx = w.live_index()
        v = w.v[idx]
        np.testing.assert_allclose(w.vsum, v.sum(axis=0), atol=1e-9)
        np.testing.assert_allclose(w.vsumsq, (v * v).sum(axis=0), rtol=1e-9)
        assert w.locality_sum == pytest.approx(w.locality[idx].sum())
        # per-node sums
        for code in range(len(w._node_names)):
            rows = idx[w.node_codes[idx] == code]
            np.testing.assert_allclose(w.node_vsums[code],
                                       w.v[rows].sum(axis=0), atol=1e-9)
            assert w.node_counts[code] == len(rows)

    def test_compaction_bounds_capacity_and_resets_exactly(self):
        w = SlidingStageWindow("s", SPARK_FEATURES, max_rows=64)
        self._fill(w, 4000, seed=6)
        assert w.live_count == 64
        assert w.compactions > 0
        assert w._starts.shape[0] <= 512   # capacity stays O(live), not O(total)
        idx = w.live_index()
        np.testing.assert_allclose(w.vsum, w.v[idx].sum(axis=0), atol=1e-9)

    def test_column_stats_from_running_sums(self):
        w = SlidingStageWindow("s", SPARK_FEATURES)
        self._fill(w, 200, seed=7)
        mean, var = w.column_stats()
        v = w.live_v()
        np.testing.assert_allclose(mean, v.mean(axis=0), atol=1e-9)
        np.testing.assert_allclose(var, v.var(axis=0), rtol=1e-6, atol=1e-9)

    def test_seal_matches_from_tasks_ingest(self):
        tasks = [
            _mk_task(0, "n1", 0.0, 4.0, cpu=0.5, weird=1.0),
            _mk_task(1, "n0", 1.0, 2.0, locality=2, read_bytes=100.0),
            _mk_task(2, "n0", 0.5, 3.0, jvm_gc_time=0.25),
        ]
        w = SlidingStageWindow("s", SPARK_FEATURES)
        for t in tasks:
            w.add_row(t.task_id, t.node, t.start, t.end, t.locality, t.features)
        sealed = w.seal()
        assert sealed.tasks == StageFrame.from_tasks("s", tasks, SPARK_FEATURES).tasks
        assert w.tasks == tasks


class TestTinyStageSketchFallback:
    def test_tiny_stage_identical_to_batch_in_sketch_mode(self):
        """The satellite fix: with fewer than MIN_SKETCH_SAMPLES rows the
        λq gate must fall back to exact np.quantile even in sketch mode,
        so tiny stages produce batch-identical root causes."""
        for n in range(1, MIN_SKETCH_SAMPLES):
            rng = np.random.default_rng(100 + n)
            tasks = []
            for i in range(n):
                dur = float(rng.uniform(0.5, 10.0)) * (4.0 if i == 0 else 1.0)
                tasks.append(_mk_task(i, f"n{i % 2}", 0.0, dur,
                                      cpu=rng.random(),
                                      read_bytes=rng.uniform(0, 1e9)))
            stage = StageRecord("s", tasks)
            w = SlidingStageWindow("s", SPARK_FEATURES)
            for t in tasks:
                w.add_row(t.task_id, t.node, t.start, t.end, t.locality,
                          t.features)
            an = BigRootsAnalyzer(SPARK_FEATURES)  # sketch mode (default)
            assert not an.window_exact_quantiles
            got = found_set(an.analyze_stage(w).root_causes)
            want = found_set(an.analyze_stage(stage).root_causes)
            assert got == want, f"n={n}"

    def test_retirement_back_below_min_samples_stays_exact(self):
        w = SlidingStageWindow("s", SPARK_FEATURES, max_rows=3)
        rng = np.random.default_rng(8)
        for i in range(50):
            w.add_row(f"t{i}", "n0", float(i), float(i) + rng.uniform(0.5, 2))
        assert w.live_count == 3 < MIN_SKETCH_SAMPLES
        np.testing.assert_array_equal(
            w.quantiles(0.9), exact_quantile(w.live_v(), 0.9)
        )


class TestSketchlessWindow:
    def test_reads_exact_and_keeps_no_sketch(self):
        """``quantile=None``: single rows, small and large blocks, ``max_rows``
        retirement, compaction and a merge all leave the sketch counters at
        0, and a read with ``exact=False`` is the exact quantile."""
        rng = np.random.default_rng(11)
        w = SlidingStageWindow("s", SPARK_FEATURES, max_rows=200,
                               quantile=None)
        for i in range(40):
            w.add_row(f"t{i}", f"n{i % 3}", 0.0, rng.uniform(0.5, 2.0),
                      features={"cpu": rng.random()})
        for b, m in enumerate([8, 8, 64, 8, 300]):
            starts = np.full(m, 1.0 + b)
            w.add_rows([f"b{b}-{i}" for i in range(m)],
                       [f"n{i % 3}" for i in range(m)], starts,
                       starts + rng.uniform(0.5, 2.0, m),
                       feature_columns={"cpu": rng.random(m)})
        other = SlidingStageWindow("s", SPARK_FEATURES)
        for i in range(30):
            other.add_row(f"o{i}", "n9", 9.0, 9.0 + rng.uniform(0.5, 2.0))
        assert w.merge(other) == 30
        assert w.retired_total > 0 and w.compactions > 0
        assert (w.sketch_rows, w.sketch_anchors, w.anchor_rows) == (0, 0, 0)
        for q in (0.5, 0.9):
            np.testing.assert_array_equal(
                w.quantiles(q), exact_quantile(w.live_v(), q))
        with pytest.raises(ValueError):
            w.quantiles()


class TestStreamingTraceStore:
    def test_routes_stages_and_analyzes_incrementally(self):
        store = StreamingTraceStore(SPARK_FEATURES, max_rows=100)
        rng = np.random.default_rng(9)
        for i in range(60):
            store.add_row(f"t{i}", f"stage{i % 3}", f"n{i % 4}",
                          0.0, float(rng.uniform(0.5, 10)),
                          features={"cpu": float(rng.random())})
        assert store.stage_ids() == ["stage0", "stage1", "stage2"]
        assert store.num_tasks == 60
        analyses = BigRootsAnalyzer(SPARK_FEATURES).analyze(store)
        assert [sa.stage_id for sa in analyses] == store.stage_ids()
        assert sum(sa.num_tasks for sa in analyses) == 60

    def test_dump_jsonl_round_trips_live_rows(self, tmp_path):
        from repro.core import Trace

        store = StreamingTraceStore(SPARK_FEATURES)
        t = _mk_task(0, "n0", 1.0, 5.0, cpu=0.0, weird_counter=42.0)
        store.add_task(t)
        p = str(tmp_path / "live.jsonl")
        store.dump_jsonl(p)
        assert Trace.load_jsonl(p).stage("s").tasks == [t]

    def test_root_cause_stream_emits_once(self):
        w = SlidingStageWindow("s", SPARK_FEATURES)
        for i in range(12):
            w.add_row(f"t{i}", f"n{i % 3}", 0.0, 1.0,
                      features={"read_bytes": 100.0})
        w.add_row("slow", "n0", 0.0, 10.0, features={"read_bytes": 5000.0})
        stream = RootCauseStream(BigRootsAnalyzer(SPARK_FEATURES), w)
        first = stream.step()
        assert ("slow", "read_bytes") in {c.key for c in first}
        assert stream.step() == []          # emit-once while hot
        assert stream.emitted == len(first)
        st = stream.state(("slow", "read_bytes"))
        assert st.confirmations == 2 and st.emits == 1 and st.severity == 1


def _cause(task="t0", feature="read_bytes"):
    from repro.core import FeatureKind, RootCause

    return RootCause(task_id=task, stage_id="s", node="n0", feature=feature,
                     kind=FeatureKind.NUMERICAL, value=2.0,
                     peer_groups=("inter",))


class _Scripted:
    """Stub analyzer: hands RootCauseStream a scripted per-step cause list
    (the stream's dedup/decay bookkeeping is what's under test, not the
    analyzer)."""

    def __init__(self, script):
        self.script = script  # step (0-based) -> list[RootCause]
        self.calls = 0

    def analyze_stage(self, source):
        causes = self.script(self.calls)
        self.calls += 1
        return StageAnalysis("s", 1, [], list(causes), 1.0)


from repro.core import StageAnalysis  # noqa: E402


class TestRootCauseStreamDecay:
    def test_reemits_with_escalated_severity_after_decay(self):
        confirm_at = {0, 1, 10, 11}  # hot at 0-1, clean 2-9, back at 10
        an = _Scripted(lambda i: [_cause()] if i in confirm_at else [])
        stream = RootCauseStream(an, object(), decay_steps=4)
        assert [c.severity for c in stream.step()] == [1]   # step 1: fresh
        assert stream.step() == []                          # step 2: dedup
        for _ in range(8):                                  # steps 3-10 clean
            assert stream.step() == []
        out = stream.step()                                 # step 11: re-confirm
        assert [c.severity for c in out] == [2]             # escalated re-emit
        assert stream.step() == []                          # step 12: hot again
        st = stream.state(("t0", "read_bytes"))
        assert st.confirmations == 4 and st.emits == 2 and st.severity == 2
        assert stream.reemitted == 1

    def test_forget_drops_state_and_resets_severity(self):
        confirm_at = {0, 50}
        an = _Scripted(lambda i: [_cause()] if i in confirm_at else [])
        stream = RootCauseStream(an, object(), decay_steps=2, forget_steps=10)
        assert len(stream.step()) == 1
        for _ in range(40):
            stream.step()
        assert stream.state(("t0", "read_bytes")) is None   # forgotten
        assert stream.forgotten == 1
        for _ in range(9):
            stream.step()
        out = stream.step()                                 # step 51: back
        assert [c.severity for c in out] == [1]             # fresh, not escalated
        assert len(stream.seen) == 1

    def test_decay_none_is_legacy_unbounded_emit_once(self):
        an = _Scripted(lambda i: [_cause(task=f"t{i}"), _cause()])
        stream = RootCauseStream(an, object(), decay_steps=None)
        for _ in range(50):
            stream.step()
        assert len(stream.seen) == 50           # every distinct key kept forever
        assert stream.emitted == 50             # each key emitted exactly once
        assert stream.reemitted == 0

    def test_soak_10k_steps_bounded_with_reemergence(self):
        """Acceptance: a 10k-step always-on loop with churning causes holds
        ``seen`` bounded while a re-confirmed cause re-emits after decay."""
        def script(i):
            causes = [_cause(task=f"t{i % 300}")]     # churn: 300 rotating keys
            if i in (5, 6000):                        # one long-gap recidivist
                causes.append(_cause(task="recidivist"))
            return causes

        an = _Scripted(script)
        stream = RootCauseStream(an, object(), decay_steps=64)  # forget = 512
        high_water = 0
        reemits = []
        for _ in range(10_000):
            for c in stream.step():
                if c.task_id == "recidivist":
                    reemits.append(c)
            high_water = max(high_water, len(stream.seen))
        # Bounded by the churn alphabet + stragglers, not by 10k steps of
        # history (the legacy set would exceed 300 + 10k/300-ish immediately).
        assert high_water <= 301 + 1
        assert len(stream.seen) <= 301
        assert stream.forgotten > 0
        # The recidivist emitted at step 6 (fresh) and re-emitted escalated
        # at step 6001 — its state decayed but had not yet been forgotten?
        # No: 6001 - 6 > forget horizon, so it was forgotten and comes back
        # fresh at severity 1.
        assert [c.severity for c in reemits] == [1, 1]

    def test_reemergence_inside_forget_horizon_escalates(self):
        gaps = {0, 100, 200}
        an = _Scripted(lambda i: [_cause()] if i in gaps else [])
        stream = RootCauseStream(an, object(), decay_steps=16, forget_steps=500)
        sev = []
        for _ in range(201):
            sev += [c.severity for c in stream.step()]
        assert sev == [1, 2, 3]


class TestTimelineCursor:
    def _random_tl(self, rng, n_series=4, n=500):
        tl = ResourceTimeline()
        for s in range(n_series):
            ts = rng.uniform(0, 1000, n)
            for t in ts:
                tl.record(f"n{s % 2}", ["cpu", "disk"][s % 2], float(t),
                          float(rng.random()))
        return tl

    def test_matches_plain_window_means_on_monotone_queries(self):
        rng = np.random.default_rng(10)
        tl = self._random_tl(rng)
        cur = tl.cursor()
        t = 0.0
        for _ in range(50):
            t += float(rng.uniform(0, 30))
            nodes = ["n0", "n1", "n0", "missing"]
            metrics = ["cpu", "disk", "disk", "cpu"]
            t0s = np.array([t - 3, t - 1, t, t])
            t1s = t0s + 2.0
            got = cur.window_means(nodes, metrics, t0s, t1s)
            want = tl.window_means(nodes, metrics, t0s, t1s)
            np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
            ok = ~np.isnan(want)
            np.testing.assert_allclose(got[ok], want[ok])

    def test_exact_after_backward_jump_and_resort(self):
        """Going backward in time and out-of-order appends (which re-sort
        the series) must both fall back to full searches — answers stay
        exact, never stale."""
        rng = np.random.default_rng(11)
        tl = ResourceTimeline()
        for t in range(200):
            tl.record("n", "cpu", float(t), float(rng.random()))
        cur = tl.cursor()
        cur.window_means(["n"], ["cpu"], np.array([150.0]), np.array([160.0]))
        got = cur.window_means(["n"], ["cpu"], np.array([5.0]), np.array([15.0]))
        assert got[0] == pytest.approx(
            tl.window_mean("n", "cpu", 5.0, 15.0))
        # out-of-order bulk merge → re-sort → sort_gen bump → hint dropped
        tl.record_many("n", "cpu", [(0.5, 1.0), (120.5, 1.0), (60.5, 1.0)])
        got = cur.window_means(["n"], ["cpu"], np.array([0.0]), np.array([1.0]))
        assert got[0] == pytest.approx(tl.window_mean("n", "cpu", 0.0, 1.0))

    def test_scalar_window_mean_contract(self):
        tl = ResourceTimeline()
        tl.record("n", "cpu", 1.0, 0.4)
        cur = tl.cursor()
        assert cur.window_mean("n", "cpu", 0.0, 2.0) == pytest.approx(0.4)
        assert cur.window_mean("n", "cpu", 5.0, 6.0) is None
        assert cur.window_mean("ghost", "cpu", 0.0, 2.0) is None
