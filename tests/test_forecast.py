"""Predictive straggler forecasting (ISSUE 10): the score-based ROC
primitives, the labeled episode exporter and its golden pins, the
forecast cell's exactness contracts, the recurrent serve path inside the
diagnosis tick, the forecast-off byte-identity pin, and the seeded
held-out value gate (model AUC must beat the paper-idiom per-feature
threshold baseline with nonzero median lead time).

The hypothesis sweep of batched-vs-per-row byte identity lives in
test_forecast_property.py (slow lane); the deterministic equivalents are
here.
"""
from __future__ import annotations

import itertools
import json
import re
from types import SimpleNamespace

import numpy as np
import pytest

from repro.anomaly.scenario import (
    EPISODE_PINS,
    ScenarioEngine,
    build_scenario,
    export_episodes,
    _episode_golden_path,
)
from repro.core import (
    BigRootsAnalyzer,
    Forecaster,
    JAX_FEATURES,
    SlidingStageWindow,
    TaskRecord,
    cause_to_wire,
    evaluate_forecaster,
    lead_time_curve,
    score_auc,
    score_points,
    synthesize_cause,
    train_forecaster,
)
from repro.core.fleet import pack_sequences
from repro.core.forecast import PREDICTED_STRAGGLER, baseline_auc
from repro.core.window import StreamingTraceStore
from repro.ft import (
    DEFAULT_RULES,
    GuardrailConfig,
    PolicyEngine,
    RecordingActuator,
    forecast_rule,
)
from repro.models.forecast_ssd import (
    ForecastConfig,
    forecast_init,
    forecast_score,
    forecast_step,
)
from repro.serve import Diagnosis


# -- satellite 1: score-based ROC edge cases ----------------------------------

class TestScoreRoc:
    def test_empty_inputs_are_degenerate_half(self):
        assert score_auc([], []) == 0.5
        assert score_points([], []) == []

    def test_one_class_labels_are_degenerate_half(self):
        assert score_auc([0.1, 0.9, 0.4], [1, 1, 1]) == 0.5
        assert score_auc([0.1, 0.9, 0.4], [0, 0, 0]) == 0.5

    def test_all_tied_scores_are_half(self):
        # A scorer that cannot separate anything is a coin flip, not 0
        # or 1 -- ties must count half, not resolve by input order.
        assert score_auc([0.5] * 6, [1, 0, 1, 0, 1, 0]) == 0.5

    def test_partial_ties_use_average_ranks(self):
        # 2x2 (pos, neg) pairs: three clean wins plus the tied
        # (0.5, 0.5) pair counting half -> 3.5 / 4.
        got = score_auc([0.9, 0.5, 0.5, 0.1], [1, 1, 0, 0])
        assert got == pytest.approx(3.5 / 4.0)

    def test_hand_computed_five_point_fixture(self):
        # positives at 0.9/0.7/0.6, negatives at 0.8/0.5: of the 6
        # (pos, neg) pairs, 4 are correctly ordered.
        scores = [0.9, 0.8, 0.7, 0.6, 0.5]
        labels = [1, 0, 1, 1, 0]
        assert score_auc(scores, labels) == pytest.approx(4.0 / 6.0)

    def test_length_mismatch_raises(self):
        with pytest.raises(ValueError):
            score_auc([0.1], [1, 0])
        with pytest.raises(ValueError):
            score_points([0.1, 0.2], [1])

    def test_points_sweep_distinct_thresholds_descending(self):
        scores = [0.9, 0.8, 0.8, 0.6, 0.5]
        labels = [1, 0, 1, 1, 0]
        pts = score_points(scores, labels)
        thrs = [p.params[0] for p in pts]
        assert thrs == sorted(set(scores), reverse=True)
        # alarm rule is score >= threshold: the first point alarms only
        # on the top score, the last alarms on everything.
        assert pts[0].tpr == pytest.approx(1.0 / 3.0)
        assert pts[0].fpr == 0.0
        assert pts[-1].tpr == 1.0 and pts[-1].fpr == 1.0

    def test_perfect_and_inverted_scorers(self):
        assert score_auc([0.9, 0.8, 0.2, 0.1], [1, 1, 0, 0]) == 1.0
        assert score_auc([0.1, 0.2, 0.8, 0.9], [1, 1, 0, 0]) == 0.0


# -- satellite 2: episode exporter determinism + golden pins ------------------

class TestEpisodeExport:
    def test_export_is_byte_reproducible(self):
        a = export_episodes("hot_host_cpu")
        b = export_episodes("hot_host_cpu")
        assert a.golden_bytes() == b.golden_bytes()
        np.testing.assert_array_equal(a.x, b.x)
        np.testing.assert_array_equal(a.y, b.y)

    @pytest.mark.parametrize("name", EPISODE_PINS)
    def test_golden_pin_matches(self, name):
        import os

        es = export_episodes(name)
        golden_dir = os.path.join(os.path.dirname(__file__), "golden")
        path = _episode_golden_path(golden_dir, name)
        with open(path, "rb") as f:
            want = f.read()
        assert es.golden_bytes() == want, (
            f"episode export for {name!r} drifted from its golden pin; "
            "if deliberate: python -m repro.anomaly.scenario --episodes "
            "--repin"
        )

    def test_row_conservation(self):
        """Every labeled sequence anchors on a produced trace row, and
        the exporter saw every row the engine produced."""
        es = export_episodes("hot_host_cpu")
        assert es.rows == es.counters["rows_produced"]
        for i in range(len(es.y)):
            assert (es.hosts[i], es.anchors[i]) in es.row_steps

    def test_labels_are_future_verdicts(self):
        """y=1 iff the node is gate-confirmed within (anchor, anchor +
        horizon] -- the label looks forward, never at the anchor row."""
        es = export_episodes("hot_host_cpu")
        assert es.positives > 0
        confirmed = set(es.confirmed)
        for i in range(len(es.y)):
            want = any(
                (es.hosts[i], s) in confirmed
                for s in range(es.anchors[i] + 1,
                               es.anchors[i] + es.horizon + 1)
            )
            assert bool(es.y[i]) == want

    def test_confirmed_excludes_synthesized_causes(self):
        """cascade_dropouts confirms host_dropout causes (synthesized,
        not Eq. 5 gate output) -- those must not leak into labels."""
        es = export_episodes("cascade_dropouts")
        assert es.rows > 0
        assert es.positives == 0


# -- the forecast cell's exactness contracts ----------------------------------

class TestForecastCell:
    def _cfg(self):
        return ForecastConfig(features=len(JAX_FEATURES))

    def test_init_is_seed_deterministic(self):
        cfg = self._cfg()
        a = forecast_init(cfg, seed=7)
        b = forecast_init(cfg, seed=7)
        c = forecast_init(cfg, seed=8)
        assert all(np.array_equal(a[k], b[k]) for k in a)
        assert any(not np.array_equal(a[k], c[k]) for k in a)

    def test_scores_live_in_unit_interval(self):
        cfg = self._cfg()
        params = forecast_init(cfg, seed=0)
        x = np.random.default_rng(0).lognormal(0, 1.0, (32, cfg.length,
                                                        cfg.features))
        s = forecast_score(params, x, xp=np)
        assert ((s > 0.0) & (s < 1.0)).all()

    def test_batched_equals_per_row_numpy(self):
        cfg = self._cfg()
        params = forecast_init(cfg, seed=1)
        rng = np.random.default_rng(2)
        x = rng.lognormal(0, 0.5, (17, cfg.length, cfg.features))
        mask = np.ones((17, cfg.length))
        mask[3, :5] = 0.0
        full = forecast_score(params, x, mask=mask, xp=np)
        for i in range(17):
            one = forecast_score(params, x[i:i + 1], mask=mask[i:i + 1],
                                 xp=np)
            assert full[i] == one[0]

    def test_left_padding_is_exactly_invisible(self):
        """A mask-padded short history scores byte-identically to the
        same rows packed without padding."""
        cfg = self._cfg()
        params = forecast_init(cfg, seed=3)
        rng = np.random.default_rng(4)
        rows = rng.lognormal(0, 0.5, (5, cfg.features))
        short = forecast_score(params, rows[None, :, :], xp=np)
        padded = np.zeros((1, cfg.length, cfg.features))
        padded[0, cfg.length - 5:] = rows
        mask = np.zeros((1, cfg.length))
        mask[0, cfg.length - 5:] = 1.0
        assert forecast_score(params, padded, mask=mask, xp=np)[0] == short[0]

    def test_step_replay_equals_windowed_numpy(self):
        """The serve-side recurrence replayed from h=0 is byte-identical
        to the one-shot windowed score (numpy path)."""
        cfg = self._cfg()
        params = forecast_init(cfg, seed=5)
        rng = np.random.default_rng(6)
        x = rng.lognormal(0, 0.5, (9, cfg.length, cfg.features))
        mask = np.ones((9, cfg.length))
        mask[2, :3] = 0.0
        windowed = forecast_score(params, x, mask=mask, xp=np)
        h = np.zeros((9, cfg.hidden, cfg.state))
        sc = None
        for t in range(cfg.length):
            h, sc = forecast_step(params, x[:, t], h, update=mask[:, t],
                                  xp=np)
        np.testing.assert_array_equal(windowed, sc)

    def test_frozen_step_reemits_identical_bits(self):
        """update=0 folds the step to identity: state bits unchanged and
        the re-emitted score equals the last live one exactly."""
        cfg = self._cfg()
        params = forecast_init(cfg, seed=7)
        rng = np.random.default_rng(8)
        x = rng.lognormal(0, 0.5, (4, cfg.features))
        h0 = rng.normal(0, 0.1, (4, cfg.hidden, cfg.state))
        h1, s1 = forecast_step(params, x, h0, update=np.ones(4), xp=np)
        h2, s2 = forecast_step(params, x, h1, update=np.zeros(4), xp=np)
        np.testing.assert_array_equal(h1, h2)
        np.testing.assert_array_equal(s1, s2)

    def test_jax_and_numpy_agree_to_ulp(self):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        cfg = self._cfg()
        params = forecast_init(cfg, seed=9)
        rng = np.random.default_rng(10)
        x = rng.lognormal(0, 0.5, (13, cfg.length, cfg.features))
        ref = forecast_score(params, x, xp=np)
        with jax.enable_x64(True):
            fn = jax.jit(lambda p, x: forecast_score(p, x, xp=jnp))
            got = np.asarray(fn(
                {k: jnp.asarray(v) for k, v in params.items()},
                jnp.asarray(x)))
        # XLA contracts a*b+c into FMAs per graph: allclose at ~1e-13,
        # not ==.  Per-backend batch invariance is the exact contract.
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-13)


# -- pack_sequences geometry --------------------------------------------------

class TestPackSequences:
    def _window(self, n_nodes=3, steps=6, stage="s0"):
        w = SlidingStageWindow(stage, JAX_FEATURES, max_rows=4096,
                               quantile=0.9)
        rng = np.random.default_rng(11)
        for t in range(steps):
            for n in range(n_nodes):
                w.add_row(f"n{n}/step{t}", f"n{n}", float(t), float(t) + 2.0,
                          features={"cpu": float(rng.random())})
        return w

    def test_pack_shapes_and_anchors(self):
        w = self._window()
        b = pack_sequences([w], JAX_FEATURES, 8, seq_bucket=4)
        assert b.count == 3
        S, L, F = b.shape
        assert L == 8 and F == len(JAX_FEATURES) and S % 4 == 0
        # 6 rows of history -> left-padded to 8 with a 2-step mask hole
        np.testing.assert_array_equal(b.mask[:3, :2], 0.0)
        np.testing.assert_array_equal(b.mask[:3, 2:], 1.0)
        for i in range(3):
            assert b.task_ids[i].endswith("/step5")  # newest row anchors
        # bucket-padding tail is inert
        np.testing.assert_array_equal(b.mask[3:], 0.0)
        np.testing.assert_array_equal(b.x[3:], 0.0)

    def test_pack_length_one_is_newest_row(self):
        w = self._window()
        b = pack_sequences([w], JAX_FEATURES, 1)
        assert b.count == 3
        np.testing.assert_array_equal(b.mask[:3], 1.0)
        for i in range(3):
            assert b.task_ids[i].endswith("/step5")

    def test_empty_windows_pack_empty(self):
        w = SlidingStageWindow("empty", JAX_FEATURES, max_rows=64,
                               quantile=0.9)
        b = pack_sequences([w], JAX_FEATURES, 8)
        assert b.count == 0


# -- the recurrent serve path -------------------------------------------------

def _train_hot_forecaster(**kwargs):
    es = export_episodes("hot_host_cpu")
    kwargs.setdefault("steps", 120)
    return Forecaster.train(es, JAX_FEATURES, seed=0, **kwargs)


@pytest.fixture(scope="module")
def hot_trained():
    """Trained once per module; serve tests clone fresh Forecasters so
    carried recurrence state never leaks between tests."""
    return _train_hot_forecaster(risk_threshold=0.7)


def _clone(fc: Forecaster, **kwargs) -> Forecaster:
    kwargs.setdefault("risk_threshold", fc.risk_threshold)
    kwargs.setdefault("min_history", fc.min_history)
    return Forecaster(fc.params, fc.config, JAX_FEATURES, **kwargs)


def _replay_rows(name, seed):
    """All task rows of a seeded scenario run, grouped by sim step."""
    eng = ScenarioEngine(build_scenario(name, seed=seed))
    eng.run()
    task_re = re.compile(r"^(.+)/step(\d+)$")
    names = JAX_FEATURES.names
    rows = []
    for h in eng.hosts:
        tr = h.telem.trace
        for sid in tr.stage_ids():
            fr = tr.stage(sid)
            for i, tid in enumerate(fr.task_ids):
                m = task_re.match(tid)
                step = int(m.group(2)) if m else 0
                feats = {names[j]: float(fr.raw[i, j])
                         for j in range(len(names))}
                rows.append((step, sid, tid, fr.node_of(i),
                             float(fr.starts[i]), float(fr.ends[i]), feats))
    rows.sort(key=lambda r: (r[0], r[3]))
    return rows


def _replay_alarms(fc, name, seed):
    """Stream a seeded run's rows through fc.step tick by tick."""
    store = StreamingTraceStore(JAX_FEATURES)
    causes = []
    for step, group in itertools.groupby(_replay_rows(name, seed),
                                         key=lambda r: r[0]):
        for _, sid, tid, node, s0, s1, feats in group:
            store.add_task(TaskRecord(task_id=tid, stage_id=sid,
                                      node=node, start=s0, end=s1,
                                      features=feats))
        for c in fc.step([store.window(sid)
                          for sid in sorted(store.stage_ids())]):
            causes.append((step, c))
    return causes


class TestForecasterServe:
    def test_alarms_land_on_injected_host(self, hot_trained):
        """Replay a held-out seeded run of the training scenario through
        the streaming tick: every alarm must name the injected host."""
        es2 = export_episodes("hot_host_cpu", seed=411)
        injected = {h for h, _ in es2.confirmed}
        assert injected == {"h0003"}
        alarms = _replay_alarms(_clone(hot_trained), "hot_host_cpu", 411)
        assert alarms, "forecaster never alarmed on its own scenario"
        assert {c.node for _, c in alarms} == injected
        for _step, c in alarms:
            assert c.value >= hot_trained.risk_threshold
        # the first page lands during the incident, not as a post-mortem
        assert min(s for s, _ in alarms) <= max(s for _, s in es2.confirmed)

    def test_candidate_cause_shape(self, hot_trained):
        alarms = _replay_alarms(_clone(hot_trained), "hot_host_cpu", 411)
        _, c = alarms[0]
        assert c.feature == PREDICTED_STRAGGLER
        assert c.peer_groups == ("forecast",)
        assert 0.0 < c.value < 1.0
        assert "forecast" in c.guidance
        assert c.stage_id and c.task_id

    def test_hold_down_and_frozen_ticks(self, hot_trained):
        """A risky node pages once per hold window; a tick with no new
        telemetry advances nothing."""
        fc = _clone(hot_trained, risk_threshold=0.0, min_history=1,
                    hold_steps=5)
        store = StreamingTraceStore(JAX_FEATURES)
        store.add_task(TaskRecord(task_id="n0/step0", stage_id="s0",
                                  node="n0", start=0.0, end=2.0,
                                  features={"cpu": 1.0}))
        first = fc.step([store.window("s0")])
        assert len(first) == 1  # threshold 0: everything alarms
        seen_before = fc._seen.copy()
        # same window, no new rows: frozen -- no state advance
        again = fc.step([store.window("s0")])
        assert again == []
        np.testing.assert_array_equal(fc._seen, seen_before)
        # new rows within the hold window: still held
        for t in range(1, 4):
            store.add_task(TaskRecord(task_id=f"n0/step{t}", stage_id="s0",
                                      node="n0", start=float(t),
                                      end=float(t) + 2.0,
                                      features={"cpu": 1.0}))
            assert fc.step([store.window("s0")]) == []
        # past the hold: pages again
        out = []
        for t in range(4, 8):
            store.add_task(TaskRecord(task_id=f"n0/step{t}", stage_id="s0",
                                      node="n0", start=float(t),
                                      end=float(t) + 2.0,
                                      features={"cpu": 1.0}))
            out = fc.step([store.window("s0")])
            if out:
                break
        assert out and out[0].node == "n0"

    def test_min_history_defaults_to_window_length(self, hot_trained):
        assert hot_trained.min_history == hot_trained.config.length

    def test_min_history_suppresses_cold_state(self, hot_trained):
        fc = _clone(hot_trained, risk_threshold=0.0)  # min_history = 8
        store = StreamingTraceStore(JAX_FEATURES)
        for t in range(fc.min_history - 1):
            store.add_task(TaskRecord(task_id=f"n0/step{t}", stage_id="s0",
                                      node="n0", start=float(t),
                                      end=float(t) + 2.0,
                                      features={"cpu": 1.0}))
            assert fc.step([store.window("s0")]) == []

    def test_numpy_backend_matches_jax(self, hot_trained):
        a = _clone(hot_trained)
        b = _clone(hot_trained, backend="numpy")
        rng = np.random.default_rng(12)
        rows = rng.lognormal(0, 0.5, (64, a.config.features))
        h = np.zeros((64, a.config.hidden, a.config.state))
        up = np.ones(64)
        ha, sa = a.step_scores(rows, h, up)
        hb, sb = b.step_scores(rows, h, up)
        np.testing.assert_allclose(sa, sb, rtol=0, atol=1e-13)
        np.testing.assert_allclose(ha, hb, rtol=0, atol=1e-13)

    def test_unknown_backend_raises(self):
        cfg = ForecastConfig(features=len(JAX_FEATURES))
        with pytest.raises(ValueError):
            Forecaster(forecast_init(cfg, seed=0), cfg, JAX_FEATURES,
                       backend="tpu-maybe")

    def test_stale_state_eviction(self):
        cfg = ForecastConfig(features=len(JAX_FEATURES))
        fc = Forecaster(forecast_init(cfg, seed=0), cfg, JAX_FEATURES)
        H, N = cfg.hidden, cfg.state
        n = 3000
        full_h = np.arange(n * H * N, dtype=np.float64).reshape(n, H, N)
        fc._index = {(f"s{i}", f"n{i}"): i for i in range(n)}
        fc._h = full_h.copy()
        fc._seen = np.arange(n, dtype=np.int64)
        fc._last_tick = np.zeros(n, dtype=np.int64)
        fc._last_tick[:10] = 200  # recently seen
        fc._anchors = [f"a{i}" for i in range(n)]
        fc._tick = 200
        fc._evict_stale(live=10)
        assert len(fc._index) == 10
        for (stage, _node), idx in fc._index.items():
            i = int(stage[1:])
            assert i < 10
            np.testing.assert_array_equal(fc._h[idx], full_h[i])
            assert fc._seen[idx] == i
            assert fc._anchors[idx] == f"a{i}"

    def test_eviction_never_touches_small_tables(self):
        cfg = ForecastConfig(features=len(JAX_FEATURES))
        fc = Forecaster(forecast_init(cfg, seed=0), cfg, JAX_FEATURES)
        fc._index = {("s0", "n0"): 0}
        fc._h = np.zeros((1, cfg.hidden, cfg.state))
        fc._seen = np.zeros(1, dtype=np.int64)
        fc._last_tick = np.zeros(1, dtype=np.int64)
        fc._anchors = ["a0"]
        fc._tick = 10_000
        fc._evict_stale(live=1)
        assert len(fc._index) == 1  # below the 2*live+1024 trigger


# -- satellite 4: forecast-off byte identity ----------------------------------

def _hot_stage_rows(step, n_rows=24):
    """One diagnosis step's rows: node n0 is contended (cpu) and slow."""
    rng = np.random.default_rng(100 + step)
    rows = []
    for i in range(n_rows):
        node = f"n{i % 6}"
        hot = node == "n0"
        dur = 30.0 if hot else float(rng.uniform(8.0, 12.0))
        rows.append((f"{node}/r{i}/step{step}", node, 0.0, dur, {
            "cpu": 0.95 * dur if hot else float(rng.uniform(0.1, 0.3)) * dur,
            "read_bytes": float(rng.uniform(0.9, 1.1)) * 64e6,
        }))
    return rows


def _drive_local_diagnosis(forecaster, audit_path):
    """Run identical telemetry through a local Diagnosis; return the
    wire bytes of every fresh cause per tick."""
    store = StreamingTraceStore(JAX_FEATURES)
    for tid, node, s0, dur, feats in _hot_stage_rows(0):
        store.add_task(TaskRecord(task_id=tid, stage_id="s0", node=node,
                                  start=s0, end=s0 + dur, features=feats))
    # The local stream binds once to this live window; later add_task
    # calls mutate it in place, which is exactly the serve shape.
    telem = SimpleNamespace(live_window=store.window("s0"),
                            schema=JAX_FEATURES)
    policy = PolicyEngine(DEFAULT_RULES, RecordingActuator(),
                          guardrails=GuardrailConfig(),
                          audit_path=str(audit_path))
    diag = Diagnosis.local(BigRootsAnalyzer(JAX_FEATURES), policy=policy,
                           forecaster=forecaster)
    out = [[json.dumps(cause_to_wire(c), sort_keys=True)
            for c in diag.tick(telem, step_time=1.0)]]
    for step in range(1, 10):
        for tid, node, s0, dur, feats in _hot_stage_rows(step):
            store.add_task(TaskRecord(task_id=tid, stage_id="s0", node=node,
                                      start=s0, end=s0 + dur,
                                      features=feats))
        out.append([json.dumps(cause_to_wire(c), sort_keys=True)
                    for c in diag.tick(telem, step_time=1.0)])
    return out


class TestForecastOffByteIdentity:
    def test_detached_stream_is_identical_and_candidates_append(
            self, tmp_path, hot_trained):
        off = _drive_local_diagnosis(None, tmp_path / "off.jsonl")
        on = _drive_local_diagnosis(
            _clone(hot_trained, min_history=2), tmp_path / "on.jsonl")
        # forecast-off run emits no predicted causes at all
        for tick in off:
            assert all(PREDICTED_STRAGGLER not in b for b in tick)
        # the on-run's confirmed prefix is byte-identical; candidates
        # only ever append after it (dedup state never sees them)
        predicted_total = 0
        for tick_off, tick_on in zip(off, on):
            n = len(tick_off)
            assert tick_on[:n] == tick_off
            assert all(f'"{PREDICTED_STRAGGLER}"' in b
                       for b in tick_on[n:])
            predicted_total += len(tick_on) - n
        assert predicted_total > 0  # the hot node did trip the forecast
        # decision logs byte-identical: DEFAULT_RULES has no forecast
        # rule, so predicted candidates change no decisions
        log_off = (tmp_path / "off.jsonl").read_bytes()
        log_on = (tmp_path / "on.jsonl").read_bytes()
        assert log_off == log_on

    def test_forecast_off_run_is_deterministic(self, tmp_path):
        a = _drive_local_diagnosis(None, tmp_path / "a.jsonl")
        b = _drive_local_diagnosis(None, tmp_path / "b.jsonl")
        assert a == b
        assert (tmp_path / "a.jsonl").read_bytes() == \
            (tmp_path / "b.jsonl").read_bytes()


# -- opt-in policy wiring -----------------------------------------------------

class TestForecastRule:
    def test_not_in_default_rules(self):
        assert all(PREDICTED_STRAGGLER not in r.features
                   for r in DEFAULT_RULES)

    def test_rule_matches_predicted_causes(self):
        rule = forecast_rule()
        assert rule.features == (PREDICTED_STRAGGLER,)
        actuator = RecordingActuator()
        eng = PolicyEngine((*DEFAULT_RULES, rule), actuator,
                           guardrails=GuardrailConfig())
        cause = synthesize_cause(
            task_id="s0/t1", stage_id="s0", node="n0",
            feature=PREDICTED_STRAGGLER, value=0.91,
            guidance="forecast", peer_groups=("forecast",))
        eng.step([cause], step_time=1.0, live_hosts=8)
        acted = [a for a in actuator.applied
                 if a.rule == "speculate_forecast"]
        assert len(acted) == 1
        assert acted[0].target == "s0/t1"  # task scope: act on the task


# -- the seeded value gate ----------------------------------------------------

class TestForecastValue:
    def test_beats_threshold_baseline_with_lead_time(self):
        """The acceptance gate: on held-out mixed-incident episodes the
        model's AUC must beat the best per-feature threshold detector,
        with nonzero median lead time at a usable precision.  Fully
        seeded -- exports, init and training are deterministic."""
        train = [export_episodes("hot_host_cpu", seed=11),
                 export_episodes("hot_host_cpu", seed=211),
                 export_episodes("clock_skew", seed=53),
                 export_episodes("clock_skew", seed=253)]
        held = [export_episodes("hot_host_cpu", seed=411),
                export_episodes("clock_skew", seed=453)]
        params = train_forecaster(train, seed=0, steps=400, lr=0.05)
        rep = evaluate_forecaster(params, held)
        assert rep["positives"] > 0
        assert rep["baseline_auc"] >= 0.5
        assert rep["auc"] > rep["baseline_auc"], (
            f"forecaster (AUC {rep['auc']:.4f}) does not beat the "
            f"per-feature threshold baseline ({rep['baseline_auc']:.4f})"
        )
        lead = lead_time_curve(params, held, thresholds=(0.5,))[0]
        assert lead["median_lead_steps"] > 0.0
        assert lead["precision"] >= 0.5
        assert lead["recall"] > 0.0

    def test_baseline_auc_floor(self):
        es = export_episodes("hot_host_cpu")
        assert baseline_auc(es) >= 0.5
