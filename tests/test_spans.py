"""Spans and counters inside the diagnosis tick (``repro.spans``,
``FleetAggregator.counters``): off by default at the cost of one shared
null context, on the profiler's host plane while a trace is collected,
and counters that agree with what the sweep did."""
import glob
import os
import subprocess
import sys
import textwrap
from contextlib import nullcontext
from pathlib import Path

import numpy as np
import pytest

from repro import spans
from repro.core import BigRootsAnalyzer, JAX_FEATURES
from repro.serve.fleet import FleetAggregator
from repro.telemetry.events import StageDelta, StepDelta

SRC = Path(__file__).resolve().parents[1] / "src"
INGEST_SPANS = ("bigroots.decode", "bigroots.merge", "bigroots.sketch")
SWEEP_SPANS = ("bigroots.prep", "bigroots.quantiles", "bigroots.gates",
               "bigroots.emit", "bigroots.whatif")
ALL_SPANS = {"bigroots.ingest", "bigroots.step", *INGEST_SPANS, *SWEEP_SPANS}


@pytest.fixture(autouse=True)
def follow_profiler():
    """Every test starts and ends with spans following the profiler."""
    spans.enable(None)
    yield
    spans.enable(None)


def block(stage, host, tick, n, rng):
    """``n`` rows of one host in one stage: a fifth of them slow, half of
    those on a hot CPU."""
    dur = rng.lognormal(0.0, 0.1, n)
    slow = rng.random(n) < 0.2
    dur[slow] *= 2.0
    starts = np.full(n, 10.0 * tick)
    cols = {"cpu": rng.uniform(0.1, 0.3, n),
            "read_bytes": rng.uniform(6e7, 7e7, n),
            "gc_time": rng.uniform(0.0, 0.05, n)}
    cols["cpu"][slow & (rng.random(n) < 0.5)] = 0.95
    return StageDelta(stage, [f"{host}/t{tick}-{i}" for i in range(n)],
                      [host] * n, starts, starts + dur,
                      np.zeros(n, np.int16), cols,
                      {k: np.ones(n, bool) for k in cols})


def tick_payloads(tick, rng, hosts=16, ranks=4, stage_every=2):
    """One payload per host; a new stage every ``stage_every`` ticks."""
    stage = f"s{tick // stage_every}"
    return [StepDelta(f"h{h}", tick + 1,
                      [block(stage, f"h{h}", tick, ranks, rng)],
                      boot=1).to_bytes() for h in range(hosts)]


def make_aggregator(**kw):
    analyzer = BigRootsAnalyzer(JAX_FEATURES, backend="jax")
    return FleetAggregator(JAX_FEATURES, analyzer, attribution=True,
                           max_stages=2, **kw)


def test_spans_off_share_one_null_object():
    spans.enable(False)
    a, b = spans.span("bigroots.decode"), spans.span("bigroots.merge", tick=1)
    assert a is b and isinstance(a, nullcontext)
    assert spans.root("bigroots.ingest") is a
    spans.enable(None)
    # Following, with no trace being collected: still the null object.
    assert spans.root("bigroots.step", tick=3) is a
    assert spans.span("bigroots.prep") is a


def test_decode_and_merge_leave_jax_unimported():
    code = textwrap.dedent("""
        import sys
        import numpy as np
        from repro import spans
        from repro.core.features import JAX_FEATURES
        from repro.core.window import StreamingTraceStore
        from repro.telemetry.events import StageDelta, StepDelta

        n = 8
        d = StepDelta("h0", 1, [StageDelta(
            "s0", [f"t{i}" for i in range(n)], ["h0"] * n,
            np.zeros(n), np.ones(n), np.zeros(n, np.int16),
            {"cpu": np.full(n, 0.2)}, {"cpu": np.ones(n, bool)})])
        store = StreamingTraceStore(JAX_FEATURES)
        with spans.root("bigroots.ingest"):
            assert StepDelta.from_bytes(d.to_bytes()).apply_to(store) == n
        assert store.counters()["sketch_rows"] == n
        assert "jax" not in sys.modules, "jax was imported"
    """)
    env = dict(os.environ, PYTHONPATH=str(SRC))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def host_events(trace_dir):
    """``{name: [(start_ns, end_ns, stats), ...]}`` of the ``bigroots.*``
    events on the host plane of the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bigroots"):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return out


def inside(leaf, roots):
    return any(s <= leaf[0] and leaf[1] <= e for s, e, _ in roots)


def test_spans_follow_the_profiler(tmp_path):
    import jax

    agg = make_aggregator()
    rng = np.random.default_rng(5)
    ticks = [tick_payloads(k, rng) for k in range(3)]
    for p in ticks[0]:          # compiles the gates outside the trace
        agg.ingest(p)
    agg.step()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        causes = []
        for payloads in ticks[1:]:
            for p in payloads:
                agg.ingest(p)
            causes += agg.step()
    finally:
        jax.profiler.stop_trace()
    assert causes, "the sweep emitted nothing for what-if to price"
    ev = host_events(tmp_path)
    assert set(ev) == ALL_SPANS
    assert [st["tick"] for *_, st in ev["bigroots.step"]] == [2, 3]
    assert len(ev["bigroots.decode"]) == sum(len(t) for t in ticks[1:])
    for name in INGEST_SPANS:
        for leaf in ev[name]:
            assert inside(leaf, ev["bigroots.ingest"] + ev["bigroots.step"]), name
    for name in SWEEP_SPANS:
        for leaf in ev[name]:
            assert inside(leaf, ev["bigroots.step"]), name
    # The trace has stopped: the next root span turns spans off again.
    assert spans.root("bigroots.ingest") is spans.span("bigroots.decode")


def test_enable_overrides_the_profiler(tmp_path):
    import jax

    spans.enable(True)
    on = spans.root("bigroots.step", tick=1)
    assert isinstance(on, jax.profiler.TraceAnnotation)
    spans.enable(False)
    agg = make_aggregator()
    rng = np.random.default_rng(6)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for p in tick_payloads(0, rng):
            agg.ingest(p)
        agg.step()
    finally:
        jax.profiler.stop_trace()
    assert host_events(tmp_path) == {}


def test_counters_agree_with_the_sweep():
    agg = make_aggregator()
    analyses = []
    fleet = agg.analyzer.analyze_fleet

    def recording(windows):
        out = fleet(windows)
        analyses.append(out)
        return out
    agg.analyzer.analyze_fleet = recording
    rng = np.random.default_rng(7)
    small = seen = 0
    for k in range(6):
        payloads = tick_payloads(k, rng)
        small += 16 * 4
        # A block above p2_batch_limit skips the per-row sketch adds.
        big = block(f"s{k // 2}", "bulk", k, 40, rng)
        payloads.append(StepDelta("bulk", k + 1, [big], boot=1).to_bytes())
        for p in payloads:
            agg.ingest(p)
        agg.step()
        seen += len(agg.stream.seen)
    c = agg.counters()
    assert c["stages_dropped"] == 1           # s0 went; its counts stay
    assert c["sketch_rows"] == small
    assert c["rows_ingested"] == small + 6 * 40
    packed = [[len(sa.straggler_ids) for sa in out if sa.straggler_ids]
              for out in analyses]
    assert c["windows_swept"] == sum(len(out) for out in analyses)
    assert c["stragglers_packed"] == sum(sum(p) for p in packed) > 0
    assert c["gate_launches"] == sum(1 for p in packed if p)
    assert c["gate_rows_launched"] == sum(
        len(p) * max(256, -(-max(p) // 256) * 256) for p in packed if p)
    assert c["seen_walked"] == seen and c["forgotten"] == 0
    assert c["ticks"] == 6 and c["emitted"] > 0
    assert c["recovered_total"] > 0.0


def cause_stream(causes):
    return [(c.task_id, c.stage_id, c.node, c.feature, c.kind, c.value,
             c.peer_groups, c.severity,
             None if c.attribution is None else repr(c.attribution))
            for c in causes]


def sketch_run(exact, keep_sketch, tmp_path):
    """Drive a journaled ``TreeAggregator`` root through small blocks, a
    block above ``p2_batch_limit``, ``max_rows`` retirement, compaction, a
    stage drop and a store merge, then recover a second incarnation from
    its journal and drive that one tick more.  ``keep_sketch`` builds the
    windows with a P² sketch whatever the analyzer reads: the reference
    that windows without one must match."""
    from repro.core.window import StreamingTraceStore
    from repro.serve.fleet import TreeAggregator

    def build(boot):
        analyzer = BigRootsAnalyzer(JAX_FEATURES, backend="numpy",
                                    window_exact_quantiles=exact
                                    and not keep_sketch)
        agg = TreeAggregator(JAX_FEATURES, analyzer, name="root", boot=boot,
                             journal=str(tmp_path / f"{keep_sketch}.journal"),
                             attribution=True, max_stages=2, max_rows=150)
        analyzer.window_exact_quantiles = exact
        return agg

    agg = build(1)
    rng = np.random.default_rng(9)
    causes = []
    for k in range(6):
        payloads = tick_payloads(k, rng)
        big = block(f"s{k // 2}", "bulk", k, 40, rng)
        payloads.append(StepDelta("bulk", k + 1, [big], boot=1).to_bytes())
        for p in payloads:
            agg.ingest(p)
        if k == 4:
            side = StreamingTraceStore(JAX_FEATURES)
            StepDelta("side", 1, [block("s2", "side", k, 24, rng)]).apply_to(
                side)
            assert agg.merge_stores(side) == 24
        causes += agg.step()
    counters = [agg.counters()]
    agg.journal.close()
    agg = build(2)
    assert agg.recovered_rows > 0
    for p in tick_payloads(6, rng):
        agg.ingest(p)
    causes += agg.step()
    counters.append(agg.counters())
    return cause_stream(causes), counters, agg


@pytest.mark.parametrize("exact", [True, False], ids=["exact", "sketch"])
def test_windows_keep_the_sketch_only_where_it_is_read(exact, tmp_path):
    got, counters, agg = sketch_run(exact, False, tmp_path)
    want, want_counters, _ = sketch_run(exact, True, tmp_path)
    assert got and got == want
    assert all((w._sketch is None) == exact for w in agg.store.stages())
    for c, w in zip(counters, want_counters):
        assert c["rows_ingested"] > 0 and c["stages_dropped"] > 0
        assert w["sketch_rows"] > 0 and w["sketch_anchors"] > 0
        assert w["anchor_rows"] > 0
        sketch = {k: c[k] for k in ("sketch_rows", "sketch_anchors",
                                    "anchor_rows")}
        if exact:
            assert sketch == dict.fromkeys(sketch, 0)
        else:
            assert sketch == {k: w[k] for k in sketch}
        assert ({k: v for k, v in c.items() if k not in sketch}
                == {k: v for k, v in w.items() if k not in sketch})
