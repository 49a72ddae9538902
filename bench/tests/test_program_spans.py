"""The readers of the program's own spans (``program_spans.py``, and the
seven metrics that split ingest and the sweep, per thousand rows in a
backlogged cell and per tick in an open-loop one): on hand-built runs, on
a recorded trace of a program without spans, and in a traced CPU
rehearsal of each cell, the held one with its entries added."""
import json
import shutil
from pathlib import Path

import pytest

import program_spans
import run
import trace_reduce
from cells import checkout, rehearse

TRACE = Path(__file__).parent / "data" / "gates_small.xplane.pb"
INGEST = ("decode", "merge")
SWEEP = ("prep", "quantiles", "gates_host", "emit", "whatif")
NAMES = [f"{m}_ms_per_krow" for m in INGEST + SWEEP]
TICK_NAMES = [f"{m}_ms_per_tick" for m in INGEST + SWEEP]


def hand_run(rows=2_000, ticks=4):
    """Two payloads and one sweep: times in ns, so 1e6 ns is 1 ms.  The
    sketch spans stand for the P² work a window that keeps a sketch does
    inside the merge and the quantiles."""
    ms = 1_000_000
    return {"rows": rows, "ticks": ticks, "program_spans": {
        "bigroots.decode": [(0, 2 * ms), (10 * ms, 13 * ms)],
        "bigroots.merge": [(2 * ms, 8 * ms), (13 * ms, 17 * ms)],
        "bigroots.sketch": [(3 * ms, 7 * ms), (14 * ms, 15 * ms),
                            (31 * ms, 32 * ms)],
        "bigroots.prep": [(20 * ms, 21 * ms), (24 * ms, 26 * ms)],
        "bigroots.quantiles": [(21 * ms, 24 * ms), (30 * ms, 33 * ms)],
        "bigroots.gates": [(26 * ms, 27 * ms)],
        "bigroots.emit": [(27 * ms, 29 * ms)],
        "bigroots.whatif": [(29 * ms, 30 * ms)],
    }}


@pytest.mark.parametrize("name,want", [
    ("decode_ms_per_krow", 5 / 2),
    # merge: 10 ms less the 5 ms of sketch inside it.
    ("merge_ms_per_krow", 5 / 2),
    ("prep_ms_per_krow", 3 / 2),
    # quantiles: 6 ms less the 1 ms re-anchor inside the second one.
    ("quantiles_ms_per_krow", 5 / 2),
    ("gates_host_ms_per_krow", 1 / 2),
    ("emit_ms_per_krow", 2 / 2),
    ("whatif_ms_per_krow", 1 / 2),
    # Per tick: the same self times over the 4 ticks.
    ("decode_ms_per_tick", 5 / 4),
    ("merge_ms_per_tick", 5 / 4),
    ("prep_ms_per_tick", 3 / 4),
    ("quantiles_ms_per_tick", 5 / 4),
    ("gates_host_ms_per_tick", 1 / 4),
    ("emit_ms_per_tick", 2 / 4),
    ("whatif_ms_per_tick", 1 / 4),
])
def test_reader_on_hand_built_run(name, want):
    assert run.load_reader(name)(hand_run()) == pytest.approx(want)


@pytest.mark.parametrize("name,empty", [("whatif_ms_per_krow", {"rows": 0}),
                                        ("whatif_ms_per_tick", {"ticks": 0})])
def test_reader_without_the_span_or_rows(name, empty):
    spans = hand_run()["program_spans"]
    del spans["bigroots.whatif"]
    assert run.load_reader(name)(hand_run(**empty)) is None
    assert run.load_reader(name)(
        {"rows": 10, "ticks": 1, "program_spans": spans}) is None


def test_self_seconds_leaves_outside_children_alone():
    spans = {"p": [(0, 100), (200, 300)], "c": [(10, 20), (150, 160),
                                                (290, 310)]}
    # Only (10, 20) lies wholly inside a parent.
    assert program_spans.self_seconds(spans, "p", ("c",)) == 190 / 1e9
    assert program_spans.self_seconds(spans, "p") == 200 / 1e9
    assert program_spans.self_seconds(spans, "missing", ("c",)) == 0.0


def place_trace(root: Path) -> dict:
    """The recorded trace where ``run.py`` leaves a traced run's profile
    under checkout ``root``, and its reduction."""
    dest = root / "bench_out" / "trace" / "plugins" / "profile" / "1"
    dest.mkdir(parents=True)
    shutil.copy(TRACE, dest / "host.xplane.pb")
    return trace_reduce.reduce(dest / "host.xplane.pb")


def test_program_without_spans_reports_nothing(tmp_path):
    """A traced run of a program that writes no ``bigroots.*`` spans (the
    recorded trace) gives no spans and no metric, and raises nothing."""
    root = checkout(tmp_path)
    t = place_trace(root)
    assert t["spans"]["bench.tick"]
    assert program_spans.load({"rows": 1_000, "trace": t}, root) == {}
    for name in NAMES + TICK_NAMES:
        assert run.load_reader(name, root)(
            {"rows": 1_000, "ticks": 3, "trace": t}) is None


def test_stale_or_missing_trace_is_not_read(tmp_path):
    t = place_trace(tmp_path)
    assert program_spans.load({"trace": dict(t, window_s=1.5)}, tmp_path) == {}
    assert program_spans.load({"trace": t}, tmp_path / "elsewhere") == {}


def test_traced_rehearsal_reports_the_split(tmp_path):
    res = rehearse("megascale_12k.incident", tmp_path, trace=True)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NAMES) <= set(m)
    assert all(m[n] > 0 for n in NAMES)
    # The program's spans nest inside the benchmark's.
    assert sum(m[f"{x}_ms_per_krow"] for x in INGEST) <= m["ingest_ms_per_krow"]
    assert sum(m[f"{x}_ms_per_krow"] for x in SWEEP) <= m["sweep_ms_per_krow"]
    json.dumps(res)


def test_traced_open_loop_rehearsal_reports_the_sweep_split(tmp_path):
    res = rehearse("hibench_spark_256.steady", tmp_path, trace=True)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(TICK_NAMES) <= set(m)
    assert all(m[n] > 0 for n in TICK_NAMES)
    assert sum(m[f"{x}_ms_per_tick"] for x in INGEST) <= m["ingest_ms_per_tick"]
    assert sum(m[f"{x}_ms_per_tick"] for x in SWEEP) <= m["sweep_ms_per_tick"]
    json.dumps(res)
