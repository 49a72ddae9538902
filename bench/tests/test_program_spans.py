"""The readers of the program's own spans (``program_spans.py`` and the
eight ``*_ms_per_krow`` metrics that split ingest and the sweep): on
hand-built runs, on a recorded trace of a program without spans, and in
a traced CPU rehearsal of ``megascale_12k.incident``."""
import json
import shutil
from pathlib import Path

import pytest

import program_spans
import run
import trace_reduce

ROOT = Path(run.ROOT)
TRACE = Path(__file__).parent / "data" / "gates_small.xplane.pb"
INGEST = ("decode", "merge", "sketch")
SWEEP = ("prep", "quantiles", "gates_host", "emit", "whatif")
NAMES = [f"{m}_ms_per_krow" for m in INGEST + SWEEP]


def hand_run(rows=2_000):
    """Two payloads and one sweep: times in ns, so 1e6 ns is 1 ms."""
    ms = 1_000_000
    return {"rows": rows, "program_spans": {
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
    ("sketch_ms_per_krow", 6 / 2),
    ("prep_ms_per_krow", 3 / 2),
    # quantiles: 6 ms less the 1 ms re-anchor inside the second one.
    ("quantiles_ms_per_krow", 5 / 2),
    ("gates_host_ms_per_krow", 1 / 2),
    ("emit_ms_per_krow", 2 / 2),
    ("whatif_ms_per_krow", 1 / 2),
])
def test_reader_on_hand_built_run(name, want):
    assert run.load_reader(name)(hand_run()) == pytest.approx(want)


def test_reader_without_the_span_or_rows():
    spans = hand_run()["program_spans"]
    del spans["bigroots.whatif"]
    assert run.load_reader("whatif_ms_per_krow")(hand_run(rows=0)) is None
    assert run.load_reader("whatif_ms_per_krow")(
        {"rows": 10, "program_spans": spans}) is None


def test_self_seconds_leaves_outside_children_alone():
    spans = {"p": [(0, 100), (200, 300)], "c": [(10, 20), (150, 160),
                                                (290, 310)]}
    # Only (10, 20) lies wholly inside a parent.
    assert program_spans.self_seconds(spans, "p", ("c",)) == 190 / 1e9
    assert program_spans.self_seconds(spans, "p") == 200 / 1e9
    assert program_spans.self_seconds(spans, "missing", ("c",)) == 0.0


def checkout(tmp_path: Path) -> Path:
    """A checkout at ``tmp_path`` with the real BENCHMARK.json and bench
    data and readers, whose traced runs leave their profile under it."""
    (tmp_path / "bench").mkdir(parents=True)
    for d in ("configs", "traffic", "metrics"):
        (tmp_path / "bench" / d).symlink_to(ROOT / "bench" / d)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


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
    for name in NAMES:
        assert run.load_reader(name, root)({"rows": 1_000, "trace": t}) is None


def test_stale_or_missing_trace_is_not_read(tmp_path):
    t = place_trace(tmp_path)
    assert program_spans.load({"trace": dict(t, window_s=1.5)}, tmp_path) == {}
    assert program_spans.load({"trace": t}, tmp_path / "elsewhere") == {}


def test_traced_rehearsal_reports_the_split(tmp_path):
    root = checkout(tmp_path)
    res = run.run_cell(
        "megascale_12k.incident", 2_200_000_017, 1.5, True, interpret=True,
        overrides={"config": {"hosts": 64, "steps_per_stage": 4,
                              "max_stages": 3}},
        root=root)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NAMES) <= set(m)
    assert all(m[n] > 0 for n in NAMES)
    # The program's spans nest inside the benchmark's.
    assert sum(m[f"{x}_ms_per_krow"] for x in INGEST) <= m["ingest_ms_per_krow"]
    assert sum(m[f"{x}_ms_per_krow"] for x in SWEEP) <= m["sweep_ms_per_krow"]
    json.dumps(res)
