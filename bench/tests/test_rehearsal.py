"""CPU rehearsals of whole runs at a tiny size: the Pallas kernel in
interpret mode, the device check skipped, everything else as on the chip.

Each cell, at the size of its ``tiny/<cell>.json``, must come out
correct; the lower-precision control and each fault the cells can have
planted in the timed path must come out not correct.  A held cell is
rehearsed with its entries added.
"""
import json
import shutil

import pytest

import run
from cells import BY_NAME, ROOT, SEED, TINY, cells, rehearse

CELLS = sorted(p.stem for p in TINY.glob("*.json"))


def test_every_cell_has_a_rehearsal_size():
    assert set(cells()) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(cell, tmp_path):
    res = rehearse(cell, tmp_path)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["info"]["causes_compared"] > 0
    assert res["info"]["traced_in_window"] == 0
    assert set(res["metrics"]) >= {"setup_s", "cpu_ms_per_krow"}
    assert list(res)[-1] == "checks"
    json.dumps(res)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cell, tmp_path):
    res = rehearse(cell, tmp_path, trace=True)
    assert res["correct"], res["checks"]
    assert res["device"]["window_s"] > 0
    spans = {"ingest_ms_per_krow", "sweep_ms_per_krow"} if "megascale" in cell \
        else {"ingest_ms_per_tick", "sweep_ms_per_tick"}
    assert spans <= set(res["metrics"])
    # No TPU plane in a CPU trace: the kernel's metrics stay silent.
    assert not {"gates_roofline", "gates_roofline.open",
                "gates_kernel_us_per_tick",
                "gates_kernel_us_per_tick.open"} & set(res["metrics"])


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, tmp_path):
    res = rehearse(cell, tmp_path, control=True)
    assert not res["correct"]
    c = res["checks"]
    assert c["value_rel_err"]["value"] > c["value_rel_err"]["limit"]


def drop_half(svc):
    """Half of each tick's payloads never reach the windows."""
    ingest, n = svc.agg.ingest, [0]

    def half(payload, **kw):
        n[0] += 1
        return ingest(payload, **kw) if n[0] % 2 else 0
    svc.agg.ingest = half


def stale_tick(svc):
    """The sweep returns without analyzing: state left as it was."""
    svc.agg.stream.step = lambda: []


def flip_gate_bits(svc):
    """The gate kernel's answer altered where it is produced."""
    gates = svc.analyzer._eval_gates_batch

    def altered(batch):
        out = gates(batch).copy()
        out[..., 0] = 1 - (out[..., 0] > 0)
        return out
    svc.analyzer._eval_gates_batch = altered


@pytest.mark.parametrize("fault", [drop_half, stale_tick, flip_gate_bits],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, tmp_path):
    res = rehearse(cell, tmp_path, hooks={fault.__name__: fault})
    assert not res["correct"], res["checks"]


#: The planted fault of the copied reference: it drops every cause of the
#: first tick that has any (its dedup state is kept, so later ticks agree).
DROP_ONE_TICK = '''
        self._attribute(fresh)
        if fresh and not getattr(self, "dropped", False):
            self.dropped = True
            fresh = []
        return fresh, records
'''


@pytest.mark.parametrize("planted", [False, True], ids=["sound", "planted"])
def test_new_cell_needs_only_data(planted, tmp_path):
    """A deployment added as new files plus BENCHMARK.json entries runs
    with no edit to the harness: a configuration that names its own
    fleet file, reference file and service options, a traffic mix and a
    per-layer metric.  The named reference is the one consulted: the
    copy with a fault planted makes the run not correct."""
    for d in BY_NAME:
        shutil.copytree(ROOT / "bench" / d, tmp_path / "bench" / d)
    bench = tmp_path / "bench"
    (bench / "fleets/drill_fleet.py").write_text(
        "from pathlib import Path\n\nimport gen\n\n\n"
        "class Fleet(gen.load_fleet('sync_training', "
        "Path(__file__).parents[2])):\n"
        "    \"\"\"The synchronous fleet under another name.\"\"\"\n")
    ref = (ROOT / "bench/reference.py").read_text()
    if planted:
        sound = "\n        self._attribute(fresh)\n        return fresh, records\n"
        assert ref.count(sound) == 1
        ref = ref.replace(sound, DROP_ONE_TICK)
    (bench / "reference_drill.py").write_text(ref)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/megascale_12k.json").read_text())
    cfg.update(name="small_fleet", hosts=32, steps_per_stage=2, max_stages=2,
               fleet="drill_fleet", reference="reference_drill",
               aggregator_options={"reorder_window": 2})
    (bench / "configs/small_fleet.json").write_text(json.dumps(cfg))
    drill = json.loads((ROOT / "bench/traffic/incident.json").read_text())
    drill.update(slow_share=0.05, hot_share=0.01)
    (bench / "traffic/drill.json").write_text(json.dumps(drill))
    (bench / "metrics/rows_per_tick.py").write_text(
        "def read(run):\n    return run['rows'] / run['ticks']\n")
    spec["configs"].append({"name": "small_fleet", "source": "https://example.org",
                            "file": "bench/configs/small_fleet.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "small_fleet.drill", "config": "small_fleet",
                              "traffic": "drill", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "rows_per_s" == m["name"]:
            m["workloads"].append("small_fleet.drill")
    spec["end_to_end"].append({"name": "rows_per_tick", "unit": "rows",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["small_fleet.drill"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    built = []
    res = run.run_cell("small_fleet.drill", SEED, 1.0, False, interpret=True,
                       root=tmp_path, hooks={"watch": built.append})
    # The option is not the aggregator's default: the run carried it.
    assert [svc.agg.reorder_window for svc in built] == [2]
    assert res["metrics"]["rows_per_tick"]["value"] == 32 * 8
    assert {"rows_per_s", "cpu_ms_per_krow", "setup_s"} <= set(res["metrics"])
    assert res["info"]["causes_compared"] > 0
    if planted:
        assert not res["correct"], res["checks"]
        assert res["checks"]["cause_flips"]["value"] > 0
    else:
        assert res["correct"], res["checks"]


@pytest.mark.parametrize("key,value", [
    ("analyzer_options", {"backend": "numpy"}),
    ("aggregator_options", {"max_stages": 2}),
])
def test_options_may_not_repeat_the_harness(key, value):
    cfg = json.loads((ROOT / "bench/configs/megascale_12k.json").read_text())
    with pytest.raises(run.BenchError, match=key):
        run.Service(dict(cfg, **{key: value}), interpret=True)


def test_options_reach_the_service():
    cfg = json.loads((ROOT / "bench/configs/megascale_12k.json").read_text())
    svc = run.Service(dict(cfg, analyzer_options={"backend_min_rows": 1},
                           aggregator_options={"reorder_window": 3}),
                      interpret=True)
    assert svc.analyzer.backend_min_rows == 1
    assert svc.agg.reorder_window == 3
