"""CPU rehearsals of whole runs at a tiny size: the Pallas kernel in
interpret mode, the device check skipped, everything else as on the chip.

Each cell must come out correct; the lower-precision control and each
fault the cells can have planted in the timed path must come out not
correct.  A held cell (``bench/held/<cell>.json``: its BENCHMARK.json
entries, waiting for the chip) is rehearsed with its entries added.
"""
import json
import shutil
from pathlib import Path

import pytest

import run

TINY = {
    "megascale_12k.incident": {
        "config": {"hosts": 64, "steps_per_stage": 4, "max_stages": 3}},
    "hibench_spark_256.steady": {
        "config": {"executors": 32, "max_stages": 12,
                   "stage_tasks": 200, "plan_stages": 400},
        "traffic": {"rows_per_s": 200.0}},
}
SEED = 2_200_000_017


def with_entries(root: Path, entries: dict, tmp_path: Path) -> Path:
    """A checkout at ``tmp_path`` whose BENCHMARK.json also holds
    ``entries``; its bench/ directories are the real ones."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for key, items in entries.items():
        spec[key] += items
    (tmp_path / "bench").mkdir(parents=True)
    for d in ("configs", "traffic", "metrics"):
        (tmp_path / "bench" / d).symlink_to(root / "bench" / d)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def rehearse(cell, tmp_path, **kw):
    root = Path(run.ROOT)
    held = root / "bench" / "held" / f"{cell}.json"
    if held.exists():
        root = with_entries(root, json.loads(held.read_text()),
                            tmp_path / "root")
    return run.run_cell(cell, kw.pop("seed", SEED), kw.pop("seconds", 1.5),
                        kw.pop("trace", False), interpret=True,
                        overrides=TINY[cell], trace_dir=tmp_path / "trace",
                        root=root, **kw)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_runs_correct(cell, tmp_path):
    res = rehearse(cell, tmp_path)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["info"]["causes_compared"] > 0
    assert res["info"]["traced_in_window"] == 0
    assert set(res["metrics"]) >= {"setup_s", "cpu_ms_per_krow"}
    assert list(res)[-1] == "checks"
    json.dumps(res)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_traced_run_reports_per_layer_metrics(cell, tmp_path):
    res = rehearse(cell, tmp_path, trace=True)
    assert res["correct"], res["checks"]
    assert res["device"]["window_s"] > 0
    spans = {"ingest_ms_per_krow", "sweep_ms_per_krow"} if "megascale" in cell \
        else {"ingest_ms_per_tick", "sweep_ms_per_tick"}
    assert spans <= set(res["metrics"])
    # No TPU plane in a CPU trace: the kernel's metrics stay silent.
    assert "gates_roofline" not in res["metrics"]


@pytest.mark.parametrize("cell", sorted(TINY))
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
@pytest.mark.parametrize("cell", sorted(TINY))
def test_fault_is_not_correct(cell, fault, tmp_path):
    res = rehearse(cell, tmp_path, hooks={fault.__name__: fault})
    assert not res["correct"], res["checks"]


def test_new_cell_needs_only_data(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as
    files plus BENCHMARK.json entries run with no edit to the harness."""
    root = Path(run.ROOT)
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(root / "bench" / d, tmp_path / "bench" / d)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/megascale_12k.json").read_text())
    cfg.update(name="small_fleet", hosts=32, steps_per_stage=2, max_stages=2)
    (tmp_path / "bench/configs/small_fleet.json").write_text(json.dumps(cfg))
    quiet = json.loads((root / "bench/traffic/incident.json").read_text())
    quiet.update(slow_share=0.012, hot_share=0.0, hot={})
    (tmp_path / "bench/traffic/quiet.json").write_text(json.dumps(quiet))
    (tmp_path / "bench/metrics/rows_per_tick.py").write_text(
        "def read(run):\n    return run['rows'] / run['ticks']\n")
    spec["configs"].append({"name": "small_fleet", "source": "https://example.org",
                            "file": "bench/configs/small_fleet.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "small_fleet.quiet", "config": "small_fleet",
                              "traffic": "quiet", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "rows_per_s" == m["name"]:
            m["workloads"].append("small_fleet.quiet")
    spec["end_to_end"].append({"name": "rows_per_tick", "unit": "rows",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["small_fleet.quiet"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    res = run.run_cell("small_fleet.quiet", SEED, 1.0, False, interpret=True,
                       root=tmp_path, trace_dir=tmp_path / "trace")
    assert res["correct"], res["checks"]
    assert res["metrics"]["rows_per_tick"]["value"] == 32 * 8
    assert {"rows_per_s", "cpu_ms_per_krow", "setup_s"} <= set(res["metrics"])
