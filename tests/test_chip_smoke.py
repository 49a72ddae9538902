"""``chip_smoke.py`` off the chip: the CPU rehearsal of its three tick
paths at a small size (Pallas in interpret mode, asked for explicitly),
and its refusal to report success without a TPU."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_tick_paths_agree(chip_smoke):
    report = chip_smoke.run_smoke(hosts=2, rows_per_host=256, stages=2,
                                  ticks=3, interpret=True)
    assert report["failures"] == []
    assert report["causes_last_tick"] > 0
    # Interpret mode keeps float64: not even a knife-edge flip.
    for path in ("pallas", "jax"):
        assert report["flips"][path] == {"knife_edge": 0, "other": 0,
                                         "gbits": 0}
    assert report["forecast_max_diff"] <= chip_smoke.FORECAST_TOL


def test_refuses_without_tpu(chip_smoke, capsys, monkeypatch, tmp_path):
    # Keep the run's compile cache out of the checkout.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main([]) != 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    result = json.loads(last)
    assert result["ok"] is False
    assert result["device"]["platform"] != "tpu"
