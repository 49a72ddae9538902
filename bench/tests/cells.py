"""The cells the benchmark's tests rehearse, and the checkouts they run in.

A cell's rehearsal size is its file ``tiny/<cell>.json``: overrides of
its configuration and traffic (``{"config": {...}, "traffic": {...}}``),
small enough for the CPU.  A held cell (``bench/held/<cell>.json``: its
BENCHMARK.json entries, waiting for the chip) runs in a checkout whose
BENCHMARK.json holds its entries too.
"""
import json
from pathlib import Path

import run

ROOT = Path(run.ROOT)
TINY = Path(__file__).parent / "tiny"
HELD = ROOT / "bench" / "held"
#: The directories under ``bench/`` whose files the harness finds by name.
BY_NAME = ("configs", "traffic", "metrics", "fleets")
SEED = 2_200_000_017


def held_entries() -> dict[str, list]:
    """The BENCHMARK.json entries of every held cell, by key."""
    out: dict[str, list] = {}
    for path in sorted(HELD.glob("*.json")):
        for key, items in json.loads(path.read_text()).items():
            out.setdefault(key, []).extend(items)
    return out


def spec_with_held() -> dict:
    """BENCHMARK.json with the held cells' entries added."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, items in held_entries().items():
        spec[key] += items
    return spec


def cells() -> list[str]:
    """Every cell of BENCHMARK.json and of ``bench/held/``."""
    return sorted(w["name"] for w in spec_with_held()["workloads"])


def tiny(cell: str) -> dict:
    return json.loads((TINY / f"{cell}.json").read_text())


def references(spec: dict) -> set[str]:
    """The reference modules that the configurations of ``spec`` name."""
    return {json.loads((ROOT / c["file"]).read_text()).get(
        "reference", "reference") for c in spec["configs"]}


def checkout(tmp_path: Path) -> Path:
    """A checkout at ``tmp_path`` whose BENCHMARK.json also holds the held
    cells' entries; every directory and reference file that the harness
    reads by name links to the real one, and traced runs leave their
    profile under it."""
    spec = spec_with_held()
    (tmp_path / "bench").mkdir(parents=True)
    for d in BY_NAME:
        (tmp_path / "bench" / d).symlink_to(ROOT / "bench" / d)
    for name in references(spec):
        (tmp_path / "bench" / f"{name}.py").symlink_to(
            ROOT / "bench" / f"{name}.py")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def rehearse(cell: str, tmp_path: Path, **kw) -> dict:
    """One run of ``cell`` at its rehearsal size, on the CPU."""
    return run.run_cell(cell, kw.pop("seed", SEED), kw.pop("seconds", 1.5),
                        kw.pop("trace", False), interpret=True,
                        overrides=tiny(cell), root=checkout(tmp_path), **kw)
