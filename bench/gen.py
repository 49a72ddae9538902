"""Load generator: the telemetry a fleet ships, built from ``--seed``.

One general generator reads a configuration (the fleet) and a traffic mix
(parameters only) and yields, for every diagnosis tick ``k``, the host
payloads that tick ingests.  The configuration's ``fleet`` names the shape
of the fleet: the class ``Fleet`` of ``bench/fleets/<fleet>.py``, built as
``Fleet(cfg, traffic, seed)``, with ``prefill_blocks()`` (the retained
windows at the start of the run), ``tick(k)`` (tick k's payloads, as
``(host, seq, boot, blocks)``) and ``due_s(k)`` (when tick k is due after
the run's origin, or None where ticks run back to back).  The helpers
below are shared by the fleets.

Every seed gets the same sizes and arrivals; the seed draws the values
(durations, features, which rows straggle).  Rows are columnar blocks::

    {"stage": str, "task_ids": [str], "nodes": [str], "starts": f64[m],
     "ends": f64[m], "locality": i16[m], "features": {name: f64[m]}}

Payload bytes are StepDelta v2, encoded by worker processes that never
import JAX (:class:`Generator`), so encoding never takes a core from the
service's own process and never touches the chip.
"""
from __future__ import annotations

import importlib.util
import multiprocessing as mp
import queue
import sys
import time
from pathlib import Path

import numpy as np

def rng_for(seed: int, *parts: int) -> np.random.Generator:
    """A generator keyed by the run's seed and the given parts."""
    return np.random.default_rng([int(seed) % (1 << 64)]
                                 + [int(p) for p in parts])


def _draw(rng: np.random.Generator, spec, n: int) -> np.ndarray:
    kind, lo, hi = spec
    if kind != "uniform":
        raise ValueError(f"unknown distribution {kind!r}")
    return rng.uniform(lo, hi, n)


def _block(stage, task_ids, nodes, starts, ends, locality, features) -> dict:
    return {"stage": stage, "task_ids": task_ids, "nodes": nodes,
            "starts": starts, "ends": ends, "locality": locality,
            "features": features}


def _take(block: dict, sl) -> dict:
    """Rows ``sl`` (a slice or an index array) of a block."""
    if isinstance(sl, slice):
        ids, nodes = block["task_ids"][sl], block["nodes"][sl]
    else:
        ids = [block["task_ids"][i] for i in sl]
        nodes = [block["nodes"][i] for i in sl]
    return _block(block["stage"], ids, nodes, block["starts"][sl],
                  block["ends"][sl], block["locality"][sl],
                  {k: v[sl] for k, v in block["features"].items()})


def concat_blocks(blocks: list[dict]) -> dict:
    """One block of the rows of ``blocks`` (all of one stage), in order."""
    feats = blocks[0]["features"].keys()
    return _block(
        blocks[0]["stage"],
        [t for b in blocks for t in b["task_ids"]],
        [n for b in blocks for n in b["nodes"]],
        np.concatenate([b["starts"] for b in blocks]),
        np.concatenate([b["ends"] for b in blocks]),
        np.concatenate([b["locality"] for b in blocks]),
        {k: np.concatenate([b["features"][k] for b in blocks])
         for k in feats})


def load_module(path: Path, name: str):
    """The module in file ``path``, loaded under ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_fleet(fleet: str, root: Path):
    """The ``Fleet`` class of ``<root>/bench/fleets/<fleet>.py``."""
    path = root / "bench" / "fleets" / f"{fleet}.py"
    return load_module(path, f"bench_fleet_{fleet}").Fleet


def make_traffic(cfg: dict, traffic: dict, seed: int, root: Path):
    """The fleet that the configuration names, with this traffic and seed."""
    return load_fleet(cfg["fleet"], root)(cfg, traffic, seed)


def block_rows(payloads) -> int:
    return sum(len(b["task_ids"]) for *_, blocks in payloads for b in blocks)


def encode(host: str, seq: int, boot: int, blocks: list[dict]) -> bytes:
    """StepDelta v2 wire bytes of one host payload."""
    from repro.telemetry.events import StageDelta, StepDelta

    stages = []
    for b in blocks:
        m = len(b["task_ids"])
        stages.append(StageDelta(
            b["stage"], b["task_ids"], b["nodes"], b["starts"], b["ends"],
            b["locality"], b["features"],
            {k: np.ones(m, dtype=bool) for k in b["features"]}))
    return StepDelta(host, seq, stages, boot=boot).to_bytes(version=2)


def _worker(cfg, traffic, seed, root, first, step, out, stop) -> None:
    """Build and encode ticks first, first + step, ... into ``out``."""
    sys.path.insert(0, str(root / "src"))
    out.cancel_join_thread()        # exit at stop even with a tick unread
    tr = make_traffic(cfg, traffic, seed, root)
    k = first
    while not stop.is_set():
        payloads = tr.tick(k)
        item = (k, [encode(*p) for p in payloads], block_rows(payloads))
        while not stop.is_set():
            try:
                out.put(item, timeout=0.2)
                break
            except queue.Full:
                continue
        k += step


class Generator:
    """Worker processes that fill a bounded buffer of encoded ticks ahead
    of need.  :meth:`get` returns tick k's payloads and their row count."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, *,
                 workers: int, ahead: int, root: Path) -> None:
        ctx = mp.get_context("spawn")
        self.n = workers
        self.stop_event = ctx.Event()
        self.queues = [ctx.Queue(maxsize=ahead) for _ in range(workers)]
        self.procs = [ctx.Process(target=_worker, daemon=True, args=(
            cfg, traffic, seed, root, w, workers, self.queues[w],
            self.stop_event))
            for w in range(workers)]
        for p in self.procs:
            p.start()

    def get(self, k: int, timeout: float = 120.0) -> tuple[list[bytes], int]:
        t = time.perf_counter()
        q, proc = self.queues[k % self.n], self.procs[k % self.n]
        while True:
            try:
                got, payloads, rows = q.get(timeout=0.5)
                break
            except queue.Empty:
                if not proc.is_alive():
                    raise RuntimeError(f"generator worker {k % self.n} "
                                       f"exited with {proc.exitcode}")
                if time.perf_counter() - t > timeout:
                    raise
        if got != k:
            raise RuntimeError(f"generator handed tick {got} for tick {k}")
        return payloads, rows

    def ready(self, k: int) -> bool:
        """Whether tick k's payloads are already buffered."""
        return not self.queues[k % self.n].empty()

    def close(self) -> None:
        """Stop and reap the workers.  The queues are not read again: a
        worker that stops mid-put leaves a partial message, and reading it
        would block for ever."""
        self.stop_event.set()
        for p in self.procs:
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)
        for q in self.queues:
            q.close()
