"""Load generator: the telemetry a fleet ships, built from ``--seed``.

One general generator reads a configuration (the fleet) and a traffic mix
(parameters only) and yields, for every diagnosis tick ``k``, the host
payloads that tick ingests.  Two fleet shapes exist:

- ``sync_training``: every host of a synchronous training job reports its
  ranks' rows of one training step per tick (one payload per host per
  step); a stage window holds ``steps_per_stage`` steps.
- ``spark_heartbeat``: executors report their completed tasks on a
  staggered heartbeat; each payload carries that executor's tasks grouped
  by stage, and ``concurrent_stages`` stages run at once.

Every seed gets the same sizes and arrivals; the seed draws the values
(durations, features, which rows straggle).  Rows are columnar blocks::

    {"stage": str, "task_ids": [str], "nodes": [str], "starts": f64[m],
     "ends": f64[m], "locality": i16[m], "features": {name: f64[m]}}

Payload bytes are StepDelta v2, encoded by worker processes that never
import JAX (:class:`Generator`), so encoding never takes a core from the
service's own process and never touches the chip.
"""
from __future__ import annotations

import multiprocessing as mp
import queue
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


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


class SyncTraining:
    """A synchronous training fleet: every host reports every step."""

    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.hosts = int(cfg["hosts"])
        self.ranks = int(cfg["ranks_per_host"])
        self.n = self.hosts * self.ranks
        self.per_stage = int(cfg["steps_per_stage"])
        self.prefill_steps = int(cfg["max_stages"]) * self.per_stage
        self.host_names = [f"h{h:04d}" for h in range(self.hosts)]
        self.nodes = [nm for nm in self.host_names for _ in range(self.ranks)]
        self.boots = [int(x) for x in rng_for(seed, 7).integers(
            1, 1 << 62, self.hosts)]

    def step_block(self, step: int) -> dict:
        """Every rank's row of training step ``step``."""
        t, n = self.traffic, self.n
        rng = rng_for(self.seed, 1, step)
        n_slow = int(round(t["slow_share"] * n))
        n_hot = int(round(t["hot_share"] * n))
        slow = rng.choice(n, size=n_slow, replace=False)
        clip = float(t["normal_log_clip"])
        dur = t["duration_s"] * np.exp(np.clip(
            rng.normal(0.0, t["normal_log_sigma"], n), -clip, clip))
        dur[slow] = t["duration_s"] * rng.uniform(*t["slow_factor"], n_slow)
        feats = {name: _draw(rng, spec, n)
                 for name, spec in t["features"].items()}
        for name, value in t.get("hot", {}).items():
            feats[name][slow[:n_hot]] = value
        start = step * float(self.cfg["step_s"])
        starts = np.full(n, start)
        return _block(f"w{step // self.per_stage}",
                      [f"s{step}/r{r}" for r in range(n)], self.nodes,
                      starts, starts + dur, np.zeros(n, dtype=np.int16),
                      feats)

    def prefill_blocks(self) -> list[dict]:
        """The retained stage windows at the start of the run, one block
        per stage in creation order."""
        out = []
        for s0 in range(0, self.prefill_steps, self.per_stage):
            out.append(concat_blocks([self.step_block(s) for s in
                                      range(s0, s0 + self.per_stage)]))
        return out

    def tick(self, k: int) -> list[tuple[str, int, int, list[dict]]]:
        """Tick ``k``'s payloads: (host, seq, boot, blocks) per host."""
        block = self.step_block(self.prefill_steps + k)
        r = self.ranks
        return [(self.host_names[h], k + 1, self.boots[h],
                 [_take(block, slice(h * r, (h + 1) * r))])
                for h in range(self.hosts)]

    def due_s(self, k: int) -> float | None:
        return None                       # backlogged: ticks back to back


class SparkHeartbeat:
    """A Spark cluster: executors report completed tasks on a heartbeat.

    Completion ``i`` (a global index) ends at ``(i - i0) / rows_per_s``
    seconds after the run's origin; it belongs to slot ``i % C`` of the
    ``C`` concurrent stage slots, which run the fixed stage plan one after
    another, and to executor ``perm[(i // C) % E]``.  Executor ``e``
    heartbeats at ``phase[e] + m * heartbeat_s`` and reports the tasks it
    completed since its previous heartbeat.  Tick ``k`` ingests the
    heartbeats due in ``(k * tick_s, (k + 1) * tick_s]``.  Rows completed
    before each executor's last heartbeat ahead of the origin are the
    pre-filled windows.
    """

    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.E = int(cfg["executors"])
        self.C = int(cfg["concurrent_stages"])
        self.hb = float(cfg["heartbeat_s"])
        self.tick_s = float(cfg["tick_s"])
        self.rate = float(traffic["rows_per_s"])
        plan = np.random.default_rng(int(cfg["plan_seed"]))
        tasks = int(cfg["stage_tasks"])
        g = int(cfg["plan_stages"])
        self.sizes = np.full(g, tasks, dtype=np.int64)
        # Slot c's first stage is c/C of a stage short, so the concurrent
        # stages start and end staggered, not in step.
        self.sizes[:self.C] -= np.arange(self.C) * tasks // self.C
        self.perm = plan.permutation(self.E)
        # Slot c runs plan stages c, c + C, c + 2C, ...
        self.slot_stages = [np.arange(c, g, self.C) for c in range(self.C)]
        self.slot_cum = [np.concatenate([[0], np.cumsum(self.sizes[s])])
                         for s in self.slot_stages]
        # The run starts a heartbeat after max_stages + C stages have been
        # created, so the pre-filled windows hold that many stages.
        created = np.sort(np.concatenate([
            cum[:-1] * self.C + c for c, cum in enumerate(self.slot_cum)]))
        self.i0 = int(created[int(cfg["max_stages"]) + self.C]
                      + self.hb * self.rate)
        self.phase = (np.arange(self.E) + 0.5) * self.hb / self.E
        self.exec_names = [f"e{e:03d}" for e in range(self.E)]
        self.boots = [int(x) for x in rng_for(seed, 7).integers(
            1, 1 << 62, self.E)]
        pick = rng_for(seed, 8).choice(self.E, size=len(
            traffic.get("contended_hosts", [])), replace=False)
        self.contended = {int(e): spec for e, spec in
                          zip(pick, traffic.get("contended_hosts", []))}
        self.defaults = cfg["profile_defaults"]
        self.t_base = 1000.0

    # -- completions -------------------------------------------------------
    def _stage_of(self, idx: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Plan stage and task index of completions ``idx``."""
        slot, j = idx % self.C, idx // self.C
        stage = np.empty(idx.size, dtype=np.int64)
        task = np.empty(idx.size, dtype=np.int64)
        for c in range(self.C):
            m = slot == c
            if not m.any():
                continue
            pos = np.searchsorted(self.slot_cum[c], j[m], side="right") - 1
            if pos.max() >= self.slot_stages[c].size:
                raise ValueError("the run outlasts the stage plan")
            stage[m] = self.slot_stages[c][pos]
            task[m] = j[m] - self.slot_cum[c][pos]
        return stage, task

    def _executor(self, idx: np.ndarray) -> np.ndarray:
        return self.perm[(idx // self.C) % self.E]

    def _time(self, idx: np.ndarray) -> np.ndarray:
        return (idx - self.i0) / self.rate

    def _rows(self, idx: np.ndarray, rng: np.random.Generator) -> dict:
        """Columns of the completions ``idx`` (one array each)."""
        stage, task = self._stage_of(idx)
        execs = self._executor(idx)
        n = idx.size
        cols = {k: np.zeros(n) for k in self.cfg["features"]
                if k != "locality"}
        dur = np.zeros(n)
        loc = np.zeros(n, dtype=np.int16)
        profiles = self.cfg["profiles"]
        pidx = stage % len(profiles)
        for p_i in np.unique(pidx):
            m = np.nonzero(pidx == p_i)[0]
            self._profile_rows(dict(self.defaults, **profiles[p_i]),
                               rng, m, dur, cols, loc, execs)
        return {"stage": stage, "task": task, "execs": execs,
                "ends": self.t_base + self._time(idx), "dur": dur,
                "loc": loc, "cols": cols}

    def _blocks(self, r: dict, sel: np.ndarray) -> list[dict]:
        """Blocks (one per stage, in order of first appearance) of rows
        ``sel`` of the columns ``r``."""
        stage = r["stage"][sel]
        order = np.argsort(stage, kind="stable")
        ranked = stage[order]
        uniq, first = np.unique(stage, return_index=True)
        blocks = []
        for g in uniq[np.argsort(first)]:
            m = sel[order[np.searchsorted(ranked, g, side="left"):
                          np.searchsorted(ranked, g, side="right")]]
            blocks.append(_block(
                f"g{g}", [f"g{g}/t{int(t)}" for t in r["task"][m]],
                [self.exec_names[int(e)] for e in r["execs"][m]],
                r["ends"][m] - r["dur"][m], r["ends"][m], r["loc"][m],
                {k: v[m] for k, v in r["cols"].items()}))
        return blocks

    def _profile_rows(self, p, rng, m, dur, cols, loc, execs) -> None:
        """Task rows of one HiBench profile (the repo's SimCluster task
        model, vectorized), written into ``dur``/``cols``/``loc`` at ``m``."""
        n = m.size
        base = p["base_duration"] * np.exp(rng.normal(0.0, p["duration_noise"], n))
        read = p["read_bytes_mean"] * np.exp(rng.normal(0.0, 0.1, n))
        skew = rng.random(n) < p["read_skew_prob"]
        read[skew] *= p["read_skew_mag"]
        sh_r = p["shuffle_mean"] * np.exp(rng.normal(0.0, 0.1, n))
        sh_w = p["shuffle_mean"] * 0.5 * np.exp(rng.normal(0.0, 0.1, n))
        skew = rng.random(n) < p["shuffle_skew_prob"]
        sh_r[skew] *= p["shuffle_skew_mag"]
        sh_w[skew] *= p["shuffle_skew_mag"] * 0.5
        d = base * ((1.0 - p["bytes_weight"]) + p["bytes_weight"] * 0.5 * (
            read / p["read_bytes_mean"] + sh_r / p["shuffle_mean"]))
        gc = np.where(rng.random(n) < p["gc_heavy_prob"], p["gc_heavy_frac"],
                      p["gc_frac"])
        remote = rng.random(n) < p["remote_prob"]
        local = np.where(remote, 2, np.where(rng.random(n) < 0.1, 1, 0))
        d = (d + np.where(remote, p["remote_delay"], 0.0)) * (1.0 + gc)
        spill = np.where(rng.random(n) < p["spill_prob"], p["spill_bytes"], 0.0)
        cpu = rng.uniform(*p["cpu_self"], n)
        heavy = rng.random(n) < p["cpu_heavy_prob"]
        cpu[heavy] = rng.uniform(0.5, 0.85, int(heavy.sum()))
        d[heavy] *= 1.6
        disk = rng.uniform(*p["disk_self"], n)
        heavy = rng.random(n) < p["io_heavy_prob"]
        disk[heavy] = rng.uniform(0.5, 0.85, int(heavy.sum()))
        d[heavy] *= 1.5
        cap = float(self.cfg["net_cap_bytes_s"])
        net = p["net_self_frac"] * cap * rng.uniform(0.5, 1.5, n)
        util = {"cpu": cpu, "disk": disk, "network": net / cap}
        lo, hi = self.traffic["response_log"]
        sens = self.traffic.get("sensitivity", {})
        ex = execs[m]
        extra = np.zeros(n)
        for e, spec in self.contended.items():
            on = ex == e
            k = int(on.sum())
            if not k:
                continue
            for kind, (l0, l1) in spec.items():
                level = rng.uniform(l0, l1, k)
                util[kind][on] += level
                extra[on] += sens.get(kind, 0.0) * np.exp(
                    rng.normal(lo, hi, k)) * level
        d = d * (1.0 + extra)
        dur[m] = d
        loc[m] = local
        cols["cpu"][m] = util["cpu"]
        cols["disk"][m] = util["disk"]
        cols["network"][m] = util["network"] * cap
        cols["read_bytes"][m] = read
        cols["shuffle_read_bytes"][m] = sh_r
        cols["shuffle_write_bytes"][m] = sh_w
        cols["memory_bytes_spilled"][m] = spill
        cols["disk_bytes_spilled"][m] = spill * 0.5
        cols["jvm_gc_time"][m] = gc * d
        cols["serialize_time"][m] = rng.uniform(0.005, 0.02, n) * d
        cols["deserialize_time"][m] = rng.uniform(0.005, 0.02, n) * d

    # -- what the run ingests ------------------------------------------------
    def prefill_blocks(self) -> list[dict]:
        """Completions reported before the origin, one block per stage in
        creation order.  The service keeps the ``max_stages`` most recent
        of them once the first payload arrives."""
        # Executor e's last heartbeat before the origin is phase[e] - hb.
        last = int(np.floor(self.i0 + (self.phase.max() - self.hb) * self.rate))
        idx = np.arange(0, last + 1)
        cut = self._time(idx) <= self.phase[self._executor(idx)] - self.hb
        idx = idx[cut]
        return self._blocks(self._rows(idx, rng_for(self.seed, 2)),
                            np.arange(idx.size))

    def heartbeats(self, k: int) -> list[tuple[int, int, float]]:
        """(executor, heartbeat number, time) of heartbeats due in tick k,
        in time order."""
        t0, t1 = k * self.tick_s, (k + 1) * self.tick_s
        out = []
        for e in range(self.E):
            m = int(np.floor((t1 - self.phase[e]) / self.hb))
            h = self.phase[e] + m * self.hb
            if t0 < h <= t1:
                out.append((e, m, h))
        out.sort(key=lambda x: x[2])
        return out

    def tick(self, k: int) -> list[tuple[str, int, int, list[dict]]]:
        beats = self.heartbeats(k)
        parts = []
        for e, m, h in beats:
            lo = int(np.floor(self.i0 + (h - self.hb) * self.rate)) + 1
            hi = int(np.floor(self.i0 + h * self.rate))
            idx = np.arange(max(lo, 0), hi + 1)
            t = self._time(idx)
            idx = idx[(t > h - self.hb) & (t <= h)]
            parts.append(idx[self._executor(idx) == e])
        r = self._rows(np.concatenate(parts) if parts else np.zeros(0, int),
                       rng_for(self.seed, 1, k))
        out, at = [], 0
        for (e, m, _h), idx in zip(beats, parts):
            sel = np.arange(at, at + idx.size)
            at += idx.size
            out.append((self.exec_names[e], m + (1 << 20), self.boots[e],
                        self._blocks(r, sel)))
        return out

    def due_s(self, k: int) -> float:
        """Seconds after the origin at which tick k's last payload is due."""
        return (k + 1) * self.tick_s


FLEETS = {"sync_training": SyncTraining, "spark_heartbeat": SparkHeartbeat}


def make_traffic(cfg: dict, traffic: dict, seed: int):
    return FLEETS[cfg["fleet"]](cfg, traffic, seed)


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


def _worker(cfg, traffic, seed, first, step, out, stop) -> None:
    """Build and encode ticks first, first + step, ... into ``out``."""
    sys.path.insert(0, str(ROOT / "src"))
    out.cancel_join_thread()        # exit at stop even with a tick unread
    tr = make_traffic(cfg, traffic, seed)
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
                 workers: int, ahead: int) -> None:
        ctx = mp.get_context("spawn")
        self.n = workers
        self.stop_event = ctx.Event()
        self.queues = [ctx.Queue(maxsize=ahead) for _ in range(workers)]
        self.procs = [ctx.Process(target=_worker, daemon=True, args=(
            cfg, traffic, seed, w, workers, self.queues[w], self.stop_event))
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
