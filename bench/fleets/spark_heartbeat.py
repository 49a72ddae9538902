"""``spark_heartbeat``: executors report their completed tasks on a
staggered heartbeat; each payload carries that executor's tasks grouped
by stage, and ``concurrent_stages`` stages run at once."""
from __future__ import annotations

import numpy as np

from gen import _block, rng_for


class Fleet:
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
