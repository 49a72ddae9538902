"""``sync_training``: every host of a synchronous training job reports its
ranks' rows of one training step per tick (one payload per host per
step); a stage window holds ``steps_per_stage`` steps."""
from __future__ import annotations

import numpy as np

from gen import _block, _draw, _take, concat_blocks, rng_for


class Fleet:
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
