"""Plain reference of the fleet diagnosis tick, and the comparison that
decides ``correct``.

The reference imports nothing of the program.  It replays the rows the
generator built (never the program's decoded rows) through a
straightforward rendering of the same semantics, written from the paper
and the service's documented behaviour:

- ingest: a payload's stage blocks append to per-stage windows, except
  blocks of stages already pruned; after each payload the oldest-created
  windows beyond ``max_stages`` are pruned (their ids remembered, up to
  ``8 * max_stages``);
- a tick analyzes the windows whose rows changed since the last tick:
  stragglers are rows longer than ``straggler * median``; Eq. 5 gates on
  gate-space values (TIME columns divided by the row's duration) against
  the exact λq quantile (the configurations ask the service for exact
  quantiles) and the inter-/intra-node peer means; the TIME
  floor and the NUMERICAL mean > 0 guard; Eq. 7 locality for DISCRETE
  columns (Eq. 6 edge detection has no timelines here and keeps all);
- emission dedups by (task, feature): a key confirmed again within
  ``decay_steps`` ticks is suppressed, one clean for longer re-emits at a
  severity one higher (at most 8), one clean for ``8 * decay_steps`` is
  forgotten;
- what-if attribution: each fresh cause's straggler row is rebased to its
  peer-mean duration, and the recovered makespan (max end minus the
  counterfactual) is split evenly among that row's fresh causes.

One tolerance is part of the service's contract, not of this check: the
gate kernel runs in float32, so an element within float32 rounding of a
threshold it is compared against (λq, a peer mean, the TIME floor) may
flip (the knife-edge rule, ``KNIFE_EPS``).  A key that flipped there is
left out of later comparisons (its dedup state differs from then on), and
so is the attribution of any row with a flipped key in that tick.
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as np

#: Float32 knife edge: four float32 epsilons of the operands' scale plus
#: the smallest normal float32 (TPUs flush denormals).
KNIFE_EPS = 4.0 * float(np.finfo(np.float32).eps)
TINY = float(np.finfo(np.float32).tiny)
MAX_SEVERITY = 8
GROUPS = {0: (), 1: ("inter",), 2: ("intra",), 3: ("inter", "intra")}

#: Limits of the numbers compared; PERF.md gives the readings behind each.
LIMITS = {
    "rows_lost": 0,
    "live_rows_diff": 0,
    "cause_flips": 0,
    "value_rel_err": 1e-9,
    "recovery_err_s": 1e-7,
}


def _bf16(x):
    """Round to bfloat16 (round to nearest even) and back to float32."""
    x = np.asarray(x, dtype=np.float32)
    b = x.view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    out = b.astype(np.uint32).view(np.float32)
    return np.where(np.isfinite(x), out, x)


class Window:
    """One stage's rows, append-only (the configurations retire none).
    Columns grow as blocks arrive; a node's code is its first-seen order."""

    def __init__(self, stage: str, uid: int, names: list[str],
                 time_cols: np.ndarray) -> None:
        self.stage, self.uid, self.names = stage, uid, names
        self.time_cols = time_cols
        self.added = 0
        self.node_code: dict[str, int] = {}
        self.node_names: list[str] = []
        self.task_ids: list[str] = []
        self._parts: list[dict] = []
        self._cols: dict | None = None

    def append(self, b: dict) -> int:
        m = len(b["task_ids"])
        starts, ends = b["starts"], b["ends"]
        durs = ends - starts
        raw = np.zeros((m, len(self.names)))
        for j, name in enumerate(self.names):
            if name == "locality":
                raw[:, j] = b["locality"]
            elif name in b["features"]:
                raw[:, j] = b["features"][name]
        v = raw
        v[:, self.time_cols] /= np.maximum(durs, 1e-12)[:, None]
        code = self.node_code
        codes = np.empty(m, dtype=np.int64)
        for i, nd in enumerate(b["nodes"]):
            c = code.get(nd)
            if c is None:
                c = code[nd] = len(self.node_names)
                self.node_names.append(nd)
            codes[i] = c
        self.task_ids.extend(b["task_ids"])
        self._parts.append({"starts": starts, "ends": ends, "durs": durs,
                            "loc": np.asarray(b["locality"], np.float64),
                            "v": v, "codes": codes})
        self.added += m
        self._cols = None
        return m

    def cols(self) -> dict:
        """The window's columns (gate-space values ``v``: TIME columns
        divided by the row's duration)."""
        if self._cols is None:
            if len(self._parts) > 1:
                self._parts = [{k: np.concatenate([p[k] for p in self._parts])
                                for k in self._parts[0]}]
            self._cols = dict(self._parts[0], task_ids=self.task_ids,
                              nodes=self.node_names)
        return self._cols


class Reference:
    """The diagnosis service's semantics, replayed on the generator's rows.

    ``precision="float64"`` is the reference; ``"control"`` evaluates the
    Eq. 5 gates in bfloat16 and reports values and attributions in
    float32 (one precision below what the configuration states), the
    control that the comparison must fail.
    """

    def __init__(self, cfg: dict, precision: str = "float64") -> None:
        self.names = list(cfg["features"])
        kinds = [cfg["features"][n] for n in self.names]
        self.num = np.array([k == "numerical" for k in kinds])
        self.time = np.array([k == "time" for k in kinds])
        self.disc = np.array([k == "discrete" for k in kinds])
        th = cfg["thresholds"]
        self.lq, self.lp = float(th["quantile"]), float(th["peer_mean"])
        self.floor_v, self.ls = float(th["time_floor"]), float(th["straggler"])
        self.max_stages = int(cfg["max_stages"])
        self.decay = int(cfg["decay_steps"])
        self.forget = 8 * self.decay
        self.precision = precision
        self.windows: OrderedDict[str, Window] = OrderedDict()
        self.pruned: OrderedDict[str, None] = OrderedDict()
        self.uids = 0
        self.stamps: dict[str, tuple[int, int]] = {}
        self.seen: dict[tuple[str, str], list[int]] = {}
        self.steps = 0
        self.rows_accepted = 0

    # -- ingest --------------------------------------------------------------
    def _window(self, stage: str) -> Window:
        w = self.windows.get(stage)
        if w is None:
            w = self.windows[stage] = Window(stage, self.uids, self.names,
                                             self.time)
            self.uids += 1
        return w

    def prefill(self, blocks: list[dict]) -> None:
        for b in blocks:
            self._window(b["stage"]).append(b)

    def ingest(self, blocks: list[dict]) -> None:
        """One accepted host payload."""
        for b in blocks:
            if b["stage"] in self.pruned:
                continue
            self.rows_accepted += self._window(b["stage"]).append(b)
        excess = len(self.windows) - self.max_stages
        for _ in range(max(excess, 0)):
            stage, _w = self.windows.popitem(last=False)
            self.pruned[stage] = None
        while len(self.pruned) > 8 * self.max_stages:
            self.pruned.popitem(last=False)

    @property
    def live_rows(self) -> int:
        return sum(w.added for w in self.windows.values())

    # -- one tick ------------------------------------------------------------
    def _changed(self) -> list[Window]:
        """The windows whose rows changed since the last tick."""
        stamps = {s: (w.uid, w.added) for s, w in self.windows.items()}
        changed = [w for s, w in self.windows.items()
                   if self.stamps.get(s) != stamps[s]]
        self.stamps = stamps
        return changed

    def straggler_counts(self) -> list[int]:
        """A tick's change detection and straggler count alone: the number
        of stragglers of each changed window that has any (the windows a
        sweep packs into its gate launch)."""
        counts = []
        for w in self._changed():
            durs = w.cols()["durs"]
            n = int((durs > self.ls * float(np.median(durs))).sum()) \
                if durs.size else 0
            if n:
                counts.append(n)
        return counts

    def step(self) -> tuple[list[dict], list[dict]]:
        """Analyze the changed windows; return (fresh causes, per-window
        records used to judge differences)."""
        records = [r for r in (self._analyze(w) for w in self._changed()) if r]
        self.steps += 1
        step = self.steps
        fresh = []
        names, seen = self.names, self.seen
        for rec in records:
            ids = rec["ids"]
            for x, (i, j) in enumerate(zip(rec["ci"], rec["cj"])):
                key = (ids[i], names[j])
                st = seen.get(key)
                if st is None:
                    seen[key] = [step, 1]
                    fresh.append(self._cause(rec, x, 1))
                    continue
                dormant = step - st[0] > self.decay
                st[0] = step
                if dormant:
                    st[1] = min(st[1] + 1, MAX_SEVERITY)
                    fresh.append(self._cause(rec, x, st[1]))
        for key in [k for k, st in self.seen.items()
                    if step - st[0] > self.forget]:
            del self.seen[key]
        self._attribute(fresh)
        return fresh, records

    def _analyze(self, w: Window) -> dict | None:
        c = w.cols()
        n = c["durs"].size
        if n == 0:
            return None
        durs = c["durs"]
        median = float(np.median(durs))
        s = np.nonzero(durs > self.ls * median)[0]
        if s.size == 0:
            return None
        v = c["v"]
        V = v[s]
        codes = c["codes"]
        F = len(self.names)
        cnt = np.bincount(codes).astype(np.float64)
        nsum = np.stack([np.bincount(codes, weights=v[:, j],
                                     minlength=cnt.size) for j in range(F)], 1)
        vsum = v.sum(axis=0)
        q = np.quantile(v, self.lq, axis=0)
        cnt_i = cnt[codes[s]][:, None]
        pv = nsum[codes[s]]
        inter_cnt, intra_cnt = n - cnt_i, cnt_i - 1.0
        floor = np.where(self.time, self.floor_v, -np.inf)
        means = np.where(self.num, vsum / n, 1.0)
        numok = ~self.num | (vsum / n > 0)
        with np.errstate(invalid="ignore", divide="ignore"):
            if self.precision == "control":
                r = _bf16
                Vb = r(V)
                inter_t = r(r(r(r(vsum) - r(pv)) / r(inter_cnt)) * r(self.lp))
                intra_t = r(r(r(r(pv) - Vb) / r(intra_cnt)) * r(self.lp))
                gi = (Vb > inter_t) & (inter_cnt > 0)
                ga = (Vb > intra_t) & (intra_cnt > 0)
                above_q = Vb > r(q)
                above_floor = Vb > r(floor)
            else:
                inter_t = (vsum - pv) / inter_cnt * self.lp
                intra_t = (pv - V) / intra_cnt * self.lp
                gi = (V > inter_t) & (inter_cnt > 0)
                ga = (V > intra_t) & (intra_cnt > 0)
                above_q = V > q
                above_floor = V > floor
            # The service re-applies the TIME floor in float64 after the
            # kernel, so a gate must pass both.
            fired = (gi | ga) & numok & above_floor & (V > floor) & above_q
            # Knife edges of the float32 kernel (peer means, TIME floor).
            aV = np.abs(V)
            ok_i = np.where(inter_cnt > 0, inter_cnt, np.nan)
            ok_a = np.where(intra_cnt > 0, intra_cnt, np.nan)
            t_i = self.lp * (vsum - pv) / ok_i
            t_a = self.lp * (pv - V) / ok_a
            s_i = aV + self.lp * (np.abs(vsum) + np.abs(pv)) / ok_i
            s_a = aV + self.lp * (np.abs(pv) + aV) / ok_a

            def near(t, scale):
                return np.isfinite(t) & (np.abs(V - t) <= KNIFE_EPS * scale + TINY)

            knife_peer = near(t_i, s_i) | near(t_a, s_a)
            knife = (knife_peer
                     | near(np.broadcast_to(q, V.shape), aV + np.abs(q))
                     | near(np.broadcast_to(floor, V.shape), aV + np.abs(floor)))
        loc_s = c["loc"][s]
        if self.disc.any():
            vote = (c["loc"].sum() - loc_s.sum()) < (n - s.size) / 2.0
            fired[:, self.disc] = ((loc_s == 2) & vote)[:, None]
            knife[:, self.disc] = False
        gbits = gi.astype(np.int8) + 2 * ga.astype(np.int8)
        ci, cj = np.nonzero(fired)
        vals = (V[ci, cj] / means[cj] if self.precision != "control" else
                V[ci, cj].astype(np.float32) / means[cj].astype(np.float32))
        return {"stage": w.stage, "ids": [c["task_ids"][i] for i in s],
                "s": s, "ci": ci.tolist(), "cj": cj.tolist(),
                "vals": vals, "gbits": gbits[ci, cj], "nodes": c["nodes"],
                "codes": codes, "soft": knife, "knife_peer": knife_peer,
                "stragglers": int(s.size)}

    def _cause(self, rec: dict, x: int, severity: int) -> dict:
        """The x-th fired element of an analysis record, as a cause."""
        i, j = rec["ci"][x], rec["cj"][x]
        row = int(rec["s"][i])
        return {"stage": rec["stage"], "task_id": rec["ids"][i],
                "node": rec["nodes"][rec["codes"][row]],
                "feature": self.names[j], "value": float(rec["vals"][x]),
                "groups": (("stage",) if self.disc[j]
                           else GROUPS[int(rec["gbits"][x])]),
                "row": row, "severity": severity}

    def _attribute(self, fresh: list[dict]) -> None:
        """What-if recovered seconds of each fresh cause (in place)."""
        dt = np.float32 if self.precision == "control" else np.float64
        by_stage: dict[str, list[dict]] = {}
        for c in fresh:
            by_stage.setdefault(c["stage"], []).append(c)
        for stage, cs in by_stage.items():
            col = self.windows[stage].cols()
            starts, ends = col["starts"].astype(dt), col["ends"].astype(dt)
            durs = ends - starts if dt is np.float32 else col["durs"]
            codes, n = col["codes"], durs.size
            median = float(np.median(durs))
            smask = durs > self.ls * median
            nsum = np.bincount(codes, weights=durs).astype(dt)
            ncnt = np.bincount(codes).astype(dt)
            total = dt(durs.sum())
            mean = dt(total / n)
            cnt_i = ncnt[codes]
            with np.errstate(invalid="ignore", divide="ignore"):
                inter = np.where(n - cnt_i > 0,
                                 (total - nsum[codes]) / (n - cnt_i), mean)
                intra = np.where(cnt_i - 1 > 0,
                                 (nsum[codes] - durs) / (cnt_i - 1), mean)
            rebased = ends.copy()
            per_row: dict[int, list[dict]] = {}
            for c in cs:
                c["recovery"] = 0.0
                row = c["row"]
                if not smask[row]:
                    continue
                if "inter" in c["groups"]:
                    peer = inter[row]
                elif "intra" in c["groups"]:
                    peer = intra[row]
                else:
                    peer = mean
                target = min(durs[row], max(peer, dt(0.0)))
                rebased[row] = min(rebased[row], starts[row] + target)
                per_row.setdefault(row, []).append(c)
            order = np.sort(ends)
            top1, top2 = order[-1], order[-2] if n > 1 else -np.inf
            tied = int((ends == top1).sum()) > 1
            for row, rcs in per_row.items():
                excl = top2 if (ends[row] == top1 and not tied) else top1
                rec = max(top1 - max(excl, rebased[row]), dt(0.0))
                for c in rcs:
                    c["recovery"] = float(rec / len(rcs))


def _key(c) -> tuple[str, str]:
    return (c["task_id"], c["feature"])


def compare(got_ticks: list[list[dict]], want_ticks: list[list[dict]],
            records: list[list[dict]], names: list[str]) -> dict:
    """Judge the causes each tick returned against the reference's.

    ``got_ticks[t]`` are the service's fresh causes of tick t (dicts with
    stage, task_id, feature, groups, value, severity, recovery);
    ``want_ticks`` and ``records`` are the reference's.  Returns the counts
    and errors that :data:`LIMITS` bounds, and what was left out."""
    col = {n: j for j, n in enumerate(names)}
    tainted: set[tuple[str, str]] = set()
    flips = exempt = compared = failed_ticks = 0
    value_err = recovery_err = 0.0
    examples: list[str] = []
    for t, (got, want, recs) in enumerate(zip(got_ticks, want_ticks, records)):
        where = {(r["stage"], tid): (r, i)
                 for r in recs for i, tid in enumerate(r["ids"])}
        g = {_key(c): c for c in got}
        w = {_key(c): c for c in want}
        bad_rows: set[tuple[str, str]] = set()
        tick_flips = 0

        def differ(key, c, what, arr="soft"):
            """A difference on ``key``: exempt where the reference marks
            the element soft (``arr``), else a flip."""
            nonlocal exempt, tick_flips
            bad_rows.add((c["stage"], key[0]))
            hit = where.get((c["stage"], key[0]))
            if (arr and hit is not None and key[1] in col
                    and hit[0][arr][hit[1], col[key[1]]]):
                exempt += 1
                tainted.add(key)
                return
            tick_flips += 1
            if len(examples) < 5:
                examples.append(f"tick {t}: {key[0]} {key[1]}: {what}")

        for key in sorted(g.keys() ^ w.keys()):
            if key not in tainted:
                differ(key, g.get(key) or w[key],
                       "returned" if key in g else "missing")
        for key in g.keys() & w.keys():
            if key in tainted:
                continue
            a, b = g[key], w[key]
            if tuple(a["groups"]) != tuple(b["groups"]):
                differ(key, b, f"peer groups {a['groups']} != {b['groups']}",
                       arr="knife_peer")
            elif a["severity"] != b["severity"]:
                differ(key, b, f"severity {a['severity']} != {b['severity']}",
                       arr=None)
        for key in g.keys() & w.keys():
            if key in tainted:
                continue
            a, b = g[key], w[key]
            compared += 1
            value_err = max(value_err, abs(a["value"] - b["value"])
                            / max(abs(b["value"]), 1e-300))
            if (b["stage"], key[0]) not in bad_rows:
                recovery_err = max(recovery_err,
                                   abs(a["recovery"] - b["recovery"]))
        flips += tick_flips
        failed_ticks += tick_flips > 0
    return {"cause_flips": flips, "value_rel_err": value_err,
            "recovery_err_s": recovery_err, "exempt": exempt,
            "compared": compared, "failed_ticks": failed_ticks,
            "examples": examples}
