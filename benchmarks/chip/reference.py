"""Plain reference for the trial engine: one trial, one seed, in Python.

It imports nothing of the program.  From a configuration (latency tables
of each layer and of each feasible layer variant) and a traffic mix
(rates, release processes, deadlines, horizon, faults) it derives the
offline plan itself — Algorithm 1's virtual budgets and the variant
choice — generates the seed's release stream, and plays the event loop
out one event at a time, the way the paper's simulator does.  Its result
holds every field of the program's ``SimResult.fingerprint()``.

Semantics covered are those of the batch engine: open-loop periodic,
Poisson and MMPP releases (with thinning), static budgets, the FCFS,
EDF, DREAM and Terastal schedulers (all three backfill guards and both
ablations), and capability faults (down, throttle, permanent,
intermittent) under the ``restart`` policy, with or without budget
re-tightening, and layer DAGs.

A model entry may carry ``preds``, one predecessor list per node (node
``i`` is layer ``i``).  Without it, or where it is the linear chain, the
chain path runs.  A DAG plan distributes the deadline over the critical
path (Algorithm 1 at the current levels: each node's earliest completion
``ecl``; feasible when the sink's ``ecl`` fits; otherwise the largest
gap among the tightenable nodes on a critical path is tightened).  A
request then has one ready entry per unblocked node, all sharing one
run record: pending predecessor counts, the applied variants and a
dropped flag.  The sink's finish completes it, the first hopeless entry
drops it once, and its siblings are swept uncounted.  Faults with a DAG
plan raise :class:`DagFaultsUnsupported`: no cell needs them, and
re-tightening over a graph is semantics of its own.

``dtype`` sets the float type of every time and latency.  ``np.float64``
is the configuration's precision; ``np.float32`` is the lower-precision
control, which has to come out as not correct.
"""

from __future__ import annotations

import heapq
import itertools
import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

LEVEL_ATOL = 1e-12
GAMMAS = (2, 3)
INTERACTION = 1.1
FAULT_SALT = 0x5EED_FA17


class DagFaultsUnsupported(ValueError):
    """Faults in a trial with a DAG plan: not covered by the reference."""


# ------------------------------------------------------------ specs ----


def parse_spec(spec: str) -> Tuple[str, Dict[str, object]]:
    """``"name"`` or ``"name(k=v, ...)"`` with bool/int/float/str literals."""
    m = re.fullmatch(r"\s*([A-Za-z_][\w.-]*)\s*(?:\((.*)\))?\s*", spec)
    if not m:
        raise ValueError(f"malformed spec {spec!r}")
    kw: Dict[str, object] = {}
    for part in (m.group(2) or "").split(","):
        if not part.strip():
            continue
        k, v = part.split("=", 1)
        v = v.strip()
        if v.lower() in ("true", "false"):
            kw[k.strip()] = v.lower() == "true"
            continue
        for cast in (int, float, str):
            try:
                kw[k.strip()] = cast(v)
                break
            except ValueError:
                continue
    return m.group(1), kw


# --------------------------------------------------------- releases ----


def _exp_run(rng, scale: float, t0: float, limit: float, prob: float, out: list) -> None:
    """Exponential gaps from ``t0`` while below ``limit``; one thinning
    draw after each candidate when ``prob < 1``."""
    t = t0 + rng.exponential(scale)
    while t < limit:
        if prob >= 1.0 or rng.random() < prob:
            out.append(t)
        t += rng.exponential(scale)


def release_times(arrival: str, fps: float, prob: float, duration: float, rng) -> List[float]:
    """Release times of one task over ``[0, duration)``, drawing from the
    trial's shared generator."""
    kind, kw = parse_spec(arrival)
    period = 1.0 / fps
    out: List[float] = []
    if kind == "periodic":
        jitter = float(kw.get("jitter", 0.0))
        for j in range(int(np.floor(duration * fps))):
            if prob >= 1.0 or rng.random() < prob:
                out.append(j * period + rng.random() * jitter * period if jitter > 0.0
                           else j * period)
    elif kind == "poisson":
        rate = fps * float(kw.get("rate_scale", 1.0))
        if rate > 0.0:
            _exp_run(rng, 1.0 / rate, 0.0, duration, prob, out)
    elif kind == "mmpp":
        b = max(1.0, float(kw.get("burstiness", 4.0)))
        p = min(max(float(kw.get("on_fraction", 0.25)), 1e-6), 1.0, 1.0 / b)
        rate_on = fps * b
        rate_off = fps * max(0.0, 1.0 - p * b) / (1.0 - p) if p < 1.0 else fps
        cycle = float(kw.get("mean_cycle", 20.0)) * period
        soj = {True: p * cycle, False: (1.0 - p) * cycle}
        t = 0.0
        on = rng.random() < p
        while t < duration:
            end = min(t + rng.exponential(soj[on]), duration)
            rate = rate_on if on else rate_off
            if rate > 0.0:
                _exp_run(rng, 1.0 / rate, t, end, prob, out)
            t = end
            on = not on
    else:
        raise ValueError(f"no reference for arrival process {kind!r}")
    return out


def releases(traffic: dict, seed: int) -> List[Tuple[float, int]]:
    """The seed's sorted ``(time, model)`` release stream."""
    rng = np.random.default_rng(seed)
    out = []
    for m, e in enumerate(traffic["entries"]):
        for t in release_times(e["arrival"], e["fps"], e.get("prob", 1.0),
                               traffic["horizon_s"], rng):
            out.append((t, m))
    out.sort()
    return out


# ----------------------------------------------------------- faults ----


def fault_timeline(spec: str, n_acc: int, duration: float, seed: int):
    """``(events, n_spans, retighten)``; events are ``(t, acc, code,
    value)`` sorted by time, stable."""
    if spec in (None, "", "none"):
        return [], 0, False
    events, n_spans, retighten = [], 0, False
    for part in spec.split("+"):
        kind, kw = parse_spec(part)
        retighten = retighten or bool(kw.get("retighten", False))
        if kw.get("interrupted", "restart") != "restart":
            raise ValueError("the reference covers the restart policy only")
        acc = int(kw["acc"])
        if acc >= n_acc:
            raise ValueError(f"fault acc {acc} out of range")
        if kind == "intermittent":
            rng = np.random.default_rng([FAULT_SALT, seed, acc])
            windows, t = [], 0.0
            while True:
                t += float(rng.exponential(1.0 / kw["rate"]))
                if t >= duration:
                    break
                d = float(rng.exponential(kw["mean_down"]))
                windows.append((t, t + d))
                t += d
        else:
            start = float(kw.get("start", 0.0))
            end = math.inf if kind == "permanent" else start + float(kw["duration"])
            windows = [] if start >= duration else [(start, end)]
        for s, e in windows:
            n_spans += 1
            if kind == "throttle":
                events += [(s, acc, "scale", float(kw["factor"])), (e, acc, "scale", 1.0)]
            else:
                events.append((s, acc, "down", 1.0))
                if math.isfinite(e):
                    events.append((e, acc, "up", 1.0))
    events.sort(key=lambda ev: ev[0])
    return events, n_spans, retighten


# ---------------------------------------------------- offline stage ----


def levels_of(row) -> np.ndarray:
    vals = np.asarray(sorted(set(float(x) for x in row), reverse=True))
    if len(vals) > 1:
        keep = [0]
        for i in range(1, len(vals)):
            if vals[keep[-1]] - vals[i] > LEVEL_ATOL:
                keep.append(i)
        vals = vals[keep]
    return vals


def tighten(levels, deadline, dt):
    """Algorithm 1: ``(feasible, budgets, rho)``."""
    levels = [np.asarray(lv, dtype=dt) for lv in levels]
    L = len(levels)
    R = np.array([len(lv) for lv in levels])
    rho = np.zeros(L, dtype=np.int64)
    while True:
        c_ref = np.array([levels[l][rho[l]] for l in range(L)], dtype=dt)
        c_total = c_ref.sum()
        if c_total <= deadline:
            return True, deadline * c_ref / c_total, rho
        if not (rho < R - 1).any():
            return False, np.zeros(L, dtype=dt), rho
        gaps = np.full(L, -np.inf, dtype=dt)
        for l in range(L):
            if rho[l] < R[l] - 1:
                gaps[l] = levels[l][rho[l]] - levels[l][rho[l] + 1]
        rho[int(np.argmax(gaps))] += 1


class Graph:
    """A model's layer DAG from its ``preds``: successors (ascending),
    a topological order, the sources (ascending) and the one sink."""

    def __init__(self, preds: Sequence[Sequence[int]]):
        n = len(preds)
        self.preds = [list(ps) for ps in preds]
        self.succs: List[List[int]] = [[] for _ in range(n)]
        for l, ps in enumerate(self.preds):
            if len(set(ps)) != len(ps) or any(not 0 <= p < n or p == l for p in ps):
                raise ValueError(f"node {l}: malformed predecessors {ps}")
            for p in ps:
                self.succs[p].append(l)
        pending = [len(ps) for ps in self.preds]
        self.sources = [l for l in range(n) if not pending[l]]
        self.topo, todo = [], list(self.sources)
        while todo:
            l = todo.pop()
            self.topo.append(l)
            for s in self.succs[l]:
                pending[s] -= 1
                if not pending[s]:
                    todo.append(s)
        sinks = [l for l in range(n) if not self.succs[l]]
        if len(self.topo) != n or len(sinks) != 1:
            raise ValueError("a layer graph is acyclic with one sink")
        self.sink = sinks[0]

    @staticmethod
    def of(model: dict, L: int) -> Optional["Graph"]:
        """The model's graph, or ``None`` for a chain."""
        preds = model.get("preds")
        if preds is not None and len(preds) != L:
            raise ValueError(f"{len(preds)} predecessor lists for {L} layers")
        if preds is None or preds == [[]] + [[l] for l in range(L - 1)]:
            return None
        return Graph(preds)


def tighten_dag(levels, deadline, graph: Graph, dt):
    """Algorithm 1 over the critical path: ``(feasible, budgets, vdl_rel,
    rho)``."""
    levels = [np.asarray(lv, dtype=dt) for lv in levels]
    L = len(levels)
    R = np.array([len(lv) for lv in levels])
    rho = np.zeros(L, dtype=np.int64)
    zero = dt(0.0)
    while True:
        c_ref = np.array([levels[l][rho[l]] for l in range(L)], dtype=dt)
        ecl = np.zeros(L, dtype=dt)
        for l in graph.topo:
            ecl[l] = max((ecl[p] for p in graph.preds[l]), default=zero) + c_ref[l]
        cp = ecl[graph.sink]
        if cp <= deadline:
            scale = deadline / cp
            return True, c_ref * scale, ecl * scale, rho
        tail = np.zeros(L, dtype=dt)  # the longest path strictly below each node
        for l in reversed(graph.topo):
            tail[l] = max((tail[s] + c_ref[s] for s in graph.succs[l]), default=zero)
        open_ = (ecl + tail >= cp - LEVEL_ATOL) & (rho < R - 1)
        if not open_.any():
            return False, np.zeros(L, dtype=dt), np.zeros(L, dtype=dt), rho
        gaps = np.full(L, -np.inf, dtype=dt)
        for l in np.flatnonzero(open_):
            gaps[l] = levels[l][rho[l]] - levels[l][rho[l] + 1]
        rho[int(np.argmax(gaps))] += 1


class Plan:
    """One model's offline plan, derived from the configuration's tables."""

    def __init__(self, model: dict, dataflows: Sequence[str], deadline, theta, variants_on, dt):
        self.dt = dt
        self.lat = np.asarray(model["lat"], dtype=dt)
        self.L, self.na = self.lat.shape
        self.deadline = dt(deadline)
        self.theta = theta
        self.graph = Graph.of(model, self.L)
        lv = [levels_of(self.lat[l]) for l in range(self.L)]
        if self.graph is None:
            self.feasible, budgets, self.rho = tighten(lv, self.deadline, dt)
            self.vdl_rel = np.cumsum(budgets)
            self.succs = [[l + 1] for l in range(self.L - 1)] + [[]]
        else:
            self.feasible, budgets, self.vdl_rel, self.rho = tighten_dag(
                lv, self.deadline, self.graph, dt)
            self.succs = self.graph.succs
        self.loss: Dict[int, float] = {}
        self.lat_var = np.full_like(self.lat, np.inf)
        if variants_on and self.feasible:
            for l in range(self.L):
                got = self._design(model, l, lv[l], int(self.rho[l]), dataflows)
                if got is not None:
                    self.lat_var[l], self.loss[l] = got
        self._derive()

    def _design(self, model, l, levels, rho, dataflows):
        """The minimum-gamma variant that brings every excluded
        accelerator to the goal latency (``None``: no variant)."""
        if rho <= 0:
            return None
        row = self.lat[l]
        targets = [k for k in range(self.na) if row[k] > levels[rho] + 1e-15]
        if not targets:
            return None
        goal = max(row.min(), levels[min(rho + 1, len(levels) - 1)])
        worst = max(targets, key=lambda k: row[k])
        direction = "d2s" if dataflows[worst] == "os" else "s2d"
        cands = model["variants"].get(str(l), {})
        for g in GAMMAS:
            vrow = cands.get(str(g), {}).get(direction)
            if vrow is None:
                continue
            vlat = np.asarray(vrow, dtype=self.dt)
            if all(vlat[k] <= goal + 1e-15 for k in targets) and all(
                    vlat[k] < row[k] for k in targets):
                return vlat, float(model["loss"][str(l)][str(g)])
        return None

    def _derive(self):
        """The minimum-latency tables: ``crit_from[l]``, the least work
        from node ``l`` on, inclusive, and ``crit_after[l]``, the least
        work strictly after it (on a chain, slices of the remaining sum
        ``rm``)."""
        self.min_lat = self.lat.min(axis=1)
        rm = np.zeros(self.L + 1, dtype=self.dt)
        rm[:-1] = np.cumsum(self.min_lat[::-1])[::-1]
        self.rm = rm
        if self.graph is None:
            self.crit_from, self.crit_after = rm[:-1], rm[1:]
            return
        zero = self.dt(0.0)
        cf = np.zeros(self.L, dtype=self.dt)
        for l in reversed(self.graph.topo):
            cf[l] = self.min_lat[l] + max((cf[s] for s in self.succs[l]), default=zero)
        self.crit_from = cf
        self.crit_after = np.array([max((cf[s] for s in self.succs[l]), default=zero)
                                    for l in range(self.L)], dtype=self.dt)

    def scaled(self, mult):
        """The plan under a capability multiplier per accelerator."""
        if np.all(mult == 1.0):
            return self
        p = object.__new__(Plan)
        p.__dict__.update(self.__dict__)
        p.lat = self.lat * mult
        p.lat_var = self.lat_var * mult
        p._derive()
        return p

    def retained(self, combo: frozenset) -> float:
        r = 1.0
        for i in combo:
            r *= (1.0 - self.loss[i]) ** INTERACTION
        return r

    def valid(self, combo: frozenset) -> bool:
        return self.retained(combo) >= self.theta


def plans_for(config: dict, traffic: dict, dt=np.float64) -> List[Plan]:
    if len(config["models"]) != len(traffic["entries"]):
        raise ValueError("traffic entries and configuration models differ in number")
    flows = [a["dataflow"] for a in config["accelerators"]]
    on = config.get("enable_variants", True)
    return [Plan(m, flows, e.get("deadline_s", 1.0 / e["fps"]), config["theta"], on, dt)
            for m, e in zip(config["models"], traffic["entries"])]


# ----------------------------------------------------------- trial ----


class Run:
    """What the node entries of one DAG request share."""
    __slots__ = ("pending", "applied", "dropped")

    def __init__(self, graph: Graph):
        self.pending = [len(ps) for ps in graph.preds]
        self.applied = frozenset()
        self.dropped = False


class Req:
    """A ready or running request; on a DAG plan, one node entry of it
    (``layer`` is the node), with its shared ``run``."""
    __slots__ = ("rid", "m", "arrival", "deadline", "layer", "applied", "evicted", "vdl_abs",
                 "run")

    def __init__(self, rid, m, arrival, deadline, layer=0, run=None):
        self.rid, self.m, self.arrival, self.deadline = rid, m, arrival, deadline
        self.layer = layer
        self.applied = frozenset()
        self.evicted = False
        self.vdl_abs = None
        self.run = run


def _holder(r: Req):
    """Where the request's applied variants live: the entry of a chain,
    the shared run of a DAG request."""
    return r if r.run is None else r.run


class Stats:
    __slots__ = ("released", "completed", "missed", "dropped", "variants", "retained",
                 "evicted", "remapped")

    def __init__(self):
        self.released = self.completed = self.missed = self.dropped = 0
        self.variants = self.evicted = self.remapped = 0
        self.retained = 0.0

    def row(self, in_flight: int) -> tuple:
        """The fingerprint's per-model tuple (shed is always 0 here)."""
        return (self.released, self.completed, self.missed, self.dropped, self.variants,
                float(self.retained), 0, in_flight, self.evicted, self.remapped)


def _scheduler(spec: str):
    name, kw = parse_spec(spec.lower())
    flags = {"terastal": (True, True), "terastal_no_variants": (True, False),
             "no_variants": (True, False), "terastal_no_budgeting": (False, True),
             "no_budgeting": (False, True)}
    if name in ("fcfs", "edf", "dream"):
        return name, False, False, ""
    if name not in flags:
        raise ValueError(f"no reference for scheduler {spec!r}")
    return ("terastal",) + flags[name] + (kw.get("backfill_mode", "ef"),)


def _round(kind, budgets, variants_on, mode, now, ready, busy, plans):
    """One scheduling round: ``[(req, layer, acc, use_variant, cost)]``."""
    na = len(busy)
    idle = [k for k in range(na) if busy[k] <= now + 1e-15]
    if not idle:
        return []
    out = []
    if kind in ("fcfs", "edf", "dream"):
        if kind == "fcfs":
            order = sorted(ready, key=lambda r: (r.arrival, r.rid, r.layer))
        elif kind == "edf":
            order = sorted(ready, key=lambda r: (r.deadline - plans[r.m].crit_after[r.layer],
                                                 r.rid, r.layer))
        else:
            order = sorted(ready, key=lambda r: (r.deadline - now - plans[r.m].crit_from[r.layer],
                                                 r.rid, r.layer))
        for r in order:
            if not idle:
                break
            lat = plans[r.m].lat[r.layer]
            if kind == "dream":
                k = min(idle, key=lambda k: max(now, busy[k]) + lat[k])
            else:
                k = min(idle, key=lambda k: lat[k])
            out.append((r, r.layer, k, False, lat[k]))
            idle.remove(k)
        return out

    tau = np.array([max(now, busy[k]) for k in range(na)], dtype=busy.dtype)

    def vdl(r, l):
        p = plans[r.m]
        if budgets:
            return r.vdl_abs[l] if r.vdl_abs is not None else r.arrival + p.vdl_rel[l]
        return r.deadline - p.crit_after[l]

    def var_ok(r, l):
        p = plans[r.m]
        return variants_on and l in p.loss and p.valid(_holder(r).applied | {l})

    def binding(r, l):
        """Eq. 8's next layer: the first successor with the least
        ``vdl - min_lat`` (-1 at the sink)."""
        p = plans[r.m]
        return min(p.succs[l], key=lambda s: vdl(r, s) - p.min_lat[s], default=-1)

    def slack(r):
        return vdl(r, r.layer) - (tau + plans[r.m].lat[r.layer]).min()

    remaining = []
    for r in sorted(ready, key=lambda r: (slack(r), r.rid, r.layer)):
        p, l = plans[r.m], r.layer
        d_v = vdl(r, l)
        cands = [k for k in idle if tau[k] + p.lat[l, k] <= d_v + 1e-15]
        if cands:
            k = min(cands, key=lambda k: tau[k] + p.lat[l, k])
            out.append((r, l, k, False, p.lat[l, k]))
            idle.remove(k)
            tau[k] += p.lat[l, k]
            continue
        if var_ok(r, l):
            lv = p.lat_var[l]
            cands = [k for k in idle if tau[k] + lv[k] <= d_v + 1e-15]
            if cands:
                k = min(cands, key=lambda k: tau[k] + lv[k])
                out.append((r, l, k, True, lv[k]))
                idle.remove(k)
                tau[k] += lv[k]
                continue
        remaining.append(r)

    for k in list(idle):
        if not remaining:
            break
        best = None
        for r in remaining:
            p, l = plans[r.m], r.layer
            s_star = slack(r)
            for use_var in (False, True):
                if use_var and not var_ok(r, l):
                    continue
                row = p.lat_var[l] if use_var else p.lat[l]
                c = row[k]
                if not np.isfinite(c):
                    continue
                finish = tau[k] + c
                if mode == "ef" and finish > (tau + row).min() + 1e-15:
                    continue
                s = binding(r, l)
                if s >= 0:
                    s_f = vdl(r, s) - finish - p.min_lat[s]
                else:
                    s_f = r.deadline - finish
                delta = s_f - s_star
                if best is None or (delta, -int(use_var)) > (best[0], -int(best[3])):
                    best = (delta, l, r, use_var, c)
        if best is None or (mode == "positive" and best[0] <= 0.0):
            continue
        delta, l, r, use_var, c = best
        out.append((r, l, k, use_var, c))
        tau[k] += c
        remaining.remove(r)
    return out


def simulate(config: dict, traffic: dict, seed: int, dtype=np.float64,
             plans: Optional[List[Plan]] = None) -> dict:
    """One trial: ``{"rounds", "busy", "busy_h", "models", "spans"}``,
    the fields of the program's ``SimResult.fingerprint()``."""
    dt = dtype
    base = plans if plans is not None else plans_for(config, traffic, dt)
    if traffic.get("faults", "none") not in (None, "", "none"):
        dags = [m["model"] for p, m in zip(base, config["models"]) if p.graph is not None]
        if dags:
            raise DagFaultsUnsupported(f"faults with the DAG plans of {dags}: the reference "
                                       "covers faults on layer chains only")
    kind, budgets, variants_on, mode = _scheduler(config["scheduler"])
    duration = dt(traffic["horizon_s"])
    na = base[0].na
    busy = np.zeros(na, dtype=dt)
    busy_t = np.zeros(na, dtype=dt)
    busy_h = np.zeros(na, dtype=dt)
    stats = [Stats() for _ in base]

    f_events, spans, retighten = fault_timeline(traffic.get("faults", "none"), na,
                                                traffic["horizon_s"], seed)
    faulted = bool(f_events)
    avail, fscale = [True] * na, [1.0] * na
    cur_fin = [-1] * na
    d_t0 = np.zeros(na, dtype=dt)
    d_w = np.zeros(na, dtype=dt)
    d_h = np.zeros(na, dtype=dt)
    eff = list(base)
    chain: List[Optional[np.ndarray]] = [None] * len(base)

    heap = []
    cnt = itertools.count()
    for t, m in releases(traffic, seed):
        heap.append((dt(t), next(cnt), 0, m))
    for t, acc, code, val in f_events:
        heap.append((dt(t), next(cnt), 3, (acc, code, val)))
    heapq.heapify(heap)

    ready: List[Req] = []
    running: Dict[int, Tuple[Req, bool]] = {}
    rids = itertools.count()
    rounds = 0
    zero = dt(0.0)

    def schedule(now):
        nonlocal rounds
        rounds += 1
        swept = False
        for r in list(ready):
            if now + eff[r.m].crit_from[r.layer] > r.deadline + 1e-12:
                ready.remove(r)
                if r.run is not None:
                    if r.run.dropped:
                        continue
                    r.run.dropped = swept = True
                stats[r.m].missed += 1
                stats[r.m].dropped += 1
        if swept:  # the dropped requests' other entries go uncounted
            ready[:] = [r for r in ready if r.run is None or not r.run.dropped]
        if not ready:
            return
        snapshot = busy.copy()
        for r, l, k, use_var, _ in _round(kind, budgets, variants_on, mode, now, list(ready),
                                           snapshot, eff):
            p = eff[r.m]
            c = p.lat_var[l, k] if use_var else p.lat[l, k]
            ready.remove(r)
            if use_var:
                holder = _holder(r)
                holder.applied = holder.applied | {l}
                stats[r.m].variants += 1
            if faulted and r.evicted:
                r.evicted = False
                stats[r.m].remapped += 1
            busy[k] = now + c
            busy_t[k] += c
            h = min(c, max(zero, duration - now))
            busy_h[k] += h
            running[k] = (r, use_var)
            fc = next(cnt)
            heapq.heappush(heap, (now + c, fc, 1, k))
            cur_fin[k], d_t0[k], d_w[k], d_h[k] = fc, now, c, h

    def refresh():
        nonlocal eff
        mult = np.array([s if a else np.inf for s, a in zip(fscale, avail)], dtype=dt)
        eff = [p.scaled(mult) for p in base]
        if retighten:
            for m, (p, ep) in enumerate(zip(base, eff)):
                if ep is p:
                    chain[m] = None
                    continue
                ok, b, _ = tighten([levels_of(ep.lat[l]) for l in range(ep.L)], p.deadline, dt)
                chain[m] = np.cumsum(b) if ok else None
            for r in ready + [r for r, _ in running.values()]:
                r.vdl_abs = None if chain[r.m] is None else r.arrival + chain[r.m]

    while heap:
        now, ec, ev, payload = heapq.heappop(heap)
        if ev == 0:
            m = payload
            graph = base[m].graph
            stats[m].released += 1
            if graph is None:
                r = Req(next(rids), m, now, now + base[m].deadline)
                if retighten and chain[m] is not None:
                    r.vdl_abs = now + chain[m]
                ready.append(r)
            else:
                rid, run = next(rids), Run(graph)
                ready.extend(Req(rid, m, now, now + base[m].deadline, s, run)
                             for s in graph.sources)
        elif ev == 3:
            k, code, val = payload
            if code == "down":
                avail[k] = False
                if k in running:
                    r, used = running.pop(k)
                    if used:
                        r.applied = r.applied - {r.layer}
                        stats[r.m].variants -= 1
                    new_w = now - d_t0[k]
                    new_h = min(new_w, max(zero, duration - d_t0[k]))
                    busy_t[k] += new_w - d_w[k]
                    busy_h[k] += new_h - d_h[k]
                    r.evicted = True
                    stats[r.m].evicted += 1
                    ready.append(r)
                busy[k] = np.inf
                cur_fin[k] = -1
            elif code == "up":
                avail[k] = True
                busy[k] = now
            else:
                old = fscale[k]
                fscale[k] = val
                if k in running and val != old:
                    fin = now + (busy[k] - now) * dt(val / old)
                    busy[k] = fin
                    new_w = fin - d_t0[k]
                    new_h = min(new_w, max(zero, duration - d_t0[k]))
                    busy_t[k] += new_w - d_w[k]
                    busy_h[k] += new_h - d_h[k]
                    d_w[k], d_h[k] = new_w, new_h
                    fc = next(cnt)
                    heapq.heappush(heap, (fin, fc, 1, k))
                    cur_fin[k] = fc
            refresh()
        elif faulted and ec != cur_fin[payload]:
            pass  # a finish orphaned by an eviction or a re-time
        else:
            r, _ = running.pop(payload)
            p = base[r.m]
            if r.run is None:
                r.layer += 1
                done = r.layer >= p.L
                if not done:
                    ready.append(r)
            elif r.run.dropped:
                done = False  # its busy time is counted, its drop too
            else:
                done = r.layer == p.graph.sink
                for s in p.succs[r.layer]:
                    r.run.pending[s] -= 1
                    if not r.run.pending[s]:
                        ready.append(Req(r.rid, r.m, r.arrival, r.deadline, s, r.run))
            if done:
                st = stats[r.m]
                st.completed += 1
                if now > r.deadline + 1e-12:
                    st.missed += 1
                st.retained += p.retained(_holder(r).applied)
        if heap and abs(heap[0][0] - now) < 1e-15:
            continue
        schedule(now)

    live = [0] * len(base)
    runs = set()  # a DAG request counts once, and not once it has dropped
    for r in ready + [r for r, _ in running.values()]:
        if r.run is None:
            live[r.m] += 1
        elif not r.run.dropped and r.run not in runs:
            runs.add(r.run)
            live[r.m] += 1
    return {"rounds": rounds, "busy": [float(x) for x in busy_t],
            "busy_h": [float(x) for x in busy_h],
            "models": [st.row(n) for st, n in zip(stats, live)], "spans": spans}
