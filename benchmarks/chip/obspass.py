"""The program's own spans and counters, read after the window of a
``--trace 1`` run, and the device time of each stage of the loop body.

:func:`ensure` runs once per run (the readers of the metrics that use it
call it from ``collect``) and keeps what it finds in
``ctx.probes["obs"]``:

1. One recorded pass (``repro.core.obs.record``) over the cell's whole
   pool, in pool order, through the program's own
   ``engine_batch.simulate_batch``: the spans (``engine.*``) and
   counters of every batch.  The programs are those the window ran,
   loaded again first (``compile_s.cold`` clears the caches).
2. One profiled batch of each distinct staged shape
   (``ctx.bucket_seeds``).  Of the first ``MAX_OPS`` device operations,
   those of the loop are summed by the first named scope under the loop
   body (:func:`scope_of`) and divided by the iterations the loop began
   in that stretch, the runs of its condition (:func:`loop_iterations`):
   device microseconds per iteration of each stage, from the trace
   alone.  Every iteration runs the same operations on the same padded
   shapes (a vmapped branch runs both sides), so the stretch stands for
   the whole loop of its shape.  The device trace names an operation
   only by its id; its op name comes from the metadata of the compiled
   program (:func:`op_names`).  A fusion carries the op name of its
   root, so a fusion that mixes stages counts under its root's stage; an
   operation with no op name (a copy XLA inserted) counts as ``other``.
3. ``<stage>_us_per_iter`` for the pass: each shape's per-iteration time
   weighted by the loop iterations (counter ``iters_max``) its batches
   ran in the pass (:func:`weighted`).

A program without ``repro.core.obs`` (before these spans existed) gives
None, and every reader then reads nothing.  The reductions
(:func:`pass_metrics`, :func:`scope_times`, :func:`loop_iterations`,
:func:`weighted`, :func:`idle_gaps`) are pure functions of plain records,
checked on the CPU on synthetic records and on a recorded chip excerpt.
"""

from __future__ import annotations

import json
import re
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import devtrace

try:  # the stages of the loop body, as the program names its scopes
    from repro.core.obs import SCOPES
except ImportError:  # a program without them
    SCOPES = ()
#: device operations read from the profiled batch
MAX_OPS = 200_000
SPAN_PREFIX = "engine."

Op = Tuple[str, float, float]  # op id, start_ns, dur_ns


def _counter(b: dict, name: str):
    return b["counters"].get(name)


def _span_ns(b: dict, name: str) -> float:
    return sum(s[4] - s[3] for s in b["spans"] if s[1] == name)


def pass_metrics(batches: Sequence[dict]) -> Dict[str, float]:
    """Per-layer readings of one recorded pass.  ``batches`` is
    ``Recorder.batches``: per batch its ``spans`` as ``(batch, name,
    parent, start_ns, end_ns)`` and its ``counters``."""
    rows = [b for b in batches if _counter(b, "iters_max")]
    if not rows:
        return {}
    iters = sum(_counter(b, "iters_max") for b in rows)
    loop_ns = sum(_span_ns(b, "loop") for b in rows)
    out = {
        "loop_iters": iters / len(rows),
        "max_it": sum(_counter(b, "max_it") for b in rows) / len(rows),
        "loop_us_per_iter": loop_ns * 1e-3 / iters,
        "lane_iter_share": sum(_counter(b, "iters_sum") for b in rows)
        / sum(_counter(b, "lanes") * _counter(b, "iters_max") for b in rows),
        "assemble_replay_ms": sum(_span_ns(b, "assemble.replay") for b in rows)
        * 1e-6 / len(rows),
    }
    if all(_counter(b, "live_peak") is not None for b in rows):
        out["live_slot_share"] = sum(_counter(b, "live_peak") / _counter(b, "nr_pad")
                                     for b in rows) / len(rows)
    return out


def op_names(hlo_text: str) -> Dict[str, str]:
    """Op id -> op name, from a compiled program's HLO text."""
    out = {}
    for m in re.finditer(r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', hlo_text,
                         re.M):
        out[m.group(1)] = m.group(2)
    return out


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The loop stage of an op name: the first scope under the loop
    body (one of ``SCOPES``), ``cond`` for the loop's predicate,
    ``other`` for the body's unscoped work; None outside the loop."""
    parts = (op_name or "").split("/")
    if "while" not in parts:
        return None
    i = parts.index("while")
    part = parts[i + 1] if i + 1 < len(parts) else ""
    if part == "body":
        s = parts[i + 2] if i + 2 < len(parts) else ""
        return s if s in SCOPES else "other"
    if part in ("cond", "body_pred"):
        return "cond"
    return None


def scope_times(ops: Sequence[Op], names: Dict[str, str]) -> Dict[str, float]:
    """Nanoseconds of device operations per loop stage.  ``ops`` are
    device operations in trace order.  The loop's stretch runs from the
    first operation of the loop to the end of the last one read (the
    ``while`` operation itself is recorded only when the loop ends,
    which a long loop does after the profiler's buffer is full), and
    every operation in it counts, ``other`` where it has no stage."""
    inside = [(s, s + d) for i, s, d in ops if scope_of(names.get(i))]
    if not inside:
        return {}
    lo, hi = min(a for a, _ in inside), max(b for _, b in inside)
    out: Dict[str, float] = {}
    for i, s, d in ops:
        if lo <= s < hi and i.split(".")[0] != "while":
            k = scope_of(names.get(i)) or "other"
            out[k] = out.get(k, 0.0) + d
    return out


def loop_iterations(ops: Sequence[Op], names: Dict[str, str]) -> int:
    """Iterations the loop began among ``ops``: the most runs of any one
    operation of its condition (each iteration runs each of them once)."""
    runs: Dict[str, int] = {}
    for i, _, _ in ops:
        if scope_of(names.get(i)) == "cond":
            runs[i] = runs.get(i, 0) + 1
    return max(runs.values(), default=0)


def stage_us_per_iter(ops: Sequence[Op], names: Dict[str, str]) -> Dict[str, float]:
    """Device microseconds per loop iteration of each stage (and
    ``total``), from the profiled operations alone."""
    n = loop_iterations(ops, names)
    if not n:
        return {}
    out = {k: v * 1e-3 / n for k, v in scope_times(ops, names).items()}
    out["total"] = sum(out.values())
    return out


def weighted(per_iter: Sequence[Dict[str, float]], iters: Sequence[float]) -> Dict[str, float]:
    """Per-iteration times of a pass: batch ``j`` ran ``iters[j]`` loop
    iterations at ``per_iter[j]`` (its shape's split), so each stage's
    time over the pass's iterations.  Stages a shape lacks count 0."""
    total = sum(iters)
    if not total or not all(per_iter):
        return {}
    keys = {k for split in per_iter for k in split}
    return {k: sum(n * split.get(k, 0.0) for split, n in zip(per_iter, iters)) / total
            for k in keys}


def idle_gaps(events: Sequence[devtrace.Event], top: int = 5) -> List[list]:
    """The longest stretches of the ``engine.batch`` span in which the
    device ran no program, each labelled by the innermost ``engine.*``
    span the host was in: an idle interval is cut where a span begins or
    ends.  Where the profiler's buffer filled first (the loop span ends
    more than ``devtrace.CUT_NS`` after the device's last event) the
    window ends at that last event."""
    spans = [(s, s + d, n[len(SPAN_PREFIX):]) for p, l, n, s, d in events
             if n.startswith(SPAN_PREFIX) and not devtrace.DEVICE_PLANE.match(p)]
    dev = [(s, s + d) for p, l, n, s, d in events if devtrace.DEVICE_PLANE.match(p)
           and l == devtrace.MODULES]
    whole = [(a, b) for a, b, n in spans if n == "batch"]
    if not whole or not dev:
        return []
    lo, hi = whole[0]
    dev_end = max(e for _, e in dev)
    if any(n == "loop" and b > dev_end + devtrace.CUT_NS for a, b, n in spans):
        hi = dev_end
    busy = devtrace._union(devtrace._clip(dev, lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    cuts = sorted({x for a, b, _ in spans for x in (a, b)})
    pieces: List[list] = []  # [start, end, label], merged while adjacent and alike
    for s, e in zip(edges[::2], edges[1::2]):
        points = [s] + [x for x in cuts if s < x < e] + [e]
        for a, b in zip(points, points[1:]):
            mid = 0.5 * (a + b)
            inside = [(y - x, n) for x, y, n in spans if x <= mid < y]
            label = min(inside)[1] if inside else "other"
            if pieces and pieces[-1][2] == label and pieces[-1][1] == a:
                pieces[-1][1] = b
            else:
                pieces.append([a, b, label])
    pieces.sort(key=lambda p: p[0] - p[1])
    return [[label, (b - a) * 1e-9] for a, b, label in pieces[:top]]


def flatten(data, max_ops: int = MAX_OPS) -> Tuple[List[devtrace.Event], List[Op]]:
    """Host ``engine.*`` spans and device program runs (as
    ``devtrace`` events) and the first ``max_ops`` device operations
    (op id, start, duration) of a ``jax.profiler.ProfileData``."""
    events: List[devtrace.Event] = []
    ops: List[Op] = []
    for plane in data.planes:
        device = bool(devtrace.DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name == devtrace.MODULES:
                events.extend((plane.name, line.name, ev.name, float(ev.start_ns),
                               float(ev.duration_ns)) for ev in line.events)
            elif device and line.name == devtrace.OPS:
                for k, ev in enumerate(line.events):
                    if k >= max_ops:
                        break
                    ops.append((ev.name.split(" = ")[0].lstrip("%"), float(ev.start_ns),
                                float(ev.duration_ns)))
            elif not device:
                events.extend((plane.name, line.name, ev.name, float(ev.start_ns),
                               float(ev.duration_ns))
                              for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    return events, ops


def _profile(run, seeds):
    import jax
    from jax._src.lib import _profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    session = _profiler.ProfilerSession(opts)
    try:
        run(seeds)
    finally:
        data = jax.profiler.ProfileData.from_serialized_xspace(session.stop())
    return flatten(data)


def _shape(staged) -> tuple:
    """What a staged batch's device program is compiled for."""
    import jax

    return (tuple(np.shape(x) for x in jax.tree_util.tree_leaves(staged.args)),
            tuple(sorted(staged.static.items())))


def ensure(ctx) -> Optional[dict]:
    """Run the recorded pass and the profiled batches once per run."""
    if "obs" in ctx.probes:
        return ctx.probes["obs"]
    ctx.probes["obs"] = None
    try:
        from repro.core import obs
    except ImportError:  # a program without spans and counters
        return None
    import jax

    prog = ctx.program

    def run(seeds):
        return prog.eb.simulate_batch(prog.plans, prog.tasks, prog.horizon, prog.scheduler,
                                      seeds, faults=prog.faults)

    for seeds in ctx.bucket_seeds:  # reloads what an earlier reader cleared
        prog.warm(seeds)
    pool = ctx.cell.pool()
    t0 = time.perf_counter()
    with obs.record() as rec:
        for seeds in pool:
            run(seeds)
    wall = time.perf_counter() - t0
    found = {"metrics": pass_metrics(rec.batches), "summary": rec.summary()}
    # the cost of recording: the same batches in the window, unrecorded
    win = sum(b["stage_s"] + b["loop_s"] + b["assemble_s"] for b in ctx.batches)
    found["trials_per_s"] = {"recorded": ctx.cell.lanes * len(pool) / wall,
                             "window": ctx.cell.lanes * len(ctx.batches) / win}

    splits, found["shapes"], found["idle_gaps"] = {}, [], []
    for k, seeds in enumerate(ctx.bucket_seeds):
        staged = prog.stage(seeds)
        with jax.enable_x64(True):
            text = prog.run_trials.lower(*staged.args, **staged.static).compile().as_text()
        events, ops = _profile(run, seeds)
        names = op_names(text)
        splits[_shape(staged)] = split = stage_us_per_iter(ops, names)
        found["shapes"].append({"nr_pad": int(np.shape(staged.args[2])[-1]),
                                "iterations_read": loop_iterations(ops, names),
                                "us_per_iter": split})
        if k == 0:  # the pool's most frequent shape
            found["idle_gaps"] = idle_gaps(events)
    per_iter = weighted([splits.get(_shape(prog.stage(seeds)), {}) for seeds in pool],
                        [b["counters"].get("iters_max", 0) for b in rec.batches])
    found["stage_us_per_iter"] = per_iter
    if per_iter:
        for s in ("round", "bind"):
            found["metrics"][s + "_us_per_iter"] = per_iter.get(s, 0.0)
    ctx.probes["obs"] = found
    print("obs " + json.dumps({k: found[k] for k in
                               ("metrics", "trials_per_s", "shapes", "stage_us_per_iter",
                                "idle_gaps", "summary")}),
          file=sys.stderr, flush=True)
    return found


def metric(ctx, name: str) -> Optional[float]:
    found = ctx.probes.get("obs")
    return found["metrics"].get(name) if found else None
