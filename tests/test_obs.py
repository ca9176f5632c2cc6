"""Spans and counters of the batch engine (:mod:`repro.core.obs`).

* ``_Out.iters`` is each lane's exact loop iteration count;
* ``live_peak`` is the most requests live at once;
* recording changes no result;
* a recorder keeps one span tree per batch, and nothing is kept without
  one; spans sit on the profiler's clock;
* every stage scope (``obs.SCOPES``) is in the lowered program.
"""

import jax
import numpy as np
import pytest

from repro.core import f64, make_scheduler, obs, simulate
from repro.core.budget import distribute_budgets
from repro.core.engine_batch import _run_trials, simulate_batch, stage_batch
from repro.core.simulator import PeriodicArrivals, TaskSpec, make_arrival_process
from repro.core.variants import ModelPlan
from repro.core.workload import SATURATION_SCENARIOS
from repro.costmodel.dnn_zoo import DnnModel
from repro.costmodel.layers import matmul
from repro.costmodel.maestro import PLATFORMS, Accelerator, Dataflow, Platform

SEEDS = [0, 1, 2]
DUR = 0.12
THROTTLE = "throttle(acc=0,start=0.02,duration=0.05,factor=4.0,retighten=true)"
#: the spans of one ``simulate_batch`` call, child -> parent
TREE = {
    "batch": None, "stage": "batch", "stage.releases": "stage", "stage.pack": "stage",
    "stage.to_device": "stage", "loop": "batch", "assemble": "batch",
    "assemble.copy": "assemble", "assemble.counts": "assemble", "assemble.replay": "assemble",
}


def _cell():
    plans, tasks = SATURATION_SCENARIOS["saturation_3x"].plans(PLATFORMS["4k_1ws2os"])
    proc = make_arrival_process("poisson")
    return plans, tasks, [t.arrival or proc for t in tasks]


def _run(staged, max_it=None):
    args = list(staged.args)
    if max_it is not None:
        args[7] = np.int32(max_it)
    with jax.enable_x64(True):
        return jax.tree_util.tree_map(np.asarray, _run_trials(*args, **staged.static))


@pytest.mark.parametrize("sched,faults", [("terastal", None), ("fcfs", None), ("edf", THROTTLE)])
def test_iters_is_each_lanes_exact_iteration_count(sched, faults):
    plans, tasks, procs = _cell()
    with jax.enable_x64(True):
        staged = stage_batch(plans, tasks, DUR, make_scheduler(sched), SEEDS, procs,
                             faults=faults)
    out = _run(staged)
    n = len(SEEDS)
    iters, rounds = out.iters[:n], out.rounds[:n]
    max_it = int(staged.args[7])
    assert out.drained[:n].all()
    assert (rounds <= iters).all() and (iters <= max_it).all() and (iters > 0).all()
    for k in sorted(set(iters.tolist())):
        # a lane drains within a bound of k iterations exactly when it needs k or fewer
        assert (_run(staged, k).drained[:n] == (iters <= k)).all()
        assert (_run(staged, k - 1).drained[:n] == (iters <= k - 1)).all()


def _single_acc_cell(service):
    """One accelerator, one one-layer model taking ``service`` s, releases
    every 1/16 s over 0.5 s (8 of them), deadlines far away."""
    plat = Platform("t", (Accelerator("a0", Dataflow.WS, 1024),))
    lat = np.array([[service]])
    deadline = 100.0
    plan = ModelPlan(model=DnnModel("m", [matmul("l0", 8, 8, 8)], redundancy=0.5),
                     platform=plat, deadline=deadline, lat=lat,
                     budget=distribute_budgets(lat, deadline), variants={}, theta=0.9)
    return [plan], [TaskSpec(0, fps=16.0, arrival=PeriodicArrivals())]


@pytest.mark.parametrize("sched", ["fcfs", "terastal"])
@pytest.mark.parametrize("service,peak", [
    (1.0, 8),     # all 8 arrive before the first finish
    (0.1875, 6),  # 3/16 s each: at 6/16 and at 7/16 six are ready or running
])
def test_live_peak_is_the_most_requests_live_at_once(sched, service, peak):
    plans, tasks = _single_acc_cell(service)
    with jax.enable_x64(True):
        staged = stage_batch(plans, tasks, 0.5, make_scheduler(sched), [1, 2])
    out = _run(staged)
    nr_pad = np.shape(staged.args[2])[-1]
    assert out.live_peak[:2].tolist() == [peak, peak]
    assert peak <= nr_pad
    assert out.state[:2, :8].tolist() == [[3] * 8] * 2  # every request completes


@pytest.mark.parametrize("sched,faults,soft", [
    ("fcfs", None, False), ("edf", None, False), ("dream", None, False),
    ("terastal", None, False), ("terastal(backfill_mode=paper)", None, False),
    ("edf", THROTTLE, True),
])
def test_counters_change_no_result(sched, faults, soft, monkeypatch):
    """The loop with its counters, run under a recorder, gives the SoA
    engine's results, and the same as with no recorder."""
    plans, tasks, procs = _cell()
    want = [simulate(plans, tasks, DUR, make_scheduler(sched), seed=s, processes=procs,
                     engine="soa", faults=faults).fingerprint() for s in SEEDS]
    if soft:
        monkeypatch.setattr(f64, "for_platform", lambda platform=None: f64.SOFT)
    off = simulate_batch(plans, tasks, DUR, make_scheduler(sched), SEEDS, procs, faults=faults)
    with obs.record() as rec:
        on = simulate_batch(plans, tasks, DUR, make_scheduler(sched), SEEDS, procs,
                            faults=faults)
    assert [r.fingerprint() for r in on] == [r.fingerprint() for r in off] == want
    (b,) = rec.batches
    assert 0 < b["counters"]["live_peak"] <= b["counters"]["nr_pad"]


def test_a_recorder_keeps_one_span_tree_per_batch():
    plans, tasks, procs = _cell()
    batches = ([0, 1], [2, 3, 4])
    for seeds in batches:  # compiles the program of each shape
        simulate_batch(plans, tasks, DUR, make_scheduler("terastal"), seeds, procs)
    with obs.record() as rec:
        for seeds in batches:
            simulate_batch(plans, tasks, DUR, make_scheduler("terastal"), seeds, procs)
    assert [b["id"] for b in rec.batches] == [0, 1]
    for b, lanes in zip(rec.batches, (2, 3)):
        assert {s.batch for s in b["spans"]} == {b["id"]}
        assert {s.name: s.parent for s in b["spans"]} == TREE
        assert len(b["spans"]) == len(TREE)
        assert all(s.start_ns <= s.end_ns for s in b["spans"])
        c = b["counters"]
        assert c["lanes"] == lanes and c["compiles"] == 0
        assert c["rounds_sum"] <= c["iters_sum"] <= lanes * c["iters_max"]
        assert c["iters_max"] <= c["max_it"] and c["replayed"] <= c["releases"]
        assert 0 < c["live_peak"] <= c["nr_pad"]
    s = rec.summary()
    assert set(s["spans"]) == set(TREE)
    for name, row in s["spans"].items():
        assert row["calls"] == 2 and 0 <= row["self_ms"] <= row["total_ms"]
    assert s["counters"]["lanes"] == {"sum": 5, "mean": 2.5, "max": 3}
    # a parent's self time is what its children leave uncovered
    dur = {}
    for b in rec.batches:
        for sp in b["spans"]:
            dur[sp.name] = dur.get(sp.name, 0) + sp.end_ns - sp.start_ns
    kids = sum(dur[k] for k, p in TREE.items() if p == "assemble")
    assert s["spans"]["assemble"]["self_ms"] == pytest.approx((dur["assemble"] - kids) * 1e-6)
    assert s["spans"]["loop"]["self_ms"] == s["spans"]["loop"]["total_ms"]


def test_nothing_is_kept_without_a_recorder():
    plans, tasks, procs = _cell()
    assert obs.active() is None
    with jax.enable_x64(True):
        staged = stage_batch(plans, tasks, DUR, make_scheduler("terastal"), SEEDS, procs)
    assert staged.batch is None
    assert obs.open_batch() is None
    obs.count("lanes", 3)
    with obs.span("loop"):
        pass
    with obs.record() as rec:
        pass
    assert rec.batches == [] and obs.active() is None
    with obs.record():
        with pytest.raises(RuntimeError):
            with obs.record():
                pass


def test_spans_are_on_the_profilers_clock():
    """A recorded span and its annotation in a profiler trace start at the
    same instant of the profiler's host clock."""
    from jax._src.lib import _profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    session = _profiler.ProfilerSession(opts)
    with obs.record() as rec:
        obs.open_batch()
        with obs.span("loop"):
            jax.block_until_ready(jax.numpy.ones(8) + 1)
    data = jax.profiler.ProfileData.from_serialized_xspace(session.stop())
    start = [int(v) for p in data.planes for k, v in p.stats if k == "profile_start_time"]
    found = [ev.start_ns for p in data.planes for line in p.lines for ev in line.events
             if ev.name == "engine.loop"]
    assert len(start) == 1 and len(found) == 1
    (span,) = rec.batches[0]["spans"]
    assert abs(start[0] + found[0] - span.start_ns) < 1e6  # within a millisecond


@pytest.mark.parametrize("sched,faults,scopes", [
    ("terastal", None, {"pop", "bind", "counters", "drop", "round", "round/stage1",
                        "round/stage2", "apply"}),
    ("edf", THROTTLE, {"pop", "bind", "counters", "fault", "epoch", "drop", "round", "apply"}),
])
def test_the_lowered_loop_names_every_scope_the_reduction_reads(sched, faults, scopes):
    plans, tasks, procs = _cell()
    with jax.enable_x64(True):
        staged = stage_batch(plans, tasks, DUR, make_scheduler(sched), SEEDS, procs,
                             faults=faults)
        text = _run_trials.lower(*staged.args, **staged.static).as_text(debug_info=True)
    for s in scopes:
        assert f"/while/body/{s}/" in text, s
    assert {s.split("/")[0] for s in scopes} <= set(obs.SCOPES)
    with pytest.raises(ValueError):
        obs.scope("stage1")
