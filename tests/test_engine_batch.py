"""Device-resident mega-batched trials (``engine="batch"``).

Pins the three contracts the batched engine ships with:

* **fingerprint parity** — every lane of one ``simulate_batch`` call
  matches ``simulate(..., engine="soa")`` exactly (the full
  :meth:`SimResult.fingerprint`: busy arrays, rounds, per-model integer
  counters and float retained sums) across the pinned differential grid
  of schedulers x arrival processes x inert budget axes;
* **named rejection** — every axis the device rollout cannot cover
  raises :class:`BatchUnsupportedError` (a ``ValueError``), never a
  silent fallback to another engine;
* **campaign integration** — ``run_trial_batch`` reproduces
  ``run_trial`` metric for metric, and ``TrialExecutor`` routes
  ``engine="batch"`` specs through the grouped device path while
  preserving result and callback order.
"""

import dataclasses
import math

import jax
import numpy as np
import pytest

from repro.core import make_scheduler, simulate
from repro.core.campaign import TrialExecutor, TrialSpec, run_trial, run_trial_batch
from repro.core.engine_batch import BatchUnsupportedError, simulate_batch
from repro.core.scheduler import Scheduler, TerastalScheduler
from repro.core.simulator import ClosedLoopClients, make_arrival_process
from repro.core.workload import SATURATION_SCENARIOS
from repro.costmodel.maestro import PLATFORMS

SEEDS = [0, 1, 2]
DUR = 0.12
CELL, PLATFORM = "saturation_3x", "4k_1ws2os"


def _plans_tasks():
    return SATURATION_SCENARIOS[CELL].plans(PLATFORMS[PLATFORM])


def _procs(tasks, arrival):
    proc = make_arrival_process(arrival)
    return [t.arrival or proc for t in tasks]


def _soa_fingerprints(plans, tasks, sched_spec, procs, seeds, **kw):
    return [
        simulate(plans, tasks, DUR, make_scheduler(sched_spec), seed=s,
                 processes=procs, engine="soa", **kw).fingerprint()
        for s in seeds
    ]


# ------------------------------------------------------ differential grid ----


@pytest.mark.parametrize("sched_spec", [
    "fcfs", "edf", "dream",
    "terastal",                        # ef backfill, budgets + variants
    "terastal(backfill_mode=paper)",
])
@pytest.mark.parametrize("arrival", ["poisson", "periodic"])
def test_batch_matches_soa_on_differential_grid(sched_spec, arrival):
    """One vmapped device program vs B scalar SoA trials: the full
    SimResult fingerprint is identical on every lane, for every
    supported scheduler kernel and pre-generable arrival process."""
    plans, tasks = _plans_tasks()
    procs = _procs(tasks, arrival)
    batch = simulate_batch(plans, tasks, DUR, make_scheduler(sched_spec),
                           SEEDS, processes=procs)
    ref = _soa_fingerprints(plans, tasks, sched_spec, procs, SEEDS)
    for s, res, want in zip(SEEDS, batch, ref):
        assert res.fingerprint() == want, (sched_spec, arrival, s)


@pytest.mark.parametrize("sched_spec,faults", [
    ("terastal", None),
    ("edf", "throttle(acc=0,start=0.02,duration=0.05,factor=4.0,retighten=true)"),
])
def test_soft_binary64_lanes_match_soa(sched_spec, faults, monkeypatch):
    """The software binary64 the engine runs on a TPU (whose float64 is
    not IEEE) keeps every lane fingerprint-identical to SoA — checked
    here on the CPU, where both arithmetics are available."""
    from repro.core import f64

    plans, tasks = _plans_tasks()
    procs = _procs(tasks, "poisson")
    monkeypatch.setattr(f64, "for_platform", lambda platform=None: f64.SOFT)
    batch = simulate_batch(plans, tasks, DUR, make_scheduler(sched_spec), SEEDS,
                           processes=procs, faults=faults)
    monkeypatch.undo()
    ref = _soa_fingerprints(plans, tasks, sched_spec, procs, SEEDS, faults=faults)
    for s, res, want in zip(SEEDS, batch, ref):
        assert res.fingerprint() == want, (sched_spec, faults, s)


def test_batch_matches_soa_with_inert_budget_axes():
    """The inert budget axes — explicit static policy, admission="none"
    — are supported and stay fingerprint-exact; they must not be
    confused with the *online* axes the engine rejects."""
    plans, tasks = _plans_tasks()
    procs = _procs(tasks, "poisson")
    batch = simulate_batch(
        plans, tasks, DUR, make_scheduler("terastal"), SEEDS,
        processes=procs, budget_policy="static", admission="none")
    ref = _soa_fingerprints(plans, tasks, "terastal", procs, SEEDS,
                            budget_policy="static", admission="none")
    for s, res, want in zip(SEEDS, batch, ref):
        assert res.fingerprint() == want, s


def test_simulate_engine_batch_dispatch():
    """simulate(engine="batch") routes a single-seed trial through the
    batched engine and returns the same fingerprint as SoA."""
    plans, tasks = _plans_tasks()
    got = simulate(plans, tasks, DUR, make_scheduler("terastal"), seed=1,
                   engine="batch")
    want = simulate(plans, tasks, DUR, make_scheduler("terastal"), seed=1,
                    engine="soa")
    assert got.fingerprint() == want.fingerprint()


# --------------------------------------------------------- named rejection ----


def test_unsupported_axes_raise_named_errors():
    """Every unsupported axis raises BatchUnsupportedError (a ValueError
    subclass) with a message naming the axis — never a silent fallback."""
    assert issubclass(BatchUnsupportedError, ValueError)
    plans, tasks = _plans_tasks()
    sched = make_scheduler("terastal")

    class WeirdScheduler(Scheduler):
        name = "weird"

        def schedule_round(self, *a, **kw):  # pragma: no cover
            raise NotImplementedError

    with pytest.raises(BatchUnsupportedError, match="no kernel for WeirdScheduler"):
        simulate_batch(plans, tasks, DUR, WeirdScheduler(), SEEDS)
    # subclasses of supported kernels are rejected too (exact-type check:
    # an overridden method would silently diverge from the device kernel)
    class TweakedTerastal(TerastalScheduler):
        pass

    with pytest.raises(BatchUnsupportedError, match="no kernel"):
        simulate_batch(plans, tasks, DUR, TweakedTerastal(), SEEDS)
    with pytest.raises(BatchUnsupportedError, match="online budget policy"):
        simulate_batch(plans, tasks, DUR, sched, SEEDS, budget_policy="reclaim")
    from repro.core.budget_online import BudgetPolicy

    ticking = BudgetPolicy()
    ticking.tick_interval = 0.02
    with pytest.raises(BatchUnsupportedError, match="tick events"):
        simulate_batch(plans, tasks, DUR, sched, SEEDS, budget_policy=ticking)
    with pytest.raises(BatchUnsupportedError, match="admission policy"):
        simulate_batch(plans, tasks, DUR, sched, SEEDS,
                       admission="shed_early(margin=1.5)")
    closed = ClosedLoopClients(n_users=4, think_time=0.05)
    with pytest.raises(BatchUnsupportedError, match="closed-loop"):
        simulate_batch(plans, tasks, DUR, sched, SEEDS,
                       processes=[closed for _ in tasks])


def test_simulate_dispatch_propagates_named_error():
    plans, tasks = _plans_tasks()
    with pytest.raises(BatchUnsupportedError, match="admission policy"):
        simulate(plans, tasks, DUR, make_scheduler("terastal"), seed=0,
                 engine="batch", admission="shed_early(margin=1.5)")


# ----------------------------------------------------- campaign integration ----


def _spec(seed, **kw):
    return TrialSpec(CELL, PLATFORM, "terastal", duration=DUR, seed=seed, **kw)


def _metrics(tr):
    """Every TrialResult field except spec and wall_s (timing)."""
    return (tr.mean_miss_rate, tr.mean_accuracy_loss, tr.utilization,
            tr.rounds, tr.models_counted, tr.released, tr.completed,
            tr.dropped, tr.variants_applied, tr.shed)


def _assert_same_metrics(a, b):
    ma, mb = _metrics(a), _metrics(b)
    for x, y in zip(ma, mb):
        if isinstance(x, float) and math.isnan(x) and math.isnan(y):
            continue
        assert x == y, (ma, mb)


def test_run_trial_batch_matches_run_trial():
    specs = [_spec(s, engine="batch") for s in SEEDS]
    batched = run_trial_batch(specs)
    assert [r.spec for r in batched] == specs
    for sp, got in zip(specs, batched):
        want = run_trial(dataclasses.replace(sp, engine="soa"))
        _assert_same_metrics(got, want)


def test_run_trial_batch_rejects_mixed_specs():
    with pytest.raises(ValueError, match="identical except seed"):
        run_trial_batch([_spec(0, engine="batch"),
                         _spec(1, engine="batch", arrival="poisson")])


def test_executor_groups_batch_specs_preserving_order():
    """run_batch groups engine="batch" seed replicates into device
    programs, runs the rest through the scalar path, and emits results
    (and on_result callbacks) in the original specs order."""
    specs = [
        _spec(0, engine="batch"),
        _spec(0, engine="soa"),
        _spec(1, engine="batch"),
        _spec(2, engine="batch", arrival="poisson"),  # second group
        _spec(3, engine="batch"),
    ]
    seen = []
    ex = TrialExecutor(parallel=False)
    results = ex.run_batch(specs, on_result=lambda r: seen.append(r.spec))
    assert [r.spec for r in results] == specs
    assert seen == specs
    # the grouped lanes match their scalar twins
    for got in (results[0], results[2], results[4]):
        want = run_trial(dataclasses.replace(got.spec, engine="soa"))
        _assert_same_metrics(got, want)


# ------------------------------------------------------ round slot window ----

WIN_DUR = 1.5  # Table II rates: 135-139 releases pad to 192 slots
WIN_SEEDS = [0, 1]


def _poisson_multicam():
    from repro.core.workload import SCENARIOS

    plans, tasks = SCENARIOS["multicam_light"].plans(PLATFORMS[PLATFORM])
    return plans, tasks, _procs(tasks, "poisson")


def _brute_span(arr_t, dl12, n_ev):
    """The widest ``[first ready-able rid, ai)`` range over every time a
    round could run: each arrival time and a point between each pair."""
    best = 0
    for t, d, n in zip(arr_t, dl12, n_ev):
        t, d = t[:n], d[:n]
        nows = np.concatenate([t, (t[:-1] + t[1:]) / 2, t[-1:] + 1.0])
        for now in nows:
            ai = int((t <= now).sum())
            live = [r for r in range(ai) if d[r] >= now]
            if live:
                best = max(best, ai - min(live))
    return best


@pytest.mark.parametrize("seed", range(6))
def test_ready_span_bound_matches_brute_force(seed):
    """The host bound equals a direct scan of every round time, on
    arrival streams with tied times and deadlines that do not grow with
    rid (models with different relative deadlines)."""
    from repro.core.engine_batch import ready_span_bound

    rng = np.random.default_rng(seed)
    B, NR = 3, 64
    arr_t = np.full((B, NR), np.inf)
    dl12 = np.full((B, NR), np.inf)
    n_ev = rng.integers(1, NR + 1, size=B)
    n_ev[0] = NR
    for b, n in enumerate(n_ev):
        t = np.sort(np.round(rng.uniform(0.0, 1.0, n), 2))  # ties
        rel = rng.choice([0.02, 0.05, 0.3], size=n)
        arr_t[b, :n] = t
        dl12[b, :n] = t + rel + 1e-12
    got = ready_span_bound(arr_t, dl12, n_ev)
    assert got == _brute_span(arr_t, dl12, n_ev)
    assert 1 <= got <= NR


def test_saturation_keeps_every_slot():
    """Saturation traffic keeps requests ready for most of a 4-period
    deadline: its live span is its release count, so the round keeps
    every padded slot (``win == nr_pad``), past 128 slots too."""
    from repro.core import obs
    from repro.core.engine_batch import stage_batch

    plans, tasks = _plans_tasks()
    with jax.enable_x64(True), obs.record() as rec:
        staged = stage_batch(plans, tasks, DUR, make_scheduler("terastal"), SEEDS)
    assert staged.static["win"] == staged.args[2].shape[-1]
    plans5, tasks5 = SATURATION_SCENARIOS["saturation_5x"].plans(PLATFORMS[PLATFORM])
    with jax.enable_x64(True), obs.record() as rec:
        staged = stage_batch(plans5, tasks5, 0.15, make_scheduler("terastal"), SEEDS)
    c = rec.batches[0]["counters"]
    assert c["nr_pad"] == 192 and c["span_bound"] > 128
    assert staged.static["win"] == c["round_slots"] == 192


@pytest.mark.parametrize("arith", ["native", "soft"])
def test_unwindowed_round_past_128_slots_matches_soa(arith, monkeypatch):
    """Saturation traffic padded to 192 slots keeps every slot in its
    round (``win == nr_pad``): the latency planes are bound and read at
    full width past 128 slots, and every lane stays fingerprint-identical
    to SoA in both arithmetics."""
    from repro.core import f64, obs

    plans, tasks = SATURATION_SCENARIOS["saturation_5x"].plans(PLATFORMS[PLATFORM])
    procs = _procs(tasks, "poisson")
    if arith == "soft":
        monkeypatch.setattr(f64, "for_platform", lambda platform=None: f64.SOFT)
    with obs.record() as rec:
        batch = simulate_batch(plans, tasks, 0.15, make_scheduler("terastal"),
                               SEEDS, processes=procs)
    monkeypatch.undo()
    c = rec.batches[0]["counters"]
    assert c["nr_pad"] == c["round_slots"] == 192
    ref = [simulate(plans, tasks, 0.15, make_scheduler("terastal"), seed=s,
                    processes=procs, engine="soa").fingerprint() for s in SEEDS]
    for s, res, want in zip(SEEDS, batch, ref):
        assert res.fingerprint() == want, (arith, s)


@pytest.mark.parametrize("arith", ["native", "soft"])
@pytest.mark.parametrize("sched_spec", [
    "terastal", "terastal(backfill_mode=paper)", "edf",
])
def test_windowed_round_matches_soa(sched_spec, arith, monkeypatch):
    """A Table II multicam Poisson cell padded to 192 slots, whose ready
    set provably spans far fewer rids, runs its round over a 128-slot
    window; every lane stays fingerprint-identical to SoA in both
    arithmetics."""
    from repro.core import f64, obs

    plans, tasks, procs = _poisson_multicam()
    if arith == "soft":
        monkeypatch.setattr(f64, "for_platform", lambda platform=None: f64.SOFT)
    with obs.record() as rec:
        batch = simulate_batch(plans, tasks, WIN_DUR, make_scheduler(sched_spec),
                               WIN_SEEDS, processes=procs)
    monkeypatch.undo()
    c = rec.batches[0]["counters"]
    assert c["nr_pad"] == 192 and c["round_slots"] == 128 and c["span_bound"] <= 128
    ref = [simulate(plans, tasks, WIN_DUR, make_scheduler(sched_spec), seed=s,
                    processes=procs, engine="soa").fingerprint() for s in WIN_SEEDS]
    for s, res, want in zip(WIN_SEEDS, batch, ref):
        assert res.fingerprint() == want, (sched_spec, arith, s)


@pytest.mark.parametrize("sched_spec,faults", [
    ("fcfs", None),
    ("dream", None),
    ("terastal", "throttle(acc=0,start=0.3,duration=0.6,factor=4.0,retighten=true)"),
    ("dream", "down(acc=1,start=0.2,duration=0.5)"),
])
def test_windowed_round_matches_soa_other_kinds_and_faults(sched_spec, faults):
    """The window reads the greedy keys' arrival and deadline rows, and
    under faults the epoch's re-bound rows; lanes stay identical to SoA."""
    from repro.core import obs

    plans, tasks, procs = _poisson_multicam()
    with obs.record() as rec:
        batch = simulate_batch(plans, tasks, WIN_DUR, make_scheduler(sched_spec),
                               WIN_SEEDS, processes=procs, faults=faults)
    assert rec.batches[0]["counters"]["round_slots"] == 128
    ref = [simulate(plans, tasks, WIN_DUR, make_scheduler(sched_spec), seed=s,
                    processes=procs, engine="soa", faults=faults).fingerprint()
           for s in WIN_SEEDS]
    for s, res, want in zip(WIN_SEEDS, batch, ref):
        assert res.fingerprint() == want, (sched_spec, faults, s)


def test_window_below_the_span_is_caught():
    """A window narrower than the ready span (forced here; the staging
    rule never picks one) trips the per-iteration guard, and assembly
    refuses the batch as an engine bug."""
    from repro.core.engine_batch import _run_trials, assemble_batch, stage_batch

    plans, tasks = _plans_tasks()
    sched = make_scheduler("terastal")
    with jax.enable_x64(True):
        staged = stage_batch(plans, tasks, DUR, sched, SEEDS)
        staged = staged._replace(static={**staged.static, "win": 16})
        out = jax.block_until_ready(_run_trials(*staged.args, **staged.static))
    assert np.asarray(out.win_miss)[: len(SEEDS)].any()
    with pytest.raises(RuntimeError, match="outside the round's proven slot window"):
        assemble_batch(out, staged, plans, tasks, DUR, sched)
