"""SoA engine differential tests: the structure-of-arrays event loop
must reproduce the retained reference engine bit-for-bit — every
``SimResult`` field, across schedulers x arrival processes x budget
policies — plus engine-dispatch semantics and the scheduler-invocation
(batched simultaneous events) accounting."""

import numpy as np
import pytest

from repro.core import (
    ALL_SCHEDULERS,
    SCENARIOS,
    TaskSpec,
    make_scheduler,
    simulate,
)
from repro.core import engine_soa
from repro.core import simulator as simulator_mod
from repro.core.budget import distribute_budgets
from repro.core.scheduler import FcfsScheduler
from repro.core.simulator import SIM_ENGINES, make_arrival_process
from repro.core.variants import ModelPlan
from repro.costmodel.dnn_zoo import DnnModel
from repro.costmodel.layers import matmul
from repro.costmodel.maestro import PLATFORMS, Accelerator, Dataflow, Platform


def _fingerprint(res):
    """Every observable field, exact — the canonical SimResult equality
    key shared with the benchmark bit-identity gates."""
    return res.fingerprint()


def _both(plans, tasks, duration, sched_spec, seed, procs=None, policy="static"):
    ref = simulate(plans, tasks, duration, make_scheduler(sched_spec), seed=seed,
                   processes=procs, budget_policy=policy, engine="reference")
    soa = simulate(plans, tasks, duration, make_scheduler(sched_spec), seed=seed,
                   processes=procs, budget_policy=policy, engine="soa")
    return ref, soa


# ------------------------------------------------------------ parity ----


def test_soa_identical_all_schedulers_periodic():
    plans, tasks = SCENARIOS["ar_gaming_heavy"].plans(PLATFORMS["6k_1ws2os"])
    for name in ALL_SCHEDULERS:
        for seed in (0, 1):
            ref, soa = _both(plans, tasks, 1.0, name, seed)
            assert _fingerprint(ref) == _fingerprint(soa), (name, seed)


def test_soa_identical_across_arrivals_and_policies():
    plans, tasks = SCENARIOS["multicam_light"].plans(PLATFORMS["4k_1ws2os"])
    for arr in ("periodic(jitter=0.5)", "poisson", "mmpp(burstiness=8)"):
        procs = [make_arrival_process(arr)] * len(tasks)
        for name in ("fcfs", "edf", "dream", "terastal"):
            for policy in ("static", "reclaim", "adaptive"):
                ref, soa = _both(plans, tasks, 0.6, name, 3, procs, policy)
                assert _fingerprint(ref) == _fingerprint(soa), (arr, name, policy)


def test_soa_identical_backfill_ablations():
    """The stage-2 guard variants exercise the kernel's rarely-hit paths
    (unconditional backfill, positive-delta gate)."""
    plans, tasks = SCENARIOS["ar_social"].plans(PLATFORMS["4k_1ws2os"])
    procs = [make_arrival_process("mmpp(burstiness=4)")] * len(tasks)
    for spec in ("terastal(backfill_mode=paper)", "terastal(backfill_mode=positive)",
                 "terastal_no_budgeting", "terastal_no_variants"):
        ref, soa = _both(plans, tasks, 0.8, spec, 0, procs)
        assert _fingerprint(ref) == _fingerprint(soa), spec


def test_soa_identical_under_overload_drops():
    """Deep queues + early drops: the vectorized drop path and its scalar
    guard must fire exactly where the reference's per-request loop does."""
    from repro.costmodel.dnn_zoo import vgg11
    from repro.core.variants import build_model_plan

    plat = PLATFORMS["4k_1ws2os"]
    plan = build_model_plan(vgg11(448), plat, deadline=1 / 60)
    tasks = [TaskSpec(0, fps=60)]
    for name in ("fcfs", "terastal"):
        ref, soa = _both([plan], tasks, 1.0, name, 0)
        assert _fingerprint(ref) == _fingerprint(soa)
        assert sum(s.dropped for s in ref.per_model.values()) > 0  # drops exercised


# ------------------------------------------------- engine dispatch ----


class _CustomScheduler(FcfsScheduler):
    """A user subclass: schedule() semantics could differ, so 'auto' must
    route it through the reference engine rather than the FCFS kernel."""

    name = "custom"


def test_engine_dispatch_and_fallback():
    plans, tasks = SCENARIOS["ar_social"].plans(PLATFORMS["4k_1ws2os"])
    assert not engine_soa.supports_scheduler(_CustomScheduler())
    # auto == soa for built-ins
    auto = simulate(plans, tasks, 0.5, make_scheduler("edf"), seed=0)
    soa = simulate(plans, tasks, 0.5, make_scheduler("edf"), seed=0, engine="soa")
    assert _fingerprint(auto) == _fingerprint(soa)
    # subclass falls back to the reference loop but still runs fine
    ref = simulate(plans, tasks, 0.5, FcfsScheduler(), seed=0, engine="reference")
    via_auto = simulate(plans, tasks, 0.5, _CustomScheduler(), seed=0, engine="auto")
    got = _fingerprint(via_auto)
    want = _fingerprint(ref)
    assert got[1:] == want[1:]  # same trajectory, different scheduler_name
    # forcing soa on an unsupported scheduler is an explicit error
    with pytest.raises(ValueError, match="no kernel"):
        simulate(plans, tasks, 0.5, _CustomScheduler(), seed=0, engine="soa")
    with pytest.raises(ValueError, match="unknown engine"):
        simulate(plans, tasks, 0.5, FcfsScheduler(), seed=0, engine="fast")
    assert set(SIM_ENGINES) == {"auto", "soa", "reference", "batch"}


# ------------------------- scheduler-invocation hot path (batching) ----


def _tiny_cell(n_models=3, n_acc=3):
    """K single-layer models released in lockstep: every arrival instant
    carries K simultaneous arrival events, and all finish events land at
    distinct timestamps (same latency row, distinct accelerators)."""
    lat = np.array([[0.0031, 0.0037, 0.0041]])[:, :n_acc]
    plat = Platform("t", tuple(
        Accelerator(f"a{k}", Dataflow.WS, 1024) for k in range(n_acc)
    ))
    plans = []
    for i in range(n_models):
        model = DnnModel(f"m{i}", [matmul("l0", 8, 8, 8)], redundancy=0.5)
        plans.append(ModelPlan(
            model=model, platform=plat, deadline=0.1, lat=lat.copy(),
            budget=distribute_budgets(lat, 0.1), variants={}, theta=0.9,
        ))
    tasks = [TaskSpec(model_idx=i, fps=10) for i in range(n_models)]
    return plans, tasks


def test_scheduler_invoked_once_per_distinct_timestamp():
    """The batched-simultaneous-events path (the |heap[0] - now| < 1e-15
    skip) must invoke the scheduler exactly once per distinct event
    timestamp, in BOTH engines: K simultaneous arrivals trigger one
    round, not K.  With K single-layer models at the same fps over T
    periods, the distinct timestamps are T arrival instants + K*T
    distinct finishes."""
    K = 3
    plans, tasks = _tiny_cell(n_models=K)
    duration = 1.05
    T = int(np.floor(duration * 10))  # releases per task
    expected_rounds = T + K * T

    # reference engine: count drop_hopeless calls == invoke_scheduler calls
    calls = {"n": 0}
    orig_drop = simulator_mod.drop_hopeless

    def counting_drop(*a, **kw):
        calls["n"] += 1
        return orig_drop(*a, **kw)

    simulator_mod.drop_hopeless = counting_drop
    try:
        ref = simulate(plans, tasks, duration, make_scheduler("fcfs"), seed=0,
                       engine="reference")
    finally:
        simulator_mod.drop_hopeless = orig_drop
    assert calls["n"] == expected_rounds

    assert ref.rounds == expected_rounds  # reference engine telemetry

    # SoA engine: the per-result round counter must agree exactly
    soa = simulate(plans, tasks, duration, make_scheduler("fcfs"), seed=0,
                   engine="soa")
    assert soa.rounds == expected_rounds
    assert _fingerprint(ref) == _fingerprint(soa)
    # sanity: everything released and completed, nothing dropped
    assert sum(s.released for s in soa.per_model.values()) == K * T
    assert sum(s.completed for s in soa.per_model.values()) == K * T


def test_soa_builds_no_schedview():
    """The SoA engine hands schedulers array state, never a SchedView."""
    import repro.core.scheduler as sched_mod

    plans, tasks = SCENARIOS["ar_social"].plans(PLATFORMS["4k_1ws2os"])
    constructed = {"n": 0}
    orig = sched_mod.SchedView.__init__

    def counting_init(self, *a, **kw):
        constructed["n"] += 1
        return orig(self, *a, **kw)

    sched_mod.SchedView.__init__ = counting_init
    try:
        simulate(plans, tasks, 0.5, make_scheduler("terastal"), seed=0, engine="soa")
        assert constructed["n"] == 0
        simulate(plans, tasks, 0.5, make_scheduler("terastal"), seed=0,
                 engine="reference")
        assert constructed["n"] > 0  # the reference builds one per invocation
    finally:
        sched_mod.SchedView.__init__ = orig


# ------------------------------------------------ hypothesis property ----

try:  # optional test extra — only the property test skips without it
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

_CELLS = (
    ("ar_social", "4k_1ws2os"),
    ("ar_gaming_heavy", "6k_1ws2os"),
    ("multicam_light", "4k_1ws2os"),
)
_SCHEDS = ALL_SCHEDULERS + (
    "terastal(backfill_mode=paper)",
    "terastal(backfill_mode=positive)",
)
_ARRIVALS = (None, "periodic(jitter=0.7)", "poisson", "mmpp(burstiness=8)",
             "mmpp(burstiness=2,on_fraction=0.5)")
_POLICIES = ("static", "reclaim", "reclaim(spread=0.5)", "adaptive",
             "adaptive(tick=0.02,skew_min=2)")


if _HAVE_HYPOTHESIS:

    @st.composite
    def _scenarios(draw):
        cell = draw(st.sampled_from(_CELLS))
        sched = draw(st.sampled_from(_SCHEDS))
        arr = draw(st.sampled_from(_ARRIVALS))
        policy = draw(st.sampled_from(_POLICIES))
        seed = draw(st.integers(0, 2**16))
        duration = draw(st.sampled_from((0.15, 0.3, 0.5)))
        return cell, sched, arr, policy, seed, duration

    @given(_scenarios())
    @settings(max_examples=30, deadline=None)
    def test_soa_engine_differential_property(case):
        """Random (scenario x scheduler x arrival x budget-policy x seed)
        draws: the SoA engine's SimResult must equal the reference
        engine's bit-for-bit."""
        (sc, pn), sched, arr, policy, seed, duration = case
        plans, tasks = SCENARIOS[sc].plans(PLATFORMS[pn])
        procs = [make_arrival_process(arr)] * len(tasks) if arr else None
        ref, soa = _both(plans, tasks, duration, sched, seed, procs, policy)
        assert _fingerprint(ref) == _fingerprint(soa)

else:  # pragma: no cover

    @pytest.mark.skip(reason="hypothesis not installed (optional test extra)")
    def test_soa_engine_differential_property():
        pass
