"""Campaign engine: arrival-process statistics, per-trial determinism,
parallel == serial, bootstrap aggregation math, and the regression pin
that the periodic process reproduces the seed simulator exactly."""

import concurrent.futures
import multiprocessing
import os

import numpy as np
import pytest

from repro.core import (
    Campaign,
    TrialSpec,
    bootstrap_ci,
    make_arrival_process,
    make_scheduler,
    run_trial,
    simulate,
)
from repro.core.simulator import (
    MmppArrivals,
    PeriodicArrivals,
    PoissonArrivals,
    TaskSpec,
    TraceArrivals,
    generate_arrivals,
)
from repro.core.specs import parse_call_spec
from repro.core.workload import SCENARIOS
from repro.costmodel.maestro import PLATFORMS


# ------------------------------------------------------ arrival processes -


def _seed_reference_arrivals(tasks, duration, seed):
    """The seed repo's generate_arrivals, verbatim: the regression oracle."""
    rng = np.random.default_rng(seed)
    out = []
    for task in tasks:
        n = int(np.floor(duration * task.fps))
        for j in range(n):
            if task.prob >= 1.0 or rng.random() < task.prob:
                out.append((j * task.period, task.model_idx))
    out.sort()
    return out


def test_periodic_process_bit_identical_to_seed_implementation():
    tasks = [TaskSpec(0, fps=60), TaskSpec(1, fps=30, prob=0.5), TaskSpec(2, fps=17)]
    for seed in range(5):
        ref = _seed_reference_arrivals(tasks, 3.0, seed)
        assert generate_arrivals(tasks, 3.0, seed) == ref
        procs = [PeriodicArrivals()] * len(tasks)
        assert generate_arrivals(tasks, 3.0, seed, processes=procs) == ref


def _periodic_sample_loop(proc, task, duration, rng):
    """The original per-release loop implementation of
    PeriodicArrivals.sample, verbatim: the fast-path regression oracle."""
    out = []
    n = int(np.floor(duration * task.fps))
    for j in range(n):
        if task.prob >= 1.0 or rng.random() < task.prob:
            t = j * task.period
            if proc.jitter > 0.0:
                t += rng.random() * proc.jitter * task.period
            out.append(t)
    return out


def test_periodic_fast_paths_match_loop_version():
    """The vectorized PeriodicArrivals paths (prob>=1 arange emission,
    batched thinning/jitter draws) must equal the scalar loop exactly —
    same values AND same rng-stream consumption, so everything drawn
    afterwards from the shared stream is unchanged too."""
    for prob, jitter in ((1.0, 0.0), (1.0, 0.4), (0.5, 0.0), (0.5, 0.4)):
        task = TaskSpec(0, fps=37, prob=prob)
        proc = PeriodicArrivals(jitter=jitter)
        for seed in range(4):
            r1, r2 = np.random.default_rng(seed), np.random.default_rng(seed)
            got = proc.sample(task, 3.0, r1)
            want = _periodic_sample_loop(proc, task, 3.0, r2)
            assert got == want, (prob, jitter, seed)
            assert all(isinstance(t, float) for t in got)
            # identical stream consumption: the next draw agrees
            assert r1.random() == r2.random(), (prob, jitter, seed)


def test_periodic_jitter_bounded_and_rate_preserving():
    task = TaskSpec(0, fps=30)
    rng = np.random.default_rng(7)
    times = PeriodicArrivals(jitter=0.5).sample(task, 4.0, rng)
    assert len(times) == int(np.floor(4.0 * 30))
    base = np.arange(len(times)) * task.period
    off = np.asarray(times) - base
    assert (off >= 0).all() and (off <= 0.5 * task.period + 1e-12).all()


def test_poisson_interarrival_statistics():
    task = TaskSpec(0, fps=200)
    rng = np.random.default_rng(0)
    times = np.asarray(PoissonArrivals().sample(task, 60.0, rng))
    gaps = np.diff(times)
    # mean rate ~ fps, exponential gaps: CV ~ 1
    assert len(times) == pytest.approx(200 * 60, rel=0.05)
    assert gaps.mean() == pytest.approx(1 / 200, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


def test_mmpp_burstiness_and_mean_rate():
    task = TaskSpec(0, fps=200)
    rng = np.random.default_rng(0)
    times = np.asarray(MmppArrivals(burstiness=4.0).sample(task, 60.0, rng))
    gaps = np.diff(times)
    # long-run mean rate is preserved ...
    assert len(times) == pytest.approx(200 * 60, rel=0.10)
    # ... but arrivals are much burstier than Poisson (CV >> 1), and the
    # burst structure is real: within-ON gaps cluster near 1/(b*fps)
    assert gaps.std() / gaps.mean() > 2.0
    assert np.median(gaps) < 1.5 / (4.0 * 200)
    # burstiness=1 degenerates to ~Poisson
    rng = np.random.default_rng(0)
    g1 = np.diff(MmppArrivals(burstiness=1.0).sample(task, 60.0, rng))
    assert g1.std() / g1.mean() == pytest.approx(1.0, abs=0.15)
    # mean rate preserved even past the on-fraction boundary (b > 1/p):
    # on_fraction clamps down instead of the offered load doubling
    rng = np.random.default_rng(0)
    t8 = MmppArrivals(burstiness=8.0, on_fraction=0.25).sample(task, 60.0, rng)
    assert len(t8) == pytest.approx(200 * 60, rel=0.15)


def test_campaign_respects_per_entry_arrival():
    """A scenario entry that pins its own arrival process keeps it; the
    campaign's arrival spec only fills the unpinned entries."""
    tasks = [
        TaskSpec(0, fps=10, arrival=PeriodicArrivals()),
        TaskSpec(1, fps=10),
    ]
    proc = PoissonArrivals()
    arr = generate_arrivals(tasks, 2.0, seed=0, processes=[t.arrival or proc for t in tasks])
    t0 = sorted(a for a, m in arr if m == 0)
    t1 = [a for a, m in arr if m == 1]
    assert t0 == [j * 0.1 for j in range(20)]  # pinned entry stayed periodic
    assert len(t1) > 0 and t1 != [j * 0.1 for j in range(len(t1))]  # default applied


def test_trace_replay_cycles_and_clips():
    task = TaskSpec(0, fps=10)
    proc = TraceArrivals(times=(0.0, 0.25, 0.9), span=1.0)
    rng = np.random.default_rng(0)
    times = proc.sample(task, 2.5, rng)
    assert times == [0.0, 0.25, 0.9, 1.0, 1.25, 1.9, 2.0, 2.25]
    rng = np.random.default_rng(0)
    assert TraceArrivals(times=(0.0, 0.25, 0.9), span=1.0, cycle=False).sample(
        task, 2.5, rng
    ) == [0.0, 0.25, 0.9]


def test_make_arrival_process_specs():
    assert make_arrival_process(None) == PeriodicArrivals()
    assert make_arrival_process("periodic") == PeriodicArrivals()
    assert make_arrival_process("periodic(jitter=0.5)") == PeriodicArrivals(jitter=0.5)
    assert make_arrival_process("mmpp(burstiness=8,on_fraction=0.1)") == MmppArrivals(
        burstiness=8, on_fraction=0.1
    )
    p = PoissonArrivals(rate_scale=2.0)
    assert make_arrival_process(p) is p
    with pytest.raises(KeyError):
        make_arrival_process("weibull")
    with pytest.raises(ValueError):
        make_arrival_process("trace")  # empty replay would mask every miss
    # unknown kwargs name the process and its valid parameters instead of
    # surfacing a bare dataclass TypeError deep inside a pool worker
    with pytest.raises(ValueError, match=r"mmpp.*burstiness"):
        make_arrival_process("mmpp(burstines=4)")
    with pytest.raises(ValueError, match=r"periodic.*jitter"):
        make_arrival_process("periodic(jiter=0.5)")
    assert parse_call_spec("a(x=1,y=true,z=hi)") == ("a", {"x": 1, "y": True, "z": "hi"})
    with pytest.raises(ValueError):
        parse_call_spec("periodic(jitter=0.5))")  # stray paren must not become a str value


def test_make_scheduler_call_specs():
    s = make_scheduler("terastal(backfill_mode=paper)")
    assert s.name == "terastal" and s.backfill_mode == "paper"
    with pytest.raises(KeyError):
        make_scheduler("edf(backfill_mode=paper)")  # baselines take no kwargs
    with pytest.raises(TypeError):
        make_scheduler("terastal(bogus=1)")
    with pytest.raises(KeyError, match="unknown scheduler"):
        make_scheduler("terstal(backfill_mode=paper)")  # typo -> unknown, not kwargs error


# ------------------------------------------------------------ determinism -


def test_trial_deterministic_per_seed_and_seed_sensitive():
    spec = TrialSpec("ar_social", "4k_1ws2os", "terastal", arrival="mmpp(burstiness=4)",
                     seed=5, duration=1.0)
    import dataclasses

    a, b = run_trial(spec), run_trial(spec)
    assert dataclasses.replace(a, wall_s=0.0) == dataclasses.replace(b, wall_s=0.0)
    c = run_trial(TrialSpec("ar_social", "4k_1ws2os", "terastal",
                            arrival="mmpp(burstiness=4)", seed=6, duration=1.0))
    assert c.released != a.released or c.mean_miss_rate != a.mean_miss_rate


def test_campaign_parallel_equals_serial():
    camp = Campaign(scenarios=("ar_social",), platforms=("4k_1ws2os",),
                    schedulers=("fcfs", "terastal"), arrivals=("periodic", "poisson"),
                    seeds=(0, 1, 2), duration=0.5)
    ser = camp.run(parallel=False)
    par = camp.run(parallel=True, max_workers=2)
    assert [t.spec for t in ser.trials] == [s for s in camp.trials()]
    assert [(t.spec, t.mean_miss_rate, t.released, t.utilization) for t in ser.trials] == [
        (t.spec, t.mean_miss_rate, t.released, t.utilization) for t in par.trials
    ]


def test_campaign_trial_matches_direct_simulate():
    """The reusable trial runner is the seed serial loop, exactly."""
    sc, pn = "ar_gaming_light", "4k_1os2ws"
    plans, tasks = SCENARIOS[sc].plans(PLATFORMS[pn])
    for seed in (0, 1):
        ref = simulate(plans, tasks, 1.0, make_scheduler("edf"), seed=seed)
        got = run_trial(TrialSpec(sc, pn, "edf", seed=seed, duration=1.0))
        assert got.mean_miss_rate == ref.mean_miss_rate
        assert got.mean_accuracy_loss == ref.mean_accuracy_loss(plans)
        assert got.released == sum(s.released for s in ref.per_model.values())


def test_campaign_budget_policy_axis():
    """budget_policy is a first-class grid dimension: expansion order puts
    it between arrival and seed, and run_trial threads the call-spec
    through to the simulator."""
    camp = Campaign(scenarios=("ar_gaming_heavy",), platforms=("6k_1ws2os",),
                    schedulers=("terastal",), arrivals=("mmpp(burstiness=4)",),
                    budget_policies=("static", "adaptive(tick=0.02)"),
                    seeds=(0, 1), duration=1.0)
    specs = camp.trials()
    assert [(s.budget_policy, s.seed) for s in specs] == [
        ("static", 0), ("static", 1),
        ("adaptive(tick=0.02)", 0), ("adaptive(tick=0.02)", 1),
    ]
    # pass-through: the trial runner reproduces direct simulate() exactly
    plans, tasks = SCENARIOS["ar_gaming_heavy"].plans(PLATFORMS["6k_1ws2os"])
    proc = make_arrival_process("mmpp(burstiness=4)")
    for spec in specs:
        ref = simulate(plans, tasks, 1.0, make_scheduler("terastal"), seed=spec.seed,
                       processes=[proc] * len(tasks), budget_policy=spec.budget_policy)
        got = run_trial(spec)
        assert got.mean_miss_rate == ref.mean_miss_rate
        assert got.released == sum(s.released for s in ref.per_model.values())
    # the policy axis genuinely changes terastal's behavior on bursty load
    res = camp.run(parallel=False)
    by_pol = {}
    for t in res.trials:
        by_pol.setdefault(t.spec.budget_policy, []).append(t.mean_miss_rate)
    assert by_pol["static"] != by_pol["adaptive(tick=0.02)"]


def test_warm_plan_cache_initializer(monkeypatch):
    """The pool initializer primes the per-process offline-plan cache for
    every campaign cell, so spawn workers skip the Algorithm-1 rebuild on
    their first trial (fork workers inherit it; the initializer is then a
    cache hit), and pins the worker's JAX to the CPU so no worker opens
    the chip.  Campaign.run must hand the initializer + its cell keys to
    the executor it constructs."""
    from repro.core import campaign as campaign_mod
    from repro.core.campaign import _PLAN_CACHE, _warm_plan_cache

    key = ("ar_social", "4k_1ws2os", 0.90, True)
    _PLAN_CACHE.pop(key, None)
    _warm_plan_cache([key])
    assert key in _PLAN_CACHE
    plans, tasks = _PLAN_CACHE[key]
    assert len(plans) == len(tasks) == len(SCENARIOS["ar_social"].entries)

    # behavioral: Campaign.run wires the initializer into the pool it
    # builds (stub executor: run the initializer the way a fresh spawn
    # worker would, then map serially)
    captured = {}

    class FakeExecutor:
        def __init__(self, max_workers=None, mp_context=None,
                     initializer=None, initargs=()):
            captured["initializer"] = initializer
            captured["initargs"] = initargs

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def map(self, fn, specs, chunksize=1):
            captured["initializer"](*captured["initargs"])  # worker startup
            return [fn(s) for s in list(specs)]

        def shutdown(self, wait=True):
            pass

    monkeypatch.setattr(
        campaign_mod.concurrent.futures, "ProcessPoolExecutor", FakeExecutor
    )
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    camp = Campaign(scenarios=("ar_social",), platforms=("4k_1ws2os",),
                    schedulers=("fcfs",), seeds=(0, 1), duration=0.3)
    res = camp.run(parallel=True, max_workers=2)
    assert len(res.trials) == 2
    assert captured["initializer"] is campaign_mod._init_worker
    assert key in captured["initargs"][0]  # the campaign's cells were handed over
    assert os.environ["JAX_PLATFORMS"] == "cpu"  # worker startup pinned the CPU


def _worker_jax_platforms():
    import jax

    return jax.config.jax_platforms


def test_pool_workers_pin_jax_to_cpu(monkeypatch):
    """A spawn worker started through the executor's initializer has its
    JAX pinned to the CPU even when the parent's environment names no
    platform, so it can never open the chip the parent holds; specs that
    run a device program never reach the pool."""
    from repro.core.campaign import TrialExecutor, TrialSpec, _init_worker

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(
        max_workers=1, mp_context=ctx, initializer=_init_worker, initargs=([],)
    ) as pool:
        assert pool.submit(_worker_jax_platforms).result(timeout=300) == "cpu"

    ex = TrialExecutor(parallel=True, max_workers=2)
    spec = TrialSpec("ar_social", "4k_1ws2os", "terastal", duration=0.1,
                     engine="batch")
    assert type(ex.submit(spec)).__name__ == "_ImmediateFuture"
    assert ex._pool is None  # no worker was started for them
    ex.close()


def test_campaign_engine_axis_threads_through():
    """TrialSpec.engine reaches simulate(): the reference and SoA engines
    must produce identical trial rows (the engine axis never changes any
    metric), and Campaign.engine stamps every spec."""
    import dataclasses

    camp = Campaign(scenarios=("ar_social",), platforms=("4k_1ws2os",),
                    schedulers=("terastal",), arrivals=("mmpp(burstiness=4)",),
                    seeds=(0, 1), duration=0.5, engine="reference")
    assert all(s.engine == "reference" for s in camp.trials())
    for spec in camp.trials():
        ref = run_trial(spec)
        soa = run_trial(dataclasses.replace(spec, engine="soa"))
        assert (ref.mean_miss_rate, ref.released, ref.utilization) == (
            soa.mean_miss_rate, soa.released, soa.utilization)


# ------------------------------------------------------------ aggregation -


def test_bootstrap_ci_math():
    rng = np.random.default_rng(0)
    vals = rng.normal(10.0, 2.0, size=200)
    lo, hi = bootstrap_ci(vals, n_boot=2000, seed=1)
    assert lo < vals.mean() < hi
    # ~95% CI of the mean of N(10, 2^2) with n=200: half-width ~ 1.96*2/sqrt(200)
    half = 1.96 * 2.0 / np.sqrt(200)
    assert (hi - lo) / 2 == pytest.approx(half, rel=0.25)
    # deterministic; degenerate samples raise a *named* error instead of
    # the old silent point/NaN intervals that dressed up nothing as a CI
    assert bootstrap_ci(vals, n_boot=2000, seed=1) == (lo, hi)
    from repro.core import DegenerateSampleError

    with pytest.raises(DegenerateSampleError, match=">= 2 values"):
        bootstrap_ci([3.0])
    with pytest.raises(DegenerateSampleError, match=">= 2 values"):
        bootstrap_ci([])
    assert issubclass(DegenerateSampleError, ValueError)  # catchable broadly
    # more trials -> tighter interval
    lo2, hi2 = bootstrap_ci(vals[:20], n_boot=2000, seed=1)
    assert (hi2 - lo2) > (hi - lo)


def test_campaign_aggregate_groups_in_grid_order():
    camp = Campaign(scenarios=("ar_social",), platforms=("4k_1ws2os",),
                    schedulers=("fcfs", "edf"), arrivals=("periodic",),
                    seeds=(0, 1, 2, 3), duration=0.5)
    res = camp.run(parallel=False)
    agg = res.aggregate(by=("scheduler",))
    assert [r["scheduler"] for r in agg] == ["fcfs", "edf"]
    for r in agg:
        assert r["n_trials"] == 4
        assert r["mean_miss_rate_ci_lo"] - 1e-12 <= r["mean_miss_rate"] <= r["mean_miss_rate_ci_hi"] + 1e-12
    vals = [t.mean_miss_rate for t in res.trials if t.spec.scheduler == "fcfs"]
    assert agg[0]["mean_miss_rate"] == pytest.approx(float(np.mean(vals)))


# ------------------------------------------------------------- regression -


def test_fig5_campaign_rows_match_seed_serial_loop():
    """The refactored fig5 must emit exactly what the seed's serial loop
    produced: same cells, same schedulers, bit-identical per-seed means."""
    import benchmarks.fig5_miss_rate as fig5
    from repro.core import ALL_SCHEDULERS
    from repro.core.workload import scenario_platform_pairs

    seeds, duration = (0,), 0.5
    rows = fig5.run(duration=duration, seeds=seeds)
    i = 0
    for sc, plat in scenario_platform_pairs():
        plans, tasks = sc.plans(plat)
        for name in ALL_SCHEDULERS:
            miss, acc = [], []
            for seed in seeds:
                res = simulate(plans, tasks, duration, make_scheduler(name), seed=seed)
                miss.append(res.mean_miss_rate)
                acc.append(res.mean_accuracy_loss(plans))
            r = rows[i]
            assert (r["scenario"], r["platform"], r["scheduler"]) == (sc.name, plat.name, name)
            assert r["miss_rate_pct"] == 100 * float(np.mean(miss))
            assert r["acc_loss_pct"] == 100 * float(np.mean(acc))
            i += 1
    assert i == len(rows)
