"""Accelerator fault injection: spec validation, timeline determinism,
capability masking, fault-off bit-identity pins, ref-vs-SoA parity with
faults active, and the batch-engine rejection contract."""

import math

import numpy as np
import pytest

from repro.core import get_scenario, make_scheduler, simulate
from repro.core.campaign import _plans_for
from repro.core.engine_batch import BatchUnsupportedError, simulate_batch
from repro.core.faults import (
    FaultModel,
    FaultSpec,
    effective_plans,
    fault_multipliers,
    make_fault_model,
)
from repro.costmodel.maestro import PLATFORMS

from data_pre_pr8_fingerprints import PRE_PR8_FINGERPRINTS


def _cell(scenario, platform="6k_1ws2os", theta=0.90, variants=True):
    return _plans_for(scenario, platform, theta, variants)


def _both(plans, tasks, duration, sched, faults, seed=0, procs=None):
    ref = simulate(plans, tasks, duration, make_scheduler(sched), seed=seed,
                   processes=procs, faults=faults, engine="reference")
    soa = simulate(plans, tasks, duration, make_scheduler(sched), seed=seed,
                   processes=procs, faults=faults, engine="soa")
    return ref, soa


# ------------------------------------------------------ validation -------


def test_unknown_fault_kind_rejected():
    with pytest.raises(ValueError, match="unknown fault kind"):
        make_fault_model("meltdown(acc=0,start=0.1)")
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(kind="meltdown", acc=0)


@pytest.mark.parametrize("bad", [
    "down(acc=-1,start=0.1,duration=0.2)",
    "down(acc=0,start=-0.5,duration=0.2)",
    "down(acc=0,start=nan,duration=0.2)",
    "down(acc=0,start=0.1,duration=-1)",
    "throttle(acc=0,start=0.1,duration=0.2,factor=0)",
    "throttle(acc=0,start=0.1,duration=0.2,factor=inf)",
    "intermittent(acc=0,rate=0,mean_down=0.1)",
    "intermittent(acc=0,rate=5,mean_down=0)",
    "down(acc=0,start=0.1)",  # transient faults need a finite duration
])
def test_malformed_numbers_rejected(bad):
    with pytest.raises(ValueError):
        make_fault_model(bad)


def test_unknown_interrupted_policy_rejected():
    with pytest.raises(ValueError, match="interrupted-work policy"):
        make_fault_model("down(acc=0,start=0.1,duration=0.2,interrupted=pause)")


def test_conflicting_interrupted_policies_rejected():
    with pytest.raises(ValueError, match="conflicting interrupted"):
        make_fault_model(
            "down(acc=0,start=0.1,duration=0.2,interrupted=resume)"
            "+down(acc=1,start=0.1,duration=0.2,interrupted=restart)")


def test_overlapping_windows_rejected():
    with pytest.raises(ValueError, match="overlapping fault windows"):
        make_fault_model("down(acc=0,start=0.1,duration=0.5)"
                         "+throttle(acc=0,start=0.3,duration=0.2,factor=2)")
    with pytest.raises(ValueError, match="overlapping permanent"):
        make_fault_model("permanent(acc=2,start=0.1)+permanent(acc=2,start=0.5)")
    # different accelerators may overlap freely
    fm = make_fault_model("down(acc=0,start=0.1,duration=0.5)"
                          "+down(acc=1,start=0.1,duration=0.5)")
    assert fm.active and len(fm.faults) == 2


def test_intermittent_owns_its_accelerator():
    with pytest.raises(ValueError, match="intermittent fault cannot"):
        make_fault_model("intermittent(acc=0,rate=5,mean_down=0.05)"
                         "+down(acc=0,start=0.1,duration=0.2)")


def test_none_spellings_resolve_to_no_model():
    assert make_fault_model(None) is None
    assert make_fault_model("none") is None
    assert make_fault_model("  ") is None
    assert make_fault_model(FaultModel()) is None


def test_format_round_trips():
    for spec in (
        "down(acc=0,start=0.5,duration=1.0)",
        "throttle(acc=1,start=0.2,duration=0.5,factor=3.0)",
        "permanent(acc=1,start=0.4,interrupted=resume)",
        "intermittent(acc=2,rate=6.0,mean_down=0.08)",
        "down(acc=0,start=0.1,duration=0.2,interrupted=resume)"
        "+throttle(acc=2,start=0.2,duration=0.4,factor=2.5)",
    ):
        fm = make_fault_model(spec)
        again = make_fault_model(fm.format())
        assert again == fm, spec


def test_acc_out_of_platform_range_rejected_at_timeline():
    fm = make_fault_model("down(acc=7,start=0.1,duration=0.2)")
    with pytest.raises(ValueError, match="out of range"):
        fm.timeline(n_acc=3, duration=1.0, seed=0)


# -------------------------------------------------- timeline/masking ----


def test_timeline_deterministic_and_seed_varied():
    fm = make_fault_model("intermittent(acc=1,rate=8.0,mean_down=0.05)")
    ev0, n0 = fm.timeline(3, 2.0, seed=0)
    ev0b, n0b = fm.timeline(3, 2.0, seed=0)
    ev1, _ = fm.timeline(3, 2.0, seed=1)
    assert (ev0, n0) == (ev0b, n0b)  # reproducible per seed
    assert ev0 != ev1  # renewal draws differ across seeds
    assert n0 == sum(e.code == "down" for e in ev0)


def test_timeline_span_counting_and_clipping():
    fm = make_fault_model("down(acc=0,start=0.5,duration=1.0)"
                          "+down(acc=1,start=9.0,duration=1.0)")
    ev, n = fm.timeline(3, duration=2.0, seed=0)
    assert n == 1  # the acc=1 window starts past the horizon
    assert [(e.t, e.acc, e.code) for e in ev] == [(0.5, 0, "down"),
                                                  (1.5, 0, "up")]
    # permanent: down event only, no closing up
    evp, np_ = make_fault_model("permanent(acc=2,start=0.3)").timeline(3, 2.0, 0)
    assert np_ == 1 and [(e.t, e.code) for e in evp] == [(0.3, "down")]


def test_effective_plans_mask_and_scale():
    plans, _ = _cell("multicam_heavy")
    mult = fault_multipliers([1.0, 2.0, 1.0], [False, True, True])
    assert mult[0] == math.inf and mult[1] == 2.0
    eff = effective_plans(plans, mult)
    for p, q in zip(plans, eff):
        assert np.all(np.isinf(q.lat[:, 0]))
        np.testing.assert_allclose(q.lat[:, 1], 2.0 * p.lat[:, 1])
        np.testing.assert_allclose(q.lat[:, 2], p.lat[:, 2])
        for idx, v in q.variants.items():
            np.testing.assert_allclose(
                v.latencies[1], 2.0 * p.variants[idx].latencies[1])
        # budgets/accuracy untouched; originals not mutated
        assert q.budget is p.budget
    assert effective_plans(plans, np.ones(3))[0] is plans[0]  # identity


# ------------------------------------------- fault-off bit-identity -----


@pytest.mark.parametrize("key", sorted(PRE_PR8_FINGERPRINTS))
def test_fault_off_bit_identical_to_pre_pr(key):
    """The load-bearing pin of the whole axis: with no faults injected,
    both engines reproduce the exact fingerprints captured at the commit
    before this PR (the new evicted/remapped counters and faulted_spans
    are projected off and must be zero everywhere)."""
    scenario, platform, arrival, duration, sched, adm, engine = key
    sc = get_scenario(scenario)
    plans, tasks = sc.plans(PLATFORMS[platform],
                            arrival=None if arrival == "scenario" else arrival)
    res = simulate(plans, tasks, duration, make_scheduler(sched), seed=0,
                   processes=[t.arrival for t in tasks], admission=adm,
                   engine=engine)
    name, rounds, bt, bh, per, fsp = res.fingerprint()
    got = (name, rounds, bt, bh, {m: tuple(v[:8]) for m, v in per.items()})
    old = PRE_PR8_FINGERPRINTS[key]
    want = (old[0], old[1], old[2], old[3],
            {m: tuple(v) for m, v in old[4].items()})
    assert got == want
    assert fsp == 0
    for v in per.values():
        assert v[8] == 0 and v[9] == 0  # evicted == remapped == 0


def test_explicit_none_spec_is_noop():
    plans, tasks = _cell("multicam_heavy")
    base = simulate(plans, tasks, 0.5, make_scheduler("terastal"), seed=0)
    none = simulate(plans, tasks, 0.5, make_scheduler("terastal"), seed=0,
                    faults="none")
    assert base.fingerprint() == none.fingerprint()


def test_window_past_horizon_is_noop():
    plans, tasks = _cell("multicam_heavy")
    base = simulate(plans, tasks, 0.5, make_scheduler("terastal"), seed=0)
    late = simulate(plans, tasks, 0.5, make_scheduler("terastal"), seed=0,
                    faults="down(acc=0,start=9.0,duration=1.0)")
    assert base.fingerprint() == late.fingerprint()
    assert late.faulted_spans == 0


# ------------------------------------------------- engine parity --------


FAULT_GRID = (
    "down(acc=0,start=0.1,duration=0.2)",
    "down(acc=0,start=0.1,duration=0.2,interrupted=resume)",
    "throttle(acc=1,start=0.05,duration=0.3,factor=2.5)",
    "permanent(acc=1,start=0.15)",
    "intermittent(acc=2,rate=8.0,mean_down=0.05)",
    "down(acc=0,start=0.1,duration=0.2,interrupted=resume)"
    "+throttle(acc=2,start=0.15,duration=0.25,factor=3.0)",
)


@pytest.mark.parametrize("faults", FAULT_GRID)
@pytest.mark.parametrize("sched", ["terastal", "edf", "dream", "fcfs"])
def test_ref_vs_soa_bit_identical_under_faults(sched, faults):
    plans, tasks = _cell("multicam_heavy")
    ref, soa = _both(plans, tasks, 0.6, sched, faults)
    assert ref.fingerprint() == soa.fingerprint()


@pytest.mark.parametrize("name", ["fault_dropout", "fault_brownout",
                                  "fault_flash_crowd"])
def test_catalog_cells_bit_identical(name):
    sc = get_scenario(name)
    plans, tasks = _cell(name)
    procs = [t.arrival for t in tasks]
    ref, soa = _both(plans, tasks, 1.0, "terastal", sc.faults, seed=1,
                     procs=procs)
    assert ref.fingerprint() == soa.fingerprint()
    assert ref.faulted_spans >= 1


# ------------------------------------------------- fault observables ----


def test_dropout_evicts_and_remaps():
    plans, tasks = _cell("multicam_heavy")
    res = simulate(plans, tasks, 0.6, make_scheduler("edf"), seed=0,
                   faults="down(acc=0,start=0.05,duration=0.3)")
    assert res.faulted_spans == 1
    evicted = sum(s.evicted for s in res.per_model.values())
    remapped = sum(s.remapped for s in res.per_model.values())
    assert evicted >= 1
    assert remapped <= evicted


def test_resume_differs_from_restart():
    """The interrupted-work policy only matters once the evicted request
    is re-dispatched; on this cell the acc=1 outage remaps it, so
    carrying the completed fraction over must change the trajectory."""
    plans, tasks = _cell("multicam_heavy")
    r = simulate(plans, tasks, 0.6, make_scheduler("edf"), seed=0,
                 faults="down(acc=1,start=0.05,duration=0.3)")
    s = simulate(plans, tasks, 0.6, make_scheduler("edf"), seed=0,
                 faults="down(acc=1,start=0.05,duration=0.3,interrupted=resume)")
    assert sum(st.remapped for st in r.per_model.values()) >= 1
    assert r.fingerprint() != s.fingerprint()


def test_variant_lever_degrades_gracefully():
    """The tentpole claim at test scale: on the dropout cell, variant-
    enabled Terastal misses strictly less than its no-variant ablation
    while the outage is active (fig10 gates the full-scale >= 5 pts)."""
    sc = get_scenario("fault_dropout")
    plans, tasks = _cell("fault_dropout")
    full = simulate(plans, tasks, 2.0, make_scheduler("terastal"), seed=0,
                    faults=sc.faults, engine="soa")
    abl = simulate(plans, tasks, 2.0, make_scheduler("terastal_no_variants"),
                   seed=0, faults=sc.faults, engine="soa")
    assert full.mean_miss_rate < abl.mean_miss_rate
    assert sum(s.variants_applied for s in full.per_model.values()) > 0


# ------------------------------------------ pre-PR10 bit-identity pin ----


from data_pre_pr10_fingerprints import PRE_PR10_FINGERPRINTS


@pytest.mark.parametrize("key", sorted(PRE_PR10_FINGERPRINTS))
def test_pre_pr10_cells_bit_identical(key):
    """The load-bearing pin of the re-tightening PR: the fault-off path
    and every faulted cell with ``retighten`` disabled reproduce the
    exact fingerprints captured at the PR 9 commit, on both engines —
    re-tightening, degraded admission, and the batch fault lane are
    strictly additive behind ``retighten=true``."""
    scenario, platform, duration, sched, adm, faults, engine = key
    sc = get_scenario(scenario)
    plans, tasks = sc.plans(PLATFORMS[platform])
    f = sc.faults if faults == "scenario" else (
        None if faults == "none" else faults)
    res = simulate(
        plans, tasks, duration, make_scheduler(sched), seed=0,
        processes=[t.arrival for t in tasks],
        admission=None if adm == "none" else adm,
        faults=f, engine=engine,
    )
    assert res.fingerprint() == PRE_PR10_FINGERPRINTS[key]


# --------------------------------------------------- batch rejection ----


def test_batch_engine_rejects_only_resume_faults():
    """PR 10 narrowed the rejection: restart-policy faults run on device
    (pre-bound capability epochs); only ``interrupted=resume`` stays out
    — fractional layer progress re-times re-dispatches mid-rollout,
    which a pre-bound epoch schedule cannot express."""
    plans, tasks = _cell("ar_social", platform="4k_1ws2os")
    resume = "down(acc=0,start=0.1,duration=0.2,interrupted=resume)"
    with pytest.raises(BatchUnsupportedError, match="resume"):
        simulate_batch(plans, tasks, 0.3, make_scheduler("terastal"),
                       seeds=[0], faults=resume)
    with pytest.raises(BatchUnsupportedError, match="resume"):
        simulate(plans, tasks, 0.3, make_scheduler("terastal"),
                 faults=resume, engine="batch")
    # restart-policy faults are now a supported batch axis
    res = simulate_batch(plans, tasks, 0.3, make_scheduler("terastal"),
                         seeds=[0], faults="down(acc=0,start=0.1,duration=0.2)")
    assert res[0].faulted_spans == 1
    # fault-off batch path unaffected ("none" strings included)
    res = simulate_batch(plans, tasks, 0.3, make_scheduler("terastal"),
                         seeds=[0], faults="none")
    assert res[0].per_model


# -------------------------------------------------- hypothesis parity ---


try:  # optional test extra — only the property test skips without it
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False


if _HAVE_HYPOTHESIS:

    @st.composite
    def _fault_specs(draw):
        parts = []
        n = draw(st.integers(min_value=1, max_value=2))
        accs = draw(st.permutations(range(3)))
        for i in range(n):
            kind = draw(st.sampled_from(["down", "throttle", "permanent"]))
            start = round(draw(st.floats(0.0, 0.4)), 3)
            dur = round(draw(st.floats(0.05, 0.4)), 3)
            if kind == "down":
                parts.append(f"down(acc={accs[i]},start={start},duration={dur})")
            elif kind == "throttle":
                factor = round(draw(st.floats(1.2, 5.0)), 2)
                parts.append(f"throttle(acc={accs[i]},start={start},"
                             f"duration={dur},factor={factor})")
            else:
                parts.append(f"permanent(acc={accs[i]},start={start})")
        if draw(st.booleans()):
            head, close = parts[0][:-1], parts[0][-1]
            parts[0] = f"{head},interrupted=resume{close}"
        return "+".join(parts)

    @settings(max_examples=20, deadline=None)
    @given(spec=_fault_specs(), seed=st.integers(0, 3),
           sched=st.sampled_from(["terastal", "edf"]))
    def test_hypothesis_engine_parity_under_faults(spec, seed, sched):
        """Random fault-model draws (kind x window x factor x policy):
        the SoA engine's SimResult must equal the reference engine's
        bit-for-bit with the fault machinery live."""
        plans, tasks = _cell("multicam_heavy")
        ref, soa = _both(plans, tasks, 0.5, sched, spec, seed=seed)
        assert ref.fingerprint() == soa.fingerprint()

else:  # pragma: no cover

    @pytest.mark.skip(reason="hypothesis not installed (optional test extra)")
    def test_hypothesis_engine_parity_under_faults():
        pass
