"""Sequential adaptive sampler: determinism differentials and the journal.

The contract under test (see repro/core/sampling.py):

* stopping disabled  == ``Campaign.run`` — every ``TrialResult`` field
  (wall time zeroed: it measures the clock, not the simulation);
* parallel == serial — round barriers decide from seed-indexed result
  prefixes, so executor scheduling cannot leak into decisions;
* the executed trials per cell are a *prefix* of the campaign's own
  seed ladder (adaptive output is always a sub-grid of the fixed grid);
* a journal interrupted after any prefix resumes bit-identically, and
  a complete journal replays with zero re-execution.
"""

import dataclasses
import json
import os

import pytest

from repro.core import (
    Campaign,
    DegenerateSampleError,
    SamplerConfig,
    fixed_grid_verdicts,
    run_adaptive,
)
from repro.core.sampling import CELL_FIELDS, GROUP_FIELDS, _cell_of


def _strip(t):
    return dataclasses.replace(t, wall_s=0.0)


def _camp(**kw):
    base = dict(
        scenarios=("ar_gaming_heavy",),
        platforms=("6k_1ws2os",),
        schedulers=("fcfs", "edf", "terastal"),
        arrivals=("periodic", "mmpp(burstiness=4)"),
        seeds=tuple(range(5)),
        duration=0.5,
    )
    base.update(kw)
    return Campaign(**base)


# ---------------------------------------------------------- differential ----


def test_stopping_disabled_reproduces_campaign_run_exactly():
    """The sampler's always-run-to-cap special case IS the fixed grid:
    same trials, same order, every field equal — across schedulers x
    arrivals x budget policies, serial and parallel."""
    camp = _camp(
        schedulers=("edf", "terastal"),
        arrivals=("periodic", "poisson"),
        budget_policies=("static", "reclaim"),
        seeds=(0, 1, 2),
    )
    fixed = camp.run(parallel=False)
    cfg = SamplerConfig(stopping=False)
    for parallel in (False, True):
        res = run_adaptive(camp, cfg, parallel=parallel, max_workers=2)
        assert [_strip(t) for t in res.trials] == [_strip(t) for t in fixed.trials]
        assert res.verdicts == [] and res.n_trials == res.n_trials_cap
        assert res.trials_saved() == 0.0


def test_adaptive_parallel_equals_serial():
    camp = _camp()
    ser = run_adaptive(camp, parallel=False)
    par = run_adaptive(camp, parallel=True, max_workers=2)
    assert [_strip(t) for t in ser.trials] == [_strip(t) for t in par.trials]
    assert ser.verdicts == par.verdicts
    assert ser.rounds == par.rounds


def test_adaptive_trials_are_fixed_grid_prefix():
    """Per cell, the sampler consumes the campaign's seed ladder in
    order — the executed specs are a prefix of the fixed grid's specs
    for that cell, and the flattened result list follows grid order."""
    camp = _camp()
    res = run_adaptive(camp, parallel=False)
    grid = camp.trials()
    by_cell = {}
    for s in grid:
        by_cell.setdefault(_cell_of(s), []).append(s)
    got = {}
    for t in res.trials:
        got.setdefault(_cell_of(t.spec), []).append(t.spec)
    assert set(got) == set(by_cell)
    for cell, specs in got.items():
        assert specs == by_cell[cell][: len(specs)]  # prefix, in ladder order
    # grid order overall: positions strictly increase
    pos = {dataclasses.astuple(s): i for i, s in enumerate(grid)}
    idx = [pos[dataclasses.astuple(t.spec)] for t in res.trials]
    assert idx == sorted(idx)
    # and the sampler genuinely stopped early somewhere on this grid
    assert res.n_trials < res.n_trials_cap
    assert any(v.reason != "cap" for v in res.verdicts)


def test_adaptive_verdicts_match_fixed_grid_on_this_grid():
    """On the test grid the early-stopped winners equal the full-ladder
    winners (the property the efficiency benchmark enforces at scale)."""
    camp = _camp()
    fixed_w = {
        (v.group, v.scheduler): v.winner
        for v in fixed_grid_verdicts(camp.run(parallel=False))
    }
    res = run_adaptive(camp, parallel=False)
    assert len(res.verdicts) == len(fixed_w)
    for v in res.verdicts:
        assert v.winner == fixed_w[(v.group, v.scheduler)]
        assert v.baseline == "terastal"
        assert 2 <= v.n_seeds <= len(camp.seeds)
        assert v.reason in ("separated", "invariant", "cap")
        assert (v.reason == "separated") == v.separated


def test_campaign_result_adapter_aggregates():
    camp = _camp(seeds=(0, 1, 2, 3))
    res = run_adaptive(camp, parallel=False)
    agg = res.campaign_result().aggregate(by=("scheduler", "arrival"))
    assert {(r["scheduler"], r["arrival"]) for r in agg} == {
        (s, a) for s in camp.schedulers for a in camp.arrivals
    }
    for r in agg:
        assert 2 <= r["n_trials"] <= len(camp.seeds)


# --------------------------------------------------------------- journal ----


def test_journal_kill_after_any_prefix_resumes_bit_identical(tmp_path):
    """Truncate the journal after every prefix length — including mid-
    line, the signature of a killed process — and resume: the final
    trials and verdicts must be bit-identical to the uninterrupted run,
    and the journal must be healed to a complete, parseable file."""
    camp = _camp(seeds=(0, 1, 2, 3))
    path = str(tmp_path / "journal.jsonl")
    full = run_adaptive(camp, parallel=False, journal=path)
    lines = open(path).read().splitlines()
    assert len(lines) == 1 + full.n_trials  # header + one line per trial
    for keep in (1, 2, len(lines) // 2, len(lines) - 1):
        trunc = "\n".join(lines[:keep]) + "\n" + '{"kind": "trial", "spe'
        with open(path, "w") as f:
            f.write(trunc)  # no trailing newline: killed mid-write
        res = run_adaptive(camp, parallel=False, journal=path)
        assert [_strip(t) for t in res.trials] == [_strip(t) for t in full.trials]
        assert res.verdicts == full.verdicts
        healed = [json.loads(l) for l in open(path).read().splitlines()]
        assert len(healed) == 1 + full.n_trials


def test_journal_complete_replay_runs_zero_trials(tmp_path, monkeypatch):
    """Resuming from a complete journal re-executes nothing: every trial
    is served from the cache (run_trial is forbidden via monkeypatch)."""
    from repro.core import campaign as campaign_mod

    camp = _camp(seeds=(0, 1, 2))
    path = str(tmp_path / "journal.jsonl")
    full = run_adaptive(camp, parallel=False, journal=path)

    def boom(spec):
        raise AssertionError(f"run_trial re-executed {spec} despite journal")

    monkeypatch.setattr(campaign_mod, "run_trial", boom)
    res = run_adaptive(camp, parallel=False, journal=path)
    assert res.trials == full.trials  # wall_s included: cached verbatim
    assert res.verdicts == full.verdicts


def test_journal_refuses_foreign_campaign(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    run_adaptive(_camp(seeds=(0, 1)), parallel=False, journal=path)
    with pytest.raises(ValueError, match="different campaign"):
        run_adaptive(_camp(seeds=(0, 1, 2)), parallel=False, journal=path)
    with open(path, "w") as f:
        f.write('{"something": "else"}\n')
    with pytest.raises(ValueError, match="not a sampler journal"):
        run_adaptive(_camp(seeds=(0, 1)), parallel=False, journal=path)


def _rewrite_journal(path, edit):
    """Apply ``edit(obj, is_header)`` to every line of a journal."""
    with open(path) as f:
        lines = [json.loads(line) for line in f.read().splitlines()]
    for n, obj in enumerate(lines):
        edit(obj, n == 0)
    with open(path, "w") as f:
        for obj in lines:
            f.write(json.dumps(obj) + "\n")


def _counting_run_trial(monkeypatch):
    from repro.core import campaign as campaign_mod
    import repro.core.sampling as sampling_mod

    calls = {"n": 0}
    orig = campaign_mod.run_trial

    def counting(spec):
        calls["n"] += 1
        return orig(spec)

    monkeypatch.setattr(campaign_mod, "run_trial", counting)
    monkeypatch.setattr(sampling_mod, "run_trial", counting, raising=False)
    return calls


def test_journal_written_before_new_default_field_resumes(tmp_path, monkeypatch):
    """A journal from before a default-valued Campaign/TrialSpec field
    existed (e.g. ``faults``) must still resume: the header is
    re-serialized through the current dataclasses, which fill the
    defaults.  Genuinely different campaigns keep being refused."""
    path = str(tmp_path / "journal.jsonl")
    camp = _camp(seeds=(0, 1))
    first = run_adaptive(camp, parallel=False, journal=path)

    # age the journal: strip the newer fields from the header and every
    # recorded trial, exactly what a writer from before them produced
    def age(obj, is_header):
        if is_header:
            del obj["campaign"]["faults"]
        else:
            del obj["spec"]["faults"]
            del obj["result"]["rounds"]

    _rewrite_journal(path, age)
    calls = _counting_run_trial(monkeypatch)
    resumed = run_adaptive(camp, parallel=False, journal=path)
    assert calls["n"] == 0  # fully replayed from the aged journal
    assert [_cell_of(t.spec) for t in resumed.trials] == \
           [_cell_of(t.spec) for t in first.trials]
    # verdicts are a pure function of replayed results: identical
    assert [dataclasses.asdict(v) for v in resumed.verdicts] == \
           [dataclasses.asdict(v) for v in first.verdicts]


@pytest.mark.parametrize("key", ["round_kernel", "warp_factor"])
def test_journal_with_extra_field_resumes_only_if_retired(tmp_path, monkeypatch, key):
    """Journals written while ``round_kernel`` existed store it in the
    header's campaign and in every trial spec: such a journal resumes
    with no trial re-run and the uninterrupted run's results.  Any other
    unknown key is still a different campaign, and is refused."""
    path = str(tmp_path / "journal.jsonl")
    camp = _camp(seeds=(0, 1))
    first = run_adaptive(camp, parallel=False, journal=path)

    def add(obj, is_header):
        (obj["campaign"] if is_header else obj["spec"])[key] = "python"

    _rewrite_journal(path, add)
    calls = _counting_run_trial(monkeypatch)
    if key != "round_kernel":
        with pytest.raises(ValueError, match="different campaign"):
            run_adaptive(camp, parallel=False, journal=path)
        return
    resumed = run_adaptive(camp, parallel=False, journal=path)
    assert calls["n"] == 0
    assert resumed.trials == first.trials
    assert [dataclasses.asdict(v) for v in resumed.verdicts] == \
           [dataclasses.asdict(v) for v in first.verdicts]


# ------------------------------------------------------------ validation ----


def test_sampler_config_validation():
    with pytest.raises(ValueError, match="min_seeds"):
        SamplerConfig(min_seeds=1)
    with pytest.raises(ValueError, match="round_seeds"):
        SamplerConfig(round_seeds=0)
    with pytest.raises(ValueError, match="alpha"):
        SamplerConfig(alpha=0.0)
    assert SamplerConfig(min_seeds=3, round_seeds=2).looks(8) == [3, 5, 7, 8]
    assert SamplerConfig(min_seeds=8).looks(8) == [8]
    assert SamplerConfig(min_seeds=5).looks(3) == [3]  # clamped to cap
    assert SamplerConfig(stopping=False).looks(8) == [8]


def test_run_adaptive_named_errors():
    with pytest.raises(DegenerateSampleError, match="seed ladder"):
        run_adaptive(_camp(seeds=(0,)), parallel=False)
    with pytest.raises(ValueError, match="baseline scheduler"):
        run_adaptive(_camp(schedulers=("fcfs", "edf")), parallel=False)
    with pytest.raises(ValueError, match="nothing to compare"):
        run_adaptive(_camp(schedulers=("terastal",)), parallel=False)
    # but both degenerate grids are fine with stopping disabled
    cfg = SamplerConfig(stopping=False)
    assert run_adaptive(_camp(schedulers=("terastal",), seeds=(0,), arrivals=("periodic",)),
                        cfg, parallel=False).n_trials == 1


def test_cell_and_group_field_contract():
    """The cell identity covers every spec axis except the seed (and the
    campaign-constant duration/engine); groups drop only the scheduler."""
    assert CELL_FIELDS == ("scenario", "platform", "theta", "scheduler",
                           "arrival", "budget_policy")
    assert GROUP_FIELDS == tuple(f for f in CELL_FIELDS if f != "scheduler")
