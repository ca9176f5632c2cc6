"""The plain reference against the program's oracle engine (the SoA
engine, bit-identical to the batch engine), on the CPU, over the axes a
later cell may name with data alone: every scheduler the batch engine
runs, thinned releases, every fault kind under the restart policy, with
and without budget re-tightening, and the catalogued layer DAGs; and the
timed path's batch under faults against the reference."""

import json

import numpy as np
import pytest

import bench
import make_config
import reference

CONFIG = bench.load_config("multicam.4k_1ws2os")
#: the heavy multi-camera rates of Table II (Poisson, deadline = period)
BASE = {"name": "heavy_rates", "faults": "none",
        "entries": [{"fps": fps, "arrival": "poisson"} for fps in (60.0, 30.0, 30.0, 15.0, 30.0)]}


def _traffic(**changes):
    t = json.loads(json.dumps(BASE))
    t.update(horizon_s=0.4, **changes)
    return t


THINNED = _traffic(entries=[
    {"fps": 60.0, "arrival": "periodic(jitter=0.5)", "prob": 0.5},
    {"fps": 30.0, "arrival": "poisson", "prob": 0.5},
    {"fps": 30.0, "arrival": "mmpp(burstiness=4)", "prob": 0.7},
    {"fps": 15.0, "arrival": "periodic"},
    {"fps": 30.0, "arrival": "periodic", "prob": 0.5}])

CASES = {
    "fcfs": ("fcfs", _traffic()),
    "edf": ("edf", _traffic()),
    "dream": ("dream", _traffic()),
    "no_budgeting": ("terastal_no_budgeting", _traffic()),
    "no_variants": ("terastal_no_variants", _traffic()),
    "positive": ("terastal(backfill_mode=positive)", _traffic()),
    "paper": ("terastal(backfill_mode=paper)", _traffic()),
    "thinned": ("terastal", THINNED),
    "down": ("terastal", _traffic(faults="down(acc=0,start=0.05,duration=0.2)")),
    "down_retighten": ("terastal",
                       _traffic(faults="down(acc=0,start=0.05,duration=0.2,retighten=true)")),
    "permanent": ("edf", _traffic(faults="permanent(acc=1,start=0.1)")),
    "intermittent": ("terastal", _traffic(faults="intermittent(acc=2,rate=8,mean_down=0.03)")),
    "throttle_retighten": ("terastal", _traffic(
        faults="throttle(acc=0,start=0.05,duration=0.2,factor=3.0,retighten=true)")),
    "brownout": ("terastal", _traffic(faults="+".join(
        f"throttle(acc={a},start={0.05 + 0.1 * a:.2f},duration=0.1,factor=3.0)"
        for a in range(3)))),
}
#: the cases whose faults the batch engine runs on the device
FAULTED = ["down", "down_retighten", "brownout"]


def _cell(case):
    scheduler, traffic = CASES[case]
    cell = bench.Cell.__new__(bench.Cell)
    cell.config, cell.traffic = dict(CONFIG, scheduler=scheduler), traffic
    return cell


@pytest.mark.parametrize("case", list(CASES))
def test_reference_equals_the_programs_oracle(case):
    from repro.core.simulator import simulate

    cell = _cell(case)
    config, traffic = cell.config, cell.traffic
    program = bench.Program(cell)
    plans = reference.plans_for(config, traffic)
    for seed in (3, 2**31 + 11):
        got = simulate(program.plans, program.tasks, traffic["horizon_s"], program.scheduler,
                       seed=seed, engine="soa", faults=traffic["faults"])
        want = reference.simulate(config, traffic, seed, plans=plans)
        assert got.fingerprint()[1:] == bench.fingerprint_of(want)


@pytest.mark.parametrize("case", FAULTED)
def test_a_faulted_batch_of_the_timed_path_equals_the_reference(case):
    """``Program.run``, the timed path, on 4 lanes of a faulted case:
    every lane against the reference."""
    cell = _cell(case)
    program = bench.Program(cell)
    seeds = [3, 17, 2**31 + 11, 2**32 - 5]
    plans = reference.plans_for(cell.config, cell.traffic)
    for s, r in zip(seeds, program.run(seeds)):
        want = reference.simulate(cell.config, cell.traffic, s, plans=plans)
        assert r.fingerprint()[1:] == bench.fingerprint_of(want)


#: the catalogued DAG scenarios (``repro.core.workload.DAG_SCENARIOS``) on
#: the platforms they are run on: each model with its input resolution
DAG_MIXES = {
    "dag_asr_encdec": (("asr_encdec", 80), ("mobilenetv2_ssd", 300), ("sp2dense", 224)),
    "dag_vlm_2branch": (("vlm_2branch", 224), ("fbnet_c", 224), ("hand_sp", 256)),
    "dag_moe_4expert": (("moe_4expert", 224), ("fbnet_c", 224)),
}
DAG_CONFIGS = [("dag_asr_encdec", "6k_1ws2os"), ("dag_vlm_2branch", "6k_1ws2os"),
               ("dag_moe_4expert", "6k_1ws2os"), ("dag_vlm_2branch", "6k_1os2ws")]
#: the scheduler of every unfaulted case above
DAG_SCHEDULERS = {"terastal": "terastal",
                  **{case: CASES[case][0] for case in ("fcfs", "edf", "dream", "no_budgeting",
                                                       "no_variants", "positive", "paper")}}


def _dag_cell(catalog, platform, scheduler="terastal"):
    """A configuration written by ``make_config.tables`` from a catalogued
    DAG scenario, with its rates and deadlines and Poisson releases."""
    from repro.core.workload import get_scenario
    from repro.costmodel import dnn_zoo

    sc = get_scenario(catalog)
    mix = DAG_MIXES[catalog]
    assert [getattr(dnn_zoo, m)(res).layers for m, res in mix] == \
        [e.model.layers for e in sc.entries]
    accs, models = make_config.tables(platform, mix)
    config = {"platform": platform, "accelerators": accs, "scheduler": scheduler,
              "theta": 0.90, "enable_variants": True, "models": json.loads(json.dumps(models))}
    entries = [{"fps": e.fps, "arrival": "poisson",
                **({} if e.deadline is None else {"deadline_s": e.deadline})}
               for e in sc.entries]
    cell = bench.Cell.__new__(bench.Cell)
    cell.config = config
    cell.traffic = {"name": catalog, "horizon_s": 0.4, "faults": "none", "entries": entries}
    return cell


def _oracle(program, traffic, seed):
    from repro.core.simulator import simulate

    return simulate(program.plans, program.tasks, traffic["horizon_s"], program.scheduler,
                    seed=seed, engine="soa", faults=traffic["faults"]).fingerprint()[1:]


@pytest.mark.parametrize("case", list(DAG_SCHEDULERS))
@pytest.mark.parametrize("catalog,platform", DAG_CONFIGS)
def test_reference_equals_the_programs_oracle_on_a_dag(catalog, platform, case):
    cell = _dag_cell(catalog, platform, DAG_SCHEDULERS[case])
    assert "preds" in cell.config["models"][0]
    assert all("preds" not in m for m in cell.config["models"][1:])
    program = bench.Program(cell)
    plans = reference.plans_for(cell.config, cell.traffic)
    for seed in (3, 2**31 + 11):
        want = reference.simulate(cell.config, cell.traffic, seed, plans=plans)
        assert _oracle(program, cell.traffic, seed) == bench.fingerprint_of(want)


@pytest.mark.parametrize("catalog,platform,scheduler", [
    ("dag_vlm_2branch", "6k_1ws2os", "terastal(backfill_mode=positive)"),
    ("dag_vlm_2branch", "6k_1os2ws", "terastal(backfill_mode=positive)"),
    ("dag_vlm_2branch", "6k_1os2ws", "terastal(backfill_mode=paper)"),
])
def test_eq8_takes_the_binding_successor_of_a_fan_out(catalog, platform, scheduler):
    """Eq. 8 at the VLM's stem, which fans out to two towers: the
    successor it binds on decides a backfill only when the stem itself
    waits in stage 2, which these 40 seeds include."""
    cell = _dag_cell(catalog, platform, scheduler)
    program = bench.Program(cell)
    plans = reference.plans_for(cell.config, cell.traffic)
    for seed in range(40):
        want = reference.simulate(cell.config, cell.traffic, seed, plans=plans)
        assert _oracle(program, cell.traffic, seed) == bench.fingerprint_of(want)


@pytest.mark.parametrize("catalog,platform", DAG_CONFIGS)
def test_reference_offline_stage_equals_the_programs_on_a_dag(catalog, platform):
    """Critical-path Algorithm 1, the variant choice and the critical-path
    tables, bit for bit, for the DAG model and the chains beside it."""
    cell = _dag_cell(catalog, platform)
    program = bench.Program(cell)
    plans = reference.plans_for(cell.config, cell.traffic)
    assert program.plans[0].dag is not None and plans[0].graph is not None
    for p, q in zip(program.plans, plans):
        assert (p.vdl_rel == q.vdl_rel).all() and (p.budget.rho == q.rho).all()
        assert (p.lat == q.lat).all() and (p.lat_var == q.lat_var).all()
        assert {l: v.loss for l, v in p.variants.items()} == q.loss
        assert (p.crit_from == q.crit_from).all() and (p.crit_after == q.crit_after).all()


@pytest.mark.parametrize("catalog,platform", DAG_CONFIGS)
def test_float32_reference_differs_from_the_program_on_a_dag(catalog, platform):
    """The lower-precision control of the check, on the DAG cases."""
    cell = _dag_cell(catalog, platform)
    program = bench.Program(cell)
    plans = reference.plans_for(cell.config, cell.traffic, np.float32)
    differing = sum(
        _oracle(program, cell.traffic, seed)
        != bench.fingerprint_of(reference.simulate(cell.config, cell.traffic, seed,
                                                   np.float32, plans))
        for seed in (3, 2**31 + 11))
    assert differing > 0


def test_faults_with_a_dag_plan_are_refused_by_name():
    cell = _dag_cell("dag_vlm_2branch", "6k_1ws2os")
    traffic = dict(cell.traffic, faults="down(acc=0,start=0.05,duration=0.2)")
    with pytest.raises(reference.DagFaultsUnsupported, match="vlm_2branch"):
        reference.simulate(cell.config, traffic, 3)
