"""The plain reference against the program's oracle engine (the SoA
engine, bit-identical to the batch engine), on the CPU, over the axes a
later cell may name with data alone: every scheduler the batch engine
runs, thinned releases, and every fault kind under the restart policy,
with and without budget re-tightening; and the timed path's batch under
faults against the reference."""

import json

import pytest

import bench
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
