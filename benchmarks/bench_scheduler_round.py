"""Scheduler-round throughput: rounds/sec vs ready-queue depth (NJ).

The deep-queue regime (saturation scenarios: overloaded multi-camera
cells, 3-8x offered load, mixed release processes) is where the
scheduling round itself — not event bookkeeping — bounds campaign
throughput.  This benchmark measures one Terastal round at controlled
queue depths for both SoA kernel implementations:

* ``scalar`` — the interpreted kernel (``engine_soa._kern_terastal``),
  the baseline, which the engine runs below ``VEC_MIN_NJ``;
* ``vec`` — the vectorized deep-round kernel
  (``engine_soa._kern_terastal_vec``), which it runs at or above it.

Round states are *captured from real saturation trials* (block clones
snapshotted mid-simulation at target depths), so the instance mix —
idle-accelerator counts, stage-2 frequency, variant availability — is
the true deep-queue distribution, not a synthetic best case.  Both
kernels are re-run on identical clones; outputs are asserted equal
instance-by-instance, and a full-simulation differential section pins
``SimResult`` equality (reference engine vs SoA x backfill modes) on
fig5/fig7/fig8-shaped cells and the saturation grid.

Times are host time on the CPU (``time.perf_counter``), recorded as
``clock`` in the JSON.  Writes ``BENCH_round.json``.  CI runs
``--smoke`` as a dedicated step that FAILS on a floor regression
(unlike the informational run.py smoke claims): aggregate vec rounds/sec over deep rounds (NJ >= 64) must stay
>= MIN_DEEP_SPEEDUP x the scalar kernel.  Honest per-NJ scorecard: the
vectorized round has a ~13us flat numpy-dispatch floor, so the 3x line
is crossed between NJ ~ 64 and 96 (~2.6x at exactly 64, ~4-7x at
96-256); the aggregate over the saturation depth mix clears 3x with
margin because deep rounds cluster well past 64.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

#: aggregate deep-round speedup floor enforced by claims() — and by CI
#: even in --smoke mode (see module docstring).
MIN_DEEP_SPEEDUP = 3.0

#: queue depths measured (instances are captured at these exact NJ).
BUCKETS = (16, 24, 32, 48, 64, 96, 128, 192, 256)
DEEP_MIN_NJ = 64  # buckets >= this enter the enforced aggregate

SATURATION_CELLS = (
    ("saturation_3x", "4k_1ws2os"),
    ("saturation_5x", "4k_1ws2os"),
    ("saturation_8x", "4k_1ws2os"),
    ("saturation_8x", "6k_1ws2os"),
)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON_PATH = os.path.join(_ROOT, "BENCH_round.json")


# ------------------------------------------------------ state capture ----


def _capture_instances(buckets, per_bucket: int, duration: float, seeds):
    """Clone real mid-trial round states at the target depths by running
    the saturation cells with the vectorized kernel forced on (so the
    deep mirrors exist in every captured clone)."""
    from repro.core import engine_soa
    from repro.core.campaign import _plans_for
    from repro.core.scheduler import make_scheduler
    from repro.core.simulator import simulate

    targets: Dict[int, List[tuple]] = {nj: [] for nj in buckets}
    want = set(buckets)
    orig = engine_soa._kern_terastal_vec

    def capture(B, now, busy, idle_mask, n_idle, mode):
        n = B.n
        if n in want and len(targets[n]) < per_bucket:
            targets[n].append((B.clone(), now, list(busy), idle_mask, n_idle, mode))
        return orig(B, now, busy, idle_mask, n_idle, mode)

    vec_min = engine_soa.VEC_MIN_NJ
    engine_soa.VEC_MIN_NJ = 2
    engine_soa._kern_terastal_vec = capture
    try:
        for sc, pn in SATURATION_CELLS:
            plans, tasks = _plans_for(sc, pn, 0.90, True)
            for seed in seeds:
                simulate(plans, tasks, duration, make_scheduler("terastal"),
                         seed=seed, engine="soa")
    finally:
        engine_soa._kern_terastal_vec = orig
        engine_soa.VEC_MIN_NJ = vec_min
    return {nj: inst for nj, inst in targets.items() if inst}


# ------------------------------------------------------------ timing ----


def _time_kernel(fn, instances, reps: int) -> float:
    """Mean microseconds per round over the captured instance mix."""
    t0 = time.perf_counter()
    for _ in range(reps):
        for args in instances:
            fn(*args)
    return (time.perf_counter() - t0) / (reps * len(instances)) * 1e6


def _measure(targets, reps: int):
    from repro.core import engine_soa

    rows = []
    for nj in sorted(targets):
        inst = targets[nj]
        # identical outputs on every captured instance, both kernels
        for B, now, busy, idle_mask, n_idle, mode in inst:
            a = engine_soa._kern_terastal(B, now, busy, idle_mask, n_idle, mode)
            b = engine_soa._kern_terastal_vec(B, now, busy, idle_mask, n_idle, mode)
            assert a == b, f"scalar/vec round mismatch at NJ={nj}"
        t_scalar = _time_kernel(engine_soa._kern_terastal, inst, reps)
        t_vec = _time_kernel(engine_soa._kern_terastal_vec, inst, reps)
        rows.append({
            "nj": nj,
            "instances": len(inst),
            "us_scalar": round(t_scalar, 1),
            "us_vec": round(t_vec, 1),
            "speedup_vec": round(t_scalar / t_vec, 2),
        })
    return rows


def _aggregate_deep(rows) -> Optional[float]:
    """Aggregate rounds/sec ratio over the deep buckets (NJ >= 64),
    weighting each bucket's instance mix equally: total scalar time /
    total vec time across one pass of every deep instance."""
    deep = [r for r in rows if r["nj"] >= DEEP_MIN_NJ]
    if not deep:
        return None
    t_s = sum(r["us_scalar"] * r["instances"] for r in deep)
    t_v = sum(r["us_vec"] * r["instances"] for r in deep)
    return round(t_s / t_v, 2)


# ------------------------------------------------- simulation parity ----


def _differential(small: bool):
    """SimResult equality: reference engine vs SoA, both backfill-mode
    ablations, on fig-shaped and saturation cells."""
    from repro.core.campaign import _plans_for
    from repro.core.scheduler import make_scheduler
    from repro.core.simulator import make_arrival_process, simulate

    cells = [
        ("ar_gaming_heavy", "6k_1ws2os", "periodic", 0.5),
        ("multicam_light", "4k_1ws2os", "mmpp(burstiness=8)", 0.5),
        ("saturation_5x", "4k_1ws2os", None, 0.5),
    ]
    scheds = ["terastal", "terastal(backfill_mode=paper)",
              "terastal(backfill_mode=positive)"]
    if not small:
        cells += [
            ("ar_social", "4k_1ws2os", "poisson", 0.6),
            ("multicam_heavy", "6k_1ws2os", "mmpp(burstiness=4)", 0.6),
            ("saturation_8x", "6k_1ws2os", None, 0.8),
        ]
        scheds += ["terastal_no_variants", "terastal_no_budgeting"]
    checked = 0
    for sc, pn, arr, dur in cells:
        plans, tasks = _plans_for(sc, pn, 0.90, True)
        procs = [make_arrival_process(arr)] * len(tasks) if arr else None
        for sched in scheds:
            ref = simulate(
                plans, tasks, dur, make_scheduler(sched), seed=0,
                processes=procs, engine="reference").fingerprint()
            got = simulate(
                plans, tasks, dur, make_scheduler(sched), seed=0,
                processes=procs, engine="soa").fingerprint()
            if got != ref:
                return checked, False, f"{sc}/{sched}"
            checked += 1
    return checked, True, ""


# --------------------------------------------------------------- run ----


def run(duration: float = None) -> List[dict]:
    from benchmarks._scale import bench_duration, bench_mode

    mode = bench_mode()
    smoke = mode == "smoke"
    duration = bench_duration(duration, smoke=1.0, fast=1.5, full=2.5)
    buckets = {"smoke": (32, 64, 96, 128),
               "fast": (24, 48, 64, 96, 128, 192)}.get(mode, BUCKETS)
    per_bucket = {"smoke": 8, "fast": 12}.get(mode, 24)
    reps = {"smoke": 30, "fast": 60}.get(mode, 120)
    seeds = (0, 1) if mode == "full" else (0,)

    targets = _capture_instances(buckets, per_bucket, duration, seeds)
    rows = _measure(targets, reps)
    agg = _aggregate_deep(rows)

    n_diff, identical, where = _differential(mode != "full")

    summary = {
        "benchmark": "scheduler_round",
        "mode": mode,
        "clock": "host CPU time (time.perf_counter), no accelerator",
        "grid": {
            "cells": [list(c) for c in SATURATION_CELLS],
            "buckets": list(targets),
            "per_bucket": per_bucket,
            "capture_duration": duration,
            "seeds": list(seeds),
        },
        "buckets": rows,
        "aggregate_deep_speedup_vec": agg,
        "deep_min_nj": DEEP_MIN_NJ,
        "min_deep_speedup_enforced": MIN_DEEP_SPEEDUP,
        "differential": {"simulations": n_diff, "bit_identical": identical,
                         "first_mismatch": where},
    }
    with open(JSON_PATH, "w") as f:
        json.dump(summary, f, indent=2)
        f.write("\n")
    return rows + [{
        "aggregate_deep_speedup_vec": agg,
        "bit_identical": identical,
        "differential_simulations": n_diff,
        "first_mismatch": where,
        "json": JSON_PATH,
    }]


def claims(rows: List[dict]):
    tail = rows[-1]
    agg = tail["aggregate_deep_speedup_vec"]
    return [
        (f"vectorized round >= {MIN_DEEP_SPEEDUP}x rounds/sec over the "
         f"scalar kernel at NJ >= {DEEP_MIN_NJ} (saturation instance mix)",
         agg is not None and agg >= MIN_DEEP_SPEEDUP,
         f"aggregate {agg}x over deep buckets"),
        ("SimResults bit-identical: reference vs SoA x backfill modes",
         bool(tail["bit_identical"]),
         f"{tail['differential_simulations']} simulations compared"
         + (f"; first mismatch {tail.get('first_mismatch')}" if not
            tail["bit_identical"] else "")),
    ]


def check_json(path: str = JSON_PATH):
    """Apply the floor/bit-identity claims to an already-written
    BENCH_round.json (e.g. the one run.py --smoke just produced) without
    re-measuring — the CI gate step, so the capture + timing +
    differential pipeline runs once per job, not twice."""
    with open(path) as f:
        summary = json.load(f)
    tail = {
        "aggregate_deep_speedup_vec": summary["aggregate_deep_speedup_vec"],
        "bit_identical": summary["differential"]["bit_identical"],
        "differential_simulations": summary["differential"]["simulations"],
        "first_mismatch": summary["differential"].get("first_mismatch"),
    }
    return claims([tail])


if __name__ == "__main__":
    import sys

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small grid; unlike run.py --smoke, the speedup "
                    "floor and bit-identity still FAIL the process (the CI "
                    "regression gate)")
    ap.add_argument("--check-json", action="store_true",
                    help="validate the claims against the existing "
                    f"{os.path.basename(JSON_PATH)} instead of re-measuring")
    args = ap.parse_args()
    if args.smoke:
        os.environ["REPRO_BENCH_FAST"] = "1"
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    sys.path.insert(0, _ROOT)  # make the `benchmarks` package importable
    if args.check_json:
        checks = check_json()
    else:
        out = run()
        for r in out:
            print(json.dumps(r))
        checks = claims(out)
    n_ok = 0
    for name, ok, detail in checks:
        print(f"[{'PASS' if ok else 'FAIL'}] {name} ({detail})")
        n_ok += bool(ok)
    if n_ok < len(checks):
        sys.exit(1)
