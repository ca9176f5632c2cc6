"""Deep-queue round kernels: scalar / vectorized parity and the jitted
round against the reference scheduler at the pow2 bucket boundaries,
ready-block growth past the initial cap, and the SoA engine's depth
dispatch between the scalar and vectorized kernels.

The parity tests run on round states CAPTURED from real saturation
trials (block clones snapshotted mid-simulation, or live scheduler
views, at exact target depths), so the instances carry the true
deep-queue structure — mixed layers, variants, partially busy
accelerators — rather than synthetic rounds.
"""

import os

import numpy as np
import pytest

from repro.core import make_scheduler, simulate
from repro.core import engine_soa
from repro.core.engine_soa import _ReadyBlock
from repro.core.workload import SATURATION_SCENARIOS
from repro.costmodel.maestro import PLATFORMS

#: either side of the pow2 shape buckets 16 and 64 (bucket_nj boundaries)
BOUNDARY_NJ = (15, 16, 17, 63, 64, 65)


# --------------------------------------------------------- state capture ----


def _capture(mode: str, targets, per_target=3, duration=1.5):
    """Clone real round states at exact depths from a saturation trial
    run with the given backfill mode (vectorized kernel forced on so the
    clones carry live deep mirrors)."""
    got = {nj: [] for nj in targets}
    want = set(targets)
    orig = engine_soa._kern_terastal_vec

    def capture(B, now, busy, idle_mask, n_idle, kmode):
        if B.n in want and len(got[B.n]) < per_target:
            got[B.n].append((B.clone(), now, list(busy), idle_mask, n_idle, kmode))
        return orig(B, now, busy, idle_mask, n_idle, kmode)

    engine_soa._kern_terastal_vec = capture
    vec_min = engine_soa.VEC_MIN_NJ
    engine_soa.VEC_MIN_NJ = 2
    try:
        for cell in ("saturation_5x", "saturation_3x"):
            if all(len(v) >= per_target for v in got.values()):
                break
            plans, tasks = SATURATION_SCENARIOS[cell].plans(PLATFORMS["4k_1ws2os"])
            simulate(plans, tasks, duration,
                     make_scheduler(f"terastal(backfill_mode={mode})"),
                     seed=0, engine="soa")
    finally:
        engine_soa._kern_terastal_vec = orig
        engine_soa.VEC_MIN_NJ = vec_min
    return got


class _Captured(Exception):
    """Ends a capture run once every target depth has its instances."""


def _check_views(mode: str, targets, per_target, check, duration=1.5):
    """Run saturation trials on the reference engine with the given
    backfill mode and hand each live scheduler view at a target depth to
    ``check(nj, view, scheduler, schedule)`` before the round is applied
    (views are live: the engine mutates their requests afterwards).  For
    a mode other than "ef", ``per_target`` more views are checked, at any
    depth, where that mode's stage 2 decides differently from "ef"'s —
    few rounds at the boundary depths do."""
    seen = dict.fromkeys(targets, 0)
    if mode != "ef":
        seen["contrast"] = 0
        ef = make_scheduler("terastal")

    def key(assignments):
        return [(a.req.rid, a.acc, a.use_variant) for a in assignments]

    for cell in ("saturation_5x", "saturation_3x"):
        sched = make_scheduler(f"terastal(backfill_mode={mode})")
        orig = sched.schedule

        def hook(view):
            nj = len(view.ready)
            at = nj if seen.get(nj, per_target) < per_target else None
            if (at is None and seen.get("contrast", per_target) < per_target
                    and key(orig(view)) != key(ef.schedule(view))):
                at = "contrast"
            if at is not None:
                check(nj, view, sched, orig)
                seen[at] += 1
                if min(seen.values()) >= per_target:
                    raise _Captured
            return orig(view)

        sched.schedule = hook
        plans, tasks = SATURATION_SCENARIOS[cell].plans(PLATFORMS["4k_1ws2os"])
        try:
            simulate(plans, tasks, duration, sched, seed=0, engine="reference")
        except _Captured:
            break
    return seen


@pytest.mark.parametrize("mode", ["ef", "paper", "positive"])
def test_vec_kernel_parity_at_bucket_boundaries(mode):
    """Scalar and vectorized rounds emit identical assignment lists —
    slots, accelerators, variant flags, latencies, emission order — at
    every boundary depth, for every backfill mode."""
    states = _capture(mode, BOUNDARY_NJ)
    checked = 0
    for nj, instances in states.items():
        assert instances, f"no round captured at NJ={nj}"
        for args in instances:
            a = engine_soa._kern_terastal(*args)
            b = engine_soa._kern_terastal_vec(*args)
            assert a == b, (mode, nj)
            checked += 1
    assert checked >= len(BOUNDARY_NJ)


@pytest.mark.parametrize("soft", [False, True], ids=["native", "soft"])
@pytest.mark.parametrize("mode", ["ef", "paper", "positive"])
def test_jax_round_parity_at_bucket_boundaries(mode, soft, monkeypatch):
    """The jitted round, staged by ``pack_view``, matches
    ``TerastalScheduler.schedule`` on live views captured from the
    reference engine at every boundary depth: same requests,
    accelerators, variant flags and costs, in the same emission order
    (reconstructed from assign_seq).  f64 end to end: the latency tables
    here are arbitrary floats, not the dyadic grid of the property test.
    ``soft`` runs the round in the software binary64 the TPU uses."""
    from repro.core import f64
    from repro.core.scheduler_jax import pack_view, terastal_round

    if soft:
        monkeypatch.setattr(f64, "for_platform", lambda platform=None: f64.SOFT)

    def check(nj, view, sched, schedule):
        inp, reqs = pack_view(view, sched)
        out = terastal_round(inp, mode=sched.backfill_mode)
        acc, var, seq = (np.asarray(a)[:nj] for a in
                         (out.assign_acc, out.assign_var, out.assign_seq))
        hit = np.flatnonzero(acc >= 0)
        jx = []
        for i in hit[np.argsort(seq[hit])]:
            r, k, uv = reqs[i], int(acc[i]), bool(var[i])
            lat = view.plans[r.model_idx].lat_var if uv else view.plans[r.model_idx].lat
            jx.append((r.rid, k, uv, float(lat[r.next_layer, k])))
        ref = [(a.req.rid, a.acc, a.use_variant, a.est_latency)
               for a in schedule(view)]
        assert jx == ref, (mode, nj)

    seen = _check_views(mode, BOUNDARY_NJ, 2, check)
    assert min(seen.values()) >= 2, seen


# ------------------------------------------------------------ block grow ----


def test_ready_block_grows_past_initial_cap_with_mirrors():
    """grow() doubles every parallel field — scalar lists, drop arrays,
    and the deep mirrors — preserving live slot contents."""
    B = _ReadyBlock()
    assert B.cap == 64
    n_acc = 3
    B.activate_deep_terastal(n_acc)
    rows = {}
    for i in range(150):
        if B.n == B.cap:
            B.grow()
        n = B.n
        row = tuple(float(x) for x in np.random.default_rng(i).uniform(0.01, 0.2, n_acc))
        B.rid[n] = i
        B.dl[n] = 1.0 + i
        B.lat[n] = row
        B.vdl[n] = 0.5 + i
        B.min_rem_arr[n] = 0.1
        B.dl_eps_arr[n] = 1.0 + i
        B.guard_arr[n] = 0.9 + i
        B.rid_arr[n] = i
        B.vdl_arr[n] = 0.5 + i
        B.vdl_next_arr[n] = 0.6 + i
        B.next_min_arr[n] = 0.01
        B.lat_arr[:, n] = row
        B.latv_arr[:, n] = np.inf
        rows[i] = row
        B.n = n + 1
    assert B.cap == 256 and B.n == 150
    assert len(B.rid) == 256 and len(B.lat) == 256
    assert B.lat_arr.shape == (n_acc, 256) and B.min_rem_arr.shape == (256,)
    for i in (0, 63, 64, 127, 128, 149):  # survived both doublings
        assert B.rid[i] == i and B.rid_arr[i] == i
        assert B.lat[i] == rows[i]
        assert tuple(B.lat_arr[:, i]) == rows[i]
        assert B.vdl_arr[i] == 0.5 + i
    # swap_remove keeps mirrors coherent across the grown region
    B.swap_remove(0)
    assert B.rid[0] == 149 and B.rid_arr[0] == 149
    assert tuple(B.lat_arr[:, 0]) == rows[149]


def test_saturation_trial_exercises_growth_and_stays_bit_identical():
    """saturation_8x queues go past 128 ready layers (two grow()s) —
    and the whole trial still matches the reference engine exactly."""
    depths = []
    orig = engine_soa._kern_terastal_vec

    def probe(B, *a):
        depths.append(B.n)
        return orig(B, *a)

    engine_soa._kern_terastal_vec = probe
    try:
        plans, tasks = SATURATION_SCENARIOS["saturation_8x"].plans(
            PLATFORMS["4k_1ws2os"])
        soa = simulate(plans, tasks, 1.5, make_scheduler("terastal"), seed=0,
                       engine="soa")
    finally:
        engine_soa._kern_terastal_vec = orig
    assert max(depths) > 128  # grew 64 -> 128 -> 256
    ref = simulate(plans, tasks, 1.5, make_scheduler("terastal"), seed=0,
                   engine="reference")
    assert ref.rounds == soa.rounds
    assert ref.acc_busy_time.tolist() == soa.acc_busy_time.tolist()
    for m in ref.per_model:
        a, b = ref.per_model[m], soa.per_model[m]
        assert (a.released, a.completed, a.missed, a.dropped,
                a.variants_applied, a.retained_sum) == \
               (b.released, b.completed, b.missed, b.dropped,
                b.variants_applied, b.retained_sum)


# --------------------------------------------------------------- dispatch ----


@pytest.mark.parametrize("vec_min", [2, 10**9], ids=["vec", "scalar"])
def test_soa_depth_dispatch_is_bit_identical(vec_min, monkeypatch):
    """The SoA engine picks its Terastal round from the observed depth
    alone: at or above ``VEC_MIN_NJ`` the vectorized kernel, below it the
    scalar one.  Either way a saturation trial matches the reference
    engine's fingerprint."""
    plans, tasks = SATURATION_SCENARIOS["saturation_5x"].plans(
        PLATFORMS["4k_1ws2os"])
    calls = {"n": 0}
    orig = engine_soa._kern_terastal_vec

    def counting(*a):
        calls["n"] += 1
        return orig(*a)

    monkeypatch.setattr(engine_soa, "_kern_terastal_vec", counting)
    monkeypatch.setattr(engine_soa, "VEC_MIN_NJ", vec_min)
    soa = simulate(plans, tasks, 0.3, make_scheduler("terastal"), seed=0,
                   engine="soa")
    ref = simulate(plans, tasks, 0.3, make_scheduler("terastal"), seed=0,
                   engine="reference")
    assert soa.fingerprint() == ref.fingerprint()
    if vec_min == 2:
        assert calls["n"] > 0
    else:
        assert calls["n"] == 0


def test_soa_trial_never_imports_scheduler_jax():
    """A SoA trial runs on the Python kernels alone: the jax machinery is
    never imported, and the trial matches the reference engine.
    Subprocess, so the import-set assertion sees a clean module table."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = (
        "import sys\n"
        "from repro.core import make_scheduler, simulate\n"
        "from repro.core.workload import SATURATION_SCENARIOS\n"
        "from repro.costmodel.maestro import PLATFORMS\n"
        "plans, tasks = SATURATION_SCENARIOS['saturation_3x'].plans("
        "PLATFORMS['4k_1ws2os'])\n"
        "soa = simulate(plans, tasks, 0.3, make_scheduler('terastal'),"
        " seed=0, engine='soa')\n"
        "assert 'repro.core.scheduler_jax' not in sys.modules, "
        "'a SoA trial imported the jax machinery'\n"
        "assert 'jax' not in sys.modules\n"
        "ref = simulate(plans, tasks, 0.3, make_scheduler('terastal'),"
        " seed=0, engine='reference')\n"
        "assert soa.fingerprint() == ref.fingerprint()\n"
        "print('OK')\n"
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


def test_batch_trial_buffers_compile_once_per_bucket_pair():
    """The batched trial engine pads the event horizon (bucket_ev) and
    the seed axis (bucket_nj) into persistent seed-major buffers, so
    ``_run_trials`` compiles at most once per (NR bucket, B bucket) pair
    per kernel config — the pack_view recompile bound lifted to the
    batch axis.  Mid-trial growth is structurally absent here (the
    horizon is padded up front, unlike _ReadyBlock.grow()); what can
    grow mid-grid is the seed batch and the horizon between calls, and
    each rung crossing must cost exactly one compilation.  Unique B
    bucket (16) keeps the pairs disjoint from every other test in the
    process, so the counter deltas are exact."""
    from repro.core.engine_batch import _run_trials, simulate_batch
    from repro.core.scheduler_jax import pack_trials
    from repro.core.workload import batch_release_events

    plans, tasks = SATURATION_SCENARIOS["saturation_3x"].plans(
        PLATFORMS["4k_1ws2os"])
    dl = np.array([p.deadline for p in plans])

    def buckets(dur, seeds):
        ev = batch_release_events(tasks, dur, list(seeds))
        _, b_pad, nr_pad = pack_trials(ev, dl)
        return nr_pad, b_pad

    def run(dur, seeds):
        return simulate_batch(plans, tasks, dur,
                              make_scheduler("terastal"), list(seeds))

    # the shape assumptions this test rides on (seeded event generation
    # is deterministic, so these are stable):
    assert buckets(0.05, range(9)) == (48, 16)    # warm pair
    assert buckets(0.05, range(16)) == (48, 16)   # B grows inside bucket
    assert buckets(0.05, range(17)) == (48, 32)   # B crosses its bucket
    assert buckets(0.12, range(9)) == (96, 16)    # horizon crosses a rung

    run(0.05, range(9))  # warm the (48, 16) pair for this kernel config
    base = _run_trials._cache_size()
    run(0.05, range(16))  # same pair: B 9 -> 16 inside the bucket
    run(0.05, range(4, 13))  # same pair, disjoint seeds
    assert _run_trials._cache_size() == base
    run(0.05, range(17))  # seed axis crosses 16 -> 32: exactly one
    assert _run_trials._cache_size() == base + 1
    run(0.12, range(9))  # horizon crosses 48 -> 96: exactly one
    assert _run_trials._cache_size() == base + 2
    run(0.05, range(9))  # revisiting the warm pair stays free
    assert _run_trials._cache_size() == base + 2
