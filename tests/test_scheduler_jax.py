"""Property test: the jitted Terastal round matches the Python reference
assignment-for-assignment on randomized instances."""

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional test-extra; skip, don't error, when absent
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core.scheduler import Request, SchedView, TerastalScheduler
from repro.core.scheduler_jax import RoundInputs, pack_view, terastal_round
from repro.core.variants import ModelPlan
from repro.costmodel.dnn_zoo import DnnModel
from repro.costmodel.layers import matmul
from repro.costmodel.maestro import Accelerator, Dataflow, Platform
from repro.core.budget import distribute_budgets


def _grid(draw, st_, lo, hi, scale=256.0):
    return draw(st_.integers(lo, hi)) / scale


@st.composite
def _instances(draw):
    """Latencies/deadlines on a dyadic grid so f64(host) == f32-safe."""
    NA = draw(st.integers(1, 4))
    NJ = draw(st.integers(1, 8))
    n_layers = draw(st.integers(1, 4))
    lat = np.array(
        [[draw(st.integers(1, 64)) / 256.0 for _ in range(NA)] for _ in range(n_layers)]
    )
    plat = Platform(
        "t", tuple(Accelerator(f"a{k}", Dataflow.WS, 1024) for k in range(NA))
    )
    deadline = lat.min(axis=1).sum() * draw(st.integers(2, 8))
    budget = distribute_budgets(lat, deadline)
    layers = [matmul(f"l{i}", 8, 8, 8) for i in range(n_layers)]
    model = DnnModel("m", layers, redundancy=0.5)
    plan = ModelPlan(
        model=model, platform=plat, deadline=deadline, lat=lat, budget=budget,
        variants={}, theta=0.9,
    )
    now = 1.0
    reqs = []
    for j in range(NJ):
        arr = now - draw(st.integers(0, 64)) / 256.0
        layer = draw(st.integers(0, n_layers - 1))
        req = Request(rid=j, model_idx=0, arrival=arr, deadline_abs=arr + deadline, next_layer=layer)
        if draw(st.booleans()):
            # dynamic per-request virtual deadlines (online budget policy
            # state) on the same dyadic grid — parity must hold for these
            incs = np.array([draw(st.integers(1, 64)) / 256.0 for _ in range(n_layers)])
            req.vdl_abs = arr + np.cumsum(incs)
        reqs.append(req)
    busy = np.array([now + (draw(st.integers(-32, 32)) / 256.0 if draw(st.booleans()) else -1.0)
                     for _ in range(NA)])
    busy = np.maximum(busy, 0.0)
    return plan, reqs, busy, now


@given(_instances())
@settings(max_examples=150, deadline=None)
def test_jax_round_matches_python(inst):
    plan, reqs, busy, now = inst
    view = SchedView(now=now, ready=list(reqs), acc_busy_until=busy.copy(), plans=[plan])
    sched = TerastalScheduler()
    py = sched.schedule(view)
    py_map = {a.req.rid: (a.acc, a.use_variant) for a in py}

    view2 = SchedView(now=now, ready=list(reqs), acc_busy_until=busy.copy(), plans=[plan])
    inp, slots = pack_view(view2, sched)
    out = terastal_round(inp)
    jx_map = {}
    for i, r in enumerate(slots):
        k = int(out.assign_acc[i])
        if k >= 0:
            jx_map[r.rid] = (k, bool(out.assign_var[i]))
    assert jx_map == py_map, (jx_map, py_map)


def test_pack_view_shape_buckets_bound_recompiles():
    """pack_view pads NJ to power-of-two buckets with persistent host
    buffers, so terastal_round compiles at most once per (bucket, NA)
    per process — asserted via the jit compilation-cache counter, which
    is cleared first so buckets compiled by earlier tests do not count."""
    from repro.core.scheduler_jax import BUCKET_MIN, bucket_nj, round_jit

    assert bucket_nj(1) == BUCKET_MIN and bucket_nj(BUCKET_MIN) == BUCKET_MIN
    assert bucket_nj(BUCKET_MIN + 1) == 2 * BUCKET_MIN
    assert bucket_nj(9) == 16 and bucket_nj(16) == 16 and bucket_nj(17) == 32

    NA, n_layers = 2, 3
    lat = np.array([[1.0, 2.0]] * n_layers)
    plat = Platform("t", tuple(Accelerator(f"a{k}", Dataflow.WS, 1024) for k in range(NA)))
    deadline = 64.0
    budget = distribute_budgets(lat, deadline)
    model = DnnModel("m", [matmul(f"l{i}", 8, 8, 8) for i in range(n_layers)], redundancy=0.5)
    plan = ModelPlan(model=model, platform=plat, deadline=deadline, lat=lat,
                     budget=budget, variants={}, theta=0.9)
    sched = TerastalScheduler()

    def round_for(nj):
        reqs = [Request(rid=j, model_idx=0, arrival=0.0, deadline_abs=deadline,
                        next_layer=j % n_layers) for j in range(nj)]
        view = SchedView(now=1.0, ready=reqs, acc_busy_until=np.zeros(NA), plans=[plan])
        inp, slots = pack_view(view, sched)
        assert inp.lat.shape == (bucket_nj(nj), NA)
        out = terastal_round(inp)
        assert len(slots) == nj
        return out

    round_jit.clear_cache()
    round_for(2)  # warm the BUCKET_MIN bucket for this NA
    base = round_jit._cache_size()
    assert base == 1
    for nj in (1, 2, 3, 4):  # same bucket: zero new compilations
        round_for(nj)
    assert round_jit._cache_size() == base
    round_for(5)  # next bucket: exactly one new compilation ...
    grown = round_jit._cache_size()
    assert grown == base + 1
    for nj in (6, 7, 8):  # ... reused across the whole bucket
        round_for(nj)
    assert round_jit._cache_size() == grown


def test_jax_round_with_variants():
    """Deterministic case exercising the variant path end-to-end."""
    from repro.core.variants import VariantInfo

    NA, n_layers = 2, 2
    lat = np.array([[1.0, 4.0], [1.0, 4.0]])
    plat = Platform("t", tuple(Accelerator(f"a{k}", Dataflow.WS, 1024) for k in range(NA)))
    deadline = 4.5
    budget = distribute_budgets(lat, deadline)
    layers = [matmul(f"l{i}", 8, 8, 8) for i in range(n_layers)]
    model = DnnModel("m", layers, redundancy=0.5)
    vlat = np.array([0.9, 0.8])
    variants = {0: VariantInfo(0, 2, "d2s", layers[0], vlat, 0.05, 10)}
    plan = ModelPlan(model=model, platform=plat, deadline=deadline, lat=lat,
                     budget=budget, variants=variants, theta=0.9)
    now = 10.0
    # acc0 busy, acc1 idle; original on acc1 misses vdl, variant makes it
    busy = np.array([now + 10.0, 0.0])
    vdl_rel = float(plan.vdl_rel[0])
    arrival = now + 2.0 - vdl_rel  # vdl_abs = now + 2.0; c_orig@1=4 > 2, c_var=0.8 < 2
    req = Request(rid=0, model_idx=0, arrival=arrival, deadline_abs=now + 100, next_layer=0)
    sched = TerastalScheduler()
    view = SchedView(now=now, ready=[req], acc_busy_until=busy.copy(), plans=[plan])
    py = sched.schedule(view)
    assert len(py) == 1 and py[0].use_variant and py[0].acc == 1
    view2 = SchedView(now=now, ready=[Request(rid=0, model_idx=0, arrival=arrival,
                                              deadline_abs=now + 100, next_layer=0)],
                      acc_busy_until=busy.copy(), plans=[plan])
    inp, slots = pack_view(view2, sched)
    out = terastal_round(inp)
    assert int(out.assign_acc[0]) == 1 and bool(out.assign_var[0])
