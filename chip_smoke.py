"""Smoke run of the main path on one TPU chip.

    python chip_smoke.py

Runs every phase in this one process (a chip belongs to one process) and
prints one JSON line per check: the device, compile and wall seconds,
what was compared and how closely.  The last line is
``{"ok": true, "device": {...}}`` only when every phase passed; any
failure exits non-zero without it, and so does a run that finds no TPU.

Phases:

* ``f64``     — float64 on the chip bit for bit against numpy, on values
  in the simulator's ranges: the chip's own float64 (recorded) and the
  software binary64 the simulator runs on the TPU (must be exact).
* ``engine``  — ``TrialExecutor`` runs ``engine="batch"`` specs, 32 seeds,
  in three cells; every lane's ``SimResult.fingerprint()`` must equal
  ``simulate(engine="soa")``'s.
* ``round``   — the jitted Terastal round, staged by ``pack_view``, on
  live scheduler views of reference-engine saturation trials at NJ 64
  and in the NJ-256 bucket, against ``TerastalScheduler.schedule``:
  requests, accelerators, variants and emission order exactly.
* ``kernels`` — the three Pallas kernels compiled for the chip (a
  ``tpu_custom_call`` in the program, never interpreted) at model widths,
  against their jnp oracles run on the host CPU backend, at the
  tolerances of tests/test_kernels.py.
* ``model``   — llama3.2-1b at published widths in bf16 through
  ``repro.launch.serve.run``: a few greedy tokens at batch 2, and the
  last decode logits against the train-mode forward.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

#: seed-replicates per engine cell
B = 32

#: (scenario, duration, fault spec): the BENCH_batch headline cell, the
#: same cell at a 2 s horizon, and fig12's brownout fault lane without
#: its admission gate (which the batch engine refuses)
ENGINE_CELLS = (
    ("saturation_5x", 0.1, "scenario"),
    ("saturation_5x", 2.0, "scenario"),
    ("saturation_3x", 2.0,
     "throttle(acc=0,start=0.2,duration=1.4,factor=4.0,retighten=true)"),
)

#: decode logits vs the train forward, relative to max |logit|: bf16
#: keeps 8 mantissa bits (eps 2^-8 ~ 3.9e-3); 16 layers of differently
#: ordered bf16 rounding stay well inside 5e-2
BF16_REL_TOL = 5e-2


def _timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


# ------------------------------------------------------------- f64 ----


def _f64_operands(n=1 << 16):
    """Times in [0, 2) s, latencies in [1e-5, 1e-1) s, and near-ties."""
    rng = np.random.default_rng(0)
    a = rng.uniform(0.0, 2.0, n)
    b = 10.0 ** rng.uniform(-5.0, -1.0, n)
    c = a + b * rng.integers(0, 4, n)
    return ((a, b), (a, c), (c, a))


def _differ(got, want) -> int:
    got, want = np.asarray(got), np.asarray(want)
    if want.dtype == np.float64:
        return int((got.view(np.int64) != want.view(np.int64)).sum())
    return int((got != want).sum())


def phase_f64(dev, cpu):
    """Float64 on the chip, bit for bit against numpy: the backend's own
    float64 (recorded: the TPU's is not IEEE, which is why the simulator
    does not use it there) and the software binary64 the simulator runs
    on it (must be exact)."""
    import jax

    from repro.core.f64 import NATIVE, SOFT

    pairs = _f64_operands()
    ops = {
        "add": (lambda F: F.add, np.add), "sub": (lambda F: F.sub, np.subtract),
        "mul": (lambda F: F.mul, np.multiply),
        "min": (lambda F: F.minimum, np.minimum), "le": (lambda F: F.le, np.less_equal),
        "le_eps": (lambda F: lambda x, y: F.le(x, F.add(y, F.const(1e-15))),
                   lambda x, y: x <= y + 1e-15),
        "argmin": (lambda F: lambda x, y: F.argmin(F.add(x, y).reshape(-1, 64), axis=1),
                   lambda x, y: np.argmin((x + y).reshape(-1, 64), axis=1)),
    }
    b = pairs[0][1][:4096]
    rows = []
    with jax.enable_x64(True):
        for F in (NATIVE, SOFT):
            put = lambda x: jax.device_put(F.to_device(x), dev)  # noqa: E731
            counts = {"transfer": _differ(F.from_device(put(pairs[0][0])), pairs[0][0])}
            for name, (make, want) in ops.items():
                f = jax.jit(make(F))
                counts[name] = 0
                for x, y in pairs:
                    got = np.asarray(f(put(x), put(y)))
                    ref = want(x, y)
                    counts[name] += _differ(F.from_device(got) if ref.dtype == np.float64
                                            else got, ref)
            acc = jax.jit(lambda z, F=F: jax.lax.fori_loop(
                0, z.shape[0], lambda i, s: F.add(s, z[i]), F.const(0.0)))
            counts["running_sum"] = _differ(F.from_device(acc(put(b))), np.cumsum(b)[-1])
            exact = not any(counts.values())
            rows.append(dict(phase="f64", arith=F.name,
                             compared=f"{3 * len(pairs[0][0])} pairs per op vs numpy, bitwise",
                             mismatches=counts, exact=exact,
                             # the chip's own float64 is only recorded
                             ok=exact or F is NATIVE))
    return rows


# ---------------------------------------------------------- engine ----


def phase_engine(dev, cpu):
    from repro.core.campaign import TrialExecutor, TrialSpec, _plans_for
    from repro.core.engine_batch import simulate_batch
    from repro.core.f64 import for_platform
    from repro.core.scheduler import make_scheduler
    from repro.core.scheduler_jax import bucket_ev
    from repro.core.simulator import make_arrival_process, simulate

    rows = []
    for scenario, duration, faults in ENGINE_CELLS:
        specs = [TrialSpec(scenario, "4k_1ws2os", "terastal", arrival="poisson",
                           duration=duration, seed=s, engine="batch", faults=faults)
                 for s in range(B)]
        with TrialExecutor(parallel=False) as ex:
            trials, first_s = _timed(ex.run_batch, specs)

        # the same batch again, warm, through simulate_batch (the engine
        # behind simulate(engine="batch")), for the SimResults whose
        # fingerprints are compared
        spec = specs[0]
        plans, tasks = _plans_for(scenario, spec.platform, spec.theta, spec.enable_variants)
        proc = make_arrival_process(spec.arrival)
        procs = [t.arrival or proc for t in tasks]
        sched = make_scheduler(spec.scheduler)
        fault_spec = "none" if faults == "scenario" else faults
        seeds = list(range(B))
        lanes, warm_s = _timed(simulate_batch, plans, tasks, duration, sched, seeds,
                               processes=procs, faults=fault_spec)
        soa, soa_s = _timed(lambda: [
            simulate(plans, tasks, duration, sched, seed=s, processes=procs,
                     engine="soa", faults=fault_spec) for s in seeds])

        same_fp = sum(x.fingerprint() == y.fingerprint() for x, y in zip(lanes, soa))
        # the entry point's trial rows carry the same integer outcomes
        keys = ("released", "completed", "dropped", "variants_applied", "evicted", "remapped")
        rows_equal = all(
            tuple(getattr(t, k) for k in keys) + (t.rounds,)
            == tuple(sum(getattr(st, k) for st in r.per_model.values()) for k in keys)
            + (r.rounds,)
            for t, r in zip(trials, soa))
        busy_err = max(float(np.abs(x.acc_busy_time - y.acc_busy_time).max())
                       for x, y in zip(lanes, soa))
        released = max(sum(st.released for st in r.per_model.values()) for r in soa)
        rows.append(dict(
            phase="engine", cell=f"{scenario}/4k_1ws2os/terastal/poisson",
            duration=duration, faults=fault_spec, seeds=B, arith=for_platform().name,
            nr_pad=bucket_ev(released), compile_s=first_s - warm_s, first_call_s=first_s,
            wall_s=warm_s, soa_s=soa_s,
            compared="SimResult.fingerprint() per lane vs simulate(engine='soa')",
            lanes_fingerprint_equal=same_fp, trial_rows_equal=rows_equal,
            busy_max_abs_err_s=busy_err, ok=same_fp == B and rows_equal))
    return rows


# ----------------------------------------------------------- round ----


class _Captured(Exception):
    """Ends the round phase's capture once both groups are full."""


def phase_round(dev, cpu):
    from benchmarks.bench_scheduler_round import SATURATION_CELLS
    from repro.core.campaign import _plans_for
    from repro.core.scheduler import make_scheduler
    from repro.core.scheduler_jax import pack_view, terastal_round
    from repro.core.simulator import simulate

    # eight live views at NJ 64, and one per depth in the NJ-256 bucket
    groups = {64: dict(nj=[], walls=[], mismatches=0),
              256: dict(nj=[], walls=[], mismatches=0)}

    def controller(view, sched):
        inp, reqs = pack_view(view, sched)
        out = terastal_round(inp, mode=sched.backfill_mode)
        nj = len(reqs)
        acc, var, seq = (np.asarray(a)[:nj] for a in
                         (out.assign_acc, out.assign_var, out.assign_seq))
        hit = np.flatnonzero(acc >= 0)
        return [(reqs[i].rid, int(acc[i]), bool(var[i]))
                for i in hit[np.argsort(seq[hit])]]

    for sc, pn in SATURATION_CELLS:
        plans, tasks = _plans_for(sc, pn, 0.90, True)
        sched = make_scheduler("terastal")
        schedule = sched.schedule

        def hook(view):
            nj = len(view.ready)
            g = groups[64] if nj == 64 else groups[256] if 193 <= nj <= 256 else None
            if g is not None and len(g["nj"]) < 8 and (nj == 64 or nj not in g["nj"]):
                got, dt = _timed(controller, view, sched)
                if "compile_s" in g:
                    g["walls"].append(dt)
                else:
                    g["compile_s"] = dt
                ref = schedule(view)
                g["mismatches"] += got != [(a.req.rid, a.acc, a.use_variant) for a in ref]
                g["nj"].append(nj)
                if all(len(x["nj"]) >= 8 for x in groups.values()):
                    raise _Captured
                return ref
            return schedule(view)

        sched.schedule = hook
        try:
            simulate(plans, tasks, 1.0, sched, seed=0, engine="reference")
        except _Captured:
            break
    rows = []
    for nj_bucket, g in groups.items():
        rows.append(dict(
            phase="round", nj_bucket=nj_bucket, nj=g["nj"], instances=len(g["nj"]),
            compile_s=g.get("compile_s"),
            wall_s=float(np.median(g["walls"])) if g["walls"] else None,
            compared="(rid, acc, variant) in emission order, pack_view + terastal_round "
                     "vs TerastalScheduler.schedule on live reference-engine views",
            mismatches=g["mismatches"], ok=bool(g["nj"]) and g["mismatches"] == 0))
    return rows


# --------------------------------------------------------- kernels ----


def _kernel_cases():
    """(name, compiled fn, oracle fn, numpy inputs, check)."""
    import functools

    from repro.kernels.decode_attn.kernel import decode_attn_pallas
    from repro.kernels.s2d_conv.kernel import s2d_conv_pallas
    from repro.kernels.s2d_conv.ref import s2d_conv_ref
    from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
    from repro.models.common import decode_attention
    from repro.models.mamba2 import ssd_naive

    rng = np.random.default_rng(42)
    f32 = np.float32

    def normal(*shape):
        return rng.standard_normal(shape).astype(f32)

    def allclose(atol, rtol):
        def check(got, want):
            err = np.abs(got - want)
            return float(err.max()), bool((err <= atol + rtol * np.abs(want)).all())
        return check

    def rel_max(tol):
        def check(got, want):
            rel = float(np.abs(got - want).max() / (np.abs(want).max() + 1e-9))
            return rel, rel < tol
        return check

    cases = []
    # llama3.2-1b decode: 32 query heads over 8 KV heads, Dh 64, 4096-slot cache
    Bd, L, H, Hkv, Dh, pos = 8, 4096, 32, 8, 64, 3000
    q, k, v = normal(Bd, H, Dh), normal(Bd, L, Hkv, Dh), normal(Bd, L, Hkv, Dh)
    cases.append((
        "decode_attn",
        lambda q, k, v: decode_attn_pallas(q, k, v, jax_full(Bd, pos + 1), chunk=512),
        lambda q, k, v: decode_attention(q[:, None], k, v, pos)[:, 0],
        (q, k, v), allclose(1e-5, 1e-4)))
    # mamba2-1.3b mixer: 64 heads of 64, state 128, 2048 tokens, chunk 256
    Bt, L2, Hs, P, N = 1, 2048, 64, 64, 128
    x = normal(Bt, L2, Hs, P)
    la = (-np.abs(normal(Bt, L2, Hs)) * 0.3).astype(f32)
    Bm, Cm = normal(Bt, L2, N), normal(Bt, L2, N)
    dt = np.log1p(np.exp(normal(Bt, L2, Hs))).astype(f32)
    cases.append(("ssd_scan", functools.partial(ssd_scan_pallas, chunk=256), ssd_naive,
                  (x, la, Bm, Cm, dt), rel_max(1e-5)))
    # VGG conv layers' gamma=2 variants at their published widths
    for hw, c in ((56, 256), (28, 512)):
        xs, ws = normal(1, hw, hw, c), normal(c // 4, c // 4)
        cases.append((f"s2d_conv_{hw}x{hw}x{c}", functools.partial(s2d_conv_pallas, gamma=2),
                      functools.partial(s2d_conv_ref, gamma=2), (xs, ws),
                      allclose(1e-5, 1e-5)))
    return cases


def jax_full(n, value):
    import jax.numpy as jnp

    return jnp.full((n,), value, jnp.int32)


def phase_kernels(dev, cpu):
    import jax

    rows = []
    for name, fn, oracle, inputs, check in _kernel_cases():
        args = [jax.device_put(a, dev) for a in inputs]
        compiled, compile_s = _timed(lambda: jax.jit(fn).lower(*args).compile())
        in_program = "tpu_custom_call" in compiled.as_text()
        got, first_s = _timed(lambda: np.asarray(compiled(*args)))
        _, wall_s = _timed(lambda: jax.block_until_ready(compiled(*args)))
        with jax.default_device(cpu):
            want = np.asarray(jax.jit(oracle)(*[jax.device_put(a, cpu) for a in inputs]))
        err, close = check(got.astype(np.float32), want.astype(np.float32))
        rows.append(dict(phase="kernels", kernel=name, shapes=[list(a.shape) for a in inputs],
                         compile_s=compile_s, wall_s=wall_s, tpu_custom_call=in_program,
                         compared="vs jnp oracle on the host CPU backend, f32", err=err,
                         ok=in_program and close))
    return rows


# ----------------------------------------------------------- model ----


def phase_model(dev, cpu):
    import jax

    from repro.launch.serve import run

    served, wall = _timed(run, "llama3.2-1b", tokens=8, batch=2, reduced=False)
    cfg = served.model.cfg
    fwd, fwd_s = _timed(lambda: np.asarray(
        jax.jit(served.model.prefill)(served.params, {"tokens": served.fed})))
    dec = np.asarray(served.logits[:, -1])
    rel = float(np.abs(dec - fwd).max() / np.abs(fwd).max())
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(served.params))
    return [dict(phase="model", arch=cfg.name, d_model=cfg.d_model, n_layers=cfg.n_layers,
                 dtype=cfg.dtype, params=n_params, tokens=int(served.fed.shape[1]), batch=2,
                 compile_s=served.first_s, wall_s=served.step_s, total_s=wall,
                 forward_s=fwd_s,
                 compared="last decode logits vs train-mode forward, max |diff| / max |logit|",
                 rel_err=rel, tol=BF16_REL_TOL, greedy_agree=int((dec.argmax(-1) == fwd.argmax(-1)).sum()),
                 finite=bool(np.isfinite(dec).all()), ok=bool(np.isfinite(dec).all()) and rel <= BF16_REL_TOL)]


PHASES = (phase_f64, phase_engine, phase_round, phase_kernels, phase_model)


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing was run",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    cpu = jax.devices("cpu")[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(json.dumps({"device": device, "compile_cache": cache_dir}), flush=True)
    failed = []
    for phase in PHASES:
        name = phase.__name__[len("phase_"):]
        t0 = time.perf_counter()
        try:
            rows = phase(dev, cpu)
        except Exception:
            traceback.print_exc()
            rows = [dict(phase=name, error=traceback.format_exc(limit=1).strip(), ok=False)]
        for row in rows:
            print(json.dumps(dict(row, device=dev.device_kind)), flush=True)
        if not all(r["ok"] for r in rows):
            failed.append(name)
        print(json.dumps({"phase": name, "phase_s": time.perf_counter() - t0}), flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
