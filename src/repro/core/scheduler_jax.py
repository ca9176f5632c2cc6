"""Algorithm 2 as a jitted, fixed-shape ``jax.lax`` program.

One Terastal scheduling round (stage 1: urgency-ordered virtual-deadline
assignment with variant fallback; stage 2: earliest-finish-guarded
backfill by slack gain) re-expressed over padded arrays:

  ready_mask [NJ]          valid request-layer slots
  vdl       [NJ]           absolute virtual deadline of the ready layer
                           (static plan table OR the request's dynamic
                           ``vdl_abs`` state from an online budget
                           policy — pack_view resolves both through
                           ``TerastalScheduler.vdl``, so Python/JAX
                           parity holds under dynamic virtual deadlines)
  vdl_next  [NJ]           Eq. 8's d^v_{l+1} (absolute deadline if last)
  next_min  [NJ]           min_k c_{l+1,k}   (0 if last layer)
  lat       [NJ, NA]       original latencies
  lat_var   [NJ, NA]       variant latencies (+inf when no variant or the
                           accumulated combo would violate theta — the
                           host precomputes incremental V_m membership)
  tau       [NA]           accelerator next-free times
  idle_mask [NA]

Outputs: assign_acc [NJ] (-1 = unassigned), assign_var [NJ] (bool), and
assign_seq [NJ] — the reference emission order (stage-1 assignments
carry their sorted-order position, stage-2 assignments NJ + k), which
the SoA engine needs because the order assignments are emitted fixes
the finish-event push counters (how simultaneous finishes tie-break).

Tie-breaking matches the Python reference bit-for-bit (stable argsort on
best-case slack == sorted(..., key=(slack, rid)); first-minimum argmin ==
min(key=...); first-maximum argmax == strict-improvement replacement),
property-tested in tests/test_scheduler_jax.py.  The round runs in
binary64: every add/sub/compare is then the same IEEE op the Python
kernels execute, so the jitted round is bit-identical on arbitrary
latency tables, not just dyadic ones, so that it can serve as the
online controller in the reference scheduler's place.  Its float operations go through
:mod:`repro.core.f64`: ``jnp`` float64 where the backend is IEEE, the
software binary64 on the TPU (whose float64 is not), with the packers
staging float arrays as their int64 bit patterns there.

64-bit types are scoped, never switched on for the process: every entry
point here that stages arrays or calls a jitted program runs under
:func:`x64` (``jax.enable_x64``), so models and kernels that share the
process keep JAX's default 32-bit types.
"""

from __future__ import annotations

from functools import partial, wraps
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import f64

EPS = 1e-15
NEG = -1e30


def x64(fn):
    """Run ``fn`` with 64-bit types enabled.

    The simulator's device programs must reproduce the Python engines'
    float64 arithmetic exactly; without x64, ``jnp.asarray`` silently
    downcasts f64 inputs to f32 and jit traces in f32.  Wrap every host
    function that stages simulator arrays or calls a simulator jit, so
    the arrays it creates and the traces it triggers are f64."""

    @wraps(fn)
    def run(*args, **kwargs):
        with jax.enable_x64(True):
            return fn(*args, **kwargs)

    return run


#: stage-2 guard variants of TerastalScheduler.backfill_mode (static
#: compile-time argument of :func:`terastal_round`).
BACKFILL_MODES = ("ef", "positive", "paper")


class RoundInputs(NamedTuple):
    ready_mask: jax.Array  # [NJ] bool
    vdl: jax.Array  # [NJ]
    vdl_next: jax.Array  # [NJ]
    next_min: jax.Array  # [NJ]
    lat: jax.Array  # [NJ, NA]
    lat_var: jax.Array  # [NJ, NA]
    tau: jax.Array  # [NA]
    idle_mask: jax.Array  # [NA] bool


class RoundOutputs(NamedTuple):
    assign_acc: jax.Array  # [NJ] int32, -1 = none
    assign_var: jax.Array  # [NJ] bool
    assign_seq: jax.Array  # [NJ] int32 emission order; NJ + NA = unassigned


def _best_case_slack(F, inp: RoundInputs, tau: jax.Array) -> jax.Array:
    finish = F.add(tau[None, :], inp.lat)  # [NJ, NA]
    return F.sub(inp.vdl, F.min(finish, axis=1))


@x64
def terastal_round(inp: RoundInputs, mode: str = "ef") -> RoundOutputs:
    """One jitted Terastal round over :func:`pack_view`
    inputs (compiles once per NJ bucket and mode: ``round_jit``), in the
    platform's binary64 (:func:`f64.for_platform`), as the packers
    staged them."""
    return round_jit(inp, mode=mode, soft=f64.for_platform() is f64.SOFT)


@partial(jax.jit, static_argnames=("mode", "soft"))
def round_jit(inp: RoundInputs, mode: str = "ef", soft: bool = False) -> RoundOutputs:
    if mode not in BACKFILL_MODES:
        raise ValueError(f"unknown backfill mode {mode!r} (have {BACKFILL_MODES})")
    F = f64.SOFT if soft else f64.NATIVE
    NJ, NA = inp.lat.shape
    inf, ninf = F.const(jnp.inf), F.const(-jnp.inf)
    eps = F.const(EPS)

    s_star0 = jnp.where(inp.ready_mask, _best_case_slack(F, inp, inp.tau), inf)
    order = F.argsort(s_star0)  # stable: ties -> lower slot index

    # ---------------- stage 1 ----------------
    def stage1_body(i, state):
        idle, tau, acc, var, seq, remaining = state
        j = order[i]
        active = inp.ready_mask[j] & remaining[j]
        d_v = inp.vdl[j]

        def try_impl(lat_row):
            finish = F.add(tau, lat_row)
            cand = idle & F.le(finish, F.add(d_v, eps)) & F.isfinite(lat_row)
            masked = jnp.where(cand, finish, inf)
            k = F.argmin(masked)
            return cand.any(), k, lat_row[k]

        ok1, k1, c1 = try_impl(inp.lat[j])
        ok2, k2, c2 = try_impl(inp.lat_var[j])
        use1 = active & ok1
        use2 = active & ~ok1 & ok2
        k = jnp.where(use1, k1, k2)
        c = jnp.where(use1, c1, c2)
        assigned = use1 | use2
        idle = jnp.where(assigned, idle.at[k].set(False), idle)
        tau = jnp.where(assigned, tau.at[k].set(F.add(tau[k], c)), tau)
        acc = jnp.where(assigned, acc.at[j].set(k.astype(jnp.int32)), acc)
        var = jnp.where(assigned, var.at[j].set(use2), var)
        seq = jnp.where(assigned, seq.at[j].set(i.astype(jnp.int32)), seq)
        remaining = jnp.where(assigned, remaining.at[j].set(False), remaining)
        return idle, tau, acc, var, seq, remaining

    idle = inp.idle_mask
    tau = inp.tau
    acc0 = jnp.full((NJ,), -1, jnp.int32)
    var0 = jnp.zeros((NJ,), bool)
    seq0 = jnp.full((NJ,), NJ + NA, jnp.int32)
    remaining0 = inp.ready_mask
    idle, tau, acc, var, seq, remaining = jax.lax.fori_loop(
        0, NJ, stage1_body, (idle, tau, acc0, var0, seq0, remaining0)
    )

    # ---------------- stage 2: guarded backfill ----------------
    def stage2_body(k, state):
        idle, tau, acc, var, seq, remaining = state
        k_idle = idle[k]
        s_star = _best_case_slack(F, inp, tau)  # [NJ] current tau

        def score(lat_tab):
            c = lat_tab[:, k]
            finish = F.add(tau[k], c)
            allowed = remaining & F.isfinite(c)
            if mode == "ef":
                # earliest-finish optimality guard across ALL accelerators
                ef_all = F.min(F.add(tau[None, :], lat_tab), axis=1)
                allowed = allowed & F.le(finish, F.add(ef_all, eps))
            s_f = F.sub(F.sub(inp.vdl_next, finish), inp.next_min)
            return jnp.where(allowed, F.sub(s_f, s_star), ninf)

        d_orig = score(inp.lat)  # [NJ] (slot order)
        d_var = score(inp.lat_var)
        # python iterates `remaining` in STAGE-1 SORTED order (j outer,
        # original-then-variant inner), replacing only on strictly-greater
        # (delta, -use_var) — permute through `order` and take the FIRST
        # maximum so exact ties resolve identically.
        d_orig_p, d_var_p = d_orig[order], d_var[order]
        flat = jnp.stack([d_orig_p, d_var_p], axis=1).reshape(-1)  # [NJ*2]
        rank = jnp.tile(jnp.array([0, -1], jnp.int32), NJ)  # original, variant
        best = F.argmax(flat)  # first max in sorted order
        is_max = F.eq(flat, flat[best])
        best = jnp.argmax(jnp.where(is_max, rank, -2))
        j = order[best // 2]
        use_var = (best % 2).astype(bool)
        have = k_idle & F.isfinite(flat[best]) & F.gt(flat[best], ninf)
        if mode == "positive":
            have = have & F.gt(flat[best], F.const(0.0))
        c = jnp.where(use_var, inp.lat_var[j, k], inp.lat[j, k])
        idle = jnp.where(have, idle.at[k].set(False), idle)
        tau = jnp.where(have, tau.at[k].set(F.add(tau[k], c)), tau)
        acc = jnp.where(have, acc.at[j].set(jnp.int32(k)), acc)
        var = jnp.where(have, var.at[j].set(use_var), var)
        seq = jnp.where(have, seq.at[j].set(jnp.int32(NJ + k)), seq)
        remaining = jnp.where(have, remaining.at[j].set(False), remaining)
        return idle, tau, acc, var, seq, remaining

    idle, tau, acc, var, seq, remaining = jax.lax.fori_loop(
        0, NA, stage2_body, (idle, tau, acc, var, seq, remaining)
    )
    return RoundOutputs(acc, var, seq)


# --------------------------------------------------------------- adapter ----


#: smallest NJ bucket; NJ pads up to the next power of two above this.
BUCKET_MIN = 4

#: persistent host-side staging buffers, one set per (NJ_pad, NA) bucket.
#: Reused across pack_view calls so a sweep over ready-queue sizes does
#: not reallocate, and — the real win — ``terastal_round`` sees only
#: O(log max_NJ) distinct shapes, so it compiles once per bucket instead
#: of re-jitting on every ready-queue size.
_HOST_BUFFERS: dict = {}


def bucket_nj(nj: int) -> int:
    """Pad a ready-queue size to its power-of-two shape bucket."""
    if nj <= BUCKET_MIN:
        return BUCKET_MIN
    return 1 << (nj - 1).bit_length()


def _buffers(nj_pad: int, na: int):
    key = (nj_pad, na)
    buf = _HOST_BUFFERS.get(key)
    if buf is None:
        buf = {
            "ready": np.zeros(nj_pad, bool),
            "vdl": np.zeros(nj_pad),
            "vdl_next": np.zeros(nj_pad),
            "next_min": np.zeros(nj_pad),
            "lat": np.full((nj_pad, na), np.inf),
            "lat_var": np.full((nj_pad, na), np.inf),
        }
        _HOST_BUFFERS[key] = buf
    return buf


@x64
def pack_view(view, scheduler) -> Tuple[RoundInputs, list]:
    """Build RoundInputs from a SchedView + TerastalScheduler (host side).
    Returns (inputs, slot->request list).  ``vdl``/``vdl_next`` come from
    ``scheduler.vdl``, which prefers a request's dynamic ``vdl_abs`` state
    (online budget policies) over the frozen plan table — the jitted round
    needs no change for dynamic budgets.

    NJ is padded to a power-of-two shape bucket (>= ``BUCKET_MIN``) with
    persistent host buffers: padded slots have ``ready_mask=False`` (so
    stage 1 skips them and stage 2's ``remaining`` mask never admits
    them) and +inf latency rows, and ``terastal_round`` recompiles at
    most once per bucket per process instead of once per ready-queue
    size — pinned by a compilation-counter test."""
    reqs = sorted(view.ready, key=lambda r: r.rid)
    NJ, NA = len(reqs), view.n_acc
    NJ_pad = bucket_nj(NJ)
    buf = _buffers(NJ_pad, NA)
    ready = buf["ready"]
    vdl = buf["vdl"]
    vdl_next = buf["vdl_next"]
    next_min = buf["next_min"]
    lat = buf["lat"]
    lat_var = buf["lat_var"]
    # reset the pad region (buffers are reused across different NJ)
    ready[:NJ] = True
    ready[NJ:] = False
    vdl[NJ:] = 0.0
    vdl_next[NJ:] = 0.0
    next_min[NJ:] = 0.0
    lat[NJ:] = np.inf
    lat_var[NJ:] = np.inf
    for i, r in enumerate(reqs):
        plan = view.plans[r.model_idx]
        l = r.next_layer
        vdl[i] = scheduler.vdl(plan, r, l)
        if l + 1 < len(plan.model.layers):
            vdl_next[i] = scheduler.vdl(plan, r, l + 1)
            next_min[i] = float(plan.lat[l + 1].min())
        else:
            vdl_next[i] = r.deadline_abs
            next_min[i] = 0.0
        lat[i] = plan.lat[l]
        if scheduler._variant_ok(plan, r, l):
            lat_var[i] = plan.lat_var[l]
        else:
            lat_var[i] = np.inf
    tau = np.array([view.tau(k) for k in range(NA)])
    idle = np.array([view.acc_busy_until[k] <= view.now + 1e-15 for k in range(NA)])
    return _stage(ready, vdl, vdl_next, next_min, lat, lat_var, tau, idle), reqs


def _stage(ready, vdl, vdl_next, next_min, lat, lat_var, tau, idle) -> RoundInputs:
    """Host arrays -> device RoundInputs, float arrays in the platform's
    binary64 (their bit patterns under the software one)."""
    fl = f64.for_platform().to_device
    return RoundInputs(
        ready_mask=jnp.asarray(ready),
        vdl=jnp.asarray(fl(vdl)),
        vdl_next=jnp.asarray(fl(vdl_next)),
        next_min=jnp.asarray(fl(next_min)),
        lat=jnp.asarray(fl(lat)),
        lat_var=jnp.asarray(fl(lat_var)),
        tau=jnp.asarray(fl(tau)),
        idle_mask=jnp.asarray(idle),
    )


# ------------------------------------------- batched trial staging ----

#: persistent seed-major staging buffers for the device-resident trial
#: engine (``repro.core.engine_batch``), one set per (B_pad, NR_pad)
#: bucket.  Same idea as ``_HOST_BUFFERS`` one level up: the batch
#: engine's jitted program sees only O(log max_B x log max_NR) distinct
#: shapes, so it compiles once per (seed-bucket, horizon-bucket) pair —
#: pinned by a compilation-counter test in tests/test_round_kernels.py.
_TRIAL_BUFFERS: dict = {}


def _trial_buffers(b_pad: int, nr_pad: int):
    key = (b_pad, nr_pad)
    buf = _TRIAL_BUFFERS.get(key)
    if buf is None:
        buf = {
            # +1 sentinel column: the event loop peeks arr_t[ai] with
            # ai == n_ev after the last arrival; the pad is +inf so the
            # peek reads "no more arrivals" without a bounds branch.
            "arr_t": np.full((b_pad, nr_pad + 1), np.inf),
            "arr_m": np.zeros((b_pad, nr_pad), np.int32),
            "dl": np.full((b_pad, nr_pad), np.inf),
            "dl12": np.full((b_pad, nr_pad), np.inf),
            "n_ev": np.zeros(b_pad, np.int32),
        }
        _TRIAL_BUFFERS[key] = buf
    return buf


def bucket_ev(n: int) -> int:
    """Pad an event-horizon length to its shape bucket.

    Finer-grained than ``bucket_nj``: rungs at every power of two AND at
    1.5x the previous one (..., 96, 128, 192, 256, 384, ...).  The batch
    engine's per-iteration cost is linear in the padded horizon, so pow2
    rounding's worst case (~2x dead width just past a boundary) is real
    wall-clock; the extra rungs cap the waste at ~33% for one more
    compile-cache entry per size class."""
    n = max(int(n), BUCKET_MIN)
    p = 1 << (n - 1).bit_length()
    h = (p >> 1) + (p >> 2)      # 1.5 * previous pow2 rung
    return h if n <= h else p


def pack_trials(events: "list[tuple]", deadline_by_model: np.ndarray):
    """Stage B seeds' pre-generated release events into the persistent
    seed-major trial buffers (the batched counterpart of
    :func:`pack_view`).

    ``events`` is ``[(times, models)]`` per seed — the output of
    ``workload.batch_release_events`` — and ``deadline_by_model`` maps
    model_idx -> relative deadline.  Both the seed axis and the event
    horizon are padded to pow2 shape buckets (``bucket_nj``), so the
    batch engine's jitted program compiles once per (B, NR) bucket pair;
    pad lanes carry ``n_ev = 0`` (immediately drained) and pad slots
    ``arr_t = +inf`` (never popped).  Absolute deadlines are computed
    here with the same IEEE-f64 adds the reference engine performs per
    request (``now + plan.deadline``; ``dl12 = dl + 1e-12`` mirrors its
    inline miss/drop epsilon), so downstream comparisons are bit-equal.

    Returns ``(buf, b_pad, nr_pad)`` where ``buf`` holds the padded
    numpy arrays (views of the persistent buffers — consume before the
    next call)."""
    B = len(events)
    NR = max((len(t) for t, _ in events), default=0)
    b_pad = bucket_nj(B)
    nr_pad = bucket_ev(max(NR, 1))
    buf = _trial_buffers(b_pad, nr_pad)
    buf["arr_t"][:] = np.inf
    buf["dl"][:] = np.inf
    buf["dl12"][:] = np.inf
    buf["arr_m"][:] = 0
    buf["n_ev"][:] = 0
    for b, (times, models) in enumerate(events):
        n = len(times)
        buf["n_ev"][b] = n
        if not n:
            continue
        buf["arr_t"][b, :n] = times
        buf["arr_m"][b, :n] = models
        dl = times + deadline_by_model[models]
        buf["dl"][b, :n] = dl
        buf["dl12"][b, :n] = dl + 1e-12
    return buf, b_pad, nr_pad


_FAULT_CODES = {"down": 0, "up": 1, "scale": 2}


def pack_fault_epochs(fault_model, plans, duration, seeds, b_pad: int, lp: int):
    """Pre-bind each lane's capability timeline as time-indexed epoch
    planes for the batch engine's fault path.

    A lane's capability state is piecewise-constant between its fault
    events, so the whole timeline is NF events plus NF+1 *epochs*; this
    stages, per lane, the event stream (``fe_t``/``fe_acc``/``fe_code``/
    ``fe_val``/``n_f``, and ``fe_ratio``: a scale event's new factor over
    the accelerator's previous one, divided here so that the device
    program needs no division) and, per epoch, every capability-derived table
    the round kernels read — the ``[NA]`` latency multiplier
    (``mult_ep``), the virtual-deadline chains (``vdlr_ep``; the
    re-tightened chains under ``retighten=true`` via
    ``faults.retightened_vdl``, the frozen offline chains otherwise),
    the remaining-min suffix sums (``rm_ep``) and per-layer min
    latencies (``minl_ep``).  All planes are replayed event-by-event
    through the exact host helpers the scalar engines call
    (``effective_plans`` / ``fault_multipliers``), so fault-time
    arithmetic is bit-identical by construction.

    The event axis is padded to a pow2 bucket (one compile per bucket);
    pad events carry ``fe_t = +inf`` (never popped) and pad epochs
    repeat the lane's final capability state (never entered).  Returns
    ``(fbuf, nf_pad, n_spans)`` with ``n_spans`` the per-seed
    intersecting-window counts for ``SimResult.faulted_spans``.
    """
    from repro.core.faults import (
        effective_plans,
        fault_multipliers,
        retightened_vdl,
    )

    M = len(plans)
    NA = plans[0].platform.n_acc
    timelines = [fault_model.timeline(NA, duration, s) for s in seeds]
    NF = max((len(ev) for ev, _ in timelines), default=0)
    nf_pad = 1 << (max(NF, 1) - 1).bit_length()

    fbuf = {
        # +1 sentinel column: the loop peeks fe_t[fi] with fi == n_f
        # after the last fault; +inf reads "no more faults"
        "fe_t": np.full((b_pad, nf_pad + 1), np.inf),
        "fe_acc": np.zeros((b_pad, nf_pad), np.int32),
        "fe_code": np.zeros((b_pad, nf_pad), np.int32),
        "fe_val": np.ones((b_pad, nf_pad)),
        "fe_ratio": np.ones((b_pad, nf_pad)),
        "n_f": np.zeros(b_pad, np.int32),
        "mult_ep": np.ones((b_pad, nf_pad + 1, NA)),
        "vdlr_ep": np.zeros((b_pad, nf_pad + 1, M, lp + 1)),
        "rm_ep": np.zeros((b_pad, nf_pad + 1, M, lp + 2)),
        "minl_ep": np.zeros((b_pad, nf_pad + 1, M, lp)),
    }

    def fill_epoch(b, e, eff, mult):
        fbuf["mult_ep"][b, e] = mult
        chains = (
            retightened_vdl(plans, eff)
            if fault_model.retighten
            else [None] * M
        )
        for m, (p, ep) in enumerate(zip(plans, eff)):
            L = len(p.model.layers)
            ch = chains[m]
            fbuf["vdlr_ep"][b, e, m, :L] = p.vdl_rel if ch is None else ch
            fbuf["rm_ep"][b, e, m, : L + 1] = ep.remaining_min
            fbuf["minl_ep"][b, e, m, :L] = ep.min_lat

    nominal = fault_multipliers([1.0] * NA, [True] * NA)
    fill_epoch(0, 0, plans, nominal)
    # broadcast the nominal epoch everywhere (pad lanes, epoch 0, and pad
    # epochs start from it; the replay below overwrites live epochs)
    for key in ("mult_ep", "vdlr_ep", "rm_ep", "minl_ep"):
        fbuf[key][:, :] = fbuf[key][0, 0]

    n_spans = []
    for b, (events, spans) in enumerate(timelines):
        n_spans.append(spans)
        fbuf["n_f"][b] = len(events)
        avail = [True] * NA
        fscale = [1.0] * NA
        for e_i, ev in enumerate(events):
            fbuf["fe_t"][b, e_i] = ev.t
            fbuf["fe_acc"][b, e_i] = ev.acc
            fbuf["fe_code"][b, e_i] = _FAULT_CODES[ev.code]
            fbuf["fe_val"][b, e_i] = ev.value if ev.code == "scale" else 1.0
            if ev.code == "down":
                avail[ev.acc] = False
            elif ev.code == "up":
                avail[ev.acc] = True
            else:
                fbuf["fe_ratio"][b, e_i] = ev.value / fscale[ev.acc]
                fscale[ev.acc] = ev.value
            mult = fault_multipliers(fscale, avail)
            eff = effective_plans(plans, mult)
            fill_epoch(b, e_i + 1, eff, mult)
        # pad epochs (fi never reaches them) repeat the final state
        if len(events) < nf_pad:
            for key in ("mult_ep", "vdlr_ep", "rm_ep", "minl_ep"):
                fbuf[key][b, len(events) + 1 :] = fbuf[key][b, len(events)]
    return fbuf, nf_pad, n_spans
