"""Device-resident mega-batched trial engine: B seeds, ONE device program.

The third engine behind ``simulate()`` (after the reference event loop
and the SoA engine) and the first one where the JAX path wins on CPU:
instead of jitting a single scheduler round (PR 5's honest negative —
~1ms dispatch per round, crossover INF), the WHOLE trial event loop runs
on device as a jitted ``lax.while_loop``, ``vmap``-ed across the seed
axis.  One host sync per trial *batch* instead of one per round — the
amortization ROADMAP item 4 calls for.

How it stays bit-identical to the reference engine
--------------------------------------------------
* **Events.**  Open-loop arrivals are pre-generated per seed on the host
  (``workload.batch_release_events`` — the exact per-seed variate
  streams) and staged seed-major into pow2 (B, NR) bucket buffers
  (``scheduler_jax.pack_trials``).  In the reference heap, arrival
  counters 0..n_ev-1 are assigned in sorted-stream order and every
  finish counter is larger, so (a) arrivals pop in stream order — the
  arrival index IS the rid, giving slot == rid on device — and (b) an
  arrival always beats a same-time finish.  Outstanding finishes are at
  most one per accelerator, so the heap reduces to per-accelerator
  ``(fin_t, fin_cnt)`` slots: pop = lexicographic (time, counter) min
  with arrivals winning time ties.
* **Rounds.**  The per-round kernels transcribe ``engine_soa``'s
  vectorized round (``_kern_terastal_vec``) and the reference
  FCFS/EDF/DREAM walks op-for-op in jnp: same IEEE-f64 adds/subs/
  compares, first-minimum argmins (slot == rid makes ``argmin``'s
  first-occurrence rule the rid tie-break), the stage-2 strictly-greater
  replacement scan, and reference emission order (stage-1 pick order
  then stage-2 ascending k) so finish-event counters tie-break
  identically.
* **Accounting.**  Per-request state lives in parallel device arrays
  (the SoA layout lifted wholesale into jnp); per-model counters are
  integer reductions on the host afterwards.  ``retained_sum`` is
  re-accumulated on the host in completion order by replaying each
  completed request's variant-application sequence through the same
  frozenset unions and ``ModelPlan.combo_retained`` calls the reference
  performs — CPython set iteration order and float accumulation order
  included — so the float sums are bit-equal, not just close.
* **Arithmetic.**  Every float op goes through :mod:`repro.core.f64`:
  JAX's float64 where it is IEEE binary64 (CPU, GPU), binary64 in
  software on int64 bit patterns on the TPU, whose float64 is a pair of
  float32s (:func:`f64.for_platform`).

Speculation and its host-side validation
----------------------------------------
The device program is a speculative rollout of the *entire* event
horizon: it assumes every event is either a pre-generated arrival or a
finish of its own making.  ``simulate_batch`` validates that assumption
twice — statically, by rejecting any axis that could inject events the
speculation cannot cover (closed-loop release coupling, admission
policies, non-inert budget policies, custom schedulers) with the named
:class:`BatchUnsupportedError`, and dynamically, by checking the
returned ``drained`` flag (every lane consumed its horizon within the
exact event-count bound).  Unsupported axes NEVER silently fall back —
callers choose the scalar engines explicitly.  The same holds for the
round's slot window: the host proves its width from the staged releases
(:func:`ready_span_bound`), and the device checks it every iteration
(``win_miss``).

Fault injection (``restart`` interrupted-work policy)
-----------------------------------------------------
Capability events don't break the speculation: a fault timeline is
seed-deterministic, so the host pre-binds it as a time-indexed epoch
schedule (``scheduler_jax.pack_fault_epochs``) — the event stream plus,
per epoch, the latency multiplier and every capability-derived table
(re-tightened virtual-deadline chains under ``retighten=true``).  On
device the lane tracks an epoch cursor, evicts/re-times in-flight
layers op-for-op (``evict_busy_adjust``/``retime_busy_adjust``
replicated in jnp, exact variant undo via a saved pre-apply retained
product), and replays orphaned finish events as *ghost* pops, because
the scalar engines' stale heap pops still trigger scheduling rounds.
Only ``interrupted="resume"`` stays rejected: fractional layer progress
re-times re-dispatches mid-rollout, which pre-bound epochs cannot
express.

Known exactness hazard (documented, not observed): the device-side
variant-combination validity check accumulates the retained-accuracy
product incrementally in application order, while the reference
recomputes it from scratch in frozenset iteration order.  Products of
<= 2 factors are bit-equal (IEEE multiplication is commutative); with
>= 3 applied variants a different association order could differ by an
ulp and flip the ``>= theta`` verdict if the product lands within an
ulp of theta.  The pinned differential grid (tests/test_engine_batch.py)
would catch it; ``retained_sum`` itself is immune (host replay above).
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.scheduler import (
    DreamScheduler,
    EdfScheduler,
    FcfsScheduler,
    Scheduler,
    TerastalScheduler,
)
from repro.core.simulator import (
    ArrivalProcess,
    ClosedLoopClients,
    DEFAULT_ARRIVAL,
    ModelStats,
    SimResult,
    TaskSpec,
)
from repro.core.variants import ModelPlan

from repro.core import f64, obs, scheduler_jax
from repro.core.scheduler_jax import jax, jnp, x64

lax = jax.lax

_INF = float("inf")

#: The slots the round reads when the host proves the ready set fits in
#: them (:func:`ready_span_bound`).  On a v5e the round's device time per
#: loop iteration is flat from 96 to 128 padded slots (63.3 and 63.3 us)
#: and 1.3x that at 192 (81.5 us; PERF.md section 5); a 256-slot program
#: reading a 128-slot window spends 65.9 us, what the narrowest shapes
#: cost.
ROUND_WINDOW = 128
#: The window starts on a multiple of this many slots: it is one of a few
#: static slices, chosen per lane by a select.  A per-lane dynamic slice
#: is a gather under ``vmap``, which the v5e runs as a loop per call
#: (PERF.md section 6).  A ready set ``span`` rids wide then fits
#: when ``span <= ROUND_WINDOW - ROUND_ALIGN + 1``.
ROUND_ALIGN = 32


class BatchUnsupportedError(ValueError):
    """A simulation axis the batched engine does not cover.

    Raised by :func:`simulate_batch` validation — never a silent
    fallback.  The message names the axis; use ``engine="soa"`` /
    ``engine="reference"`` (or ``engine="auto"``) for these cells.
    """


class _Tables(NamedTuple):
    """Shared per-model device tables (broadcast across the seed axis)."""

    lat: "jnp.ndarray"     # [M, LP, NA] original latencies, +inf pad
    latv: "jnp.ndarray"    # [M, LP, NA] variant latencies, +inf where none
    vdlr: "jnp.ndarray"    # [M, LP+1]  relative virtual deadlines (pad 0)
    rm: "jnp.ndarray"      # [M, LP+2]  remaining-min suffix sums (pad 0)
    minl: "jnp.ndarray"    # [M, LP]    per-layer min latency (pad 0)
    nl: "jnp.ndarray"      # [M] i32    layer counts
    factor: "jnp.ndarray"  # [M, LP]    per-variant retained factor (pad 0)
    hasv: "jnp.ndarray"    # [M, LP] bool  layer has a variant
    theta: "jnp.ndarray"   # [M]


class _Out(NamedTuple):
    """Per-lane device outputs fetched in the single host sync."""

    state: "jnp.ndarray"     # [B, NR] final status: 3 completed / 4 dropped
    #                          / 0 still ready/running (or unreleased)
    missed: "jnp.ndarray"    # [B, NR] bool
    app_seq: "jnp.ndarray"   # [B, NR, LP] application order index, -1 unused
    app_cnt: "jnp.ndarray"   # [B, NR] i32 variants applied per request
    done_seq: "jnp.ndarray"  # [B, NR] global completion order, -1 if not
    busy_t: "jnp.ndarray"    # [B, NA]
    busy_h: "jnp.ndarray"    # [B, NA]
    rounds: "jnp.ndarray"    # [B] i32
    drained: "jnp.ndarray"   # [B] bool — horizon fully consumed
    evict_cnt: "jnp.ndarray"  # [B, NR] i32 in-flight evictions (faults)
    remap_cnt: "jnp.ndarray"  # [B, NR] i32 post-eviction re-dispatches
    iters: "jnp.ndarray"     # [B] i32 loop iterations (events popped); the
    #                          max over lanes is the vmapped loop's trip count
    live_peak: "jnp.ndarray"  # [B] i32 most requests live at once (ready
    #                          or running)
    win_miss: "jnp.ndarray"  # [B] bool — a round found a ready request
    #                          outside its slot window (an engine bug)


def _build_tables(plans: Sequence[ModelPlan]) -> Tuple[_Tables, int, int]:
    """Precompute the per-model tables (numpy); returns (tables, LP, NA)."""
    from repro.core.accuracy import combo_retained_fraction

    M = len(plans)
    NA = plans[0].platform.n_acc
    LP = max(len(p.model.layers) for p in plans)
    lat = np.full((M, LP, NA), np.inf)
    latv = np.full((M, LP, NA), np.inf)
    vdlr = np.zeros((M, LP + 1))
    rm = np.zeros((M, LP + 2))
    minl = np.zeros((M, LP))
    nl = np.zeros(M, np.int32)
    factor = np.zeros((M, LP))
    hasv = np.zeros((M, LP), bool)
    theta = np.zeros(M)
    for m, p in enumerate(plans):
        L = len(p.model.layers)
        nl[m] = L
        lat[m, :L] = p.lat
        latv[m, :L] = p.lat_var
        vdlr[m, :L] = p.vdl_rel
        rm[m, : L + 1] = p.remaining_min
        minl[m, :L] = p.min_lat
        theta[m] = p.theta
        for l, v in p.variants.items():
            hasv[m, l] = True
            factor[m, l] = combo_retained_fraction((v.loss,))
    t = _Tables(lat=lat, latv=latv, vdlr=vdlr, rm=rm, minl=minl, nl=nl,
                factor=factor, hasv=hasv, theta=theta)
    return t, LP, NA


@functools.partial(
    jax.jit,
    static_argnames=(
        "kind", "mode", "use_budgets", "use_variants", "na", "lp", "faulted",
        "soft", "win",
    ),
)
def _run_trials(
    T: _Tables,
    arr_t, arr_m, dl, dl12, n_ev,  # [B, NR+1], [B, NR], [B, NR], [B, NR], [B]
    duration, max_it,
    # fault lane (dummy minimal arrays when ``faulted=False``): the
    # pre-bound capability timeline — per-lane event stream plus the
    # time-indexed epoch planes (scheduler_jax.pack_fault_epochs)
    fe_t, fe_acc, fe_code, fe_val, fe_ratio, n_f,  # [B,NF+1],[B,NF]x4,[B]
    mult_ep,  # [B, NF+1, NA]
    vdlr_ep,  # [B, NF+1, M, LP+1]
    rm_ep,    # [B, NF+1, M, LP+2]
    minl_ep,  # [B, NF+1, M, LP]
    *, kind: str, mode: str, use_budgets: bool, use_variants: bool,
    na: int, lp: int, win: int, faulted: bool = False, soft: bool = False,
) -> _Out:
    """The whole-trial device program: vmap(lane while_loop) over seeds.

    Compiles once per ((B, NR) shape bucket x scheduler config) — pinned
    via ``_run_trials._cache_size()`` by the compilation-counter test.
    jax's batched ``while_loop`` masks carry updates for lanes whose
    predicate is already false, so lanes drain independently; the loop
    runs until the slowest lane finishes.

    Request slot == rid == arrival-stream index, so ``argmin``'s
    first-occurrence rule IS the reference's rid tie-break.

    ``win`` (static) is the width ``W`` of the slots the round reads:
    all ``NR``, or ``W`` contiguous rows in rid order from ``base``, the
    first ready rid rounded down to a multiple of :data:`ROUND_ALIGN`
    (at most ``NR - W``), so the kernels and their tie-breaks are
    unchanged and a pick maps back as ``base + i``.
    ``stage_batch`` picks ``W < NR`` only where :func:`ready_span_bound`
    proves every round's ready set fits, and each iteration checks it
    (``win_miss``).  An earlier ring window (state in a ``rid % W``
    ring) was reverted: judged on CPU times, where the round's cost
    grows linearly with width, it needed two-phase rid tie-breaks and
    overflowed on saturation traffic, whose live span really is wide.

    Every float64 operation goes through ``F`` (:mod:`repro.core.f64`):
    ``jnp`` float64 where the backend is IEEE, and with ``soft=True``
    binary64 in software on the int64 bit patterns every float array
    then holds (the TPU's float64 is not IEEE).

    Each part of the loop body runs under a ``jax.named_scope`` (one of
    ``obs.SCOPES``; ``round/stage1`` and ``round/stage2`` inside the
    Terastal kernel), so every device operation names its stage in its
    op name; scopes are metadata and change no number.  Each lane also
    returns its iteration count (``iters``) and the most requests live
    at once (``live_peak``, one [NR] reduction per iteration).
    """
    F = f64.SOFT if soft else f64.NATIVE
    INF, NINF, ZERO = F.const(_INF), F.const(-_INF), F.const(0.0)
    EPS15 = F.const(1e-15)
    NA, LP = na, lp
    NR = arr_m.shape[-1]
    W = win
    NF = fe_acc.shape[-1]
    I32 = jnp.int32

    class St(NamedTuple):
        ai: object; it: object; cnt: object; rounds: object; done_ctr: object
        state: object; layer: object
        c_lat: object; c_latv: object
        c_vdl: object; c_vdln: object; c_nm: object; c_rm: object; c_ek: object
        ret: object; app_seq: object; app_cnt: object
        missed: object; done_seq: object
        busy: object; busy_t: object; busy_h: object
        fin_t: object; fin_cnt: object; run_req: object
        # fault lane (zero-cost placeholders when ``faulted=False``):
        # epoch cursor, per-acc throttle state, ghost-finish slots (stale
        # heap entries the scalar engines pop as no-ops — their pops
        # still trigger rounds, so the device must reproduce them), the
        # in-flight dispatch bookkeeping eviction needs to undo, and the
        # per-request eviction/remap counters
        fi: object; fscale: object
        gh_t: object; gh_cnt: object; gh_n: object
        disp_t0: object; disp_w: object; disp_h: object
        run_uv: object; run_prev_ret: object
        ev_pend: object; evict_cnt: object; remap_cnt: object
        live_pk: object  # most requests live at once (ready or running)
        win_miss: object  # a ready request lay outside the round's window

    def one_lane(at, am, d_abs, d_eps12, ne,
                 fe_t, fe_acc, fe_code, fe_val, fe_ratio, nf,
                 MULT_EP, VDLR_EP, RM_EP, MINL_EP):
        # State updates are ONE-HOT PREDICATED SELECTS, not scatters: a
        # single-row write becomes ``where(arange == idx, val, arr)`` with
        # an out-of-range sentinel index meaning "masked, write nothing".
        # Two earlier drafts were 2-3x slower end to end: lax.cond +
        # whole-carry tree-selects (vmap executes both branches and copies
        # the full ~65KB/lane carry per select), then ``.at[idx].set(...,
        # mode="drop")`` scatters (bit-correct, but a vmapped scatter
        # lowers to a slow per-row loop on CPU, and the body had ~65 of
        # them).  One-hot selects fuse into the surrounding elementwise
        # work; only the [NR, LP] variant-sequence table keeps a real
        # scatter (a 2D one-hot mask would touch NR*LP lanes per pick).
        NRi = jnp.asarray(NR, I32)  # sentinel: matches no row
        NAi = jnp.asarray(NA, I32)
        NFi = jnp.asarray(NF, I32)
        IMAXi = jnp.asarray(jnp.iinfo(I32).max, I32)
        NRa = jnp.arange(NR, dtype=I32)
        NAa = jnp.arange(NA, dtype=I32)
        NFa = jnp.arange(NF, dtype=I32)

        # -- per-event row bind: request r becomes ready at layer l ---------
        @obs.scope("bind")
        def bind(st: St, pred, r, l, m):
            a = at[r]
            dr = d_abs[r]
            lat_row = T.lat[m, l]
            if use_variants:
                # LayerVariantFeasible at push time (static while ready):
                # empty-combo / singleton cases are exact; see the module
                # docstring for the >= 3-variant ulp hazard.
                vok = T.hasv[m, l] & F.ge(F.mul(st.ret[r], T.factor[m, l]), T.theta[m])
                latv_row = jnp.where(vok, T.latv[m, l], INF)
            else:
                latv_row = F.full((NA,), _INF)
            has_next = (l + 1) < T.nl[m]
            if use_budgets:
                vdl = F.add(a, T.vdlr[m, l])
                vdln = jnp.where(has_next, F.add(a, T.vdlr[m, l + 1]), dr)
            else:
                vdl = F.sub(dr, T.rm[m, l + 1])
                vdln = jnp.where(has_next, F.sub(dr, T.rm[m, l + 2]), dr)
            nm = jnp.where(has_next, T.minl[m, l + 1], ZERO)
            hit = NRa == jnp.where(pred, r, NRi)
            # the two [NA, NR] cache planes take a one-hot column select at
            # every width: with the slots in the lanes it rewrites NA lane
            # rows per 128 slots, and a row scatter would pin the planes
            # to an NA-minor layout on the TPU past 128 slots
            return st._replace(
                c_lat=jnp.where(hit[None, :], lat_row[:, None], st.c_lat),
                c_latv=jnp.where(hit[None, :], latv_row[:, None], st.c_latv),
                c_vdl=jnp.where(hit, vdl, st.c_vdl),
                c_vdln=jnp.where(hit, vdln, st.c_vdln),
                c_nm=jnp.where(hit, nm, st.c_nm),
                c_rm=jnp.where(hit, T.rm[m, l], st.c_rm),
                c_ek=jnp.where(hit, F.sub(dr, T.rm[m, l + 1]), st.c_ek),
            )

        # -- scheduler kernels ----------------------------------------------
        # Each kernel returns a PYTHON list of (valid, i, k, use_var, cost)
        # traced-scalar tuples in reference emission order (stage-1 pick
        # order, then stage-2 ascending k); the unrolled pick loops make
        # the emission buffer a compile-time structure instead of a device
        # array, so applying emissions needs no compaction scatters.
        def col_adds(plane, tau):
            """``[plane[k] + tau[k] for k]`` over an [NA, NR] plane: one
            [NR] row per accelerator.  Natively per row: a static-k slice
            fuses into its elementwise consumers, so the round never
            materializes an [NA, NR] f64 temporary.  In software binary64
            as ONE [NA, NR] add: each software add is a large program, and
            NA copies of it multiply compile time."""
            if soft:
                full = F.add(plane, tau[:, None])
                return [full[k] for k in range(NA)]
            return [F.add(plane[k], tau[k]) for k in range(NA)]

        # Both kernels read the round's rows (all NR slots, or the W-slot
        # window): ``st``'s per-request planes, ``ready``, and the arrival
        # and deadline rows ``at_r``/``d_r`` the greedy keys use.
        def kern_terastal(st: St, ready, idle0, now, at_r, d_r):
            # Column-unrolled over the NA accelerators (``col_adds``):
            # fo/fv/f0/ev are per-column [NR] chains.  Same IEEE adds/
            # compares — pairwise minimums and per-column adds are the
            # exact ops the materialized form ran, in the same order.
            rows = jnp.arange(ready.shape[0], dtype=I32)
            with jax.named_scope("stage1"):
                tau0 = F.maximum(st.busy, now)                   # [NA]
                fo_c = col_adds(st.c_lat, tau0)
                fv_c = col_adds(st.c_latv, tau0)
                fmin = fo_c[0]
                for k in range(1, NA):
                    fmin = F.minimum(fmin, fo_c[k])
                keys = F.sub(st.c_vdl, fmin)  # stage-1 (slack, rid) sort key
                d_eps = F.add(st.c_vdl, EPS15)
                oko_c = [F.le(f, d_eps) for f in fo_c]
                okv_c = [F.le(f, d_eps) for f in fv_c]  # +inf (no variant) fails
                idle = idle0
                alive = ready
                picks = []
                # stage 1: repeated (slack, rid)-argmin over feasible slots;
                # argmin's first-occurrence rule == rid tie-break (slot == rid).
                # Each accelerator takes at most one stage-1 pick, so stage 2's
                # tau is tau0 plus one cost (c1) per picked accelerator.
                c1 = F.full(NA, 0.0)
                hit1 = jnp.zeros(NA, bool)
                for _ in range(NA):
                    feas_any = (oko_c[0] | okv_c[0]) & idle[0]
                    for k in range(1, NA):
                        feas_any = feas_any | ((oko_c[k] | okv_c[k]) & idle[k])
                    feas = alive & feas_any
                    mk = jnp.where(feas, keys, INF)
                    i = F.argmin(mk).astype(I32)
                    valid = F.lt(mk[i], INF)
                    fo_i = jnp.stack([f[i] for f in fo_c])  # [NA], round-start tau
                    fv_i = jnp.stack([f[i] for f in fv_c])
                    vo = jnp.where(idle & F.le(fo_i, d_eps[i]), fo_i, INF)
                    ko = F.argmin(vo).astype(I32)
                    any_o = F.lt(vo[ko], INF)  # original first (lines 4-10)
                    vv = jnp.where(idle & F.le(fv_i, d_eps[i]), fv_i, INF)
                    kv = F.argmin(vv).astype(I32)
                    use_var = ~any_o
                    k_sel = jnp.where(any_o, ko, kv)
                    c = jnp.where(use_var, st.c_latv[k_sel, i], st.c_lat[k_sel, i])
                    picks.append((valid, i, k_sel, use_var, c))
                    hitk = (NAa == k_sel) & valid
                    c1 = jnp.where(hitk, c, c1)
                    hit1 = hit1 | hitk
                    idle = idle & ~hitk
                    alive = alive & ~((rows == i) & valid)
                tau = jnp.where(hit1, F.add(tau0, c1), tau0)
            with jax.named_scope("stage2"):
                # stage 2: backfill remaining idle accelerators, ascending k.
                # Original and variant rows are stacked ([2, NR]: 0 original,
                # 1 variant) so each chain is one float op, not two.
                for k in range(NA):
                    fo_k = col_adds(st.c_lat, tau)  # s* at CURRENT tau
                    f0 = fo_k[0]
                    for kk in range(1, NA):
                        f0 = F.minimum(f0, fo_k[kk])
                    s_star = F.sub(st.c_vdl, f0)
                    cv = st.c_latv[k]
                    if mode == "ef":
                        fv_k = col_adds(st.c_latv, tau)
                        ev = fv_k[0]
                        for kk in range(1, NA):
                            ev = F.minimum(ev, fv_k[kk])
                        fin = jnp.stack([fo_k[k], fv_k[k]])
                        # earliest-finish guards
                        ok = F.le(fin, F.add(jnp.stack([f0, ev]), EPS15))
                        ok = ok & jnp.stack([alive, F.isfinite(cv)])
                    else:
                        fin = jnp.stack([fo_k[k], F.add(cv, tau[k])])
                        ok = jnp.stack([jnp.ones_like(alive), F.isfinite(cv)])
                    t = F.sub(F.sub(F.sub(st.c_vdln, fin), st.c_nm), s_star)  # Eq. 8-9
                    dd = jnp.where(ok & alive, t, NINF)
                    do, dv = dd[0], dd[1]
                    mo, mv = F.max(dd, axis=1)
                    orig_wins = F.ge(mo, mv)  # (delta, -use_var) strictly-greater
                    best = jnp.where(orig_wins, mo, mv)
                    valid = idle[k] & F.gt(best, NINF)
                    if mode == "positive":
                        valid = valid & F.gt(best, ZERO)
                    d_sel = jnp.where(orig_wins, do, dv)
                    tb = jnp.where(F.eq(d_sel, best), keys, INF)
                    i = F.argmin(tb).astype(I32)  # earliest in stage-1 order
                    use_var = ~orig_wins
                    c = jnp.where(use_var, st.c_latv[k, i], st.c_lat[k, i])
                    picks.append((valid, i, jnp.asarray(k, I32), use_var, c))
                    tau = jnp.where((NAa == k) & valid, F.add(tau, c), tau)
                    alive = alive & ~((rows == i) & valid)
            return picks

        def kern_greedy(st: St, ready, idle0, now, at_r, d_r):
            rows = jnp.arange(ready.shape[0], dtype=I32)
            if kind == "fcfs":
                key = at_r                          # (arrival, rid)
            elif kind == "edf":
                key = st.c_ek                       # (edf deadline, rid)
            else:  # dream
                key = F.sub(F.sub(d_r, now), st.c_rm)  # (slack, rid)
            tau0 = F.maximum(st.busy, now)          # round-start, not updated
            idle = idle0
            alive = ready
            fK = jnp.asarray(False)
            picks = []
            for _ in range(NA):
                mk = jnp.where(alive, key, INF)
                i = F.argmin(mk).astype(I32)
                ok_i = F.lt(mk[i], INF)
                if kind == "dream":
                    vals = jnp.where(idle, F.add(tau0, st.c_lat[:, i]), INF)
                else:   # fcfs/edf: lowest latency, first-min ascending k
                    vals = jnp.where(idle, st.c_lat[:, i], INF)
                k = F.argmin(vals).astype(I32)
                valid = ok_i & F.lt(vals[k], INF)
                c = st.c_lat[k, i]
                picks.append((valid, i, k, fK, c))
                idle = idle & ~((NAa == k) & valid)
                alive = alive & ~((rows == i) & valid)
            return picks

        kern = kern_terastal if kind == "terastal" else kern_greedy

        # -- the event loop --------------------------------------------------
        def cond(st: St):
            active = (st.ai < ne) | jnp.any(st.run_req >= 0)
            if faulted:
                active = active | (st.fi < nf) | jnp.any(F.lt(st.gh_t, INF))
            return active & (st.it < max_it)

        def body(st: St):
            with obs.scope("pop"):
                st = st._replace(it=st.it + 1)
                # pop: lexicographic (time, counter) min; arrivals beat
                # same-time finishes (their heap counters are always smaller).
                # With faults: arrival < fault < finish/ghost at equal times
                # (the reference allocates arrival counters first, then fault
                # counters, then dynamic finish counters), and ghost-vs-finish
                # ties break on the stored finish counters.
                arr_next = at[st.ai]
                ft_min = F.min(st.fin_t)
                k_f = jnp.argmin(
                    jnp.where(F.eq(st.fin_t, ft_min), st.fin_cnt, IMAXi)
                ).astype(I32)
                if faulted:
                    f_next = fe_t[st.fi]
                    gh_min = F.min(st.gh_t)
                    oth = F.minimum(ft_min, gh_min)
                    is_arr = F.le(arr_next, F.minimum(f_next, oth))
                    is_fault = (~is_arr) & F.le(f_next, oth)
                    g_i = jnp.argmin(
                        jnp.where(F.eq(st.gh_t, gh_min), st.gh_cnt, IMAXi)
                    ).astype(I32)
                    is_ghost = (~is_arr) & (~is_fault) & (
                        F.lt(gh_min, ft_min)
                        | (F.eq(gh_min, ft_min) & (st.gh_cnt[g_i] < st.fin_cnt[k_f]))
                    )
                    is_fin = (~is_arr) & (~is_fault) & (~is_ghost)
                    now = jnp.where(
                        is_arr, arr_next,
                        jnp.where(is_fault, f_next,
                                  jnp.where(is_ghost, gh_min, ft_min)),
                    )
                    # ghost pop: a stale finish is a no-op state-wise; its pop
                    # still falls through to the round logic below
                    st = st._replace(
                        gh_t=jnp.where(
                            NFa == jnp.where(is_ghost, g_i, NFi), INF, st.gh_t
                        )
                    )
                else:
                    is_arr = F.le(arr_next, ft_min)
                    is_fin = ~is_arr
                    now = jnp.where(is_arr, arr_next, ft_min)

                # finish candidate (garbage when not is_fin; writes are masked)
                pop_rf = is_arr | is_fin
                r_f = st.run_req[k_f]
                r = jnp.where(is_arr, st.ai, r_f)  # slot == rid == stream index
                m = am[r]
                l_new = jnp.where(is_arr, 0, st.layer[r] + 1)
                done = is_fin & (l_new >= T.nl[m])

                hit_f = NAa == jnp.where(is_fin, k_f, NAi)
                r_m = jnp.where(pop_rf, r, NRi)
                hit_r = NRa == r_m
                hit_d = NRa == jnp.where(done, r, NRi)
                st = st._replace(
                    ai=st.ai + is_arr.astype(I32),
                    fin_t=jnp.where(hit_f, INF, st.fin_t),
                    run_req=jnp.where(hit_f, -1, st.run_req),
                    layer=jnp.where(hit_r, l_new, st.layer),
                    state=jnp.where(hit_r, jnp.where(done, 3, 1), st.state),
                    missed=jnp.where(hit_d, F.gt(now, d_eps12[r]), st.missed),
                    done_seq=jnp.where(hit_d, st.done_ctr, st.done_seq),
                    done_ctr=st.done_ctr + done.astype(I32),
                )
            st = bind(st, pop_rf & ~done, r, l_new, m)
            # after the pop, before any drop or completion of this
            # event's round: the peak slot demand
            with obs.scope("counters"):
                live = jnp.sum((st.state == 1) | (st.state == 2), dtype=I32)
                st = st._replace(live_pk=jnp.maximum(st.live_pk, live))

            if faulted:
                with obs.scope("fault"):
                    # ---- capability event (masked is_fault) -------------------
                    fi_c = jnp.minimum(st.fi, NFi - 1)
                    fk = fe_acc[fi_c]
                    code = fe_code[fi_c]
                    val = fe_val[fi_c]
                    ratio = fe_ratio[fi_c]  # val / old, divided on the host
                    is_down = is_fault & (code == 0)
                    is_up = is_fault & (code == 1)
                    is_scale = is_fault & (code == 2)
                    r_e = st.run_req[fk]
                    has_run = r_e >= 0
                    # down with an in-flight layer: undo the dispatch (variant
                    # bookkeeping, un-run busy time) and re-enter the ready set
                    ev = is_down & has_run
                    r_ec = jnp.where(ev, r_e, NRi)
                    l_e = st.layer[jnp.where(ev, r_e, 0)]
                    m_e = am[jnp.where(ev, r_e, 0)]
                    undo = ev & st.run_uv[fk]
                    r_u = jnp.where(undo, r_e, NRi)
                    st = st._replace(
                        # exact ret restore: the evicted variant is the
                        # request's most recent apply, so the pre-dispatch
                        # product saved at dispatch time is the undone value
                        ret=jnp.where(NRa == r_u, st.run_prev_ret[fk], st.ret),
                        app_seq=st.app_seq.at[r_u, l_e].set(-1, mode="drop"),
                        app_cnt=st.app_cnt.at[r_u].add(-1, mode="drop"),
                    )
                    # evict_busy_adjust replicated op-for-op in jnp
                    t0 = st.disp_t0[fk]
                    new_w = F.sub(now, t0)
                    new_h = F.minimum(new_w, F.maximum(ZERO, F.sub(duration, t0)))
                    dw = F.sub(new_w, st.disp_w[fk])
                    dh = F.sub(new_h, st.disp_h[fk])
                    hit_e = NAa == jnp.where(ev, fk, NAi)
                    # scale with an in-flight layer: re-time the finish by
                    # new_scale / old_scale (retime_busy_adjust in jnp)
                    old = st.fscale[fk]
                    changed = is_scale & has_run & F.ne(val, old)
                    fin_old = st.busy[fk]
                    fin_new = F.add(now, F.mul(F.sub(fin_old, now), ratio))
                    nw2 = F.sub(fin_new, t0)
                    nh2 = F.minimum(nw2, F.maximum(ZERO, F.sub(duration, t0)))
                    dw2 = F.sub(nw2, st.disp_w[fk])
                    dh2 = F.sub(nh2, st.disp_h[fk])
                    hit_s = NAa == jnp.where(changed, fk, NAi)
                    # both eviction and re-time orphan the old finish event:
                    # push it onto the ghost list (the reference leaves it in
                    # the heap as a stale pop)
                    ghost = ev | changed
                    gh_hit = NFa == jnp.where(ghost, st.gh_n, NFi)
                    hit_dn = NAa == jnp.where(is_down, fk, NAi)
                    hit_up = NAa == jnp.where(is_up, fk, NAi)
                    st = st._replace(
                        gh_t=jnp.where(gh_hit, st.fin_t[fk], st.gh_t),
                        gh_cnt=jnp.where(gh_hit, st.fin_cnt[fk], st.gh_cnt),
                        gh_n=st.gh_n + ghost.astype(I32),
                        busy=jnp.where(
                            hit_dn, INF,
                            jnp.where(hit_up, now,
                                      jnp.where(hit_s, fin_new, st.busy)),
                        ),
                        busy_t=jnp.where(
                            hit_e, F.add(st.busy_t, dw),
                            jnp.where(hit_s, F.add(st.busy_t, dw2), st.busy_t),
                        ),
                        busy_h=jnp.where(
                            hit_e, F.add(st.busy_h, dh),
                            jnp.where(hit_s, F.add(st.busy_h, dh2), st.busy_h),
                        ),
                        fin_t=jnp.where(
                            hit_dn, INF, jnp.where(hit_s, fin_new, st.fin_t)
                        ),
                        fin_cnt=jnp.where(hit_s, st.cnt, st.fin_cnt),
                        run_req=jnp.where(hit_dn, -1, st.run_req),
                        cnt=st.cnt + changed.astype(I32),
                        fscale=jnp.where(
                            NAa == jnp.where(is_scale, fk, NAi), val, st.fscale
                        ),
                        state=jnp.where(NRa == r_ec, 1, st.state),
                        ev_pend=jnp.where(NRa == r_ec, True, st.ev_pend),
                        evict_cnt=st.evict_cnt + (NRa == r_ec).astype(I32),
                        disp_w=jnp.where(hit_s, nw2, st.disp_w),
                        disp_h=jnp.where(hit_s, nh2, st.disp_h),
                        fi=st.fi + is_fault.astype(I32),
                    )
                    # re-bind the evicted row at its current layer with the
                    # post-undo ret (variant feasibility may have changed)
                    st = bind(st, ev, jnp.where(ev, r_e, NRi), l_e, m_e)

            # batch simultaneous events before scheduling (ref: abs < 1e-15
            # against the just-popped now; empty heap -> +inf -> round runs).
            # A suppressed round folds into the masks below (ready empty ->
            # the kernel emits nothing) instead of a whole-carry select.
            with obs.scope("pop"):
                t_next = F.minimum(at[st.ai], F.min(st.fin_t))
                if faulted:
                    t_next = F.minimum(
                        t_next, F.minimum(fe_t[st.fi], F.min(st.gh_t))
                    )
                do_round = ~F.lt(F.abs(F.sub(t_next, now)), EPS15)

                st = st._replace(rounds=st.rounds + do_round.astype(I32))
            if faulted:
                with obs.scope("epoch"):
                    # the round sees the CURRENT capability epoch: nominal
                    # cache planes times the epoch multiplier (elementwise —
                    # bit-equal to the effective tables the scalar engines
                    # swap in), and the capability-derived scalar vectors
                    # regathered from the epoch planes (vdl chains re-bound
                    # to arrival + chain under retighten, effective
                    # remaining-min for early-drop/EDF/DREAM keys)
                    mult = MULT_EP[st.fi]
                    vdlr_f = VDLR_EP[st.fi]
                    rm_f = RM_EP[st.fi]
                    minl_f = MINL_EP[st.fi]
                    l_all = st.layer
                    m_all = am
                    LPi = jnp.asarray(LP, I32)
                    LP1i = jnp.asarray(LP + 1, I32)
                    has_nx = (l_all + 1) < T.nl[m_all]
                    if use_budgets:
                        vdl_v = F.add(at[:NR], vdlr_f[m_all, jnp.minimum(l_all, LPi)])
                        vdln_v = jnp.where(
                            has_nx,
                            F.add(at[:NR], vdlr_f[m_all, jnp.minimum(l_all + 1, LPi)]),
                            d_abs,
                        )
                    else:
                        vdl_v = F.sub(d_abs, rm_f[m_all, jnp.minimum(l_all + 1, LP1i)])
                        vdln_v = jnp.where(
                            has_nx,
                            F.sub(d_abs, rm_f[m_all, jnp.minimum(l_all + 2, LP1i)]),
                            d_abs,
                        )
                    nm_v = jnp.where(
                        has_nx,
                        minl_f[m_all, jnp.minimum(l_all + 1, LPi - 1)],
                        ZERO,
                    )
                    rm_v = rm_f[m_all, jnp.minimum(l_all, LP1i)]
                    ek_v = F.sub(d_abs, rm_f[m_all, jnp.minimum(l_all + 1, LP1i)])
                    stk = st._replace(
                        c_lat=F.mul(st.c_lat, mult[:, None]),
                        c_latv=F.mul(st.c_latv, mult[:, None]),
                        c_vdl=vdl_v, c_vdln=vdln_v, c_nm=nm_v,
                        c_rm=rm_v, c_ek=ek_v,
                    )
            else:
                stk = st
            with obs.scope("drop"):
                ready0 = (st.state == 1) & do_round
                dropm = ready0 & F.gt(F.add(now, stk.c_rm), d_eps12)  # early-drop
                st = st._replace(
                    state=jnp.where(dropm, 4, st.state),
                    missed=st.missed | dropm,
                )
                ready = ready0 & ~dropm
            with obs.scope("round"):
                idle = F.le(st.busy, F.add(now, EPS15))
                if W < NR:
                    # every ready rid r has r < ai and dl12[r] >= now (the
                    # drop above, c_rm >= 0), and the host proved that
                    # range fits: read W rows from the aligned slot at or
                    # below the first ready rid
                    first = jnp.argmax(ready).astype(I32)
                    base = jnp.minimum(first - first % ROUND_ALIGN, NR - W)

                    def rows_w(x):  # the slot axis is the last, [NR] or [NA, NR]
                        out = x[..., NR - W:]
                        for b0 in range(0, NR - W, ROUND_ALIGN):
                            out = jnp.where(base == b0, x[..., b0:b0 + W], out)
                        return out

                    stw = stk._replace(**{f: rows_w(getattr(stk, f)) for f in (
                        "c_lat", "c_latv", "c_vdl", "c_vdln", "c_nm", "c_rm", "c_ek")})
                    picks = [(v, base + i, k, uv, c) for v, i, k, uv, c in kern(
                        stw, rows_w(ready), idle, now, rows_w(at[:NR]), rows_w(d_abs))]
                    outside = ready & ((NRa < base) | (NRa >= base + W))
                    st = st._replace(win_miss=st.win_miss | jnp.any(outside))
                else:
                    picks = kern(stk, ready, idle, now, at[:NR], d_abs)

            # apply emissions: chained one-hot selects per pick.  Finish
            # counters are cnt + (# valid picks before this one) — the
            # compacted emission index, tracked as traced scalars.  An
            # accelerator takes at most one pick per round, so the float
            # updates gather each one's cost ``c_acc`` and run one [NA]
            # add each: the same single add per accelerator as per pick.
            with obs.scope("apply"):
                state_n, run_req = st.state, st.run_req
                fin_t, fin_cnt = st.fin_t, st.fin_cnt
                busy, busy_t, busy_h = st.busy, st.busy_t, st.busy_h
                disp_t0, disp_w, disp_h = st.disp_t0, st.disp_w, st.disp_h
                run_uv, run_prev = st.run_uv, st.run_prev_ret
                rem = F.sub(duration, now)
                rem = jnp.where(F.gt(rem, ZERO), rem, ZERO)
                n_e = jnp.asarray(0, I32)
                c_acc = F.full(NA, 0.0)
                hit = jnp.zeros(NA, bool)
                rs, uvs, vas, vls = [], [], [], []
                for valid, i, k, uv, c in picks:
                    hit_a = (NAa == k) & valid
                    hit = hit | hit_a
                    c_acc = jnp.where(hit_a, c, c_acc)
                    state_n = jnp.where((NRa == i) & valid, 2, state_n)
                    run_req = jnp.where(hit_a, i, run_req)
                    fin_cnt = jnp.where(hit_a, st.cnt + n_e, fin_cnt)
                    if faulted:
                        # dispatch bookkeeping eviction/re-timing must undo;
                        # run_prev snapshots the pre-apply retained product
                        run_uv = jnp.where(hit_a, uv, run_uv)
                        run_prev = jnp.where(hit_a, st.ret[i], run_prev)
                    n_e = n_e + valid.astype(I32)
                    rs.append(i)
                    uvs.append(uv)
                    vas.append(valid & uv)
                    vls.append(valid)
                fin = F.add(now, c_acc)
                hc = jnp.where(F.le(c_acc, rem), c_acc, rem)
                fin_t = jnp.where(hit, fin, fin_t)
                busy = jnp.where(hit, fin, busy)
                busy_t = jnp.where(hit, F.add(busy_t, c_acc), busy_t)
                busy_h = jnp.where(hit, F.add(busy_h, hc), busy_h)
                if faulted:
                    disp_t0 = jnp.where(hit, now, disp_t0)
                    disp_w = jnp.where(hit, c_acc, disp_w)
                    disp_h = jnp.where(hit, hc, disp_h)
                # variant bookkeeping: a picked row is unique per round, so the
                # pre-round app_cnt/layer reads are the scatter-time values; the
                # [NR, LP] sequence table keeps a true (vector) scatter
                r_vec = jnp.stack(rs)
                va = jnp.stack(vas)
                rv = jnp.where(va, r_vec, NRi)
                l_vec = st.layer[r_vec]
                st = st._replace(
                    state=state_n, run_req=run_req,
                    fin_t=fin_t, fin_cnt=fin_cnt,
                    busy=busy, busy_t=busy_t, busy_h=busy_h,
                    app_seq=st.app_seq.at[rv, l_vec].set(
                        st.app_cnt[r_vec], mode="drop"),
                    app_cnt=st.app_cnt.at[rv].add(1, mode="drop"),
                    ret=st.ret.at[rv].set(
                        F.mul(st.ret[r_vec], T.factor[am[r_vec], l_vec]), mode="drop"),
                    cnt=st.cnt + n_e,
                )
                if faulted:
                    # a dispatched evicted-pending request is remapped (SoA:
                    # evicted_pending cleared + remapped += 1 at dispatch)
                    valid_vec = jnp.stack(vls)
                    was_pend = st.ev_pend[r_vec] & valid_vec
                    st = st._replace(
                        disp_t0=disp_t0, disp_w=disp_w, disp_h=disp_h,
                        run_uv=run_uv, run_prev_ret=run_prev,
                        remap_cnt=st.remap_cnt.at[
                            jnp.where(was_pend, r_vec, NRi)
                        ].add(1, mode="drop"),
                        ev_pend=st.ev_pend.at[
                            jnp.where(valid_vec, r_vec, NRi)
                        ].set(False, mode="drop"),
                    )
            return st

        z = jnp.zeros

        def fz(shape):
            return F.full(shape, 0.0)

        st0 = St(
            ai=jnp.asarray(0, I32), it=jnp.asarray(0, I32),
            cnt=jnp.asarray(0, I32), rounds=jnp.asarray(0, I32),
            done_ctr=jnp.asarray(0, I32),
            state=z(NR, I32), layer=z(NR, I32),
            c_lat=F.full((NA, NR), _INF), c_latv=F.full((NA, NR), _INF),
            c_vdl=fz(NR), c_vdln=fz(NR), c_nm=fz(NR),
            c_rm=F.full(NR, _INF), c_ek=fz(NR),
            ret=F.full(NR, 1.0), app_seq=jnp.full((NR, LP), -1, I32),
            app_cnt=z(NR, I32),
            missed=z(NR, bool), done_seq=jnp.full(NR, -1, I32),
            busy=fz(NA), busy_t=fz(NA), busy_h=fz(NA),
            fin_t=F.full(NA, _INF), fin_cnt=z(NA, I32),
            run_req=jnp.full(NA, -1, I32),
            fi=jnp.asarray(0, I32), fscale=F.full(NA, 1.0),
            gh_t=F.full(NF, _INF), gh_cnt=z(NF, I32),
            gh_n=jnp.asarray(0, I32),
            disp_t0=fz(NA), disp_w=fz(NA), disp_h=fz(NA),
            run_uv=z(NA, bool), run_prev_ret=F.full(NA, 1.0),
            ev_pend=z(NR, bool), evict_cnt=z(NR, I32), remap_cnt=z(NR, I32),
            live_pk=jnp.asarray(0, I32), win_miss=jnp.asarray(False),
        )
        st = lax.while_loop(cond, body, st0)
        act = (st.ai < ne) | jnp.any(st.run_req >= 0)
        if faulted:
            act = act | (st.fi < nf) | jnp.any(F.lt(st.gh_t, INF))
        return _Out(
            state=st.state, missed=st.missed, app_seq=st.app_seq,
            app_cnt=st.app_cnt, done_seq=st.done_seq,
            busy_t=st.busy_t, busy_h=st.busy_h, rounds=st.rounds,
            drained=~act,
            evict_cnt=st.evict_cnt, remap_cnt=st.remap_cnt,
            iters=st.it,
            live_peak=st.live_pk, win_miss=st.win_miss,
        )

    return jax.vmap(one_lane)(
        arr_t, arr_m, dl, dl12, n_ev,
        fe_t, fe_acc, fe_code, fe_val, fe_ratio, n_f,
        mult_ep, vdlr_ep, rm_ep, minl_ep,
    )


# ------------------------------------------------------- host wrapper ----


def _validate(
    plans, tasks, scheduler, processes, policy, adm, fault_model=None
) -> None:
    """Static event-horizon validation: reject every axis whose events the
    speculative device rollout cannot cover.  Named errors, no fallback."""
    from repro.core.admission import NoAdmission
    from repro.core.budget_online import BudgetPolicy, StaticBudgetPolicy

    for p in plans:
        if p.dag is not None:
            raise BatchUnsupportedError(
                f"engine='batch' does not support DAG plans (model "
                f"{p.model.name!r}): sibling node entries of one request "
                "break the one-slot-per-request lane layout; use "
                "engine='soa' or engine='reference'"
            )
    if (
        fault_model is not None
        and fault_model.active
        and fault_model.interrupted == "resume"
    ):
        # The remaining eviction-timing caveat of the fault lane: under
        # ``resume`` an evicted layer carries fractional progress
        # (layer_frac) that rescales its next dispatch cost, which the
        # pre-bound epoch planes cannot express.  ``restart`` (the
        # default) fault injection is fully supported — capability events
        # are pre-bound as a time-indexed epoch schedule.
        raise BatchUnsupportedError(
            "engine='batch' does not support fault injection with the "
            f"'resume' interrupted-work policy ({fault_model.format()!r}): "
            "partial layer progress re-times re-dispatches mid-rollout, "
            "which the pre-bound capability epochs cannot express; use "
            "engine='soa' or engine='reference'"
        )
    if type(scheduler) not in (
        FcfsScheduler, EdfScheduler, DreamScheduler, TerastalScheduler
    ):
        raise BatchUnsupportedError(
            f"engine='batch' has no kernel for {type(scheduler).__name__}; "
            "custom Scheduler subclasses need the reference engine"
        )
    if type(policy) not in (StaticBudgetPolicy, BudgetPolicy):
        raise BatchUnsupportedError(
            f"engine='batch' does not support online budget policy "
            f"{type(policy).__name__}: per-event vdl mutation breaks the "
            "pre-bound virtual-deadline rows; use engine='soa'"
        )
    if policy.tick_interval > 0:
        raise BatchUnsupportedError(
            "engine='batch' does not support budget-policy tick events"
        )
    if adm is not None and type(adm) is not NoAdmission:
        raise BatchUnsupportedError(
            f"engine='batch' does not support admission policy "
            f"{type(adm).__name__}: backlog accounting is event-sequential; "
            "use engine='soa'"
        )
    for t_idx, task in enumerate(tasks):
        proc = processes[t_idx] if processes is not None else None
        proc = proc or task.arrival or DEFAULT_ARRIVAL
        if isinstance(proc, ClosedLoopClients):
            raise BatchUnsupportedError(
                "engine='batch' does not support closed-loop release "
                "coupling (ClosedLoopClients): completion-gated releases "
                "cannot be pre-generated; use engine='soa'"
            )


def ready_span_bound(arr_t, dl12, n_ev) -> int:
    """The widest rid range any round of the batch can find ready.

    A ready request at a round at time ``now`` has been released
    (``r < ai``) and survived the early drop (``now + c_rm <= dl12[r]``
    with ``c_rm >= 0``, so ``dl12[r] >= now``); rids are in time order.
    The ready set therefore lies in ``[lo(now), ai)`` with ``lo(t) =
    min{r : dl12[r] >= t}``.  ``ai`` changes only at arrivals and ``lo``
    never decreases, so per lane the widest such range is
    ``max_j (j + 1 - lo(t_j))`` over its arrivals ``j`` at ``t_j``; this
    is the maximum over the lanes of ``(B, NR)`` staged rows, each lane's
    first ``n_ev`` slots live."""
    span = 0
    for t, d, n in zip(arr_t, dl12, n_ev):
        if n:
            lo = np.searchsorted(np.maximum.accumulate(d[:n]), t[:n], "left")
            span = max(span, int((np.arange(1, n + 1) - lo).max()))
    return span


class _Staged(NamedTuple):
    """One cell's seed batch, staged for :func:`_run_trials`."""

    args: tuple      # positional arguments (host arrays and scalars)
    static: dict     # static keyword arguments (the scheduler config)
    events: list     # per-seed ``(times, models)`` release streams
    n_spans: list    # per-seed faulted-window counts
    batch: Optional[int] = None  # recorder batch id (:mod:`repro.core.obs`)


def stage_batch(
    plans: Sequence[ModelPlan],
    tasks: Sequence[TaskSpec],
    duration: float,
    scheduler: Scheduler,
    seeds: Sequence[int],
    processes: Optional[Sequence[Optional[ArrivalProcess]]] = None,
    budget_policy=None,
    admission=None,
    faults=None,
) -> _Staged:
    """Validate a cell and stage its B seeds' inputs on the host.

    ``_run_trials(*staged.args, **staged.static)`` then runs the batch;
    the arrays are numpy and become 64-bit device arrays when the jit
    is called under :func:`scheduler_jax.x64`.  The binary64 follows the
    platform (:func:`f64.for_platform`); under the software one, float
    arrays are staged as their int64 bit patterns."""
    from repro.core.admission import make_admission_policy
    from repro.core.budget_online import make_budget_policy
    from repro.core.faults import make_fault_model
    from repro.core.workload import batch_release_events

    bid = obs.open_batch()
    policy = make_budget_policy(budget_policy)
    policy.reset()
    adm = make_admission_policy(admission)
    adm.reset()
    fault_model = faults if not isinstance(faults, str) else make_fault_model(faults)
    _validate(plans, tasks, scheduler, processes, policy, adm, fault_model)

    kind = type(scheduler)
    if kind is TerastalScheduler:
        cfg = dict(
            kind="terastal", mode=scheduler.backfill_mode,
            use_budgets=scheduler.use_budgets,
            use_variants=scheduler.use_variants,
        )
    else:
        name = {FcfsScheduler: "fcfs", EdfScheduler: "edf",
                DreamScheduler: "dream"}[kind]
        cfg = dict(kind=name, mode="", use_budgets=False, use_variants=False)

    F = f64.for_platform()
    soft = F is f64.SOFT
    tables, LP, NA = _build_tables(plans)
    deadline_by_model = np.array([p.deadline for p in plans])
    with obs.span("stage.releases", bid):
        events = batch_release_events(tasks, duration, seeds, processes)
    with obs.span("stage.pack", bid):
        buf, b_pad, nr_pad = scheduler_jax.pack_trials(events, deadline_by_model)

    # the round reads a ROUND_WINDOW-slot window where every lane's ready
    # set provably fits in one from its aligned start; otherwise all nr_pad
    span = ready_span_bound(buf["arr_t"], buf["dl12"], buf["n_ev"])
    fits = span <= ROUND_WINDOW - ROUND_ALIGN + 1
    win = ROUND_WINDOW if fits and ROUND_WINDOW < nr_pad else nr_pad

    # exact event-count bound: each loop iteration pops exactly one event,
    # and the horizon holds n_ev arrivals plus at most one finish per
    # executed layer (sum of layer counts over released requests)
    nl_by_model = np.array([len(p.model.layers) for p in plans])
    max_it = 2 + max(
        (len(t) + int(nl_by_model[m].sum()) for t, m in events), default=2
    )

    faulted = fault_model is not None and fault_model.active
    if faulted:
        with obs.span("stage.faults", bid):
            fbuf, nf_pad, n_spans = scheduler_jax.pack_fault_epochs(
                fault_model, plans, duration, seeds, b_pad, LP
            )
        # each fault event adds at most three pops: itself, the ghost of
        # an orphaned finish, and the re-dispatched layer's new finish
        max_it += 3 * int(fbuf["n_f"].max())
    else:
        # minimal dummies: the fault path is a static branch, so these
        # are never read — they only have to vmap over the lane axis
        n_spans = [0] * len(seeds)
        fbuf = {
            "fe_t": np.full((b_pad, 2), np.inf),
            "fe_acc": np.zeros((b_pad, 1), np.int32),
            "fe_code": np.zeros((b_pad, 1), np.int32),
            "fe_val": np.ones((b_pad, 1)),
            "fe_ratio": np.ones((b_pad, 1)),
            "n_f": np.zeros(b_pad, np.int32),
            "mult_ep": np.ones((b_pad, 1, NA)),
            "vdlr_ep": np.zeros((b_pad, 1, 1, 1)),
            "rm_ep": np.zeros((b_pad, 1, 1, 1)),
            "minl_ep": np.zeros((b_pad, 1, 1, 1)),
        }
    fl = F.to_device
    with obs.span("stage.to_device", bid):
        tables = tables._replace(**{
            k: fl(v) for k, v in tables._asdict().items() if v.dtype == np.float64})
        args = (
            tables,
            fl(buf["arr_t"]), buf["arr_m"], fl(buf["dl"]), fl(buf["dl12"]),
            buf["n_ev"],
            fl(np.float64(duration)), np.int32(max_it),
            fl(fbuf["fe_t"]), fbuf["fe_acc"], fbuf["fe_code"], fl(fbuf["fe_val"]),
            fl(fbuf["fe_ratio"]), fbuf["n_f"],
            fl(fbuf["mult_ep"]), fl(fbuf["vdlr_ep"]), fl(fbuf["rm_ep"]),
            fl(fbuf["minl_ep"]),
        )
    obs.count("lanes", len(seeds), bid)
    obs.count("nr_pad", nr_pad, bid)
    obs.count("max_it", max_it, bid)
    obs.count("releases", int(buf["n_ev"].sum()), bid)
    obs.count("span_bound", span, bid)
    obs.count("round_slots", win, bid)
    return _Staged(args, dict(na=NA, lp=LP, faulted=faulted, soft=soft, win=win, **cfg),
                   events, n_spans, bid)


@x64  # bit-parity requires f64 tables, buffers and traces
def simulate_batch(
    plans: Sequence[ModelPlan],
    tasks: Sequence[TaskSpec],
    duration: float,
    scheduler: Scheduler,
    seeds: Sequence[int],
    processes: Optional[Sequence[Optional[ArrivalProcess]]] = None,
    budget_policy=None,
    admission=None,
    faults=None,
) -> List[SimResult]:
    """Run B = ``len(seeds)`` trials of one cell as ONE device program.

    Same contract as ``simulate()`` for every supported axis — each
    returned :class:`SimResult` is fingerprint-identical to
    ``simulate(..., seed=s, engine="soa")`` (pinned by
    tests/test_engine_batch.py).  Unsupported axes raise
    :class:`BatchUnsupportedError` (see :func:`_validate`); an
    undrained lane (the speculation bound failed — an engine bug, not a
    workload property) raises ``RuntimeError``, as does a lane whose
    round found a ready request outside its proven slot window.

    The three stages run under the spans ``engine.stage``,
    ``engine.loop`` and ``engine.assemble`` of ``engine.batch``; while a
    recorder of :mod:`repro.core.obs` is active they and the batch's
    counters are kept.
    """
    with obs.span("batch"):
        with obs.span("stage"):
            staged = stage_batch(plans, tasks, duration, scheduler, seeds,
                                 processes, budget_policy, admission, faults)
        with obs.span("loop", staged.batch):
            before = _run_trials._cache_size()
            out: _Out = jax.block_until_ready(_run_trials(*staged.args, **staged.static))
            obs.count("compiles", _run_trials._cache_size() - before, staged.batch)
        with obs.span("assemble", staged.batch):
            return assemble_batch(out, staged, plans, tasks, duration, scheduler)


def assemble_batch(out, staged: _Staged, plans, tasks, duration, scheduler):
    """Host assembly: one device->host copy of ``out``, then a
    :class:`SimResult` per seed."""
    events, n_spans, bid = staged.events, staged.n_spans, staged.batch
    with obs.span("assemble.copy", bid):
        out = jax.tree_util.tree_map(np.asarray, out)  # ONE host sync
        F = f64.SOFT if staged.static["soft"] else f64.NATIVE
        out = out._replace(busy_t=F.from_device(out.busy_t),
                           busy_h=F.from_device(out.busy_h))
    nb = len(events)
    iters = out.iters[:nb]
    obs.count("iters_max", int(iters.max(initial=0)), bid)
    obs.count("iters_sum", int(iters.sum()), bid)
    obs.count("rounds_sum", int(out.rounds[:nb].sum()), bid)
    obs.count("live_peak", int(out.live_peak[:nb].max(initial=0)), bid)

    drained = out.drained[:nb]
    if not drained.all():
        raise RuntimeError(
            "engine='batch' lane(s) %s did not drain their event horizon "
            "within the exact bound — engine bug" % np.flatnonzero(~drained)
        )
    win_miss = out.win_miss[:nb]
    if win_miss.any():
        raise RuntimeError(
            "engine='batch' lane(s) %s found a ready request outside the "
            "round's proven slot window — engine bug" % np.flatnonzero(win_miss)
        )

    per_lane: List[Dict[int, ModelStats]] = []
    with obs.span("assemble.counts", bid):
        for b, (times, models) in enumerate(events):
            n = len(times)
            state = out.state[b, :n]
            missed_f = out.missed[b, :n]
            app_cnt = out.app_cnt[b, :n]
            evict_c = out.evict_cnt[b, :n]
            remap_c = out.remap_cnt[b, :n]
            stats: Dict[int, ModelStats] = {t.model_idx: ModelStats() for t in tasks}
            for m in stats:
                mm = models[:n] == m
                st = stats[m]
                st.released = int(mm.sum())
                st.completed = int((mm & (state == 3)).sum())
                st.dropped = int((mm & (state == 4)).sum())
                st.missed = int((mm & missed_f).sum())
                # every released request ends completed, dropped, or in flight
                st.in_flight = st.released - st.completed - st.dropped
                st.variants_applied = int(app_cnt[mm].sum())
                st.evicted = int(evict_c[mm].sum())
                st.remapped = int(remap_c[mm].sum())
            per_lane.append(stats)
    # retained_sum: host replay in completion order, through the same
    # frozenset unions + combo_retained calls the reference performs
    replayed = 0
    with obs.span("assemble.replay", bid):
        for b, (times, models) in enumerate(events):
            state = out.state[b, :len(times)]
            done = np.flatnonzero(state == 3)
            replayed += len(done)
            for r in done[np.argsort(out.done_seq[b, done])]:
                m = int(models[r])
                applied = frozenset()
                seq = out.app_seq[b, r]
                order = np.flatnonzero(seq >= 0)
                for l in order[np.argsort(seq[order])]:
                    applied = applied | {int(l)}
                per_lane[b][m].retained_sum += plans[m].combo_retained(applied)
    obs.count("replayed", replayed, bid)
    return [
        SimResult(
            duration=duration,
            per_model=per_lane[b],
            acc_busy_time=out.busy_t[b].copy(),
            scheduler_name=scheduler.name,
            acc_busy_in_horizon=out.busy_h[b].copy(),
            rounds=int(out.rounds[b]),
            faulted_spans=n_spans[b],
        )
        for b in range(nb)
    ]
