"""Pallas TPU kernel: Mamba2 SSD chunked scan.

Grid: (batch, heads, n_chunks) with the chunk axis sequential
("arbitrary" semantics); the inter-chunk SSM state [N, P] lives in VMEM
scratch and persists across chunk steps — the recurrence never round-
trips HBM.  Each chunk step computes the intra-chunk quadratic term on
the MXU (Q x Q decay-masked C.B^T against the chunk inputs) plus the
inter-chunk contribution from the carried state, then advances the state.

Block shapes: x*dt [Q, P], B/C [Q, N], and the chunk-local cumulative
log-decay as a column [Q, 1] and a row [1, Q] — every block's last two
dims are a multiple of (8, 128) or the whole dim, as the TPU tiling rule
requires.  The wrapper computes x*dt and the per-chunk cumsum of log_a
(elementwise and [Bt, H, L]-sized, cheap next to the Q x Q work), so the
kernel holds no 1-D vectors.  With the production Q=256, N=128, P=64
this is ~0.5 MiB of VMEM per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )


def _ssd_kernel(xdt_ref, cc_ref, cr_ref, b_ref, c_ref, o_ref, state_ref, *, n_chunks: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xdt = xdt_ref[0, 0]  # [Q, P]
    cum_c = cc_ref[0, 0]  # [Q, 1] chunk-local cumsum of log_a
    cum_r = cr_ref[0, 0]  # [1, Q] the same, as a row
    B = b_ref[0].astype(jnp.float32)  # [Q, N]
    C = c_ref[0].astype(jnp.float32)  # [Q, N]
    Q = xdt.shape[0]
    total = cum_c[Q - 1, 0]  # scalar: a [1, 1] slice would need a 2-D broadcast

    # intra-chunk: decay-masked quadratic term
    seg = cum_c - cum_r  # [Q, Q]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    decay = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    scores = _dot(C, B, ((1,), (1,))) * decay  # [Q, Q]
    y = _dot(scores, xdt)  # [Q, P]

    # inter-chunk: contribution of the carried state
    S = state_ref[...]  # [N, P]
    y += jnp.exp(cum_c) * _dot(C, S)

    # state update: S' = e^total * S + sum_j e^(total - cum_j) B_j (x) xdt_j
    w = jnp.exp(total - cum_c)  # [Q, 1]
    state_ref[...] = jnp.exp(total) * S + _dot(B * w, xdt, ((0,), (0,)))

    o_ref[0, 0] = y.astype(o_ref.dtype)


def ssd_scan_pallas(
    x: jax.Array,  # [Bt, L, H, P]
    log_a: jax.Array,  # [Bt, L, H]
    B: jax.Array,  # [Bt, L, N]
    C: jax.Array,  # [Bt, L, N]
    dt: jax.Array,  # [Bt, L, H]
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    Bt, L, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0
    nc = L // Q
    # layout: head-major so each (b, h) streams its own chunks
    f32 = jnp.float32
    xdt = (x.astype(f32) * dt.astype(f32)[..., None]).transpose(0, 2, 1, 3)  # [Bt, H, L, P]
    la = log_a.astype(f32).transpose(0, 2, 1)  # [Bt, H, L]
    cum = jnp.cumsum(la.reshape(Bt, H, nc, Q), axis=-1).reshape(Bt, H, L)
    grid = (Bt, H, nc)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bt, H, L, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xdt, cum[..., None], cum[:, :, None, :], B, C)
    return out.transpose(0, 2, 1, 3)  # [Bt, L, H, P]
