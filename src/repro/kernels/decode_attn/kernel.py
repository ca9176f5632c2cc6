"""Pallas TPU kernel: GQA decode attention (one query token, long cache).

Decode attention is memory-bound: the whole KV cache streams HBM->VMEM
once per step.  Grid: (batch, kv_heads, L/chunk) with the cache-length
axis sequential; online-softmax running stats (m, l) and the weighted
accumulator [G, Dh] live in VMEM scratch, so the output is written once
at the final chunk.  The query tile [G, Dh] (G = H/Hkv grouped heads)
rides along every chunk step.

The kernel streams a head-major cache [B, Hkv, L, Dh]: each block is a
[chunk, Dh] slab whose last two dims satisfy the TPU tiling rule (chunk
a multiple of 8, Dh the whole dim).  The models keep a position-major
cache [B, L, Hkv, Dh], whose per-head block [chunk, 1, Dh] the compiler
refuses, so the wrapper transposes — one extra pass over the cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def _decode_attn_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref, acc_ref, *, n_chunks: int, scale: float):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # [G, Dh]
    k = k_ref[0, 0].astype(jnp.float32)  # [Lc, Dh]
    v = v_ref[0, 0].astype(jnp.float32)  # [Lc, Dh]
    Lc = k.shape[0]
    valid_len = len_ref[pl.program_id(0)]  # whole [B] vector sits in SMEM

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    ) * scale  # [G, Lc]
    pos = c * Lc + jax.lax.broadcasted_iota(jnp.int32, (1, Lc), 1)
    s = jnp.where(pos < valid_len, s, -1e30)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))  # [G, 1]
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32, precision=_HIGHEST
    )
    m_ref[...] = m_new

    @pl.when(c == n_chunks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attn_pallas(
    q: jax.Array,  # [B, H, Dh] single-token queries
    cache_k: jax.Array,  # [B, L, Hkv, Dh]
    cache_v: jax.Array,
    valid_len: jax.Array,  # [B] number of valid cache positions (pos+1)
    chunk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, Dh = q.shape
    _, L, Hkv, _ = cache_k.shape
    G = H // Hkv
    Lc = min(chunk, L)
    assert L % Lc == 0
    nc = L // Lc
    scale = 1.0 / (Dh**0.5)
    qg = q.reshape(B, Hkv, G, Dh)
    kh = cache_k.transpose(0, 2, 1, 3)  # [B, Hkv, L, Dh]
    vh = cache_v.transpose(0, 2, 1, 3)
    vlen = valid_len.astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, n_chunks=nc, scale=scale),
        grid=(B, Hkv, nc),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Lc, Dh), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Lc, Dh), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dh), lambda b, h, c: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qg, kh, vh, vlen)
    return out.reshape(B, H, Dh)
