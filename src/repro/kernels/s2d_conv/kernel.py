"""Pallas TPU kernel: fused D2S -> 1x1 conv -> S2D (the Terastal variant).

For a pointwise conv the whole D2S -> conv -> S2D sandwich is a
block-diagonal matmul: output channels ``(a, b, k)`` of pixel ``(h, w)``
are ``sum_c x[h, w, (a, b, c)] * w[c, k]`` — the two rearrangements move
each pixel's ``g^2`` channel groups to sub-pixels and back, and cancel.
On the contiguous NHWC buffer that is a free reshape: ``x`` viewed as
``[B*H*W*g^2, C/g^2]`` rows times the variant weights ``[C/g^2, K/g^2]``
gives the output viewed as ``[B*H*W*g^2, K/g^2]``.  So the kernel is a
row-tiled MXU matmul with the weights resident in VMEM, and nothing is
rearranged on chip (an in-kernel reshape/transpose of the tile is a
shape cast the TPU compiler refuses at VGG widths).

BlockSpec sizing: row tiles are multiples of 8 (f32 sublanes), picked
against a VMEM budget, and the contraction/output dims are whole.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _s2d_conv_kernel(x_ref, w_ref, o_ref):
    # x_ref: [rows, C/g^2]; w_ref: [C/g^2, K/g^2]; o_ref: [rows, K/g^2]
    # f32 operands contract at full f32 precision; bf16 ones are exact
    f32 = x_ref.dtype == jnp.float32
    o_ref[...] = jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if f32 else None,
    ).astype(o_ref.dtype)


#: VMEM the row tiles may take, leaving headroom of the 16 MiB per core.
VMEM_BUDGET = 12 * 1024 * 1024


def pick_rows(rows: int, Cv: int, Kv: int, bytes_per_elem: int) -> int:
    """Largest row tile (a multiple of 8 dividing ``rows``) whose double-
    buffered x and out tiles plus the resident weights fit the budget."""
    for t in (2048, 1024, 512, 256, 128, 64, 32, 16, 8):
        if rows % t:
            continue
        vmem = (2 * t * (Cv + Kv) + Cv * Kv) * bytes_per_elem
        if vmem <= VMEM_BUDGET:
            return t
    return rows


def s2d_conv_pallas(
    x: jax.Array,  # [B, H, W, C]
    w: jax.Array,  # [C/g^2, K/g^2]
    gamma: int,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, H, W, C = x.shape
    g2 = gamma * gamma
    Cv, Kv = w.shape
    assert Cv * g2 == C, (C, Cv, gamma)
    rows = B * H * W * g2
    tm = block_rows or pick_rows(rows, Cv, Kv, x.dtype.itemsize)
    assert rows % tm == 0, (rows, tm)
    y = pl.pallas_call(
        _s2d_conv_kernel,
        grid=(rows // tm,),
        in_specs=[
            pl.BlockSpec((tm, Cv), lambda i: (i, 0)),
            pl.BlockSpec((Cv, Kv), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, Kv), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, Kv), x.dtype),
        interpret=interpret,
    )(x.reshape(rows, Cv), w)
    return y.reshape(B, H, W, Kv * g2)
