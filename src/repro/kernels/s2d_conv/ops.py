"""jit'd public wrapper for the fused S2D-variant conv.

``s2d_variant_conv`` runs the kernel compiled on a TPU and interpreted
only on the CPU backend.  ``s2d_variant_conv_rs`` covers the general
R x S case in jnp.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.s2d_conv.kernel import s2d_conv_pallas


@functools.partial(jax.jit, static_argnames=("gamma",))
def s2d_variant_conv(x: jax.Array, w: jax.Array, gamma: int) -> jax.Array:
    """Fused variant pointwise conv. x: [B,H,W,C], w: [C/g^2, K/g^2]."""
    return s2d_conv_pallas(x, w, gamma, interpret=jax.default_backend() == "cpu")


def s2d_variant_conv_rs(x: jax.Array, w_full: jax.Array, gamma: int) -> jax.Array:
    """R x S > 1 variant conv: im2col at the D2S resolution, then a jnp
    patch matmul and S2D.

    w_full: [R, S, C/g^2, K/g^2] variant filter (operates in d2s space);
    x is patched at the d2s resolution, matching the paper's Fig. 1
    construction exactly (stride 1, 'same' padding)."""
    from repro.kernels.s2d_conv.ref import d2s, s2d

    R, S, Cv, Kv = w_full.shape
    y = d2s(x, gamma)
    # im2col at the expanded resolution
    pads = ((R // 2, (R - 1) // 2), (S // 2, (S - 1) // 2))
    yp = jnp.pad(y, ((0, 0), pads[0], pads[1], (0, 0)))
    B, Hg, Wg, _ = y.shape
    cols = []
    for r in range(R):
        for s in range(S):
            cols.append(yp[:, r : r + Hg, s : s + Wg, :])
    patches = jnp.concatenate(cols, axis=-1)  # [B, Hg, Wg, R*S*Cv]
    w2 = w_full.reshape(R * S * Cv, Kv)
    out = jnp.einsum("bhwc,ck->bhwk", patches, w2, preferred_element_type=jnp.float32)
    return s2d(out.astype(x.dtype), gamma)
