"""jit'd wrapper for the decode-attention kernel and its jnp oracle."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attn.kernel import decode_attn_pallas
from repro.models.common import decode_attention


@functools.partial(jax.jit, static_argnames=("backend", "chunk"))
def gqa_decode_attention(
    q: jax.Array,  # [B, 1, H, Dh]
    cache_k: jax.Array,  # [B, L, Hkv, Dh]
    cache_v: jax.Array,
    pos: jax.Array,  # [] int32 (position of the newest token)
    backend: str = "pallas",
    chunk: int = 512,
) -> jax.Array:
    """The Pallas kernel runs compiled on a TPU and interpreted only on
    the CPU backend; ``backend="jnp"`` is the model's own oracle."""
    if backend == "jnp":
        return decode_attention(q, cache_k, cache_v, pos)
    B = q.shape[0]
    valid = jnp.broadcast_to(pos + 1, (B,))
    out = decode_attn_pallas(q[:, 0], cache_k, cache_v, valid, chunk=chunk,
                             interpret=jax.default_backend() == "cpu")
    return out[:, None]  # [B, 1, H, Dh]
