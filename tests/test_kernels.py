"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn.ops import gqa_decode_attention
from repro.kernels.s2d_conv.kernel import s2d_conv_pallas
from repro.kernels.s2d_conv.ops import s2d_variant_conv, s2d_variant_conv_rs
from repro.kernels.s2d_conv.ref import d2s, s2d, s2d_conv_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.models.common import decode_attention
from repro.models.mamba2 import ssd_chunked, ssd_naive

KEY = jax.random.PRNGKey(42)


# ------------------------------------------------------------- s2d_conv ----


def test_d2s_s2d_inverse():
    x = jax.random.normal(KEY, (2, 8, 8, 16))
    np.testing.assert_allclose(s2d(d2s(x, 2), 2), x)
    x3 = jax.random.normal(KEY, (1, 6, 6, 18))
    np.testing.assert_allclose(s2d(d2s(x3, 3), 3), x3)


@pytest.mark.parametrize("B,H,W,C,K,g", [
    (2, 8, 8, 16, 32, 2),
    (1, 16, 16, 64, 64, 2),
    (2, 12, 12, 36, 72, 3),
    (1, 8, 8, 256, 128, 2),
    (1, 4, 4, 512, 512, 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_s2d_conv_matches_ref(B, H, W, C, K, g, dtype):
    x = jax.random.normal(KEY, (B, H, W, C), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (C // g**2, K // g**2), dtype)
    ref = s2d_conv_ref(x, w, g).astype(jnp.float32)
    got = s2d_conv_pallas(x, w, g, block_rows=32, interpret=True).astype(jnp.float32)
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(got, ref, atol=tol, rtol=tol)


def test_s2d_conv_tile_invariance():
    """Output independent of BlockSpec tiling."""
    x = jax.random.normal(KEY, (1, 16, 16, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
    outs = [
        s2d_conv_pallas(x, w, 2, block_rows=t, interpret=True) for t in (8, 64, 256, 1024)
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-5)


def test_s2d_variant_weight_count():
    """Fused variant uses 1/g^4 of the original layer's weights (paper)."""
    C, K, g = 64, 128, 2
    w_orig = C * K
    w_var = (C // g**2) * (K // g**2)
    assert w_var * g**4 == w_orig


def test_s2d_conv_rs_shapes():
    x = jax.random.normal(KEY, (1, 8, 8, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 8))
    out = s2d_variant_conv_rs(x, w, 2)
    assert out.shape == (1, 8, 8, 32)
    assert bool(jnp.isfinite(out).all())


# ------------------------------------------------------------- ssd_scan ----


@pytest.mark.parametrize("Bt,L,H,P,N,Q", [
    (2, 64, 4, 8, 16, 16),
    (1, 128, 2, 64, 128, 32),
    (2, 32, 8, 16, 8, 32),
    (1, 64, 1, 128, 64, 64),
])
def test_ssd_scan_matches_naive(Bt, L, H, P, N, Q):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bt, L, H, P))
    la = -jnp.abs(jax.random.normal(ks[1], (Bt, L, H))) * 0.3
    B = jax.random.normal(ks[2], (Bt, L, N))
    C = jax.random.normal(ks[3], (Bt, L, N))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (Bt, L, H)))
    ref = ssd_naive(x, la, B, C, dt)
    got = ssd_scan(x, la, B, C, dt, chunk=Q, backend="pallas")
    rel = float(jnp.abs(ref - got).max() / (jnp.abs(ref).max() + 1e-9))
    assert rel < 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_dtypes(dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (1, 64, 2, 16), dtype)
    la = (-jnp.abs(jax.random.normal(ks[1], (1, 64, 2))) * 0.3).astype(dtype)
    B = jax.random.normal(ks[2], (1, 64, 8), dtype)
    C = jax.random.normal(ks[3], (1, 64, 8), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (1, 64, 2))).astype(dtype)
    ref = ssd_naive(x, la, B, C, dt).astype(jnp.float32)
    got = ssd_scan(x, la, B, C, dt, chunk=16, backend="pallas").astype(jnp.float32)
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    rel = float(jnp.abs(ref - got).max() / (jnp.abs(ref).max() + 1e-9))
    assert rel < tol


def test_ssd_chunked_equals_pallas_paths():
    """The model-level jnp blocked path and the kernel agree (same math)."""
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (2, 64, 4, 8))
    la = -jnp.abs(jax.random.normal(ks[1], (2, 64, 4))) * 0.2
    B = jax.random.normal(ks[2], (2, 64, 16))
    C = jax.random.normal(ks[3], (2, 64, 16))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (2, 64, 4)))
    a = ssd_chunked(x, la, B, C, dt, 16)
    b = ssd_scan(x, la, B, C, dt, chunk=16, backend="pallas")
    np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------- decode_attn ----


@pytest.mark.parametrize("B,L,H,Hkv,Dh,pos,chunk", [
    (2, 64, 8, 2, 16, 63, 16),
    (1, 128, 4, 4, 32, 80, 32),
    (3, 256, 16, 8, 64, 255, 64),
    (1, 64, 8, 1, 128, 10, 64),
])
def test_decode_attn_matches_ref(B, L, H, Hkv, Dh, pos, chunk):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, Dh))
    k = jax.random.normal(ks[1], (B, L, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, L, Hkv, Dh))
    ref = decode_attention(q, k, v, jnp.int32(pos))
    got = gqa_decode_attention(q, k, v, jnp.int32(pos), backend="pallas", chunk=chunk)
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-4)


def test_decode_attn_respects_valid_length():
    """Entries beyond pos must not influence the output."""
    ks = jax.random.split(KEY, 3)
    B, L, H, Hkv, Dh = 1, 64, 4, 2, 16
    q = jax.random.normal(ks[0], (B, 1, H, Dh))
    k = jax.random.normal(ks[1], (B, L, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, L, Hkv, Dh))
    pos = jnp.int32(20)
    out1 = gqa_decode_attention(q, k, v, pos, backend="pallas", chunk=16)
    k2 = k.at[:, 30:].set(999.0)
    v2 = v.at[:, 30:].set(-999.0)
    out2 = gqa_decode_attention(q, k2, v2, pos, backend="pallas", chunk=16)
    np.testing.assert_allclose(out1, out2, atol=1e-6)
