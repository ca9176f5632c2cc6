"""Software binary64 (``repro.core.f64.SOFT``) against numpy, bit for bit.

The batch engine runs on ``SOFT`` wherever the backend's float64 is not
IEEE (the TPU), and its lanes must stay fingerprint-identical to the
numpy engine there; so every operation is checked here on the CPU
against numpy's own float64 on values in the simulator's ranges, on
near-ties and cancellations, on subnormals and on the special values.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.f64 import NATIVE, SOFT, for_platform

SPECIALS = np.array([0.0, -0.0, 1.0, -1.0, np.inf, -np.inf, np.nan, 5e-324, -5e-324,
                     2.2250738585072014e-308, 1.7976931348623157e308, -1.7976931348623157e308,
                     1e-15, 1e-12, 0.1, 0.2, 0.3, 2.0, 4.0, 0.25])


def _operands(seed, n=20000):
    """Pairs from the engine's ranges (times, latencies, deadline and
    epsilon sums), random bit patterns, near-equal pairs, and every
    pair of special values."""
    rng = np.random.default_rng(seed)
    t = rng.uniform(0.0, 4.0, n)
    lat = 10.0 ** rng.uniform(-6.0, 0.0, n)
    bits = rng.integers(-(1 << 63), (1 << 63) - 1, n, dtype=np.int64).view(np.float64)
    bits = np.where(np.isnan(bits), 1.5, bits)
    near = t * (1.0 + rng.integers(-4, 5, n) * 2.0**-52)
    sa, sb = np.meshgrid(SPECIALS, SPECIALS)
    a = np.concatenate([t, t, t + lat, bits, t, lat, -t, sa.ravel()])
    b = np.concatenate([lat, -lat, t, bits[::-1], near, -lat * 3.0, near, sb.ravel()])
    return a, b


def _same(got, want):
    got = np.asarray(got)
    want = np.asarray(want)
    if want.dtype == np.float64:
        both_nan = np.isnan(got) & np.isnan(want)
        return (got.view(np.int64) == want.view(np.int64)) | both_nan
    return got == want


def _soft(fn, *xs, floats=True):
    """Run ``fn`` on the soft bit patterns of ``xs``; read a float result
    back from its bits (``floats=False`` for indices and masks)."""
    with jax.enable_x64(True):
        out = np.asarray(jax.jit(fn)(*[SOFT.to_device(x) for x in xs]))
    return SOFT.from_device(out) if floats and out.dtype == np.int64 else out


@pytest.mark.parametrize("op,want", [
    ("add", np.add), ("sub", np.subtract), ("mul", np.multiply),
    ("minimum", np.minimum), ("maximum", np.maximum),
    ("lt", np.less), ("le", np.less_equal), ("gt", np.greater),
    ("ge", np.greater_equal), ("eq", np.equal), ("ne", np.not_equal),
])
@pytest.mark.parametrize("seed", [0, 1])
def test_soft_binary_ops_match_numpy(op, want, seed):
    a, b = _operands(seed)
    with np.errstate(all="ignore"):
        ref = want(a, b)
    got = _soft(getattr(SOFT, op), a, b)
    if op == "mul":
        # documented: products below the smallest normal flush to zero
        tiny = (np.abs(ref) < 2.2250738585072014e-308) | (np.abs(a) < 2.2250738585072014e-308) \
            | (np.abs(b) < 2.2250738585072014e-308)
        ok = _same(got, ref) | (tiny & (got == 0.0))
    elif op in ("minimum", "maximum"):
        # the sign of a zero picked from (-0, +0) is unspecified
        ok = _same(got, ref) | ((got == 0.0) & (ref == 0.0))
    else:
        ok = _same(got, ref)
    bad = np.flatnonzero(~ok)
    assert bad.size == 0, [(a[i], b[i], got[i], ref[i]) for i in bad[:5]]


def test_soft_add_is_exact_on_long_running_sums():
    """A running sum through the while-loop carry, as the engine's busy
    accumulators: every partial sum equals numpy's."""
    rng = np.random.default_rng(3)
    xs = 10.0 ** rng.uniform(-6.0, -1.0, 4096)
    want = np.cumsum(xs)

    def run(v):
        def body(i, c):
            s, out = c
            s = SOFT.add(s, v[i])
            return s, out.at[i].set(s)
        return jax.lax.fori_loop(0, v.shape[0], body, (SOFT.const(0.0), jnp.zeros_like(v)))[1]

    assert _same(_soft(run, xs), want).all()


@pytest.mark.parametrize("seed", [0, 1])
def test_soft_reductions_match_numpy(seed):
    rng = np.random.default_rng(seed)
    a = rng.uniform(-2.0, 2.0, (64, 96))
    a[::7, 3] = np.inf
    a[5] = a[5, 0]  # a row of ties: argmin is the first
    a[9, 10] = -0.0
    a[9, :10] = 0.0
    for name, want in (("min", np.min), ("max", np.max), ("argmin", np.argmin)):
        got = _soft(lambda x, name=name: getattr(SOFT, name)(x, axis=1), a,
                    floats=name != "argmin")
        assert _same(got, want(a, axis=1)).all(), name
    with_nan = a.copy()
    with_nan[3, 7] = np.nan
    assert _soft(lambda x: SOFT.argmin(x, axis=1), with_nan, floats=False)[3] == 7
    assert np.isnan(_soft(lambda x: SOFT.min(x, axis=1), with_nan)[3])


def test_soft_unary_ops_and_constants():
    a = np.concatenate([SPECIALS, -SPECIALS])
    assert _same(_soft(SOFT.abs, a), np.abs(a)).all()
    assert (_soft(SOFT.isfinite, a) == np.isfinite(a)).all()
    for x in SPECIALS:
        assert _same(SOFT.from_device(SOFT.const(x)), np.float64(x))
    assert _same(_soft(lambda: SOFT.full((3,), 1e-15)), np.full(3, 1e-15)).all()


def test_platform_choice():
    assert for_platform("tpu") is SOFT
    assert for_platform("cpu") is NATIVE and for_platform("gpu") is NATIVE
    assert for_platform() is NATIVE  # the test process runs on the CPU
