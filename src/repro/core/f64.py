"""IEEE binary64 arithmetic for the simulator's device programs.

The batch engine and the jitted Terastal round must reproduce the
Python engines' float64 results bit for bit, which needs every add,
subtract, multiply and compare to be the IEEE operation numpy performs.
Two implementations of the same small operation set provide that:

* :data:`NATIVE` — ``jnp`` float64 operations.  Binary64 where the
  backend has it (CPU, GPU).
* :data:`SOFT` — binary64 in software on the int64 bit patterns,
  rounding to nearest even.  For the TPU, whose XLA float64 is a pair
  of float32s: about 48 significand bits and the float32 exponent
  range, so neither a host-to-device copy nor an add reproduces numpy.

:func:`for_platform` picks one.  A program written against the
interface (``F.add(a, b)`` instead of ``a + b``, ``F.const(1e-15)``
instead of ``1e-15``) runs either, and arrays cross the host boundary
through :meth:`to_device`/:meth:`from_device` (a reinterpretation of the
bits for ``SOFT``, nothing for ``NATIVE``).  Both need 64-bit types
enabled (``jax.enable_x64``).

``SOFT`` is exact for every normal and subnormal result of add and
subtract, and for every normal result of multiply; a product below the
smallest normal (2.2e-308) flushes to a signed zero, as does a product
with a subnormal operand.  NaN propagates as one quiet NaN, comparisons
with NaN are false, and ``-0 == +0``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_SIGN = np.int64(-(1 << 63))          # sign bit
_ABS = np.int64((1 << 63) - 1)        # everything but the sign
_EXP = np.int64(0x7FF << 52)          # exponent field; also the bits of +inf
_FRAC = np.int64((1 << 52) - 1)
_HIDDEN = np.int64(1 << 52)
_QNAN = np.int64(0x7FF8 << 48)
_I64 = jnp.int64


class _Native:
    """``jnp`` float64: binary64 where the backend implements it."""

    name = "native"
    dtype = jnp.float64

    @staticmethod
    def const(x: float):
        return x

    @staticmethod
    def to_device(a):
        return a

    @staticmethod
    def from_device(a):
        return np.asarray(a)

    add = staticmethod(jnp.add)
    sub = staticmethod(jnp.subtract)
    mul = staticmethod(jnp.multiply)
    lt = staticmethod(jnp.less)
    le = staticmethod(jnp.less_equal)
    gt = staticmethod(jnp.greater)
    ge = staticmethod(jnp.greater_equal)
    eq = staticmethod(jnp.equal)
    ne = staticmethod(jnp.not_equal)
    minimum = staticmethod(jnp.minimum)
    maximum = staticmethod(jnp.maximum)
    min = staticmethod(jnp.min)
    max = staticmethod(jnp.max)
    argmin = staticmethod(jnp.argmin)
    argmax = staticmethod(jnp.argmax)
    abs = staticmethod(jnp.abs)
    isfinite = staticmethod(jnp.isfinite)

    @staticmethod
    def argsort(a):
        return jnp.argsort(a, stable=True)

    @staticmethod
    def full(shape, x: float):
        return jnp.full(shape, x)


def _isnan(a):
    return (a & _ABS) > _EXP


def _key(a):
    """Order-preserving int64 key: ``_key(a) < _key(b)`` iff ``a < b``
    for non-NaN binary64 bit patterns, and ``-0`` and ``+0`` share 0."""
    s = a >> 63  # 0, or -1 for a set sign bit
    return (a ^ (s & _ABS)) - s


def _unkey(k):
    s = k >> 63
    return (k + s) ^ (s & _ABS)


def _unpack(a):
    """(biased exponent >= 1, significand with its hidden bit)."""
    e = (a >> 52) & 0x7FF
    f = a & _FRAC
    return jnp.maximum(e, 1), jnp.where(e == 0, f, f | _HIDDEN)


def _clz56(s):
    """Leading zeros of ``0 <= s < 2^56`` in a 56-bit field."""
    return jax.lax.clz(s) - 8


def _add(a, b):
    # order by magnitude (for one format, bit order is magnitude order)
    swap = (b & _ABS) > (a & _ABS)
    x = jnp.where(swap, b, a)
    y = jnp.where(swap, a, b)
    ex, mx = _unpack(x)
    ey, my = _unpack(y)
    subtract = (x ^ y) < 0
    # three extra low bits: guard, round, and sticky (bit 0 keeps the OR
    # of every bit shifted out of the smaller operand)
    mx = mx << 3
    my = my << 3
    d = jnp.minimum(ex - ey, 58)
    lost = (my & ((np.int64(1) << d) - 1)) != 0
    my = (my >> d) | lost.astype(_I64)
    s = jnp.where(subtract, mx - my, mx + my)
    # carry out of the top: one right shift, folding the lost bit in
    carry = s >= (np.int64(1) << 56)
    s = jnp.where(carry, (s >> 1) | (s & 1), s)
    e = ex + carry.astype(_I64)
    # cancellation: shift left to the top, not below the subnormal scale
    lz = jnp.minimum(_clz56(s), e - 1)
    s = s << lz
    e = e - lz
    # round to nearest, ties to even
    low = s & 7
    m = s >> 3
    m = m + ((low > 4) | ((low == 4) & ((m & 1) == 1))).astype(_I64)
    up = m >= (np.int64(1) << 53)
    m = jnp.where(up, m >> 1, m)
    e = e + up.astype(_I64)
    bits = jnp.where(m >= _HIDDEN, e << 52, 0) | (m & _FRAC)
    bits = jnp.where(e >= 0x7FF, _EXP, bits) | (x & _SIGN)
    # exact cancellation is +0 (x + -x), never -0
    bits = jnp.where(subtract & (s == 0), 0, bits)
    # x holds the larger magnitude, so an infinite y means x is too
    inf_x = (x & _ABS) == _EXP
    inf_y = (y & _ABS) == _EXP
    bits = jnp.where(inf_x, jnp.where(subtract & inf_y, _QNAN, x), bits)
    return jnp.where(_isnan(a) | _isnan(b), _QNAN, bits)


def _mul(a, b):
    sign = (a ^ b) & _SIGN
    ea, ma = _unpack(a)
    eb, mb = _unpack(b)
    # 53 x 53 -> 106-bit product from 27 x 26-bit pieces, as hi * 2^52 + lo
    lo_mask = np.int64((1 << 26) - 1)
    ah, al = ma >> 26, ma & lo_mask
    bh, bl = mb >> 26, mb & lo_mask
    mid = ah * bl + al * bh
    lo = al * bl + ((mid & lo_mask) << 26)
    hi = ah * bh + (mid >> 26) + (lo >> 52)
    lo = lo & _FRAC
    # normal operands: hi in [2^52, 2^54); keep 53 bits, round the rest
    top = hi >= (np.int64(1) << 53)
    m = jnp.where(top, hi >> 1, hi)
    rem = jnp.where(top, ((hi & 1) << 52) | lo, lo << 1)  # scaled to 53 bits
    half = np.int64(1) << 52
    m = m + ((rem > half) | ((rem == half) & ((m & 1) == 1))).astype(_I64)
    e = ea + eb - 1023 + top.astype(_I64)
    up = m >= (np.int64(1) << 53)
    m = jnp.where(up, m >> 1, m)
    e = e + up.astype(_I64)
    bits = (e << 52) | (m & _FRAC)
    bits = jnp.where(e >= 0x7FF, _EXP, jnp.where(e <= 0, 0, bits))
    flush = ((a & _EXP) == 0) | ((b & _EXP) == 0)  # zero or subnormal operand
    inf = ((a & _ABS) == _EXP) | ((b & _ABS) == _EXP)
    bits = jnp.where(inf, _EXP, jnp.where(flush, 0, bits)) | sign
    zero = ((a & _ABS) == 0) | ((b & _ABS) == 0)
    nan = _isnan(a) | _isnan(b) | (inf & zero)
    return jnp.where(nan, _QNAN, bits)


def _ordered(op):
    def cmp(a, b):
        return op(_key(a), _key(b)) & ~(_isnan(a) | _isnan(b))

    return staticmethod(cmp)


class _Soft:
    """Binary64 in software on int64 bit patterns (round to nearest even)."""

    name = "soft"
    dtype = jnp.int64

    @staticmethod
    def const(x: float):
        return np.float64(x).view(np.int64)

    @staticmethod
    def to_device(a):
        return np.asarray(a, np.float64).view(np.int64)

    @staticmethod
    def from_device(a):
        return np.asarray(a).view(np.float64)

    add = staticmethod(_add)
    mul = staticmethod(_mul)

    @staticmethod
    def sub(a, b):
        return _add(a, b ^ _SIGN)

    lt = _ordered(jnp.less)
    le = _ordered(jnp.less_equal)
    gt = _ordered(jnp.greater)
    ge = _ordered(jnp.greater_equal)
    eq = _ordered(jnp.equal)

    @staticmethod
    def ne(a, b):
        return ~_Soft.eq(a, b)

    @staticmethod
    def minimum(a, b):
        pick = jnp.where(_key(b) < _key(a), b, a)
        return jnp.where(_isnan(a) | _isnan(b), _QNAN, pick)

    @staticmethod
    def maximum(a, b):
        pick = jnp.where(_key(b) > _key(a), b, a)
        return jnp.where(_isnan(a) | _isnan(b), _QNAN, pick)

    @staticmethod
    def min(a, axis=None):
        m = _unkey(jnp.min(_key(a), axis=axis))
        return jnp.where(jnp.any(_isnan(a), axis=axis), _QNAN, m)

    @staticmethod
    def max(a, axis=None):
        m = _unkey(jnp.max(_key(a), axis=axis))
        return jnp.where(jnp.any(_isnan(a), axis=axis), _QNAN, m)

    @staticmethod
    def argmin(a, axis=None):
        """First index of the minimum; of the first NaN if there is one
        (``jnp.argmin``'s rule)."""
        nan = _isnan(a)
        return jnp.where(jnp.any(nan, axis=axis), jnp.argmax(nan, axis=axis),
                         jnp.argmin(_key(a), axis=axis))

    @staticmethod
    def argmax(a, axis=None):
        """First index of the maximum; of the first NaN if there is one."""
        nan = _isnan(a)
        return jnp.where(jnp.any(nan, axis=axis), jnp.argmax(nan, axis=axis),
                         jnp.argmax(_key(a), axis=axis))

    @staticmethod
    def argsort(a):
        """Stable ascending order (NaN-free input)."""
        return jnp.argsort(_key(a), stable=True)

    @staticmethod
    def abs(a):
        return a & _ABS

    @staticmethod
    def isfinite(a):
        return (a & _EXP) != _EXP

    @staticmethod
    def full(shape, x: float):
        return jnp.full(shape, _Soft.const(x), _I64)


NATIVE = _Native()
SOFT = _Soft()

#: backends whose XLA float64 is not IEEE binary64
_SOFT_PLATFORMS = ("tpu",)


def for_platform(platform: str | None = None):
    """The implementation for ``platform`` (default: JAX's default
    backend): :data:`SOFT` on the TPU, :data:`NATIVE` elsewhere."""
    platform = platform or jax.default_backend()
    return SOFT if platform in _SOFT_PLATFORMS else NATIVE
