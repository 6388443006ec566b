"""Exact IEEE-754 binary64 arithmetic on int64 bit patterns.

The jax engine must reproduce the C kernel's double arithmetic bit for
bit, and an accelerator need not: the TPU emulates float64 as a pair of
float32s (about 48 significant bits), so even loading a double onto it
can round.  So the engine keeps every double as its int64 bit pattern
and does the few operations it needs — add, subtract, divide by a
static constant, compare — in integer arithmetic, rounding to nearest,
ties to even, exactly as IEEE does.  Integer arithmetic is exact on
every backend, so a result that equals the C kernel's on the CPU equals
it on the chip.

Scope: finite, non-negative, normal operands (simulated times and
latencies); results are normal or zero.  ``key`` orders arbitrary
(non-NaN) doubles for comparisons.  There is no 64-bit integer
division: the TPU emulates it, and it dominated the engine's compile
time there.
"""

from __future__ import annotations

import math
import struct
from fractions import Fraction
from typing import Dict

import jax.numpy as jnp
from jax import lax

_M52 = (1 << 52) - 1
_HID = 1 << 52
_M32 = (1 << 32) - 1
_KEYFLIP = (1 << 63) - 1


def bits(x: float) -> int:
    """The int64 bit pattern of a Python float."""
    return struct.unpack("<q", struct.pack("<d", float(x)))[0]


def key(x):
    """A total-order int64 key: ``key(a) < key(b)`` iff ``a < b`` for
    doubles given as bit patterns (any sign; -0.0 sorts below +0.0)."""
    return jnp.where(x < 0, x ^ _KEYFLIP, x)


def _unpack(x):
    e = x >> 52
    m = (x & _M52) | jnp.where(e > 0, _HID, 0)
    return jnp.maximum(e, 1), m


def _shr_sticky(m, d):
    """``m >> d`` (0 <= d <= 60) with the shifted-out bits ORed into the
    lowest bit (the sticky bit)."""
    s = m >> d
    return s | ((s << d) != m).astype(m.dtype)


def _round_pack(e, s):
    """Round the 56-bit significand ``s`` (53 bits + guard, round and
    sticky bits) to nearest even and pack it with biased exponent e."""
    q = s >> 3
    g = s & 7
    q = q + ((g > 4) | ((g == 4) & ((q & 1) == 1))).astype(q.dtype)
    c = q >> 53
    q = jnp.where(c == 1, q >> 1, q)
    e = e + c
    return jnp.where(q == 0, 0, (e << 52) | (q & _M52))


def add(a, b):
    """a + b for non-negative doubles."""
    hi = jnp.maximum(a, b)
    lo = jnp.minimum(a, b)
    eh, mh = _unpack(hi)
    el, ml = _unpack(lo)
    s = (mh << 3) + _shr_sticky(ml << 3, jnp.minimum(eh - el, 60))
    c = s >> 56
    s = jnp.where(c == 1, (s >> 1) | (s & 1), s)
    return _round_pack(eh + c, s)


def sub(a, b):
    """a - b for doubles a >= b >= 0."""
    ea, ma = _unpack(a)
    eb, mb = _unpack(b)
    s = (ma << 3) - _shr_sticky(mb << 3, jnp.minimum(ea - eb, 60))
    # renormalise so that bit 55 leads; a one-bit cancellation at most
    # when bits were shifted out, so the sticky bit stays below guard
    lz = jnp.minimum(lax.clz(s) - 8, ea - 1)
    s = s << lz
    return _round_pack(ea - lz, s)


def sub_clamp(a, b):
    """max(a - b, 0.0) for non-negative doubles."""
    return jnp.where(a > b, sub(a, jnp.minimum(a, b)), 0)


def _odd_part(c: float):
    """c = odd * 2**k; returns (odd, k)."""
    num, den = Fraction(c).as_integer_ratio()
    tz = (num & -num).bit_length() - 1
    return num >> tz, tz - (den.bit_length() - 1)


def exact_divisor(c: float) -> bool:
    """Whether :func:`div_const` takes ``c``."""
    return c > 0 and _odd_part(c)[0] < 1 << 15


def div_const(a, c: float):
    """a / c for a non-negative double a and a static positive double c
    whose significand's odd part is below 2**15 (e.g. 6.0 = 3 * 2)."""
    if not exact_divisor(c):
        raise ValueError(f"divisor {c!r} is not an exact_divisor")
    odd, k = _odd_part(c)
    ea, ma = _unpack(a)
    if odd == 1:
        return jnp.where(a == 0, 0, a - (k << 52))
    # long division of ma * 2**10 (in [2**62, 2**63)) by ``odd`` in four
    # 16-bit digits, each step small enough for int32
    n = ma << 10
    i32 = jnp.int32
    r = jnp.zeros_like(n, dtype=i32)
    q = jnp.zeros_like(n)
    for shift in (48, 32, 16, 0):
        cur = (r << 16) | ((n >> shift) & 0xFFFF).astype(i32)
        qd = cur // odd
        r = cur - qd * odd
        q = (q << 16) | qd.astype(q.dtype)
    sh = (63 - lax.clz(q)) - 55             # bring the leading one to bit 55
    s = _shr_sticky(q, sh) | (r != 0).astype(q.dtype)
    return jnp.where(a == 0, 0, _round_pack(ea - 7 + sh - k, s))


# ---------------------------------------------------------------------------
# exact "RN(n / d) < t" for integer n >= 0, d >= 1 and a double threshold t
# ---------------------------------------------------------------------------
#: fixed binary point of the threshold boundaries: thresholds must lie in
#: [2**-20, 2**11] (or be <= 0) for the boundary to be an integer here
_TSHIFT = 76


def ratio_threshold(t: float) -> Dict[str, int]:
    """Host-side constants for :func:`ratio_lt`: the midpoint between t
    and the next smaller double as an integer over 2**76 (three 32-bit
    limbs), whether a quotient landing exactly on it rounds below t
    (ties to even), and t > 0.  ``RN(q) < t`` iff ``q`` is below that
    midpoint, so the test needs no division."""
    t = float(t)
    if t <= 0.0:
        return {"m0": 0, "m1": 0, "m2": 0, "tie": 0, "pos": 0}
    if not (2.0 ** -20 <= t <= 2.0 ** 11):
        raise ValueError(f"threshold {t!r} outside [2**-20, 2**11]")
    p = math.nextafter(t, 0.0)
    mid = (Fraction(p) + Fraction(t)) / 2 * (1 << _TSHIFT)
    assert mid.denominator == 1
    m = int(mid)
    return {"m0": m & _M32, "m1": (m >> 32) & _M32, "m2": m >> 64,
            "tie": int((bits(p) & 1) == 0), "pos": 1}


def ratio_lt(n, d, c: Dict):
    """``RN(n / d) < t`` for int64 n in [0, 2**40), d in [1, 2**31), with
    ``c`` the (possibly traced) constants of :func:`ratio_threshold`."""
    # RHS = M * d as four 32-bit limbs; LHS = n * 2**76 = (n << 12) * 2**64
    p0 = c["m0"] * d
    t1 = c["m1"] * d + (p0 >> 32)
    t2 = c["m2"] * d + (t1 >> 32)
    r0, r1, r2, r3 = p0 & _M32, t1 & _M32, t2 & _M32, t2 >> 32
    ln = n << 12
    l2, l3 = ln & _M32, ln >> 32
    low = (r1 > 0) | (r0 > 0)
    lt = (l3 < r3) | ((l3 == r3) & ((l2 < r2) | ((l2 == r2) & low)))
    eq = (l3 == r3) & (l2 == r2) & ~low
    return (c["pos"] == 1) & (lt | (eq & (c["tie"] == 1)))
