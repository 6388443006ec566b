"""Exact binary64 arithmetic on int64 bit patterns (core/f64bits.py)
against the host's IEEE doubles: every result must match bit for bit."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import f64bits as fb  # noqa: E402


def _doubles(rng, n):
    """Times and latencies as the simulator makes them: integers,
    quotients by the MLP constants, long sums with fractional parts,
    and values spanning many binades."""
    ints = rng.integers(0, 1 << 20, n).astype(np.float64)
    sixths = rng.integers(1, 1 << 16, n) / 6.0
    big = np.cumsum(rng.uniform(0.5, 300.0, n)) + rng.choice(
        [0.0, 1e6, 1e9, 3.3e12], n)
    wide = np.exp2(rng.uniform(-30, 40, n)) * rng.uniform(1, 2, n)
    x = np.concatenate([ints, sixths, big, wide, [0.0, 1.0, 2.0, 6.0]])
    return rng.permutation(x)


def _bits(x):
    return np.asarray(x, np.float64).view(np.int64)


@pytest.fixture(scope="module")
def pair():
    rng = np.random.default_rng(11)
    a, b = _doubles(rng, 4000), _doubles(rng, 4000)
    n = min(len(a), len(b))
    return a[:n], b[:n]


def _run(f, *xs):
    with jax.enable_x64(True):
        return np.asarray(jax.jit(f)(*[jnp.asarray(_bits(x)) for x in xs]))


def test_add_matches_ieee(pair):
    a, b = pair
    np.testing.assert_array_equal(_run(fb.add, a, b), _bits(a + b))


def test_sub_matches_ieee(pair):
    a, b = np.maximum(*pair), np.minimum(*pair)
    np.testing.assert_array_equal(_run(fb.sub, a, b), _bits(a - b))
    np.testing.assert_array_equal(_run(fb.sub_clamp, b, a),
                                  np.zeros(len(a), np.int64))


@pytest.mark.parametrize("c", [6.0, 48.0, 2.0, 3.0, 0.75, 10.0])
def test_div_const_matches_ieee(pair, c):
    a = np.abs(pair[0])
    np.testing.assert_array_equal(_run(lambda x: fb.div_const(x, c), a),
                                  _bits(a / c))


def test_key_orders_like_doubles():
    rng = np.random.default_rng(3)
    x = np.concatenate([rng.normal(0, 1e3, 500), [0.0, 1.0, -1.0, 4.0]])
    k = _run(fb.key, x)
    order = np.argsort(x, kind="stable")
    assert np.all(np.diff(k[order]) >= 0)


@pytest.mark.parametrize("t", [0.05, 0.2, 0.4, 0.8, 1.0, 0.1, 2.5, 4.0,
                               0.3, 0.0])
def test_ratio_lt_matches_ieee(t):
    """RN(n / d) < t decided without a division, including the
    quotients that land exactly on the threshold's decimal value."""
    rng = np.random.default_rng(int(t * 1000) + 7)
    d = np.concatenate([rng.integers(1, 1 << 31, 3000),
                        rng.integers(1, 200, 3000)])
    n = np.concatenate([rng.integers(0, 1 << 33, 3000),
                        np.floor(d[3000:] * t).astype(np.int64)
                        + rng.integers(-1, 2, 3000)])
    n = np.maximum(n, 0)
    c = fb.ratio_threshold(t)
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(lambda n_, d_: fb.ratio_lt(
            n_, d_, {k: jnp.int64(v) for k, v in c.items()}))(
                jnp.asarray(n), jnp.asarray(d)))
    want = (n.astype(np.float64) / d.astype(np.float64)) < t
    np.testing.assert_array_equal(got, want)
