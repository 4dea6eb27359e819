"""Property tests for the compression operators (Definition 3).

``hypothesis`` is an optional dev dependency (requirements-dev.txt); the
module skips cleanly instead of failing collection when it is absent.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.compressors import (compress, count_sketch, decode_int8,
                                    dither_bits, encode_int8, identity,
                                    make_spec, min_max, natural,
                                    random_dithering, spec_omega, top_k)

vec = st.lists(st.floats(-100, 100, allow_nan=False, width=32),
               min_size=2, max_size=64).map(
                   lambda xs: np.asarray(xs, np.float32))


@settings(max_examples=25, deadline=None)
@given(vec, st.sampled_from([4, 16, 64, 128]))
def test_dithering_unbiased(x, s):
    """E[Q(x)] = x — empirical mean over many independent draws."""
    Q = random_dithering(s)
    if np.allclose(x, 0):
        return
    keys = jax.random.split(jax.random.key(0), 512)
    qs = jax.vmap(lambda k: Q.compress(k, jnp.asarray(x)))(keys)
    mean = np.asarray(jnp.mean(qs, axis=0))
    norm = np.max(np.abs(x))
    # std error of the mean per coord <= norm/(2 s sqrt(n))
    tol = 6.0 * norm / (2 * s * np.sqrt(512)) + 1e-6
    np.testing.assert_allclose(mean, x, atol=tol)


@settings(max_examples=25, deadline=None)
@given(vec, st.sampled_from([16, 64]))
def test_dithering_second_moment_bound(x, s):
    """E||Q(x)||² ≤ (1 + ω(d))||x||² with ω = d/(4s²)."""
    Q = random_dithering(s)
    nrm2 = float(np.sum(x * x))
    if nrm2 == 0:
        return
    keys = jax.random.split(jax.random.key(1), 256)
    qs = jax.vmap(lambda k: Q.compress(k, jnp.asarray(x)))(keys)
    second = float(jnp.mean(jnp.sum(qs * qs, axis=-1)))
    omega = Q.omega(x.size)
    assert second <= (1 + omega) * nrm2 * 1.05 + 1e-5


@settings(max_examples=25, deadline=None)
@given(vec, st.sampled_from([4, 16, 64, 128]))
def test_dithering_error_variance_bound(x, s):
    """Definition 3 membership: E‖Q(x) − x‖² ≤ ω‖x‖² with ω = d/(4s²).

    The expected error of ∞-norm dithering is available in closed form
    (per-coordinate stochastic rounding: p(1-p)·(‖x‖_∞/s)²), so the bound
    is checked *deterministically*, and the sampled error is only required
    to agree with the analytic value within statistical tolerance."""
    nrm2 = float(np.sum(np.float64(x) ** 2))
    if nrm2 == 0:
        return
    Q = random_dithering(s)
    norm = float(np.max(np.abs(x)))
    y = np.abs(np.float64(x)) / norm * s
    p = y - np.floor(y)
    analytic = float(np.sum(p * (1 - p))) * (norm / s) ** 2
    assert analytic <= Q.omega(x.size) * nrm2 * (1 + 1e-6) + 1e-12

    keys = jax.random.split(jax.random.key(5), 512)
    qs = jax.vmap(lambda k: Q.compress(k, jnp.asarray(x)))(keys)
    err = float(jnp.mean(jnp.sum((qs - jnp.asarray(x)) ** 2, axis=-1)))
    tol = 0.25 * analytic + 6.0 * (norm / s) ** 2 / np.sqrt(512) + 1e-6
    assert abs(err - analytic) <= tol


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 30000),
       st.lists(st.integers(1, 64), min_size=1, max_size=3))
def test_dither_bits_formula_random_levels_and_shapes(s, dims):
    """Wire accounting: an s-level dithered tensor of d elements ships
    exactly ceil(log2(2s+1))·d payload bits — for the static Compressor,
    the traced-sweep ``dither_bits`` helper, and any tensor shape.
    (Levels are capped at 30k: far above any practical dithering level,
    below where float32 log2 ulp error could misround the ceiling.)"""
    d = int(np.prod(dims))
    expect = math.ceil(math.log2(2 * s + 1))
    assert random_dithering(s).bits(1) == expect
    # traced-safe helper agrees, on python ints and traced f32 scalars alike
    assert float(dither_bits(s)) == expect
    assert float(dither_bits(jnp.float32(s))) == expect
    assert float(dither_bits(jnp.float32(s))) * d == expect * d


@settings(max_examples=20, deadline=None)
@given(vec)
def test_natural_unbiased(x):
    Q = natural()
    keys = jax.random.split(jax.random.key(2), 1024)
    qs = jax.vmap(lambda k: Q.compress(k, jnp.asarray(x)))(keys)
    mean = np.asarray(jnp.mean(qs, axis=0))
    tol = 6.0 * np.maximum(np.abs(x), 1e-3) / np.sqrt(1024) + 1e-5
    assert np.all(np.abs(mean - x) <= tol)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(2**-7, 100, allow_nan=False, width=16),
                min_size=1, max_size=48),
       st.lists(st.integers(1, 8), min_size=1, max_size=3),
       st.sampled_from([np.float32, np.float16]),
       st.booleans())
def test_natural_error_variance_bound(mags, dims, dtype, negate):
    """Definition 3 membership of natural compression, mirroring the
    dithering test: unbiased (above) with E‖Q(x) − x‖² ≤ (1/8)‖x‖² over
    random shapes/dtypes.

    Per coordinate the error variance is p(1−p)·lo² with lo = 2^⌊log2|x|⌋
    and p = (|x| − lo)/lo, and p(1−p)/(1+p)² ≤ 1/8 (tight at p = 1/3), so
    the ω = 1/8 bound is checked *deterministically* in closed form; the
    sampled error only has to agree with the analytic value within
    statistical tolerance.  Both rounding targets {lo, 2lo} are powers of
    two, hence exactly representable in f16/f32 — the bound is exact for
    every dtype in the normal range."""
    d = int(np.prod(dims))
    x = np.resize(np.asarray(mags, np.float64), d)
    x = np.where(negate, -x, x)
    x = x.astype(dtype)                          # representable values only
    shaped = jnp.asarray(x.reshape(dims))
    xf = np.asarray(x, np.float64)
    nrm2 = float(np.sum(xf ** 2))
    lo = 2.0 ** np.floor(np.log2(np.abs(xf)))
    p = np.abs(xf) / lo - 1.0
    analytic = float(np.sum(p * (1 - p) * lo * lo))
    assert analytic <= nrm2 / 8.0 * (1 + 1e-6) + 1e-12
    assert float(spec_omega(natural().spec, d)) == 0.125

    Q = natural()
    keys = jax.random.split(jax.random.key(7), 512)
    qs = jax.vmap(lambda k: Q.compress(k, shaped).reshape(-1))(keys)
    assert qs.dtype == shaped.dtype
    err = float(jnp.mean(jnp.sum(
        (qs.astype(jnp.float32) - jnp.asarray(xf, jnp.float32)) ** 2,
        axis=-1)))
    # per-coordinate error range is lo ≤ |x|: CLT tolerance on the mean
    tol = 0.25 * analytic + 6.0 * float(np.max(lo)) ** 2 / np.sqrt(512) + 1e-6
    assert abs(err - analytic) <= tol
    # realized error never exceeds the per-draw worst case Σ lo²
    worst = float(np.sum(lo * lo)) * (1 + 1e-5) + 1e-6
    assert float(jnp.max(jnp.sum(
        (qs.astype(jnp.float32) - jnp.asarray(xf, jnp.float32)) ** 2,
        axis=-1))) <= worst


def test_identity_exact(rng):
    Q = identity()
    x = jnp.asarray(rng.normal(size=37), jnp.float32)
    np.testing.assert_array_equal(Q.compress(jax.random.key(0), x), x)


def test_topk_keeps_largest(rng):
    Q = top_k(0.25)
    x = jnp.asarray(rng.normal(size=100), jnp.float32)
    y = np.asarray(Q.compress(jax.random.key(0), x))
    nz = np.nonzero(y)[0]
    assert len(nz) == 25
    thresh = np.sort(np.abs(np.asarray(x)))[-25]
    assert np.all(np.abs(np.asarray(x)[nz]) >= thresh - 1e-6)
    np.testing.assert_allclose(y[nz], np.asarray(x)[nz])


def test_int8_roundtrip_error_bounded(rng):
    x = jnp.asarray(rng.normal(size=(64, 33)), jnp.float32)
    levels, scale = encode_int8(jax.random.key(3), x, s=127)
    assert levels.dtype == jnp.int8
    err = np.max(np.abs(np.asarray(decode_int8(levels, scale) - x)))
    assert err <= float(scale) + 1e-7


def test_int8_sum_compatible(rng):
    """decode(Σ levels)·scale == Σ decode(levels) — the property the
    compressed all-reduce relies on."""
    xs = [jnp.asarray(rng.normal(size=50), jnp.float32) for _ in range(4)]
    # shared scale
    s = 63
    norm = max(float(jnp.max(jnp.abs(x))) for x in xs)
    lvls = []
    for i, x in enumerate(xs):
        y = x / norm * s
        lo = jnp.floor(y)
        u = jax.random.uniform(jax.random.key(i), x.shape)
        lvls.append((lo + (u < (y - lo))).astype(jnp.int8))
    summed = sum(l.astype(jnp.int32) for l in lvls)
    lhs = np.asarray(summed, np.float32) * norm / s
    rhs = sum(np.asarray(l, np.float32) * norm / s for l in lvls)
    np.testing.assert_allclose(lhs, rhs, rtol=1e-6)


def test_registry():
    assert random_dithering(64).name == "dither64"
    assert identity().bits(1) == 32.0
    assert random_dithering(128).bits(1) == np.ceil(np.log2(257))
    with pytest.raises(ValueError):
        make_spec("nope")


# ---------------------------------------------------------------------------
# The sketch/sampling families (Definition 3 membership, like the above)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(vec, st.sampled_from([8, 16, 32]), st.sampled_from([1, 3, 5]))
def test_count_sketch_unbiased_and_omega_bound(x, width, depth):
    """E[Q(x)] = x at hh_frac = 1 (each estimator row's collision noise is
    symmetric about the true value, so the row median is exactly unbiased)
    and E‖Q(x) − x‖² ≤ ω‖x‖² with ω = d/width reported by ``spec_omega``
    (the single-row collision-variance bound; the median over depth rows
    only concentrates it further)."""
    if np.allclose(x, 0):
        return
    d = x.size
    Q = count_sketch(width, depth)
    assert Q.unbiased
    nrm2 = float(np.sum(np.float64(x) ** 2))
    wc = min(width, d)
    assert float(spec_omega(Q.spec, d)) == pytest.approx(d / wc)
    keys = jax.random.split(jax.random.key(11), 512)
    qs = jax.vmap(lambda k: Q.compress(k, jnp.asarray(x)))(keys)
    mean = np.asarray(jnp.mean(qs, axis=0))
    # per-coordinate estimator std <= sqrt(||x||²/w): CLT tolerance
    tol = 6.0 * np.sqrt(nrm2 / wc) / np.sqrt(512) + 1e-5
    np.testing.assert_allclose(mean, x, atol=tol)
    err = float(jnp.mean(jnp.sum(
        (qs.astype(jnp.float64) - np.float64(x)) ** 2, axis=-1)))
    slack = 6.0 * (d / wc) * nrm2 / np.sqrt(512)
    assert err <= (d / wc) * nrm2 * 1.05 + slack + 1e-5


@settings(max_examples=15, deadline=None)
@given(vec, st.sampled_from([0.1, 0.3, 0.7]))
def test_minmax_unbiased_and_omega_bound(x, frac):
    """Min-max sampling: inverse-probability reweighting makes E[Q(x)] = x
    exactly, and the error variance Σ x_i²(1 − p_i)/p_i is available in
    closed form — checked *deterministically* against the ω = d/⌈frac·d⌉
    bound of ``spec_omega`` (Σ x_i²/p_i ≤ ‖x‖₁²/k ≤ (d/k)‖x‖² by
    Cauchy–Schwarz); the sampled error only has to agree with the analytic
    value within statistical tolerance."""
    if np.allclose(x, 0):
        return
    d = x.size
    Q = min_max(frac)
    assert Q.unbiased
    xf = np.float64(x)
    nrm2 = float(np.sum(xf ** 2))
    k = min(max(1, math.ceil(frac * d)), d)
    p = np.minimum(k * np.abs(xf) / np.sum(np.abs(xf)), 1.0)
    var = np.where(p > 0, xf ** 2 * (1 - p) / np.maximum(p, 1e-300), 0.0)
    analytic = float(np.sum(var))
    omega = float(spec_omega(Q.spec, d))
    assert omega == pytest.approx(d / k)
    assert analytic <= omega * nrm2 * (1 + 1e-6) + 1e-9

    keys = jax.random.split(jax.random.key(13), 512)
    qs = jax.vmap(lambda kk: Q.compress(kk, jnp.asarray(x)))(keys)
    mean = np.asarray(jnp.mean(qs, axis=0), np.float64)
    tol = 6.0 * np.sqrt(var / 512) + 1e-4
    assert np.all(np.abs(mean - xf) <= tol)
    err = float(jnp.mean(jnp.sum(
        (qs.astype(jnp.float64) - xf) ** 2, axis=-1)))
    # per-draw error is a sum of d bounded-variance terms: CLT on the mean
    tol_err = 0.25 * analytic + 6.0 * np.sqrt(
        float(np.sum(var ** 2)) / 512) + 1e-4
    assert abs(err - analytic) <= tol_err + analytic  # one-sided slack
    assert err <= omega * nrm2 * 1.05 + tol_err


def test_count_sketch_heavy_hitters_sparsify(rng):
    """hh_frac < 1 keeps at most ⌈hh_frac·d⌉ coordinates of the median
    estimate (a biased top-k-style contraction — ``unbiased`` flags it)."""
    Q = count_sketch(width=32, depth=3, hh_frac=0.25)
    assert not Q.unbiased
    x = jnp.asarray(np.random.default_rng(3).normal(size=40), jnp.float32)
    y = np.asarray(Q.compress(jax.random.key(0), x))
    assert np.count_nonzero(y) <= 10


def test_count_sketch_encode_is_linear(rng):
    """sketch(Σx) == Σ sketch(x) under a shared key — the property the
    hierarchy's sketch-domain aggregation fast path rests on (decode of
    the summed table equals flat compression of the sum)."""
    from repro.core.compressors import (count_sketch_decode,
                                        count_sketch_encode)
    spec = make_spec("count_sketch", width=16, depth=3)
    key = jax.random.key(21)
    xs = jnp.asarray(rng.normal(size=(5, 24)), jnp.float32)
    t_sum = count_sketch_encode(key, jnp.sum(xs, axis=0), spec.params)
    t_each = sum(count_sketch_encode(key, xs[i], spec.params)
                 for i in range(5))
    np.testing.assert_allclose(np.asarray(t_sum), np.asarray(t_each),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(count_sketch_decode(key, t_sum, xs[0], spec.params)),
        np.asarray(compress(spec, key, jnp.sum(xs, axis=0))))
