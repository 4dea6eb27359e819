"""Pallas kernel validation (interpret mode): shape/dtype sweeps vs the
pure-jnp oracles in each kernel's ref.py.

The fused-compressor section pins the BIT-IDENTITY contract of
``repro.kernels.compressor``: kernel and jnp reference are compared
within a consistent evaluation context (both eager, or both inside one
jit) — that is the drop-in guarantee ``compressors.compress(...,
use_kernel=True)`` relies on.  Comparing a jitted program against an
eager one is outside the contract (XLA fusion may perturb last-ulp
results of either path).  The hypothesis property tests run only when
hypothesis is installed (requirements-dev.txt; CI always has it) — the
module must not importorskip wholesale, the non-property kernel tests
are tier-1 either way.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compressors
from repro.kernels.compressor import ops as comp_ops
from repro.kernels.compressor.ops import (dither_bits_fused, fused_dither,
                                          fused_topk, topk_bits_fused)
from repro.kernels.compressor.ref import (dither_bits_ref, fused_dither_ref,
                                          fused_topk_ref, topk_bits_ref)
from repro.kernels.dither.dither import dither_decode, dither_encode
from repro.kernels.dither.ops import dequantize, quantize
from repro.kernels.dither.ref import dither_decode_ref, dither_encode_ref
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd import ref as ssd_ref
from repro.models import ssm

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


@pytest.mark.parametrize("R,C,br,s", [(16, 128, 8, 127), (32, 256, 8, 63),
                                      (8, 512, 4, 15), (64, 128, 16, 127)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dither_encode_matches_ref(rng, R, C, br, s, dtype):
    x = jnp.asarray(rng.normal(size=(R, C)) * 10, dtype)
    u = jax.random.uniform(jax.random.key(0), (R, C), jnp.float32)
    lv_k, sc_k = dither_encode(x, u, s=s, block_rows=br, interpret=True)
    lv_r, sc_r = dither_encode_ref(x, u, s, br)
    np.testing.assert_array_equal(np.asarray(lv_k), np.asarray(lv_r))
    np.testing.assert_allclose(np.asarray(sc_k), np.asarray(sc_r), rtol=1e-6)
    out_k = dither_decode(lv_k, sc_k, block_rows=br, interpret=True)
    out_r = dither_decode_ref(lv_r, sc_r, br)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6)


@pytest.mark.parametrize("shape", [(1000,), (33, 77), (4, 5, 6), (128, 512)])
def test_dither_roundtrip_any_shape(rng, shape):
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    lv, sc, meta = quantize(jax.random.key(1), x, s=63, interpret=True)
    xr = dequantize(lv, sc, meta, interpret=True)
    assert xr.shape == x.shape
    assert float(jnp.max(jnp.abs(xr - x))) <= float(jnp.max(sc)) + 1e-6


def test_dither_unbiased_through_kernel(rng):
    x = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
    keys = jax.random.split(jax.random.key(2), 256)

    def enc(k):
        u = jax.random.uniform(k, x.shape)
        lv, sc = dither_encode(x, u, s=31, block_rows=8, interpret=True)
        return dither_decode(lv, sc, block_rows=8, interpret=True)

    mean = jnp.mean(jax.vmap(enc)(keys), axis=0)
    step = float(jnp.max(jnp.abs(x)) / 31)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(x),
                               atol=6 * step / 2 / np.sqrt(256) + 1e-5)


@pytest.mark.parametrize("B,H,KV,S,D,window,cap", [
    (1, 4, 2, 256, 64, 0, 0.0),
    (2, 4, 4, 128, 32, 0, 50.0),
    (1, 8, 2, 512, 64, 128, 0.0),
    (2, 2, 1, 256, 128, 64, 30.0),
    (1, 2, 2, 384, 64, 0, 0.0),      # non-pow2 block count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.slow
def test_flash_attention_matches_ref(rng, B, H, KV, S, D, window, cap, dtype):
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, KV, S, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, KV, S, D)), dtype)
    o_k = flash_attention(q, k, v, window=window, cap=cap,
                          block_q=128, block_k=128, interpret=True)
    o_r = attention_ref(q, k, v, window=window, cap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_block_size_invariance(rng):
    B, H, KV, S, D = 1, 2, 2, 256, 32
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
            for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# SSD intra-chunk kernel (repro.kernels.ssd) — tolerance suite
# ---------------------------------------------------------------------------

def _ssd_inputs(rng, B, S, H, P, N, Q):
    """Chunked C, B, a = dt·A (decays as Mamba-2 draws them) and x·dt."""
    nc = S // Q
    C = rng.normal(size=(B, nc, Q, N)) / np.sqrt(N)
    Bm = rng.normal(size=(B, nc, Q, N)) / np.sqrt(N)
    dt = rng.uniform(1e-3, 0.1, size=(B, nc, H, Q))
    a = -dt * np.linspace(1.0, 16.0, H)[None, None, :, None]
    x = rng.normal(size=(B, nc, Q, H, P)) * dt.transpose(0, 1, 3, 2)[..., None]
    return tuple(jnp.asarray(v, jnp.float32) for v in (C, Bm, a, x))


def _close(got, want, tol):
    """Within ``tol`` of the largest entry of ``want``, entry by entry."""
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol,
                               atol=tol * float(np.max(np.abs(want))))


@pytest.mark.parametrize("B,S,H,P,N,Q", [
    (1, 256, 2, 64, 128, 128),      # two chunks, one head pair
    (2, 256, 4, 64, 128, 256),      # one chunk, two head pairs
    (1, 512, 4, 64, 128, 128),      # four chunks
    (2, 512, 2, 64, 128, 256),      # the cell's chunk size
])
@pytest.mark.parametrize("mxu,tol", [(jnp.float32, 2e-5),
                                     (jnp.bfloat16, 2e-2)])
def test_ssd_chunk_terms_match_ref(rng, B, S, H, P, N, Q, mxu, tol):
    """y_diag and the chunk-end states, and the gradients of a weighted sum
    of both with respect to C, B, a and x·dt, kernel (fwd and bwd,
    interpret mode) against the XLA reference.  f32 products agree to 2e-5
    of the largest entry (the tile sums in another order); bf16 operands,
    as compiled for the TPU, to 2e-2."""
    assert ssd_ops.supports(Q, N, H, P)
    args = _ssd_inputs(rng, B, S, H, P, N, Q)
    w_y = jnp.asarray(rng.normal(size=args[3].shape), jnp.float32)
    w_s = jnp.asarray(rng.normal(size=(B, S // Q, H, P, N)), jnp.float32)
    kern = lambda *v: ssd_ops.chunk_terms(*v, scope=ssm.SCOPE_SSD,  # noqa
                                          interpret=True, mxu=mxu)
    for got, want in zip(jax.jit(kern)(*args),
                         jax.jit(ssd_ref.chunk_terms)(*args)):
        _close(got, want, tol)

    def loss(f):
        def weighted(*v):
            y, st = f(*v)
            return jnp.sum(y * w_y) + jnp.sum(st * w_s)
        return jax.jit(jax.grad(weighted, argnums=(0, 1, 2, 3)))

    for got, want in zip(loss(kern)(*args), loss(ssd_ref.chunk_terms)(*args)):
        _close(got, want, tol)


def test_ssd_supports_refuses_unaligned_or_oversize_blocks():
    assert ssd_ops.supports(256, 128, 64, 64)         # mamba2-1.3b
    assert ssd_ops.supports(128, 256, 8, 128)
    assert not ssd_ops.supports(96, 128, 64, 64)      # Q not a lane tile
    assert not ssd_ops.supports(256, 64, 64, 64)      # N not a lane tile
    assert not ssd_ops.supports(256, 128, 3, 64)      # a head pair split
    assert not ssd_ops.supports(256, 128, 8, 12)      # P not in sublanes
    assert not ssd_ops.supports(1024, 128, 64, 64)    # [Q, Q] tiles too big


@pytest.mark.parametrize("tpu,N,path", [(True, 128, "kernel"),
                                        (True, 5, "ref"),
                                        (False, 128, "ref")])
def test_ssd_scan_dispatch(rng, monkeypatch, tpu, N, path):
    """``ssd_scan`` takes the kernel on a TPU where ``supports`` accepts
    the shapes, and ``ref.chunk_terms`` otherwise; both give its result."""
    B, S, H, P, Q = 1, 256, 2, 64, 128
    xh = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, size=(B, S, H)), jnp.float32)
    A_log = jnp.asarray(rng.uniform(-1, 1, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    want = ssm.ssd_scan(xh, dt, A_log, Bm, Cm, Q)
    taken = []
    kernel, reference = ssd_ops.chunk_terms, ssd_ref.chunk_terms
    monkeypatch.setattr(ssd_ops, "on_tpu", lambda: tpu)
    monkeypatch.setattr(ssd_ops, "chunk_terms", lambda *a, **k: (
        taken.append("kernel"), kernel(*a, **k, interpret=True))[1])
    monkeypatch.setattr(ssd_ref, "chunk_terms", lambda *a: (
        taken.append("ref"), reference(*a))[1])
    got = ssm.ssd_scan(xh, dt, A_log, Bm, Cm, Q)
    assert taken == [path]
    for g, w in zip(got, want):
        _close(g, w, 2e-5)


# ---------------------------------------------------------------------------
# fused compressor kernels (repro.kernels.compressor) — bit-identity suite
# ---------------------------------------------------------------------------

def _exact(kernel_pair, ref_pair):
    """Assert (values, bits) bit-identity of a kernel/ref result pair."""
    out_k, bits_k = kernel_pair
    out_r, bits_r = ref_pair
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    assert out_k.dtype == out_r.dtype
    assert float(bits_k) == float(bits_r)


# d = 1, d < lane width, d = lane, d = lane + 1 (odd block), d not a
# multiple of 128, multi-row, multi-dim
EDGE_SHAPES = [(1,), (5,), (128,), (129,), (1000,), (33, 7), (4, 5, 6)]


@pytest.mark.parametrize("shape", EDGE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s", [1.0, 127.0])
def test_fused_dither_matches_ref(rng, shape, dtype, s):
    x = jnp.asarray(rng.normal(size=shape) * 10, dtype)
    key = jax.random.key(int(np.prod(shape)))
    _exact(fused_dither(key, x, s), fused_dither_ref(key, x, s))


@pytest.mark.parametrize("shape", EDGE_SHAPES)
@pytest.mark.parametrize("frac", [0.01, 0.5, 1.0])
def test_fused_topk_matches_ref(rng, shape, frac):
    x = jnp.asarray(rng.normal(size=shape) * 10, jnp.float32)
    key = jax.random.key(0)
    _exact(fused_topk(key, x, frac), fused_topk_ref(key, x, frac))


def test_fused_topk_ties_and_rounding_edges(rng):
    """Integer-valued magnitudes make massive tie groups, and frac·d is
    placed exactly at/around ceil() boundaries — the reference's
    lowest-index tie-breaking and k = ceil(frac·d) rounding must be
    reproduced exactly."""
    key = jax.random.key(0)
    for d, frac in [(7, 1 / 7), (7, 2 / 7 - 1e-7), (12, 0.25),
                    (12, 0.2500001), (128, 1.0), (129, 0.5), (200, 0.015)]:
        x = jnp.asarray(rng.integers(-3, 4, size=d), jnp.float32)
        _exact(fused_topk(key, x, frac), fused_topk_ref(key, x, frac))


def test_fused_zero_vector(rng):
    """All-zero input: dither's norm guard (norm=0 -> 1) and top-k's
    all-tied-at-zero threshold both match the reference exactly."""
    z = jnp.zeros((257,), jnp.float32)
    key = jax.random.key(3)
    _exact(fused_dither(key, z, 63.0), fused_dither_ref(key, z, 63.0))
    _exact(fused_topk(key, z, 0.25), fused_topk_ref(key, z, 0.25))


def test_fused_nonfinite_policy(rng):
    """Pinned inf/nan policy — identical to the jnp reference:

    * dither: a non-finite coordinate poisons the GLOBAL ∞-norm, so every
      output element becomes NaN (one bad coordinate poisons the whole
      message — callers must sanitize upstream);
    * top-k: |NaN|'s bit pattern sorts above +inf (matching jnp.sort's
      NaN-last ascending order), so non-finite coordinates occupy top
      slots and displace finite values — but NaN itself is never
      emitted, because it fails both the `>` and `==` threshold tests.
    """
    key = jax.random.key(7)
    xi = jnp.asarray([1.0, np.inf, 3.0, -2.0, 0.5, 0.0, 7.0, -np.inf],
                     jnp.float32)
    xn = jnp.asarray([1.0, np.nan, 3.0, -2.0], jnp.float32)
    for x in (xi, xn):
        out_k, bits_k = fused_dither(key, x, 15.0)
        out_r, bits_r = fused_dither_ref(key, x, 15.0)
        assert bool(jnp.all(jnp.isnan(out_k)))           # poisons all
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
        assert float(bits_k) == float(bits_r)
        t_k, tb_k = fused_topk(key, x, 0.5)
        t_r, tb_r = fused_topk_ref(key, x, 0.5)
        np.testing.assert_array_equal(np.asarray(t_k), np.asarray(t_r))
        assert float(tb_k) == float(tb_r)
    # k=2 over [1, nan, 3, -2]: NaN claims a slot (it outranks 3 in the
    # threshold search) yet is dropped by the keep mask, so only 3.0
    # survives — one slot is burned, exactly as in the reference.
    kept, _ = fused_topk(key, xn, 0.5)
    np.testing.assert_array_equal(np.asarray(kept),
                                  np.asarray([0.0, 0.0, 3.0, 0.0]))


@pytest.mark.parametrize("s", [1.0, 64.0, 1000.0])
@pytest.mark.parametrize("d", [1, 129, 10_000])
def test_bits_only_kernels_match_spec_bits(s, d):
    assert float(dither_bits_fused(s, d)) == float(dither_bits_ref(s, d))
    for frac in (0.01, 0.37, 1.0):
        assert (float(topk_bits_fused(frac, d))
                == float(topk_bits_ref(frac, d)))


@pytest.mark.parametrize("name", ["identity", "dither64", "natural",
                                  "topk0.1"])
def test_compress_dispatch_kernel_equals_jnp(rng, name):
    """`compress`/`spec_bits` with use_kernel=True are drop-ins for the
    jnp path: exact values and exact bits, eagerly and under jit."""
    spec = compressors.make_spec(name)
    x = jnp.asarray(rng.normal(size=(300,)), jnp.float32)
    key = jax.random.key(1)
    a = compressors.compress(spec, key, x, False)
    b = compressors.compress(spec, key, x, True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (float(compressors.spec_bits(spec, x.size, False))
            == float(compressors.spec_bits(spec, x.size, True)))
    f0 = jax.jit(lambda k, x: compressors.compress(spec, k, x, False))
    f1 = jax.jit(lambda k, x: compressors.compress(spec, k, x, True))
    np.testing.assert_array_equal(np.asarray(f0(key, x)),
                                  np.asarray(f1(key, x)))


def test_fused_vmap_jit_switch(rng):
    """The kernel path survives the sweep engine's composition: lax.switch
    dispatch inside jit(vmap(...)) over a batch of keys — gridless
    kernels are vmap-safe (no program_id to shift)."""
    xs = jnp.asarray(rng.normal(size=(8, 200)), jnp.float32)
    keys = jax.random.split(jax.random.key(3), 8)
    for name in ("dither64", "topk0.25"):
        spec = compressors.make_spec(name)
        f0 = jax.jit(jax.vmap(
            lambda k, x: compressors.compress(spec, k, x, False)))
        f1 = jax.jit(jax.vmap(
            lambda k, x: compressors.compress(spec, k, x, True)))
        np.testing.assert_array_equal(np.asarray(f0(keys, xs)),
                                      np.asarray(f1(keys, xs)))


def test_oversize_and_unsupported_dtype_fall_back(rng, monkeypatch):
    """Tensors the kernels reject (too large for one VMEM block, or a
    non-float dtype) silently keep the jnp path — and stay exact,
    because the fallback IS the reference."""
    monkeypatch.setattr(comp_ops, "MAX_FUSED_ELEMS", 64)
    x = jnp.asarray(rng.normal(size=(200,)), jnp.float32)
    key = jax.random.key(2)
    assert not comp_ops.supports(x)
    spec = compressors.make_spec("dither64")
    a = compressors.compress(spec, key, x, False)
    b = compressors.compress(spec, key, x, True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    small = x[:32]
    assert comp_ops.supports(small)
    assert not comp_ops.supports(small.astype(jnp.int32))
    assert not comp_ops.supports(jnp.zeros((0,), jnp.float32))


if HAVE_HYPOTHESIS:
    finite_vec = st.lists(
        st.floats(-1e6, 1e6, allow_nan=False, width=32),
        min_size=1, max_size=300).map(
            lambda xs: np.asarray(xs, np.float32))

    @settings(max_examples=30, deadline=None)
    @given(finite_vec, st.sampled_from([1, 3, 7, 15, 63, 127, 511]),
           st.integers(0, 2**31 - 1))
    def test_fused_dither_property(x, s, seed):
        key = jax.random.key(seed)
        x = jnp.asarray(x)
        out_k, bits_k = fused_dither(key, x, float(s))
        out_r, bits_r = fused_dither_ref(key, x, float(s))
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
        assert float(bits_k) == float(bits_r)

    @settings(max_examples=30, deadline=None)
    @given(finite_vec, st.floats(1e-3, 1.0, allow_nan=False),
           st.booleans())
    def test_fused_topk_property(x, frac, quantize_ties):
        if quantize_ties:                       # force big tie groups
            x = np.round(x / (np.max(np.abs(x)) + 1e-9) * 3)
        key = jax.random.key(0)
        x = jnp.asarray(x)
        out_k, bits_k = fused_topk(key, x, float(frac))
        out_r, bits_r = fused_topk_ref(key, x, float(frac))
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
        assert float(bits_k) == float(bits_r)
else:
    @pytest.mark.skip(reason="hypothesis not installed (requirements-dev)")
    def test_fused_dither_property():
        pass

    @pytest.mark.skip(reason="hypothesis not installed (requirements-dev)")
    def test_fused_topk_property():
        pass
