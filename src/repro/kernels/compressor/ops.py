"""Drop-in entry points for the fused compressor kernels.

Arbitrary-shape tensors are flattened and zero-padded into the kernels'
gridless [rows, 128] VMEM layout; dither uniforms are drawn OUTSIDE the
kernel with the exact key consumption of the jnp reference path
(``jax.random.uniform(key, x.shape)``), so static runs, sweeps, and
kernel runs share one key stream and the two paths are interchangeable
mid-run.

``interpret=None`` (the default) resolves to interpret mode off-TPU, so
tier-1 tests and CI execute the kernels as ordinary traced jax ops on
CPU while a TPU deployment compiles the real thing from the same call
sites (``compressors.compress(..., use_kernel=True)``).

``supports(x)`` is the STATIC eligibility gate ``compressors`` consults:
the documented size and dtype limit of the kernels.  Shapes/dtypes it
rejects keep the jnp path, which the kernels are bit-identical to — so
the fallback is numerics-free by construction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.compressor.compressor import (dither_bits_call,
                                                 fused_dither_call,
                                                 fused_topk_call,
                                                 topk_bits_call)

_LANES = 128

#: Largest element count the gridless single-block kernels accept: the
#: whole padded [rows, 128] f32 block (plus uniforms + output) must sit in
#: the kernel's scoped VMEM (16 MiB by default on TPU v5e).  Vmapped over
#: workers, pallas pipelines the batch and double-buffers every block, so
#: the fused dither kernel at 2^19 elements already exceeds it there; 2^18
#: compiles for v5e unbatched and vmapped (tests/test_tpu_compile.py).
MAX_FUSED_ELEMS = 1 << 18

#: Dtypes the kernels accept: computed in f32 exactly like the jnp
#: reference (`_dither` upcasts to f32 internally); f64 would lose
#: precision against a native-dtype reference, so it stays on jnp.
_SUPPORTED_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


def _resolve_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _to_rows(x):
    """Flatten + zero-pad to the kernels' [rows, 128] layout."""
    n = x.size
    rows = -(-n // _LANES)
    flat = jnp.pad(x.reshape(-1), (0, rows * _LANES - n))
    return flat.reshape(rows, _LANES), n


def supports(x) -> bool:
    """Static kernel-path eligibility of a concrete-shape tensor."""
    return (0 < x.size <= MAX_FUSED_ELEMS
            and x.dtype in _SUPPORTED_DTYPES)


def fused_dither(key, x, s, *, interpret=None):
    """Fused (Q(x), payload bits) — bit-identical to the pair
    ``(_dither(key, x, s), spec_bits(dither_spec(s), x.size))``."""
    u = jax.random.uniform(key, x.shape)         # == _dither's draw
    x2, n = _to_rows(x.astype(jnp.float32))
    u2, _ = _to_rows(u)
    s1 = jnp.asarray(s, jnp.float32).reshape(1, 1)
    out2, bits = fused_dither_call(
        x2, u2, s1, d=n, interpret=_resolve_interpret(interpret))
    out = out2.reshape(-1)[:n].reshape(x.shape).astype(x.dtype)
    return out, bits[0, 0]


def fused_topk(key, x, frac, *, interpret=None):
    """Fused (top-k(x), payload bits) — bit-identical to the pair
    ``(_topk(key, x, frac), spec_bits(topk_spec(frac), x.size))``.
    ``key`` is unused (top-k is deterministic) but kept for key-stream
    parity with the reference signature."""
    del key                                      # parity with _topk
    x2, n = _to_rows(x.astype(jnp.float32))
    f1 = jnp.asarray(frac, jnp.float32).reshape(1, 1)
    out2, bits = fused_topk_call(
        x2, f1, d=n, interpret=_resolve_interpret(interpret))
    out = out2.reshape(-1)[:n].reshape(x.shape).astype(x.dtype)
    return out, bits[0, 0]


def dither_bits_fused(s, d, *, interpret=None):
    """Bits-only ledger query: ``spec_bits``'s dither branch as a kernel
    (s and d both traced)."""
    s1 = jnp.asarray(s, jnp.float32).reshape(1, 1)
    d1 = jnp.asarray(d, jnp.float32).reshape(1, 1)
    return dither_bits_call(
        s1, d1, interpret=_resolve_interpret(interpret))[0, 0]


def topk_bits_fused(frac, d, *, interpret=None):
    """Bits-only ledger query: ``spec_bits``'s top-k branch as a kernel
    (frac and d both traced)."""
    f1 = jnp.asarray(frac, jnp.float32).reshape(1, 1)
    d1 = jnp.asarray(d, jnp.float32).reshape(1, 1)
    return topk_bits_call(
        f1, d1, interpret=_resolve_interpret(interpret))[0, 0]
