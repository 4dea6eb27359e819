"""Pallas TPU kernels: the fused compressor hot path of FLECS-CGD.

Every method in the registry runs a compressor over every message every
round (FedNL's inner loop IS the compressor), so the chain the jnp path
dispatches — norm reduction, stochastic rounding, bit-ledger pricing,
top-k threshold selection — is the memory-bound hot spot at DL scale.
These kernels fuse each family's chain into ONE pass over the tensor
while it is VMEM-resident:

* ``_fused_dither_kernel`` — ∞-norm reduction + error-variance-safe
  stochastic rounding (the paper's unbiased dithering: round up with
  probability equal to the fractional level, so E[Q(x)] = x) + the
  ⌈log2(2s+1)⌉·d payload-bit count, one launch, two outputs.
* ``_fused_topk_kernel`` — exact traced-k threshold selection + gather +
  the dimension-aware (32 + ⌈log2 d⌉)·⌈frac·d⌉ bit count.  The k-th
  largest magnitude is found WITHOUT a sort: ``bitcast(|x|, int32)`` is
  order-preserving for non-negative floats (NaN's 0x7FC00000 pattern
  sorts above +inf, matching ``jnp.sort``'s NaN-last order), so a
  31-step MSB-first greedy search recovers the exact threshold bit
  pattern in O(d log W) VPU work and O(1) scratch where the jnp
  reference sorts.
* ``_dither_bits_kernel`` / ``_topk_bits_kernel`` — the bits-only
  ledger queries (``spec_bits``'s branch formulas) as kernels, so the
  fused price and the standalone price come from the same expressions
  (``_dither_bits_expr`` / ``_topk_bits_expr`` are shared).

Differential contract (pinned bit-for-bit by tests/test_kernels.py):
each kernel replicates the corresponding ``repro.core.compressors``
expression op-for-op — same reduction, same expression order, same
rounding — so under a consistent evaluation context (both eager or both
inside one jit) kernel and jnp path return IDENTICAL bits.  Comparing a
jitted program against an eager one is outside the contract: XLA fusion
may legally perturb last-ulp results of either path.

All kernels are gridless — the wrapper (ops.py) pads the flattened
tensor into one [rows, 128] VMEM block and there is no ``pl.program_id``
— which keeps them safe under ``jax.vmap``: pallas batches a kernel by
prepending a grid dimension, which would shift any program_id indexing.
Traced operands (s, frac, d) enter as (1, 1) f32 arrays, so compressor
levels and fractions stay sweepable grid axes through the kernel path.
Every scalar operand and the (1, 1) bit-count outputs live in SMEM: the
TPU lowering cannot store a scalar to VMEM, and a 2-D (1, 1) block stays
legal when vmap prepends a batch dimension to it.
Zero padding is harmless by construction: pads cannot change a max-abs
reduction, dither maps them to 0, and the top-k tie budget never reaches
them (k counts real elements only, ties at a zero threshold keep pads at
their already-zero value).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.numerics import ceil_log2

_VMEM = pl.BlockSpec(memory_space=pltpu.VMEM)
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _dither_bits_expr(s, d):
    """spec_bits' dither branch: ⌈log2(2s+1)⌉ bits/value × d values."""
    return ceil_log2(2.0 * s + 1.0) * d


def _topk_bits_expr(frac, d):
    """spec_bits' top-k branch: ⌈frac·d⌉ kept values, each a 32-bit
    payload plus a ⌈log2 d⌉-bit index (dimension-aware)."""
    kept = jnp.clip(jnp.ceil(frac * d), 1.0, d)
    return kept * (32.0 + ceil_log2(jnp.maximum(d, 1.0)))


# ---------------------------------------------------------------------------
# fused dither: quantize + stochastic rounding + bit count
# ---------------------------------------------------------------------------

def _fused_dither_kernel(x_ref, u_ref, s_ref, out_ref, bits_ref, *, d: int):
    """One pass: ∞-norm, dither to s levels with uniforms u, price bits.

    Mirrors ``compressors._dither`` expression-for-expression; ``d`` is
    the REAL element count (pads excluded) so the ledger is exact."""
    x = x_ref[...]
    s = s_ref[0, 0]
    norm = jnp.max(jnp.abs(x))                   # pads are 0: never the max
    norm = jnp.where(norm == 0, 1.0, norm)
    y = jnp.abs(x) / norm * s                    # in [0, s]
    lo = jnp.floor(y)
    p = y - lo                                   # P(round up)
    level = lo + (u_ref[...] < p)
    out_ref[...] = jnp.sign(x) * level * norm / s
    bits_ref[0, 0] = _dither_bits_expr(s, jnp.float32(d))


def fused_dither_call(x2, u2, s1, *, d: int, interpret: bool):
    """Launch the fused dither kernel on a padded [R, 128] block.

    Returns (quantized [R, 128] f32, payload bits (1, 1) f32)."""
    R, C = x2.shape
    return pl.pallas_call(
        functools.partial(_fused_dither_kernel, d=d),
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        in_specs=[_VMEM, _VMEM, _SMEM],
        out_specs=[_VMEM, _SMEM],
        interpret=interpret,
    )(x2, u2, s1)


# ---------------------------------------------------------------------------
# fused top-k: threshold selection + gather + bit count
# ---------------------------------------------------------------------------

def _fused_topk_kernel(x_ref, frac_ref, out_ref, bits_ref, *, d: int):
    """One pass: exact traced-k selection without a sort.

    The MSB-first greedy search builds the k-th-largest |x| bit pattern
    one bit at a time: a candidate bit survives iff at least k magnitudes
    still compare >= the candidate threshold.  The float-domain keep mask
    then mirrors ``compressors._topk`` exactly: everything strictly above
    the threshold, plus the lowest-index ties up to the remaining budget
    k - n_above.  The tie cut is a second greedy search, over the flat
    index (row·128 + lane, the flattened order of the real elements): the
    largest cut C with #(ties at index < C) <= budget keeps exactly the
    ties whose 1-based rank is within the budget — the reference's cumsum
    rank, without a cumsum (which the TPU lowering lacks).  Pads are
    zeros, and the tie budget can reach them only when the threshold is
    itself 0 AND every real zero is kept — where keeping a pad writes 0,
    a no-op."""
    x = x_ref[...]
    frac = frac_ref[0, 0]
    ax = jnp.abs(x)
    k = jnp.clip(jnp.ceil(frac * d).astype(jnp.int32), 1, d)
    bits = jax.lax.bitcast_convert_type(ax, jnp.int32)

    def grow(j, t):
        cand = t | (jnp.int32(1) << (30 - j))
        count = jnp.sum((bits >= cand).astype(jnp.int32))
        return jnp.where(count >= k, cand, t)

    # NB: "pat" not "bits" — this int32 is a float BIT PATTERN for the
    # threshold search, not a wire-cost ledger (R3 guards the latter).
    thresh_pat = jax.lax.fori_loop(0, 31, grow, jnp.int32(0))
    # bitcast as a vector: the TPU lowering bitcasts vectors, not scalars
    thresh = jax.lax.bitcast_convert_type(
        jnp.broadcast_to(thresh_pat, x.shape), jnp.float32)
    above = ax > thresh
    n_above = jnp.sum(above.astype(jnp.int32))
    ties = ax == thresh
    budget = k - n_above
    rows, lanes = x.shape
    flat = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1))
    top = (rows * lanes).bit_length()            # cut ranges over [0, R·128]

    def cut(j, c):
        cand = c | (jnp.int32(1) << (top - 1 - j))
        count = jnp.sum((ties & (flat < cand)).astype(jnp.int32))
        return jnp.where(count <= budget, cand, c)

    tie_cut = jax.lax.fori_loop(0, top, cut, jnp.int32(0))
    keep = above | (ties & (flat < tie_cut))
    out_ref[...] = jnp.where(keep, x, jnp.zeros((), x.dtype))
    bits_ref[0, 0] = _topk_bits_expr(frac, jnp.float32(d))


def fused_topk_call(x2, frac1, *, d: int, interpret: bool):
    """Launch the fused top-k kernel on a padded [R, 128] block.

    Returns (sparsified [R, 128] f32, payload bits (1, 1) f32)."""
    R, C = x2.shape
    return pl.pallas_call(
        functools.partial(_fused_topk_kernel, d=d),
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        in_specs=[_VMEM, _SMEM],
        out_specs=[_VMEM, _SMEM],
        interpret=interpret,
    )(x2, frac1)


# ---------------------------------------------------------------------------
# bits-only ledger kernels (spec_bits' branch formulas, traced d)
# ---------------------------------------------------------------------------

def _dither_bits_kernel(s_ref, d_ref, bits_ref):
    bits_ref[0, 0] = _dither_bits_expr(s_ref[0, 0], d_ref[0, 0])


def _topk_bits_kernel(frac_ref, d_ref, bits_ref):
    bits_ref[0, 0] = _topk_bits_expr(frac_ref[0, 0], d_ref[0, 0])


def dither_bits_call(s1, d1, *, interpret: bool):
    return pl.pallas_call(
        _dither_bits_kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        in_specs=[_SMEM, _SMEM],
        out_specs=_SMEM,
        interpret=interpret,
    )(s1, d1)


def topk_bits_call(frac1, d1, *, interpret: bool):
    return pl.pallas_call(
        _topk_bits_kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        in_specs=[_SMEM, _SMEM],
        out_specs=_SMEM,
        interpret=interpret,
    )(frac1, d1)
