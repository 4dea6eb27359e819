"""Pallas TPU kernels: the SSD chunk terms, forward and backward.

Mamba-2's chunked SSD [arXiv:2405.21060] splits y into a diagonal-block
term and a part carried between chunks.  Over one chunk of Q positions,
with ``CB = C Bᵀ`` (one group: shared by the heads) and ``cs`` the f32
cumsum of ``a = dt·A`` over the chunk, per head,

    y_diag[l] = sum_{s <= l} CB[l, s] * exp(cs[l] - cs[s]) * xdt[s],
    state[p, n] = sum_s exp(cs[Q-1] - cs[s]) * xdt[s, p] * B[s, n],

the chunk's own output and the state it hands to the next chunk.  The
recurrence over chunks and the output it carries in (``y_off``) stay in
XLA (``models/ssm.py``).  XLA materialises the decay ``exp(cs[l] - cs[s])``
and its gradients as ``[B, nc, H, Q, Q]`` tensors in HBM; here each
``[Q, Q]`` tile lives only in VMEM, and ``xdt`` is read once for both
terms.

Layouts are XLA's, so that no operand or result needs a relayout copy:
positions are minor.  ``xdtᵀ`` and its gradient are ``[B, H·P, S]``, as
the depthwise conv lays out the activation; ``y_diagᵀ``, its cotangent
and the state are ``[B, nc, H·P, ·]``, as XLA lays out ``y_off`` and the
recurrence per chunk.  Grid ``(B, nc, H·P / W)``: one step per batch row,
chunk and block of W rows, ``W / P`` whole heads; a head is a static slice
of P sublanes.  The head-block axis is innermost and sequential: ``CBᵀ``
is computed once per chunk into scratch, and the backward's per-chunk
outputs stay resident across it.

Everything runs in the transposed orientation ``[s, l]``: ``Mᵀ = CBᵀ ∘
Lᵀ`` with ``Lᵀ[s, l] = exp(cs[l] - cs[s])`` masked to ``l >= s``, and the
decay to the chunk's end ``d[s] = exp(cs[Q-1] - cs[s])`` is a row.  ``cs``
comes in two layouts, ``[B, H, S]`` (a head's row, indexed by lanes) and
``[B, S, H]`` (its column, indexed by sublanes); a head's row or column is
picked by a masked reduction, which is exact.

Forward, per head: ``y_diagᵀ = xdtᵀ Mᵀ`` and ``state = (xdtᵀ ∘ d) B``.

Backward, per head, from ``dyᵀ`` and ``dstate``: ``Mᵀ`` is recomputed,
then ``dxdtᵀ = dyᵀ M + (dstate Bᵀ) ∘ d``, ``dMᵀ = xdt dyᵀ``,
``dCBᵀ += dMᵀ ∘ Lᵀ``, ``dB += (xdtᵀ ∘ d)ᵀ dstate`` and
``dsegᵀ = dMᵀ ∘ Mᵀ``.  Since ``seg[l, s] = cs[l] - cs[s]``, the gradient
reaches the row layout of ``cs`` as the column sums of ``dsegᵀ`` and the
column layout as minus its row sums; the decay's, ``dd = Σ_p xdtᵀ ∘
(dstate Bᵀ) ∘ d``, reaches ``cs[s]`` negated and ``cs[Q-1]`` summed.  ``dC``
and ``dB`` from ``dCBᵀ``, and the reverse cumsum, are left to XLA (``ops``).

Precision: every matrix product takes ``mxu`` operands and accumulates in
f32 (``ops`` picks bf16 compiled for the TPU, XLA's DEFAULT there, and f32
in interpret mode, XLA's DEFAULT on the CPU); every elementwise step
(difference, exp, mask, products, sums) is f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))          # contract the last dims: a bᵀ
_TN = (((0,), (0,)), ((), ()))          # contract the first dims: aᵀ b
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _head_row(csr, h):
    """Row ``h`` of a [H, Q] block, as [1, Q]."""
    return jnp.sum(jnp.where(_iota(csr.shape, 0) == h, csr, 0.0), axis=0,
                   keepdims=True)


def _head_col(csc, h):
    """Column ``h`` of a [Q, H] block, as [Q, 1]."""
    return jnp.sum(jnp.where(_iota(csc.shape, 1) == h, csc, 0.0), axis=1,
                   keepdims=True)


def _mm(a, b, dims, mxu):
    return jax.lax.dot_general(a.astype(mxu), b.astype(mxu), dims,
                               preferred_element_type=F32)


def _heads(c_ref, b_ref, csr_ref, csc_ref, x_ref, cbt_ref, P, mxu):
    """CBᵀ into scratch at a chunk's first head block; then, per head of
    the block: its row slice, ``h``, ``Lᵀ``, ``Mᵀ`` and ``d``."""
    @pl.when(pl.program_id(2) == 0)
    def _cbt():
        cbt_ref[...] = _mm(b_ref[0], c_ref[0], _NT, mxu)

    cbt = cbt_ref[...]
    Q = cbt.shape[0]
    causal_t = _iota((Q, Q), 1) >= _iota((Q, Q), 0)
    csr, csc = csr_ref[0], csc_ref[0]
    hb = x_ref.shape[1] // P
    for k in range(hb):
        h = pl.program_id(2) * hb + k
        row = _head_row(csr, h)                               # [1, l]
        l_t = jnp.exp(jnp.where(causal_t, row - _head_col(csc, h),
                                -jnp.inf))                    # [s, l]
        yield (slice(k * P, (k + 1) * P), h, l_t, cbt * l_t,
               jnp.exp(row[:, Q - 1:] - row))                 # d: [1, s]


def _fwd_kernel(c_ref, b_ref, csr_ref, csc_ref, x_ref, y_ref, st_ref,
                cbt_ref, *, P: int, mxu):
    for rows, _h, _l_t, m_t, d in _heads(
            c_ref, b_ref, csr_ref, csc_ref, x_ref, cbt_ref, P, mxu):
        x = x_ref[0, rows, :]                                 # [p, s]
        y_ref[0, 0, rows, :] = _mm(x, m_t, (((1,), (0,)), ((), ())), mxu)
        st_ref[0, 0, rows, :] = _mm(x * d, b_ref[0],
                                    (((1,), (0,)), ((), ())), mxu)


def _bwd_kernel(c_ref, b_ref, csr_ref, csc_ref, x_ref, dy_ref, dst_ref,
                dx_ref, dcbt_ref, db_ref, dcsr_ref, dcsc_ref, cbt_ref, *,
                P: int, mxu):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        for ref in (dcbt_ref, db_ref, dcsr_ref, dcsc_ref):
            ref[...] = jnp.zeros_like(ref)

    csr, csc = csr_ref[0], csc_ref[0]
    row_h, col_h = _iota(csr.shape, 0), _iota(csc.shape, 1)
    last = _iota(csr.shape, 1) == csr.shape[1] - 1
    dcbt, db = dcbt_ref[0], db_ref[0]
    dcsr, dcsc = dcsr_ref[0], dcsc_ref[0]
    for rows, h, l_t, m_t, d in _heads(
            c_ref, b_ref, csr_ref, csc_ref, x_ref, cbt_ref, P, mxu):
        x, dy = x_ref[0, rows, :], dy_ref[0, 0, rows, :]
        dst = dst_ref[0, 0, rows, :]
        g = _mm(dst, b_ref[0], _NT, mxu)                      # [p, s]
        dx_ref[0, rows, :] = _mm(dy, m_t, _NT, mxu) + g * d
        dm_t = _mm(x, dy, _TN, mxu)                           # [s, l]
        dcbt = dcbt + dm_t * l_t
        db = db + _mm(x * d, dst, _TN, mxu)
        dseg_t = dm_t * m_t
        dd = jnp.sum(x * g, axis=0, keepdims=True) * d        # [1, s]
        dcsr = dcsr + jnp.where(
            row_h == h, jnp.sum(dseg_t, axis=0, keepdims=True) - dd
            + jnp.where(last, jnp.sum(dd), 0.0), 0.0)
        dcsc = dcsc - jnp.where(col_h == h,
                                jnp.sum(dseg_t, axis=1, keepdims=True), 0.0)
    dcbt_ref[0] = dcbt
    db_ref[0] = db
    dcsr_ref[0] = dcsr
    dcsc_ref[0] = dcsc


def _specs(Q: int, N: int, H: int, W: int):
    """Blocks of C, B [B, S, N], cs [B, H, S], cs [B, S, H], W rows of
    xdtᵀ [B, H·P, S], of y_diagᵀ [B, nc, H·P, Q] and of a state
    [B, nc, H·P, N]."""
    return (pl.BlockSpec((1, Q, N), lambda b, c, j: (b, c, 0)),
            pl.BlockSpec((1, H, Q), lambda b, c, j: (b, 0, c)),
            pl.BlockSpec((1, Q, H), lambda b, c, j: (b, c, 0)),
            pl.BlockSpec((1, W, Q), lambda b, c, j: (b, j, c)),
            pl.BlockSpec((1, 1, W, Q), lambda b, c, j: (b, c, j, 0)),
            pl.BlockSpec((1, 1, W, N), lambda b, c, j: (b, c, j, 0)))


def chunk_terms_fwd(C, B, cs_row, cs_col, xdt_t, *, Q: int, P: int, W: int,
                    mxu, interpret: bool):
    """(y_diagᵀ [B, nc, H·P, Q], state [B, nc, H·P, N]) from C, B
    [B, S, N], cs_row [B, H, S], cs_col [B, S, H] and xdtᵀ [B, H·P, S],
    all f32."""
    Bb, S, N = C.shape
    H, HP = cs_row.shape[1], xdt_t.shape[1]
    cn, crow, ccol, xs, ys, st = _specs(Q, N, H, W)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, mxu=mxu),
        grid=(Bb, S // Q, HP // W),
        in_specs=[cn, cn, crow, ccol, xs],
        out_specs=[ys, st],
        out_shape=[jax.ShapeDtypeStruct((Bb, S // Q, HP, Q), F32),
                   jax.ShapeDtypeStruct((Bb, S // Q, HP, N), F32)],
        scratch_shapes=[pltpu.VMEM((Q, Q), F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(C, B, cs_row, cs_col, xdt_t)


def chunk_terms_bwd(C, B, cs_row, cs_col, xdt_t, dy_t, dstate, *, Q: int,
                    P: int, W: int, mxu, interpret: bool):
    """(dxdtᵀ [B, H·P, S], dCBᵀ [B, S, Q] (chunk c's [s, l] tile in rows
    c·Q…), dB of the state [B, S, N], dcs_row [B, H, S], dcs_col
    [B, S, H]) from the forward's inputs, dy_diagᵀ [B, nc, H·P, Q] and
    dstate [B, nc, H·P, N]."""
    Bb, S, N = C.shape
    H, HP = cs_row.shape[1], xdt_t.shape[1]
    cn, crow, ccol, xs, ys, st = _specs(Q, N, H, W)
    tile = pl.BlockSpec((1, Q, Q), lambda b, c, j: (b, c, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, mxu=mxu),
        grid=(Bb, S // Q, HP // W),
        in_specs=[cn, cn, crow, ccol, xs, ys, st],
        out_specs=[xs, tile, cn, crow, ccol],
        out_shape=[jax.ShapeDtypeStruct((Bb, HP, S), F32),
                   jax.ShapeDtypeStruct((Bb, S, Q), F32),
                   jax.ShapeDtypeStruct((Bb, S, N), F32),
                   jax.ShapeDtypeStruct((Bb, H, S), F32),
                   jax.ShapeDtypeStruct((Bb, S, H), F32)],
        scratch_shapes=[pltpu.VMEM((Q, Q), F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(C, B, cs_row, cs_col, xdt_t, dy_t, dstate)
