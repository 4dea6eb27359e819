"""The SSD chunk terms through the Pallas kernels, with their gradient.

``chunk_terms`` takes the chunked layouts ``models/ssm.py`` ``ssd_scan``
holds, and returns what ``ref.chunk_terms`` returns: the diagonal-block
output and the chunk-end states.  It computes the chunk-local cumsum
``cs`` of ``a`` in XLA (the same ``jnp.cumsum`` the reference takes), lays
``cs`` out as rows ``[B, H, S]`` and columns ``[B, S, H]``, ``xdt`` as
``xdtᵀ`` ``[B, H·P, S]`` and ``y_diag`` as ``[B, nc, H·P, Q]`` (positions
minor, as XLA lays these out around the kernel, so the transposes are
relabellings, not copies), and calls the forward kernel under a
``jax.custom_vjp`` whose backward is the backward kernel.  The backward
keeps no ``[Q, Q]`` residual: it takes the forward's inputs and recomputes
the decay.  ``dC = dCB B`` and ``dB = dCBᵀ C`` are XLA products at DEFAULT
precision, and the reverse cumsum and the layout transposes are XLA's
autodiff of this function.

The backward runs under ``scope`` (the caller's, ``ssm.ssd``) of its own,
so a profile finds its kernel under the scope whatever name stack JAX's
transpose of the ``custom_vjp`` gives it.

``supports(Q, N, H, P)`` is the static gate: Q and N multiples of 128, P
a multiple of 8 and H·P of the W rows a grid step takes (whole heads),
and the blocks within ``VMEM_BUDGET``.  ``applies`` adds the
backend (``on_tpu()``) and whether Mosaic can lower the call where it is
traced; ``ssd_scan`` calls the kernel where it holds and
``ref.chunk_terms`` elsewhere.  Inside a shard_map that leaves mesh axes
automatic (the DL trainer's, data manual and model automatic), the call
runs in a shard_map of its own over those axes, replicated over them.

``interpret=None`` compiles the kernels with Mosaic on a TPU and runs them
in interpret mode elsewhere.  The products take bf16 operands when
compiled (XLA's DEFAULT on the TPU) and f32 when interpreted (XLA's
DEFAULT on the CPU); ``mxu`` overrides that.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.compat import shard_map
from repro.kernels.ssd.ssd import chunk_terms_bwd, chunk_terms_fwd

_LANES = 128
#: Scoped VMEM a kernel gets by default on a TPU v5e; ``supports`` keeps
#: the larger kernel's estimate (``vmem_bytes``) within it.
VMEM_BUDGET = 16 << 20


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def applies(Q: int, N: int, H: int, P: int) -> bool:
    """Where ``ssd_scan`` takes the kernel: on a TPU, for shapes it
    ``supports``, and where Mosaic can lower its call: inside a mesh context
    (``chunk_terms`` makes the axes that are not manual manual) or in a
    process with one device (a jit over several devices with no mesh
    context would have to partition the kernel)."""
    return (on_tpu() and supports(Q, N, H, P)
            and (not jax.sharding.get_abstract_mesh().empty
                 or jax.device_count() == 1))


def block_rows(P: int) -> int:
    """W: the rows of ``xdtᵀ`` a grid step takes, whole heads of P rows,
    128 of them where P divides 128."""
    return P * max(1, _LANES // P)


def vmem_bytes(Q: int, N: int, H: int, W: int) -> int:
    """f32 bytes of the backward kernel (the larger): C, B, the two cs
    layouts, the xdtᵀ, dyᵀ and dstate blocks, double-buffered; the dxdtᵀ,
    dCBᵀ, dB and dcs output blocks, double-buffered; CBᵀ scratch; eight
    [Q, Q] and six [W, Q] temporaries.  Lanes pad to 128, sublanes to 8."""
    pad = lambda n, m: -(-n // m) * m                          # noqa: E731
    tiles = lambda r, c: pad(r, 8) * pad(c, _LANES)           # noqa: E731
    blocks = (2 * tiles(Q, N) + tiles(H, Q) + tiles(Q, H) + 2 * tiles(W, Q)
              + tiles(W, N))
    outs = (tiles(W, Q) + tiles(Q, Q) + tiles(Q, N) + tiles(H, Q)
            + tiles(Q, H))
    temps = 8 * tiles(Q, Q) + 6 * tiles(W, Q)
    return 4 * (2 * blocks + 2 * outs + tiles(Q, Q) + temps)


def supports(Q: int, N: int, H: int, P: int) -> bool:
    W = block_rows(P)
    return (Q % _LANES == 0 and N % _LANES == 0 and P % 8 == 0
            and (H * P) % W == 0 and vmem_bytes(Q, N, H, W) <= VMEM_BUDGET)


def chunk_terms(C_c, B_c, a_c, x_c, *, scope: str, interpret=None,
                mxu=None):
    """(y_diag [B, nc, Q, H, P], states [B, nc, H, P, N]) from C_c, B_c
    [B, nc, Q, N], a_c [B, nc, H, Q] and x_c [B, nc, Q, H, P], all f32,
    as ``ref.chunk_terms``; for shapes ``supports`` accepts."""
    Bb, nc, Q, H, P = x_c.shape
    N, S = C_c.shape[-1], nc * Q
    if interpret is None:
        interpret = not on_tpu()
    if mxu is None:
        mxu = jnp.float32 if interpret else jnp.bfloat16
    static = (Q, P, block_rows(P), jnp.dtype(mxu), interpret, scope)

    def call(*operands):
        return _chunk_terms(*operands, static)

    mesh = jax.sharding.get_abstract_mesh()
    auto = set(mesh.axis_names) - set(mesh.manual_axes)
    if auto:            # Mosaic lowers only where no axis is partitioned
        call = shard_map(call, mesh, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), axis_names=auto)
    cs = jnp.cumsum(a_c, axis=-1)                           # [B,nc,H,Q]
    y_t, states = call(C_c.reshape(Bb, S, N), B_c.reshape(Bb, S, N),
                       cs.transpose(0, 2, 1, 3).reshape(Bb, H, S),
                       cs.transpose(0, 1, 3, 2).reshape(Bb, S, H),
                       x_c.reshape(Bb, S, H * P).transpose(0, 2, 1))
    return (y_t.reshape(Bb, nc, H, P, Q).transpose(0, 1, 4, 2, 3),
            states.reshape(Bb, nc, H, P, N))


def _call(kernel, static, *operands):
    Q, P, W, mxu, interpret, _scope = static
    return kernel(*operands, Q=Q, P=P, W=W, mxu=mxu, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunk_terms(C, B, cs_row, cs_col, xdt, static):
    return tuple(_call(chunk_terms_fwd, static, C, B, cs_row, cs_col, xdt))


def _chunk_terms_fwd(C, B, cs_row, cs_col, xdt, static):
    return (_chunk_terms(C, B, cs_row, cs_col, xdt, static),
            (C, B, cs_row, cs_col, xdt))


def _chunk_terms_bwd(static, res, cotangents):
    C, B = res[:2]
    Q, scope = static[0], static[-1]
    Bb, S, N = C.shape
    nc = S // Q
    with jax.named_scope(scope):
        dx, dcbt, dB, dcs_row, dcs_col = _call(chunk_terms_bwd, static,
                                               *res, *cotangents)
        dcbt = dcbt.reshape(Bb, nc, Q, Q)                   # [b, c, s, l]
        C_c, B_c = C.reshape(Bb, nc, Q, N), B.reshape(Bb, nc, Q, N)
        dC = jnp.einsum("bcsl,bcsn->bcln", dcbt, B_c).reshape(Bb, S, N)
        dB = dB + jnp.einsum("bcsl,bcln->bcsn", dcbt, C_c).reshape(Bb, S, N)
    return dC, dB, dcs_row, dcs_col, dx


_chunk_terms.defvjp(_chunk_terms_fwd, _chunk_terms_bwd)
