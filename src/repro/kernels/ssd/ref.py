"""Pure-jnp oracle for the SSD chunk terms: the diagonal-block output and
the chunk-end states.

This is the XLA formulation ``models/ssm.py`` ``ssd_scan`` runs where the
kernel does not apply: the decay ``L = exp(segsum(a))`` is materialised as a
``[B, nc, H, Q, Q]`` tensor and contracted in one four-operand einsum, and
the states are one three-operand einsum.
"""
from __future__ import annotations

import jax.numpy as jnp


def segsum(a):
    """a: [..., Q] -> lower-triangular cumulative sums [..., Q, Q]:
    out[i, j] = sum(a[j+1..i]) for i >= j, -inf above diagonal."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def chunk_terms(C_c, B_c, a_c, x_c):
    """(y_diag [B, nc, Q, H, P], states [B, nc, H, P, N]) from C_c, B_c
    [B, nc, Q, N], a_c [B, nc, H, Q] (dt·A) and x_c [B, nc, Q, H, P]
    (x·dt), all f32."""
    L = jnp.exp(segsum(a_c))                                # [B,nc,H,Q,Q]
    y_diag = jnp.einsum("bcln,bcsn,bchls,bcshp->bclhp", C_c, B_c, L, x_c)
    a_cum = jnp.cumsum(a_c, axis=-1)                        # [B,nc,H,Q]
    a_tail = a_cum[..., -1:] - a_cum                        # decay to chunk end
    states = jnp.einsum("bcsn,bchs,bcshp->bchpn", B_c, jnp.exp(a_tail), x_c)
    return y_diag, states
