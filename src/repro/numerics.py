"""Arithmetic whose result must not depend on the backend.

* ``matmul`` / ``einsum``: float32 products.  At its default precision a
  TPU rounds the operands of an f32 product to bfloat16 (one MXU pass),
  which moves the federated engine's objective and curvature well past
  the float32 reference; the engine's oracles, Hessian-approximation
  updates and search directions ask for ``HIGHEST`` precision through
  these.  The CPU computes f32 products in f32 whatever the precision.
* ``ceil_log2``: the integer ⌈log2 x⌉ of the wire-price formulas.  A
  TPU's ``log2`` can land just above an exact power of two
  (log2(4096) > 12 there), and a bare ``ceil`` then overcharges every
  top-k message by one index bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b):
    """``a @ b`` in float32 on every backend."""
    return jnp.matmul(a, b, precision=HIGHEST)


def einsum(subscripts, *operands):
    """``jnp.einsum`` in float32 on every backend."""
    return jnp.einsum(subscripts, *operands, precision=HIGHEST)


def ceil_log2(x):
    """⌈log2 x⌉ as f32, exact for 1 <= x < 2^31 on every backend.

    The integer r nearest log2 x is right whatever the last-ulp error of
    ``log2``; the answer is r, or r + 1 when 2^r < x (2^r by an integer
    shift, exact)."""
    x = jnp.asarray(x, jnp.float32)
    r = jnp.floor(jnp.log2(x) + 0.5)
    pow2 = jnp.left_shift(jnp.int32(1), r.astype(jnp.int32))
    return jnp.where(pow2.astype(jnp.float32) < x, r + 1.0, r)
