"""Server-side Hessian-approximation updates (Algorithms 2 and 3).

Both consume the reconstructed sketched Hessian Ỹ_k^i = C_k^i + B_k^i S_k
and the exact Gram M_k^i = S_k^T Y_k^i, and produce B_{k+1}^i.

Truncated L-SR1 (Alg 2):
    M - SᵀỸ = U L Uᵀ  (symmetric eigendecomposition of the m×m residual)
    B⁺ = B + (Ỹ - B S) U [L⁻¹]_ω Uᵀ (Ỹ - B S)ᵀ
where [L⁻¹]_ω truncates |eigenvalues| of L⁻¹ into [-1/ω... the paper keeps
entries whose |l_jj| ≥ ω (safeguard against tiny curvature denominators).

Direct update (Alg 3):
    B̃ = Ỹ M† Ỹᵀ;   B⁺ = (1-β) B + β B̃.

Both take a symmetric B, as the engine's B always is (B⁰ = 0 and each
update keeps it symmetric), and symmetrise only their m×m factors.  For
symmetric B the symmetric part of the update is then exact algebra:
sym((1-β)B + βỸPỸᵀ) = (1-β)B + βỸ·sym(P)·Ỹᵀ.  Symmetrising the [d, d]
result instead would transpose it, which XLA:TPU lowers to a copy of B
(a read and a write of the whole state) and which keeps the update from
writing B in place: the update is one rank-m product with an elementwise
epilogue that reads B once.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.numerics import matmul


def _sym(a):
    return 0.5 * (a + a.swapaxes(-1, -2))


def truncated_lsr1_update(B, Y_tilde, M, S, omega: float):
    """Alg 2.  B: [d,d] symmetric; Y_tilde: [d,m]; M: [m,m]; S: [d,m]."""
    BS = matmul(B, S)
    R = Y_tilde - BS                         # d x m residual
    G = _sym(M - matmul(S.T, BS))            # m x m  (= Sᵀ(H - B)S residual)
    lam, U = jnp.linalg.eigh(G)
    # [L⁻¹]_ω: Definition-7-style safeguard on the inverse — |λ| is floored
    # at ω before inverting (sign preserved).  Without the floor, compression
    # noise produces |λ| ≈ 0 directions whose 1/λ blows B up geometrically
    # (observed: NaN within ~100 iterations on the logreg problem).
    inv = jnp.sign(lam) / jnp.maximum(jnp.abs(lam), omega)
    W = matmul(R, U)
    return B + matmul(W * inv[None, :], W.T), G


def direct_update(B, Y_tilde, M, beta: float):
    """Alg 3.  B⁺ = (1-β) B + β Ỹ sym(M†) Ỹᵀ, for symmetric B."""
    P = _sym(jnp.linalg.pinv(M, rcond=1e-10))
    return (1.0 - beta) * B + beta * matmul(matmul(Y_tilde, P), Y_tilde.T)
