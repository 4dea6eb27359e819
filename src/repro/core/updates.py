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
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.numerics import matmul


def _sym(a):
    return 0.5 * (a + a.swapaxes(-1, -2))


def truncated_lsr1_update(B, Y_tilde, M, S, omega: float):
    """Alg 2.  B: [d,d]; Y_tilde: [d,m]; M: [m,m]; S: [d,m]."""
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
    return _sym(B + matmul(W * inv[None, :], W.T)), G


def direct_update(B, Y_tilde, M, beta: float):
    """Alg 3.  B⁺ = (1-β) B + β Ỹ M† Ỹᵀ."""
    B_tilde = matmul(matmul(Y_tilde, jnp.linalg.pinv(M, rcond=1e-10)),
                     Y_tilde.T)
    return _sym((1.0 - beta) * B + beta * B_tilde)
