"""Search directions (Definition 7, Algorithms 4 and 5).

Truncated inverse (Alg 4) needs an O(d³) eigendecomposition of the averaged
d×d approximation — exact-mode only.  FedSONIA (Alg 5) works purely from the
current sketch (Ỹ, M): O(d m² + m³), the scalable path reused verbatim by
the DL-scale adapter.

Lemma 9 invariant: both produce p = -A g with μ₁ I ⪯ A ⪯ μ₂ I, where
μ₁ ≥ 1/Ω and μ₂ ≤ 1/ω (+ ρ for the SONIA orthogonal complement) — verified
by tests/test_directions.py.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.numerics import matmul


def truncate_eigs(lam, omega: float, Omega: float):
    """Definition 7, with one safeguard deviation (documented in DESIGN.md):
    eigendirections with |λ| < ω carry no trustworthy curvature; the literal
    Def. 7 floors them at ω, i.e. an enormous 1/ω step along exactly the
    directions we know nothing about (with B₀ = 0 and rank-m updates, that is
    *most* of R^d early on — observed to diverge immediately).  We instead
    map them to Ω (step 1/Ω ≈ 0), which is precisely how FedSONIA treats its
    orthogonal complement (ρ = 1/Ω).  Directions with observed curvature are
    clipped into [ω, Ω] as written."""
    a = jnp.abs(lam)
    return jnp.where(a >= omega, jnp.minimum(a, Omega), Omega)


def truncated_inverse_direction_floored(B, grad, omega, Omega, floor):
    """Alg 4 with a curvature floor: averaging rank-m per-worker PSD
    approximations produces junk eigenvalues in (ω, μ) whose inverses are
    enormous steps along uninformed directions (observed: divergence at
    α = 1 on the paper's own hyperparameters).  Eigendirections with
    |λ| < floor are treated like FedSONIA's orthogonal complement (1/Ω)."""
    lam, V = jnp.linalg.eigh(0.5 * (B + B.T))
    a = jnp.abs(lam)
    lam_t = jnp.where(a >= floor, jnp.clip(a, omega, Omega), Omega)
    return -matmul(V, matmul(V.T, grad) / lam_t)


def truncated_inverse_direction(B, grad, omega: float, Omega: float):
    """Alg 4: p = -(|B|_ω^Ω)^{-1} ∇F.  B: [d,d] symmetric."""
    lam, V = jnp.linalg.eigh(0.5 * (B + B.T))
    lam_t = truncate_eigs(lam, omega, Omega)
    p = -matmul(V, matmul(V.T, grad) / lam_t)
    return p


def fedsonia_direction(Y_tilde, M, grad, omega: float, Omega: float,
                       rho: float):
    """Alg 5 (FedSONIA): low-rank truncated inverse + scaled complement.

    B_sonia = Ỹ M† Ỹᵀ = Q (R M† Rᵀ) Qᵀ with Ỹ = Q R.
    p = -(|B_sonia|_ω^Ω)^{-1} g_∥  -  ρ g_⊥,
    where g_∥ is the projection of ∇F onto span(Q).
    """
    Q, R = jnp.linalg.qr(Y_tilde)                       # d x m, m x m
    core = matmul(matmul(R, jnp.linalg.pinv(M, rcond=1e-10)), R.T)  # m x m
    lam, V = jnp.linalg.eigh(0.5 * (core + core.T))
    lam_t = truncate_eigs(lam, omega, Omega)
    Vq = matmul(Q, V)                                   # d x m orthonormal
    coef = matmul(Vq.T, grad)                           # m
    g_par = matmul(Vq, coef)
    g_perp = grad - g_par
    p = -matmul(Vq, coef / lam_t) - rho * g_perp
    return p
