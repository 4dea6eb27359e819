"""Unit tests for sketches, HVPs, Hessian-approximation updates and search
directions (Algorithms 2-5, Definition 7, Lemma 9)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.directions import (fedsonia_direction,
                                   truncated_inverse_direction)
from repro.core.hessian import hvp, sketched_hessian
from repro.core.sketch import sketch
from repro.core.updates import _sym, direct_update, truncated_lsr1_update
from repro.numerics import matmul


# --- sketches --------------------------------------------------------------

def test_sketch_seeded_agreement():
    """Worker and server agree on S_k given only the iteration index."""
    for kind in ("rademacher", "gaussian", "coordinate"):
        a = sketch(kind, 32, 4, 7)
        b = sketch(kind, 32, 4, 7)
        c = sketch(kind, 32, 4, 8)
        np.testing.assert_array_equal(a, b)
        assert not np.allclose(a, c)
        assert a.shape == (32, 4)


def test_coordinate_sketch_is_selector():
    S = np.asarray(sketch("coordinate", 16, 3, 0))
    assert np.all(np.sum(S != 0, axis=0) == 1)
    assert np.all(np.sum(S, axis=0) == 1.0)


# --- HVP -------------------------------------------------------------------

def test_hvp_matches_quadratic():
    rng = np.random.default_rng(0)
    A = rng.normal(size=(10, 10))
    H = jnp.asarray(A @ A.T + 10 * np.eye(10), jnp.float32)
    loss = lambda w: 0.5 * w @ H @ w
    w = jnp.asarray(rng.normal(size=10), jnp.float32)
    v = jnp.asarray(rng.normal(size=10), jnp.float32)
    np.testing.assert_allclose(hvp(loss, w, v), H @ v, rtol=1e-5)


def test_sketched_hessian_matches_dense():
    rng = np.random.default_rng(1)
    A = rng.normal(size=(8, 8))
    H = jnp.asarray(A @ A.T + np.eye(8), jnp.float32)
    loss = lambda w: 0.5 * w @ H @ w
    w = jnp.zeros(8)
    S = sketch("gaussian", 8, 3, 0)
    np.testing.assert_allclose(sketched_hessian(loss, w, S), H @ S,
                               rtol=1e-4, atol=1e-4)


# --- updates ---------------------------------------------------------------

def _psd(rng, d, lo=0.5, hi=3.0):
    Q, _ = np.linalg.qr(rng.normal(size=(d, d)))
    lam = rng.uniform(lo, hi, size=d)
    return jnp.asarray((Q * lam) @ Q.T, jnp.float32)


def test_direct_update_full_sketch_recovers_hessian():
    """With m = d (full sketch) and exact Y, B̃ = H exactly."""
    rng = np.random.default_rng(2)
    d = 6
    H = _psd(rng, d)
    S = jnp.asarray(rng.normal(size=(d, d)), jnp.float32)
    Y = H @ S
    M = S.T @ Y
    B1 = direct_update(jnp.zeros((d, d)), Y, M, beta=1.0)
    np.testing.assert_allclose(B1, H, rtol=2e-3, atol=2e-3)


def test_direct_update_interpolates():
    rng = np.random.default_rng(3)
    d, m = 8, 3
    H = _psd(rng, d)
    B0 = _psd(rng, d)
    S = jnp.asarray(rng.normal(size=(d, m)), jnp.float32)
    Y = H @ S
    M = S.T @ Y
    B_half = direct_update(B0, Y, M, beta=0.5)
    B_tilde = Y @ jnp.linalg.pinv(M) @ Y.T
    np.testing.assert_allclose(B_half, 0.5 * B0 + 0.5 * B_tilde,
                               rtol=1e-3, atol=1e-3)


def test_lsr1_secant_on_sketch():
    """SR1 property: after the update, B⁺S = Ỹ (when no truncation binds)."""
    rng = np.random.default_rng(4)
    d, m = 10, 3
    H = _psd(rng, d, lo=1.0, hi=2.0)
    B0 = jnp.zeros((d, d))
    S = jnp.asarray(np.linalg.qr(rng.normal(size=(d, m)))[0], jnp.float32)
    Y = H @ S
    M = S.T @ Y
    B1, G = truncated_lsr1_update(B0, Y, M, S, omega=1e-8)
    np.testing.assert_allclose(B1 @ S, Y, rtol=5e-3, atol=5e-3)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("rule, beta", [("direct", 0.25), ("direct", 1.0),
                                        ("lsr1", None)])
def test_update_of_symmetric_B_matches_symmetrised_update(rule, beta):
    """For symmetric B, symmetrising only the m×m factor gives what
    symmetrising the [d, d] result gave, and B⁺ is symmetric."""
    rng = np.random.default_rng(6)
    d, m = 48, 4
    H, B0 = _psd(rng, d), _psd(rng, d)
    S = jnp.asarray(rng.normal(size=(d, m)) / np.sqrt(m), jnp.float32)
    # compression noise on Ỹ; M = SᵀY is not exactly symmetric in f32
    Y = matmul(H, S)
    Yt = Y + 0.05 * jnp.asarray(rng.normal(size=(d, m)), jnp.float32)
    M = matmul(S.T, Y)
    if rule == "direct":
        got = direct_update(B0, Yt, M, beta)
        P = jnp.linalg.pinv(M, rcond=1e-10)
        old = _sym((1.0 - beta) * B0 + beta * matmul(matmul(Yt, P), Yt.T))
    else:
        got, G = truncated_lsr1_update(B0, Yt, M, S, omega=1e-3)
        lam, U = jnp.linalg.eigh(G)
        inv = jnp.sign(lam) / jnp.maximum(jnp.abs(lam), 1e-3)
        W = matmul(Yt - matmul(B0, S), U)
        old = _sym(B0 + matmul(W * inv[None, :], W.T))
    assert _rel(got, old) <= 1e-5
    assert _rel(got, got.T) <= 1e-6


# --- directions (Lemma 9 invariant) ---------------------------------------

@pytest.mark.parametrize("omega,Omega", [(1e-3, 1e3), (1e-1, 10.0)])
def test_truncated_inverse_spectral_bounds(omega, Omega):
    """p = -A g with (1/Ω) I ⪯ A ⪯ (1/ω) I  =>  for any g:
    |g|²/Ω ≤ -gᵀp ≤ |g|²/ω and |p| ≤ |g|/ω."""
    rng = np.random.default_rng(5)
    d = 12
    B = _psd(rng, d, lo=1e-4, hi=1e4)     # spectrum exceeds [ω, Ω] both ways
    for _ in range(5):
        g = jnp.asarray(rng.normal(size=d), jnp.float32)
        p = truncated_inverse_direction(B, g, omega, Omega)
        quad = float(-g @ p)
        g2 = float(g @ g)
        assert g2 / Omega - 1e-4 <= quad <= g2 / omega + 1e-4
        assert float(jnp.linalg.norm(p)) <= float(jnp.linalg.norm(g)) / omega


def test_fedsonia_spectral_bounds():
    rng = np.random.default_rng(6)
    d, m = 16, 4
    H = _psd(rng, d, lo=0.5, hi=2.0)
    S = jnp.asarray(rng.normal(size=(d, m)), jnp.float32)
    Y = H @ S
    M = S.T @ Y
    omega, Omega, rho = 1e-3, 1e3, 1e-3
    for _ in range(5):
        g = jnp.asarray(rng.normal(size=d), jnp.float32)
        p = fedsonia_direction(Y, M, g, omega, Omega, rho)
        quad = float(-g @ p)
        g2 = float(g @ g)
        mu1 = min(1.0 / Omega, rho)
        mu2 = max(1.0 / omega, rho)
        assert mu1 * g2 - 1e-5 <= quad <= mu2 * g2 + 1e-5


def test_fedsonia_newton_in_subspace():
    """Inside span(Y), FedSONIA is a Newton step on the sketched Hessian."""
    rng = np.random.default_rng(7)
    d, m = 10, 10                          # full-rank sketch
    H = _psd(rng, d, lo=0.5, hi=2.0)
    S = jnp.asarray(rng.normal(size=(d, m)), jnp.float32)
    Y = H @ S
    M = S.T @ Y
    g = jnp.asarray(rng.normal(size=d), jnp.float32)
    p = fedsonia_direction(Y, M, g, 1e-6, 1e6, 0.0)
    np.testing.assert_allclose(p, -jnp.linalg.solve(H, g), rtol=2e-2,
                               atol=2e-2)
