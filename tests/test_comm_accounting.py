"""Communication accounting: the paper's §3 complexity table, measured.

* FLECS-CGD charges ⌈log2(2s+1)⌉·d bits for the gradient difference vs
  FLECS's uncompressed 32·d, plus the shared c·m·d sketched-Hessian and
  32·m² Gram payloads.
* ``bits_per_node`` is a per-worker [n] vector: a worker skipped by
  partial participation is charged exactly zero bits that round.
* Bit counters share one x64-aware dtype across flecs and every baseline
  (f32 loses integer counts past 2^24, reachable in long sweeps).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compressors import dither_bits
from repro.core.driver import (bits_dtype, participation_mask, run_experiment)
from repro.core.flecs import (FlecsConfig, bits_per_round,
                              hparams_from_config, init_state,
                              make_flecs_sweep_step)
from repro.data.logreg import make_problem
from repro.optim.baselines import (DianaConfig, FedNLConfig, GDConfig,
                                   diana_hparams_from_config,
                                   fednl_hparams_from_config,
                                   gd_hparams_from_config, init_diana,
                                   init_fednl, init_gd,
                                   make_diana_sweep_step,
                                   make_fednl_sweep_step, make_gd_sweep_step)

PROB = make_problem(d=40, n_workers=8, r=32, mu=1e-3, seed=2)
LG, LH = PROB.make_oracles(batch=0)


def _flecs_step(cfg, lg, lh):
    """The FLECS sweep step at the config's own hparams point."""
    return functools.partial(make_flecs_sweep_step(cfg, lg, lh),
                             hparams_from_config(cfg))
D, N = PROB.d, PROB.n_workers


def _one_round(cfg):
    step = _flecs_step(cfg, LG, LH)
    st, _ = run_experiment(step, init_state(jnp.zeros(D), N),
                           jax.random.key(0), 1)
    return st


@pytest.mark.parametrize("s", [16, 128])
def test_cgd_gradient_bits_formula(s):
    """CGD grad payload = ⌈log2(2s+1)⌉·d; FLECS pays 32·d for the same."""
    m = 2
    c_hess = float(dither_bits(jnp.float32(64)))
    cgd = _one_round(FlecsConfig(m=m, grad_compressor=f"dither{s}",
                                 hess_compressor="dither64"))
    flecs = _one_round(FlecsConfig(m=m, grad_compressor="identity",
                                   hess_compressor="dither64"))
    shared = m * D * c_hess + 32 * m * m
    c_grad = math.ceil(math.log2(2 * s + 1))
    assert float(dither_bits(jnp.float32(s))) == c_grad
    np.testing.assert_allclose(np.asarray(cgd.bits_per_node),
                               c_grad * D + shared)
    np.testing.assert_allclose(np.asarray(flecs.bits_per_node),
                               32 * D + shared)
    # helper agrees with the measured counters
    assert bits_per_round(
        FlecsConfig(m=m, grad_compressor=f"dither{s}",
                    hess_compressor="dither64"), D) == c_grad * D + shared


def test_skipped_worker_charged_zero_bits():
    """Under exact-k sampling each round bills k workers the full round
    price and everyone else exactly zero."""
    cfg = FlecsConfig(m=1, grad_compressor="dither64",
                      hess_compressor="dither64",
                      participation=0.5, sampling="choice")
    per_round = bits_per_round(cfg, D)
    step = _flecs_step(cfg, LG, LH)
    st, tr = run_experiment(step, init_state(jnp.zeros(D), N),
                            jax.random.key(7), 10)
    bills = np.asarray(tr["bits_per_node"])                 # [10, n] cumulative
    increments = np.diff(np.concatenate([np.zeros((1, N)), bills]), axis=0)
    assert set(np.unique(increments)) == {0.0, per_round}
    assert np.all(increments.sum(axis=1) == (N // 2) * per_round)
    # cumulative totals never decrease and end strictly below full price
    assert np.all(np.diff(bills, axis=0) >= 0)
    assert np.all(bills[-1] < 10 * per_round)


def test_bernoulli_sampling_bills_only_sampled():
    cfg = FlecsConfig(m=1, participation=0.3, sampling="bernoulli")
    per_round = bits_per_round(cfg, D)
    st, tr = run_experiment(_flecs_step(cfg, LG, LH),
                            init_state(jnp.zeros(D), N), jax.random.key(1), 20)
    inc = np.diff(np.concatenate(
        [np.zeros((1, N)), np.asarray(tr["bits_per_node"])]), axis=0)
    assert set(np.unique(inc)) <= {0.0, per_round}
    # per-round active counts match the billed counts exactly
    np.testing.assert_allclose(np.asarray(tr["n_active"]) * per_round,
                               inc.sum(axis=1))


def test_participation_mask_properties():
    key = jax.random.key(0)
    assert np.all(np.asarray(participation_mask(key, 5, 1.0)) == 1.0)
    m = np.asarray(participation_mask(key, 8, 0.5, "choice"))
    assert m.sum() == 4 and set(np.unique(m)) == {0.0, 1.0}
    with pytest.raises(ValueError):
        participation_mask(key, 8, 0.5, "nope")


@pytest.mark.parametrize("p", [0.01, 0.3, 0.5, 0.9])
def test_choice_samples_exactly_k_under_scan_and_vmap(p):
    """kind="choice" contract: exactly max(1, round(p*n)) workers every
    round — including p small enough that round(p*n) == 0 — and the count
    holds when the mask is drawn inside scan and vmap tracing."""
    n = 8
    k_expect = max(1, round(p * n))
    keys = jax.random.split(jax.random.key(42), 64)
    _, scanned = jax.lax.scan(
        lambda c, k: (c, participation_mask(k, n, p, "choice")), 0, keys)
    vmapped = jax.vmap(
        lambda k: participation_mask(k, n, p, "choice"))(keys)
    for masks in (np.asarray(scanned), np.asarray(vmapped)):
        assert masks.shape == (64, n)
        assert set(np.unique(masks)) <= {0.0, 1.0}
        np.testing.assert_array_equal(masks.sum(axis=1), k_expect)
    # scan and vmap consume the same keys => identical masks
    np.testing.assert_array_equal(np.asarray(scanned), np.asarray(vmapped))


def test_participation_p_nonpositive_raises():
    """p <= 0 cannot mean 'sample nobody forever' — both sampling kinds
    reject it instead of silently producing a dead federation."""
    key = jax.random.key(0)
    for kind in ("bernoulli", "choice"):
        with pytest.raises(ValueError):
            participation_mask(key, 8, 0.0, kind)
        with pytest.raises(ValueError):
            participation_mask(key, 8, -0.25, kind)


def test_bits_dtype_unified_across_methods():
    """init_diana/init_fednl/init_gd used to hard-code f32 zeros while
    flecs was x64-aware; all four must agree and be [n]-shaped."""
    w0 = jnp.zeros(D)
    states = [init_state(w0, N), init_diana(w0, N), init_fednl(w0, N),
              init_gd(w0, N)]
    for st in states:
        assert st.bits_per_node.shape == (N,)
        assert st.bits_per_node.dtype == bits_dtype()


def test_baseline_bits_respect_participation():
    """DIANA / FedNL / GD: skipped workers pay zero."""
    diana = DianaConfig(0.5, 0.5, "dither64", participation=0.5,
                        sampling="choice")
    gd = GDConfig(1.0, participation=0.5, sampling="choice")
    runs = {
        "diana": (functools.partial(make_diana_sweep_step(diana, LG),
                                    diana_hparams_from_config(diana)),
                  init_diana(jnp.zeros(D), N), D * 8.0),
        "gd": (functools.partial(make_gd_sweep_step(gd, LG, N),
                                 gd_hparams_from_config(gd)),
               init_gd(jnp.zeros(D), N), D * 32.0),
    }

    def local_hessian(w, i):
        return jax.hessian(lambda ww: PROB.local_loss(ww, i))(w)

    # FedNL ships the d² Hessian difference through top-k: ⌈frac·d²⌉ kept
    # values at (32 + ⌈log2 d²⌉) bits each (dimension-aware index cost)
    kept = math.ceil(0.25 * D * D)
    fednl_hess_bits = kept * (32.0 + math.ceil(math.log2(D * D)))
    fednl = FedNLConfig(1.0, "topk0.25", PROB.mu, participation=0.5,
                        sampling="choice")
    runs["fednl"] = (
        functools.partial(make_fednl_sweep_step(fednl, LG, local_hessian),
                          fednl_hparams_from_config(fednl)),
        init_fednl(jnp.zeros(D), N), D * 32.0 + fednl_hess_bits)
    for name, (step, st0, per_round) in runs.items():
        st, tr = run_experiment(step, st0, jax.random.key(3), 6)
        inc = np.diff(np.concatenate(
            [np.zeros((1, N)), np.asarray(tr["bits_per_node"])]), axis=0)
        assert set(np.unique(inc)) == {0.0, per_round}, name
        assert np.all(inc.sum(axis=1) == (N // 2) * per_round), name
