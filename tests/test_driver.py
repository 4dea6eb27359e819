"""The scan-based experiment engine (repro.core.driver).

Checks the engine against a hand-rolled loop over the same key sequence,
the trace plumbing (aux stacking + in-scan record hook), and the vmapped
hyperparameter sweep path.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.driver import (iters_for_bit_budget, masked_mean,
                               run_experiment, run_sweep)
from repro.core.flecs import (FlecsConfig, hparam_grid, hparams_from_config,
                              init_state, make_flecs_sweep_step)
from repro.data.logreg import make_problem

PROB = make_problem(d=24, n_workers=4, r=24, mu=1e-3, seed=5)
LG, LH = PROB.make_oracles(batch=0)


def _flecs_step(cfg, lg, lh):
    """The FLECS sweep step at the config's own hparams point."""
    return functools.partial(make_flecs_sweep_step(cfg, lg, lh),
                             hparams_from_config(cfg))
CFG = FlecsConfig(m=2, grad_compressor="dither64", hess_compressor="dither64")


def test_scan_matches_manual_loop():
    """One scan program == stepping the same keys by hand."""
    step = _flecs_step(CFG, LG, LH)
    st0 = init_state(jnp.zeros(PROB.d), PROB.n_workers)
    iters = 7
    st_scan, traces = run_experiment(step, st0, jax.random.key(11), iters)

    jstep = jax.jit(step)
    st = st0
    for k in jax.random.split(jax.random.key(11), iters):
        st, aux = jstep(st, k)
    np.testing.assert_allclose(np.asarray(st.w), np.asarray(st_scan.w),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st.bits_per_node),
                               np.asarray(st_scan.bits_per_node))
    np.testing.assert_allclose(float(aux["g_tilde_norm"]),
                               float(traces["g_tilde_norm"][-1]), rtol=1e-6)


def test_traces_stack_and_record_hook():
    step = _flecs_step(CFG, LG, LH)
    st, tr = run_experiment(step, init_state(jnp.zeros(PROB.d),
                                             PROB.n_workers),
                            jax.random.key(0), 12,
                            record=lambda s: PROB.metrics(s.w))
    assert tr["F"].shape == (12,)
    assert tr["bits_per_node"].shape == (12, PROB.n_workers)
    # bits are cumulative and strictly increasing under full participation
    assert np.all(np.diff(np.asarray(tr["bits_per_node"]), axis=0) > 0)
    assert float(tr["F"][-1]) < float(tr["F"][0])
    # final trace row is the final state
    np.testing.assert_allclose(np.asarray(tr["bits_per_node"][-1]),
                               np.asarray(st.bits_per_node))


def test_record_every_matches_dense_trace():
    """record_every=E traces have length iters // E and equal the dense
    trace at the recorded indices (rows E-1, 2E-1, …)."""
    step = _flecs_step(CFG, LG, LH)
    st0 = init_state(jnp.zeros(PROB.d), PROB.n_workers)
    iters, every = 30, 10
    rec = lambda s: PROB.metrics(s.w)                       # noqa: E731
    st_d, dense = run_experiment(step, st0, jax.random.key(8), iters,
                                 record=rec)
    st_t, thin = run_experiment(step, st0, jax.random.key(8), iters,
                                record=rec, record_every=every)
    assert thin["F"].shape == (iters // every,)
    assert thin["bits_per_node"].shape == (iters // every, PROB.n_workers)
    for key in ("F", "grad_sq", "bits_per_node", "g_tilde_norm"):
        np.testing.assert_array_equal(np.asarray(thin[key]),
                                      np.asarray(dense[key])[every - 1::every])
    # identical final state either way (thinning only affects the ys)
    np.testing.assert_array_equal(np.asarray(st_d.w), np.asarray(st_t.w))
    with pytest.raises(ValueError):
        run_experiment(step, st0, jax.random.key(8), 30, record_every=7)


def test_trace_dtype_bf16_keeps_bits_ledger_exact():
    """trace_dtype=bf16 quarters trace memory for long runs, but the bits
    ledger must stay in driver.bits_dtype() (bf16 loses integer counts)."""
    from repro.core.driver import bits_dtype
    step = _flecs_step(CFG, LG, LH)
    st0 = init_state(jnp.zeros(PROB.d), PROB.n_workers)
    _, tr = run_experiment(step, st0, jax.random.key(1), 8,
                           record=lambda s: PROB.metrics(s.w),
                           trace_dtype=jnp.bfloat16)
    assert tr["F"].dtype == jnp.bfloat16
    assert tr["g_tilde_norm"].dtype == jnp.bfloat16
    assert tr["bits_per_node"].dtype == bits_dtype()
    # ledger values are exact, not rounded
    _, tr32 = run_experiment(step, st0, jax.random.key(1), 8)
    np.testing.assert_array_equal(np.asarray(tr["bits_per_node"]),
                                  np.asarray(tr32["bits_per_node"]))
    # sweep path honors the same contract (+ record_every)
    hp = hparam_grid([1.0], [1.0], [16.0, 64.0])
    sts, trs = run_sweep(make_flecs_sweep_step(CFG, LG, LH), hp, st0,
                         jax.random.key(2), 8,
                         record=lambda s: PROB.metrics(s.w),
                         record_every=4, trace_dtype=jnp.bfloat16)
    assert trs["F"].shape == (2, 2) and trs["F"].dtype == jnp.bfloat16
    assert trs["bits_per_node"].dtype == bits_dtype()


def test_sweep_matches_independent_runs():
    """run_sweep over a [G] grid == G standalone run_experiment calls with
    the same per-grid-point key streams: the stochastic compression draws
    and bit ledgers match bit-for-bit (same keys), while float iterates
    agree to the last-ulp tolerance of batched vs unbatched eigh/qr
    kernels."""
    hp = hparam_grid([0.5, 1.0], [1.0], [16.0])
    sweep = make_flecs_sweep_step(CFG, LG, LH)
    st0 = init_state(jnp.zeros(PROB.d), PROB.n_workers)
    iters = 9
    rec = lambda s: PROB.metrics(s.w)                       # noqa: E731
    sts, tr = run_sweep(sweep, hp, st0, jax.random.key(13), iters,
                        record=rec)
    G = hp.alpha.shape[0]
    for g in range(G):
        hp_g = jax.tree.map(lambda a: a[g], hp)
        st_g, tr_g = run_experiment(
            lambda st, k: sweep(hp_g, st, k), st0,
            jax.random.split(jax.random.key(13), G)[g], iters, record=rec)
        # key streams identical => identical dither draws => exact ledgers
        np.testing.assert_array_equal(np.asarray(tr_g["bits_per_node"]),
                                      np.asarray(tr["bits_per_node"][g]))
        np.testing.assert_allclose(np.asarray(st_g.w), np.asarray(sts.w[g]),
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(tr_g["F"]),
                                   np.asarray(tr["F"][g]), rtol=1e-6)


def test_masked_mean():
    x = jnp.asarray([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]])
    np.testing.assert_allclose(
        np.asarray(masked_mean(x, jnp.asarray([1.0, 0.0, 1.0]))), [3.0, 4.0])
    # all-zero mask must not divide by zero
    np.testing.assert_allclose(
        np.asarray(masked_mean(x, jnp.zeros(3))), [0.0, 0.0])


def test_iters_for_bit_budget():
    assert iters_for_bit_budget(100, 10) == 10
    assert iters_for_bit_budget(101, 10) == 11
    assert iters_for_bit_budget(1, 10) == 1


def test_hparam_grid_shapes():
    hp = hparam_grid([0.5, 1.0], [1.0], [16.0, 64.0, 128.0])
    assert hp.alpha.shape == hp.gamma.shape == hp.grad_s.shape == (6,)
    combos = set(zip(np.asarray(hp.alpha).tolist(),
                     np.asarray(hp.grad_s).tolist()))
    assert combos == {(a, s) for a in (0.5, 1.0) for s in (16., 64., 128.)}


def test_vmapped_sweep_runs_grid_in_one_program():
    """A step-size x dithering-level grid vmapped through one scan: every
    grid point descends, the billed bits follow each point's level, and
    the objective separates a tiny step size from a sane one."""
    hp = hparam_grid([1e-3, 1.0], [1.0], [4.0, 64.0])
    sweep = make_flecs_sweep_step(CFG, LG, LH)
    st0 = init_state(jnp.zeros(PROB.d), PROB.n_workers)
    f0 = float(PROB.global_loss(st0.w))
    iters = 60
    sts, tr = run_sweep(sweep, hp, st0, jax.random.key(2), iters,
                        record=lambda s: PROB.metrics(s.w))
    assert tr["F"].shape == (4, iters)
    assert sts.w.shape == (4, PROB.d)
    assert np.all(np.asarray(tr["F"][:, -1]) < f0)
    # dither4 => ceil(log2(9)) = 4 grad bits/val, dither64 => ceil(log2(129)) = 8
    m = CFG.m
    shared = m * PROB.d * 8.0 + 32.0 * m * m     # hess dither64 + Gram
    per_level = {4.0: iters * (4.0 * PROB.d + shared),
                 64.0: iters * (8.0 * PROB.d + shared)}
    np.testing.assert_allclose(
        np.asarray(sts.bits_per_node),
        np.stack([[per_level[float(s)]] * PROB.n_workers
                  for s in hp.grad_s]))
    # alpha=1e-3 grid points barely move; alpha=1.0 points clearly descend
    f_end = np.asarray(tr["F"][:, -1])
    tiny = np.asarray(hp.alpha) < 1e-2
    assert f_end[~tiny].max() < f_end[tiny].min() - 1e-3, f_end
