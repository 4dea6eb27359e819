"""Asynchronous buffered aggregation (FedBuff-style staleness).

Covers the async engine's hard contracts:
  * the delay models of ``driver.StalenessSchedule`` (fixed / uniform /
    geometric) stay in [0, tau] and match their distributions;
  * ``driver.MessageBuffer`` routes each message to its arrival round and
    flags in-flight workers busy;
  * at tau=0 the async steps (FLECS, DIANA, FedNL, GD) reproduce the
    synchronous
    engine's traces exactly — allclose on F, exact on bits_per_node — for
    buffer_k=n at full participation AND buffer_k=1 under client sampling;
  * communication bits are charged at the *arrival* round, never at the
    compute round;
  * a tau=2, p=0.5 FLECS-CGD run on a d=40 logreg problem converges to
    F - F* < 1e-3.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.driver import (AsyncHParams, StalenessSchedule, buffer_busy,
                               buffer_receive, buffer_send, init_async_state,
                               init_buffer, make_async_step, run_experiment)
from repro.core.flecs import (FlecsConfig, bits_per_round,
                              hparams_from_config, init_state,
                              make_flecs_async_hooks, make_flecs_sweep_step)
from repro.data.logreg import make_problem
from repro.optim.baselines import (DianaConfig, FedNLConfig, GDConfig,
                                   diana_hparams_from_config,
                                   fednl_hparams_from_config,
                                   gd_hparams_from_config, init_diana,
                                   init_fednl, init_gd,
                                   make_diana_async_hooks,
                                   make_diana_sweep_step,
                                   make_fednl_async_hooks,
                                   make_fednl_sweep_step,
                                   make_gd_async_hooks, make_gd_sweep_step)

PROB = make_problem(d=24, n_workers=4, r=24, mu=1e-3, seed=5)
LG, LH = PROB.make_oracles(batch=0)
N, D = PROB.n_workers, PROB.d


def _local_hessian(w, i):
    return jax.hessian(lambda ww: PROB.local_loss(ww, i))(w)


def _flecs_step(cfg, lg=LG, lh=LH):
    """The FLECS sweep step at the config's own hparams point."""
    return functools.partial(make_flecs_sweep_step(cfg, lg, lh),
                             hparams_from_config(cfg))


def _async(hooks, hp, state, sched, buffer_k):
    """The driver's async step at one (hp, tau, buffer_k) point, and the
    async state around the method's initial ``state``."""
    ahp = AsyncHParams(hp, jnp.int32(sched.tau), jnp.float32(buffer_k))
    step = functools.partial(make_async_step(hooks, sched.kind, sched.q),
                             ahp)
    return step, init_async_state(hooks, state, hp, sched.max_delay)


def _flecs_async(cfg, sched, buffer_k, lg=LG, lh=LH, n=N, d=D):
    return _async(make_flecs_async_hooks(cfg, lg, lh),
                  hparams_from_config(cfg), init_state(jnp.zeros(d), n),
                  sched, buffer_k)


# ---------------------------------------------------------------------------
# StalenessSchedule
# ---------------------------------------------------------------------------

def test_schedule_fixed_and_validation():
    s = StalenessSchedule("fixed", tau=3)
    assert s.max_delay == 3
    np.testing.assert_array_equal(
        np.asarray(s.sample(jax.random.key(0), 5)), 3)
    with pytest.raises(ValueError):
        StalenessSchedule("exponential", tau=1)
    with pytest.raises(ValueError):
        StalenessSchedule("fixed", tau=-1)
    with pytest.raises(ValueError):
        StalenessSchedule("geometric", tau=2, q=1.5)


def test_schedule_uniform_covers_range():
    d = np.asarray(StalenessSchedule("uniform", tau=3).sample(
        jax.random.key(1), 8000))
    counts = np.bincount(d, minlength=4)
    assert d.min() == 0 and d.max() == 3
    # all four delays roughly equally likely
    assert counts.min() > 8000 / 4 * 0.85


def test_schedule_geometric_capped_and_decaying():
    sched = StalenessSchedule("geometric", tau=5, q=0.5)
    d = np.asarray(sched.sample(jax.random.key(2), 20000))
    assert d.min() == 0 and d.max() == 5
    counts = np.bincount(d, minlength=6)
    # P(delay=0) = 1 - q = 0.5; each subsequent (uncapped) delay halves
    assert abs(counts[0] / 20000 - 0.5) < 0.02
    # geometric head decays monotonically (halves each round before the cap)
    assert np.all(np.diff(counts[:4]) < 0)


def test_schedule_sampling_traces_under_scan():
    sched = StalenessSchedule("geometric", tau=3, q=0.3)
    _, ds = jax.lax.scan(lambda c, k: (c, sched.sample(k, 6)), 0,
                         jax.random.split(jax.random.key(3), 11))
    assert ds.shape == (11, 6) and ds.dtype == jnp.int32
    assert int(ds.min()) >= 0 and int(ds.max()) <= 3


# ---------------------------------------------------------------------------
# MessageBuffer
# ---------------------------------------------------------------------------

def test_buffer_routes_messages_to_arrival_round():
    n = 4
    buf = init_buffer({"x": jnp.zeros((n, 2))}, max_delay=2)
    msgs = {"x": jnp.arange(8.0).reshape(n, 2)}
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0])     # worker 2 not sampled
    delays = jnp.asarray([0, 1, 2, 1], jnp.int32)
    buf = buffer_send(buf, msgs, mask, delays, 0)
    np.testing.assert_array_equal(np.asarray(buffer_busy(buf)), [1, 1, 0, 1])

    buf, out, arrived = buffer_receive(buf, 0)
    np.testing.assert_array_equal(np.asarray(arrived), [1, 0, 0, 0])
    np.testing.assert_allclose(np.asarray(out["x"][0]), [0.0, 1.0])

    buf, out, arrived = buffer_receive(buf, 1)
    np.testing.assert_array_equal(np.asarray(arrived), [0, 1, 0, 1])
    np.testing.assert_allclose(np.asarray(out["x"][1]), [2.0, 3.0])
    np.testing.assert_allclose(np.asarray(out["x"][3]), [6.0, 7.0])

    buf, _, arrived = buffer_receive(buf, 2)
    np.testing.assert_array_equal(np.asarray(arrived), [0, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(buffer_busy(buf)), 0.0)


def test_buffer_cyclic_slot_reuse():
    """Slot r % S must be drained before round r + S re-files into it."""
    n = 2
    buf = init_buffer({"x": jnp.zeros((n,))}, max_delay=1)   # S = 2 slots
    for k in range(5):
        buf = buffer_send(buf, {"x": jnp.full((n,), float(k))},
                          jnp.ones((n,)), jnp.ones((n,), jnp.int32), k)
        buf, out, arrived = buffer_receive(buf, k)
        if k == 0:
            np.testing.assert_array_equal(np.asarray(arrived), 0.0)
        else:
            # round k drains the message sent at k-1 (delay 1)
            np.testing.assert_array_equal(np.asarray(arrived), 1.0)
            np.testing.assert_allclose(np.asarray(out["x"]), float(k - 1))


# ---------------------------------------------------------------------------
# tau=0 collapse to the synchronous engine
# ---------------------------------------------------------------------------

def _compare_sync_async(step_sync, st_sync0, async_run, iters=30, seed=11):
    step_async, st_async0 = async_run
    rec = lambda s: {"F": PROB.global_loss(s.w)}            # noqa: E731
    st_s, tr_s = run_experiment(step_sync, st_sync0, jax.random.key(seed),
                                iters, record=rec)
    st_a, tr_a = run_experiment(step_async, st_async0, jax.random.key(seed),
                                iters, record=rec)
    np.testing.assert_allclose(np.asarray(tr_a["F"]), np.asarray(tr_s["F"]),
                               rtol=0, atol=0)
    np.testing.assert_array_equal(np.asarray(tr_a["bits_per_node"]),
                                  np.asarray(tr_s["bits_per_node"]))
    np.testing.assert_array_equal(np.asarray(st_a.w), np.asarray(st_s.w))


@pytest.mark.parametrize("cfg_kw,K", [
    (dict(), None),                                          # K = n, full
    (dict(participation=0.5, sampling="choice"), 1),
    (dict(participation=0.3, sampling="bernoulli"), 1),
])
def test_tau0_flecs_matches_sync_engine(cfg_kw, K):
    cfg = FlecsConfig(m=2, grad_compressor="dither64",
                      hess_compressor="dither64", **cfg_kw)
    sched = StalenessSchedule("fixed", tau=0)
    _compare_sync_async(
        _flecs_step(cfg), init_state(jnp.zeros(D), N),
        _flecs_async(cfg, sched, N if K is None else K))


def test_tau0_flecs_lsr1_tinv_matches_sync_engine():
    """The L-SR1 arrival path regenerates each message's compute-time
    sketch from its round stamp — at tau=0 that is this round's sketch."""
    cfg = FlecsConfig(m=2, hessian_update="lsr1",
                      direction="truncated_inverse", tinv_floor=1e-3)
    sched = StalenessSchedule("fixed", tau=0)
    _compare_sync_async(
        _flecs_step(cfg), init_state(jnp.zeros(D), N),
        _flecs_async(cfg, sched, N))


def test_tau0_diana_gd_match_sync_engine():
    sched = StalenessSchedule("fixed", tau=0)
    cfg = DianaConfig(1.0, 0.5, "dither64", participation=0.3)
    hp = diana_hparams_from_config(cfg)
    _compare_sync_async(
        functools.partial(make_diana_sweep_step(cfg, LG), hp),
        init_diana(jnp.zeros(D), N),
        _async(make_diana_async_hooks(cfg, LG), hp,
               init_diana(jnp.zeros(D), N), sched, 1))
    cfg = GDConfig(1.0, participation=0.5, sampling="choice")
    hp = gd_hparams_from_config(cfg)
    _compare_sync_async(
        functools.partial(make_gd_sweep_step(cfg, LG, N), hp),
        init_gd(jnp.zeros(D), N),
        _async(make_gd_async_hooks(cfg, LG, N), hp,
               init_gd(jnp.zeros(D), N), sched, 1))


@pytest.mark.parametrize("fednl_kw,K", [
    (dict(), None),                                          # K = n, full
    (dict(participation=0.5, sampling="choice"), 1),
    (dict(participation=0.3, sampling="bernoulli"), 1),
])
def test_tau0_fednl_matches_sync_engine(fednl_kw, K):
    """Async FedNL (compressed Hessian diffs through the FedBuff buffer)
    collapses bit-for-bit onto the synchronous learned-Hessian path at
    tau=0 — the last method to close the five-method async matrix."""
    sched = StalenessSchedule("fixed", tau=0)
    cfg = FedNLConfig(1.0, "topk0.25", PROB.mu, **fednl_kw)
    hp = fednl_hparams_from_config(cfg)
    _compare_sync_async(
        functools.partial(make_fednl_sweep_step(cfg, LG, _local_hessian),
                          hp),
        init_fednl(jnp.zeros(D), N),
        _async(make_fednl_async_hooks(cfg, LG, _local_hessian), hp,
               init_fednl(jnp.zeros(D), N), sched, N if K is None else K))


# ---------------------------------------------------------------------------
# Bits are charged at the ARRIVAL round
# ---------------------------------------------------------------------------

def test_bits_charged_only_at_arrival_rounds():
    """Fixed tau=2, full participation: the federation cycles send → wait →
    arrive, so the bits ledger increments exactly at rounds 2, 5, 8, … —
    never at the compute round."""
    cfg = FlecsConfig(m=1, grad_compressor="dither64",
                      hess_compressor="dither64")
    sched = StalenessSchedule("fixed", tau=2)
    step, st0 = _flecs_async(cfg, sched, N)
    iters = 12
    st, tr = run_experiment(step, st0, jax.random.key(0), iters)
    per_round = bits_per_round(cfg, D)
    inc = np.diff(np.concatenate([np.zeros((1, N)),
                                  np.asarray(tr["bits_per_node"])]), axis=0)
    for k in range(iters):
        expect = per_round if k % 3 == 2 else 0.0
        np.testing.assert_allclose(inc[k], expect, err_msg=f"round {k}")
    # sends happen at rounds 0, 3, 6, … — busy workers are not re-sampled
    n_active = np.asarray(tr["n_active"])
    assert all(n_active[k] == (N if k % 3 == 0 else 0) for k in range(iters))
    # every arrival round flushes a full-size FedBuff buffer
    flushed = np.asarray(tr["flushed"])
    assert all(flushed[k] == (1.0 if k % 3 == 2 else 0.0)
               for k in range(iters))
    np.testing.assert_allclose(np.asarray(tr["staleness_mean"])[2::3], 2.0)
    # drained buffer => zero buffered updates after each flush
    assert np.all(np.asarray(tr["buffered"])[2::3] == 0.0)


def test_arrivals_conserve_sends():
    """Every sent message arrives exactly once (within the horizon)."""
    cfg = FlecsConfig(m=1, participation=0.5, sampling="choice")
    sched = StalenessSchedule("uniform", tau=3)
    step, st0 = _flecs_async(cfg, sched, 2)
    st, tr = run_experiment(step, st0, jax.random.key(4), 60)
    sent = float(np.sum(np.asarray(tr["n_active"])))
    arrived = float(np.sum(np.asarray(tr["n_arrived"])))
    in_flight = float(np.sum(np.asarray(buffer_busy(st.buf))))
    assert arrived == sent - in_flight
    assert 0 <= in_flight <= N
    # per-worker ledger: bits = arrivals x the fixed round price
    per_round = bits_per_round(cfg, D)
    np.testing.assert_allclose(
        np.asarray(st.bits_per_node).sum() / per_round, arrived)


# ---------------------------------------------------------------------------
# Convergence under real staleness (acceptance run)
# ---------------------------------------------------------------------------

def test_stale_flecs_cgd_converges_to_1e3():
    """tau=2, p=0.5 FLECS-CGD on a d=40 logreg problem: F - F* < 1e-3,
    with every bit charged at an arrival round.

    Damping note (recorded in ROADMAP): under client sampling the
    preconditioned update amplifies subset-mean noise along low-curvature
    directions, so the staleness run needs alpha well below the sync
    full-participation step (0.1 here vs 1.0) — the variance ball then
    shrinks with alpha instead of flooring.
    """
    prob = make_problem(d=40, n_workers=8, r=256, mu=1e-2,
                        heterogeneity=0.2, seed=0)
    lg, lh = prob.make_oracles(batch=0)
    f_star = float(prob.global_loss(prob.solve(iters=8000)))
    cfg = FlecsConfig(m=4, alpha=0.1, grad_compressor="dither128",
                      hess_compressor="dither128",
                      participation=0.5, sampling="choice")
    sched = StalenessSchedule("fixed", tau=2)
    step, st0 = _flecs_async(cfg, sched, 4, lg, lh, 8, prob.d)
    st, tr = run_experiment(step, st0, jax.random.key(0), 2400,
                            record_every=10)
    F = float(prob.global_loss(st.w))
    assert F - f_star < 1e-3, (F, f_star)
    # thinned traces: 2400 // 10 rows, bits ledger still exact multiples of
    # the arrival-round price
    assert tr["bits_per_node"].shape == (240, 8)
    per_round = bits_per_round(cfg, prob.d)
    counts = np.asarray(st.bits_per_node) / per_round
    np.testing.assert_allclose(counts, np.round(counts))
    # mean applied staleness equals the fixed delay
    w = np.asarray(tr["n_arrived"])
    stale = float((np.asarray(tr["staleness_mean"]) * w).sum() / w.sum())
    assert stale == pytest.approx(2.0)
