"""Federated first/second-order baselines the paper(s) compare against.

* DIANA [24]  — first-order compressed gradient differences (exactly the
  "CGD" part of FLECS-CGD with no second-order preconditioning).
* FedNL [34]  — per-worker d×d Hessian LEARNING with compressed Hessian
  differences (small-d only; the memory bottleneck FLECS removes).
* DistributedGD — uncompressed synchronous gradient descent.

All share the (local_grad, local_hvp) oracle interface of
``repro.core.flecs``, run under ``repro.core.driver.run_experiment``
(lax.scan), and report per-node communicated bits as a per-worker [n]
vector (``bits_per_node``), so the benchmark plots share an x-axis.

Traced hyperparameters — the FLECS collapse, applied to every baseline
-----------------------------------------------------------------------
Each method is a (config, hparams, sweep step) triple exactly like
``repro.core.flecs``:

* a static config dataclass (:class:`DianaConfig`, :class:`FedNLConfig`,
  :class:`GDConfig`) holds the structural choices (sampling kind, FedNL's
  regularizer μ) plus scalar defaults;
* an hparam pytree (:class:`DianaHParams`, :class:`FedNLHParams`,
  :class:`GDHParams`) carries the per-round knobs as traced values — step
  sizes, full ``CompressorSpec``s, and a Bernoulli participation
  probability ``p`` — with ``*_hparam_grid`` / ``*_hparams_from_config``
  constructors;
* ``make_*_sweep_step(cfg, oracles…)`` builds the single
  ``step(hp, state, key)`` implementation; a single run is that step at
  one hparams point (``*_hparams_from_config``).

This is what lets ``repro.core.api``'s method registry put DIANA / FedNL /
GD on the same sweep-native footing as FLECS: a (p × level × alpha) grid
for any method is ONE compiled ``driver.run_sweep`` program.

Partial participation: sampled via ``driver.resolve_participation`` — the
hparams' traced ``p`` (bernoulli) when present, else the static config
``participation``/``sampling`` (the only path for exact-k "choice").  Only
sampled workers enter the server aggregate, update their local server-side
state (DIANA shift h^i, FedNL Hessian H^i), and pay bits.

Asynchronous buffered aggregation: ``make_diana_async_hooks`` /
``make_fednl_async_hooks`` / ``make_gd_async_hooks`` give each baseline
its part of the driver's one FedBuff engine (``driver.make_async_step``):
the same traced staleness axes as FLECS (``driver.AsyncHParams``), busy
workers excluded from sampling, bits charged at the *arrival* round, an
aggregate step once ``buffer_k`` updates have buffered, and an optional
``repro.core.traffic.TrafficModel``.  At ``tau=0`` (with ``buffer_k=1``,
or ``buffer_k=n`` under full participation) they collapse to the
synchronous steps trace-for-trace.

Population scale: DIANA and GD additionally ship sharded
(``make_*_sharded_sweep_step`` + ``*_sharded_state_specs`` for
``driver.run_sharded_sweep``) and cohort-subsampled
(``make_*_cohort_sweep_step``) engines, mirroring the FLECS contracts in
``repro.core.flecs``.  FedNL is deliberately excluded from both: its
per-worker d×d Hessian estimates make state AND payload O(n·d²) — the
very bottleneck the population engines exist to avoid — so scaling it to
a 100k-client registry has no faithful reading.

Spec-based compression: every ``compressor`` argument accepts a registry
name, a ``Compressor``, or a (possibly traced) ``CompressorSpec`` — the
steps apply ``compressors.compress(spec, …)`` and charge
``compressors.spec_bits(spec, d)``, the same traced algebra FLECS uses, so
the compressor choice is a vmappable sweep axis here too and FedNL's top-k
Hessian differences get the dimension-aware (32 + ⌈log2 d²⌉)-bits-per-kept-
value wire accounting.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.compressors import (CompressorSpec, make_spec, compress,
                                    spec_bits, spec_bits_many)
from repro.core.driver import (COHORT_SALT, AsyncHooks, bits_dtype,
                               cohort_indices, masked_mean,
                               resolve_participation, validate_ps,
                               SCOPE_COMPRESS_GRAD, SCOPE_ORACLE,
                               SCOPE_SERVER)
from repro.numerics import matmul


def _grid_axes(*axes, ps=None):
    """Cartesian product of 1-D axes (+ an optional participation axis),
    each returned raveled to [G] float32.  The participation axis is
    validated (``driver.validate_ps``) at build time — the traced path
    cannot."""
    validate_ps(ps)
    mesh = jnp.meshgrid(*[jnp.asarray(a, jnp.float32) for a in axes],
                        jnp.asarray([1.0] if ps is None else ps,
                                    jnp.float32),
                        indexing="ij")
    flat = [m.ravel() for m in mesh]
    return flat[:-1] + [None if ps is None else flat[-1]]


# ---------------------------------------------------------------------------
# DIANA
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DianaConfig:
    """Static structure + scalar defaults for DIANA."""
    alpha: float = 1.0
    gamma: float = 0.5
    compressor: str = "dither64"      # name / Compressor / CompressorSpec
    participation: float = 1.0
    sampling: str = "bernoulli"       # "bernoulli" | "choice" (exact-k)
    use_kernel: bool = False          # fused Pallas compressor path


class DianaHParams(NamedTuple):
    """Traced per-round DIANA knobs — scalars or [G] sweep-axis arrays.
    ``p=None`` defers participation to the static config path;
    ``bit_budget`` (per-node uplink bits, None = unbounded) engages the
    budget-freeze scan mode (``driver.freeze_on_bit_budget``)."""
    alpha: jnp.ndarray
    gamma: jnp.ndarray
    spec: CompressorSpec
    p: Optional[jnp.ndarray] = None
    bit_budget: Optional[jnp.ndarray] = None


def diana_hparams_from_config(cfg: DianaConfig) -> DianaHParams:
    return DianaHParams(jnp.float32(cfg.alpha), jnp.float32(cfg.gamma),
                        make_spec(cfg.compressor))


def diana_hparam_grid(alphas=(1.0,), gammas=(0.5,), levels=(64.0,),
                      ps=None) -> DianaHParams:
    """Cartesian (alpha × gamma × dither-level [× p]) grid, [G] leaves."""
    from repro.core.compressors import dither_spec
    a, g, s, p = _grid_axes(alphas, gammas, levels, ps=ps)
    return DianaHParams(a, g, dither_spec(s), p)


def diana_round_bits(cfg: DianaConfig, hp: DianaHParams, d: int):
    """Per-participating-worker uplink bits/round at each grid point —
    the spec-aware price behind plan-level bit budgets (one compressed
    gradient difference per round)."""
    return spec_bits_many(hp.spec, d)


class DianaState(NamedTuple):
    w: jnp.ndarray
    h: jnp.ndarray          # [n, d]
    k: jnp.ndarray
    bits_per_node: jnp.ndarray   # [n]


def _diana_round(cfg: DianaConfig, local_grad: Callable, hp: DianaHParams,
                 state: DianaState, key, axis: Optional[str] = None,
                 n_total: Optional[int] = None):
    """One DIANA round — dense (``axis=None``, op-for-op the original) or
    sharded, mirroring ``flecs._flecs_round``'s contract: under
    ``driver.run_sharded_sweep`` the state's worker leaves are one device's
    contiguous block, workers compute against global ids and the global
    per-worker key stream, the full shifted-gradient array is rebuilt with
    ``all_gather(tiled=True)``, and the server mean runs replicated —
    bit-for-bit the dense round on the same keys."""
    n_loc, d = state.h.shape
    n = n_loc if axis is None else n_total
    k_g, k_q, k_p = jax.random.split(key, 3)
    mask = resolve_participation(k_p, n, cfg.participation,
                                 cfg.sampling, hp.p)                    # [n]

    def worker(i, hk, kq):
        with jax.named_scope(SCOPE_ORACLE):
            g = local_grad(state.w, i, jax.random.fold_in(k_g, i))
        with jax.named_scope(SCOPE_COMPRESS_GRAD):
            return compress(hp.spec, kq, g - hk, cfg.use_kernel)

    if axis is None:
        ids, mask_loc = jnp.arange(n), mask
        ks = jax.random.split(k_q, n)
    else:
        idx = jax.lax.axis_index(axis)
        ids = idx * n_loc + jnp.arange(n_loc)
        mask_loc = jax.lax.dynamic_slice_in_dim(mask, idx * n_loc, n_loc)
        ks = jax.random.split(k_q, n)[ids]
    c = jax.vmap(worker)(ids, state.h, ks)
    with jax.named_scope(SCOPE_SERVER):
        g_i = c + state.h
        if axis is None:
            g_full, n_active = g_i, jnp.sum(mask)
        else:
            g_full = jax.lax.all_gather(g_i, axis, tiled=True)
            n_active = jax.lax.psum(jnp.sum(mask_loc), axis)  # integer-exact
        g_tilde = masked_mean(g_full, mask)
        w = state.w - hp.alpha * g_tilde
        h = state.h + hp.gamma * mask_loc[:, None] * c
        bits = state.bits_per_node + mask_loc.astype(
            state.bits_per_node.dtype) * spec_bits(hp.spec, d, cfg.use_kernel)
        new = DianaState(w, h, state.k + 1, bits)
        return new, {"g_tilde_norm": jnp.linalg.norm(g_tilde),
                     "n_active": n_active,
                     "bits_per_node": new.bits_per_node}


def make_diana_sweep_step(cfg: DianaConfig, local_grad: Callable):
    """Build step(hp: DianaHParams, state, key) -> (state, aux) whose step
    sizes, compressor spec, and participation p are traced — the single
    DIANA round implementation."""

    def step(hp: DianaHParams, state: DianaState, key):
        return _diana_round(cfg, local_grad, hp, state, key)

    return step


def make_diana_sharded_sweep_step(cfg: DianaConfig, local_grad: Callable,
                                  n_total: int, axis: str = "workers"):
    """The DIANA sweep step for ``driver.run_sharded_sweep`` — the state's
    worker leaves hold one device's block of the ``n_total`` federation."""

    def step(hp: DianaHParams, state: DianaState, key):
        return _diana_round(cfg, local_grad, hp, state, key, axis=axis,
                            n_total=n_total)

    return step


def diana_sharded_state_specs(axis: str = "workers") -> DianaState:
    """``driver.run_sharded_sweep`` state-spec tree for ``DianaState``."""
    return DianaState(w="", h=axis, k="", bits_per_node=axis)


def make_diana_cohort_sweep_step(cfg: DianaConfig, local_grad: Callable,
                                 n_total: int, cohort: int):
    """Cohort-subsampled DIANA over an N-client population: per round only
    the size-K cohort's rows of the persistent [N, d] shift table and [N]
    uplink ledger are gathered, computed on, and scatter-updated — no
    [N, ...] per-round intermediates (analysis rule R7).  Selection,
    participation, and key-stream conventions match
    ``flecs.make_flecs_cohort_sweep_step``; at ``cohort == n_total`` with
    an identity compressor (per-worker compressor keys unused) the rounds
    reproduce the dense engine bit-for-bit at a single grid point —
    across a vmapped sweep grid the two programs' gather/scatter context
    steers XLA's fusion (FMA) differently, so grids agree to 1 ulp while
    the integer-exact ledgers and activity counts stay exact
    (tests/test_cohort.py pins both)."""
    if not 1 <= cohort <= n_total:
        raise ValueError(f"cohort={cohort} must be in [1, {n_total}]")
    if n_total % cohort:
        raise ValueError(
            f"cohort={cohort} must divide the population {n_total} "
            "(stratified selection draws one client per contiguous "
            "stratum)")

    def step(hp: DianaHParams, state: DianaState, key):
        d = state.w.shape[0]
        k_g, k_q, k_p = jax.random.split(key, 3)             # == dense split
        k_sel = jax.random.fold_in(k_p, COHORT_SALT)
        idx = cohort_indices(k_sel, n_total, cohort)         # [K] distinct
        mask = resolve_participation(k_p, n_total, cfg.participation,
                                     cfg.sampling, hp.p, cohort=cohort)

        def worker(i, hk, kq):
            g = local_grad(state.w, i, jax.random.fold_in(k_g, i))
            return compress(hp.spec, kq, g - hk, cfg.use_kernel)

        h_c = state.h[idx]                                   # [K, d]
        ks = jax.vmap(lambda i: jax.random.fold_in(k_q, i))(idx)
        c = jax.vmap(worker)(idx, h_c, ks)
        g_tilde = masked_mean(c + h_c, mask)
        w = state.w - hp.alpha * g_tilde
        h = state.h.at[idx].add(hp.gamma * mask[:, None] * c)
        per_round = mask.astype(state.bits_per_node.dtype) * spec_bits(
            hp.spec, d, cfg.use_kernel)
        bits = state.bits_per_node.at[idx].add(per_round)
        new = DianaState(w, h, state.k + 1, bits)
        return new, {"g_tilde_norm": jnp.linalg.norm(g_tilde),
                     "n_active": jnp.sum(mask),
                     "cohort_bits": jnp.sum(per_round)}

    return step


def init_diana(w0, n_workers):
    return DianaState(w0.astype(jnp.float32),
                      jnp.zeros((n_workers, w0.shape[0]), jnp.float32),
                      jnp.zeros((), jnp.int32),
                      jnp.zeros((n_workers,), bits_dtype()))


def make_diana_async_hooks(cfg: DianaConfig,
                           local_grad: Callable) -> AsyncHooks:
    """DIANA's part of the buffered-async engine: compressed gradient
    differences arrive late, shifts h^i learn and bits are billed at the
    arrival round (busy workers are not re-sampled, so each c^i
    reconstructs against its compute-time shift), and a flush steps
    along the buffered mean."""

    def compute(hp: DianaHParams, state: DianaState, keys):
        k_g, k_q = keys
        n = state.h.shape[0]

        def worker(i, hk, kq):
            g = local_grad(state.w, i, jax.random.fold_in(k_g, i))
            return compress(hp.spec, kq, g - hk, cfg.use_kernel)

        return lambda: {"c": jax.vmap(worker)(jnp.arange(n), state.h,
                                              jax.random.split(k_q, n))}

    def arrive(hp: DianaHParams, state: DianaState, msg, arrived):
        d = state.h.shape[1]
        h = state.h + hp.gamma * arrived[:, None] * msg["c"]
        bits = state.bits_per_node + arrived.astype(
            state.bits_per_node.dtype) * spec_bits(hp.spec, d,
                                                   cfg.use_kernel)
        return (state._replace(h=h, bits_per_node=bits),
                {"g": msg["c"] + state.h})

    def flush(hp: DianaHParams, state: DianaState, means, flush):
        return jnp.where(flush, state.w - hp.alpha * means["g"], state.w), {}

    return AsyncHooks(cfg, 3, compute, arrive, flush)


# ---------------------------------------------------------------------------
# FedNL
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FedNLConfig:
    """Static structure + scalar defaults for FedNL (μ is structural: the
    positive-definite safeguard of the projected direction)."""
    alpha: float = 1.0
    compressor: str = "topk0.25"
    mu: float = 1e-3
    participation: float = 1.0
    sampling: str = "bernoulli"
    use_kernel: bool = False          # fused Pallas compressor path


class FedNLHParams(NamedTuple):
    """Traced per-round FedNL knobs — scalars or [G] sweep-axis arrays
    (``bit_budget``: per-node budget-freeze axis, None = unbounded)."""
    alpha: jnp.ndarray
    spec: CompressorSpec
    p: Optional[jnp.ndarray] = None
    bit_budget: Optional[jnp.ndarray] = None


def fednl_hparams_from_config(cfg: FedNLConfig) -> FedNLHParams:
    return FedNLHParams(jnp.float32(cfg.alpha), make_spec(cfg.compressor))


def fednl_hparam_grid(alphas=(1.0,), fracs=(0.25,), ps=None) -> FedNLHParams:
    """Cartesian (alpha × top-k fraction [× p]) grid, [G] leaves."""
    from repro.core.compressors import topk_spec
    a, f, p = _grid_axes(alphas, fracs, ps=ps)
    return FedNLHParams(a, topk_spec(f), p)


def fednl_round_bits(cfg: FedNLConfig, hp: FedNLHParams, d: int):
    """FedNL's per-round price: an uncompressed gradient (32·d) plus the
    compressed d×d Hessian difference — the dimension-aware top-k
    accounting, so budget-fair comparisons charge FedNL what it ships."""
    return 32.0 * d + spec_bits_many(hp.spec, d * d)


class FedNLState(NamedTuple):
    w: jnp.ndarray
    H: jnp.ndarray          # [n, d, d] per-worker Hessian estimates
    k: jnp.ndarray
    bits_per_node: jnp.ndarray   # [n]


def make_fednl_sweep_step(cfg: FedNLConfig, local_grad: Callable,
                          local_hessian: Callable):
    """FedNL (option with projection/regularized direction), sweep-native:
    H^i_{k+1} = H^i_k + C(∇²f_i(w_k) - H^i_k);  w⁺ = w - α [H̄]_μ^{-1} ḡ."""

    def step(hp: FedNLHParams, state: FedNLState, key):
        n, d = state.H.shape[:2]
        k_g, k_c, k_p = jax.random.split(key, 3)
        mask = resolve_participation(k_p, n, cfg.participation,
                                     cfg.sampling, hp.p)

        def worker(i, Hk, kc):
            g = local_grad(state.w, i, jax.random.fold_in(k_g, i))
            Hi = local_hessian(state.w, i)
            D = compress(hp.spec, kc, Hi - Hk, cfg.use_kernel)
            return g, D

        ks = jax.random.split(k_c, n)
        g_all, D_all = jax.vmap(worker)(jnp.arange(n), state.H, ks)
        H_new = state.H + mask[:, None, None] * D_all
        g_bar = masked_mean(g_all, mask)
        H_bar = masked_mean(H_new, mask)
        # positive-definite safeguard: H̄ + μI on the symmetric part
        Hs = 0.5 * (H_bar + H_bar.T) + cfg.mu * jnp.eye(d)
        lam, V = jnp.linalg.eigh(Hs)
        lam = jnp.maximum(jnp.abs(lam), cfg.mu)
        p = -matmul(V, matmul(V.T, g_bar) / lam)
        w = state.w + hp.alpha * p
        # uncompressed gradient + dimension-aware compressed Hessian diff
        bits = state.bits_per_node + mask.astype(
            state.bits_per_node.dtype) * (
                d * 32.0 + spec_bits(hp.spec, d * d, cfg.use_kernel))
        new = FedNLState(w, H_new, state.k + 1, bits)
        return new, {"g_tilde_norm": jnp.linalg.norm(g_bar),
                     "n_active": jnp.sum(mask),
                     "bits_per_node": new.bits_per_node}

    return step


def init_fednl(w0, n_workers):
    d = w0.shape[0]
    return FedNLState(w0.astype(jnp.float32),
                      jnp.zeros((n_workers, d, d), jnp.float32),
                      jnp.zeros((), jnp.int32),
                      jnp.zeros((n_workers,), bits_dtype()))


def make_fednl_async_hooks(cfg: FedNLConfig, local_grad: Callable,
                           local_hessian: Callable) -> AsyncHooks:
    """FedNL's part of the buffered-async engine — the compressed d×d
    Hessian DIFFERENCES arrive late, which is what makes second-order
    staleness interesting: a stale difference was compressed against the
    sender's compute-time estimate H^i, so (exactly like the DIANA shift
    algebra) a busy worker is not re-sampled until its message drains and
    the server-side H^i learning applies strictly at the arrival round.
    Bits — the uncompressed gradient plus the dimension-aware compressed
    Hessian diff, FedNL's full wire price — are charged at *arrival*.
    Arrived (gradient, updated-H) pairs accumulate in the FedBuff buffer;
    on flush the server takes one regularized-Newton step from the
    buffered means."""

    def compute(hp: FedNLHParams, state: FedNLState, keys):
        k_g, k_c = keys
        n = state.H.shape[0]

        def worker(i, Hk, kc):
            g = local_grad(state.w, i, jax.random.fold_in(k_g, i))
            Hi = local_hessian(state.w, i)
            D = compress(hp.spec, kc, Hi - Hk, cfg.use_kernel)
            return g, D

        def work():
            g_all, D_all = jax.vmap(worker)(jnp.arange(n), state.H,
                                            jax.random.split(k_c, n))
            return {"g": g_all, "D": D_all}

        return work

    def arrive(hp: FedNLHParams, state: FedNLState, msg, arrived):
        d = state.H.shape[1]
        H_new = state.H + arrived[:, None, None] * msg["D"]
        bits = state.bits_per_node + arrived.astype(
            state.bits_per_node.dtype) * (
                d * 32.0 + spec_bits(hp.spec, d * d, cfg.use_kernel))
        return (state._replace(H=H_new, bits_per_node=bits),
                {"g": msg["g"], "H": H_new})

    def flush(hp: FedNLHParams, state: FedNLState, means, flush):
        d = state.w.shape[0]

        def newton(_):
            # positive-definite safeguard: H̄ + μI on the symmetric part —
            # the synchronous direction, applied to the buffered means
            Hs = 0.5 * (means["H"] + means["H"].T) + cfg.mu * jnp.eye(d)
            lam, V = jnp.linalg.eigh(Hs)
            lam = jnp.maximum(jnp.abs(lam), cfg.mu)
            p = -matmul(V, matmul(V.T, means["g"]) / lam)
            return state.w + hp.alpha * p, jnp.linalg.norm(p)

        # the eigh only runs (per scan step) on flush rounds
        w, dir_norm = jax.lax.cond(
            flush, newton,
            lambda _: (state.w, jnp.zeros((), state.w.dtype)), None)
        return w, {"dir_norm": dir_norm}

    return AsyncHooks(cfg, 3, compute, arrive, flush)


# ---------------------------------------------------------------------------
# Distributed GD
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GDConfig:
    """Static structure + scalar defaults for uncompressed distributed GD."""
    alpha: float = 2.0
    participation: float = 1.0
    sampling: str = "bernoulli"


class GDHParams(NamedTuple):
    """Traced per-round GD knobs — scalars or [G] sweep-axis arrays
    (``bit_budget``: per-node budget-freeze axis, None = unbounded)."""
    alpha: jnp.ndarray
    p: Optional[jnp.ndarray] = None
    bit_budget: Optional[jnp.ndarray] = None


def gd_hparams_from_config(cfg: GDConfig) -> GDHParams:
    return GDHParams(jnp.float32(cfg.alpha))


def gd_hparam_grid(alphas=(2.0,), ps=None) -> GDHParams:
    """Cartesian (alpha [× p]) grid, [G] leaves."""
    a, p = _grid_axes(alphas, ps=ps)
    return GDHParams(a, p)


def gd_round_bits(cfg: GDConfig, hp: GDHParams, d: int):
    """Uncompressed GD ships one 32-bit float gradient per round —
    constant over the grid, broadcast to its [G] axis."""
    return jnp.broadcast_to(jnp.float32(32.0 * d), jnp.shape(hp.alpha))


class GDState(NamedTuple):
    w: jnp.ndarray
    k: jnp.ndarray
    bits_per_node: jnp.ndarray   # [n]


def make_gd_sweep_step(cfg: GDConfig, local_grad: Callable, n_workers: int):
    """Uncompressed synchronous GD, sweep-native (traced alpha and p)."""

    def step(hp: GDHParams, state: GDState, key):
        d = state.w.shape[0]
        k_g, k_p = jax.random.split(key)
        mask = resolve_participation(k_p, n_workers, cfg.participation,
                                     cfg.sampling, hp.p)
        g_all = jax.vmap(
            lambda i: local_grad(state.w, i, jax.random.fold_in(k_g, i)))(
                jnp.arange(n_workers))
        g = masked_mean(g_all, mask)
        bits = state.bits_per_node + mask.astype(
            state.bits_per_node.dtype) * (d * 32.0)
        new = GDState(state.w - hp.alpha * g, state.k + 1, bits)
        return new, {"g_tilde_norm": jnp.linalg.norm(g),
                     "n_active": jnp.sum(mask),
                     "bits_per_node": new.bits_per_node}

    return step


def make_gd_cohort_sweep_step(cfg: GDConfig, local_grad: Callable,
                              n_total: int, cohort: int):
    """Cohort-subsampled uncompressed GD: only the size-K cohort evaluates
    gradients each round; the persistent [N] uplink ledger is
    scatter-updated.  Selection/participation conventions match the DIANA
    and FLECS cohort engines."""
    if not 1 <= cohort <= n_total:
        raise ValueError(f"cohort={cohort} must be in [1, {n_total}]")
    if n_total % cohort:
        raise ValueError(
            f"cohort={cohort} must divide the population {n_total}")

    def step(hp: GDHParams, state: GDState, key):
        d = state.w.shape[0]
        k_g, k_p = jax.random.split(key)                     # == dense split
        k_sel = jax.random.fold_in(k_p, COHORT_SALT)
        idx = cohort_indices(k_sel, n_total, cohort)
        mask = resolve_participation(k_p, n_total, cfg.participation,
                                     cfg.sampling, hp.p, cohort=cohort)
        g_all = jax.vmap(
            lambda i: local_grad(state.w, i, jax.random.fold_in(k_g, i)))(
                idx)
        g = masked_mean(g_all, mask)
        per_round = mask.astype(state.bits_per_node.dtype) * (d * 32.0)
        bits = state.bits_per_node.at[idx].add(per_round)
        new = GDState(state.w - hp.alpha * g, state.k + 1, bits)
        return new, {"g_tilde_norm": jnp.linalg.norm(g),
                     "n_active": jnp.sum(mask),
                     "cohort_bits": jnp.sum(per_round)}

    return step


def init_gd(w0, n_workers):
    return GDState(w0.astype(jnp.float32), jnp.zeros((), jnp.int32),
                   jnp.zeros((n_workers,), bits_dtype()))


def make_gd_async_hooks(cfg: GDConfig, local_grad: Callable,
                        n_workers: int) -> AsyncHooks:
    """Uncompressed GD's part of the buffered-async engine — the classic
    stale-gradient baseline: delayed gradients are billed on arrival and a
    flush steps along their buffered mean."""

    def compute(hp: GDHParams, state: GDState, keys):
        (k_g,) = keys
        return lambda: {"g": jax.vmap(
            lambda i: local_grad(state.w, i, jax.random.fold_in(k_g, i)))(
                jnp.arange(n_workers))}

    def arrive(hp: GDHParams, state: GDState, msg, arrived):
        d = state.w.shape[0]
        bits = state.bits_per_node + arrived.astype(
            state.bits_per_node.dtype) * (d * 32.0)
        return state._replace(bits_per_node=bits), {"g": msg["g"]}

    def flush(hp: GDHParams, state: GDState, means, flush):
        return jnp.where(flush, state.w - hp.alpha * means["g"], state.w), {}

    return AsyncHooks(cfg, 2, compute, arrive, flush)
