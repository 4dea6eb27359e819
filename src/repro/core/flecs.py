"""FLECS-CGD, Algorithm 1 — exact mode (d×d per-worker state on the server).

This is the paper-faithful reproduction used to validate against the paper's
own experiments (regularized logistic regression, LIBSVM-dim synthetic
shards).  One `FlecsState` + step pair implements BOTH:

  * FLECS      — gradient compressor = identity (the paper's baseline)
  * FLECS-CGD  — gradient compressor = random dithering (+ shift h update)

and both Hessian updates (Alg 2 truncated L-SR1 / Alg 3 direct) and both
iterate updates (Alg 4 truncated inverse / Alg 5 FedSONIA), selected in
`FlecsConfig` exactly as in the paper's experiment grid.

Everything is jit-compatible; worker loops are vmapped (the n workers of a
federation are a batch dim here) and whole experiments run under
``repro.core.driver.run_experiment`` (lax.scan — no Python step loops).

Traced hyperparameters — ONE code path for static runs and sweeps
------------------------------------------------------------------
Every per-round knob lives in :class:`FlecsHParams` (step sizes alpha/gamma,
direct-update beta, and full ``CompressorSpec``s for the gradient AND
Hessian compressors — see ``repro.core.compressors``).  ``_flecs_round``
consumes the hparams as traced values, so:

  * a single run is ``make_flecs_sweep_step`` at one hparams point, e.g.
    ``functools.partial(step, hparams_from_config(cfg))`` — there is no
    parallel static round implementation to drift;
  * ``driver.run_sweep`` vmaps a whole (alpha × gamma × beta × grad_s ×
    hess_s) grid through one compiled program, with exact per-point bit
    ledgers (``compressors.spec_bits`` is traced too).

Partial participation (beyond-paper axis, FedNL/FedLab-style): set
``FlecsConfig.participation < 1`` and each round draws a client mask via
``driver.participation_mask``.  Only sampled workers contribute to the
server aggregates (g̃, Ỹ, M̄, B̄), update their shift h^i / approximation
B^i, and pay communication bits; skipped workers are charged zero bits.
Participation is ALSO a sweep axis: ``FlecsHParams.p`` (``hparam_grid``'s
``ps=``) carries a traced Bernoulli probability per grid point, so a
participation ablation vmaps through one compiled program
(``driver.resolve_participation``; exact-k "choice" sampling stays on the
static config path).

Asynchronous buffered aggregation (beyond-paper axis, FedBuff-style): a
sampled worker's message (c_k^i, Ỹ_k^i, M_k^i) arrives ``tau`` rounds
after it was computed (delays from ``driver.sample_delays``), buffers
FedBuff-style on the server, and is applied once ``buffer_k`` updates have
accumulated.  The worker's shift h^i and approximation B^i are updated —
and its bits charged — at the *arrival* round; a worker with a message in
flight is busy and is not sampled again, which keeps the shift algebra
exact (every c^i is reconstructed against the same h^i it was compressed
against).  It runs on the driver's one FedBuff engine
(``driver.make_async_step``) over :func:`make_flecs_async_hooks`, with a
traced (tau × buffer_k) grid from :func:`async_hparam_grid`.  With
``tau=0`` (and ``buffer_k=n`` at full participation, or ``buffer_k=1``
under sampling) it reproduces the synchronous round trace-for-trace.

Communication accounting (per *participating* worker per iteration, bits;
``FlecsState.bits_per_node`` is a per-worker [n] vector):
  c_k^i : spec_bits(grad_spec, d)     (gradient difference, compressed)
  C_k^i : spec_bits(hess_spec, d·m)   (sketched-Hessian difference)
  M_k^i : m² float32
  FLECS sends the gradient uncompressed: spec_bits(identity, d) = 32·d.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.compressors import (CompressorSpec, compress, dither_spec,
                                    make_spec, spec_bits, spec_bits_many)
from repro.core.directions import (fedsonia_direction,
                                   truncated_inverse_direction,
                                   truncated_inverse_direction_floored)
from repro.core.driver import (COHORT_SALT, AsyncHParams, AsyncHooks,
                               bits_dtype, cohort_indices, damped_alpha,
                               masked_mean, resolve_participation,
                               validate_ps, SCOPE_COMPRESS_GRAD,
                               SCOPE_COMPRESS_HESS, SCOPE_CURVATURE,
                               SCOPE_ORACLE, SCOPE_SERVER)
from repro.core.hierarchy import (EDGE_SALT, HierarchyConfig, charge_edges,
                                  edge_combine, edge_combine_cohort,
                                  edge_round_bits, init_edge_bits,
                                  validate_hierarchy)
from repro.core.sketch import sketch
from repro.core.updates import direct_update, truncated_lsr1_update
from repro.numerics import matmul


@dataclasses.dataclass(frozen=True)
class FlecsConfig:
    m: int = 1                        # memory size (sketch columns)
    omega: float = 1e-5               # lower truncation (ω)
    Omega: float = 1e8                # upper truncation (Ω)
    alpha: float = 1.0                # iterate step size
    beta: float = 1.0                 # direct-update learning rate
    gamma: float = 1.0                # shift learning rate (≤ 1/(ω_Q+1))
    rho: Optional[float] = None       # FedSONIA complement step (default 1/Ω)
    grad_compressor: str = "dither64"     # "identity" => plain FLECS
    hess_compressor: str = "dither64"
    hessian_update: str = "direct"    # "direct" (Alg 3) | "lsr1" (Alg 2)
    direction: str = "fedsonia"       # "fedsonia" (Alg 5) | "truncated_inverse"
    sketch_kind: str = "rademacher"
    tinv_floor: float = 0.0           # curvature floor for Alg 4 (see
                                      # directions.truncated_inverse_direction_floored)
    participation: float = 1.0        # per-round client sampling probability
    sampling: str = "bernoulli"       # "bernoulli" | "choice" (exact-k)
    use_kernel: bool = False          # fused Pallas compressor path
                                      # (repro.kernels.compressor;
                                      # interpret-mode off-TPU, bit-identical)
    hierarchy: Optional[HierarchyConfig] = None
                                      # two-tier server tree: edge
                                      # aggregators re-compress per-edge
                                      # partial sums before the top-level
                                      # combine, billed on the separate
                                      # edge_bits backhaul ledger
                                      # (repro.core.hierarchy)

    @property
    def rho_val(self):
        return 1.0 / self.Omega if self.rho is None else self.rho


class FlecsHParams(NamedTuple):
    """Traced per-round hyperparameters (see ``driver.run_sweep``).

    All fields are scalars — or [G] arrays across a sweep-grid axis:
      alpha     — iterate step size
      gamma     — shift learning rate
      beta      — direct-update (Alg 3) learning rate
      grad_spec — gradient CompressorSpec (family + level/fraction, traced)
      hess_spec — Hessian-difference CompressorSpec
      p         — Bernoulli participation probability, or None to defer to
                  the static ``FlecsConfig.participation``/``sampling``
                  (None is an empty pytree leaf, so pre-axis grids are
                  untouched; a traced p axis requires bernoulli sampling —
                  see ``driver.resolve_participation``)
      bit_budget — per-node uplink bit budget, or None for an unbounded
                  run.  A traced budget switches the sweep into the
                  budget-freeze scan mode (``driver.freeze_on_bit_budget``):
                  the state lax.select-freezes once the cumulative ledger
                  reaches it, so budget-fair comparisons are ONE fixed-
                  length program (``api.ExperimentPlan.bit_budget`` crosses
                  this axis with a grid).
      edge_spec — edge-tier CompressorSpec for hierarchical aggregation
                  (``FlecsConfig.hierarchy``), the traced backhaul-
                  compression axis; None whenever the config has no
                  hierarchy (an empty pytree leaf, so flat grids are
                  untouched).
    """
    alpha: jnp.ndarray
    gamma: jnp.ndarray
    beta: jnp.ndarray
    grad_spec: CompressorSpec
    hess_spec: CompressorSpec
    p: Optional[jnp.ndarray] = None
    bit_budget: Optional[jnp.ndarray] = None
    edge_spec: Optional[CompressorSpec] = None

    @property
    def grad_s(self):
        """Gradient dithering level axis (the pre-spec sweep API)."""
        return self.grad_spec.s

    @property
    def hess_s(self):
        return self.hess_spec.s


def hparams_from_config(cfg: FlecsConfig) -> FlecsHParams:
    """The concrete hparams point of a single run of ``cfg``."""
    return FlecsHParams(jnp.float32(cfg.alpha), jnp.float32(cfg.gamma),
                        jnp.float32(cfg.beta),
                        make_spec(cfg.grad_compressor),
                        make_spec(cfg.hess_compressor),
                        edge_spec=(None if cfg.hierarchy is None else
                                   make_spec(cfg.hierarchy.edge_compressor)))


def hparam_grid(alphas, gammas, grad_levels, betas=(1.0,),
                hess_levels=(64.0,), ps=None,
                edge_levels=None) -> FlecsHParams:
    """Cartesian product of the sweep axes, flattened to [G] leaves.

    ``grad_levels``/``hess_levels`` build dithering specs (the paper's
    experimental compressor); grids over other families — or mixing
    families along an axis — can be built directly as a ``FlecsHParams``
    of stacked ``CompressorSpec`` leaves (``compressors.stack_specs``).
    ``ps`` (optional) adds a traced Bernoulli participation axis; ``None``
    keeps participation on the static config path.  ``edge_levels``
    (optional) adds a traced edge-tier dithering axis — the backhaul
    compression of hierarchical aggregation; it requires a config with
    ``hierarchy`` set and ``None`` leaves flat grids untouched.
    """
    validate_ps(ps)
    a, g, s, b, hs, p = jnp.meshgrid(
        jnp.asarray(alphas, jnp.float32),
        jnp.asarray(gammas, jnp.float32),
        jnp.asarray(grad_levels, jnp.float32),
        jnp.asarray(betas, jnp.float32),
        jnp.asarray(hess_levels, jnp.float32),
        jnp.asarray([1.0] if ps is None else ps, jnp.float32),
        indexing="ij")
    hp = FlecsHParams(a.ravel(), g.ravel(), b.ravel(),
                      dither_spec(s.ravel()), dither_spec(hs.ravel()),
                      None if ps is None else p.ravel())
    if edge_levels is None:
        return hp
    # cross the base grid with the edge axis: repeat every base point E
    # times, tile the edge levels across them (base-major order)
    E = len(edge_levels)
    hp = jax.tree.map(lambda leaf: jnp.repeat(leaf, E, axis=0), hp)
    tiled = jnp.tile(jnp.asarray(edge_levels, jnp.float32),
                     a.size)
    return hp._replace(edge_spec=dither_spec(tiled))


class FlecsState(NamedTuple):
    w: jnp.ndarray        # [d]
    h: jnp.ndarray        # [n, d]   per-worker gradient shifts
    B: jnp.ndarray        # [n, d, d] per-worker Hessian approximations
    k: jnp.ndarray        # iteration counter
    bits_per_node: jnp.ndarray   # [n] cumulative communicated bits per worker
    edge_bits: Optional[jnp.ndarray] = None
                          # [n_edges] cumulative backhaul bits per edge
                          # aggregator (hierarchical aggregation only;
                          # None — an empty pytree leaf — for flat configs,
                          # so pre-hierarchy states are untouched)


def init_state(w0: jnp.ndarray, n_workers: int,
               n_edges: Optional[int] = None) -> FlecsState:
    """``n_edges`` allocates the hierarchical backhaul ledger — pass
    ``cfg.hierarchy.n_edges`` iff the config aggregates hierarchically."""
    d = w0.shape[0]
    return FlecsState(
        w=w0.astype(jnp.float32),
        h=jnp.zeros((n_workers, d), jnp.float32),
        B=jnp.zeros((n_workers, d, d), jnp.float32),
        k=jnp.zeros((), jnp.int32),
        bits_per_node=jnp.zeros((n_workers,), bits_dtype()),
        edge_bits=None if n_edges is None else init_edge_bits(n_edges),
    )


def _round_bits(grad_spec: CompressorSpec, hess_spec: CompressorSpec,
                d: int, m: int, use_kernel: bool = False):
    """Per-participating-worker uplink bits of one round (traced)."""
    return (spec_bits(grad_spec, d, use_kernel)      # c_k^i
            + spec_bits(hess_spec, d * m, use_kernel)  # C_k^i (dim-aware)
            + 32.0 * m * m)                          # M_k^i (float32)


def bits_per_round(cfg: FlecsConfig, d: int) -> float:
    """Deterministic per-participating-worker uplink bits of one round."""
    return float(_round_bits(make_spec(cfg.grad_compressor),
                             make_spec(cfg.hess_compressor), d, cfg.m,
                             cfg.use_kernel))


def hparams_round_bits(cfg: FlecsConfig, hp: FlecsHParams, d: int):
    """Per-participating-worker uplink bits of one round at EACH hparams
    grid point ([G] when the specs carry a grid axis) — the spec-aware
    price query behind plan-level bit budgets (``compressors.
    spec_bits_many`` handles family-stacked axes).  ``bits_per_round`` is
    this at the ``hparams_from_config`` point."""
    return (spec_bits_many(hp.grad_spec, d)
            + spec_bits_many(hp.hess_spec, d * cfg.m)
            + 32.0 * cfg.m * cfg.m)


def _worker_messages(local_grad: Callable, local_hvp: Callable,
                     grad_spec: CompressorSpec, hess_spec: CompressorSpec,
                     w, h, B, S, k_g, k_h, k_q, k_c,
                     use_kernel: bool = False, ids=None,
                     n_total: Optional[int] = None,
                     fold_keys: bool = False):
    """Worker compute phase of Algorithm 1, vmapped over the federation.

    Returns (c_all [n,d], M_all [n,m,m], C_all [n,d,m], BS_all [n,d,m]) at
    the current iterate ``w`` against the current shifts/approximations —
    shared verbatim by the synchronous round and the async (buffered) step,
    so the two consume identical key streams and are trace-equivalent at
    zero delay.  The compressor specs may be traced (sweep axes);
    ``use_kernel`` (static) selects the fused Pallas compressor path.

    ids/n_total: the sharded and cohort engines compute a SUBSET of the
    federation's rows (a device's contiguous block / a sampled cohort) —
    they pass the rows' GLOBAL worker ids plus the registered population
    size, and each row draws the exact per-worker keys the dense engine
    would (``split(k, n_total)`` rows, gathered by id), so a block's
    messages match the dense run bit-for-bit.  ``fold_keys=True`` (cohort
    at population scale) derives compressor keys by ``fold_in(k, id)``
    instead — O(rows) with no [n_total] key array, matching how the
    gradient/HVP keys are already drawn (analysis rule R7).
    """
    n = h.shape[0]

    def worker(i, hk, Bk, kq, kc):
        with jax.named_scope(SCOPE_ORACLE):
            g = local_grad(w, i, jax.random.fold_in(k_g, i))
            Y = local_hvp(w, S, i, jax.random.fold_in(k_h, i))
            M = matmul(S.T, Y)                          # m x m (exact)
        with jax.named_scope(SCOPE_COMPRESS_GRAD):
            c = compress(grad_spec, kq, g - hk, use_kernel)   # grad diff
        with jax.named_scope(SCOPE_CURVATURE):
            BS = matmul(Bk, S)
        with jax.named_scope(SCOPE_COMPRESS_HESS):
            Cm = compress(hess_spec, kc, Y - BS, use_kernel)  # hess diff
        return c, M, Cm, BS

    if ids is None:
        ids = jnp.arange(n)
        ks_q = jax.random.split(k_q, n)
        ks_c = jax.random.split(k_c, n)
    elif fold_keys:
        ks_q = jax.vmap(lambda i: jax.random.fold_in(k_q, i))(ids)
        ks_c = jax.vmap(lambda i: jax.random.fold_in(k_c, i))(ids)
    else:
        if n_total is None:
            raise ValueError("explicit worker ids require n_total")
        ks_q = jax.random.split(k_q, n_total)[ids]
        ks_c = jax.random.split(k_c, n_total)[ids]
    return jax.vmap(worker)(ids, h, B, ks_q, ks_c)


@jax.named_scope(SCOPE_SERVER)
def _direction(cfg: FlecsConfig, g_tilde, Y_tilde, M_bar, B_bar):
    """Search-direction dispatch (Alg 4 variants / Alg 5) from the server
    aggregates — shared by the synchronous round and the async flush."""
    if cfg.direction == "truncated_inverse":
        if cfg.tinv_floor > 0:
            return truncated_inverse_direction_floored(
                B_bar, g_tilde, cfg.omega, cfg.Omega, cfg.tinv_floor)
        return truncated_inverse_direction(B_bar, g_tilde, cfg.omega,
                                           cfg.Omega)
    return fedsonia_direction(Y_tilde, M_bar, g_tilde, cfg.omega,
                              cfg.Omega, cfg.rho_val)


@jax.named_scope(SCOPE_CURVATURE)
def _update_B(cfg: FlecsConfig, beta, B, Y_tilde_i, M_all, S_of_t, t):
    """Per-worker Hessian-approximation update (Alg 2 / Alg 3), shared by
    the synchronous round and the async arrival path.  ``beta`` may be
    traced; ``S_of_t(t_i)`` regenerates each message's compute-time sketch
    (the L-SR1 path needs it; synchronous rounds pass the current sketch)."""
    if cfg.hessian_update == "direct":
        return jax.vmap(
            lambda Bk, Y, M: direct_update(Bk, Y, M, beta))(
                B, Y_tilde_i, M_all)
    return jax.vmap(
        lambda Bk, Y, M, ti: truncated_lsr1_update(
            Bk, Y, M, S_of_t(ti), cfg.omega)[0])(
                B, Y_tilde_i, M_all, t)


def _hierarchy_guards(cfg: FlecsConfig, hp, state, n: int) -> None:
    """Trace-time contract checks for hierarchical aggregation (shared by
    the dense/sharded and cohort rounds)."""
    if hp.edge_spec is None:
        raise ValueError(
            "FlecsConfig.hierarchy requires hparams carrying an edge_spec "
            "(hparams_from_config fills it from the config; grids pass "
            "edge_levels=...)")
    if state.edge_bits is None:
        raise ValueError(
            "FlecsConfig.hierarchy requires init_state(..., n_edges="
            "cfg.hierarchy.n_edges) so the backhaul ledger exists")
    validate_hierarchy(cfg.hierarchy, n)


@jax.named_scope(SCOPE_SERVER)
def _aggregate(cfg: FlecsConfig, hp, state, key, mask, mask_loc, g_i, Y_i,
               M_i, axis: Optional[str], n: int):
    """Server aggregation of one synchronous round: the masked means of
    the reconstructed messages, or the hierarchy's edge combine, over the
    full federation (rebuilt by ``all_gather`` under sharding).  Returns
    (g̃, Ỹ, M̄, active count, edge ledger)."""
    d, m = state.h.shape[1], cfg.m
    if axis is None:
        n_active = jnp.sum(mask)
    else:
        gather = lambda x: jax.lax.all_gather(x, axis, tiled=True)  # noqa: E731
        g_i, Y_i, M_i = gather(g_i), gather(Y_i), gather(M_i)
        # psum of per-device {0,1} counts: integer-exact, == jnp.sum(mask)
        n_active = jax.lax.psum(jnp.sum(mask_loc), axis)

    if cfg.hierarchy is None:
        return (masked_mean(g_i, mask), masked_mean(Y_i, mask),
                masked_mean(M_i, mask), n_active, state.edge_bits)
    _hierarchy_guards(cfg, hp, state, n)
    E = cfg.hierarchy.n_edges
    k_e = jax.random.fold_in(key, EDGE_SALT)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    g_sum, edge_active = edge_combine(
        hp.edge_spec, jax.random.fold_in(k_e, 0), g_i, mask, E,
        cfg.use_kernel)
    Y_sum, _ = edge_combine(hp.edge_spec, jax.random.fold_in(k_e, 1),
                            Y_i, mask, E, cfg.use_kernel)
    M_sum, _ = edge_combine(hp.edge_spec, jax.random.fold_in(k_e, 2),
                            M_i, mask, E, cfg.use_kernel)
    g_tilde, Y_tilde, M_bar = g_sum / denom, Y_sum / denom, M_sum / denom
    edge_bits_new = charge_edges(
        state.edge_bits, edge_active,
        edge_round_bits(hp.edge_spec, d, m, cfg.use_kernel))
    return g_tilde, Y_tilde, M_bar, n_active, edge_bits_new


def _flecs_round(cfg: FlecsConfig, local_grad: Callable, local_hvp: Callable,
                 hp: FlecsHParams, state: FlecsState, key,
                 axis: Optional[str] = None, n_total: Optional[int] = None):
    """One round of Algorithm 1 with client sampling.

    Every ``hp`` field may be traced (sweep path) or concrete (a single
    run at one hparams point); structural choices (m, Hessian
    update rule, direction, sampling kind, hierarchy shape) stay static
    from cfg.

    axis/n_total: under ``driver.run_sharded_sweep`` the state's worker
    leaves are one device's contiguous ``[n_local, ...]`` block of the
    ``n_total``-worker federation, with ``axis`` the mesh axis name.  The
    block computes its workers' messages against global ids and the global
    key stream, full-federation aggregates are reconstructed with
    ``lax.all_gather(tiled=True)`` and integer-exact totals with
    ``lax.psum``, and the server math runs replicated on the gathered
    arrays — the same ops on the same values as the dense round, which is
    the bit-for-bit equivalence contract.  ``axis=None`` is the dense
    engine, op-for-op as before.
    """
    n_loc, d = state.h.shape
    n = n_loc if axis is None else n_total
    m = cfg.m
    S = sketch(cfg.sketch_kind, d, m, state.k)          # shared via seed

    k_g, k_h, k_q, k_c, k_p = jax.random.split(key, 5)
    # full-federation mask — replicated (identical draw) on every device
    mask = resolve_participation(k_p, n, cfg.participation, cfg.sampling,
                                 hp.p)                                  # [n]
    if axis is None:
        ids, mask_loc = None, mask
    else:
        idx = jax.lax.axis_index(axis)
        ids = idx * n_loc + jnp.arange(n_loc)
        mask_loc = jax.lax.dynamic_slice_in_dim(mask, idx * n_loc, n_loc)

    c_all, M_all, C_all, BS_all = _worker_messages(
        local_grad, local_hvp, hp.grad_spec, hp.hess_spec,
        state.w, state.h, state.B, S, k_g, k_h, k_q, k_c,
        cfg.use_kernel, ids=ids, n_total=n)

    # --- per-worker server state (local rows under sharding) --------------
    with jax.named_scope(SCOPE_SERVER):
        g_tilde_i = c_all + state.h                      # [n_loc, d]
        Y_tilde_i = C_all + BS_all                       # [n_loc, d, m]

    B_upd = _update_B(cfg, hp.beta, state.B, Y_tilde_i, M_all,
                      lambda ti: S, jnp.zeros((n_loc,), jnp.float32))
    with jax.named_scope(SCOPE_CURVATURE):
        # only sampled workers communicated a Hessian difference this round
        B_new = jnp.where(mask_loc[:, None, None] > 0, B_upd, state.B)

    # --- full-federation aggregates (replicated under sharding) -----------
    g_tilde, Y_tilde, M_bar, n_active, edge_bits_new = _aggregate(
        cfg, hp, state, key, mask, mask_loc, g_tilde_i, Y_tilde_i, M_all,
        axis, n)

    # B̄ is server-side curvature state, not wire traffic — it stays a flat
    # mean under hierarchy, and the sharded engine only pays the [n, d, d]
    # gather when the direction actually consumes it
    with jax.named_scope(SCOPE_CURVATURE):
        if cfg.direction == "truncated_inverse" or axis is None:
            B_full = B_new if axis is None else jax.lax.all_gather(
                B_new, axis, tiled=True)
            B_bar = masked_mean(B_full, mask)
        else:
            B_bar = jnp.zeros((d, d), jnp.float32)

    p = _direction(cfg, g_tilde, Y_tilde, M_bar, B_bar)
    with jax.named_scope(SCOPE_SERVER):
        w_new = state.w + hp.alpha * p
        h_new = state.h + hp.gamma * mask_loc[:, None] * c_all

        round_bits = _round_bits(hp.grad_spec, hp.hess_spec, d, m,
                                 cfg.use_kernel)
        bits_new = (state.bits_per_node
                    + mask_loc.astype(state.bits_per_node.dtype) * round_bits)
        new_state = FlecsState(w_new, h_new, B_new, state.k + 1, bits_new,
                               edge_bits_new)
        aux = {"g_tilde_norm": jnp.linalg.norm(g_tilde),
               "dir_norm": jnp.linalg.norm(p),
               "n_active": n_active,
               "bits_per_node": new_state.bits_per_node}
    if edge_bits_new is not None:
        aux["edge_bits"] = edge_bits_new
    return new_state, aux


def make_flecs_sweep_step(cfg: FlecsConfig, local_grad: Callable,
                          local_hvp: Callable):
    """Build step(hp: FlecsHParams, state, key) -> (state, aux) whose step
    sizes, beta, and BOTH compressor specs are traced, for
    ``driver.run_sweep`` — the single round implementation; a single run
    is this step at one hparams point."""
    def step(hp: FlecsHParams, state: FlecsState, key) -> tuple:
        return _flecs_round(cfg, local_grad, local_hvp, hp, state, key)

    return step


# ---------------------------------------------------------------------------
# Sharded engine (device-mesh data parallelism over the worker axis)
# ---------------------------------------------------------------------------

def make_flecs_sharded_sweep_step(cfg: FlecsConfig, local_grad: Callable,
                                  local_hvp: Callable, n_total: int,
                                  axis: str = "workers"):
    """The sweep step for ``driver.run_sharded_sweep``: identical signature
    to ``make_flecs_sweep_step``'s, but the state's worker leaves are one
    device's contiguous block of the ``n_total``-worker federation and the
    round runs under a ``shard_map`` axis.  Bit-for-bit equal to the dense
    sweep step on the same key stream (see ``_flecs_round``)."""
    def step(hp: FlecsHParams, state: FlecsState, key) -> tuple:
        return _flecs_round(cfg, local_grad, local_hvp, hp, state, key,
                            axis=axis, n_total=n_total)

    return step


def sharded_state_specs(hierarchy: bool = False,
                        axis: str = "workers") -> FlecsState:
    """``driver.run_sharded_sweep`` state-spec tree for ``FlecsState``:
    per-worker leaves (h, B, bits_per_node) shard along the mesh axis, the
    iterate/counter (and the [n_edges] backhaul ledger, whose edges span
    devices) stay replicated."""
    return FlecsState(w="", h=axis, B=axis, k="", bits_per_node=axis,
                      edge_bits="" if hierarchy else None)


# ---------------------------------------------------------------------------
# Cohort engine (population-scale client subsampling)
# ---------------------------------------------------------------------------

class FlecsCohortState(NamedTuple):
    """Population-scale server state: O(N·d) persistent per-client arrays,
    O(d²) shared curvature — NEVER O(N·d²).

    The registered population N only appears in the per-client shift table
    ``h`` and the uplink ledger ``bits_per_node``; each round gathers the
    sampled cohort's rows, computes on [K, ...] arrays, and scatter-adds
    the updates back (distinct indices by construction, so the scatter is
    deterministic).  The Hessian approximation ``B`` is SHARED across
    clients (the population variant of Algorithm 1): per-client B is
    O(N·d²) — 4.6 TB at N=100k, d=24 — and the directions only ever
    consume aggregate curvature, so the cohort engine maintains the
    aggregate directly.
    """
    w: jnp.ndarray        # [d]
    h: jnp.ndarray        # [N, d]   per-client gradient shifts
    B: jnp.ndarray        # [d, d]   SHARED Hessian approximation
    k: jnp.ndarray        # iteration counter
    bits_per_node: jnp.ndarray   # [N] cumulative uplink bits per client
    edge_bits: Optional[jnp.ndarray] = None   # [n_edges] backhaul ledger


def init_cohort_state(w0: jnp.ndarray, n_total: int,
                      n_edges: Optional[int] = None) -> FlecsCohortState:
    d = w0.shape[0]
    return FlecsCohortState(
        w=w0.astype(jnp.float32),
        h=jnp.zeros((n_total, d), jnp.float32),
        B=jnp.zeros((d, d), jnp.float32),
        k=jnp.zeros((), jnp.int32),
        bits_per_node=jnp.zeros((n_total,), bits_dtype()),
        edge_bits=None if n_edges is None else init_edge_bits(n_edges),
    )


def make_flecs_cohort_sweep_step(cfg: FlecsConfig, local_grad: Callable,
                                 local_hvp: Callable, n_total: int,
                                 cohort: int):
    """Build the cohort-subsampled sweep step: each round draws a size-K
    cohort from the N-client population (``driver.cohort_indices`` —
    stratified, distinct ids), samples participation WITHIN the cohort,
    and materializes only [K, ...] per-round arrays, so per-round compute
    and memory are independent of N (analysis rule R7; the scaling claim
    ``benchmarks/scaling_bench.py`` gates).

    Key-stream notes: the round key splits exactly like the dense round;
    cohort selection folds ``COHORT_SALT`` into the participation key, and
    compressor keys are derived by ``fold_in(k, client_id)``
    (``_worker_messages(fold_keys=True)``) so no [N] key array ever
    exists.  At ``cohort == n_total`` the selection is the identity and
    the participation draw matches the dense engine bit-for-bit
    (tests/test_cohort.py pins this for an identity-compressor config,
    where the compressor key stream is unused).

    Restrictions (population variant): ``hessian_update="direct"`` only —
    the L-SR1 path replays per-message sketches against per-client state
    the shared-B variant does not keep.
    """
    if cfg.hessian_update != "direct":
        raise ValueError(
            "the cohort engine maintains a SHARED Hessian approximation "
            "and supports hessian_update='direct' only (L-SR1 needs "
            f"per-client state), got {cfg.hessian_update!r}")
    if not 1 <= cohort <= n_total:
        raise ValueError(f"cohort={cohort} must be in [1, {n_total}]")
    if n_total % cohort:
        raise ValueError(
            f"cohort={cohort} must divide the population {n_total} "
            "(stratified selection draws one client per contiguous "
            "stratum)")

    def step(hp: FlecsHParams, state: FlecsCohortState, key) -> tuple:
        d = state.w.shape[0]
        m = cfg.m
        S = sketch(cfg.sketch_kind, d, m, state.k)
        k_g, k_h, k_q, k_c, k_p = jax.random.split(key, 5)   # == dense split

        k_sel = jax.random.fold_in(k_p, COHORT_SALT)
        idx = cohort_indices(k_sel, n_total, cohort)          # [K] distinct
        # participation over the COHORT axis only — same key as the dense
        # draw, so cohort == n_total reproduces it bit-for-bit
        mask = resolve_participation(k_p, n_total, cfg.participation,
                                     cfg.sampling, hp.p, cohort=cohort)

        h_c = state.h[idx]                                    # [K, d]
        B_rows = jnp.broadcast_to(state.B, (cohort, d, d))
        c_c, M_c, C_c, BS_c = _worker_messages(
            local_grad, local_hvp, hp.grad_spec, hp.hess_spec,
            state.w, h_c, B_rows, S, k_g, k_h, k_q, k_c,
            cfg.use_kernel, ids=idx, n_total=n_total, fold_keys=True)

        g_tilde_i = c_c + h_c                                 # [K, d]
        Y_tilde_i = C_c + BS_c                                # [K, d, m]

        B_upd = _update_B(cfg, hp.beta, B_rows, Y_tilde_i, M_c,
                          lambda ti: S, jnp.zeros((cohort,), jnp.float32))
        # shared curvature: average the active cohort members' updated
        # approximations; an all-idle round leaves B untouched
        any_active = jnp.sum(mask) > 0
        B_new = jnp.where(any_active, masked_mean(B_upd, mask), state.B)

        if cfg.hierarchy is not None:
            _hierarchy_guards(cfg, hp, state, n_total)
            E = cfg.hierarchy.n_edges
            k_e = jax.random.fold_in(key, EDGE_SALT)
            denom = jnp.maximum(jnp.sum(mask), 1.0)
            g_sum, edge_active = edge_combine_cohort(
                hp.edge_spec, jax.random.fold_in(k_e, 0), g_tilde_i, mask,
                idx, n_total, E, cfg.use_kernel)
            Y_sum, _ = edge_combine_cohort(
                hp.edge_spec, jax.random.fold_in(k_e, 1), Y_tilde_i, mask,
                idx, n_total, E, cfg.use_kernel)
            M_sum, _ = edge_combine_cohort(
                hp.edge_spec, jax.random.fold_in(k_e, 2), M_c, mask,
                idx, n_total, E, cfg.use_kernel)
            g_tilde, Y_tilde, M_bar = (g_sum / denom, Y_sum / denom,
                                       M_sum / denom)
            edge_bits_new = charge_edges(
                state.edge_bits, edge_active,
                edge_round_bits(hp.edge_spec, d, m, cfg.use_kernel))
        else:
            g_tilde = masked_mean(g_tilde_i, mask)
            Y_tilde = masked_mean(Y_tilde_i, mask)
            M_bar = masked_mean(M_c, mask)
            edge_bits_new = state.edge_bits

        p = _direction(cfg, g_tilde, Y_tilde, M_bar, B_new)
        w_new = state.w + hp.alpha * p

        # scatter the cohort's updates back into the persistent per-client
        # arrays — idx rows are distinct by construction, so .at[].add is
        # deterministic
        h_new = state.h.at[idx].add(hp.gamma * mask[:, None] * c_c)
        round_bits = _round_bits(hp.grad_spec, hp.hess_spec, d, m,
                                 cfg.use_kernel)
        bits_new = state.bits_per_node.at[idx].add(
            mask.astype(state.bits_per_node.dtype) * round_bits)

        new_state = FlecsCohortState(w_new, h_new, B_new, state.k + 1,
                                     bits_new, edge_bits_new)
        aux = {"g_tilde_norm": jnp.linalg.norm(g_tilde),
               "dir_norm": jnp.linalg.norm(p),
               "n_active": jnp.sum(mask),
               "cohort_bits": jnp.sum(
                   mask.astype(state.bits_per_node.dtype) * round_bits)}
        if edge_bits_new is not None:
            aux["edge_bits"] = edge_bits_new
        return new_state, aux

    return step


# ---------------------------------------------------------------------------
# Asynchronous buffered aggregation (FedBuff-style staleness)
# ---------------------------------------------------------------------------

def async_hparam_grid(taus, buffer_ks, *, alpha=1.0, gamma=1.0, beta=1.0,
                      grad_s=64.0, hess_s=64.0, ps=None,
                      auto_damp=None) -> AsyncHParams:
    """Cartesian (tau × buffer_k [× p]) staleness grid, [G] leaves.

    ps: optional traced Bernoulli participation axis (requires a config
    with ``sampling="bernoulli"``); None keeps the static config path.

    auto_damp: optional ``(sampled_frac, n_workers)`` — per-point alpha
    becomes ``driver.damped_alpha(alpha, sampled_frac, K_eff, n_workers)``,
    so the grid stops needing hand-tuned async step sizes.  The damping
    count is the number of updates a flush actually averages: at tau=0 the
    whole sampled cohort (round(p·n) messages) lands at once, so a flush
    can never average fewer than that and K_eff = max(K, round(p·n)) —
    matching the synchronous engine the tau=0 point collapses to; delayed
    points trickle arrivals (busy-exclusion staggers the cohort) and keep
    K_eff = K.  With a ``ps`` axis the damping uses each point's own p.
    """
    validate_ps(ps)
    t, K, p = jnp.meshgrid(
        jnp.asarray(taus, jnp.int32), jnp.asarray(buffer_ks, jnp.float32),
        jnp.asarray([1.0] if ps is None else ps, jnp.float32),
        indexing="ij")
    t, K, p = t.ravel(), K.ravel(), p.ravel()
    G = t.shape[0]
    if auto_damp is not None:
        frac, n_workers = auto_damp
        if ps is None:
            cohort = jnp.float32(max(1, round(frac * n_workers)))
            frac_pt = frac
        else:
            cohort = jnp.maximum(1.0, jnp.round(p * n_workers))
            frac_pt = p
        K_eff = jnp.where(t == 0, jnp.maximum(K, cohort), K)
        alphas = damped_alpha(alpha, frac_pt, K_eff, n_workers)
    else:
        alphas = jnp.full((G,), alpha, jnp.float32)
    full = lambda v: jnp.full((G,), v, jnp.float32)     # noqa: E731
    hp = FlecsHParams(alphas, full(gamma), full(beta),
                      dither_spec(full(grad_s)), dither_spec(full(hess_s)),
                      None if ps is None else p)
    return AsyncHParams(hp, t, K)


def make_flecs_async_hooks(cfg: FlecsConfig, local_grad: Callable,
                           local_hvp: Callable) -> AsyncHooks:
    """FLECS-CGD's part of the buffered-async engine
    (``driver.make_async_step``).  A sampled worker's (c, Ỹ, M) is
    computed as in the synchronous round (``_worker_messages``); on
    arrival its shift h^i and approximation B^i learn and its bits are
    billed; a flush takes the direction from the buffered means.

    Stale-curvature note: FedSONIA consumes Ỹ/M̄ means over messages from
    *different* compute rounds (different sketches S_t) — exactly the
    staleness a real async federation sees.  The L-SR1 path regenerates
    each message's compute-time sketch from its buffered round stamp.
    """
    m = cfg.m

    def compute(hp: FlecsHParams, state: FlecsState, keys):
        k_g, k_h, k_q, k_c = keys
        # the sketch is drawn outside the send gate, as the synchronous
        # round draws it: inside the gated branch XLA fuses the m = 1
        # products differently and the tau = 0 run leaves the sync one
        S = sketch(cfg.sketch_kind, state.h.shape[1], m, state.k)

        def work():
            c, M, C, BS = _worker_messages(
                local_grad, local_hvp, hp.grad_spec, hp.hess_spec,
                state.w, state.h, state.B, S, k_g, k_h, k_q, k_c,
                cfg.use_kernel)
            return {"c": c, "Y": C + BS, "M": M}

        return work

    def arrive(hp: FlecsHParams, state: FlecsState, msg, arrived):
        d = state.h.shape[1]

        def update_B(_):
            upd = _update_B(
                cfg, hp.beta, state.B, msg["Y"], msg["M"],
                lambda ti: sketch(cfg.sketch_kind, d, m,
                                  ti.astype(jnp.int32)), msg["t"])
            return jnp.where(arrived[:, None, None] > 0, upd, state.B)

        B_new = jax.lax.cond(jnp.any(arrived > 0), update_B,
                             lambda _: state.B, None)
        h_new = state.h + hp.gamma * arrived[:, None] * msg["c"]
        round_bits = _round_bits(hp.grad_spec, hp.hess_spec, d, m,
                                 cfg.use_kernel)
        bits_new = (state.bits_per_node
                    + arrived.astype(state.bits_per_node.dtype) * round_bits)
        return (state._replace(h=h_new, B=B_new, bits_per_node=bits_new),
                {"g": msg["c"] + state.h, "Y": msg["Y"], "M": msg["M"],
                 "B": B_new})

    def flush(hp: FlecsHParams, state: FlecsState, means, flush):
        # lax.cond so the direction runs only on flush rounds (a
        # tau-round buffered run flushes every ~tau+1 rounds)
        def flush_step(_):
            p = _direction(cfg, means["g"], means["Y"], means["M"],
                           means["B"])
            return state.w + hp.alpha * p, jnp.linalg.norm(p)

        w, dir_norm = jax.lax.cond(
            flush, flush_step,
            lambda _: (state.w, jnp.zeros((), state.w.dtype)), None)
        return w, {"dir_norm": dir_norm}

    return AsyncHooks(cfg, 5, compute, arrive, flush)
