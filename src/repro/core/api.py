"""Declarative method registry + ``ExperimentPlan``: every optimizer behind
one sweep-native API, one compiled program per figure.

FLECS-CGD's headline claims are *comparisons* — FLECS vs FLECS-CGD vs DIANA
vs FedNL vs GD under shared compression and participation budgets.  This
module makes a whole comparison figure a single declarative object:

* :class:`MethodSpec` — a method as *data*: ``init(problem, n, cfg)``, one
  sweep-native ``step(hp, state, key)``, an hparam pytree with
  ``grid(...)`` / ``from_config(...)`` constructors, and its hooks into
  the driver's one buffered-async engine (``driver.make_async_step``).
  :func:`get_method` resolves ``"flecs" | "flecs_cgd" | "diana" |
  "fednl" | "gd"``; a single run is the same sweep step at one hparams
  point, so the registry changes no numerics.
* :class:`MethodRun` — one *structural segment* of a figure: a method, its
  static config (sampling kind, FLECS's sketch size m, FedNL's μ — the
  things that change array shapes or code paths), and a [G] hparam grid
  whose leaves are traced sweep axes (step sizes, ``CompressorSpec``s —
  including the *family* axis via ``compressors.stack_specs`` — and the
  Bernoulli participation probability ``p``).
* :class:`ExperimentPlan` + :func:`run_plan` — a tuple of runs plus
  (iters, staleness, record_every, trace_dtype), lowered to ONE jitted
  program: each run is a ``driver.sweep_program`` (the unjitted
  ``run_sweep``), and all of them are composed inside a single ``jax.jit``
  — so a figure that previously compiled 8 programs (fig1: 4 sketch sizes
  × 2 methods) compiles exactly one, with the method axis traced.
* ``ExperimentPlan.bit_budget`` — budget-fair comparisons: a (grid of)
  per-node uplink bit budget(s) crossed with every run's hparam axis
  (:func:`cross_bit_budget`) and enforced by the budget-freeze scan mode
  (``driver.freeze_on_bit_budget``): each grid point steps until its
  cumulative ledger reaches its traced budget, then lax.select-freezes —
  equal transmitted bits across methods with different wire prices, still
  ONE compiled program, with scan lengths auto-derived from the methods'
  ``round_bits`` price queries (``driver.iters_for_bit_budget``).

Key streams (reproducibility contract): run ``j`` of a plan sweeps with
``fold_in(key(plan.seed), j)``, and its grid point ``g`` consumes the
stream ``split(split(fold_in(key(seed), j), G)[g], iters)`` — exactly what
a standalone ``run_experiment(step_g, state, split(fold_in(key, j), G)[g],
iters)`` would use.  tests/test_api.py pins ``run_plan`` against
single-point runs of each method with exact bit ledgers for all five.

Compile accounting: every :func:`run_plan` call jits ONE fresh program
whose trace increments :func:`plan_compiles` — the one-compile-per-figure
invariant the tests and the CI plan-smoke step assert on (a plan that
secretly retraced would bump the counter twice).

Authoring a plan::

    from repro.core.api import ExperimentPlan, MethodRun, get_method, run_plan
    from repro.core.compressors import stack_specs
    from repro.core.flecs import FlecsConfig
    from repro.data.logreg import make_problem

    prob = make_problem(d=123, n_workers=20, r=64, mu=1e-3)

    # (1) five methods, default grids, one compiled program:
    plan = ExperimentPlan(
        problem=prob,
        runs=tuple(MethodRun(m) for m in
                   ("flecs", "flecs_cgd", "diana", "fednl", "gd")),
        iters=200)
    result = run_plan(plan)
    result.traces["flecs_cgd"]["F"]          # [G, iters] objective traces

    # (2) a participation ablation as ONE vmapped axis (traced Bernoulli p):
    flecs_cgd = get_method("flecs_cgd")
    plan = ExperimentPlan(
        problem=prob,
        runs=(MethodRun("flecs_cgd",
                        hparams=flecs_cgd.grid(ps=(1.0, 0.5, 0.25))),),
        iters=300)

    # (3) FLECS vs FLECS-CGD as a traced compressor-FAMILY axis:
    hp = flecs_cgd.grid(grad_specs=stack_specs("identity", "dither64"))
    plan = ExperimentPlan(problem=prob,
                          runs=(MethodRun("flecs_cgd", hparams=hp),))
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flecs
from repro.core.compressors import make_spec
from repro.core.driver import (AsyncHParams, StalenessSchedule, bits_dtype,
                               check_buffer_slots, hparams_bit_budget,
                               init_async_state, iters_for_bit_budget,
                               make_async_step, sweep_keys, sweep_program)
from repro.core.traffic import (TrafficModel, init_traffic_state,
                                traffic_hparams)
from repro.optim import baselines


# ---------------------------------------------------------------------------
# MethodSpec registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MethodSpec:
    """A federated method as data — everything :func:`run_plan` needs.

    name:            registry key.
    config_cls:      static-config dataclass (structural choices).
    default_config:  () -> cfg.
    init:            (problem, n_workers, cfg) -> initial sweep state
                     (shared by every grid point; iterate starts at 0).
    sweep_step:      (problem, cfg) -> step(hp, state, key) with every hp
                     field traced (``driver.run_sweep``-compatible).
    grid:            keyword axes -> [G] hparam pytree (cartesian).
    from_config:     (cfg) -> scalar hparam point (a single run's hparams).
    async_hooks:     (problem, cfg) -> ``driver.AsyncHooks``, the method's
                     part of the buffered-async engine
                     (``driver.make_async_step``); None => the method has
                     no async variant.
    round_bits:      (problem, cfg, hp) -> per-participating-worker uplink
                     bits of one round at each grid point ([G]) — the
                     spec-aware wire-price query ``plan.bit_budget`` uses
                     to choose scan lengths (None => budget plans must
                     pass ``run.iters`` explicitly).
    """
    name: str
    config_cls: type
    default_config: Callable[[], Any]
    init: Callable[[Any, int, Any], Any]
    sweep_step: Callable[[Any, Any], Callable]
    grid: Callable[..., Any]
    from_config: Callable[[Any], Any]
    async_hooks: Optional[Callable] = None
    round_bits: Optional[Callable] = None


def _flecs_grid(alphas=(1.0,), gammas=(1.0,), betas=(1.0,),
                grad_levels=(64.0,), hess_levels=(64.0,), ps=None,
                grad_specs=None, hess_specs=None,
                edge_levels=None) -> flecs.FlecsHParams:
    """FLECS grid with optional explicit spec arguments.

    ``grad_specs`` / ``hess_specs`` take a ``CompressorSpec``:
    * a [K] stacked spec (``compressors.stack_specs``) REPLACES the
      dithering-level axis with a K-point axis — the compressor *family*
      as a grid axis (the other axes must then be scalar);
    * a scalar spec pins the compressor for every grid point (e.g.
      ``identity`` gradients for plain FLECS while ``ps`` sweeps).

    ``edge_levels`` adds the traced backhaul-compression axis of
    hierarchical aggregation (requires a cfg with ``hierarchy`` set; see
    ``flecs.hparam_grid``).
    """
    if grad_specs is None and hess_specs is None:
        return flecs.hparam_grid(alphas, gammas, grad_levels, betas=betas,
                                 hess_levels=hess_levels, ps=ps,
                                 edge_levels=edge_levels)
    hp = flecs.hparam_grid(alphas, gammas, grad_levels, betas=betas,
                           hess_levels=hess_levels, ps=ps)
    # an explicit spec REPLACES its slot's level axis — a multi-point
    # level axis alongside it would be silently discarded
    if grad_specs is not None and len(grad_levels) > 1:
        raise ValueError("grad_levels and grad_specs are mutually "
                         "exclusive ways to set the gradient compressor")
    if hess_specs is not None and len(hess_levels) > 1:
        raise ValueError("hess_levels and hess_specs are mutually "
                         "exclusive ways to set the Hessian compressor")
    G = hp.alpha.shape[0]
    Ks = [jax.tree.leaves(s)[0].shape[0]
          for s in (grad_specs, hess_specs)
          if s is not None and jax.tree.leaves(s)[0].ndim > 0]
    if len(set(Ks)) > 1:
        raise ValueError(f"grad_specs/hess_specs axes disagree: {Ks}")
    K = Ks[0] if Ks else 1
    if K > 1 and G > 1:
        raise ValueError(
            "a stacked spec axis replaces the level axes: pass scalar "
            "level/alpha/p axes (or build the FlecsHParams pytree "
            f"directly) — got a level grid of size {G}")
    Gf = max(G, K)

    def fix(spec, default):
        if spec is None:
            spec = default                   # the level-grid dither specs
        return jax.tree.map(
            lambda a: jnp.broadcast_to(jnp.asarray(a), (Gf,)), spec)

    scal = lambda a: jnp.broadcast_to(a, (Gf,))            # noqa: E731
    hp = flecs.FlecsHParams(
        scal(hp.alpha), scal(hp.gamma), scal(hp.beta),
        fix(grad_specs, hp.grad_spec), fix(hess_specs, hp.hess_spec),
        None if hp.p is None else scal(hp.p))
    if edge_levels is None:
        return hp
    # cross with the backhaul axis, base-major (as flecs.hparam_grid does)
    from repro.core.compressors import dither_spec
    E = len(edge_levels)
    hp = jax.tree.map(lambda leaf: jnp.repeat(leaf, E, axis=0), hp)
    tiled = jnp.tile(jnp.asarray(edge_levels, jnp.float32), Gf)
    return hp._replace(edge_spec=dither_spec(tiled))


def _flecs_spec(name: str, default_grad: str) -> MethodSpec:
    def default_config():
        return flecs.FlecsConfig(grad_compressor=default_grad)

    def grid(alphas=(1.0,), gammas=(1.0,), betas=(1.0,), grad_levels=None,
             hess_levels=(64.0,), ps=None, grad_specs=None,
             hess_specs=None, edge_levels=None):
        """:func:`_flecs_grid` with the gradient compressor defaulting to
        THIS method's own — ``get_method("flecs").grid(...)`` sweeps with
        identity gradients, not FLECS-CGD's dither64."""
        if grad_levels is None and grad_specs is None:
            grad_specs = make_spec(default_grad)
        return _flecs_grid(
            alphas, gammas, betas,
            grad_levels if grad_levels is not None else (64.0,),
            hess_levels, ps, grad_specs, hess_specs, edge_levels)

    return MethodSpec(
        name=name,
        config_cls=flecs.FlecsConfig,
        default_config=default_config,
        init=lambda prob, n, cfg: flecs.init_state(
            jnp.zeros(prob.d), n,
            n_edges=None if cfg.hierarchy is None
            else cfg.hierarchy.n_edges),
        sweep_step=lambda prob, cfg: flecs.make_flecs_sweep_step(
            cfg, *prob.make_oracles()),
        grid=grid,
        from_config=flecs.hparams_from_config,
        async_hooks=lambda prob, cfg: flecs.make_flecs_async_hooks(
            cfg, *prob.make_oracles()),
        round_bits=lambda prob, cfg, hp: flecs.hparams_round_bits(
            cfg, hp, prob.d),
    )


def _local_hessian(prob):
    return lambda w, i: jax.hessian(lambda ww: prob.local_loss(ww, i))(w)


_REGISTRY: Dict[str, MethodSpec] = {}


def register_method(spec: MethodSpec) -> MethodSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"method {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def get_method(name: str) -> MethodSpec:
    """Resolve a registry name ("flecs", "flecs_cgd", "diana", "fednl",
    "gd") to its :class:`MethodSpec`."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown method {name!r}; registered: "
                         f"{sorted(_REGISTRY)}") from None


def method_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_method(_flecs_spec("flecs", "identity"))
register_method(_flecs_spec("flecs_cgd", "dither64"))

register_method(MethodSpec(
    name="diana",
    config_cls=baselines.DianaConfig,
    default_config=baselines.DianaConfig,
    init=lambda prob, n, cfg: baselines.init_diana(jnp.zeros(prob.d), n),
    sweep_step=lambda prob, cfg: baselines.make_diana_sweep_step(
        cfg, prob.make_oracles()[0]),
    grid=baselines.diana_hparam_grid,
    from_config=baselines.diana_hparams_from_config,
    async_hooks=lambda prob, cfg: baselines.make_diana_async_hooks(
        cfg, prob.make_oracles()[0]),
    round_bits=lambda prob, cfg, hp: baselines.diana_round_bits(
        cfg, hp, prob.d),
))

register_method(MethodSpec(
    name="fednl",
    config_cls=baselines.FedNLConfig,
    default_config=baselines.FedNLConfig,
    init=lambda prob, n, cfg: baselines.init_fednl(jnp.zeros(prob.d), n),
    sweep_step=lambda prob, cfg: baselines.make_fednl_sweep_step(
        cfg, prob.make_oracles()[0], _local_hessian(prob)),
    grid=baselines.fednl_hparam_grid,
    from_config=baselines.fednl_hparams_from_config,
    async_hooks=lambda prob, cfg: baselines.make_fednl_async_hooks(
        cfg, prob.make_oracles()[0], _local_hessian(prob)),
    round_bits=lambda prob, cfg, hp: baselines.fednl_round_bits(
        cfg, hp, prob.d),
))

register_method(MethodSpec(
    name="gd",
    config_cls=baselines.GDConfig,
    default_config=baselines.GDConfig,
    init=lambda prob, n, cfg: baselines.init_gd(jnp.zeros(prob.d), n),
    sweep_step=lambda prob, cfg: baselines.make_gd_sweep_step(
        cfg, prob.make_oracles()[0], prob.n_workers),
    grid=baselines.gd_hparam_grid,
    from_config=baselines.gd_hparams_from_config,
    async_hooks=lambda prob, cfg: baselines.make_gd_async_hooks(
        cfg, prob.make_oracles()[0], prob.n_workers),
    round_bits=lambda prob, cfg, hp: baselines.gd_round_bits(
        cfg, hp, prob.d),
))


# ---------------------------------------------------------------------------
# ExperimentPlan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MethodRun:
    """One structural segment of a plan.

    method:  registry name or a :class:`MethodSpec`.
    cfg:     static config (None => the method's default).
    hparams: [G] hparam pytree (None => ``from_config(cfg)`` as a [1]
             grid).  For async plans this may already be the method's
             async hparams (carrying ``tau``); a sync pytree is wrapped
             with the plan's (staleness.tau, buffer_k).
    iters:   per-run override of the plan's round count (e.g. FedNL's
             shorter budget in the baselines figure).
    label:   result key (defaults to the method name, deduplicated).
    """
    method: Union[str, MethodSpec]
    cfg: Any = None
    hparams: Any = None
    iters: Optional[int] = None
    label: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class ExperimentPlan:
    """A figure as data: problem + method runs + schedule knobs.

    record:      optional (state) -> dict of extra in-scan trace entries;
                 defaults to ``problem.metrics(state.w)``.
    staleness:   a ``StalenessSchedule`` switches every run to its async
                 engine (all five registry methods have one — async FedNL
                 included; a custom MethodSpec without one fails loudly),
                 with ``buffer_k`` the FedBuff flush threshold broadcast
                 over each run's grid.
    traffic:     an optional ``repro.core.traffic.TrafficModel`` layered
                 on every run's async engine (requires ``staleness``):
                 arrival process, availability chain, and admission policy.
                 The lowering threads the model statically into each async
                 step, broadcasts its traced leaves
                 (``traffic_hparams(model)``) over each run's [G] grid
                 (unless the run's async hparams already carry their own
                 ``traffic`` leaves), and seeds the per-worker availability
                 state — so a traffic-profile comparison is still ONE
                 compiled program.
    bit_budget:  a per-node uplink bit budget (scalar) or a budget GRID
                 (sequence) — budget-fair mode.  The axis is crossed with
                 every run's hparam grid (point ``b*G + g`` pairs budget b
                 with grid point g) and traced through the budget-freeze
                 scan mode (``driver.freeze_on_bit_budget``): each point
                 steps until its cumulative ledger reaches its budget,
                 then freezes — so methods with different wire prices run
                 "to the same budget" inside the plan's single compiled
                 program.  Runs without an explicit ``iters`` get a
                 spec-aware upper-bound scan length from
                 ``driver.iters_for_bit_budget`` (prices via each method's
                 ``round_bits`` query, stretched by 1/p_min for client
                 sampling and (tau+1) for async arrival billing).
    """
    problem: Any
    runs: Sequence[MethodRun]
    iters: int = 200
    seed: int = 0
    record_every: int = 1
    trace_dtype: Any = None
    record: Optional[Callable] = None
    staleness: Optional[StalenessSchedule] = None
    buffer_k: float = 1.0
    bit_budget: Any = None
    traffic: Optional[TrafficModel] = None


@dataclasses.dataclass
class PlanResult:
    """run_plan output: per-run final sweep states / traces / hparams,
    keyed by run label (leading [G] grid axis on every array), and the
    plan's compiled program (``compiled.as_text()`` is its HLO).

    Host seconds: ``compile_s`` traces, lowers and compiles the program
    (or loads it from the persistent cache), ``run_s`` executes it once
    until its outputs are ready; ``seconds`` is their sum.  A profiler
    trace shows the two as the host spans ``plan.compile`` and
    ``plan.run``."""
    labels: Tuple[str, ...]
    states: Dict[str, Any]
    traces: Dict[str, Any]
    hparams: Dict[str, Any]
    seconds: float
    compiled: Any = None
    compile_s: float = 0.0
    run_s: float = 0.0

    def __getitem__(self, label: str):
        return self.states[label], self.traces[label]


# One-compile-per-figure accounting.  "traces" increments inside the plan
# program's Python body, which only runs when jax (re)traces it — i.e.
# once per compile; "programs" counts run_plan calls.  The invariant the
# tests assert: traces advances by exactly 1 per run_plan.
_STATS = {"programs": 0, "traces": 0}


def plan_compiles() -> int:
    """Number of plan-program compiles (traces) since import/reset."""
    return _STATS["traces"]


def plan_programs() -> int:
    return _STATS["programs"]


def reset_plan_stats() -> None:
    _STATS["programs"] = 0
    _STATS["traces"] = 0


def _grid_size(hp) -> int:
    leaves = jax.tree.leaves(hp)
    sizes = {leaf.shape[0] for leaf in leaves}
    if len(sizes) != 1:
        raise ValueError(
            f"hparam leaves disagree on the grid axis: sizes {sorted(sizes)}")
    return sizes.pop()


def _validate_p(spec: MethodSpec, cfg, hp) -> None:
    p = getattr(hp, "hp", hp)
    p = getattr(p, "p", None)
    if p is None:
        return
    if getattr(cfg, "sampling", "bernoulli") != "bernoulli":
        raise ValueError(
            f"run {spec.name!r}: a traced participation axis requires "
            f"sampling='bernoulli', got {cfg.sampling!r}")
    from repro.core.driver import _concrete_nonpositive
    if _concrete_nonpositive(jnp.asarray(p)):
        raise ValueError(
            f"run {spec.name!r}: participation p must be > 0, got "
            f"{np.asarray(p)}")


def cross_bit_budget(hp, budgets):
    """Cross a [B] bit-budget axis with an hparam grid's [G] points.

    Returns (hparams', budgets') with [B*G] leaves: point ``b*G + g``
    pairs ``budgets[b]`` with grid point g.  Works on sync and async
    hparam pytrees — the budget always lands on the sync hparams'
    ``bit_budget`` slot, where ``driver.freeze_on_bit_budget`` reads it.
    Budgets are cast to ``driver.bits_dtype()`` to match the ledger they
    gate (f32 loses integer bit counts past 2^24 — reachable on the
    d=20958 problems, which is why the ledgers go f64 under x64).
    """
    budgets = jnp.atleast_1d(jnp.asarray(budgets, bits_dtype()))
    G = _grid_size(hp)
    tiled = jax.tree.map(
        lambda a: jnp.tile(a, (budgets.shape[0],) + (1,) * (a.ndim - 1)), hp)
    bud = jnp.repeat(budgets, G)
    if hasattr(tiled, "bit_budget"):
        return tiled._replace(bit_budget=bud), bud
    inner = getattr(tiled, "hp", None)
    if inner is not None and hasattr(inner, "bit_budget"):
        return tiled._replace(hp=inner._replace(bit_budget=bud)), bud
    raise ValueError(
        f"hparams {type(hp).__name__} carry no bit_budget slot")


def _budget_scan_len(spec: MethodSpec, plan: ExperimentPlan, cfg, hp,
                     bud) -> int:
    """Spec-aware upper bound on the rounds a budget run can charge:
    ``iters_for_bit_budget`` over the (budget × wire-price) grid,
    stretched by 1/p_min under client sampling (a worker only pays on
    sampled rounds) and by (tau+1) for async arrival billing
    (busy-exclusion spaces a worker's messages tau+1 rounds apart) —
    exact for full-participation sync runs, a heuristic bound for the
    stochastic cases (pin ``run.iters`` to override)."""
    sync = getattr(hp, "hp", hp)
    if spec.round_bits is None:
        raise ValueError(
            f"method {spec.name!r} has no round_bits price query; pass "
            "run.iters explicitly to combine it with plan.bit_budget")
    prices = np.asarray(spec.round_bits(plan.problem, cfg, sync), float)
    iters = iters_for_bit_budget(np.asarray(bud), prices)
    p_axis = getattr(sync, "p", None)
    p_min = (float(np.min(np.asarray(p_axis))) if p_axis is not None
             else float(getattr(cfg, "participation", 1.0)))
    if p_min < 1.0:
        iters = int(np.ceil(iters / p_min))
    if hasattr(hp, "tau"):
        tau_max = int(jnp.max(hp.tau))
        iters = iters * (tau_max + 1) + tau_max
    return iters


def _resolve(plan: ExperimentPlan, run: MethodRun):
    spec = run.method if isinstance(run.method, MethodSpec) else get_method(
        run.method)
    cfg = run.cfg if run.cfg is not None else spec.default_config()
    if not isinstance(cfg, spec.config_cls):
        raise TypeError(
            f"run {spec.name!r}: cfg must be a {spec.config_cls.__name__}, "
            f"got {type(cfg).__name__}")
    hp = run.hparams
    if hp is None:
        hp = jax.tree.map(lambda a: jnp.asarray(a)[None],
                          spec.from_config(cfg))
    _validate_p(spec, cfg, hp)
    bud = None
    if plan.bit_budget is not None:
        if hparams_bit_budget(hp) is not None:
            raise ValueError(
                f"run {spec.name!r}: hparams already carry a bit_budget "
                "axis — drop plan.bit_budget or the hparams axis")
        budgets = np.atleast_1d(np.asarray(plan.bit_budget, np.float64))
        if budgets.ndim != 1 or np.any(budgets <= 0):
            raise ValueError(
                "plan.bit_budget must be a positive scalar or a 1-D grid "
                f"of positive budgets, got {np.asarray(plan.bit_budget)}")
        hp, bud = cross_bit_budget(hp, budgets)
    n = plan.problem.n_workers
    if plan.staleness is not None:
        if spec.async_hooks is None:
            raise ValueError(
                f"method {spec.name!r} has no async variant — drop it from "
                "the plan or clear plan.staleness")
        sched = plan.staleness
        hooks = spec.async_hooks(plan.problem, cfg)
        step = make_async_step(hooks, sched.kind, sched.q, plan.traffic)
        state = init_async_state(hooks, spec.init(plan.problem, n, cfg),
                                 spec.from_config(cfg), sched.max_delay)
        if not isinstance(hp, AsyncHParams):
            # the plan's staleness point, broadcast over the run's grid
            G = _grid_size(hp)
            hp = AsyncHParams(hp, jnp.full((G,), sched.tau, jnp.int32),
                              jnp.full((G,), plan.buffer_k, jnp.float32))
        if plan.traffic is not None:
            # seed the availability chain and broadcast the model's traced
            # leaves over the run's [G] grid (a run whose async hparams
            # already carry traffic leaves keeps its own — e.g. a traffic
            # sweep built by hand)
            state = state._replace(traffic=init_traffic_state(n))
            if getattr(hp, "traffic", None) is None:
                thp = traffic_hparams(plan.traffic)
                G = _grid_size(hp)
                hp = hp._replace(traffic=jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (G,) + a.shape), thp))
        check_buffer_slots(hp, state)
    else:
        if plan.traffic is not None:
            raise ValueError(
                "plan.traffic rides the async engine's buffered path — set "
                "plan.staleness (tau=0 for synchronous-delay traffic) or "
                "drop the traffic model")
        if isinstance(hp, AsyncHParams):
            raise ValueError(
                f"run {spec.name!r}: async hparams (tau/buffer_k axes) "
                "require plan.staleness — set a StalenessSchedule or pass "
                "sync hparams")
        step = spec.sweep_step(plan.problem, cfg)
        state = spec.init(plan.problem, n, cfg)
    if run.iters is not None:
        iters = run.iters
    elif bud is not None:
        # budget-fair mode: the scan length is a spec-aware upper bound,
        # NOT a per-method round count — the traced freeze equalizes the
        # actual budgets inside the program
        iters = _budget_scan_len(spec, plan, cfg, hp, bud)
        if plan.record_every > 1:
            iters = -(-iters // plan.record_every) * plan.record_every
    else:
        iters = plan.iters
    return spec, cfg, hp, step, state, iters


def run_plan(plan: ExperimentPlan) -> PlanResult:
    """Lower a plan to ONE compiled program and execute it.

    Every run becomes a ``driver.sweep_program`` (a vmapped lax.scan over
    its [G] hparam grid); all runs are composed inside a single ``jax.jit``
    call, so the whole figure — any mix of methods, sketch sizes, traced
    compressor families, and participation axes — costs exactly one
    compilation (see :func:`plan_compiles`).

    Returns a :class:`PlanResult`; run j, grid point g reproduces the
    standalone ``run_experiment`` with key
    ``split(fold_in(key(plan.seed), j), G)[g]`` bit-for-bit.
    """
    if not plan.runs:
        raise ValueError("plan has no runs")
    record = plan.record
    if record is None:
        prob = plan.problem
        record = lambda st: prob.metrics(st.w)              # noqa: E731

    labels, fns, hps, states, keys = [], [], [], [], []
    base = jax.random.key(plan.seed)
    for j, run in enumerate(plan.runs):
        spec, cfg, hp, step, state, iters = _resolve(plan, run)
        label = run.label or spec.name
        while label in labels:
            label = f"{label}#{j}"
        labels.append(label)
        fns.append(sweep_program(step, iters, record=record,
                                 record_every=plan.record_every,
                                 trace_dtype=plan.trace_dtype))
        hps.append(hp)
        states.append(state)
        keys.append(sweep_keys(jax.random.fold_in(base, j),
                               _grid_size(hp), iters))

    def program(states, hps, keyss):
        # Python body executes only while jax traces — once per compile.
        _STATS["traces"] += 1
        return tuple(fn(hp, st, ks)
                     for fn, hp, st, ks in zip(fns, hps, states, keyss))

    _STATS["programs"] += 1
    args = (tuple(states), tuple(hps), tuple(keys))
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("plan.compile"):
        compiled = jax.jit(program).lower(*args).compile()
    t1 = time.perf_counter()
    with jax.profiler.TraceAnnotation("plan.run"):
        out = jax.block_until_ready(compiled(*args))
    t2 = time.perf_counter()
    return PlanResult(
        labels=tuple(labels),
        states={lab: o[0] for lab, o in zip(labels, out)},
        traces={lab: o[1] for lab, o in zip(labels, out)},
        hparams={lab: hp for lab, hp in zip(labels, hps)},
        seconds=t2 - t0, compiled=compiled, compile_s=t1 - t0,
        run_s=t2 - t1)
