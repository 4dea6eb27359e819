"""Declarative method registry + ExperimentPlan (the api redesign).

Pins the redesign's hard contracts:
  * ``run_plan`` reproduces single-point ``run_experiment`` runs of each
    method (its sweep step at one hparams point, the "legacy" path) with
    EXACT bit ledgers for all five methods (key rule: run j, point g
    steps with ``split(split(fold_in(key(seed), j), G)[g], iters)``);
  * the traced-p participation mask equals the static
    ``participation_mask`` draw-for-draw, matches Bernoulli statistics,
    and the p<=0 / choice-sampling guards hold on the traced path;
  * a mixed grid — method axis static (structural segments), (p × grad_s)
    traced — runs as ONE compiled program (``api.plan_compiles``);
  * the benchmark figures ``fig1_flecs_vs_cgd`` (8 curves: compressor
    FAMILY axis × structural m segments) and ``participation_ablation``
    each execute as exactly one compiled program, numerically identical to
    the per-method single-point runs;
  * async plans (FedBuff staleness) match single-point runs of the
    driver's async step;
  * the DL dither-level cap is expressed on the traced path
    (``compressors.psum_level_cap``).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.api import ExperimentPlan, MethodRun, get_method, run_plan
from repro.core.compressors import (FAMILY_DITHER, FAMILY_IDENTITY,
                                    psum_level_cap, spec_bits, stack_specs)
from repro.core.driver import (AsyncHParams, StalenessSchedule,
                               init_async_state, make_async_step,
                               participation_mask, resolve_participation,
                               run_experiment)
from repro.core.flecs import (FlecsConfig, hparam_grid, hparams_from_config,
                              init_state, make_flecs_sweep_step)
from repro.data.logreg import make_problem
from repro.optim.baselines import (DianaConfig, FedNLConfig, GDConfig,
                                   diana_hparam_grid,
                                   diana_hparams_from_config,
                                   fednl_hparams_from_config,
                                   gd_hparam_grid, gd_hparams_from_config,
                                   init_diana, init_fednl, init_gd,
                                   make_diana_async_hooks,
                                   make_diana_sweep_step,
                                   make_fednl_sweep_step, make_gd_sweep_step)

PROB = make_problem(d=16, n_workers=4, r=16, mu=1e-3, seed=3)
LG, LH = PROB.make_oracles(batch=0)
N, D = PROB.n_workers, PROB.d
ALL_METHODS = ("flecs", "flecs_cgd", "diana", "fednl", "gd")


def _local_hessian(w, i):
    return jax.hessian(lambda ww: PROB.local_loss(ww, i))(w)


def _flecs_step(cfg):
    """The FLECS sweep step at the config's own hparams point."""
    return functools.partial(make_flecs_sweep_step(cfg, LG, LH),
                             hparams_from_config(cfg))


def _legacy_key(seed, j, G, g):
    """The documented plan key rule: run j, grid point g."""
    return jax.random.split(
        jax.random.fold_in(jax.random.key(seed), j), G)[g]


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_resolves_all_five_methods():
    for name in ALL_METHODS:
        spec = get_method(name)
        assert spec.name == name
        cfg = spec.default_config()
        assert isinstance(cfg, spec.config_cls)
        # every method is constructible end-to-end from the registry
        state = spec.init(PROB, N, cfg)
        step = spec.sweep_step(PROB, cfg)
        hp = jax.tree.map(lambda a: jnp.asarray(a)[None],
                          spec.from_config(cfg))
        hp0 = jax.tree.map(lambda a: a[0], hp)
        new, aux = jax.jit(step)(hp0, state, jax.random.key(0))
        assert aux["bits_per_node"].shape == (N,)
    assert set(ALL_METHODS) <= set(api.method_names())
    with pytest.raises(ValueError):
        get_method("sgd")


def test_flecs_vs_cgd_registry_defaults_differ_only_in_compressor():
    f, c = get_method("flecs"), get_method("flecs_cgd")
    assert f.default_config().grad_compressor == "identity"
    assert c.default_config().grad_compressor == "dither64"
    # grid() follows the METHOD's own gradient compressor, so a plain-FLECS
    # sweep built the natural way really ships identity gradients
    assert np.asarray(f.grid(ps=(1.0, 0.5)).grad_spec.family).tolist() == \
        [FAMILY_IDENTITY] * 2
    assert np.asarray(c.grid(ps=(1.0, 0.5)).grad_spec.family).tolist() == \
        [FAMILY_DITHER] * 2


# ---------------------------------------------------------------------------
# Acceptance (a): run_plan == legacy per-method paths, exact bit ledgers
# ---------------------------------------------------------------------------

def test_run_plan_matches_legacy_runs_all_five_methods():
    iters = 5
    plan = ExperimentPlan(problem=PROB,
                          runs=tuple(MethodRun(m) for m in ALL_METHODS),
                          iters=iters, seed=0)
    res = run_plan(plan)
    assert res.labels == ALL_METHODS
    rec = lambda st: PROB.metrics(st.w)                     # noqa: E731
    w0 = jnp.zeros(D)
    diana, fednl = DianaConfig(1.0, 0.5, "dither64"), FedNLConfig(
        1.0, "topk0.25", 1e-3)
    legacy = {
        "flecs": (_flecs_step(FlecsConfig(grad_compressor="identity")),
                  init_state(w0, N)),
        "flecs_cgd": (_flecs_step(FlecsConfig(grad_compressor="dither64")),
                      init_state(w0, N)),
        "diana": (functools.partial(make_diana_sweep_step(diana, LG),
                                    diana_hparams_from_config(diana)),
                  init_diana(w0, N)),
        "fednl": (functools.partial(
            make_fednl_sweep_step(fednl, LG, _local_hessian),
            fednl_hparams_from_config(fednl)), init_fednl(w0, N)),
        "gd": (functools.partial(make_gd_sweep_step(GDConfig(2.0), LG, N),
                                 gd_hparams_from_config(GDConfig(2.0))),
               init_gd(w0, N)),
    }
    for j, lab in enumerate(res.labels):
        step, st0 = legacy[lab]
        st, tr = run_experiment(step, st0, _legacy_key(0, j, 1, 0), iters,
                                record=rec)
        # same key streams => identical compression draws => EXACT ledgers
        np.testing.assert_array_equal(
            np.asarray(tr["bits_per_node"]),
            np.asarray(res.traces[lab]["bits_per_node"][0]), err_msg=lab)
        np.testing.assert_allclose(np.asarray(st.w),
                                   np.asarray(res.states[lab].w[0]),
                                   rtol=0, atol=1e-6, err_msg=lab)
        np.testing.assert_allclose(np.asarray(tr["F"]),
                                   np.asarray(res.traces[lab]["F"][0]),
                                   rtol=1e-6, err_msg=lab)


# ---------------------------------------------------------------------------
# Acceptance (b): traced-p participation mask
# ---------------------------------------------------------------------------

def test_traced_p_mask_matches_static_draw_for_draw():
    """Same key, same p: the traced path is the identical uniform<p draw
    (p>=1 static short-circuits to ones; traced compares — same values)."""
    for p in (0.25, 0.5, 0.9, 1.0):
        for k in range(5):
            key = jax.random.key(k)
            static = participation_mask(key, 8, p, "bernoulli")
            traced = jax.jit(
                lambda pv: participation_mask(key, 8, pv, "bernoulli"))(
                    jnp.float32(p))
            np.testing.assert_array_equal(np.asarray(static),
                                          np.asarray(traced))


def test_traced_p_mask_bernoulli_statistics():
    """Vmapped traced-p axis: per-point participation frequency matches
    its own p (the sweep-axis semantics the ablation relies on)."""
    ps = jnp.asarray([0.2, 0.5, 0.8], jnp.float32)
    keys = jax.random.split(jax.random.key(0), 800)
    masks = jax.vmap(lambda p: jax.vmap(
        lambda k: participation_mask(k, 8, p, "bernoulli"))(keys))(ps)
    assert masks.shape == (3, 800, 8)
    freq = np.asarray(masks).mean(axis=(1, 2))
    np.testing.assert_allclose(freq, np.asarray(ps), atol=0.03)


def test_concrete_scalar_p_stays_on_static_path():
    """Concrete scalars — np.float32, 0-d numpy/jax arrays — are static,
    not traced: they must keep working with kind='choice' exactly like
    Python floats (only genuine tracers have no choice form)."""
    key = jax.random.key(0)
    for p in (np.float32(0.5), np.float64(0.5), np.asarray(0.5),
              jnp.float32(0.5)):
        m = np.asarray(participation_mask(key, 8, p, "choice"))
        assert m.sum() == 4
        np.testing.assert_array_equal(
            m, np.asarray(participation_mask(key, 8, 0.5, "choice")))
    with pytest.raises(ValueError):
        participation_mask(key, 8, np.float32(0.0), "choice")


def test_traced_p_guards():
    key = jax.random.key(0)
    # choice has no traced form: resolved-at-trace-time k
    with pytest.raises(ValueError):
        jax.jit(lambda pv: participation_mask(key, 8, pv, "choice"))(
            jnp.float32(0.5))
    with pytest.raises(ValueError):
        resolve_participation(key, 8, 1.0, "choice", jnp.float32(0.5))
    # the p<=0 guard holds for concrete traced-path values too
    with pytest.raises(ValueError):
        participation_mask(key, 8, jnp.float32(0.0), "bernoulli")
    with pytest.raises(ValueError):
        participation_mask(key, 8, jnp.asarray([0.5, -1.0]), "bernoulli")
    # ... and at grid-construction time
    for bad_grid in (lambda: hparam_grid([1.0], [1.0], [64.0], ps=(0.0,)),
                     lambda: diana_hparam_grid(ps=(0.5, -0.1)),
                     lambda: gd_hparam_grid(ps=(0.0,))):
        with pytest.raises(ValueError):
            bad_grid()
    # run_plan rejects a traced p axis on a choice-sampling config
    plan = ExperimentPlan(
        problem=PROB,
        runs=(MethodRun("flecs_cgd",
                        cfg=FlecsConfig(sampling="choice"),
                        hparams=hparam_grid([1.0], [1.0], [64.0],
                                            ps=(0.5, 1.0))),),
        iters=2)
    with pytest.raises(ValueError):
        run_plan(plan)


def test_traced_p_sweep_matches_static_participation_runs():
    """A traced-p grid point reproduces the legacy static-participation
    bernoulli run trace-for-trace (exact ledgers)."""
    ps = (0.5, 1.0)
    hp = hparam_grid([0.5], [1.0], [64.0], ps=ps)
    plan = ExperimentPlan(
        problem=PROB,
        runs=(MethodRun("flecs_cgd", cfg=FlecsConfig(m=1, alpha=0.5),
                        hparams=hp),),
        iters=6, seed=4)
    res = run_plan(plan)
    tr = res.traces["flecs_cgd"]
    rec = lambda st: PROB.metrics(st.w)                     # noqa: E731
    for g, p in enumerate(ps):
        cfg = FlecsConfig(m=1, alpha=0.5, participation=p,
                          sampling="bernoulli")
        st, tr_g = run_experiment(_flecs_step(cfg),
                                  init_state(jnp.zeros(D), N),
                                  _legacy_key(4, 0, len(ps), g), 6,
                                  record=rec)
        np.testing.assert_array_equal(np.asarray(tr_g["bits_per_node"]),
                                      np.asarray(tr["bits_per_node"][g]))
        np.testing.assert_allclose(np.asarray(tr_g["F"]),
                                   np.asarray(tr["F"][g]), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(st.w), np.asarray(res.states["flecs_cgd"].w[g]),
            rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# Acceptance (c): mixed method-static x (p x grad_s traced) grid, ONE compile
# ---------------------------------------------------------------------------

def test_mixed_method_and_traced_axes_grid_is_one_compile():
    flecs_grid = get_method("flecs_cgd").grid(
        grad_levels=(16.0, 64.0), ps=(0.5, 1.0))       # 4 traced points
    diana_grid = diana_hparam_grid(levels=(16.0, 64.0), ps=(0.5, 1.0))
    plan = ExperimentPlan(
        problem=PROB,
        runs=(MethodRun("flecs_cgd", hparams=flecs_grid),
              MethodRun("diana", hparams=diana_grid)),
        iters=4)
    api.reset_plan_stats()
    res = run_plan(plan)
    assert api.plan_compiles() == 1       # method axis static, all else traced
    assert res.traces["flecs_cgd"]["F"].shape == (4, 4)
    assert res.traces["diana"]["F"].shape == (4, 4)
    # the billed bits follow each point's traced level axis
    bits = np.asarray(res.states["diana"].bits_per_node)
    hp = res.hparams["diana"]
    for g in range(4):
        per_round = float(spec_bits(jax.tree.map(lambda a: a[g],
                                                 hp.spec), D))
        active = np.asarray(res.traces["diana"]["n_active"][g]).sum()
        np.testing.assert_allclose(bits[g].sum(), active * per_round)


# ---------------------------------------------------------------------------
# Figure plans: one compiled program each, identical to legacy paths
# ---------------------------------------------------------------------------

def test_fig1_plan_single_compile_and_matches_legacy():
    from benchmarks.paper_experiments import FIG1_MS, fig1_plan
    iters = 4
    plan = fig1_plan(PROB, iters=iters)
    api.reset_plan_stats()
    res = run_plan(plan)
    assert api.plan_compiles() == 1       # was 8 programs pre-redesign
    rec = lambda st: PROB.metrics(st.w)                     # noqa: E731
    for j, m in enumerate(FIG1_MS):
        tr = res.traces[f"m{m}"]
        for g, gc in enumerate(("identity", "dither64")):
            cfg = FlecsConfig(m=m, alpha=1.0, beta=1.0, gamma=1.0,
                              grad_compressor=gc,
                              hess_compressor="dither64")
            st, tr_g = run_experiment(_flecs_step(cfg),
                                      init_state(jnp.zeros(D), N),
                                      _legacy_key(0, j, 2, g), iters,
                                      record=rec)
            np.testing.assert_array_equal(
                np.asarray(tr_g["bits_per_node"]),
                np.asarray(tr["bits_per_node"][g]), err_msg=f"m{m}/{gc}")
            np.testing.assert_allclose(np.asarray(tr_g["F"]),
                                       np.asarray(tr["F"][g]), rtol=1e-6)
    # the family axis actually separates the wire formats: FLECS ships
    # 32·d grad bits, CGD ⌈log2 129⌉·d = 8·d
    m1 = np.asarray(res.traces["m1"]["bits_per_node"])[:, 0, 0]
    assert m1[0] - m1[1] == (32 - 8) * D


def test_use_kernel_plan_single_compile_and_exact_ledgers():
    """The fused Pallas compressor path threads through run_plan as a
    config flag: still ONE compiled program per figure, and — because the
    kernels are bit-identical to the jnp reference — the bit ledgers
    match EXACTLY and the trajectories match to float tolerance."""
    def _plan(use_kernel):
        return ExperimentPlan(
            problem=PROB,
            runs=(MethodRun("flecs_cgd",
                            cfg=FlecsConfig(use_kernel=use_kernel)),
                  MethodRun("diana",
                            cfg=DianaConfig(use_kernel=use_kernel)),
                  MethodRun("fednl",
                            cfg=FedNLConfig(use_kernel=use_kernel))),
            iters=5, seed=0)
    api.reset_plan_stats()
    res_k = run_plan(_plan(True))
    assert api.plan_compiles() == 1
    res_j = run_plan(_plan(False))
    for lab in ("flecs_cgd", "diana", "fednl"):
        np.testing.assert_array_equal(
            np.asarray(res_k.traces[lab]["bits_per_node"]),
            np.asarray(res_j.traces[lab]["bits_per_node"]), err_msg=lab)
        np.testing.assert_allclose(
            np.asarray(res_k.traces[lab]["F"]),
            np.asarray(res_j.traces[lab]["F"]), rtol=1e-6, err_msg=lab)


def test_participation_plan_single_compile():
    from benchmarks.paper_experiments import (PARTICIPATION_PS,
                                              participation_plan)
    plan = participation_plan(PROB, iters=6)
    api.reset_plan_stats()
    res = run_plan(plan)
    assert api.plan_compiles() == 1
    tr = res.traces["participation"]
    assert tr["F"].shape == (len(PARTICIPATION_PS), 6)
    # active counts follow the traced p axis (full > half > quarter)
    active = np.asarray(tr["n_active"]).mean(axis=1)
    assert active[0] == N and active[0] > active[1] > active[2] > 0


# ---------------------------------------------------------------------------
# Async plans
# ---------------------------------------------------------------------------

def test_async_plan_matches_legacy_async_step():
    sched = StalenessSchedule("fixed", tau=1)
    plan = ExperimentPlan(
        problem=PROB,
        runs=(MethodRun("diana",
                        cfg=DianaConfig(participation=0.5,
                                        sampling="choice")),),
        iters=8, seed=2, staleness=sched, buffer_k=2)
    res = run_plan(plan)
    cfg = DianaConfig(1.0, 0.5, "dither64", participation=0.5,
                      sampling="choice")
    hooks, hp = make_diana_async_hooks(cfg, LG), diana_hparams_from_config(cfg)
    step = functools.partial(make_async_step(hooks, sched.kind, sched.q),
                             AsyncHParams(hp, jnp.int32(1), jnp.float32(2)))
    st0 = init_async_state(hooks, init_diana(jnp.zeros(D), N), hp, 1)
    st, tr = run_experiment(step, st0, _legacy_key(2, 0, 1, 0), 8,
                            record=lambda s: PROB.metrics(s.w))
    np.testing.assert_array_equal(
        np.asarray(tr["bits_per_node"]),
        np.asarray(res.traces["diana"]["bits_per_node"][0]))
    np.testing.assert_allclose(np.asarray(st.w),
                               np.asarray(res.states["diana"].w[0]),
                               rtol=0, atol=1e-6)


def test_async_fednl_plan_matches_legacy_async_step():
    """Async FedNL closes the five-method async matrix: the plan path
    reproduces a single-point run of the buffered step with exact bit
    ledgers."""
    from repro.optim.baselines import make_fednl_async_hooks
    sched = StalenessSchedule("fixed", tau=1)
    plan = ExperimentPlan(problem=PROB, runs=(MethodRun("fednl"),),
                          iters=8, seed=2, staleness=sched, buffer_k=2)
    res = run_plan(plan)
    cfg = FedNLConfig(1.0, "topk0.25", 1e-3)
    hooks = make_fednl_async_hooks(cfg, LG, _local_hessian)
    hp = fednl_hparams_from_config(cfg)
    step = functools.partial(make_async_step(hooks, sched.kind, sched.q),
                             AsyncHParams(hp, jnp.int32(1), jnp.float32(2)))
    st0 = init_async_state(hooks, init_fednl(jnp.zeros(D), N), hp, 1)
    st, tr = run_experiment(step, st0, _legacy_key(2, 0, 1, 0), 8,
                            record=lambda s: PROB.metrics(s.w))
    np.testing.assert_array_equal(
        np.asarray(tr["bits_per_node"]),
        np.asarray(res.traces["fednl"]["bits_per_node"][0]))
    np.testing.assert_allclose(np.asarray(st.w),
                               np.asarray(res.states["fednl"].w[0]),
                               rtol=0, atol=1e-6)


def test_async_plan_rejects_methods_without_async_variant():
    """All five registry methods now carry async variants, so the guard
    is pinned with a stripped spec: a custom method without async hooks
    must fail loudly on a staleness plan."""
    import dataclasses
    no_async = dataclasses.replace(get_method("fednl"), name="_noasync",
                                   async_hooks=None)
    plan = ExperimentPlan(problem=PROB, runs=(MethodRun(no_async),),
                          iters=2, staleness=StalenessSchedule("fixed",
                                                               tau=1))
    with pytest.raises(ValueError):
        run_plan(plan)


def test_async_plan_rejects_undersized_buffer_for_user_tau_grid():
    """A user-supplied async hparam grid whose tau exceeds the schedule's
    max_delay must fail loudly — slot indices wrap modulo the buffer size,
    so the oversized-tau point would silently run at a shorter delay."""
    hp = jax.tree.map(lambda a: jnp.broadcast_to(a, (2,)),
                      diana_hparam_grid())
    ahp = AsyncHParams(hp, jnp.asarray([0, 4], jnp.int32),
                       jnp.ones((2,), jnp.float32))
    plan = ExperimentPlan(
        problem=PROB, runs=(MethodRun("diana", hparams=ahp),),
        iters=4, staleness=StalenessSchedule("fixed", tau=1))
    with pytest.raises(ValueError):
        run_plan(plan)
    # ... and the mirror image: async hparams on a synchronous plan fail
    # at plan validation, not deep inside jit tracing
    with pytest.raises(ValueError):
        run_plan(ExperimentPlan(problem=PROB,
                                runs=(MethodRun("diana", hparams=ahp),),
                                iters=4))


# ---------------------------------------------------------------------------
# Satellite: family-axis grids + the DL dither-level cap on the traced path
# ---------------------------------------------------------------------------

def test_stack_specs_family_axis_grid():
    hp = get_method("flecs_cgd").grid(
        grad_specs=stack_specs("identity", "dither64"))
    assert np.asarray(hp.grad_spec.family).tolist() == [FAMILY_IDENTITY,
                                                        FAMILY_DITHER]
    assert hp.alpha.shape == (2,)
    # a level grid cannot silently combine with an explicit spec — stacked
    # OR scalar (the scalar case used to drop the level axis quietly)
    with pytest.raises(ValueError):
        get_method("flecs_cgd").grid(grad_levels=(16.0, 64.0),
                                     grad_specs=stack_specs("identity",
                                                            "dither64"))
    from repro.core.compressors import make_spec as _sfn
    with pytest.raises(ValueError):
        get_method("flecs_cgd").grid(grad_levels=(16.0, 64.0),
                                     grad_specs=_sfn("dither64"))
    with pytest.raises(ValueError):
        get_method("flecs_cgd").grid(hess_levels=(16.0, 64.0),
                                     hess_specs=_sfn("dither64"))
    # a SCALAR spec pins the compressor across the other axes (plain
    # FLECS's identity gradients alongside a traced p sweep)
    from repro.core.compressors import make_spec
    hp = get_method("flecs").grid(grad_specs=make_spec("identity"),
                                  ps=(1.0, 0.5))
    assert hp.alpha.shape == hp.p.shape == (2,)
    assert np.asarray(hp.grad_spec.family).tolist() == [FAMILY_IDENTITY] * 2
    assert np.asarray(hp.hess_spec.family).tolist() == [FAMILY_DITHER] * 2
    np.testing.assert_allclose(
        np.asarray(jax.vmap(lambda sp: spec_bits(sp, D))(hp.grad_spec)),
        [32.0 * D] * 2)


def test_psum_level_cap_traced():
    """min(s, 2047//n) as a lax-side clip: equals the old Python formula
    and admits s_levels as a traced/vmapped sweep axis."""
    for n in (1, 4, 16, 100, 4096):
        for s in (1, 8, 127, 511, 5000):
            expect = max(1, min(s, max(1, 2047 // n)))
            assert float(psum_level_cap(s, n)) == expect, (s, n)
            assert float(jax.jit(
                lambda sv: psum_level_cap(sv, n))(jnp.float32(s))) == expect
    levels = jnp.asarray([8.0, 127.0, 2000.0])
    out = jax.jit(jax.vmap(lambda s: psum_level_cap(s, 4)))(levels)
    np.testing.assert_allclose(np.asarray(out), [8.0, 127.0, 511.0])


# ---------------------------------------------------------------------------
# Acceptance: {dither, topk, count_sketch, minmax} as ONE traced family axis
# ---------------------------------------------------------------------------

def test_four_family_traced_axis_one_compile_exact_ledgers():
    """The widened compressor algebra end-to-end: all four non-trivial
    families stacked on ONE traced grid axis run as a single compiled
    program, and each grid point's cumulative ledger equals its own
    family's round_bits exactly — dither at ⌈log2(2s+1)⌉·d, the selection
    families at kept·(32+⌈log2 d⌉), count-sketch d-free at
    32·depth·min(width, d)."""
    from repro.core.compressors import (FAMILY_COUNT_SKETCH, FAMILY_MINMAX,
                                        FAMILY_TOPK)
    from repro.core.flecs import hparams_round_bits
    names = ("dither64", "topk0.25", "count_sketch64", "minmax0.5")
    cfg = FlecsConfig(m=2)
    hp = get_method("flecs_cgd").grid(grad_specs=stack_specs(*names))
    plan = ExperimentPlan(problem=PROB,
                          runs=(MethodRun("flecs_cgd", cfg=cfg,
                                          hparams=hp),),
                          iters=4)
    api.reset_plan_stats()
    res = run_plan(plan)
    assert api.plan_compiles() == 1
    assert np.asarray(hp.grad_spec.family).tolist() == [
        FAMILY_DITHER, FAMILY_TOPK, FAMILY_COUNT_SKETCH, FAMILY_MINMAX]

    price = np.asarray(hparams_round_bits(cfg, hp, D))          # [4]
    m = cfg.m
    db = int(np.ceil(np.log2(2 * 64 + 1)))            # dither64 bits/value
    idx = 32 + int(np.ceil(np.log2(D)))               # selection wire word
    hess = db * D * m + 32 * m * m                    # shared dither64 C, M
    expect = [db * D + hess,                                  # dither64
              int(np.ceil(0.25 * D)) * idx + hess,            # topk0.25
              32 * 3 * min(64, D) + hess,                     # count_sketch
              int(np.ceil(0.5 * D)) * idx + hess]             # minmax0.5
    np.testing.assert_array_equal(price, expect)

    bits = np.asarray(res.states["flecs_cgd"].bits_per_node)  # [4, N]
    tr = res.traces["flecs_cgd"]
    for g, name in enumerate(names):
        active = np.asarray(tr["n_active"][g]).sum()
        np.testing.assert_allclose(bits[g].sum(), active * price[g],
                                   err_msg=name)
