"""Traced compressor algebra + unified sweep engine (the spec refactor).

Pins the refactor's hard contracts:
  * ``compress``/``spec_bits``/``spec_omega`` dispatch on traced specs
    (lax.switch) and agree with the static ``Compressor`` wrappers;
  * top-k wire accounting is dimension-aware: ⌈frac·d⌉ kept values at
    (32 + ⌈log2 d⌉) bits each — not the old flat 64·frac per element;
  * a single compiled ``run_sweep`` over a grid varying hess_s AND beta
    reproduces per-point single runs trace-for-trace (iterates + exact
    bit ledgers);
  * ``run_async_sweep`` over a (tau, buffer_k) grid matches independent
    single-point runs of the driver's async step, and its tau=0 point
    collapses to the synchronous engine bit-for-bit;
  * ``damped_alpha`` implements alpha0 · min(1, p·K/n).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compressors import (ALL_FAMILIES, FAMILY_COUNT_SKETCH,
                                    FAMILY_DITHER, FAMILY_IDENTITY,
                                    FAMILY_MINMAX, FAMILY_NATURAL,
                                    FAMILY_TOPK, Compressor, CompressorSpec,
                                    compress, count_sketch_spec,
                                    dither_spec, family_set, fill_params,
                                    identity_spec, make_spec, minmax_spec,
                                    natural_spec, random_dithering,
                                    spec_bits, spec_omega, stack_specs,
                                    topk_spec)
from repro.core.driver import (AsyncHParams, damped_alpha, init_async_state,
                               make_async_step, run_async_sweep,
                               run_experiment, run_sweep, sample_delays)
from repro.core.flecs import (FlecsConfig, async_hparam_grid, bits_per_round,
                              hparam_grid, hparams_from_config, init_state,
                              make_flecs_async_hooks, make_flecs_sweep_step)
from repro.data.logreg import make_problem

PROB = make_problem(d=24, n_workers=4, r=24, mu=1e-3, seed=5)
LG, LH = PROB.make_oracles(batch=0)
N, D = PROB.n_workers, PROB.d


def _flecs_step(cfg, lg, lh):
    """The FLECS sweep step at the config's own hparams point."""
    return functools.partial(make_flecs_sweep_step(cfg, lg, lh),
                             hparams_from_config(cfg))


def _flecs_async_state(cfg, lg, lh, d, n, max_delay):
    """The driver's async state around a fresh FLECS state."""
    return init_async_state(make_flecs_async_hooks(cfg, lg, lh),
                            init_state(jnp.zeros(d), n),
                            hparams_from_config(cfg), max_delay)


# ---------------------------------------------------------------------------
# Spec dispatch
# ---------------------------------------------------------------------------

def test_spec_dispatch_matches_static_wrappers(rng):
    """compress(spec, …) == Compressor.compress for every family (same key
    => same draws; the static wrapper IS the spec path)."""
    x = jnp.asarray(rng.normal(size=37), jnp.float32)
    key = jax.random.key(3)
    for name in ("identity", "dither16", "natural", "topk0.25",
                 "count_sketch16", "minmax0.25"):
        Q = Compressor(name, make_spec(name))
        np.testing.assert_array_equal(
            np.asarray(compress(make_spec(name), key, x)),
            np.asarray(Q.compress(key, x)))
        np.testing.assert_allclose(float(spec_bits(Q.spec, 37)), Q.bits(37))
        np.testing.assert_allclose(float(spec_omega(Q.spec, 37)),
                                   Q.omega(37))


def test_identity_and_natural_specs_roundtrip(rng):
    x = jnp.asarray(rng.normal(size=20), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(compress(identity_spec(), jax.random.key(0), x)),
        np.asarray(x))
    y = np.asarray(compress(natural_spec(), jax.random.key(1), x))
    # natural keeps signs and rounds magnitudes to powers of two
    np.testing.assert_array_equal(np.sign(y), np.sign(np.asarray(x)))
    lg = np.log2(np.abs(y[np.abs(y) > 0]))
    np.testing.assert_allclose(lg, np.round(lg), atol=1e-6)


def test_traced_family_axis_in_one_program(rng):
    """A grid whose axis varies the FAMILY (not just a level) runs as one
    vmapped program — the lax.switch dispatch the CI pin exercises."""
    x = jnp.asarray(rng.normal(size=30), jnp.float32)
    specs = stack_specs(identity_spec(), dither_spec(16.0), natural_spec(),
                        topk_spec(0.2), count_sketch_spec(16.0, 3.0),
                        minmax_spec(0.2))
    key = jax.random.key(0)
    ys = jax.jit(jax.vmap(lambda sp: compress(sp, key, x)))(specs)
    assert ys.shape == (6, 30)
    np.testing.assert_array_equal(np.asarray(ys[0]), np.asarray(x))
    np.testing.assert_array_equal(
        np.asarray(ys[1]),
        np.asarray(compress(dither_spec(16.0), key, x)))
    np.testing.assert_array_equal(
        np.asarray(ys[4]),
        np.asarray(compress(count_sketch_spec(16.0, 3.0), key, x)))
    bits = jax.vmap(lambda sp: spec_bits(sp, 30))(specs)
    np.testing.assert_allclose(
        np.asarray(bits),
        [32 * 30, math.ceil(math.log2(33)) * 30, 9 * 30,
         6 * (32 + math.ceil(math.log2(30))),
         32 * 3 * 16,                       # sketch accumulator, d-free
         6 * (32 + math.ceil(math.log2(30)))])


def test_traced_dither_level_matches_static(rng):
    """dither_spec with a traced s draws exactly the static compressor's
    randomness (same ops, same key)."""
    x = jnp.asarray(rng.normal(size=50), jnp.float32)
    key = jax.random.key(9)
    for s in (4, 64):
        traced = jax.jit(
            lambda sv: compress(dither_spec(sv), key, x))(jnp.float32(s))
        np.testing.assert_array_equal(
            np.asarray(traced),
            np.asarray(random_dithering(s).compress(key, x)))


def test_topk_bits_dimension_aware():
    """Satellite: ⌈frac·d⌉ kept values × (32 + ⌈log2 d⌉) bits — the old
    flat 64·frac/element hardcoded a 32-bit index and overcharged small d
    while undercharging d > 2^32."""
    for d, frac in ((100, 0.25), (1600, 0.25), (7, 0.5), (1, 1.0)):
        kept = max(1, math.ceil(frac * d))
        idx_bits = math.ceil(math.log2(d)) if d > 1 else 0
        expect = kept * (32 + idx_bits)
        assert float(spec_bits(topk_spec(frac), d)) == expect, (d, frac)
        assert Compressor("topk", make_spec("topk", frac=frac)).bits(d) \
            == expect
    # per-element bits are ill-defined for every dimension-dependent
    # family: the deprecated query still fails loudly
    for name in ("topk0.25", "count_sketch16", "minmax0.25"):
        with pytest.warns(DeprecationWarning):
            with pytest.raises(ValueError, match=r"use \.bits\(d\)"):
                Compressor(name, make_spec(name)).bits_per_value
    # ... and flow through the round ledger when top-k compresses the
    # Hessian difference
    cfg = FlecsConfig(m=2, grad_compressor="dither64",
                      hess_compressor="topk0.25")
    dm = D * cfg.m
    kept = math.ceil(0.25 * dm)
    expect = (8 * D + kept * (32 + math.ceil(math.log2(dm)))
              + 32 * cfg.m * cfg.m)
    assert bits_per_round(cfg, D) == expect
    step = _flecs_step(cfg, LG, LH)
    st, _ = run_experiment(step, init_state(jnp.zeros(D), N),
                           jax.random.key(0), 2)
    np.testing.assert_allclose(np.asarray(st.bits_per_node), 2 * expect)


def test_make_spec_accepts_all_forms():
    Q = random_dithering(16)
    for form in ("dither16", Q, Q.spec):
        sp = make_spec(form)
        assert int(sp.family) == FAMILY_DITHER
        assert float(sp.s) == 16.0
    # bare family name + keyword params
    sp = make_spec("count_sketch", width=32, depth=5, hh_frac=0.5)
    assert int(sp.family) == FAMILY_COUNT_SKETCH
    assert [float(v) for v in sp.params] == [32.0, 5.0, 0.5]
    assert float(make_spec("minmax", frac=0.4).frac) == pytest.approx(0.4)
    # suffix/keyword conflicts, unknown keywords, and params on a spec
    # pass-through all fail loudly
    with pytest.raises(ValueError, match="both"):
        make_spec("dither64", s=16)
    with pytest.raises(ValueError, match="width"):
        make_spec("topk0.1", width=8)
    with pytest.raises(ValueError, match="keyword"):
        make_spec(identity_spec(), s=2.0)


def test_make_spec_unknown_name_lists_valid_families():
    # Satellite: an unknown family fails at CONSTRUCTION time with the
    # valid-name list, not as an opaque switch-index error deep in a trace.
    with pytest.raises(ValueError, match="identity.*dither.*natural.*topk"
                                         ".*count_sketch.*minmax"):
        make_spec("nope")
    with pytest.raises(ValueError, match="valid names"):
        make_spec("ditherx")                  # unparseable numeric suffix


def test_deprecated_constructor_aliases_warn_and_delegate():
    # spec_from_name / as_spec / get_compressor survive as thin
    # DeprecationWarning aliases of make_spec.
    from repro.core.compressors import (as_spec, get_compressor,
                                        spec_from_name)
    with pytest.warns(DeprecationWarning):
        sp = spec_from_name("dither64")
    assert float(sp.s) == 64.0
    with pytest.warns(DeprecationWarning):
        assert int(as_spec("identity").family) == FAMILY_IDENTITY
    with pytest.warns(DeprecationWarning):
        Q = get_compressor("natural")
    assert Q.name == "natural"
    # the aliases inherit make_spec's loud unknown-name error
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="valid names"):
            spec_from_name("nope")


# ---------------------------------------------------------------------------
# Family sets: the static structure the dispatch switches over
# ---------------------------------------------------------------------------

SIX = ("identity", "dither16", "natural", "topk0.2", "count_sketch16",
       "minmax0.2")


@pytest.mark.parametrize("build, families", [
    (identity_spec, (FAMILY_IDENTITY,)),
    (lambda: dither_spec(64.0), (FAMILY_DITHER,)),
    (lambda: dither_spec(jnp.asarray([16.0, 64.0])), (FAMILY_DITHER,)),
    (natural_spec, (FAMILY_NATURAL,)),
    (lambda: topk_spec(0.1), (FAMILY_TOPK,)),
    (count_sketch_spec, (FAMILY_COUNT_SKETCH,)),
    (lambda: minmax_spec(0.25), (FAMILY_MINMAX,)),
    (lambda: make_spec("dither64"), (FAMILY_DITHER,)),
    (lambda: make_spec("topk", frac=0.3), (FAMILY_TOPK,)),
    (lambda: make_spec("count_sketch8"), (FAMILY_COUNT_SKETCH,)),
    (lambda: make_spec(random_dithering(16)), (FAMILY_DITHER,)),
    (lambda: stack_specs("dither64", "dither16"), (FAMILY_DITHER,)),
    (lambda: stack_specs("topk0.1", "dither64"), (FAMILY_DITHER,
                                                  FAMILY_TOPK)),
    (lambda: stack_specs("minmax0.1", CompressorSpec(
        jnp.int32(FAMILY_DITHER), jnp.float32(64.0), jnp.float32(1.0))),
     ALL_FAMILIES),
    (lambda: stack_specs(*SIX), ALL_FAMILIES),
], ids=["identity", "dither", "dither_grid", "natural", "topk",
        "count_sketch", "minmax", "make_spec_name", "make_spec_kw",
        "make_spec_sketch", "make_spec_compressor", "stack_one_family",
        "stack_union", "stack_unknown", "stack_six"])
def test_constructors_set_family_set(build, families):
    spec = build()
    assert family_set(spec) == families
    # every id the leaf holds is in the set (a switch would clamp one
    # that is not)
    assert set(np.asarray(spec.family).ravel().tolist()) <= set(families)


def _traced_set(spec):
    seen = []

    def f(sp):
        seen.append(family_set(sp))
        return compress(sp, jax.random.key(0), jnp.ones(8))

    jax.jit(f)(spec)
    return seen[0]


@pytest.mark.parametrize("transform", [
    "jit_argument", "vmap", "tree_map", "device_put", "fill_params",
    "replace_params", "shard_map_specs", "flecs_grid", "flecs_grid_edges",
])
def test_family_set_survives(transform):
    from jax.sharding import PartitionSpec

    from repro.core.api import get_method

    axis = stack_specs("dither64", "topk0.1")
    want = (FAMILY_DITHER, FAMILY_TOPK)
    if transform == "jit_argument":
        got = _traced_set(jax.tree.map(lambda a: a[0], axis))
    elif transform == "vmap":
        seen = []
        jax.vmap(lambda sp: seen.append(family_set(sp)) or sp.s)(axis)
        got = seen[0]
    elif transform == "tree_map":
        got = family_set(jax.tree.map(lambda a: jnp.repeat(a, 3), axis))
    elif transform == "device_put":
        got = family_set(jax.device_put(axis))
    elif transform == "fill_params":
        legacy = CompressorSpec(axis.family, axis.s, axis.frac,
                                families=axis.families)
        got = family_set(fill_params(legacy))
    elif transform == "replace_params":
        got = family_set(axis._replace(s=axis.s * 2.0))
    elif transform == "shard_map_specs":
        specs = jax.tree.map(lambda _: PartitionSpec(), axis)
        assert (jax.tree.structure(specs, is_leaf=lambda v: isinstance(
            v, PartitionSpec)) == jax.tree.structure(axis))
        got = family_set(specs)
    else:
        edges = dict(edge_levels=(8.0, 64.0)) if "edges" in transform \
            else {}
        hp = get_method("flecs_cgd").grid(alphas=(0.5,), grad_specs=axis,
                                          **edges)
        assert family_set(hp.hess_spec) == (FAMILY_DITHER,)
        assert jnp.shape(hp.hess_spec.family) == jnp.shape(hp.alpha)
        got = family_set(hp.grad_spec)
    assert got == want


def test_spec_from_bare_arrays_holds_all_families():
    bare = CompressorSpec(jnp.int32(FAMILY_DITHER), jnp.float32(64.0),
                          jnp.float32(1.0))
    assert family_set(bare) == ALL_FAMILIES
    assert family_set(fill_params(bare)) == ALL_FAMILIES
    assert family_set(jax.tree.map(lambda a: a, bare)) == ALL_FAMILIES
    assert _traced_set(bare) == ALL_FAMILIES
    # a family id replaced with a value the code cannot see: all six
    assert family_set(
        dither_spec(64.0)._replace(family=jnp.int32(FAMILY_TOPK))) \
        == ALL_FAMILIES
    # unknown and explicit all-six sets are one structure
    assert (jax.tree.structure(bare) == jax.tree.structure(
        bare._replace(families=ALL_FAMILIES)))


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp", "kernel"])
@pytest.mark.parametrize("names", [
    ("dither64", "dither16"), ("dither64", "topk0.1"), SIX,
], ids=["one_family", "two_families", "six_families"])
def test_narrowed_dispatch_is_bit_identical(rng, names, use_kernel):
    """Under vmap over a stacked spec, the switch over the spec's family
    set gives exactly what a literal six-branch ``lax.switch`` on the
    family id gives, each branch a one-family spec of that id.
    (``spec_bits`` keeps its full switch; it is compared all the same.)"""
    spec = stack_specs(*names)
    d = 40
    x = jnp.asarray(rng.normal(size=(len(names), d)), jnp.float32)
    keys = jax.random.split(jax.random.key(11), len(names))

    def algebra(sp, k, xi):
        return (compress(sp, k, xi, use_kernel=use_kernel),
                spec_bits(sp, d, use_kernel=use_kernel),
                spec_omega(sp, d))

    def literal_switch(sp, k, xi):
        return jax.lax.switch(sp.family, [
            lambda f=f: algebra(CompressorSpec(*sp[:4], families=(f,)),
                                k, xi)
            for f in ALL_FAMILIES])

    run = jax.jit(lambda fn, *a: jax.vmap(fn)(*a), static_argnums=0)
    for a, b in zip(run(algebra, spec, keys, x),
                    run(literal_switch, spec, keys, x)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Acceptance: sweep over (hess_s, beta) == per-point static runs
# ---------------------------------------------------------------------------

def test_sweep_over_hess_and_beta_matches_static_steps():
    """ONE compiled run_sweep varying hess_s AND beta reproduces each
    point's single run trace-for-trace: exact bit ledgers (same
    key streams => same dither draws) and iterates to batched-kernel ulp."""
    hp = hparam_grid([1.0], [1.0], [16.0], betas=[0.5, 1.0],
                     hess_levels=[8.0, 64.0])
    cfg0 = FlecsConfig(m=2, grad_compressor="dither16")
    sweep = make_flecs_sweep_step(cfg0, LG, LH)
    st0 = init_state(jnp.zeros(D), N)
    iters = 8
    rec = lambda s: PROB.metrics(s.w)                       # noqa: E731
    sts, tr = run_sweep(sweep, hp, st0, jax.random.key(21), iters,
                        record=rec)
    G = hp.alpha.shape[0]
    assert G == 4
    keys = jax.random.split(jax.random.key(21), G)
    for g in range(G):
        cfg_g = FlecsConfig(
            m=2, beta=float(hp.beta[g]), grad_compressor="dither16",
            hess_compressor=f"dither{int(hp.hess_s[g])}")
        st_g, tr_g = run_experiment(_flecs_step(cfg_g, LG, LH), st0,
                                    keys[g], iters, record=rec)
        np.testing.assert_array_equal(np.asarray(tr_g["bits_per_node"]),
                                      np.asarray(tr["bits_per_node"][g]))
        np.testing.assert_allclose(np.asarray(st_g.w), np.asarray(sts.w[g]),
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(tr_g["F"]),
                                   np.asarray(tr["F"][g]), rtol=1e-6)
        # the billed bits actually follow the point's hessian level
        expect = iters * bits_per_round(cfg_g, D)
        np.testing.assert_allclose(np.asarray(st_g.bits_per_node), expect)


def test_hparam_grid_widened_axes():
    hp = hparam_grid([0.5], [1.0], [16.0, 64.0], betas=[0.25, 1.0],
                     hess_levels=[8.0, 32.0])
    assert hp.alpha.shape == hp.beta.shape == hp.hess_s.shape == (8,)
    combos = set(zip(np.asarray(hp.grad_s).tolist(),
                     np.asarray(hp.beta).tolist(),
                     np.asarray(hp.hess_s).tolist()))
    assert combos == {(s, b, hs) for s in (16., 64.) for b in (0.25, 1.0)
                      for hs in (8., 32.)}
    # every point's specs are dithering family
    assert set(np.asarray(hp.grad_spec.family).tolist()) == {FAMILY_DITHER}
    assert set(np.asarray(hp.hess_spec.family).tolist()) == {FAMILY_DITHER}


# ---------------------------------------------------------------------------
# Acceptance: async sweep over (tau, buffer_k) == independent async runs
# ---------------------------------------------------------------------------

def test_async_sweep_matches_independent_async_runs():
    """run_async_sweep over a (tau, buffer_k) grid sharing one max-delay
    buffer shape == independent single-point async runs per point; the
    tau=0 point == the synchronous engine bit-for-bit."""
    taus, Ks = [0, 2], [1.0, 2.0]
    cfg = FlecsConfig(m=2, alpha=0.5, grad_compressor="dither64",
                      hess_compressor="dither64",
                      participation=0.5, sampling="choice")
    ahp = async_hparam_grid(taus, Ks, alpha=cfg.alpha, gamma=cfg.gamma,
                            beta=cfg.beta, grad_s=64.0, hess_s=64.0)
    sweep = make_async_step(make_flecs_async_hooks(cfg, LG, LH))
    max_delay = max(taus)
    st0 = _flecs_async_state(cfg, LG, LH, D, N, max_delay)
    iters = 20
    rec = lambda s: {"F": PROB.global_loss(s.w)}            # noqa: E731
    sts, tr = run_async_sweep(sweep, ahp, st0, jax.random.key(17), iters,
                              record=rec)
    G = ahp.tau.shape[0]
    keys = jax.random.split(jax.random.key(17), G)
    for g in range(G):
        # IMPORTANT: the independent run must use the SAME buffer shape
        # (the shared max-delay slots) to consume identical slot indices
        step_g = functools.partial(sweep, AsyncHParams(
            hparams_from_config(cfg), jnp.int32(int(ahp.tau[g])),
            jnp.float32(float(ahp.buffer_k[g]))))
        st_g, tr_g = run_experiment(step_g, st0, keys[g], iters, record=rec)
        np.testing.assert_array_equal(np.asarray(tr_g["bits_per_node"]),
                                      np.asarray(tr["bits_per_node"][g]))
        np.testing.assert_allclose(np.asarray(st_g.w), np.asarray(sts.w[g]),
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(tr_g["F"]),
                                   np.asarray(tr["F"][g]), rtol=1e-6)

    # tau=0, K=1 under sampling: the async grid point IS the sync engine.
    # Bit-for-bit is pinned against the unbatched specialization (the same
    # ops the vmapped grid runs; batched eigh kernels differ from the
    # unbatched ones only in the last ulp, so the in-grid row is compared
    # with exact ledgers + ulp-tolerance iterates above).
    g0 = int(np.argmax((np.asarray(ahp.tau) == 0)
                       & (np.asarray(ahp.buffer_k) == 1.0)))
    step_g0 = functools.partial(sweep, AsyncHParams(
        hparams_from_config(cfg), jnp.int32(0), jnp.float32(1)))
    st_a, tr_a = run_experiment(step_g0, st0, keys[g0], iters, record=rec)
    st_s, tr_s = run_experiment(_flecs_step(cfg, LG, LH),
                                init_state(jnp.zeros(D), N), keys[g0],
                                iters, record=rec)
    np.testing.assert_allclose(np.asarray(tr_s["F"]),
                               np.asarray(tr_a["F"]), rtol=0, atol=0)
    np.testing.assert_array_equal(np.asarray(tr_s["bits_per_node"]),
                                  np.asarray(tr_a["bits_per_node"]))
    np.testing.assert_array_equal(np.asarray(st_s.w), np.asarray(st_a.w))
    np.testing.assert_array_equal(np.asarray(tr_s["bits_per_node"]),
                                  np.asarray(tr["bits_per_node"][g0]))


def test_async_sweep_rejects_undersized_buffer():
    ahp = async_hparam_grid([0, 3], [1.0], alpha=0.5)
    cfg = FlecsConfig(m=1)
    sweep = make_async_step(make_flecs_async_hooks(cfg, LG, LH))
    st0 = _flecs_async_state(cfg, LG, LH, D, N, max_delay=1)  # 2 slots < 4
    with pytest.raises(ValueError):
        run_async_sweep(sweep, ahp, st0, jax.random.key(0), 4)


def test_sample_delays_traced_tau():
    """sample_delays under vmap over a traced tau axis: bounds hold per
    point and tau=0 is all-zero for every delay model."""
    taus = jnp.asarray([0, 1, 3], jnp.int32)
    for kind in ("fixed", "uniform", "geometric"):
        ds = jax.vmap(
            lambda t: sample_delays(kind, jax.random.key(4), 64, t))(taus)
        assert ds.shape == (3, 64) and ds.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(ds[0]), 0)
        for i, t in enumerate((0, 1, 3)):
            assert int(ds[i].max()) <= t
        if kind == "fixed":
            np.testing.assert_array_equal(np.asarray(ds[2]), 3)
    with pytest.raises(ValueError):
        sample_delays("exponential", jax.random.key(0), 4, 1)


# ---------------------------------------------------------------------------
# Auto-damped alpha
# ---------------------------------------------------------------------------

def test_damped_alpha_rule():
    """alpha0 · min(1, p·K/n): full participation sync point undamped; the
    ROADMAP's p=0.5, K=n/4 study point lands at alpha0/8 (the empirically
    needed 0.1–0.2 band for alpha0=1)."""
    assert float(damped_alpha(1.0, 1.0, 20, 20)) == 1.0
    assert float(damped_alpha(1.0, 0.5, 5, 20)) == pytest.approx(0.125)
    assert float(damped_alpha(0.8, 1.0, 40, 20)) == pytest.approx(0.8)  # clip
    # traced [G] buffer_k axis => [G] damped alphas
    out = damped_alpha(1.0, 0.5, jnp.asarray([1.0, 5.0, 20.0]), 20)
    np.testing.assert_allclose(np.asarray(out), [0.025, 0.125, 0.5])


def test_async_grid_auto_damping_converges():
    """Auto-damped (tau, K) grid on the staleness study problem: every grid
    point converges near F* without hand-tuned alphas."""
    prob = make_problem(d=24, n_workers=8, r=96, mu=1e-2,
                        heterogeneity=0.2, seed=0)
    lg, lh = prob.make_oracles(batch=0)
    f_star = float(prob.global_loss(prob.solve()))
    cfg = FlecsConfig(m=2, grad_compressor="dither128",
                      hess_compressor="dither128",
                      participation=0.5, sampling="choice")
    ahp = async_hparam_grid([0, 2], [2.0, 4.0], alpha=1.0,
                            auto_damp=(cfg.participation, prob.n_workers))
    sweep = make_async_step(make_flecs_async_hooks(cfg, lg, lh))
    st0 = _flecs_async_state(cfg, lg, lh, prob.d, prob.n_workers, 2)
    f0 = float(prob.global_loss(st0.w))
    sts, tr = run_async_sweep(sweep, ahp, st0, jax.random.key(1), 400,
                              record_every=100,
                              record=lambda s: {"F": prob.global_loss(s.w)})
    f_end = np.asarray(tr["F"][:, -1], np.float64)
    assert np.all(f_end - f_star < 5e-3), (f_star, f_end)
    assert np.all(f_end < f0 - 5e-3), (f0, f_end)
