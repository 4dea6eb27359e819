"""Plan-level bit budgets: the budget-freeze scan mode.

Pins the budget-fair contracts:
  * ``iters_for_bit_budget`` edge cases — zero budget, budget below one
    round's price, grid (budget × price) form, dimension-aware top-k
    prices;
  * budget-freeze == truncated-run equivalence with EXACT bit ledgers:
    a T-round budget run equals the unbudgeted run for the first
    t* = iters_for_bit_budget(budget, price) rounds and is a frozen no-op
    (bit-stable rows, frozen iterate, zeroed activity counters) after;
  * the async engine freezes on the same ledger — bits billed at the
    *arrival* round gate the freeze exactly like synchronous bits;
  * ``ExperimentPlan.bit_budget`` crosses a traced budget grid with every
    run, derives spec-aware scan lengths, and still lowers the whole
    figure to ONE compiled program (``api.plan_compiles``);
  * the guards: non-positive plan budgets, double budget axes, and
    price-query consistency with the concrete ``bits_per_round``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.api import ExperimentPlan, MethodRun, get_method, run_plan
from repro.core.compressors import spec_bits, topk_spec
from repro.core.driver import (StalenessSchedule, freeze_on_bit_budget,
                               hparams_bit_budget, init_async_state,
                               iters_for_bit_budget, make_async_step,
                               run_async_sweep, run_sweep)
from repro.core.flecs import (FlecsConfig, async_hparam_grid, bits_per_round,
                              hparam_grid, hparams_from_config,
                              hparams_round_bits, init_state,
                              make_flecs_async_hooks, make_flecs_sweep_step)
from repro.data.logreg import make_problem
from repro.optim.baselines import (DianaConfig, FedNLConfig, GDConfig,
                                   diana_round_bits,
                                   diana_hparams_from_config,
                                   fednl_round_bits,
                                   fednl_hparams_from_config, gd_round_bits,
                                   gd_hparams_from_config)

PROB = make_problem(d=16, n_workers=4, r=16, mu=1e-3, seed=7)
LG, LH = PROB.make_oracles(batch=0)
N, D = PROB.n_workers, PROB.d
CFG = FlecsConfig(m=2, grad_compressor="dither64", hess_compressor="dither64")
PRICE = bits_per_round(CFG, D)


# ---------------------------------------------------------------------------
# iters_for_bit_budget
# ---------------------------------------------------------------------------

def test_iters_for_bit_budget_edge_cases():
    # the pre-existing scalar contract
    assert iters_for_bit_budget(100, 10) == 10
    assert iters_for_bit_budget(101, 10) == 11
    assert iters_for_bit_budget(1, 10) == 1
    # zero budget: the scan still needs one round (the freeze gate holds
    # it frozen — see test_zero_and_subround_budgets)
    assert iters_for_bit_budget(0, 10) == 1
    # budget below one round's price
    assert iters_for_bit_budget(3, 10) == 1
    # grid form: the bound covers every (budget, price) point
    assert iters_for_bit_budget([100, 10], [10, 1]) == 10
    assert iters_for_bit_budget([100, 990], [10, 11]) == 90
    with pytest.raises(ValueError):
        iters_for_bit_budget(10, 0)
    with pytest.raises(ValueError):
        iters_for_bit_budget([], [10])
    # non-finite budgets have no derivable scan length: fail loudly
    # instead of silently minting an int from inf/nan
    with pytest.raises(ValueError, match="finite"):
        iters_for_bit_budget(float("inf"), 10)
    with pytest.raises(ValueError, match="finite"):
        iters_for_bit_budget(float("nan"), 10)
    with pytest.raises(ValueError, match="finite"):
        iters_for_bit_budget([100.0, float("inf")], [10.0, 10.0])


def test_iters_for_bit_budget_topk_dimension_aware_price():
    """Top-k prices are dimension-aware: ceil(frac·d) kept values, each
    (32 + ceil(log2 d)) bits — the budget bound must follow .bits(d), not
    the old flat 64·frac-per-element rule."""
    d = 1000
    price = float(spec_bits(topk_spec(0.1), d))
    assert price == 100 * (32 + 10)                     # ceil(log2 1000)=10
    assert iters_for_bit_budget(2 * price, price) == 2
    assert iters_for_bit_budget(2 * price + 1, price) == 3
    # the flat rule would give a different (wrong) round count
    flat = 64.0 * 0.1 * d
    assert iters_for_bit_budget(10 * price, flat) != 10


def test_round_bits_queries_match_concrete_prices():
    """Every registry price query agrees with the concrete accounting the
    comm tests pin (bits_per_round / spec_bits)."""
    hp = hparams_round_bits(CFG, get_method("flecs_cgd").from_config(CFG), D)
    assert float(hp) == PRICE
    dc = DianaConfig()
    assert float(diana_round_bits(dc, diana_hparams_from_config(dc), D)) \
        == float(spec_bits(get_method("diana").from_config(dc).spec, D))
    fc = FedNLConfig()
    assert float(fednl_round_bits(fc, fednl_hparams_from_config(fc), D)) \
        == 32.0 * D + float(spec_bits(topk_spec(0.25), D * D))
    gc = GDConfig()
    assert float(gd_round_bits(gc, gd_hparams_from_config(gc), D)) == 32.0 * D
    # grid form: a [G] hparams pytree prices per point
    grid = hparam_grid([1.0], [1.0], [16.0, 64.0], hess_levels=[64.0])
    prices = np.asarray(hparams_round_bits(CFG, grid, D))
    assert prices.shape == (2,)
    assert prices[0] != prices[1]                        # level-dependent


# ---------------------------------------------------------------------------
# Budget-freeze == truncated run (exact ledgers)
# ---------------------------------------------------------------------------

def _budget_hp(budget, **grid_kw):
    hp = hparam_grid(**{"alphas": [1.0], "gammas": [1.0],
                        "grad_levels": [64.0], **grid_kw})
    G = hp.alpha.shape[0]
    return hp._replace(bit_budget=jnp.full((G,), budget, jnp.float32))


def test_budget_freeze_equals_truncated_run_exact_ledgers():
    """A T-round budget run == the unbudgeted run truncated at t*, padded
    with frozen rows: EXACT bit ledgers on the live prefix, bit-stable
    ledger and frozen iterate on the tail."""
    budget = 4.5 * PRICE                                 # t* = 5
    t_star = iters_for_bit_budget(budget, PRICE)
    assert t_star == 5
    T = 9
    sweep = make_flecs_sweep_step(CFG, LG, LH)
    st0 = init_state(jnp.zeros(D), N)
    rec = lambda s: {"w": s.w}                           # noqa: E731
    hp = hparam_grid([1.0], [1.0], [64.0])
    sts_b, tr_b = run_sweep(sweep, _budget_hp(budget), st0,
                            jax.random.key(3), T, record=rec)
    sts, tr = run_sweep(sweep, hp, st0, jax.random.key(3), T, record=rec)

    bits_b = np.asarray(tr_b["bits_per_node"][0])        # [T, n]
    bits = np.asarray(tr["bits_per_node"][0])
    np.testing.assert_array_equal(bits_b[:t_star], bits[:t_star])
    for k in range(t_star, T):                           # bit-stable tail
        np.testing.assert_array_equal(bits_b[k], bits[t_star - 1])
    assert float(sts_b.bits_per_node[0, 0]) == t_star * PRICE

    w_b = np.asarray(tr_b["w"][0])
    w = np.asarray(tr["w"][0])
    np.testing.assert_allclose(w_b[:t_star], w[:t_star], rtol=0, atol=1e-6)
    for k in range(t_star, T):                           # frozen iterate
        np.testing.assert_array_equal(w_b[k], w_b[t_star - 1])

    # activity counters report the freeze: nothing sampled on frozen rows
    n_active = np.asarray(tr_b["n_active"][0])
    assert np.all(n_active[:t_star] > 0)
    np.testing.assert_array_equal(n_active[t_star:], 0.0)


def test_zero_and_subround_budgets():
    """budget <= one round's price: exactly one live round is charged
    (rounds run while max bits < budget, and round 0 starts at 0 bits) —
    except budget 0, which freezes from the very first round."""
    sweep = make_flecs_sweep_step(CFG, LG, LH)
    st0 = init_state(jnp.zeros(D), N)
    sts, tr = run_sweep(sweep, _budget_hp(0.5 * PRICE), st0,
                        jax.random.key(0), 4)
    assert float(sts.bits_per_node[0, 0]) == PRICE       # one round charged
    sts0, tr0 = run_sweep(sweep, _budget_hp(0.0), st0, jax.random.key(0), 4)
    assert float(sts0.bits_per_node[0, 0]) == 0.0        # frozen from k=0
    np.testing.assert_array_equal(np.asarray(sts0.w[0]), np.zeros(D))


def test_budget_axis_vmaps_with_other_axes():
    """A (budget × level) grid runs as one program with per-point freeze
    points: each point's final ledger is its own t*(budget, price)·price."""
    budgets = (2.5 * PRICE, 7.5 * PRICE)
    hp2 = hparam_grid([1.0], [1.0], [16.0, 64.0])        # G = 2
    hp, bud = api.cross_bit_budget(hp2, jnp.asarray(budgets, jnp.float32))
    assert hp.alpha.shape == (4,)
    # ordering contract: point b*G + g
    np.testing.assert_array_equal(np.asarray(hp.grad_s),
                                  [16.0, 64.0, 16.0, 64.0])
    np.testing.assert_array_equal(
        np.asarray(bud), [budgets[0]] * 2 + [budgets[1]] * 2)
    prices = np.asarray(hparams_round_bits(CFG, hp2, D))
    T = iters_for_bit_budget(np.asarray(bud),
                             np.asarray(hparams_round_bits(CFG, hp, D)))
    sweep = make_flecs_sweep_step(CFG, LG, LH)
    sts, tr = run_sweep(sweep, hp, init_state(jnp.zeros(D), N),
                        jax.random.key(1), T)
    for i in range(4):
        b, g = divmod(i, 2)
        t_star = iters_for_bit_budget(budgets[b], prices[g])
        assert float(sts.bits_per_node[i, 0]) == t_star * prices[g], i


def test_async_budget_freeze_arrival_billing():
    """The async engine freezes on the same ledger — bits charged at the
    ARRIVAL round gate the freeze, and the frozen tail is bit-stable."""
    cfg = FlecsConfig(m=2, grad_compressor="dither64",
                      hess_compressor="dither64")
    tau, T = 2, 18
    ahp = async_hparam_grid([tau], [float(N)])           # G = 1
    budget = 2.5 * PRICE
    ahp_b = ahp._replace(hp=ahp.hp._replace(
        bit_budget=jnp.full((1,), budget, jnp.float32)))
    hooks = make_flecs_async_hooks(cfg, LG, LH)
    sweep = make_async_step(hooks)
    st0 = init_async_state(hooks, init_state(jnp.zeros(D), N),
                           hparams_from_config(cfg), tau)
    sts_b, tr_b = run_async_sweep(sweep, ahp_b, st0, jax.random.key(5), T)
    sts, tr = run_async_sweep(sweep, ahp, st0, jax.random.key(5), T)

    led_b = np.max(np.asarray(tr_b["bits_per_node"][0]), axis=1)    # [T]
    led = np.max(np.asarray(tr["bits_per_node"][0]), axis=1)
    # freeze point: the first round whose ledger reached the budget
    t_star = int(np.flatnonzero(led >= budget)[0]) + 1
    np.testing.assert_array_equal(led_b[:t_star], led[:t_star])
    np.testing.assert_array_equal(led_b[t_star:], led_b[t_star - 1])
    assert led_b[-1] >= budget
    # live prefix identical (same keys, same arrival billing)
    np.testing.assert_array_equal(
        np.asarray(tr_b["n_arrived"][0][:t_star]),
        np.asarray(tr["n_arrived"][0][:t_star]))
    # frozen tail: no arrivals, no flushes reported
    np.testing.assert_array_equal(np.asarray(tr_b["n_arrived"][0][t_star:]),
                                  0.0)
    np.testing.assert_array_equal(np.asarray(tr_b["flushed"][0][t_star:]),
                                  0.0)


def test_freeze_requires_bits_ledger():
    class NoBits:
        bit_budget = jnp.float32(10.0)

    step = freeze_on_bit_budget(lambda hp, st, k: (st, {}))
    with pytest.raises(ValueError, match="bits_per_node"):
        step(NoBits(), object(), jax.random.key(0))
    assert hparams_bit_budget(NoBits()) is not None
    assert hparams_bit_budget(hparam_grid([1.0], [1.0], [64.0])) is None


# ---------------------------------------------------------------------------
# ExperimentPlan.bit_budget
# ---------------------------------------------------------------------------

def test_plan_budget_axis_five_methods_one_compile():
    """All five methods × a [2] traced budget grid: ONE compiled program,
    every point reaches its budget within one round's price, smaller
    budgets end in bit-stable frozen tails."""
    budgets = (2.0 * 32.0 * D, 8.0 * 32.0 * D)
    plan = ExperimentPlan(
        problem=PROB,
        runs=tuple(MethodRun(m) for m in
                   ("flecs", "flecs_cgd", "diana", "fednl", "gd")),
        bit_budget=budgets)
    before = api.plan_compiles()
    res = run_plan(plan)
    assert api.plan_compiles() - before == 1
    for lab in res.labels:
        spec = get_method(lab)
        cfg = spec.default_config()
        price = float(np.asarray(
            spec.round_bits(PROB, cfg, jax.tree.map(
                lambda a: jnp.asarray(a)[None],
                spec.from_config(cfg)))).ravel()[0])
        bits = np.asarray(res.traces[lab]["bits_per_node"])     # [2, T, n]
        for b, budget in enumerate(budgets):
            ledger = np.max(bits[b], axis=1)
            assert ledger[-1] >= budget, (lab, budget)
            assert ledger[-1] < budget + price, (lab, budget)
            t_star = int(np.flatnonzero(ledger >= budget)[0]) + 1
            np.testing.assert_array_equal(ledger[t_star:],
                                          ledger[t_star - 1])
        # scan length is the spec-aware bound for the largest budget
        assert bits.shape[1] == iters_for_bit_budget(max(budgets), price)


def test_plan_budget_matches_truncated_legacy_run():
    """Plan budget run == the SAME plan truncated at t* via run.iters:
    identical live rounds (exact ledgers), frozen tail after."""
    budget = 6.0 * 32.0 * D
    run = MethodRun("diana", cfg=DianaConfig(alpha=1.0, gamma=0.5))
    res_b = run_plan(ExperimentPlan(problem=PROB, runs=(run,),
                                    bit_budget=budget))
    price = 8.0 * D                                      # dither64
    t_star = iters_for_bit_budget(budget, price)
    res_t = run_plan(ExperimentPlan(problem=PROB, runs=(
        MethodRun("diana", cfg=DianaConfig(alpha=1.0, gamma=0.5),
                  iters=t_star),)))
    bits_b = np.asarray(res_b.traces["diana"]["bits_per_node"][0])
    bits_t = np.asarray(res_t.traces["diana"]["bits_per_node"][0])
    np.testing.assert_array_equal(bits_b[:t_star], bits_t)
    np.testing.assert_allclose(
        np.asarray(res_b.traces["diana"]["F"][0][t_star - 1:]),
        float(res_t.traces["diana"]["F"][0][-1]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(res_b.states["diana"].w[0]),
                               np.asarray(res_t.states["diana"].w[0]),
                               rtol=0, atol=1e-6)


def test_plan_budget_async_derives_stretched_scan_length():
    """Async budget plans stretch the scan bound by (tau+1) for arrival
    billing and still reach the budget."""
    budget = 3.0 * 32.0 * D
    tau = 2
    plan = ExperimentPlan(
        problem=PROB,
        runs=(MethodRun("gd", cfg=GDConfig(alpha=1.0)),),
        staleness=StalenessSchedule("fixed", tau=tau),
        buffer_k=float(N),
        bit_budget=budget)
    res = run_plan(plan)
    base = iters_for_bit_budget(budget, 32.0 * D)
    T = res.traces["gd"]["bits_per_node"].shape[1]
    assert T == base * (tau + 1) + tau
    ledger = np.max(np.asarray(res.traces["gd"]["bits_per_node"][0]), axis=1)
    assert ledger[-1] >= budget


def test_plan_budget_guards():
    runs = (MethodRun("gd"),)
    with pytest.raises(ValueError, match="positive"):
        run_plan(ExperimentPlan(problem=PROB, runs=runs, bit_budget=-1.0))
    with pytest.raises(ValueError, match="positive"):
        run_plan(ExperimentPlan(problem=PROB, runs=runs,
                                bit_budget=(1024.0, 0.0)))
    # double budget axes fail loudly instead of silently overwriting
    hp = _budget_hp(PRICE)
    with pytest.raises(ValueError, match="bit_budget"):
        run_plan(ExperimentPlan(
            problem=PROB, runs=(MethodRun("flecs_cgd", hparams=hp),),
            bit_budget=2048.0))
    # hparams-level budgets (no plan crossing) still work standalone
    res = run_plan(ExperimentPlan(
        problem=PROB, runs=(MethodRun("flecs_cgd", cfg=CFG, hparams=hp),),
        iters=8))
    assert float(res.states["flecs_cgd"].bits_per_node[0, 0]) == PRICE
