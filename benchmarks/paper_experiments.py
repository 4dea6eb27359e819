"""Paper-experiment reproductions (one per paper figure/claim).

Figure 1/2  — FLECS vs FLECS-CGD: objective F(w_k) and ||∇F(w_k)||² versus
              communicated bits per node, on LIBSVM-dimension synthetic
              logistic regression (a9a d=123), m ∈ {1, 2, 4, 8}.
Figure 3    — iterate updates: truncated inverse (Alg 4) vs FedSONIA (Alg 5).
Claim §3    — communication complexity table:
              O(cmd + 32d + 32m²) vs O(cmd + cd + 32m²), measured.
Comparison  — vs DIANA / FedNL / GD baselines (as the FLECS paper does),
              plus a BUDGET-FAIR comparison: all five methods frozen at
              the same traced per-node bit budgets (the DIANA/FedNL-style
              x-axis) via the budget-freeze scan mode — one compiled
              program for the whole (method × budget) figure.
Beyond-paper — dithering-level ablation, a *vmapped* step-size x level grid
              (one compiled program for the whole grid), a partial-
              participation ablation as a TRACED Bernoulli-p sweep axis,
              an async buffered-aggregation grid (FedBuff-style delay x
              participation, bits charged at the arrival round), and the
              full traced-spec ablation grids: (grad_s x hess_s x beta) and
              auto-damped (tau x buffer_k).

One compiled program per figure: the comparison figures (fig1, baselines,
participation, ablation grid) are authored as ``repro.core.api``
``ExperimentPlan``s and lowered by ``run_plan`` to a single jitted
program each — fig1's old 8 compiles (4 sketch sizes × 2 methods) are now
ONE, with the FLECS-vs-FLECS-CGD axis a traced compressor-*family* grid
axis (``compressors.stack_specs``) and the m axis a set of structural
segments inside the same program.  ``assert_one_compile`` checks the
invariant at run time via ``api.plan_compiles()``.

Every trajectory is ONE lax.scan program via ``repro.core.driver`` —
per-iteration metrics are recorded inside the scan, not by re-entering the
host between rounds.

Emits CSV rows ``name,us_per_call,derived`` plus human-readable tables;
raw trajectories land in benchmarks/out/*.json for plotting.

Standalone smoke entries (the CI sweep-smoke / plan-smoke jobs)::

    PYTHONPATH=src python benchmarks/paper_experiments.py \
        --grids-only --d 16 --workers 4 --r 16 --iters 6
    PYTHONPATH=src python benchmarks/paper_experiments.py \
        --plans-only --d 16 --workers 4 --r 16 --iters 6
"""
from __future__ import annotations

import functools
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.core.api import ExperimentPlan, MethodRun, get_method, run_plan
from repro.core.compressors import stack_specs
from repro.core.driver import (AsyncHParams, StalenessSchedule,
                               init_async_state, make_async_step,
                               run_async_sweep, run_experiment, run_sweep)
from repro.core.flecs import (FlecsConfig, async_hparam_grid, bits_per_round,
                              hparam_grid, hparams_from_config, init_state,
                              make_flecs_async_hooks, make_flecs_sweep_step)
from repro.data.logreg import make_problem
from repro.optim.baselines import DianaConfig, FedNLConfig, GDConfig

OUT = Path(__file__).resolve().parent / "out"


def _flecs_step(cfg, lg, lh):
    """The FLECS sweep step at the config's own hparams point."""
    return functools.partial(make_flecs_sweep_step(cfg, lg, lh),
                             hparams_from_config(cfg))


def assert_one_compile(run):
    """Execute ``run()`` (a run_plan call) asserting it compiled exactly
    one program — the figure-level invariant the redesign exists for."""
    before = api.plan_compiles()
    result = run()
    compiles = api.plan_compiles() - before
    assert compiles == 1, f"plan compiled {compiles} programs, expected 1"
    return result


def _rows_from_traces(tr, iters, every):
    """Thin JSON rows from one run's {F, grad_sq, bits_per_node [n]}
    traces — the single row schema every figure JSON shares."""
    F = np.asarray(tr["F"])
    g2 = np.asarray(tr["grad_sq"])
    bits = np.asarray(tr["bits_per_node"]).max(axis=1)
    return [{"iter": k, "F": float(F[k]), "grad_sq": float(g2[k]),
             "bits_per_node": float(bits[k])}
            for k in range(iters) if k % every == 0 or k == iters - 1]


def _trajectory(step, state, prob, iters, seed=0, every=5):
    """One scan program; thin the in-scan trace to every`every`-th row."""
    t0 = time.perf_counter()
    state, tr = run_experiment(step, state, jax.random.key(seed), iters,
                               record=lambda st: prob.metrics(st.w))
    jax.block_until_ready(state)
    dt = (time.perf_counter() - t0) / iters * 1e6
    return _rows_from_traces(tr, iters, every), dt


def _trace_rows(tr, g, iters, every=5):
    """:func:`_rows_from_traces` for grid point ``g`` of a [G, iters, ...]
    plan trace."""
    return _rows_from_traces(jax.tree.map(lambda a: a[g], tr), iters, every)


FIG1_MS = (1, 2, 4, 8)
FIG1_FAMILIES = ("FLECS", "FLECS-CGD")       # grid order of the family axis


def fig1_plan(prob, iters=300) -> ExperimentPlan:
    """Fig 1/2 as ONE ExperimentPlan: the FLECS-vs-FLECS-CGD comparison is
    a traced compressor-FAMILY grid axis (identity vs dither64) inside each
    sketch-size segment; the m axis changes array shapes, so each m is a
    structural segment of the same single compiled program."""
    fam = stack_specs("identity", "dither64")
    flecs_m = get_method("flecs_cgd")
    return ExperimentPlan(
        problem=prob,
        runs=tuple(
            MethodRun("flecs_cgd",
                      cfg=FlecsConfig(m=m, alpha=1.0, beta=1.0, gamma=1.0,
                                      hess_compressor="dither64"),
                      hparams=flecs_m.grid(grad_specs=fam),
                      label=f"m{m}")
            for m in FIG1_MS),
        iters=iters)


def fig1_flecs_vs_cgd(prob, iters=300, every=5):
    """Fig 1/2: both methods, m sweep, dithering s=64 (paper's setting) —
    8 trajectories, ONE compiled program (was 8 before the plan API)."""
    res = assert_one_compile(lambda: run_plan(fig1_plan(prob, iters)))
    results = {}
    us = {}
    dt = res.run_s / (iters * len(FIG1_MS) * len(FIG1_FAMILIES)) * 1e6
    for m in FIG1_MS:
        tr = res.traces[f"m{m}"]
        for g, name in enumerate(FIG1_FAMILIES):
            results[f"{name}-m{m}"] = _trace_rows(tr, g, iters, every)
            us[f"{name}-m{m}"] = dt
    return results, us


def fig3_iterate_updates(prob, iters=300):
    """Fig 3: Alg 4 (truncated inverse, curvature floor = μ) vs Alg 5."""
    lg, lh = prob.make_oracles()
    results = {}
    us = {}
    for name, kw in (
        ("FedSONIA(Alg5)", dict(direction="fedsonia")),
        ("TruncInv(Alg4)", dict(direction="truncated_inverse",
                                tinv_floor=prob.mu * 10)),
        ("TruncInv+LSR1", dict(direction="truncated_inverse",
                               hessian_update="lsr1",
                               tinv_floor=prob.mu)),
    ):
        cfg = FlecsConfig(m=4, grad_compressor="dither64",
                          hess_compressor="dither64", **kw)
        step = _flecs_step(cfg, lg, lh)
        st = init_state(jnp.zeros(prob.d), prob.n_workers)
        rows, dt = _trajectory(step, st, prob, iters)
        results[name] = rows
        us[name] = dt
    return results, us


def comm_table(prob):
    """§3 communication complexity, measured vs formula."""
    lg, lh = prob.make_oracles()
    d = prob.d
    rows = []
    for m in (1, 4):
        for name, gc, c_bits in (("FLECS", "identity", 32),
                                 ("FLECS-CGD", "dither64", 8)):
            cfg = FlecsConfig(m=m, grad_compressor=gc,
                              hess_compressor="dither64")
            step = _flecs_step(cfg, lg, lh)
            st = init_state(jnp.zeros(prob.d), prob.n_workers)
            st, _ = run_experiment(step, st, jax.random.key(0), 1)
            measured = float(st.bits_per_node[0])
            formula = 8 * m * d + c_bits * d + 32 * m * m
            rows.append({"method": name, "m": m, "measured_bits": measured,
                         "formula_bits": formula,
                         "match": abs(measured - formula) < 1e-3
                         and formula == bits_per_round(cfg, d)})
    return rows


def baselines_plan(prob, iters=200) -> ExperimentPlan:
    """The four-method comparison as ONE plan (four structural segments,
    one compiled program); FedNL keeps its shorter round budget."""
    return ExperimentPlan(
        problem=prob,
        runs=(
            MethodRun("flecs_cgd",
                      cfg=FlecsConfig(m=2, grad_compressor="dither64",
                                      hess_compressor="dither64"),
                      label="FLECS-CGD"),
            MethodRun("diana", cfg=DianaConfig(alpha=1.0, gamma=0.5,
                                               compressor="dither64"),
                      label="DIANA"),
            MethodRun("fednl", cfg=FedNLConfig(alpha=1.0,
                                               compressor="topk0.25",
                                               mu=prob.mu),
                      iters=min(iters, 80), label="FedNL"),
            MethodRun("gd", cfg=GDConfig(alpha=2.0), label="GD"),
        ),
        iters=iters)


def baselines_comparison(prob, iters=200):
    res = assert_one_compile(lambda: run_plan(baselines_plan(prob, iters)))
    out = {}
    for lab in res.labels:
        n_it = res.traces[lab]["F"].shape[1]
        dt = res.run_s / (len(res.labels) * n_it) * 1e6
        out[lab] = (_trace_rows(res.traces[lab], 0, n_it), dt)
    return out


def ablation_dither_levels(prob, iters=200):
    """Beyond-paper ablation: dithering levels s ∈ {4,16,64,128} — the
    bits/quality trade-off behind the paper's fixed s=64/128 choice."""
    lg, lh = prob.make_oracles()
    rows = []
    for s in (4, 16, 64, 128):
        cfg = FlecsConfig(m=1, grad_compressor=f"dither{s}",
                          hess_compressor=f"dither{s}")
        step = _flecs_step(cfg, lg, lh)
        st, tr = run_experiment(step, init_state(jnp.zeros(prob.d),
                                                 prob.n_workers),
                                jax.random.key(0), iters,
                                record=lambda st: prob.metrics(st.w))
        rows.append({"s": s,
                     "F": float(tr["F"][-1]),
                     "grad_sq": float(tr["grad_sq"][-1]),
                     "Mbits": float(jnp.max(st.bits_per_node)) / 1e6})
    return rows


def vmapped_grid(prob, iters=200):
    """Beyond-paper: the whole step-size x dithering-level comparison grid
    as ONE compiled vmapped scan (driver.run_sweep)."""
    lg, lh = prob.make_oracles()
    cfg = FlecsConfig(m=2, hess_compressor="dither64")
    hp = hparam_grid([0.5, 1.0], [1.0], [16.0, 64.0, 128.0])
    sweep = make_flecs_sweep_step(cfg, lg, lh)
    t0 = time.perf_counter()
    sts, tr = run_sweep(sweep, hp, init_state(jnp.zeros(prob.d),
                                              prob.n_workers),
                        jax.random.key(0), iters,
                        record=lambda st: prob.metrics(st.w))
    jax.block_until_ready(sts)
    G = hp.alpha.shape[0]
    dt = (time.perf_counter() - t0) / (iters * G) * 1e6
    rows = [{"alpha": float(hp.alpha[g]), "grad_s": float(hp.grad_s[g]),
             "F": float(tr["F"][g, -1]),
             "grad_sq": float(tr["grad_sq"][g, -1]),
             "Mbits": float(jnp.max(sts.bits_per_node[g])) / 1e6}
            for g in range(G)]
    return rows, dt


PARTICIPATION_PS = (1.0, 0.5, 0.25)


def participation_plan(prob, iters=300) -> ExperimentPlan:
    """Beyond-paper participation ablation as ONE vmapped sweep axis: the
    Bernoulli probability p is a TRACED hparam (paired with a damped alpha
    per point), replacing the old per-p Python loop of separate compiles.
    Bernoulli sampling (the traced form) — exact-k "choice" resolves its
    worker count at trace time and cannot join a traced axis."""
    from repro.core.flecs import FlecsHParams
    from repro.core.compressors import dither_spec
    G = len(PARTICIPATION_PS)
    full = lambda v: jnp.full((G,), v, jnp.float32)      # noqa: E731
    hp = FlecsHParams(
        alpha=jnp.asarray([1.0 if p == 1.0 else 0.5
                           for p in PARTICIPATION_PS], jnp.float32),
        gamma=full(1.0), beta=full(1.0),
        grad_spec=dither_spec(full(64.0)),
        hess_spec=dither_spec(full(64.0)),
        p=jnp.asarray(PARTICIPATION_PS, jnp.float32))
    return ExperimentPlan(
        problem=prob,
        runs=(MethodRun("flecs_cgd", cfg=FlecsConfig(m=2), hparams=hp,
                        label="participation"),),
        iters=iters)


def participation_ablation(prob, iters=300):
    """Client sampling p ∈ {1.0, 0.5, 0.25} — objective vs the per-worker
    cumulative bits ledger, the whole axis one compiled program."""
    res = assert_one_compile(lambda: run_plan(participation_plan(prob,
                                                                 iters)))
    st = res.states["participation"]
    tr = res.traces["participation"]
    return [{"p": p, "F": float(tr["F"][g, -1]),
             "grad_sq": float(tr["grad_sq"][g, -1]),
             "Mbits_mean": float(jnp.mean(st.bits_per_node[g])) / 1e6,
             "active_mean": float(jnp.mean(tr["n_active"][g]))}
            for g, p in enumerate(PARTICIPATION_PS)]


SKETCH_FAMILY_NAMES = ("dither64", "topk0.25", "count_sketch64",
                       "minmax0.5")


def sketch_families_plan(prob, iters=200) -> ExperimentPlan:
    """Beyond-paper: all four non-trivial compressor families — random
    dithering, top-k selection, count-sketch, min-max sampling — stacked
    on ONE traced grid axis over FLECS-CGD gradients
    (``compressors.stack_specs``), so the whole family comparison is a
    single compiled program."""
    hp = get_method("flecs_cgd").grid(
        grad_specs=stack_specs(*SKETCH_FAMILY_NAMES))
    return ExperimentPlan(
        problem=prob,
        runs=(MethodRun("flecs_cgd", cfg=FlecsConfig(m=2), hparams=hp,
                        label="families"),),
        iters=iters)


def sketch_families(prob, iters=200):
    """Objective vs wire price vs omega across the family axis.  The
    ``round_bits`` / ``omega`` columns are deterministic wire arithmetic
    (exact under the drift gate); F / grad_sq / Mbits_mean are
    PRNG/BLAS-dependent (tolerant keys)."""
    from repro.core.compressors import spec_omega
    from repro.core.flecs import hparams_round_bits
    res = assert_one_compile(
        lambda: run_plan(sketch_families_plan(prob, iters)))
    hp = res.hparams["families"]
    st = res.states["families"]
    tr = res.traces["families"]
    price = hparams_round_bits(FlecsConfig(m=2), hp, prob.d)
    omg = jax.vmap(lambda sp: spec_omega(sp, prob.d))(hp.grad_spec)
    return [{"family": name,
             "round_bits": float(price[g]),
             "omega": float(omg[g]),
             "F": float(tr["F"][g, -1]),
             "grad_sq": float(tr["grad_sq"][g, -1]),
             "Mbits_mean": float(jnp.mean(st.bits_per_node[g])) / 1e6}
            for g, name in enumerate(SKETCH_FAMILY_NAMES)]


BUDGET_GRID_MULTS = (2.0, 8.0, 32.0)


def budget_fair_budgets(prob):
    """The traced per-node budget grid, in multiples of one uncompressed
    32-bit d-vector (the unit the DIANA / FedNL papers plot against)."""
    return tuple(c * 32.0 * prob.d for c in BUDGET_GRID_MULTS)


def budget_fair_plan(prob) -> ExperimentPlan:
    """All five methods to the SAME traced bit budgets: five structural
    segments × a [3] budget axis, ONE compiled program.  No per-method
    iteration counts — each run's scan length is a spec-aware upper bound
    (``driver.iters_for_bit_budget`` over the method's wire price) and the
    budget-freeze scan mode equalizes the transmitted bits inside the
    program."""
    return ExperimentPlan(
        problem=prob,
        runs=(
            MethodRun("flecs",
                      cfg=FlecsConfig(m=1, grad_compressor="identity",
                                      hess_compressor="dither64"),
                      label="FLECS"),
            MethodRun("flecs_cgd",
                      cfg=FlecsConfig(m=1, grad_compressor="dither64",
                                      hess_compressor="dither64"),
                      label="FLECS-CGD"),
            MethodRun("diana", cfg=DianaConfig(alpha=1.0, gamma=0.5,
                                               compressor="dither64"),
                      label="DIANA"),
            MethodRun("fednl", cfg=FedNLConfig(alpha=1.0,
                                               compressor="topk0.25",
                                               mu=prob.mu),
                      label="FedNL"),
            MethodRun("gd", cfg=GDConfig(alpha=2.0), label="GD"),
        ),
        bit_budget=budget_fair_budgets(prob))


def budget_fair_comparison(prob):
    """The paper's headline axis, made fair: objective reached per
    transmitted bit, every method frozen at the same traced budgets.
    Asserts the figure compiled ONCE, that every (method, budget) point
    actually reached its budget, and that the frozen-tail ledger rows are
    bit-stable (the freeze charged nothing after exhaustion)."""
    budgets = budget_fair_budgets(prob)
    res = assert_one_compile(lambda: run_plan(budget_fair_plan(prob)))
    rows = []
    for lab in res.labels:
        tr = res.traces[lab]
        bits = np.asarray(tr["bits_per_node"])          # [B, T, n]
        for b, budget in enumerate(budgets):
            ledger = np.max(bits[b], axis=1)            # [T] max-worker bits
            reached = np.flatnonzero(ledger >= budget)
            assert reached.size, (lab, budget, float(ledger[-1]))
            rounds = int(reached[0]) + 1                # live rounds run
            assert np.all(ledger[rounds - 1:] == ledger[rounds - 1]), \
                (lab, budget)                           # bit-stable tail
            rows.append({"method": lab, "budget": float(budget),
                         "F": float(tr["F"][b, -1]),
                         "grad_sq": float(tr["grad_sq"][b, -1]),
                         "bits_per_node": float(ledger[-1]),
                         "rounds": rounds})
    return rows


def ablation_grid_plan(prob, iters=200) -> ExperimentPlan:
    """The (grad_s x hess_s x beta) cube as an ExperimentPlan (one
    flecs_cgd segment, eight traced grid points)."""
    hp = hparam_grid([1.0], [1.0], grad_levels=[16.0, 64.0],
                     betas=[0.5, 1.0], hess_levels=[16.0, 64.0])
    return ExperimentPlan(
        problem=prob,
        runs=(MethodRun("flecs_cgd", cfg=FlecsConfig(m=2), hparams=hp,
                        label="grid"),),
        iters=iters)


def ablation_grid(prob, iters=200):
    """Traced-spec ablation: the (grad_s x hess_s x beta) cube the paper's
    fixed s=64/beta=1 choices sit in, as ONE compiled vmapped scan — the
    Hessian compressor level and beta are traced sweep axes now, so no
    recompiles per point."""
    res = assert_one_compile(lambda: run_plan(ablation_grid_plan(prob,
                                                                 iters)))
    hp = res.hparams["grid"]
    sts, tr = res["grid"]
    G = hp.alpha.shape[0]
    dt = res.run_s / (iters * G) * 1e6
    rows = [{"grad_s": float(hp.grad_s[g]), "hess_s": float(hp.hess_s[g]),
             "beta": float(hp.beta[g]), "F": float(tr["F"][g, -1]),
             "grad_sq": float(tr["grad_sq"][g, -1]),
             "Mbits": float(jnp.max(sts.bits_per_node[g])) / 1e6}
            for g in range(G)]
    return rows, dt


def async_grid(prob, iters=600):
    """Traced staleness ablation: the (tau x buffer_k) grid as ONE compiled
    vmapped scan sharing a max-delay MessageBuffer shape, with per-point
    alpha auto-damped (driver.damped_alpha) instead of hand-tuned."""
    lg, lh = prob.make_oracles()
    n = prob.n_workers
    p = 0.5
    cfg = FlecsConfig(m=2, grad_compressor="dither64",
                      hess_compressor="dither64",
                      participation=p, sampling="choice")
    taus = [0, 2, 4]
    Ks = sorted({1.0, float(max(1, n // 4)), float(n)})
    ahp = async_hparam_grid(taus, Ks, alpha=1.0, auto_damp=(p, n))
    hooks = make_flecs_async_hooks(cfg, lg, lh)
    sweep = make_async_step(hooks)
    st0 = init_async_state(hooks, init_state(jnp.zeros(prob.d), n),
                           hparams_from_config(cfg), max(taus))
    t0 = time.perf_counter()
    sts, tr = run_async_sweep(sweep, ahp, st0, jax.random.key(0), iters,
                              record=lambda st: prob.metrics(st.w))
    jax.block_until_ready(sts)
    G = ahp.tau.shape[0]
    dt = (time.perf_counter() - t0) / (iters * G) * 1e6
    rows = [{"tau": int(ahp.tau[g]), "K": float(ahp.buffer_k[g]),
             "alpha": float(ahp.hp.alpha[g]), "F": float(tr["F"][g, -1]),
             "grad_sq": float(tr["grad_sq"][g, -1]),
             "Mbits_mean": float(jnp.mean(sts.bits_per_node[g])) / 1e6,
             "flushes": float(jnp.sum(tr["flushed"][g]))}
            for g in range(G)]
    return rows, dt


def staleness_ablation(prob, iters=600):
    """Beyond-paper: FedBuff-style async aggregation — a delay (tau) x
    participation (p) grid.  Messages arrive tau rounds after they were
    computed, buffer on the server, and are applied once K updates have
    accumulated; bits are charged at the *arrival* round.  tau=0, p=1
    is exactly the synchronous engine (the equivalence the tests pin)."""
    lg, lh = prob.make_oracles()
    rows = []
    n = prob.n_workers
    for kind, tau in (("fixed", 0), ("fixed", 2), ("fixed", 4),
                      ("geometric", 4)):
        for p in (1.0, 0.5):
            alpha = 1.0 if (tau == 0 and p == 1.0) else 0.2
            cfg = FlecsConfig(m=2, alpha=alpha, grad_compressor="dither64",
                              hess_compressor="dither64",
                              participation=p, sampling="choice")
            sched = StalenessSchedule(kind, tau=tau, q=0.5)
            K = n if (tau == 0 and p == 1.0) else max(1, n // 4)
            hooks = make_flecs_async_hooks(cfg, lg, lh)
            hp = hparams_from_config(cfg)
            step = functools.partial(
                make_async_step(hooks, sched.kind, sched.q),
                AsyncHParams(hp, jnp.int32(tau), jnp.float32(K)))
            st, tr = run_experiment(
                step, init_async_state(hooks, init_state(jnp.zeros(prob.d), n),
                                       hp, sched.max_delay),
                jax.random.key(0), iters, record_every=5,
                record=lambda st: prob.metrics(st.w))
            # record_every=5 thins traces on device; arrival-weighted
            # staleness over the recorded rounds is a sampled estimate
            arr = np.asarray(tr["n_arrived"])
            stale = float((np.asarray(tr["staleness_mean"]) * arr).sum()
                          / max(arr.sum(), 1.0))
            rows.append({"kind": kind, "tau": tau, "p": p, "K": K,
                         "alpha": alpha, "F": float(tr["F"][-1]),
                         "grad_sq": float(tr["grad_sq"][-1]),
                         "Mbits_mean": float(jnp.mean(st.bits_per_node)) / 1e6,
                         "staleness_mean": stale})
    return rows


def run_plans(prob, csv_rows: list, iters=200):
    """The plan-lowered comparison figures (fig1 + participation +
    budget_fair) — ONE compiled program each, asserted via
    ``api.plan_compiles()``.  Shared by the full benchmark run and the CI
    plan-smoke job (whose JSONs feed the scripts/check_bench_drift.py
    regression gate)."""
    OUT.mkdir(exist_ok=True)
    res1, us1 = fig1_flecs_vs_cgd(prob, iters=iters)
    json.dump(res1, open(OUT / "fig1_flecs_vs_cgd.json", "w"), indent=1)
    print("\n=== Fig 1/2: FLECS vs FLECS-CGD — 8 curves, ONE compiled "
          "program ===")
    print(f"{'method':16s} {'F@end':>10s} {'|g|^2@end':>11s} "
          f"{'Mbits/node':>11s}")
    for k, rows in res1.items():
        last = rows[-1]
        print(f"{k:16s} {last['F']:10.5f} {last['grad_sq']:11.2e} "
              f"{last['bits_per_node'] / 1e6:11.2f}")
        csv_rows.append(
            (f"fig1/{k}", us1[k],
             f"F={last['F']:.5f};bits={last['bits_per_node']:.0f}"))

    part = participation_ablation(prob, iters=iters)
    json.dump(part, open(OUT / "participation.json", "w"), indent=1)
    print("\n=== Participation ablation: traced Bernoulli-p axis, ONE "
          "program ===")
    for r in part:
        print(f"  p={r['p']:4.2f}: F={r['F']:.5f} "
              f"Mbits/node(mean)={r['Mbits_mean']:.2f} "
              f"active/round={r['active_mean']:.1f}")
        csv_rows.append((f"participation/p{r['p']}", 0.0,
                         f"F={r['F']:.5f};Mbits={r['Mbits_mean']:.2f}"))

    bud = budget_fair_comparison(prob)
    json.dump(bud, open(OUT / "budget_fair.json", "w"), indent=1)
    print("\n=== Budget-fair comparison: five methods x traced bit-budget "
          "grid, ONE program ===")
    for r in bud:
        print(f"  {r['method']:10s} budget={r['budget'] / 1e3:8.1f}kb: "
              f"F={r['F']:.5f} rounds={r['rounds']:4d} "
              f"bits/node={r['bits_per_node'] / 1e3:8.1f}kb")
        csv_rows.append((f"budget_fair/{r['method']}@{r['budget']:.0f}", 0.0,
                         f"F={r['F']:.5f};rounds={r['rounds']}"))

    fam = sketch_families(prob, iters=iters)
    json.dump(fam, open(OUT / "sketch_families.json", "w"), indent=1)
    print("\n=== Compressor families: dither / topk / count-sketch / "
          "minmax as ONE traced axis ===")
    for r in fam:
        print(f"  {r['family']:14s} omega={r['omega']:8.2f} "
              f"round_bits={r['round_bits']:8.0f} F={r['F']:.5f} "
              f"Mbits/node(mean)={r['Mbits_mean']:.3f}")
        csv_rows.append((f"families/{r['family']}", 0.0,
                         f"F={r['F']:.5f};bits={r['round_bits']:.0f}"))
    return res1, part, bud, fam


def run_grids(prob, csv_rows: list, iters_sync=200, iters_async=600):
    """The two traced-spec ablation grids — TWO compiled programs total.
    Shared by the full benchmark run and the CI sweep-smoke job."""
    OUT.mkdir(exist_ok=True)
    abl, dt_a = ablation_grid(prob, iters=iters_sync)
    json.dump(abl, open(OUT / "ablation_grid.json", "w"), indent=1)
    print("\n=== Traced-spec ablation: grad_s x hess_s x beta, ONE program "
          "===")
    for r in abl:
        print(f"  s={r['grad_s']:4.0f} hess_s={r['hess_s']:4.0f} "
              f"beta={r['beta']:.2f}: F={r['F']:.5f} Mbits={r['Mbits']:.2f}")
        csv_rows.append((f"grid/s{r['grad_s']:.0f}-hs{r['hess_s']:.0f}"
                         f"-b{r['beta']}", dt_a, f"F={r['F']:.5f}"))

    stale, dt_s = async_grid(prob, iters=iters_async)
    json.dump(stale, open(OUT / "async_grid.json", "w"), indent=1)
    print("\n=== Traced staleness grid: tau x buffer_k, auto-damped alpha, "
          "ONE program ===")
    for r in stale:
        print(f"  tau={r['tau']} K={r['K']:4.1f} alpha={r['alpha']:.3f}: "
              f"F={r['F']:.5f} Mbits/node={r['Mbits_mean']:.2f} "
              f"flushes={r['flushes']:.0f}")
        csv_rows.append((f"asyncgrid/tau{r['tau']}-K{r['K']:.0f}", dt_s,
                         f"F={r['F']:.5f};alpha={r['alpha']:.3f}"))


def run(csv_rows: list):
    OUT.mkdir(exist_ok=True)
    prob = make_problem(d=123, n_workers=20, r=64, mu=1e-3, seed=0)

    res1, part, _, _ = run_plans(prob, csv_rows, iters=300)
    # headline check: for the same iterate count CGD ships fewer bits
    f_cgd = res1["FLECS-CGD-m1"][-1]
    f_fl = res1["FLECS-m1"][-1]
    ratio = f_fl["bits_per_node"] / f_cgd["bits_per_node"]
    print(f"--> m=1 bits ratio FLECS/FLECS-CGD = {ratio:.2f}x "
          f"(paper: (8d+32d)/(8d+8d) = 2.5x)")

    res3, us3 = fig3_iterate_updates(prob)
    json.dump(res3, open(OUT / "fig3_iterate_updates.json", "w"), indent=1)
    print("\n=== Fig 3: iterate updates (Alg 4 vs Alg 5) ===")
    for k, rows in res3.items():
        last = rows[-1]
        print(f"{k:16s} F@end={last['F']:.5f} |g|^2={last['grad_sq']:.2e}")
        csv_rows.append((f"fig3/{k}", us3[k], f"F={last['F']:.5f}"))

    rows = comm_table(prob)
    json.dump(rows, open(OUT / "comm_table.json", "w"), indent=1)
    print("\n=== §3 communication complexity (bits/node/iter, d=123) ===")
    for r in rows:
        print(f"{r['method']:10s} m={r['m']}: measured={r['measured_bits']:.0f} "
              f"formula={r['formula_bits']} match={r['match']}")
        csv_rows.append((f"comm/{r['method']}-m{r['m']}", 0.0,
                         f"bits={r['measured_bits']:.0f}"))
        assert r["match"], r

    abl = ablation_dither_levels(prob)
    json.dump(abl, open(OUT / "ablation_dither.json", "w"), indent=1)
    print("\n=== Ablation: dithering levels s (beyond-paper) ===")
    for r in abl:
        print(f"  s={r['s']:4d}: F@200={r['F']:.5f} |g|^2={r['grad_sq']:.2e} "
              f"Mbits={r['Mbits']:.2f}")
        csv_rows.append((f"ablation/dither-s{r['s']}", 0.0,
                         f"F={r['F']:.5f};Mbits={r['Mbits']:.2f}"))

    grid, dt_g = vmapped_grid(prob)
    json.dump(grid, open(OUT / "vmapped_grid.json", "w"), indent=1)
    print("\n=== Vmapped sweep: alpha x dither-level grid, ONE program ===")
    for r in grid:
        print(f"  alpha={r['alpha']:.1f} s={r['grad_s']:4.0f}: "
              f"F={r['F']:.5f} Mbits={r['Mbits']:.2f}")
        csv_rows.append((f"grid/a{r['alpha']}-s{r['grad_s']:.0f}", dt_g,
                         f"F={r['F']:.5f}"))

    run_grids(prob, csv_rows)

    stale = staleness_ablation(prob)
    json.dump(stale, open(OUT / "staleness.json", "w"), indent=1)
    print("\n=== Async buffered aggregation: delay x participation "
          "(FedBuff-style, beyond-paper) ===")
    for r in stale:
        print(f"  {r['kind']:9s} tau={r['tau']} p={r['p']:4.2f} K={r['K']}: "
              f"F@600={r['F']:.5f} Mbits/node={r['Mbits_mean']:.2f} "
              f"staleness={r['staleness_mean']:.2f}")
        csv_rows.append((f"staleness/{r['kind']}-tau{r['tau']}-p{r['p']}",
                         0.0, f"F={r['F']:.5f};stale={r['staleness_mean']:.2f}"))

    base = baselines_comparison(prob)
    json.dump({k: v[0] for k, v in base.items()},
              open(OUT / "baselines.json", "w"), indent=1)
    print("\n=== Baselines (200 iters) ===")
    for k, (rows_, dt) in base.items():
        last = rows_[-1]
        print(f"{k:10s} F@end={last['F']:.5f} |g|^2={last['grad_sq']:.2e} "
              f"Mbits={last['bits_per_node'] / 1e6:.2f}")
        csv_rows.append((f"baseline/{k}", dt, f"F={last['F']:.5f}"))


def main():
    """Standalone entry for the CI smoke jobs: --grids-only runs the two
    traced-spec ablation grids, --plans-only runs the plan-lowered
    comparison figures (fig1 + participation + budget_fair, ONE compile
    each, asserted) — both at toy size, landing JSONs in benchmarks/out/
    (uploaded as CI artifacts and diffed against the committed goldens by
    scripts/check_bench_drift.py)."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--grids-only", action="store_true",
                    help="run only ablation_grid + async_grid")
    ap.add_argument("--plans-only", action="store_true",
                    help="run only the run_plan figures (fig1 + "
                         "participation_ablation + budget_fair_comparison)")
    ap.add_argument("--d", type=int, default=123,
                    help="problem size (with --grids-only/--plans-only)")
    ap.add_argument("--workers", type=int, default=20)
    ap.add_argument("--r", type=int, default=64)
    ap.add_argument("--iters", type=int, default=200)
    args = ap.parse_args()
    smoke = args.grids_only or args.plans_only
    if not smoke and (args.d, args.workers, args.r,
                      args.iters) != (123, 20, 64, 200):
        # the full run() reproduces the paper's fixed problem sizes; fail
        # loudly rather than silently dropping the size flags
        ap.error("--d/--workers/--r/--iters require --grids-only or "
                 "--plans-only")

    csv_rows: list = []
    if smoke:
        prob = make_problem(d=args.d, n_workers=args.workers, r=args.r,
                            mu=1e-3, seed=0)
        if args.grids_only:
            run_grids(prob, csv_rows, iters_sync=args.iters,
                      iters_async=3 * args.iters)
        if args.plans_only:
            programs0 = api.plan_programs()
            run_plans(prob, csv_rows, iters=args.iters)
            # the one-compile-per-figure invariant, end to end: every
            # run_plan call above compiled exactly one program
            assert api.plan_compiles() == api.plan_programs() > programs0
            print(f"\nplan programs: {api.plan_programs()}, "
                  f"compiles: {api.plan_compiles()} (1 per figure)")
    else:
        run(csv_rows)
    print("\nname,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
