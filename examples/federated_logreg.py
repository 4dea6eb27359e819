"""Method comparison on one federated problem: FLECS vs FLECS-CGD vs DIANA
vs FedNL vs GD — objective versus communicated bits (the paper's x-axis).

Every method is resolved through the declarative registry
(``repro.core.api.get_method``) and the whole invocation is ONE
``ExperimentPlan`` lowered by ``run_plan`` to a single compiled program —
regardless of how many methods or participation levels are requested.

    PYTHONPATH=src python examples/federated_logreg.py [--d 123] [--iters 200]
    PYTHONPATH=src python examples/federated_logreg.py --method flecs_cgd
    PYTHONPATH=src python examples/federated_logreg.py --participation 0.5
    PYTHONPATH=src python examples/federated_logreg.py \
        --participation 1.0,0.5,0.25          # traced sweep axis, ONE compile
    PYTHONPATH=src python examples/federated_logreg.py --staleness 2 \
        --delay-kind geometric --participation 0.5
    PYTHONPATH=src python examples/federated_logreg.py \
        --bit-budget 200000                   # budget-fair: equal bits/node

--method selects one registry method ("all", the default, compares every
one).  --participation is SWEEPABLE: a comma-list becomes a traced
Bernoulli-p hparam axis — all levels for all methods still execute as one
compiled program (the per-p rows print separately).  Single values < 1 use
the --sampling kind ("choice" = exact-k, static); comma-lists require
bernoulli, the traced form.

With --bit-budget BITS > 0 the comparison is budget-fair instead of
rounds-fair: every method runs until its cumulative per-node uplink ledger
reaches BITS and then freezes (``driver.freeze_on_bit_budget`` — the
traced budget is a sweep axis, so it is STILL one compiled program), with
each run's scan length a spec-aware upper bound from
``driver.iters_for_bit_budget``.  This reproduces the communicated-bits
x-axis the paper's headline claim lives on: FLECS-CGD wins per transmitted
bit, not per round.

With --staleness TAU > 0 every row — FedNL included, via its compressed-
Hessian-diff async variant — switches to the FedBuff-style async engine:
updates arrive TAU rounds late (per --delay-kind), buffer on the server
until --buffer-k have accumulated, and bits are charged at the arrival
round — the extra stale/round column reports the mean age of applied
updates.  --auto-alpha replaces the hand-tuned per-mode step sizes with
the variance-motivated ``driver.damped_alpha`` rule (alpha0 · min(1,
p·K/n)).

--arrival-profile swaps the delay model for a ``repro.core.traffic``
arrival process (requires --staleness, whose TAU stays the delay cap):

    fixed:    the plain --delay-kind StalenessSchedule draw (default);
    poisson:  Poisson-thinned completion — each in-flight message lands
              with probability 0.6 per round (geometric service time);
    diurnal:  the same thinning against a 4-phase piecewise-constant
              rate table (rush hours and lulls).

    PYTHONPATH=src python examples/federated_logreg.py --staleness 4 \
        --arrival-profile diurnal --participation 0.5
"""
import argparse

import jax.numpy as jnp

import jax

from repro.core import api
from repro.core.api import ExperimentPlan, MethodRun, run_plan
from repro.core.compressors import make_spec
from repro.core.driver import StalenessSchedule, damped_alpha
from repro.core.flecs import FlecsConfig, FlecsHParams
from repro.core.traffic import ArrivalSchedule, TrafficModel
from repro.data.logreg import make_problem
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.baselines import (DianaConfig, DianaHParams, FedNLConfig,
                                   FedNLHParams, GDConfig, GDHParams)

METHOD_ORDER = ("flecs", "flecs_cgd", "diana", "fednl", "gd")


def build_runs(args, prob, ps, alphas):
    """One MethodRun per selected method; a multi-valued --participation
    list rides along as a traced p axis inside each run's hparam grid,
    PAIRED with its own damped alpha per point (``alphas[i]`` goes with
    ``ps[i]`` — a p=1.0 row always runs at its standalone step size)."""
    p0 = ps[0]
    sweeping = len(ps) > 1
    # single p: honor --sampling via the static config path; p-list: the
    # traced axis (bernoulli only — validated by the grid constructors)
    static = dict(participation=p0 if not sweeping else 1.0,
                  sampling=args.sampling if not sweeping else "bernoulli")
    G = len(ps)
    p_axis = jnp.asarray(ps, jnp.float32) if sweeping else None
    a_axis = jnp.asarray(alphas, jnp.float32)
    full = lambda v: jnp.full((G,), v, jnp.float32)      # noqa: E731

    def bcast_spec(name):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(jnp.asarray(a), (G,)),
            make_spec(name))

    names = METHOD_ORDER if args.method == "all" else (args.method,)
    budgeted = args.bit_budget > 0
    runs = []
    for name in names:
        if name in ("flecs", "flecs_cgd"):
            gc = "identity" if name == "flecs" else "dither64"
            cfg = FlecsConfig(m=1, alpha=float(alphas[0]),
                              grad_compressor=gc,
                              hess_compressor="dither64", **static)
            # paired (alpha, p) axes, gradient spec pinned per method
            # (plain FLECS ships identity gradients)
            hp = FlecsHParams(a_axis, full(1.0), full(1.0),
                              bcast_spec(gc), bcast_spec("dither64"),
                              p_axis)
        elif name == "diana":
            cfg = DianaConfig(alpha=1.0, gamma=0.5, compressor="dither64",
                              **static)
            hp = DianaHParams(full(1.0), full(0.5), bcast_spec("dither64"),
                              p_axis)
        elif name == "fednl":
            cfg = FedNLConfig(alpha=float(alphas[0]), compressor="topk0.25",
                              mu=prob.mu, **static)
            hp = FedNLHParams(a_axis, bcast_spec("topk0.25"), p_axis)
        else:
            gd_alpha = 2.0 if args.staleness == 0 else 1.0
            cfg = GDConfig(alpha=gd_alpha, **static)
            hp = GDHParams(full(gd_alpha), p_axis)
        # budget-fair mode derives each run's scan length from its wire
        # price (driver.iters_for_bit_budget) — the freeze, not the round
        # count, equalizes the methods
        iters = (None if budgeted
                 else min(args.iters, 80) if name == "fednl" else args.iters)
        runs.append(MethodRun(name, cfg=cfg, hparams=hp, iters=iters))
    return runs


def print_rows(res, ps, budget=0.0):
    for lab in res.labels:
        st, tr = res[lab]
        for g, p in enumerate(ps):
            F = float(tr["F"][g, -1])
            gn = float(jnp.sqrt(tr["grad_sq"][g, -1]))
            mbits = float(jnp.max(st.bits_per_node[g])) / 1e6
            # budget mode: the scan length is an upper bound and frozen
            # rows report zero activity — average over the LIVE rounds
            # (up to the row the ledger reached the budget) so the stat
            # reflects actual per-round participation
            ledger = jnp.max(tr["bits_per_node"][g], axis=-1)
            live = (int(jnp.argmax(ledger >= budget)) + 1
                    if budget > 0 and bool(jnp.any(ledger >= budget))
                    else ledger.shape[0])
            active = float(jnp.mean(tr["n_active"][g, :live]))
            name = lab if len(ps) == 1 else f"{lab}@p={p}"
            line = (f"{name:18s} F={F:.6f} ||grad||={gn:.2e} "
                    f"Mbits/node={mbits:7.3f} active/round={active:5.1f}")
            if "staleness_mean" in tr:
                arr = tr["n_arrived"][g]
                stale = float(jnp.sum(tr["staleness_mean"][g] * arr)
                              / jnp.maximum(jnp.sum(arr), 1.0))
                line += f" stale/round={stale:4.2f}"
            print(line)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--d", type=int, default=123)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--workers", type=int, default=20)
    ap.add_argument("--method", default="all",
                    choices=("all",) + METHOD_ORDER,
                    help="registry method to run (default: compare all)")
    ap.add_argument("--participation", default="1.0",
                    help="per-round client sampling probability; a comma-"
                         "list (e.g. 1.0,0.5,0.25) sweeps p as ONE traced "
                         "axis — still a single compile")
    ap.add_argument("--sampling", choices=("bernoulli", "choice"),
                    default="choice",
                    help="single-p sampling kind (comma-lists are always "
                         "bernoulli, the traced form)")
    ap.add_argument("--staleness", type=int, default=0, metavar="TAU",
                    help="async mode: updates arrive TAU rounds late "
                         "(0 = synchronous)")
    ap.add_argument("--delay-kind", choices=("fixed", "uniform", "geometric"),
                    default="fixed")
    ap.add_argument("--arrival-profile",
                    choices=("fixed", "poisson", "diurnal"), default="fixed",
                    help="arrival process for async rounds: 'fixed' keeps "
                         "the --delay-kind StalenessSchedule draw; "
                         "'poisson'/'diurnal' Poisson-thin completions by a "
                         "flat / 4-phase rate table (repro.core.traffic), "
                         "capped at --staleness")
    ap.add_argument("--buffer-k", type=int, default=0,
                    help="FedBuff aggregation goal (0 = auto: n/4, min 1)")
    ap.add_argument("--auto-alpha", action="store_true",
                    help="derive the step size via driver.damped_alpha "
                         "(alpha0=1, scaled by p·K/n) instead of the "
                         "hand-tuned per-mode defaults")
    ap.add_argument("--bit-budget", type=float, default=0.0, metavar="BITS",
                    help="budget-fair mode: freeze every method once its "
                         "per-node uplink ledger reaches BITS (still one "
                         "compiled program; scan lengths become spec-aware "
                         "upper bounds via driver.iters_for_bit_budget and "
                         "--iters is ignored).  0 = rounds-fair, the "
                         "default")
    args = ap.parse_args()

    ps = tuple(float(p) for p in args.participation.split(","))
    if any(p <= 0 for p in ps):
        raise SystemExit(f"--participation values must be > 0, got {ps}")
    prob = make_problem(d=args.d, n_workers=args.workers, r=64, mu=1e-3)
    tau = args.staleness
    K = args.buffer_k or max(1, args.workers // 4)
    # second-order steps need damping once client sampling / staleness add
    # variance (stale preconditioned updates amplify subset noise).  Each
    # sweep point gets the alpha its own p would get standalone.
    if args.auto_alpha:
        # synchronous rounds flush a whole sampled cohort at once, so the
        # effective buffer size is round(p·n)
        alphas = []
        for p in ps:
            K_eff = K if tau > 0 else max(1, round(p * args.workers))
            alphas.append(float(damped_alpha(1.0, p, K_eff, args.workers)))
            print(f"auto-damped alpha = {alphas[-1]:.3f} "
                  f"(p={p}, K={K_eff}, n={args.workers})")
    else:
        alphas = [1.0 if (p >= 1.0 and tau == 0)
                  else (0.5 if tau == 0 else 0.2) for p in ps]

    if args.arrival_profile != "fixed":
        if tau <= 0:
            raise SystemExit("--arrival-profile rides the async engine; "
                             "set --staleness TAU > 0 (TAU caps the delays)")
        arrival = (ArrivalSchedule("poisson", rates=(0.6,))
                   if args.arrival_profile == "poisson"
                   else ArrivalSchedule("diurnal",
                                        rates=(0.9, 0.5, 0.2, 0.5)))
        traffic = TrafficModel(arrival=arrival)
    else:
        traffic = None

    plan = ExperimentPlan(
        problem=prob,
        runs=tuple(build_runs(args, prob, ps, alphas)),
        iters=args.iters,
        staleness=(StalenessSchedule(args.delay_kind, tau=tau)
                   if tau > 0 else None),
        buffer_k=K,
        bit_budget=args.bit_budget if args.bit_budget > 0 else None,
        traffic=traffic)
    res = run_plan(plan)
    assert api.plan_compiles() == api.plan_programs() == 1, \
        "the example must lower to exactly one compiled program"
    print_rows(res, ps, budget=args.bit_budget)
    n_traj = sum(len(ps) for _ in res.labels)
    if args.bit_budget > 0:
        print(f"(budget-fair: trajectories freeze once their ledger reaches "
              f"{args.bit_budget:.0f} bits/node; the Mbits/node column is "
              f"the ACTUAL final ledger — a method whose single-round wire "
              f"price exceeds the budget overshoots by up to one round, "
              f"e.g. FedNL's d^2 payload on small budgets)")
    print(f"({n_traj} trajectories, 1 compiled program)")


if __name__ == "__main__":
    main()
