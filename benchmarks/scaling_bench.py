"""Population-scale scaling benchmark: cohort engine vs registered
population size, sharded engine vs device count.

Two claims are measured and gated:

1. **N-independence of the per-round working set.**  The cohort engine
   (``flecs.make_flecs_cohort_sweep_step`` over a
   ``data.logreg.VirtualLogReg`` population) must materialize only
   [cohort, ...] per-round intermediates — growing the registered
   population from 1k to 100k clients grows the *persistent* state
   (the [N, d] shift table and [N] ledger) but NOT the per-round
   transient footprint.  Measured from the step's jaxpr: every
   intermediate with a population-sized dimension is counted (those must
   be exactly the persistent-state scatter updates, a structural
   constant), and the remaining transient bytes must be byte-identical
   across populations.  The booleans land in the EXACT-matched ``meta``
   of the gate JSON, so a regression (one ``zeros((n_total,))`` in the
   scan body) flips a flag and fails the drift gate even if timings stay
   plausible; analysis rule R7 guards the same invariant statically.

2. **Device scaling of the sharded engine.**  ``driver.run_sharded_sweep``
   per-round wall time over 1..8 forced host devices.  Each device count
   needs its own process (XLA_FLAGS must be set before jax imports), so
   the parent re-invokes this file with ``--child-devices N``; children
   print one JSON line on stdout.

As a CLI this writes ``benchmarks/out/scaling.json``::

    {"meta":       {... exact-matched coverage + invariant flags ...},
     "timings_us": {"<key>": <median us or byte count>, ...}}

gated by ``scripts/check_bench_drift.py --timing scaling.json``: ``meta``
exactly, ``timings_us`` under the generous timing rtol (byte counts ride
here too — they are jax-version-dependent jaxpr measurements, but an [N]
intermediate blows them up by orders of magnitude, far past any rtol).
Refresh the golden with ``--timing --update scaling.json`` after an
intentional change.  ``--toy`` is the CI size class.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

OUT = Path(__file__).resolve().parent / "out" / "scaling.json"
SRC = Path(__file__).resolve().parents[1] / "src"
sys.path.insert(0, str(SRC))

COHORT = 64
D = 12


def _median_us(fn, *args, repeats=5):
    import jax
    jax.block_until_ready(fn(*args))            # warm-up: compile + run
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples)) * 1e6


def _jaxpr_footprint(jaxpr, n_total: int):
    """(population_dim_array_count, transient_bytes) over ALL equations
    (sub-jaxprs included): intermediates carrying a population-sized
    dimension vs everything else.  The population-dim arrays must be
    exactly the persistent-state scatter updates — a structural constant
    across populations — and the transient bytes must not move with N."""
    import jax.core as core

    def _sub_jaxprs(val):
        if isinstance(val, core.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, core.Jaxpr):
            yield val
        elif isinstance(val, (list, tuple)):
            for item in val:
                yield from _sub_jaxprs(item)

    n_dim_count, transient = 0, 0

    def walk(jx):
        nonlocal n_dim_count, transient
        for eqn in jx.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                shape = getattr(aval, "shape", None)
                if shape is None or not hasattr(aval, "dtype"):
                    continue
                nbytes = (int(np.prod(shape, dtype=np.int64))
                          * aval.dtype.itemsize)
                if n_total in shape:
                    n_dim_count += 1
                else:
                    transient += nbytes
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    walk(sub)

    walk(jaxpr.jaxpr)
    return n_dim_count, transient


def _persistent_bytes(n_total: int, d: int):
    """Analytic persistent-state footprint of FlecsCohortState (shared
    [d, d] curvature; the [N, ...] leaves are the contract)."""
    import jax.numpy as jnp
    from repro.core.driver import bits_dtype
    f32 = jnp.dtype(jnp.float32).itemsize
    return (d * f32                                  # w
            + n_total * d * f32                      # h (shift table)
            + d * d * f32                            # B (SHARED)
            + jnp.dtype(jnp.int32).itemsize          # k
            + n_total * jnp.dtype(bits_dtype()).itemsize)   # ledger


def bench_population(populations, iters, timings, meta):
    """Cohort engine across registered populations at fixed cohort."""
    import jax
    import jax.numpy as jnp
    from repro.core.driver import run_sweep
    from repro.core.flecs import (FlecsConfig, hparams_from_config,
                                  init_cohort_state,
                                  make_flecs_cohort_sweep_step)
    from repro.data.logreg import make_virtual_problem

    print(f"\n=== cohort engine vs population (K={COHORT}, d={D}) ===")
    cfg = FlecsConfig(m=2, participation=0.5)
    hp1 = jax.tree.map(lambda a: jnp.asarray(a)[None],
                       hparams_from_config(cfg))
    counts, transients = {}, {}
    for n_total in populations:
        vp = make_virtual_problem(d=D, n_total=n_total, r=8,
                                  probe_clients=8, seed=0)
        lg, lh = vp.make_oracles()
        step = make_flecs_cohort_sweep_step(cfg, lg, lh, n_total, COHORT)
        st0 = init_cohort_state(jnp.zeros(D), n_total)
        hp0 = hparams_from_config(cfg)
        n_dim, transient = _jaxpr_footprint(
            jax.make_jaxpr(step)(hp0, st0, jax.random.key(0)), n_total)
        counts[n_total], transients[n_total] = n_dim, transient

        runner = jax.jit(lambda s, k: run_sweep(
            step, hp1, s, k, iters, record=lambda st: vp.metrics(st.w)))
        us = _median_us(runner, st0, jax.random.key(0))
        us_round = us / iters
        key = f"cohort/n{n_total}/K{COHORT}"
        timings[key] = us_round
        timings[f"transient_bytes/n{n_total}"] = float(transient)
        print(f"  N={n_total:7d}: {us_round:9.1f} us/round, "
              f"transient {transient / 1024:.1f} KiB, "
              f"{n_dim} population-dim arrays, "
              f"persistent {_persistent_bytes(n_total, D) / 1024:.1f} KiB")

    # The gate's exact-matched invariants: the per-round working set is
    # independent of the registered population.
    meta["population_dim_array_count_constant"] = len(set(
        counts.values())) == 1
    meta["transient_bytes_independent_of_n"] = len(set(
        transients.values())) == 1
    meta["persistent_state_bytes"] = {
        f"n{n}": int(_persistent_bytes(n, D)) for n in populations}
    assert meta["transient_bytes_independent_of_n"], transients
    assert meta["population_dim_array_count_constant"], counts


def bench_devices(device_counts, iters, timings):
    """Sharded engine wall time per round, one subprocess per count."""
    print("\n=== sharded engine vs device count ===")
    for ndev in device_counts:
        env = dict(os.environ)
        env["PYTHONPATH"] = str(SRC)
        # forced host devices: the child stays on the CPU, off any
        # accelerator this parent process already holds
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={ndev}")
        out = subprocess.run(
            [sys.executable, __file__, "--child-devices", str(ndev),
             "--iters", str(iters)],
            env=env, capture_output=True, text=True, timeout=540)
        if out.returncode != 0:
            raise RuntimeError(
                f"device-sweep child (ndev={ndev}) failed:\n"
                f"{out.stdout}\n{out.stderr}")
        child = json.loads(out.stdout.strip().splitlines()[-1])
        timings.update(child)
        for k, v in child.items():
            print(f"  {k}: {v:9.1f} us/round")


def child_devices(ndev: int, iters: int):
    """Child body: time the sharded flecs engine on ``ndev`` forced host
    devices (two workers per device — the engine's bitwise floor)."""
    import jax
    import jax.numpy as jnp
    from repro.core.driver import run_sharded_sweep, worker_mesh
    from repro.core.flecs import (FlecsConfig, hparam_grid, init_state,
                                  make_flecs_sharded_sweep_step,
                                  sharded_state_specs)
    from repro.data.logreg import make_problem

    assert jax.device_count() == ndev, (jax.device_count(), ndev)
    n_workers = 16
    prob = make_problem(d=D, n_workers=n_workers, r=8, mu=1e-3, seed=0)
    lg, lh = prob.make_oracles()
    cfg = FlecsConfig(m=2, participation=0.6)
    hp = hparam_grid((1.0,), (1.0,), (64.0,))
    st0 = init_state(jnp.zeros(D), n_workers)
    step = make_flecs_sharded_sweep_step(cfg, lg, lh, n_total=n_workers)
    mesh = worker_mesh(ndev)

    # run_sharded_sweep jits a freshly-built shard_map per call, so an
    # outer jit (stable function identity) is what keeps the repeats on
    # the compiled path instead of re-tracing every sample.
    runner = jax.jit(lambda s, k: run_sharded_sweep(
        step, hp, s, k, iters, sharded_state_specs(), mesh=mesh))

    us = _median_us(runner, st0, jax.random.key(0))
    print(json.dumps({f"sharded/dev{ndev}/w{n_workers}": us / iters}))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--toy", action="store_true",
                    help="CI size class (smaller population list)")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--child-devices", type=int, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child_devices is not None:
        child_devices(args.child_devices, args.iters)
        return

    # The 100k-client population runs in BOTH size classes: completing it
    # with an N-independent working set is the acceptance claim.  Sizes
    # are multiples of the cohort (stratified selection divides N by K).
    populations = ([1024, 10240, 102_400] if args.toy
                   else [1024, 10240, 102_400, 204_800])
    device_counts = [1, 2] if args.toy else [1, 2, 4, 8]

    timings, meta = {}, {
        "toy": bool(args.toy),
        "iters": args.iters,
        "cohort": COHORT,
        "d": D,
        "populations": populations,
        "devices": device_counts,
    }
    bench_population(populations, args.iters, timings, meta)
    bench_devices(device_counts, args.iters, timings)
    meta["keys"] = sorted(timings)

    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps(
        {"meta": meta, "timings_us": timings}, indent=1, sort_keys=True))
    print(f"\nwrote {OUT}")


if __name__ == "__main__":
    main()
