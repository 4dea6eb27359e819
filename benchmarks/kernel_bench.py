"""Micro-benchmarks for the Pallas kernels' XLA fallbacks + wire-format
accounting (wall-clock interpret-mode numbers are NOT TPU times; the roofline
section carries the deployment analysis).  Also measures the exact-mode
FLECS-CGD step cost scaling in d and m (the paper's O(md²) worker cost).

As a CLI this writes ``benchmarks/out/kernel_bench.json``::

    {"meta":       {"toy": ..., "iters": ..., "keys": [...]},
     "timings_us": {"<bench key>": <median µs>, ...}}

which ``scripts/check_bench_drift.py --timing`` gates against the committed
golden: ``meta`` must match EXACTLY (coverage — a silently dropped benchmark
is a gate hole), ``timings_us`` under a deliberately generous ``--timing-rtol``
(CI hardware varies; the gate catches order-of-magnitude regressions like an
accidental eager fallback or a recompile per call, not scheduler noise).
Medians, not means: one GC pause or page-fault spike must not move the gate.
The committed golden is generated with ``--toy`` (the CI step's exact
invocation); rerun ``python benchmarks/kernel_bench.py --toy`` and refresh
with ``--update`` after an intentional perf change.
"""
from __future__ import annotations

import argparse
import json
import functools
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compressors import Compressor, compress, make_spec
from repro.core.flecs import (FlecsConfig, hparams_from_config, init_state,
                              make_flecs_sweep_step)
from repro.data.logreg import make_problem

OUT = Path(__file__).resolve().parent / "out" / "kernel_bench.json"


def _time(fn, *args, iters=20):
    """Median per-call wall time in µs (one warm-up call excluded)."""
    jax.block_until_ready(fn(*args))    # warm-up: compile + run
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples)) * 1e6


def run(csv_rows: list, *, toy: bool = False, iters: int = 20):
    """All sections; returns {bench key: median µs} for the timing gate.

    ``toy=True`` is the CI gate's size class: small enough that the whole
    run is a few seconds, large enough that a per-call recompile or an
    eager fallback still blows through the generous rtol.
    """
    timings = {}
    rng = np.random.default_rng(0)

    print("\n=== compressor micro-bench (XLA path, CPU wall time) ===")
    for n in ((1 << 10,) if toy else (1 << 14, 1 << 18)):
        x = jnp.asarray(rng.normal(size=n), jnp.float32)
        for name in ("dither64", "natural", "topk0.1"):
            Q = Compressor(name, make_spec(name))
            f = jax.jit(lambda k, x, Q=Q: Q.compress(k, x))
            us = _time(f, jax.random.key(0), x, iters=iters)
            # dimension-aware wire accounting: top-k pays per kept value
            bpv = Q.bits(n) / n
            print(f"  {name:10s} n={n:7d}: {us:9.1f} us "
                  f"({bpv:.1f} bits/val)")
            csv_rows.append((f"compressor/{name}/n{n}", us,
                             f"bits={bpv:.1f}"))
            timings[f"compressor/{name}/n{n}"] = us

    print("\n=== fused Pallas kernel vs jnp reference "
          "(interpret mode off-TPU) ===")
    for n in ((1 << 10,) if toy else (1 << 12, 1 << 16)):
        x = jnp.asarray(rng.normal(size=n), jnp.float32)
        for name in ("dither64", "topk0.1"):
            spec = make_spec(name)
            for impl, flag in (("jnp", False), ("kernel", True)):
                f = jax.jit(lambda k, x, spec=spec, flag=flag:
                            compress(spec, k, x, flag))
                us = _time(f, jax.random.key(0), x, iters=iters)
                print(f"  {name:10s} n={n:7d} {impl:6s}: {us:9.1f} us")
                csv_rows.append((f"fused/{name}/n{n}/{impl}", us, ""))
                timings[f"fused/{name}/n{n}/{impl}"] = us

    print("\n=== FLECS-CGD step cost vs (d, m) — worker O(md²) claim ===")
    for d in ((123,) if toy else (123, 500)):
        prob = make_problem(d=d, n_workers=8, r=32, mu=1e-3, seed=0)
        lg, lh = prob.make_oracles()
        for m in ((1, 4) if toy else (1, 4, 8)):
            cfg = FlecsConfig(m=m, grad_compressor="dither64",
                              hess_compressor="dither64")
            step = jax.jit(functools.partial(
                make_flecs_sweep_step(cfg, lg, lh), hparams_from_config(cfg)))
            st = init_state(jnp.zeros(prob.d), prob.n_workers)

            def f(st, key):
                s2, _ = step(st, key)
                return s2.w

            us = _time(f, st, jax.random.key(0), iters=min(iters, 10))
            print(f"  d={d:5d} m={m}: {us:9.1f} us/iter")
            csv_rows.append((f"flecs_step/d{d}/m{m}", us, ""))
            timings[f"flecs_step/d{d}/m{m}"] = us

    return timings


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="kernel micro-bench; writes the timing-gate JSON")
    ap.add_argument("--out", default=str(OUT),
                    help="output JSON path (default benchmarks/out/)")
    ap.add_argument("--toy", action="store_true",
                    help="CI gate sizes: seconds, not minutes")
    ap.add_argument("--iters", type=int, default=20,
                    help="timed calls per benchmark (median reported)")
    args = ap.parse_args(argv)
    timings = run([], toy=args.toy, iters=args.iters)
    payload = {
        # meta is the gate's EXACT-match coverage contract; timings are
        # rounded so the golden diff stays readable.
        "meta": {"toy": args.toy, "iters": args.iters,
                 "keys": sorted(timings)},
        "timings_us": {k: round(v, 1) for k, v in sorted(timings.items())},
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"\nwrote {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
