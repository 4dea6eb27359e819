"""Smoke run of FLECS-CGD's main paths on a TPU, in one process.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the multi-chip paths, four chips

One chip runs three phases through the entry points a user calls:

* environment: jax / jaxlib / libtpu versions, the devices, and the
  compilation-cache directory (``repro.launch.compile_cache``);
* DL trainer: ``repro.launch.train``'s FLECS-CGD path (m = 0) on
  tinyllama-1.1b at its published widths, f32 params, batch 8 x 512, for
  five steps.  Checks: finite losses, the uplink ledger equals the
  dither price times the parameter count, and the compiled step's memory
  (donated params and shifts aliased) fits the chip;
* federated engine: a FLECS-CGD ``ExperimentPlan`` through ``run_plan`` at
  cross-silo size (16 clients, d = 4096, m = 8, full participation) with
  the fused Pallas compressors (``use_kernel=True``) and dither64 / top-k
  as one traced gradient-compressor family axis (Hessian messages are
  dithered at both points).  Checks: the compiled plan holds
  the Mosaic kernels (``tpu_custom_call``), every bit ledger equals the
  per-round price times the rounds exactly, the plan compiled once, and
  the final objective matches the same plan run on the host CPU with the
  jnp compressors.

``--chips 4`` runs only the two paths that span chips, each with its
comparison: the sharded federation (``driver.run_sharded_sweep`` over a
4-device worker mesh against the dense ``run_sweep`` on the same key
stream) and the FLECS DL step on a (4, 1) data mesh against the one-chip
step-0 loss on the same global batch.

Every phase runs even when an earlier one fails; the script exits
non-zero if any check failed, and prints as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
only when all passed.  It exits non-zero, printing no result, when JAX
finds no TPU or the repository's ``src/`` is not next to it.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from importlib import metadata
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Cross-silo federation (ROADMAP S1(a)): n clients, d features, m sketch
# columns, r samples per client.  ROUNDS keeps each node's f32 ledger far
# below 2^24 bits (dither64 prices a round at ~3e5 bits here).  ALPHA:
# the default step 1.0 raises the objective at d >= 1024 on this problem.
N, D, M, R, ROUNDS, ALPHA = 16, 4096, 8, 256, 10, 0.5
FAMILIES = ("dither64", "topk0.1")      # gradient compressor family axis
# Final objective, TPU kernels vs host-CPU jnp compressors: relative.
# The engine's f32 products run at HIGHEST precision (repro.numerics);
# at the TPU's default (bf16 operands) this plan ends 6e-5 off.
F_RTOL = 2e-5
# Sharded vs dense federation on four chips: objective trace, relative.
SHARDED_RTOL = 1e-4
# DL step-0 loss, (4, 1) data mesh vs one chip on the same global batch.
LOSS_RTOL = 1e-3
# uplink_mbits vs the exact price: the step reports bits / 1e6 in f32.
MBITS_RTOL = 1e-6
DL_ARGS = ["--arch", "tinyllama-1.1b", "--flecs", "--batch", "8",
           "--seq", "512"]


class Checks:
    """Collects named pass/fail results; every phase keeps running."""

    def __init__(self):
        self.failed = []

    def __call__(self, name, ok, detail=""):
        print(f"  check {name}: {'PASS' if ok else 'FAIL'} {detail}",
              flush=True)
        if not ok:
            self.failed.append(name)

    def phase(self, name, fn, *args):
        print(f"[{name}]", flush=True)
        t0 = time.perf_counter()
        try:
            fn(self, *args)
        except Exception as e:                   # report, keep going
            import traceback
            traceback.print_exc()
            self(f"{name} raised", False, f"{type(e).__name__}: {e}")
        print(f"[{name}] host seconds, compiles included: "
              f"{time.perf_counter() - t0:.1f}", flush=True)


def _version(pkg):
    try:
        return metadata.version(pkg)
    except metadata.PackageNotFoundError:
        return "not installed"


def environment(check, cache_dir):
    import jax
    devs = jax.devices()
    print(f"  jax {_version('jax')}, jaxlib {_version('jaxlib')}, "
          f"libtpu {_version('libtpu')}")
    print(f"  devices: {len(devs)} x {devs[0].device_kind} "
          f"({devs[0].platform})")
    print(f"  compilation cache: {cache_dir}")


def _flecs_plan(prob, use_kernel):
    """The cross-silo FLECS-CGD plan: one run whose [2] grid axis is the
    gradient compressor's family (top-k on the Hessian messages diverges
    here, so they stay dithered)."""
    from repro.core.api import ExperimentPlan, MethodRun, get_method
    from repro.core.compressors import stack_specs
    from repro.core.flecs import FlecsConfig

    cfg = FlecsConfig(m=M, use_kernel=use_kernel)
    fam = stack_specs(*FAMILIES)
    hp = get_method("flecs_cgd").grid(alphas=(ALPHA,), grad_specs=fam)
    plan = ExperimentPlan(problem=prob, runs=(MethodRun(
        "flecs_cgd", cfg=cfg, hparams=hp),), iters=ROUNDS)
    return cfg, hp, plan


def _expected_ledger(cfg, hp, rounds):
    """[G] per-node bits after ``rounds`` full-participation rounds."""
    import numpy as np
    from repro.core.flecs import hparams_round_bits
    return np.asarray(hparams_round_bits(cfg, hp, D), np.float64) * rounds


def federated(check):
    import jax
    import numpy as np
    from repro.core import api
    from repro.data.logreg import make_problem
    from repro.kernels.compressor import ops

    prob = make_problem(d=D, n_workers=N, r=R, mu=1e-3, seed=0)
    cfg, hp, plan = _flecs_plan(prob, use_kernel=True)
    print(f"  n={N} d={D} m={M} r={R} rounds={ROUNDS} "
          f"families={FAMILIES}; kernel size limit "
          f"{ops.MAX_FUSED_ELEMS}, messages of {D} and {D * M} elements")
    api.reset_plan_stats()
    res = api.run_plan(plan)
    st, tr = res["flecs_cgd"]
    hlo = res.compiled.as_text()
    print(f"  run_plan host seconds: compile {res.compile_s:.2f}, "
          f"run {res.run_s:.2f}")
    check("(a) Mosaic kernels in the compiled plan",
          "tpu_custom_call" in hlo,
          f"{hlo.count('tpu_custom_call')} tpu_custom_call mentions")
    bits = np.asarray(st.bits_per_node, np.float64)
    want = _expected_ledger(cfg, hp, ROUNDS)
    print(f"  per-node bits {bits[:, 0].tolist()}, round price x rounds "
          f"{want.tolist()}")
    check("(b) ledgers == round_bits x rounds",
          np.array_equal(bits, np.broadcast_to(want[:, None], bits.shape)))
    check("(c) plan compiled once", api.plan_compiles() == 1,
          f"plan_compiles() = {api.plan_compiles()}")
    f_tpu = np.asarray(tr["F"][:, -1], np.float64)

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        prob_c = make_problem(d=D, n_workers=N, r=R, mu=1e-3, seed=0)
        _, _, plan_c = _flecs_plan(prob_c, use_kernel=False)
        res_c = api.run_plan(plan_c)
    st_c, tr_c = res_c["flecs_cgd"]
    f_cpu = np.asarray(tr_c["F"][:, -1], np.float64)
    rel = np.abs(f_tpu - f_cpu) / np.abs(f_cpu)
    print(f"  F[0] {float(tr['F'][0, 0])!r}; final F tpu {f_tpu.tolist()} "
          f"cpu {f_cpu.tolist()}; cpu run_plan host seconds: compile "
          f"{res_c.compile_s:.2f}, run {res_c.run_s:.2f}")
    check(f"(d) final F within rtol {F_RTOL} of the host-CPU plan",
          bool(np.all(rel <= F_RTOL)), f"rel diff {rel.tolist()}")
    check("(d') CPU ledgers equal TPU ledgers",
          np.array_equal(np.asarray(st_c.bits_per_node, np.float64), bits))


def _dl_run(mesh_shape, steps):
    from repro.launch import train
    return train.run(train.parse_args(
        DL_ARGS + ["--mesh-shape", mesh_shape, "--steps", str(steps)]))


def dl_trainer(check):
    import jax
    from repro.core.compressors import dither_spec, psum_level_cap, spec_bits

    out = _dl_run("1,1", 5)
    del out["state"]
    losses = [h["loss"] for h in out["history"]]
    mbits = [h["uplink_mbits"] for h in out["history"]]
    print(f"  losses {losses}")
    print(f"  uplink_mbits {mbits}")
    print(f"  step host seconds {out['step_s']}")
    check("losses finite", all(math.isfinite(v) for v in losses))
    per_value = float(spec_bits(dither_spec(psum_level_cap(127, 1)), 1.0))
    want = per_value * out["n_params"] / 1e6
    check("uplink_mbits == dither price x params",
          all(abs(v - want) <= MBITS_RTOL * want for v in mbits),
          f"{per_value:g} bits x {out['n_params']} params = {want!r} Mbit")
    mem, limit = out["memory"], jax.devices()[0].memory_stats()["bytes_limit"]
    print(f"  memory_analysis {mem}; peak_bytes_in_use {out['peak_bytes']}; "
          f"bytes_limit {limit}")
    check("donated buffers aliased", mem["alias"] > 0)
    check("step fits the chip", mem["peak"] < limit,
          f"compiler's peak {mem['peak']} < {limit}")


def sharded_federation(check):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.driver import run_sharded_sweep, run_sweep, worker_mesh
    from repro.core.flecs import (init_state, make_flecs_sharded_sweep_step,
                                  make_flecs_sweep_step, sharded_state_specs)
    from repro.data.logreg import make_problem

    prob = make_problem(d=D, n_workers=N, r=R, mu=1e-3, seed=0)
    cfg, hp, _ = _flecs_plan(prob, use_kernel=True)
    lg, lh = prob.make_oracles()
    st0 = init_state(jnp.zeros(D), N)
    key = jax.random.key(0)
    rec = lambda s: prob.metrics(s.w)                    # noqa: E731
    mesh = worker_mesh(4)
    fs_d, tr_d = run_sweep(make_flecs_sweep_step(cfg, lg, lh), hp, st0,
                           key, ROUNDS, record=rec)
    fs_s, tr_s = run_sharded_sweep(
        make_flecs_sharded_sweep_step(cfg, lg, lh, n_total=N), hp, st0,
        key, ROUNDS, sharded_state_specs(), mesh=mesh, record=rec)
    for name in ("h", "B", "bits_per_node"):
        leaf = getattr(fs_s, name)
        print(f"  sharded state {name} {leaf.shape}: device_set size "
              f"{len(leaf.sharding.device_set)}")
    check("sharded state on 4 devices",
          len(fs_s.B.sharding.device_set) == 4)
    bits_d = np.asarray(tr_d["bits_per_node"])
    bits_s = np.asarray(tr_s["bits_per_node"])
    check("ledgers sharded == dense (exact)",
          np.array_equal(np.asarray(fs_d.bits_per_node),
                         np.asarray(fs_s.bits_per_node))
          and np.array_equal(bits_d, bits_s))
    want = _expected_ledger(cfg, hp, ROUNDS)
    check("ledgers == round_bits x rounds",
          np.array_equal(np.asarray(fs_s.bits_per_node, np.float64),
                         np.broadcast_to(want[:, None], (len(want), N))))
    f_d = np.asarray(tr_d["F"], np.float64)
    f_s = np.asarray(tr_s["F"], np.float64)
    rel = float(np.max(np.abs(f_s - f_d) / np.abs(f_d)))
    print(f"  final F dense {f_d[:, -1].tolist()} sharded "
          f"{f_s[:, -1].tolist()}")
    check(f"objective trajectory within rtol {SHARDED_RTOL}",
          rel <= SHARDED_RTOL, f"max rel diff {rel!r}")


def dl_data_parallel(check):
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = _dl_run("1,1", 1)
    ref_loss = ref["history"][0]["loss"]
    del ref
    out = _dl_run("4,1", 2)
    params, shifts = out["state"]
    loss0 = out["history"][0]["loss"]
    print(f"  step-0 loss: one chip {ref_loss!r}, (4, 1) mesh {loss0!r}")
    print(f"  memory_analysis {out['memory']}; peak_bytes_in_use "
          f"{out['peak_bytes']}; step host seconds {out['step_s']}")
    check(f"step-0 loss within rtol {LOSS_RTOL} of one chip",
          abs(loss0 - ref_loss) <= LOSS_RTOL * abs(ref_loss))
    own = shifts["own"]
    p_leaf = jax.tree.leaves(params)[0]
    s_leaf = jax.tree.leaves(own)[0]
    print(f"  params leaf {p_leaf.shape}: device_set size "
          f"{len(p_leaf.sharding.device_set)}; per-worker shift leaf "
          f"{s_leaf.shape}: device_set size "
          f"{len(s_leaf.sharding.device_set)}")
    check("params and shifts on 4 devices",
          len(p_leaf.sharding.device_set) == 4
          and len(s_leaf.sharding.device_set) == 4)
    differs = jax.jit(lambda tree: sum(
        jnp.any(a != a[:1], axis=tuple(range(1, a.ndim))).astype(jnp.int32)
        for a in jax.tree.leaves(tree)))(own)
    differs = np.asarray(differs)
    print(f"  leaves where each worker's shift differs from worker 0's: "
          f"{differs.tolist()}")
    check("per-worker shifts differ across workers",
          bool(np.all(differs[1:] > 0)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 1
    check = Checks()
    check.phase("environment", environment, enable_compile_cache())
    if args.chips == 1:
        # the full-width step first, while nothing else holds device memory
        check.phase("dl_trainer", dl_trainer)
        check.phase("federated", federated)
    else:
        check.phase("dl_data_parallel", dl_data_parallel)
        check.phase("sharded_federation", sharded_federation)
    if check.failed:
        print(f"chip_smoke: FAILED {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
