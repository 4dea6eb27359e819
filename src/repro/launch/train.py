"""Production training launcher.

    # on a real pod slice (or with forced host devices for a dry run):
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --mesh debug --steps 20 --flecs

    --mesh production  : 16x16 (requires 256 devices)
    --mesh multi       : 2x16x16 (512 devices)
    --mesh debug       : smallest mesh that fits the local device count
                         (or --mesh-shape DATA,MODEL)
    --smoke            : the registry's reduced-width variant of --arch
                         (default: its published widths)
Builds the mesh, shards params/optimizer per repro.launch.sharding, and
runs the standard or FLECS-CGD trainer on a synthetic heterogeneous token
stream (swap `stream` for a real data pipeline in deployment).  Params
and optimizer state (or FLECS shifts) are donated to the step, which
updates them in place.  ``run(parse_args([...]))`` is the in-process
entry point; it returns the per-step metrics and the compiled step's
memory analysis.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.sharding import batch_specs, named_shardings
from repro.models.context import ModelContext
from repro.models.model import init_params
from repro.optim.optimizers import get_optimizer
from repro.train.step import make_train_step


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-width config (default: published widths)")
    ap.add_argument("--mesh", choices=["production", "multi", "debug"],
                    default="debug")
    ap.add_argument("--mesh-shape", default=None,
                    help="debug mesh as DATA,MODEL over the first "
                         "DATA*MODEL devices (default: every device, model "
                         "axis 2 when the count is even)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--flecs", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    return ap.parse_args(argv)


def _mesh(args):
    if args.mesh != "debug":
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        return mesh, (("pod", "data") if args.mesh == "multi" else ("data",))
    if args.mesh_shape:
        shape = tuple(int(v) for v in args.mesh_shape.split(","))
    else:
        n = len(jax.devices())
        dm = 2 if n % 2 == 0 and n > 1 else 1
        shape = (max(n // dm, 1), dm)
    return make_debug_mesh(shape, ("data", "model")), ("data",)


def memory_summary(compiled) -> dict:
    """Bytes of the compiled step's arguments, outputs, temporaries and
    aliased (donated) buffers on one device, and ``peak``: the compiler's
    own estimate of the most the step holds at once (arguments included),
    which is what has to fit the device."""
    ma = compiled.memory_analysis()
    out = {k: int(getattr(ma, f"{k}_size_in_bytes"))
           for k in ("argument", "output", "temp", "alias")}
    out["peak"] = int(ma.peak_memory_in_bytes)
    return out


def peak_bytes(devices):
    """Largest ``peak_bytes_in_use`` over ``devices`` (None where the
    backend keeps no memory stats)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def run(args) -> dict:
    """Build, compile and run ``args.steps`` steps.  Returns the per-step
    metrics (host floats), the compiled step's ``memory_summary``, the
    peak device bytes, compile and per-step host seconds, and the final
    ``state`` (params plus FLECS shifts or optimizer state)."""
    mesh, data_axes = _mesh(args)
    print(f"mesh: {dict(mesh.shape)}")

    cfg = get_config(args.arch, smoke=args.smoke)
    ctx = ModelContext(mesh=mesh, data_axes=data_axes, moe_impl="sorted"
                       if mesh.shape["model"] > 1 and cfg.moe else "ref",
                       remat=True)

    def init():
        return init_params(cfg, jax.random.key(0), jnp.float32)

    pa = jax.eval_shape(init)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(pa))
    print(f"arch: {args.arch} ({'smoke' if args.smoke else 'published'} "
          f"widths), {n_params} params in float32")
    rng = np.random.default_rng(0)

    def batch():
        t = rng.integers(0, cfg.vocab, (args.batch, args.seq + 1))
        return {"tokens": np.asarray(t[:, :-1], np.int32),
                "labels": np.asarray(t[:, 1:], np.int32)}

    b0 = batch()
    ba = jax.eval_shape(lambda: jax.tree.map(jnp.asarray, b0))
    pshard = named_shardings(pa, mesh)
    bshard = named_shardings(ba, mesh, batch_specs(ba, mesh, data_axes))

    t0 = time.perf_counter()
    if args.flecs:
        from repro.core.dl_flecs import (FlecsDLConfig, init_shifts,
                                         make_flecs_train_step)
        lower = make_flecs_train_step(cfg, ctx,
                                      FlecsDLConfig(alpha=args.lr * 30))
        jitted, shifts_abs = lower.build(pa, ba, pshard, bshard)
        pshard = lower.param_shardings(pshard)   # replicated per worker
        aux = init_shifts(shifts_abs)
        extra = lambda i: (jnp.int32(i),)                   # noqa: E731
    else:
        opt = get_optimizer(args.optimizer, args.lr)
        oshard = named_shardings(jax.eval_shape(opt.init, pa), mesh)
        aux = jax.jit(lambda: opt.init(init()), out_shardings=oshard)()
        # out_shardings pinned to the inputs' shardings, so each step's
        # outputs feed the next call and the donated buffers are reused
        jitted = jax.jit(make_train_step(cfg, ctx, opt,
                                         microbatches=args.microbatches),
                         in_shardings=(pshard, oshard, bshard),
                         out_shardings=(pshard, oshard, None),
                         donate_argnums=(0, 1))
        extra = lambda i: ()                                # noqa: E731
    # params are created in place with their shardings, never gathered
    # onto one device first
    params = jax.jit(init, out_shardings=pshard)()
    compiled = jitted.lower(params, aux, jax.device_put(b0, bshard),
                            *extra(0)).compile()
    compile_s = time.perf_counter() - t0
    mem = memory_summary(compiled)
    print(f"compile: {compile_s:.1f} s; memory_analysis per device: "
          + ", ".join(f"{k}={v}" for k, v in mem.items()))

    history, step_s = [], []
    for i in range(args.steps):
        b = jax.device_put(b0 if i == 0 else batch(), bshard)
        t = time.perf_counter()
        params, aux, m = compiled(params, aux, b, *extra(i))
        m = {k: float(v) for k, v in jax.device_get(m).items()}
        step_s.append(time.perf_counter() - t)
        history.append(m)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d} " + " ".join(
                f"{k} {v:.4f}" for k, v in sorted(m.items())))
    peak = peak_bytes(mesh.devices.flat)
    print(f"peak_bytes_in_use: {peak}")

    if args.checkpoint:
        from repro.checkpoint.store import save
        save(args.checkpoint, params, step=args.steps)
        print("saved", args.checkpoint)
    return {"history": history, "memory": mem, "peak_bytes": peak,
            "n_params": n_params, "compile_s": compile_s,
            "step_s": step_s, "mesh": dict(mesh.shape),
            "state": (params, aux)}


def main(argv=None):
    enable_compile_cache()
    run(parse_args(argv))


if __name__ == "__main__":
    main()
