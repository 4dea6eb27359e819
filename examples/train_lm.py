"""End-to-end LM training driver with the FLECS-CGD trainer.

    PYTHONPATH=src python examples/train_lm.py --arch tinyllama-1.1b --smoke \
        --steps 50 --flecs                      # CPU-sized demo
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
        # ~100M-param model, a few hundred steps (the deliverable driver;
        #  budget several hours on CPU — it is sized for a single TPU host)

Data: synthetic power-law token stream with per-worker distribution shift
(heterogeneous federation; ζ² > 0 in Assumption 5).  Supports the standard
(adam/adafactor) trainer and the FLECS-CGD compressed-difference trainer
(--flecs [--flecs-m M]), plus checkpoint save/restore.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ATTN_GLOBAL, FFN_DENSE, ModelConfig, uniform_plan
from repro.core.dl_flecs import (FlecsDLConfig, init_shifts,
                                 make_flecs_train_step)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.sharding import batch_specs, named_shardings
from repro.models.context import ModelContext
from repro.models.model import init_params
from repro.optim.optimizers import get_optimizer
from repro.train.step import make_train_step


def preset_100m() -> ModelConfig:
    return ModelConfig(
        arch_id="preset-100m", family="dense", n_layers=12, d_model=768,
        n_heads=12, n_kv_heads=4, head_dim=64, d_ff=2048, vocab=32000,
        layer_plan=uniform_plan(12, ATTN_GLOBAL, FFN_DENSE),
        source="example driver")


def token_stream(cfg, rng, batch, seq, n_workers=4):
    """Power-law unigram stream; each worker's distribution is shifted."""
    V = cfg.vocab
    base = 1.0 / (np.arange(1, V + 1) ** 1.1)
    while True:
        toks = np.empty((batch, seq + 1), np.int32)
        for b in range(batch):
            w = b % n_workers
            p = np.roll(base, w * (V // max(n_workers, 1) // 8))
            p = p / p.sum()
            toks[b] = rng.choice(V, size=seq + 1, p=p)
        yield {"tokens": jnp.asarray(toks[:, :-1]),
               "labels": jnp.asarray(toks[:, 1:])}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--preset", choices=["100m"], default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--flecs", action="store_true",
                    help="FLECS-CGD compressed-difference trainer")
    ap.add_argument("--flecs-m", type=int, default=0,
                    help="sketched-Hessian columns (0 = first-order CGD)")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()

    if args.preset == "100m":
        cfg = preset_100m()
    else:
        cfg = get_config(args.arch or "tinyllama-1.1b", smoke=args.smoke)
    print(f"arch={cfg.arch_id} params≈"
          f"{sum(int(np.prod(l.shape)) for l in jax.tree.leaves(jax.eval_shape(lambda: init_params(cfg, jax.random.key(0), jnp.float32)))) / 1e6:.1f}M")

    ctx = ModelContext()  # single host; use launch/ for pod meshes
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    rng = np.random.default_rng(0)
    stream = token_stream(cfg, rng, args.batch, args.seq)

    if args.flecs:
        # single-device federation still exercises the full compress path
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                                 ("data", "model"))
        ctx = ModelContext(mesh=mesh, data_axes=("data",), moe_impl="ref")
        fcfg = FlecsDLConfig(alpha=args.lr * 10, m=args.flecs_m)
        pa = jax.eval_shape(lambda: params)
        batch0 = next(stream)
        ba = jax.eval_shape(lambda: batch0)
        pshard = named_shardings(pa, mesh)
        bshard = named_shardings(ba, mesh, batch_specs(ba, mesh, ("data",)))
        lower = make_flecs_train_step(cfg, ctx, fcfg)
        jitted, shifts_abs = lower.build(pa, ba, pshard, bshard)
        shifts = init_shifts(shifts_abs)
        t0 = time.time()
        for step_i in range(args.steps):
            batch = next(stream)
            params, shifts, metrics = jitted(params, shifts, batch,
                                             jnp.int32(step_i))
            if step_i % 10 == 0 or step_i == args.steps - 1:
                print(f"step {step_i:4d} loss {float(metrics['loss']):.4f} "
                      f"({(time.time() - t0) / (step_i + 1):.2f}s/step)")
    else:
        opt = get_optimizer("adam", args.lr)
        opt_state = opt.init(params)
        step = jax.jit(make_train_step(cfg, ctx, opt))
        t0 = time.time()
        for step_i in range(args.steps):
            batch = next(stream)
            params, opt_state, metrics = step(params, opt_state, batch)
            if step_i % 10 == 0 or step_i == args.steps - 1:
                print(f"step {step_i:4d} loss {float(metrics['loss']):.4f} "
                      f"({(time.time() - t0) / (step_i + 1):.2f}s/step)")

    if args.checkpoint:
        from repro.checkpoint.store import save
        save(args.checkpoint, params, step=args.steps)
        print(f"checkpoint saved to {args.checkpoint}")


if __name__ == "__main__":
    main()
