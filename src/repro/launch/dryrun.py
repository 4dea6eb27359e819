import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
# combination against the production mesh, record memory/cost/collective
# analysis for EXPERIMENTS.md §Dry-run and §Roofline.
#
# The environment lines above MUST stay first: jax locks the platform and
# the device count on first initialization, and the production meshes need
# 512 placeholder host devices (on the CPU, even where an accelerator is
# attached).
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
#       --shape train_4k --mesh single
#   PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
# (no `from __future__` here: the XLA_FLAGS assignment must be line 2.)

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, INPUT_SHAPES, get_config
from repro.launch.hlo_analysis import model_flops, roofline_terms
from repro.launch.inputs import abstract_cache, input_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_specs, cache_specs, named_shardings,
                                   param_specs)
from repro.models.context import ModelContext
from repro.models.model import abstract_params
from repro.optim.optimizers import get_optimizer
from repro.train.step import make_prefill_step, make_serve_step, make_train_step

RESULTS = Path(__file__).resolve().parents[3] / "benchmarks" / "dryrun_results.json"


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree.leaves(tree))


def _tree_bytes_local(tree, mesh, specs) -> float:
    """Per-chip bytes given PartitionSpecs (replicated dims count fully)."""
    total = 0.0
    for leaf, spec in zip(jax.tree.leaves(tree),
                          jax.tree.leaves(specs, is_leaf=lambda s: isinstance(
                              s, jax.sharding.PartitionSpec))):
        n = 1
        for entry in spec:
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                if a is not None:
                    n *= mesh.shape[a]
        total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize / n
    return total


def _param_counts(cfg, params_abs):
    total = routed = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_abs)[0]:
        n = int(np.prod(leaf.shape))
        total += n
        names = [getattr(k, "key", None) for k in path]
        if "moe" in names and "shared" not in names and any(
                str(x) in ("w_gate", "w_up", "w_down") for x in names):
            routed += n
    active = total
    if cfg.moe is not None and routed:
        active = total - routed + routed * cfg.moe.top_k / cfg.moe.n_experts
    return total, int(active)


def run_one(arch: str, shape_name: str, multi_pod: bool,
            flecs: bool = False, ctx_overrides=None,
            variant: str = "", microbatches: int = 0) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "flecs": flecs, "variant": variant, "status": "?"}
    if shape_name == "long_500k" and not cfg.supports_long_context:
        rec.update(status="SKIP",
                   reason="pure full-attention arch; see DESIGN.md long_500k policy")
        return rec
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    data_axes = ("pod", "data") if multi_pod else ("data",)
    n_chips = int(np.prod(list(mesh.shape.values())))
    n_data = int(np.prod([mesh.shape[a] for a in data_axes]))
    ctx = ModelContext(mesh=mesh, data_axes=data_axes, moe_impl="sorted",
                       remat=True,
                       seq_shard_decode=(shape_name == "long_500k"))
    param_mode = "train"
    if variant in ("moe-fshard", "serve") and shape.kind == "decode":
        # serve-mode shardings: weights resident (no data-axis FSDP);
        # experts in the fshard layout when the arch has them.
        if cfg.moe is not None:
            ctx = __import__("dataclasses").replace(ctx, moe_impl="fshard")
        param_mode = "serve"
    if "gatherq" in variant:
        ctx = __import__("dataclasses").replace(ctx, moe_gather_quant=True)
    if ctx_overrides:
        import dataclasses
        ctx = dataclasses.replace(ctx, **ctx_overrides)
    params_abs = abstract_params(cfg, jnp.bfloat16)
    pspecs = param_specs(params_abs, mesh, mode=param_mode)
    pshard = named_shardings(params_abs, mesh, pspecs)
    param_local = _tree_bytes_local(params_abs, mesh, pspecs)
    batch_abs = input_specs(cfg, shape)
    bshard = named_shardings(batch_abs, mesh,
                             batch_specs(batch_abs, mesh, data_axes))
    n_total, n_active = _param_counts(cfg, params_abs)
    rec.update(n_params=n_total, n_active=n_active, n_chips=n_chips)
    opt_local = cache_local = 0.0

    try:
        if shape.kind == "train":
            if flecs:
                from repro.core.dl_flecs import (FlecsDLConfig,
                                                 make_flecs_train_step)
                m_sketch = 0 if "m0" in variant else 1
                fcfg = FlecsDLConfig(m=m_sketch)
                rec.update(flecs_m=fcfg.m, flecs_levels=fcfg.s_levels)
                lowered = make_flecs_train_step(cfg, ctx, fcfg)(
                    params_abs, batch_abs, pshard, bshard)
            else:
                opt_name = "adafactor" if n_total > 20e9 else "adam"
                opt = get_optimizer(opt_name, 1e-3)
                opt_abs = jax.eval_shape(opt.init, params_abs)
                ospecs = param_specs(opt_abs, mesh)
                oshard = named_shardings(opt_abs, mesh, ospecs)
                opt_local = _tree_bytes_local(opt_abs, mesh, ospecs)
                mb = microbatches or max(1, shape.global_batch // n_data)
                step = make_train_step(cfg, ctx, opt, microbatches=mb)
                rec.update(optimizer=opt_name, microbatches=mb)
                lowered = jax.jit(
                    step, in_shardings=(pshard, oshard, bshard)
                ).lower(params_abs, opt_abs, batch_abs)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, ctx, max_len=shape.seq_len)
            lowered = jax.jit(step, in_shardings=(pshard, bshard)).lower(
                params_abs, batch_abs)
        else:  # decode
            cache_abs = abstract_cache(cfg, shape, ctx)
            cspecs = cache_specs(cache_abs, mesh, data_axes,
                                 seq_shard=ctx.seq_shard_decode)
            cshard = named_shardings(cache_abs, mesh, cspecs)
            cache_local = _tree_bytes_local(cache_abs, mesh, cspecs)
            step = make_serve_step(cfg, ctx)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jax.jit(
                step, in_shardings=(pshard, cshard, bshard, None),
                donate_argnums=(1,),
            ).lower(params_abs, cache_abs, batch_abs, pos)
            rec["cache_bytes_global"] = _tree_bytes(cache_abs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        # Loop-aware static analysis (cost_analysis counts scan bodies once).
        from repro.launch import hlo_static
        stat = hlo_static.analyze(hlo)
        flops = stat["flops_per_chip"]
        wire_total = stat["wire_bytes_per_chip"]
        # Analytic per-chip HBM traffic (DESIGN.md; the HLO op-bytes sum is
        # recorded separately as an upper bound — CPU backend barely fuses).
        from repro.launch.hlo_analysis import analytic_hbm_bytes
        tokens_local = shape.global_batch * shape.seq_len / n_data
        if shape.kind == "train":
            mb_n = rec.get("microbatches", 1)
            act_local = cfg.n_layers * (tokens_local / mb_n) * cfg.d_model * 2
            bytes_acc = analytic_hbm_bytes(
                param_bytes_local=param_local, kind="train",
                microbatches=mb_n, act_bytes_local=act_local * mb_n,
                opt_bytes_local=opt_local)
        elif shape.kind == "prefill":
            act_local = cfg.n_layers * tokens_local * cfg.d_model * 2
            bytes_acc = analytic_hbm_bytes(
                param_bytes_local=param_local, kind="prefill",
                act_bytes_local=act_local)
        else:
            bytes_acc = analytic_hbm_bytes(
                param_bytes_local=param_local, kind="decode",
                cache_bytes_local=cache_local)
        wires = {k: stat[f"wire_{k}"] for k in hlo_static.COLL_KINDS}
        wires["counts"] = {k: stat[f"count_{k}"] for k in hlo_static.COLL_KINDS}
        rec["wire_model_axis"] = stat["wire_model_axis"]
        rec["wire_data_axis"] = stat["wire_data_axis"]
        terms = roofline_terms(flops, bytes_acc, wire_total)
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                       else 1)
        mflops = model_flops(n_total, n_active, tokens, shape.kind)
        rec.update(
            status="OK",
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            param_bytes_global=_tree_bytes(params_abs),
            hlo_flops_per_chip=flops,
            hbm_bytes_analytic_per_chip=bytes_acc,
            hlo_bytes_upper_per_chip=stat["mem_bytes_per_chip"],
            param_bytes_local=param_local,
            wire_bytes_per_chip=wire_total,
            collectives=wires,
            cost_analysis_flops=float(cost.get("flops", 0.0)),
            model_flops_global=mflops,
            useful_flops_ratio=(mflops / n_chips / flops) if flops else None,
            **terms,
        )
        if mem is not None:
            for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                         "output_size_in_bytes", "alias_size_in_bytes",
                         "generated_code_size_in_bytes"):
                v = getattr(mem, attr, None)
                if v is not None:
                    rec[f"mem_{attr}"] = int(v)
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def append_result(rec: dict):
    RESULTS.parent.mkdir(exist_ok=True)
    data = []
    if RESULTS.exists():
        data = json.loads(RESULTS.read_text())
    data = [r for r in data
            if not (r["arch"] == rec["arch"] and r["shape"] == rec["shape"]
                    and r["mesh"] == rec["mesh"]
                    and r.get("flecs") == rec.get("flecs")
                    and r.get("variant", "") == rec.get("variant", ""))]
    data.append(rec)
    RESULTS.write_text(json.dumps(data, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--flecs", action="store_true",
                    help="lower the FLECS-CGD compressed-difference train step")
    ap.add_argument("--variant", default="",
                    help="perf variant tag (e.g. moe-fshard, gatherq)")
    ap.add_argument("--microbatches", type=int, default=0)
    args = ap.parse_args()

    archs = sorted(ARCHS) if args.all or not args.arch else [args.arch]
    shapes = (sorted(INPUT_SHAPES) if args.all or not args.shape
              else [args.shape])
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_one(arch, shape, mp, flecs=args.flecs,
                              variant=args.variant,
                              microbatches=args.microbatches)
                append_result(rec)
                keys = ("status", "compile_s", "t_compute_s", "t_memory_s",
                        "t_collective_s", "dominant", "reason", "error")
                brief = {k: rec.get(k) for k in keys if rec.get(k) is not None}
                print(f"[dryrun] {arch} x {shape} x {rec['mesh']}: {brief}",
                      flush=True)


if __name__ == "__main__":
    main()
