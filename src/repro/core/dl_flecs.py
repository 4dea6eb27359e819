"""FLECS-CGD at deep-learning scale: the paper's technique as a
first-class feature of the distributed trainer.

Mapping (DESIGN.md §3):
  * federated workers  = data-parallel groups (mesh data axes, manual in a
    partial-auto shard_map; the model axis stays auto so tensor/expert
    parallelism inside each worker is untouched).
  * params are REPLICATED over the data axes (faithful: each federated
    worker holds the full model) and sharded over `model`.
  * compressed gradient differences: per-tensor int8 random dithering with
    a pmax-shared scale, summed via an integer psum (widened to int16 for
    ring accumulation: wire = 2x smaller than f32; the paper's idealized
    c/32 assumes a parameter-server that decodes each payload — a ring
    all-reduce must carry the accumulation width).  The wire format is a
    ``compressors.dither_spec`` realized by the collective quantizer
    ``compressors.shared_scale_levels``, and the idealized per-worker
    payload is reported per step via ``compressors.spec_bits``
    (``metrics["uplink_mbits"]``).
  * shifts h^i: one bf16 pytree per worker (lives sharded over data —
    each worker's shift is its own slice; realized as per-device state
    inside shard_map).
  * second-order: per-tensor blocks of a GLOBAL Hessian sketch (m seeded
    columns, jvp-of-grad once per column), FedSONIA direction per tensor.
    B ≡ 0 (the paper's experimental init) makes Ỹ = C(Y) + 0 — no d×m
    state is ever stored; sketches are regenerated from the step index.
  * one step is one FLECS-CGD round, and its phases run under the
    federated engine's round scopes (``driver.ROUND_SCOPES``): the loss,
    gradient and HVPs under ``fed.oracle``, the quantizer under
    ``fed.compress.grad`` / ``fed.compress.hess`` (with ``compress.dither``
    inside, as ``COMPRESS_SCOPES`` names it), the psum, the shift and
    parameter updates and the metrics under ``fed.server``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import axis_size, shard_map
from repro.configs.base import ModelConfig
from repro.core.driver import (SCOPE_COMPRESS_GRAD, SCOPE_COMPRESS_HESS,
                               SCOPE_ORACLE, SCOPE_SERVER, bits_dtype)
from repro.core.compressors import (COMPRESS_SCOPES, FAMILY_DITHER,
                                    dither_spec, identity_spec,
                                    psum_level_cap, shared_scale_levels,
                                    spec_bits)
from repro.models.context import ModelContext
from repro.train.step import _loss_fn

P = jax.sharding.PartitionSpec
SCOPE_DITHER = COMPRESS_SCOPES[FAMILY_DITHER]


@dataclasses.dataclass(frozen=True)
class FlecsDLConfig:
    alpha: float = 1e-2            # iterate step size
    gamma: float = 0.5             # shift learning rate
    s_levels: int = 127            # int8 dithering levels (may be a traced
                                   # jax scalar: the cap is lax-side, see
                                   # compressors.psum_level_cap)
    m: int = 0                     # sketch columns (0 = first-order CGD/DIANA)
    omega: float = 1e-5
    Omega: float = 1e2
    rho: float = 1.0               # FedSONIA complement step: at DL scale the
                                   # complement IS most of the space, so ρ=1
                                   # makes the perp component behave like SGD
                                   # at lr=α while the sketched subspace gets
                                   # curvature-scaled steps
    compress: bool = True          # False = uncompressed DP baseline


def _tensor_sketch(step, idx, shape, m):
    """Seeded per-tensor sketch column block [numel, m] — regenerated, never
    stored or communicated (Algorithm 1's shared-seed trick)."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(23), step), idx)
    numel = int(np.prod(shape))  # repro-lint: disable=R2 -- folds a STATIC Python shape tuple at trace time; no traced value crosses to host
    v = jax.random.rademacher(key, (numel, m), jnp.float32)
    return v / np.sqrt(m)


def _fedsonia_tensor(y, mmat, g, cfg: FlecsDLConfig):
    """FedSONIA (Alg 5) on one flattened tensor block.
    y: [d, m] sketched Hessian block; mmat: [m, m]; g: [d]."""
    q, r = jnp.linalg.qr(y)                       # d x m, m x m
    core = r @ jnp.linalg.pinv(mmat, rcond=1e-6) @ r.T
    lam, v = jnp.linalg.eigh(0.5 * (core + core.T))
    a = jnp.abs(lam)
    lam_t = jnp.where(a >= cfg.omega, jnp.clip(a, cfg.omega, cfg.Omega),
                      cfg.Omega)
    vq = q @ v
    coef = vq.T @ g
    g_perp = g - vq @ coef
    return -(vq @ (coef / lam_t)) - cfg.rho * g_perp


def make_flecs_train_step(cfg: ModelConfig, ctx: ModelContext,
                          fcfg: Optional[FlecsDLConfig] = None):
    """Returns lower(params_abs, batch_abs, pshard, bshard) -> jax Lowered.

    The returned step signature is (params, shifts, batch, step_idx) ->
    (params, shifts, metrics).  ``pshard`` passed in is the standard
    FSDP sharding; the data axes are STRIPPED (params replicated per
    worker, as in the federation).
    """
    fcfg = fcfg or FlecsDLConfig()
    axes = ctx.data_axes
    mesh = ctx.mesh

    def strip_data(spec: P) -> P:
        out = []
        for entry in spec:
            es = entry if isinstance(entry, tuple) else (entry,)
            kept = tuple(a for a in es if a not in axes)
            out.append(kept[0] if len(kept) == 1 else (kept or None) and kept)
        return P(*out)

    # Inside the manual-data shard_map the model must not emit data-axis
    # sharding constraints (they are now manual); MoE token resharding also
    # drops to the (auto) model axis only.
    ctx_in = dataclasses.replace(ctx, data_axes=())

    def body(params, shifts, batch, step_idx):
        """Per-worker code (manual over data axes, auto over model).

        shifts = {"own":  per-worker shift h^i (leading worker dim, local
                          slice size 1 inside the body),
                  "mean": replicated running average h̄ — maintained
                          locally from the already-reduced c̄ (DIANA server
                          bookkeeping: h̄⁺ = h̄ + γ c̄; NO communication)}.
        """
        axis = axes if len(axes) > 1 else axes[0]
        with jax.named_scope(SCOPE_ORACLE):
            loss, grads = jax.value_and_grad(_loss_fn)(params, batch, cfg,
                                                       ctx_in)
        leaves, treedef = jax.tree.flatten(grads)
        h_own = [h[0] for h in jax.tree.leaves(shifts["own"])]
        h_mean = jax.tree.leaves(shifts["mean"])
        key0 = jax.random.fold_in(jax.random.key(29), step_idx)
        n = 1
        for a in axes:
            n *= axis_size(a)
        # the wire-format spec of the compressed collective: int8 random
        # dithering, levels capped so n workers' level sums stay exact in
        # the f16 psum accumulation below.  The cap is a lax-side clip
        # (compressors.psum_level_cap), so fcfg.s_levels may be a traced
        # sweep axis — DL-scale level grids vmapped in one program.
        gspec = dither_spec(psum_level_cap(fcfg.s_levels, n))
        payload_bits = jnp.zeros((), bits_dtype())  # idealized uplink

        # --- compressed gradient differences (the CGD contribution) -------
        g_tilde, new_own, new_mean = [], [], []
        for i, (g, ho, hm) in enumerate(zip(leaves, h_own, h_mean)):
            if not fcfg.compress:
                with jax.named_scope(SCOPE_SERVER):
                    g_avg = jax.lax.pmean(g.astype(jnp.float32), axis)
                g_tilde.append(g_avg)
                new_own.append(ho)
                new_mean.append(hm)
                payload_bits += spec_bits(identity_spec(), g.size)
                continue
            key = jax.random.fold_in(key0, i)
            with jax.named_scope(SCOPE_COMPRESS_GRAD), \
                    jax.named_scope(SCOPE_DITHER):
                delta = g.astype(jnp.float32) - ho.astype(jnp.float32)
                levels, scale = shared_scale_levels(key, delta, gspec.s,
                                                    axis)
            payload_bits += spec_bits(gspec, delta.size)
            with jax.named_scope(SCOPE_SERVER):
                # f16 psum: the compressed collective (wire = 2 bytes/elem).
                # f16 holds integers exactly up to 2048, so with s·n < 2048
                # the sum of n workers' levels is exact; XLA PROMOTES s16
                # all-reduce back to f32 (observed in the lowered HLO), f16
                # it keeps.
                summed = jax.lax.psum(levels.astype(jnp.float16), axis)
                q_own = levels.astype(jnp.float32) * scale      # own Q(δ_i)
                q_mean = summed.astype(jnp.float32) * scale / n  # c̄
                g_tilde.append(q_mean + hm.astype(jnp.float32))
                new_own.append((ho.astype(jnp.float32)
                                + fcfg.gamma * q_own).astype(ho.dtype))
                new_mean.append((hm.astype(jnp.float32)
                                 + fcfg.gamma * q_mean).astype(hm.dtype))
        g_tilde = jax.tree.unflatten(treedef, g_tilde)
        new_shifts = {
            "own": jax.tree.unflatten(treedef, [h[None] for h in new_own]),
            "mean": jax.tree.unflatten(treedef, new_mean),
        }

        # --- optional per-tensor sketched-Hessian preconditioning ---------
        if fcfg.m > 0:
            p_leaves = jax.tree.leaves(params)
            # m HVP passes, one jvp-of-grad per sketch column; the sketched
            # Hessian difference C(Y - B S) with B = 0 is C(Y): compressed
            # with the same int8/int16 integer collective.
            y_cols_all = [[] for _ in p_leaves]
            for col in range(fcfg.m):
                with jax.named_scope(SCOPE_ORACLE):
                    tang_col = jax.tree.unflatten(treedef, [
                        _tensor_sketch(step_idx, i, p.shape, fcfg.m)[:, col]
                        .reshape(p.shape).astype(p.dtype)
                        for i, p in enumerate(p_leaves)])
                    gfun = lambda pp: jax.grad(_loss_fn)(pp, batch, cfg,
                                                         ctx_in)
                    _, hv = jax.jvp(gfun, (params,), (tang_col,))
                for i, y in enumerate(jax.tree.leaves(hv)):
                    key = jax.random.fold_in(jax.random.fold_in(key0, col),
                                             1000 + i)
                    if fcfg.compress:
                        with jax.named_scope(SCOPE_COMPRESS_HESS), \
                                jax.named_scope(SCOPE_DITHER):
                            lv, sc = shared_scale_levels(
                                key, y.astype(jnp.float32), gspec.s, axis)
                        payload_bits += spec_bits(gspec, y.size)
                        with jax.named_scope(SCOPE_SERVER):
                            y_bar = (jax.lax.psum(lv.astype(jnp.float16),
                                                  axis)
                                     .astype(jnp.float32) * sc / n)
                    else:
                        with jax.named_scope(SCOPE_SERVER):
                            y_bar = jax.lax.pmean(y.astype(jnp.float32),
                                                  axis)
                        payload_bits += spec_bits(identity_spec(), y.size)
                    y_cols_all[i].append(y_bar.reshape(-1))
            directions = []
            with jax.named_scope(SCOPE_SERVER):
                for i, g in enumerate(jax.tree.leaves(g_tilde)):
                    V = _tensor_sketch(step_idx, i, g.shape, fcfg.m)  # [d, m]
                    Y = jnp.stack(y_cols_all[i], axis=1)              # [d, m]
                    M = V.T @ Y                                       # [m, m]
                    p_dir = _fedsonia_tensor(
                        Y, M, g.reshape(-1).astype(jnp.float32), fcfg)
                    directions.append(p_dir.reshape(g.shape))
            update = jax.tree.unflatten(treedef, directions)
        else:
            update = jax.tree.map(lambda g: -g, g_tilde)

        with jax.named_scope(SCOPE_SERVER):
            new_params = jax.tree.map(
                lambda p, u: (p.astype(jnp.float32)
                              + fcfg.alpha * u).astype(p.dtype),
                params, update)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                 for g in jax.tree.leaves(g_tilde)))
            # uplink_mbits: the idealized per-worker payload (spec_bits of
            # the wire spec — what a parameter-server federation would
            # ship); the ring all-reduce actually carries the 16-bit
            # accumulation width, a fixed 16/ceil(log2(2s+1)) factor on top
            metrics = {"loss": jax.lax.pmean(loss, axis), "grad_norm": gnorm,
                       "uplink_mbits": payload_bits / 1e6}
        return new_params, new_shifts, metrics

    ns = lambda sp: jax.sharding.NamedSharding(mesh, sp)
    is_spec = lambda sp: isinstance(sp, P)

    def param_shardings(pshard):
        """The step's param shardings: ``pshard`` with the data axes
        stripped — each worker holds the full model.  jit-level shardings
        keep the model axis (auto); shard_map in_specs may only mention
        MANUAL axes, over which params are replicated."""
        return jax.tree.map(
            lambda s: ns(strip_data(s.spec if hasattr(s, "spec") else s)),
            pshard,
            is_leaf=lambda s: isinstance(s, (jax.sharding.NamedSharding, P)))

    def build(params_abs, batch_abs, pshard, bshard):
        """Construct the shard_mapped step + shardings (shared by lower()
        and the executable path).  Returns (jitted, shifts_abs): the
        shifts' shapes carry their shardings, so callers can allocate them
        in place (``init_shifts``); params go in with
        ``param_shardings(pshard)``."""
        prep = jax.tree.map(lambda _: P(), params_abs)
        n_data = 1
        for a in axes:
            n_data *= mesh.shape[a]
        shifts_abs = {
            "own": jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                (n_data,) + x.shape, jnp.bfloat16), params_abs),
            "mean": jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, jnp.bfloat16), params_abs),
        }
        sspec = {
            "own": jax.tree.map(lambda _: P(axes), params_abs),
            "mean": jax.tree.map(lambda _: P(), params_abs),
        }
        bspec = jax.tree.map(
            lambda s: s.spec if hasattr(s, "spec") else s, bshard,
            is_leaf=lambda s: isinstance(s, (jax.sharding.NamedSharding, P)))
        smapped = shard_map(
            body, mesh=mesh,
            in_specs=(prep, sspec, bspec, P()),
            out_specs=(prep, sspec, P()),
            axis_names=set(axes), check_vma=False)
        psh = param_shardings(pshard)
        ssh = jax.tree.map(ns, sspec, is_leaf=is_spec)
        # params and shifts are donated and come back with the shardings
        # they went in with, so each step updates them in place: without
        # donation a full-width step holds two copies of both.
        jitted = jax.jit(smapped, in_shardings=(psh, ssh, bshard, None),
                         out_shardings=(psh, ssh, None),
                         donate_argnums=(0, 1))
        shifts_abs = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            shifts_abs, ssh)
        return jitted, shifts_abs

    def lower(params_abs, batch_abs, pshard, bshard):
        jitted, shifts_abs = build(params_abs, batch_abs, pshard, bshard)
        step_sds = jax.ShapeDtypeStruct((), jnp.int32)
        return jitted.lower(params_abs, shifts_abs, batch_abs, step_sds)

    lower.build = build
    lower.param_shardings = param_shardings
    return lower


def init_shifts(shifts_abs):
    """Zero shifts allocated directly with the shardings ``build`` chose —
    never gathered onto one device first (the per-worker shifts of a
    full-width model on n workers would not fit there)."""
    return jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype, device=a.sharding), shifts_abs)
