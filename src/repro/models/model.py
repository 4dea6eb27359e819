"""LM assembler: embeds + scanned layer groups + head, for all 10 archs.

Layers are grouped into maximal repeating patterns (cfg.layer_groups()) and
executed with ``lax.scan`` over the repeat dim so XLA compiles each distinct
block body exactly once — essential for 61–94-layer dry-run compiles.

Three entry points:
  * ``forward``      — full-sequence hidden states (train).
  * ``prefill``      — full-sequence + populated decode caches.
  * ``decode_step``  — one token with caches (serve).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import shard_map
from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA, FFN_DENSE,
                                FFN_MOE, FFN_NONE, RGLRU, SSM, ModelConfig)
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.context import ModelContext
from repro.models.layers import ffn, init_ffn, rms_norm, softcap

P = jax.sharding.PartitionSpec


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_sublayer(key, mixer, ffnk, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 3)
    p: Dict[str, Any] = {"pre_norm": jnp.zeros((cfg.d_model,), dtype)}
    if mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        p["mixer"] = attn_mod.init_attn(ks[0], cfg, dtype)
    elif mixer == ATTN_MLA:
        p["mixer"] = mla_mod.init_mla(ks[0], cfg, dtype)
    elif mixer == SSM:
        p["mixer"] = ssm_mod.init_ssm(ks[0], cfg, dtype)
    elif mixer == RGLRU:
        p["mixer"] = rglru_mod.init_rglru(ks[0], cfg, dtype)
    else:
        raise ValueError(mixer)
    if cfg.use_post_norms:
        p["post_mixer_norm"] = jnp.zeros((cfg.d_model,), dtype)
    if ffnk == FFN_DENSE:
        p["ffn_norm"] = jnp.zeros((cfg.d_model,), dtype)
        p["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype)
    elif ffnk == FFN_MOE:
        p["ffn_norm"] = jnp.zeros((cfg.d_model,), dtype)
        p["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
    if ffnk != FFN_NONE and cfg.use_post_norms:
        p["post_ffn_norm"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4 + len(cfg.layer_groups()))
    D, V = cfg.d_model, cfg.vocab
    params: Dict[str, Any] = {}
    if cfg.n_codebooks:
        params["embed"] = (jax.random.normal(ks[0], (cfg.n_codebooks, V, D))
                           / np.sqrt(D)).astype(dtype)
    else:
        params["embed"] = (jax.random.normal(ks[0], (V, D))
                           / np.sqrt(D)).astype(dtype)
    groups = []
    for gi, (block_plan, reps) in enumerate(cfg.layer_groups()):
        gk = jax.random.split(ks[2 + gi], reps)

        def make_rep(k):
            sks = jax.random.split(k, len(block_plan))
            return [
                _init_sublayer(sks[i], m, f, cfg, dtype)
                for i, (m, f) in enumerate(block_plan)
            ]

        reps_params = [make_rep(gk[r]) for r in range(reps)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *reps_params)
        groups.append(stacked)
    params["blocks"] = groups
    params["final_norm"] = jnp.zeros((D,), dtype)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks:
            params["head"] = (jax.random.normal(ks[1], (cfg.n_codebooks, D, V))
                              / np.sqrt(D)).astype(dtype)
        else:
            params["head"] = (jax.random.normal(ks[1], (D, V))
                              / np.sqrt(D)).astype(dtype)
    return params


def abstract_params(cfg: ModelConfig, dtype=jnp.bfloat16):
    """ShapeDtypeStruct pytree — dry-run init without allocation."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k, dtype), jax.random.key(0))


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_inputs(params, batch, cfg: ModelConfig):
    """batch: {"tokens": [B,S] or [B,S,C]; optional "image_embeds"}."""
    tokens = batch["tokens"]
    if cfg.n_codebooks:
        x = sum(jnp.take(params["embed"][i], tokens[..., i], axis=0)
                for i in range(cfg.n_codebooks))
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.family == "vlm" and "image_embeds" in batch:
        img = batch["image_embeds"].astype(x.dtype)
        n_img = img.shape[1]
        pos = jnp.arange(x.shape[1])[None, :, None]
        pad = x.shape[1] - n_img
        img_full = jnp.pad(img, ((0, 0), (0, pad), (0, 0)))
        x = jnp.where(pos < n_img, img_full, x)
    if cfg.embed_multiplier is not None:
        x = x * float(cfg.embed_multiplier)
    elif cfg.use_post_norms or cfg.tie_embeddings:   # gemma-style scaling
        x = x * float(np.sqrt(cfg.d_model))
    return x


def head_logits(params, hidden, cfg: ModelConfig):
    """hidden: [..., D] -> logits [..., V] (or [..., C, V] for audio)."""
    h = rms_norm(hidden, params["final_norm"], cfg.norm_eps)
    if cfg.n_codebooks:
        table = params.get("head")
        if table is None:
            table = jnp.swapaxes(params["embed"], -1, -2)
        logits = jnp.einsum("...d,cdv->...cv", h, table)
    elif cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h, params["embed"])
    else:
        logits = jnp.einsum("...d,dv->...v", h, params["head"])
    return softcap(logits.astype(jnp.float32), cfg.logit_softcap)


# ---------------------------------------------------------------------------
# Blocks (full sequence)
# ---------------------------------------------------------------------------

def shard_act(x, ctx: ModelContext):
    """Pin activations to batch-over-data sharding — without this, Shardy
    may resolve the FSDP-weight/batch conflict by replicating the batch and
    sharding contraction dims instead (verified on tinyllama train_4k)."""
    if ctx.mesh is None or x.ndim < 2 or not ctx.data_axes:
        return x  # data_axes=() => already inside a manual-data shard_map
    n = int(np.prod([ctx.mesh.shape[a] for a in ctx.data_axes]))
    if x.shape[0] % n:
        return x
    spec = P(ctx.data_axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx.mesh, spec))


def _moe_call(p, x, cfg, ctx: ModelContext):
    B, S, D = x.shape
    if ctx.moe_impl == "ref" or ctx.mesh is None:
        return moe_mod.moe_ref(p, x, cfg)
    if ctx.moe_impl == "fshard":
        # Decode layout: weights resident [E(model), D, F(data)]; tokens
        # replicated inside the layer (see moe.moe_fshard).
        T = B * S
        fn = functools.partial(
            moe_mod.moe_fshard, cfg=cfg, model_axis=ctx.model_axis,
            data_axes=ctx.data_axes, n_model=ctx.n_model, n_data=ctx.n_data)
        moe_params = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
        fspec = {
            "router": P(),
            "w_gate": P(ctx.model_axis, None, "data"),
            "w_up": P(ctx.model_axis, None, "data"),
            "w_down": P(ctx.model_axis, "data", None),
        }
        out, aux = shard_map(
            fn, mesh=ctx.mesh,
            in_specs=(fspec, P(ctx.data_axes, None)),
            out_specs=(P(ctx.data_axes, None), P()),
            check_vma=False,
        )(moe_params, x.reshape(T, D))
        return out.reshape(B, S, D), aux
    T = B * S
    token_axes = ctx.data_axes + (ctx.model_axis,)
    n_tok_shards = int(np.prod([ctx.mesh.shape[a] for a in token_axes]))
    if T % n_tok_shards or (T // n_tok_shards) < cfg.moe.top_k:
        token_axes = ctx.data_axes          # decode / tiny token counts
        n_tok_shards = ctx.n_data
        if T % n_tok_shards:
            return moe_mod.moe_ref(p, x, cfg)   # degenerate token counts
    gather_axis = "data" if ("data" in ctx.data_axes
                             and p["w_gate"].ndim == 3) else None

    fn = functools.partial(
        moe_mod.moe_sorted, cfg=cfg, axis_name=ctx.model_axis,
        n_shards=ctx.n_model, gather_axis=gather_axis,
        aux_axes=token_axes if len(token_axes) > 1 else token_axes[0],
        gather_quant=ctx.moe_gather_quant)
    moe_params = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
    wspec = {
        "router": P(),
        "w_gate": P(ctx.model_axis, gather_axis, None),
        "w_up": P(ctx.model_axis, gather_axis, None),
        "w_down": P(ctx.model_axis, None, gather_axis),
    }
    out, aux = shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(wspec, P(token_axes, None)),
        out_specs=(P(token_axes, None), P()),
        check_vma=False,
    )(moe_params, x.reshape(T, D))
    return out.reshape(B, S, D), aux


def apply_block(p, x, mixer, ffnk, cfg, ctx, positions):
    """One transformer block (full-seq).  Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    x = shard_act(x, ctx)
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        window = cfg.window if mixer == ATTN_LOCAL else 0
        out = attn_mod.attn_forward(p["mixer"], h, cfg, window=window,
                                    positions=positions)
    elif mixer == ATTN_MLA:
        out = mla_mod.mla_forward(p["mixer"], h, cfg, positions=positions)
    elif mixer == SSM:
        out, _ = ssm_mod.ssm_forward(p["mixer"], h, cfg)
    elif mixer == RGLRU:
        out, _ = rglru_mod.rglru_forward(p["mixer"], h, cfg)
    if cfg.use_post_norms:
        out = rms_norm(out, p["post_mixer_norm"], cfg.norm_eps)
    x = x + out
    if ffnk != FFN_NONE:
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if ffnk == FFN_DENSE:
            out = ffn(p["ffn"], h, cfg.act)
        else:
            out, aux = _moe_call(p["moe"], h, cfg, ctx)
            if cfg.moe.n_shared:
                out = out + ffn(p["moe"]["shared"], h, cfg.act)
        if cfg.use_post_norms:
            out = rms_norm(out, p["post_ffn_norm"], cfg.norm_eps)
        x = x + out
    return x, aux


def forward(params, batch, cfg: ModelConfig, ctx: ModelContext):
    """Full-sequence forward.  Returns (hidden [B,S,D], aux scalar)."""
    x = shard_act(embed_inputs(params, batch, cfg), ctx)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :].astype(jnp.int32)
    aux_total = jnp.zeros((), jnp.float32)
    for (block_plan, reps), gp in zip(cfg.layer_groups(), params["blocks"]):

        def body(xc, sub_stack, _plan=block_plan):
            aux = jnp.zeros((), jnp.float32)
            for sp, (m, f) in zip(sub_stack, _plan):
                xc, a = apply_block(sp, xc, m, f, cfg, ctx, positions)
                aux += a
            return xc, aux

        if ctx.remat:
            body = jax.checkpoint(body)
        x, auxs = jax.lax.scan(body, x, gp)
        aux_total = aux_total + jnp.sum(auxs)
    return x, aux_total


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _cache_for(mixer, cfg: ModelConfig, batch, max_len, dtype, ctx):
    if mixer == ATTN_GLOBAL or (mixer == ATTN_LOCAL and not cfg.window):
        S = max_len
        if ctx.seq_shard_decode:
            pass  # sharding is expressed via NamedSharding at the step level
        return {"k": jnp.zeros((batch, S, cfg.n_kv_heads, cfg.qk_head_dim), dtype),
                "v": jnp.zeros((batch, S, cfg.n_kv_heads, cfg.head_dim), dtype)}
    if mixer == ATTN_LOCAL:
        S = min(cfg.window, max_len)
        return {"k": jnp.zeros((batch, S, cfg.n_kv_heads, cfg.qk_head_dim), dtype),
                "v": jnp.zeros((batch, S, cfg.n_kv_heads, cfg.head_dim), dtype)}
    if mixer == ATTN_MLA:
        return {"c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
                "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype)}
    if mixer == SSM:
        d_inner, H, Pd, N = ssm_mod._dims(cfg)
        K = cfg.ssm.conv_width - 1
        return {"state": jnp.zeros((batch, H, Pd, N), jnp.float32),
                "conv_x": jnp.zeros((batch, K, d_inner), dtype),
                "conv_B": jnp.zeros((batch, K, N), dtype),
                "conv_C": jnp.zeros((batch, K, N), dtype)}
    if mixer == RGLRU:
        W = cfg.rglru.lru_width or cfg.d_model
        return {"state": jnp.zeros((batch, W), jnp.float32),
                "conv": jnp.zeros((batch, cfg.rglru.conv_width - 1, W), dtype)}
    raise ValueError(mixer)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               ctx: ModelContext, dtype=jnp.bfloat16):
    """Cache pytree mirroring params["blocks"] group structure."""
    groups = []
    for block_plan, reps in cfg.layer_groups():
        sub = [
            jax.tree.map(lambda x: jnp.broadcast_to(x, (reps,) + x.shape),
                         _cache_for(m, cfg, batch, max_len, dtype, ctx))
            for (m, f) in block_plan
        ]
        groups.append(sub)
    return groups


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def decode_block(p, c, x, mixer, ffnk, cfg, ctx, pos):
    x = shard_act(x, ctx)
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        window = cfg.window if mixer == ATTN_LOCAL else 0
        seq_shard = ctx.seq_shard_decode and not window
        out, c = attn_mod.attn_decode(p["mixer"], h, c, pos, cfg,
                                      window=window, ctx=ctx,
                                      seq_shard=seq_shard)
    elif mixer == ATTN_MLA:
        out, c = mla_mod.mla_decode(p["mixer"], h, c, pos, cfg)
    elif mixer == SSM:
        out, c = ssm_mod.ssm_decode(p["mixer"], h, c, cfg)
    elif mixer == RGLRU:
        out, c = rglru_mod.rglru_decode(p["mixer"], h, c, cfg)
    if cfg.use_post_norms:
        out = rms_norm(out, p["post_mixer_norm"], cfg.norm_eps)
    x = x + out
    if ffnk != FFN_NONE:
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if ffnk == FFN_DENSE:
            out = ffn(p["ffn"], h, cfg.act)
        else:
            out, _ = _moe_call(p["moe"], h, cfg, ctx)
            if cfg.moe.n_shared:
                out = out + ffn(p["moe"]["shared"], h, cfg.act)
        if cfg.use_post_norms:
            out = rms_norm(out, p["post_ffn_norm"], cfg.norm_eps)
        x = x + out
    return x, c


def decode_step(params, cache, batch, pos, cfg: ModelConfig,
                ctx: ModelContext):
    """One-token decode.  batch["tokens"]: [B,1] (or [B,1,C] audio).
    Returns (logits [B,1,...], new_cache)."""
    x = embed_inputs(params, batch, cfg)
    new_groups = []
    for (block_plan, reps), gp, gc in zip(cfg.layer_groups(),
                                          params["blocks"], cache):

        def body(xc, pc, _plan=block_plan):
            sub_p, sub_c = pc
            new_cs = []
            for sp, sc, (m, f) in zip(sub_p, sub_c, _plan):
                xc, nc = decode_block(sp, sc, xc, m, f, cfg, ctx, pos)
                new_cs.append(nc)
            return xc, new_cs

        x, new_c = jax.lax.scan(body, x, (gp, gc))
        new_groups.append(new_c)
    logits = head_logits(params, x, cfg)
    return logits, new_groups


# ---------------------------------------------------------------------------
# Prefill (full sequence, returns caches for subsequent decode)
# ---------------------------------------------------------------------------

def prefill(params, batch, cfg: ModelConfig, ctx: ModelContext,
            max_len: int = 0):
    """Full-sequence forward that also populates decode caches.

    Returns (last_logits [B, ...], cache).  max_len defaults to S.
    """
    x = embed_inputs(params, batch, cfg)
    B, S, D = x.shape
    max_len = max_len or S
    positions = jnp.arange(S)[None, :].astype(jnp.int32)
    cache_groups = []
    for (block_plan, reps), gp in zip(cfg.layer_groups(), params["blocks"]):

        def body(xc, sub_stack, _plan=block_plan):
            caches = []
            for sp, (m, f) in zip(sub_stack, _plan):
                xc, c = _prefill_block(sp, xc, m, f, cfg, ctx, positions,
                                       max_len)
                caches.append(c)
            return xc, caches

        x, caches = jax.lax.scan(body, x, gp)
        cache_groups.append(caches)
    logits = head_logits(params, x[:, -1:], cfg)
    return logits, cache_groups


def _prefill_block(p, x, mixer, ffnk, cfg, ctx, positions, max_len):
    """Like apply_block but captures the decode cache."""
    B, S, D = x.shape
    x = shard_act(x, ctx)
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    dtype = x.dtype
    if mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        window = cfg.window if mixer == ATTN_LOCAL else 0
        q = jnp.einsum("bsd,dhk->bshk", h, p["mixer"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, p["mixer"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, p["mixer"]["wv"])
        from repro.models.layers import apply_rope
        q = apply_rope(q, positions, cfg.rope_base)
        k = apply_rope(k, positions, cfg.rope_base)
        o = attn_mod.chunked_attention(q, k, v, window=window,
                                       cap=cfg.attn_softcap)
        out = jnp.einsum("bshk,hkd->bsd", o, p["mixer"]["wo"])
        if window:
            W = min(window, max_len)
            if S >= W:   # keep only the trailing window, at its ring slots
                slots = (S - W + jnp.arange(W)) % W
                kc = jnp.zeros((B, W) + k.shape[2:], dtype).at[:, slots].set(
                    k[:, S - W:])
                vc = jnp.zeros((B, W) + v.shape[2:], dtype).at[:, slots].set(
                    v[:, S - W:])
            else:
                kc = jnp.zeros((B, W) + k.shape[2:], dtype).at[:, :S].set(k)
                vc = jnp.zeros((B, W) + v.shape[2:], dtype).at[:, :S].set(v)
            c = {"k": kc, "v": vc}
        else:
            pad = max_len - S
            c = {"k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(dtype),
                 "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(dtype)}
    elif mixer == ATTN_MLA:
        q_nope, q_rope, c_kv, k_rope = mla_mod._latents(p["mixer"], h, cfg,
                                                        positions)
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["mixer"]["wk_b"])
        v = jnp.einsum("bsr,rhv->bshv", c_kv, p["mixer"]["wv_b"])
        H = cfg.n_heads
        k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                    (B, S, H, cfg.qk_rope_dim))
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        kf = jnp.concatenate([k_nope, k_rope_h], axis=-1)
        o = attn_mod.chunked_attention(qf, kf, v)
        out = jnp.einsum("bshv,hvd->bsd", o, p["mixer"]["wo"])
        pad = max_len - S
        c = {"c_kv": jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))).astype(dtype),
             "k_rope": jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))).astype(dtype)}
    elif mixer == SSM:
        out, (state, convs) = ssm_mod.ssm_forward(p["mixer"], h, cfg)
        c = {"state": state, "conv_x": convs["x"], "conv_B": convs["B"],
             "conv_C": convs["C"]}
    elif mixer == RGLRU:
        out, (state, conv) = rglru_mod.rglru_forward(p["mixer"], h, cfg)
        c = {"state": state, "conv": conv}
    if cfg.use_post_norms:
        out = rms_norm(out, p["post_mixer_norm"], cfg.norm_eps)
    x = x + out
    if ffnk != FFN_NONE:
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if ffnk == FFN_DENSE:
            out = ffn(p["ffn"], h, cfg.act)
        else:
            out, _ = _moe_call(p["moe"], h, cfg, ctx)
            if cfg.moe.n_shared:
                out = out + ffn(p["moe"]["shared"], h, cfg.act)
        if cfg.use_post_norms:
            out = rms_norm(out, p["post_ffn_norm"], cfg.norm_eps)
        x = x + out
    return x, c
