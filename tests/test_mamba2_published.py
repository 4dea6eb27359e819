"""The published Mamba-2 mixer and head: the conv bias over x, B and C, the
embedding multiplier of a tied model, and the published parameter count.
Plain float64 numpy references on seeded weights at small sizes."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import FFN_NONE, SSM, uniform_plan
from repro.models import ssm as ssm_mod
from repro.models.context import ModelContext
from repro.models.model import (abstract_params, embed_inputs, forward,
                                head_logits, init_params)

CPU_CTX = ModelContext(mesh=None, data_axes=(), remat=False)


def _cfg(conv_bias=True, n_layers=1, **kw):
    """mamba2 at a small size (d_model 128, 16 heads of 16, d_state 16)."""
    base = get_config("mamba2-1.3b", smoke=True)
    return dataclasses.replace(
        base, n_layers=n_layers, layer_plan=uniform_plan(n_layers, SSM,
                                                         FFN_NONE),
        ssm=dataclasses.replace(base.ssm, conv_bias=conv_bias), **kw)


def _seeded(params, key):
    """Every leaf drawn afresh (the initialiser leaves norms and dt_bias at
    constants), so each weight moves the output."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        (0.3 * jax.random.normal(k, x.shape)).astype(x.dtype)
        for k, x in zip(keys, leaves)])


def _silu(v):
    return v / (1.0 + np.exp(-v))


def _rms(x, scale, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * (1 + scale)


def _mixer_ref(p, x, cfg):
    """Mamba2 on x [B, S, D]: in_proj, depthwise causal conv1d over xBC with
    its bias, SiLU, the SSM as a sequential recurrence, D skip, gated
    RMSNorm, out_proj; float64."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    Bb, S, _ = x.shape
    P = cfg.ssm.head_dim
    H = cfg.ssm.expand * cfg.d_model // P
    K = cfg.ssm.conv_width

    def conv(u, g):
        w = p[f"conv_{g}"]
        up = np.concatenate([np.zeros((Bb, K - 1, u.shape[-1])), u], 1)
        y = sum(up[:, i:i + S] * w[i] for i in range(K))
        if cfg.ssm.conv_bias:
            y = y + p[f"conv_{g}_bias"]
        return _silu(y)

    z = x @ p["in_z"]
    xin, Bm, Cm = (conv(x @ p[f"in_{g}"], g) for g in ("x", "B", "C"))
    dt = np.log1p(np.exp(x @ p["in_dt"] + p["dt_bias"]))
    A = -np.exp(p["A_log"])
    xh = xin.reshape(Bb, S, H, P)
    h = np.zeros((Bb, H, P, Bm.shape[-1]))
    ys = []
    for t in range(S):
        h = (h * np.exp(dt[:, t] * A)[..., None, None]
             + np.einsum("bh,bn,bhp->bhpn", dt[:, t], Bm[:, t], xh[:, t]))
        ys.append(np.einsum("bn,bhpn->bhp", Cm[:, t], h))
    y = np.stack(ys, 1) + p["D_skip"][:, None] * xh
    y = _rms(y.reshape(Bb, S, -1) * _silu(z), p["norm"], cfg.norm_eps)
    return y @ p["out_proj"]


def _mixer_params(cfg, seed=0):
    p = ssm_mod.init_ssm(jax.random.key(seed), cfg, jnp.float32)
    return _seeded(p, jax.random.key(seed + 1))


def test_conv_bias_leaves_only_when_asked():
    assert {"conv_x_bias", "conv_B_bias", "conv_C_bias"} <= set(
        ssm_mod.init_ssm(jax.random.key(0), _cfg(True), jnp.float32))
    assert not any(k.startswith("conv_") and k.endswith("_bias")
                   for k in ssm_mod.init_ssm(jax.random.key(0), _cfg(False),
                                             jnp.float32))


@pytest.mark.parametrize("conv_bias", [True, False])
def test_ssm_forward_matches_plain_reference(rng, conv_bias):
    cfg = _cfg(conv_bias)
    p = _mixer_params(cfg)
    x = jnp.asarray(rng.normal(size=(2, 24, cfg.d_model)), jnp.float32)
    out, _ = ssm_mod.ssm_forward(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), _mixer_ref(p, x, cfg),
                               rtol=2e-3, atol=2e-3)


def test_prefill_then_decode_matches_forward_with_bias(rng):
    cfg = _cfg(True)
    p = _mixer_params(cfg, seed=3)
    S, S0 = 20, 13
    x = jnp.asarray(rng.normal(size=(2, S, cfg.d_model)), jnp.float32)
    full, _ = ssm_mod.ssm_forward(p, x, cfg)
    _, (state, convs) = ssm_mod.ssm_forward(p, x[:, :S0], cfg)
    cache = {"state": state, "conv_x": convs["x"], "conv_B": convs["B"],
             "conv_C": convs["C"]}
    outs = []
    for t in range(S0, S):
        o, cache = ssm_mod.ssm_decode(p, x[:, t:t + 1], cache, cfg)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(full[:, S0:]), rtol=2e-3,
                               atol=2e-3)


def _logits_ref(params, tokens, cfg, mult):
    """Tied one-layer model: embedding × mult, pre-norm mixer, residual,
    final norm, logits against the embedding table."""
    emb = np.asarray(params["embed"], np.float64)
    x = emb[np.asarray(tokens)] * mult
    (stack,) = params["blocks"][0]
    layer = jax.tree.map(lambda a: a[0], stack)
    h = _rms(x, np.asarray(layer["pre_norm"], np.float64), cfg.norm_eps)
    x = x + _mixer_ref(layer["mixer"], h, cfg)
    h = _rms(x, np.asarray(params["final_norm"], np.float64), cfg.norm_eps)
    return h @ emb.T


@pytest.mark.parametrize("mult", [1.0, None])
def test_tied_embedding_multiplier(rng, mult):
    cfg = _cfg(True, tie_embeddings=True, embed_multiplier=mult)
    params = _seeded(init_params(cfg, jax.random.key(0), jnp.float32),
                     jax.random.key(7))
    params["embed"] = params["embed"] / math.sqrt(cfg.d_model)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
    h, _ = forward(params, {"tokens": tokens}, cfg, CPU_CTX)
    logits = head_logits(params, h, cfg)
    # None keeps the legacy rule for a tied model: × √d_model
    want = _logits_ref(params, tokens, cfg,
                       math.sqrt(cfg.d_model) if mult is None else mult)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3,
                               atol=2e-3)


def _count(cfg):
    return sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(abstract_params(cfg, jnp.float32)))


def test_published_parameter_count():
    """state-spaces/mamba2-1.3b: tied, vocab padded to 50288, conv bias."""
    cfg = dataclasses.replace(get_config("mamba2-1.3b"), tie_embeddings=True,
                              embed_multiplier=1.0)
    assert (cfg.vocab, cfg.ssm.chunk, cfg.ssm.conv_bias) == (50288, 256, True)
    assert _count(cfg) == 1_343_757_312
    cut = dataclasses.replace(cfg, n_layers=16,
                              layer_plan=uniform_plan(16, SSM, FFN_NONE))
    assert _count(cut) == 516_580_352


@pytest.mark.parametrize("arch", ["gemma2-9b", "gemma3-27b",
                                  "recurrentgemma-9b"])
def test_gemma_family_keeps_sqrt_d_model(rng, arch):
    """With no explicit multiplier the Gemma-family configs embed as
    before: the same params, and the logits of an explicit ×√d_model."""
    cfg = get_config(arch, smoke=True)
    assert cfg.embed_multiplier is None
    explicit = dataclasses.replace(cfg, embed_multiplier=math.sqrt(
        cfg.d_model))
    assert (jax.tree.structure(abstract_params(cfg))
            == jax.tree.structure(abstract_params(explicit)))
    assert ([a.shape for a in jax.tree.leaves(abstract_params(cfg))]
            == [a.shape for a in jax.tree.leaves(abstract_params(explicit))])
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)),
                                   jnp.int32)}
    x = embed_inputs(params, batch, cfg)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(
        embed_inputs(params, batch, explicit)))
    rows = np.asarray(params["embed"])[np.asarray(batch["tokens"])]
    np.testing.assert_allclose(np.asarray(x), rows * math.sqrt(cfg.d_model),
                               rtol=1e-6)
    logits = [head_logits(params, forward(params, batch, c, CPU_CTX)[0], c)
              for c in (cfg, explicit)]
    np.testing.assert_array_equal(np.asarray(logits[0]),
                                  np.asarray(logits[1]))
