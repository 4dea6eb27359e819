"""Mamba-2 SSD (state-space duality) mixer [arXiv:2405.21060].

Chunked SSD: intra-chunk "attention-like" quadratic term + inter-chunk state
recurrence (lax.scan over chunks).  Decode carries (ssm_state, conv_states) —
O(1) in sequence length, which is why mamba2 runs the long_500k shape.

Projections are stored UNFUSED (separate z/x/B/C/dt weights) so the inner
dim (d_inner) and head dim can be cleanly sharded over the model axis —
a fused in_proj would force resharding at the split points (DESIGN.md §5).
The depthwise conv over x, B and C likewise keeps one weight (and, with
``SSMConfig.conv_bias``, one bias) per channel group.

``ssd_scan`` runs under the ``jax.named_scope`` ``SCOPE_SSD``, so its
compiled instructions can be read by name in a profiler trace.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import causal_conv1d, rms_norm

SCOPE_SSD = "ssm.ssd"           # the chunked SSD scan, fwd and bwd
CONV_GROUPS = ("x", "B", "C")   # the channel groups of the depthwise conv


def _dims(cfg):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    return d_inner, n_heads, s.head_dim, s.d_state


def init_ssm(key, cfg, dtype):
    s = cfg.ssm
    d_inner, H, P, N = _dims(cfg)
    D = cfg.d_model
    ks = jax.random.split(key, 9)
    sc = 1.0 / np.sqrt(D)
    params = {
        "in_z": (jax.random.normal(ks[0], (D, d_inner)) * sc).astype(dtype),
        "in_x": (jax.random.normal(ks[1], (D, d_inner)) * sc).astype(dtype),
        "in_B": (jax.random.normal(ks[2], (D, N)) * sc).astype(dtype),
        "in_C": (jax.random.normal(ks[3], (D, N)) * sc).astype(dtype),
        "in_dt": (jax.random.normal(ks[4], (D, H)) * sc).astype(dtype),
        "conv_x": (jax.random.normal(ks[5], (s.conv_width, d_inner))
                   * 0.1).astype(dtype),
        "conv_B": (jax.random.normal(ks[6], (s.conv_width, N)) * 0.1).astype(dtype),
        "conv_C": (jax.random.normal(ks[7], (s.conv_width, N)) * 0.1).astype(dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "D_skip": jnp.ones((H,), jnp.float32),
        "norm": jnp.zeros((d_inner,), dtype),
        "out_proj": (jax.random.normal(ks[8], (d_inner, D))
                     / np.sqrt(d_inner)).astype(dtype),
    }
    if s.conv_bias:
        widths = {"x": d_inner, "B": N, "C": N}
        bk = jax.random.split(jax.random.fold_in(key, 9), len(CONV_GROUPS))
        bound = 1.0 / np.sqrt(s.conv_width)   # Conv1d's init, fan-in K
        for k, g in zip(bk, CONV_GROUPS):
            params[f"conv_{g}_bias"] = jax.random.uniform(
                k, (widths[g],), jnp.float32, -bound, bound).astype(dtype)
    return params


def _segsum(a):
    """a: [..., Q] -> lower-triangular cumulative sums [..., Q, Q]:
    out[i, j] = sum(a[j+1..i]) for i >= j, -inf above diagonal."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, diff, -jnp.inf)


@jax.named_scope(SCOPE_SSD)
def ssd_scan(xh, dt, A_log, B_mat, C_mat, chunk, init_state=None):
    """Chunked SSD.  xh: [B,S,H,P]; dt: [B,S,H]; B_mat/C_mat: [B,S,N].

    Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    Bb, S, H, P = xh.shape
    N = B_mat.shape[-1]
    Q = min(chunk, S)
    while S % Q:          # largest divisor of S not exceeding the chunk size
        Q -= 1
    nc = S // Q
    A = -jnp.exp(A_log.astype(jnp.float32))                 # [H], negative
    a = dt.astype(jnp.float32) * A                          # [B,S,H]
    xdt = xh.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    a_c = a.reshape(Bb, nc, Q, H).transpose(0, 1, 3, 2)     # [B,nc,H,Q]
    x_c = xdt.reshape(Bb, nc, Q, H, P)
    B_c = B_mat.astype(jnp.float32).reshape(Bb, nc, Q, N)
    C_c = C_mat.astype(jnp.float32).reshape(Bb, nc, Q, N)

    L = jnp.exp(_segsum(a_c))                               # [B,nc,H,Q,Q]
    # Intra-chunk (diagonal blocks).
    y_diag = jnp.einsum("bcln,bcsn,bchls,bcshp->bclhp",
                        C_c, B_c, L, x_c)
    # Per-chunk end states.
    a_cum = jnp.cumsum(a_c, axis=-1)                        # [B,nc,H,Q]
    a_tail = a_cum[..., -1:] - a_cum                        # decay to chunk end
    states = jnp.einsum("bcsn,bchs,bcshp->bchpn",
                        B_c, jnp.exp(a_tail), x_c)
    # Inter-chunk recurrence.
    decay = jnp.exp(a_cum[..., -1])                         # [B,nc,H]

    def step(s_prev, inp):
        st, dc = inp
        s_new = s_prev * dc[..., None, None] + st
        return s_new, s_prev

    s0 = (jnp.zeros((Bb, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))
    final, s_prevs = jax.lax.scan(
        step, s0,
        (states.transpose(1, 0, 2, 3, 4), decay.transpose(1, 0, 2)))
    s_prevs = s_prevs.transpose(1, 0, 2, 3, 4)              # [B,nc,H,P,N]
    y_off = jnp.einsum("bcln,bchl,bchpn->bclhp",
                       C_c, jnp.exp(a_cum), s_prevs)
    y = (y_diag + y_off).reshape(Bb, S, H, P)
    return y, final


def _project(params, x):
    z = jnp.einsum("bsd,dk->bsk", x, params["in_z"])
    xin = jnp.einsum("bsd,dk->bsk", x, params["in_x"])
    B_in = jnp.einsum("bsd,dn->bsn", x, params["in_B"])
    C_in = jnp.einsum("bsd,dn->bsn", x, params["in_C"])
    dt = jnp.einsum("bsd,dh->bsh", x, params["in_dt"])
    return z, xin, B_in, C_in, dt


def _conv(params, g, x, state, cfg):
    """Depthwise causal conv of channel group ``g`` (x, B or C), plus its
    bias where the config has one (``Mamba2``'s ``conv1d`` over xBC), then
    SiLU.  Returns (activation, new conv state)."""
    y, new_state = causal_conv1d(x, params[f"conv_{g}"], state)
    if cfg.ssm.conv_bias:
        y = y + params[f"conv_{g}_bias"].astype(y.dtype)
    return jax.nn.silu(y), new_state


def ssm_forward(params, x, cfg, *, state=None, conv_state=None):
    """Full-sequence mixer.  x: [B,S,D] -> (y [B,S,D], (state, convs))."""
    d_inner, H, P, N = _dims(cfg)
    z, xin, B_in, C_in, dt = _project(params, x)
    cs = conv_state or {"x": None, "B": None, "C": None}
    xin, cx = _conv(params, "x", xin, cs["x"], cfg)
    B_in, cb = _conv(params, "B", B_in, cs["B"], cfg)
    C_in, cc = _conv(params, "C", C_in, cs["C"], cfg)
    xh = xin.reshape(*x.shape[:2], H, P)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    y, state = ssd_scan(xh, dt, params["A_log"], B_in, C_in,
                        cfg.ssm.chunk, init_state=state)
    y = y + params["D_skip"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(*x.shape[:2], d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, params["out_proj"])
    return out, (state, {"x": cx, "B": cb, "C": cc})


def ssm_decode(params, x, cache, cfg):
    """One-token decode.  x: [B,1,D]; cache: {"state","conv_x","conv_B","conv_C"}."""
    d_inner, H, P, N = _dims(cfg)
    z, xin, B_in, C_in, dt = _project(params, x)
    xin, cx = _conv(params, "x", xin, cache["conv_x"], cfg)
    B_in, cb = _conv(params, "B", B_in, cache["conv_B"], cfg)
    C_in, cc = _conv(params, "C", C_in, cache["conv_C"], cfg)
    xh = xin[:, 0].reshape(-1, H, P).astype(jnp.float32)
    B1 = B_in[:, 0].astype(jnp.float32)
    C1 = C_in[:, 0].astype(jnp.float32)
    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    decay = jnp.exp(dt1 * A)                                # [B,H]
    h = cache["state"] * decay[..., None, None] + jnp.einsum(
        "bh,bn,bhp->bhpn", dt1, B1, xh)
    y = jnp.einsum("bn,bhpn->bhp", C1, h)
    y = y + params["D_skip"][:, None] * xh
    y = y.reshape(-1, 1, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, params["out_proj"])
    return out, {"state": h, "conv_x": cx, "conv_B": cb, "conv_C": cc}
