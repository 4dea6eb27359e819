"""The published mamba2-1.3b, cut to 16 of its 48 layers, trained by
FLECS-CGD (m = 0) through the repository's DL trainer.

Sizes are in ``mamba2-1.3b-pub-l16.json``: the published widths, the conv
bias of the ``Mamba2`` layer, and a tied embedding fed to the first layer
unscaled (``embed_multiplier`` 1).  A traffic file gives the data mesh, the
batch per worker, the sequence length and the CGD settings.  One timed
call is one step, which is one FLECS-CGD round.

The timed path is the jitted step of
``core.dl_flecs.make_flecs_train_step(...).build``, built and compiled as
``launch/train.py`` builds it (f32 params, bf16 shifts, remat, params and
shifts donated).  Set-up makes the weights on the device in one jitted
call from the seed (this file's generator, not the program's
initialiser), compiles the step, and drives the first ``check_steps``
steps through the window's own call and feed; the window goes on from
there with the same object.

This file also holds the model FLOP count and the plain reference: the
published Mamba-2 forward pass in ``jax.numpy`` at float32 ``HIGHEST``
precision (the conv bias before SiLU, the tied embedding unscaled, whatever
the program's settings), with the SSD mixer written as the semiseparable
matrix of the paper (y = M x, M[l, s] = C_lᵀB_s · exp(Σ_{k=s+1..l} dt_k A)
· dt_s), no chunking, in blocks of heads, each exponent the difference of
two compensated prefix sums; and the FLECS-CGD update of ``dl_flecs``
written out: int8 dithering of g - h_own with a scale shared over the
workers, the level sum over the workers, the shift updates in bfloat16.
It draws the dither uniforms from the same keys as the program
(``fold_in(fold_in(key(29), step), leaf)``), and imports nothing of
``repro.core``.

``readings`` compares the losses of the checked steps; per leaf, the norms
of the first round's compressed gradient (h̄ after step 0, over γ) and of
the parameters' change over the checked steps; and, entry by entry, a fixed
sample of that first compressed gradient (``g0_dist``, each leaf's
relative distance, averaged over the leaves).  The norms hardly see the
precision: an error of random sign moves a norm to second order only.
The entries do: each dither level that an error pushes across its rounding
threshold moves its entry by a whole level, so ``g0_dist`` grows as the
square root of the gradient's error, and so it tells the program's
precision (``compute_precision`` in the json) from a lower one.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.seeds import base_key

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
DITHER_SEED = 29        # dl_flecs: key0 = fold_in(key(29), step)
WIRE_LEVEL_SUM_MAX = 2047   # level sums stay exact in the f16 psum
CE_BLOCK = 1024             # reference loss: tokens per block
SSD_HEAD_GROUP = 8          # reference SSD: heads per [heads, S, S] block
SAMPLE_ENTRIES = 1 << 18    # entries of a leaf compared one by one
SAMPLE_SEED = 31            # which entries: fold_in(key(31), leaf)


# ---------------------------------------------------------------------------
# The model configuration, the weights, the batches
# ---------------------------------------------------------------------------

def padded_vocab(c: dict) -> int:
    """Rows of the embedding table: the vocabulary padded up to a multiple
    of ``pad_vocab_size_multiple``, as the published model pads it."""
    mult = c.get("pad_vocab_size_multiple", 1)
    return -(-c["vocab_size"] // mult) * mult


def model_config(c: dict):
    """The registry's ``mamba2-1.3b`` ModelConfig at this file's sizes, with
    its conv bias and embedding multiplier."""
    from repro.configs import get_config
    from repro.configs.base import FFN_NONE, SSM, SSMConfig, uniform_plan
    base = get_config(c["registry_arch"])
    d_inner = c["expand"] * c["d_model"]
    heads = d_inner // c["headdim"]
    return dataclasses.replace(
        base, n_layers=c["n_layer"], d_model=c["d_model"], n_heads=heads,
        n_kv_heads=heads, head_dim=c["headdim"], vocab=padded_vocab(c),
        layer_plan=uniform_plan(c["n_layer"], SSM, FFN_NONE),
        ssm=SSMConfig(d_state=c["d_state"], head_dim=c["headdim"],
                      expand=c["expand"], conv_width=c["d_conv"],
                      chunk=c["chunk_size"], conv_bias=c["conv_bias"]),
        tie_embeddings=c["tie_embeddings"], norm_eps=c["norm_eps"],
        embed_multiplier=c["embed_multiplier"])


def _leaf_init(name: str, key, shape, c: dict):
    """One weight, by its name in the param tree."""
    last = name.rsplit("'", 2)[-2] if "'" in name else name
    if last == "A_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if last == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))            # softplus⁻¹(dt)
    if last == "D_skip":
        return jnp.ones(shape, F32)
    if "norm" in last:
        return jnp.zeros(shape, F32)
    if last.startswith("conv_") and last.endswith("_bias"):
        bound = 1.0 / math.sqrt(c["d_conv"])    # Conv1d's init, fan-in K
        return jax.random.uniform(key, shape, F32, -bound, bound)
    if last.startswith("conv_"):
        return jax.random.normal(key, shape, F32) / math.sqrt(c["d_conv"])
    if last == "embed":
        return jax.random.normal(key, shape, F32) / math.sqrt(shape[-1])
    return jax.random.normal(key, shape, F32) / math.sqrt(shape[-2])


def make_params(key, abstract, c: dict):
    """Weights for the param tree ``abstract`` from ``key`` (the bench's
    generator; program and reference both take their weights from it)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    leaves = [_leaf_init(jax.tree_util.keystr(path), jax.random.fold_in(
        key, i), leaf.shape, c).astype(leaf.dtype)
        for i, (path, leaf) in enumerate(flat)]
    return jax.tree.unflatten(treedef, leaves)


def make_batch(seed: int, step: int, rows: int, seq: int, vocab: int):
    """Step ``step``'s token rows, uniform over the vocabulary."""
    rng = np.random.default_rng([seed, step])
    t = rng.integers(0, vocab, (rows, seq + 1), dtype=np.int32)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


# ---------------------------------------------------------------------------
# Counts
# ---------------------------------------------------------------------------

def flops_per_token(c: dict) -> float:
    """Model FLOPs a training token needs (forward and backward, remat's
    recompute not counted): 6 per weight of every matrix product (the
    layers' projections, their depthwise convolutions and the head; the
    embedding is a lookup), plus 3× the forward FLOPs of the SSD chunk
    contractions per layer: C·Bᵀ within a chunk (2QN), its decay-masked
    product with x (2QHP), the chunk-end states and their read-out (2HPN
    each), with Q the chunk length.  The conv bias adds are not counted."""
    D, N, P = c["d_model"], c["d_state"], c["headdim"]
    d_inner = c["expand"] * D
    H = d_inner // P
    Q = c["chunk_size"]
    per_layer = (D * (2 * d_inner + 2 * N + H) + d_inner * D
                 + c["d_conv"] * (d_inner + 2 * N))
    matmul_params = c["n_layer"] * per_layer + D * padded_vocab(c)
    ssd = 2 * Q * N + 2 * Q * H * P + 4 * H * P * N
    return 6.0 * matmul_params + 3.0 * c["n_layer"] * ssd


# ---------------------------------------------------------------------------
# Plain reference
# ---------------------------------------------------------------------------

def _mm(x, w, cd):
    prec = HIGHEST if cd == F32 else None
    return jnp.einsum("...d,dk->...k", x.astype(cd), w.astype(cd),
                      precision=prec, preferred_element_type=cd)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(x.dtype))


def _conv(x, w):
    """Depthwise causal convolution over the sequence: x [S, C], w [K, C]."""
    K = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], 0)
    return sum(xp[i:i + x.shape[0]] * w[i].astype(x.dtype) for i in range(K))


def _prefix_sums(a):
    """Inclusive prefix sums of a [..., S] along its last axis as float32
    pairs (hi, lo), hi + lo the exact sum to about float32's precision
    squared: a_k less the step hi_k - hi_(k-1) is what rounding dropped at
    k (both differences exact where their terms are within a factor two),
    and lo sums those."""
    hi = jnp.cumsum(a, axis=-1)
    prev = jnp.concatenate([jnp.zeros_like(hi[..., :1]), hi[..., :-1]], -1)
    return hi, jnp.cumsum(a - (hi - prev), axis=-1)


def _segsum(a):
    """a [H, S] -> [H, S, S]: out[h, l, s] = Σ_{k=s+1..l} a[h, k] for
    l >= s, -inf above the diagonal: the difference of two compensated
    prefix sums, rounded once (a masked cumsum over [H, S, S] costs
    O(S²) a column on the TPU)."""
    S = a.shape[-1]
    hi, lo = _prefix_sums(a)
    x = ((hi[..., :, None] - hi[..., None, :])
         + (lo[..., :, None] - lo[..., None, :]))
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), x, -jnp.inf)


def ssd_reference(xh, dt, A, Bm, Cm, cd):
    """One row's SSM as its semiseparable matrix: xh [S, H, P], dt [S, H],
    A [H] (negative), Bm/Cm [S, N] -> y [S, H, P].  Heads go in groups of
    ``SSD_HEAD_GROUP``, so the [heads, S, S] matrices of a group fit."""
    prec = HIGHEST if cd == F32 else None
    S, H, P = xh.shape
    hg = math.gcd(H, SSD_HEAD_GROUP)
    CB = jnp.einsum("ln,sn->ls", Cm, Bm, precision=prec)

    def group(args):
        x_g, dt_g, A_g = args                     # [S,hg,P], [S,hg], [hg]
        decay = jnp.exp(_segsum((dt_g * A_g).T.astype(F32))).astype(cd)
        M = CB[None] * decay * dt_g.T[:, None, :]            # [hg, l, s]
        return jnp.einsum("hls,shp->lhp", M, x_g, precision=prec)

    split = lambda a: jnp.moveaxis(                           # noqa: E731
        a.reshape(a.shape[:1] + (H // hg, hg) + a.shape[2:]), 1, 0)
    y = jax.lax.map(jax.checkpoint(group),
                    (split(xh), split(dt), A.reshape(H // hg, hg)))
    return jnp.moveaxis(y, 0, 1).reshape(S, H, P)


def _mixer(p, h, c, cd):
    """One Mamba-2 mixer on one row h [S, D]: ``Mamba2``'s conv1d over xBC,
    with its bias, then SiLU."""
    d_inner = c["expand"] * c["d_model"]
    H, P = d_inner // c["headdim"], c["headdim"]

    def xbc(g):
        u = _conv(_mm(h, p[f"in_{g}"], cd), p[f"conv_{g}"])
        return jax.nn.silu(u + p[f"conv_{g}_bias"].astype(cd))

    z = _mm(h, p["in_z"], cd)
    xin, Bm, Cm = xbc("x"), xbc("B"), xbc("C")
    dt = jax.nn.softplus(_mm(h, p["in_dt"], cd) + p["dt_bias"].astype(cd))
    A = -jnp.exp(p["A_log"].astype(cd))
    xh = xin.reshape(-1, H, P)
    y = ssd_reference(xh, dt, A, Bm, Cm, cd)
    y = y + p["D_skip"].astype(cd)[:, None] * xh
    y = _rms(y.reshape(-1, d_inner) * jax.nn.silu(z), p["norm"],
             c["norm_eps"])
    return _mm(y, p["out_proj"], cd)


def reference_loss(params, tokens, labels, c: dict, cd=F32):
    """Mean next-token cross-entropy of the rows ``tokens`` [R, S]."""
    eps = c["norm_eps"]
    (blocks,) = params["blocks"]
    (stack,) = blocks if isinstance(blocks, list) else (blocks,)

    def layer(x, p):
        out = jax.lax.map(lambda xr: _mixer(p["mixer"], _rms(
            xr, p["pre_norm"], eps), c, cd), x)
        return x + out, None

    x = params["embed"].astype(cd)[tokens]        # fed unscaled
    head = params["embed"].T if c["tie_embeddings"] else params["head"]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stack)
    h = _rms(x, params["final_norm"], eps).reshape(-1, x.shape[-1])
    lab = labels.reshape(-1)
    T = h.shape[0]
    blk = math.gcd(T, CE_BLOCK)

    def ce(args):
        hb, lb = args
        logits = _mm(hb, head, cd).astype(F32)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    tot = jax.lax.map(jax.checkpoint(ce), (h.reshape(T // blk, blk, -1),
                                           lab.reshape(T // blk, blk)))
    return jnp.sum(tot) / T


def cgd_reference_step(params, own, mean, tokens, labels, step, *, c, t,
                       n, cd=F32):
    """One FLECS-CGD (m = 0) step of one worker, under ``pmap`` over the
    ``n`` workers (axis ``w``).  Returns (params, own, mean, loss)."""
    loss, grads = jax.value_and_grad(reference_loss)(params, tokens,
                                                     labels, c, cd)
    s = min(float(t["s_levels"]), float(max(1, WIRE_LEVEL_SUM_MAX // n)))
    key0 = jax.random.fold_in(jax.random.key(DITHER_SEED), step)
    g_l, treedef = jax.tree.flatten(grads)
    new_p, new_own, new_mean = [], [], []
    for i, (g, p, ho, hm) in enumerate(zip(
            g_l, jax.tree.leaves(params), jax.tree.leaves(own),
            jax.tree.leaves(mean))):
        delta = g.astype(F32) - ho.astype(F32)
        norm = jax.lax.pmax(jnp.max(jnp.abs(delta)), "w")
        norm = jnp.where(norm == 0, 1.0, norm)
        y = delta / norm * s
        lo = jnp.floor(y)
        u = jax.random.uniform(jax.random.fold_in(key0, i), delta.shape)
        lev = lo + (u < (y - lo))
        scale = norm / s
        q_mean = jax.lax.psum(lev, "w") * scale / n
        g_t = q_mean + hm.astype(F32)
        new_p.append((p.astype(F32) - t["alpha"] * g_t).astype(p.dtype))
        new_own.append((ho.astype(F32) + t["gamma"] * (lev * scale))
                       .astype(ho.dtype))
        new_mean.append((hm.astype(F32) + t["gamma"] * q_mean)
                        .astype(hm.dtype))
    un = functools.partial(jax.tree.unflatten, treedef)
    return un(new_p), un(new_own), un(new_mean), jax.lax.pmean(loss, "w")


def uplink_mbits(abstract, s_levels: float, n: int) -> np.float32:
    """The step's idealised uplink in Mbit, summed leaf by leaf in float32
    as the step sums it: ⌈log2(2s+1)⌉ bits per value."""
    s = min(s_levels, max(1, WIRE_LEVEL_SUM_MAX // n))
    per = np.float32(math.ceil(math.log2(2 * s + 1)))
    tot = np.float32(0.0)
    for leaf in jax.tree.leaves(abstract):
        tot = np.float32(tot + np.float32(per * np.float32(leaf.size)))
    return np.float32(tot / np.float32(1e6))


def leaf_samples(leaves, scale):
    """The same entries of each leaf on both sides, in float32 and times
    ``scale``: all of a leaf up to ``SAMPLE_ENTRIES``, else that many drawn
    from the leaf's index range by ``fold_in(key(SAMPLE_SEED), leaf)``."""
    out = []
    for i, a in enumerate(leaves):
        flat = a.reshape(-1).astype(F32) * scale
        if flat.size > SAMPLE_ENTRIES:
            flat = flat[jax.random.randint(jax.random.fold_in(
                jax.random.key(SAMPLE_SEED), i), (SAMPLE_ENTRIES,), 0,
                flat.size)]
        out.append(flat)
    return out


def sample_dists(prog, ref):
    """Each leaf's ‖prog - ref‖ / ‖ref‖ over its sampled entries."""
    return np.asarray([np.linalg.norm(np.asarray(p, np.float64) - r)
                       / np.linalg.norm(np.asarray(r, np.float64))
                       for p, r in zip(prog, ref)])


def leaf_gaps(prog, ref, floor=None):
    """Worst leaf's |‖prog‖ - ‖ref‖| over the larger of ‖ref‖ and the
    median leaf's ‖ref‖; leaves where ``floor`` (a reference norm per leaf)
    is under a thousandth of its median are left out."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(ref.shape, bool)
    if floor is not None:
        floor = np.asarray(floor, np.float64)
        keep = floor >= 1e-3 * np.median(floor)
    den = np.maximum(ref, np.median(ref))
    return float(np.max((np.abs(prog - ref) / den)[keep]))


# ---------------------------------------------------------------------------
# The cell
# ---------------------------------------------------------------------------

class Cell:
    """One cell: build in ``setup``, time ``call``, ``check`` after."""

    def __init__(self, config, traffic, limits, seed, devices):
        self.c, self.t, self.limits = config, traffic, limits
        self.seed, self.devices = seed, devices
        self.n = int(traffic["data_parallel"])
        self.rows = self.n * int(traffic["batch_per_worker"])
        self.seq = int(traffic["seq_len"])
        self.k_check = int(traffic["check_steps"])
        if len(devices) != self.n:
            raise ValueError(f"{self.n} workers need {self.n} devices")

    def _batch(self, step):
        return make_batch(self.seed, step, self.rows, self.seq,
                          self.c["vocab_size"])

    def setup(self):
        from repro.core.dl_flecs import (FlecsDLConfig, init_shifts,
                                         make_flecs_train_step)
        from repro.launch.sharding import batch_specs, named_shardings
        from repro.models.context import ModelContext
        from repro.models.model import init_params
        t = self.t
        marks = [("start", time.perf_counter())]
        mesh = jax.sharding.Mesh(np.asarray(self.devices).reshape(self.n, 1),
                                 ("data", "model"))
        cfg = model_config(self.c)
        ctx = ModelContext(mesh=mesh, data_axes=("data",), remat=t["remat"])
        self.abstract = jax.eval_shape(
            lambda: init_params(cfg, jax.random.key(0), F32))
        b0 = self._batch(0)
        ba = jax.eval_shape(lambda: jax.tree.map(jnp.asarray, b0))
        pshard = named_shardings(self.abstract, mesh)
        self.bshard = named_shardings(ba, mesh,
                                      batch_specs(ba, mesh, ("data",)))
        step = make_flecs_train_step(cfg, ctx, FlecsDLConfig(
            alpha=t["alpha"], gamma=t["gamma"], s_levels=t["s_levels"],
            m=t["m"]))
        jitted, shifts_abs = step.build(self.abstract, ba, pshard,
                                        self.bshard)
        psh = step.param_shardings(pshard)
        c, abstract = self.c, self.abstract
        self.key = base_key(self.seed)
        gen = jax.jit(lambda k: make_params(k, abstract, c),
                      out_shardings=psh)
        params = jax.block_until_ready(gen(self.key))
        shifts = init_shifts(shifts_abs)
        marks.append(("weights", time.perf_counter()))
        self.compiled = jitted.lower(
            params, shifts, jax.device_put(b0, self.bshard),
            np.int32(0)).compile()
        self.state = (params, shifts)
        marks.append(("step compile", time.perf_counter()))

        def norms(p, ref_key):            # per leaf ‖p - p0‖
            p0 = make_params(ref_key, abstract, c)
            return [jnp.linalg.norm((a - b).ravel())
                    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))]

        def mean_norms(mean):             # per leaf ‖h̄‖
            return [jnp.linalg.norm(a.astype(F32).ravel())
                    for a in jax.tree.leaves(mean)]

        self.i = 0
        self.prog = {"loss": [], "mbits": []}
        for k in range(self.k_check):
            self.call()
            if k == 0:
                mean = self.state[1]["mean"]
                self.prog["g0"] = np.asarray(jax.device_get(jax.jit(
                    mean_norms)(mean))) / t["gamma"]
                self.prog["g0_sample"] = jax.device_get(jax.jit(
                    lambda m: leaf_samples(jax.tree.leaves(m),
                                           1.0 / t["gamma"]))(mean))
        self.prog["change"] = np.asarray(jax.device_get(
            jax.jit(norms)(self.state[0], self.key)))
        marks.append(("checked steps", time.perf_counter()))
        print("mamba2: set-up " + ", ".join(
            f"{name} {b - a:.3f} s" for (_, a), (name, b)
            in zip(marks, marks[1:])), file=sys.stderr, flush=True)

    def call(self):
        with jax.profiler.TraceAnnotation("bench.feed"):
            b = jax.device_put(self._batch(self.i), self.bshard)
        with jax.profiler.TraceAnnotation("bench.step"):
            params, shifts, m = self.compiled(*self.state, b,
                                              np.int32(self.i))
            self.state = (params, shifts)
        with jax.profiler.TraceAnnotation("bench.fetch"):
            loss, mbits = jax.device_get((m["loss"], m["uplink_mbits"]))
        if self.i < self.k_check:
            self.prog["loss"].append(float(loss))
            self.prog["mbits"].append(np.float32(mbits))
        self.i += 1
        return 1.0, bool(np.isfinite(loss))

    def kernels(self):
        return {}

    def counts(self):
        tokens = self.rows * self.seq
        return {"rounds_per_call": 1, "tokens_per_round": tokens,
                "flops_per_round": flops_per_token(self.c) * tokens,
                "chips": self.n}

    def release(self):
        self.compiled = None
        self.state = None

    # -- reference side -----------------------------------------------------
    def reference_run(self, cd=F32):
        """The reference's first ``check_steps`` steps from the same weights
        and batches: losses, per-leaf ‖g̃₀‖ and ‖p_k - p₀‖."""
        c, t, n, abstract = self.c, self.t, self.n, self.abstract
        devs, seed = self.devices, self.seed

        def init(_):
            zeros = lambda: jax.tree.map(                         # noqa
                lambda a: jnp.zeros(a.shape, BF16), abstract)
            return make_params(base_key(seed), abstract, c), zeros(), zeros()

        params, own, mean = jax.pmap(init, devices=devs)(jnp.zeros(n))
        step = jax.pmap(functools.partial(cgd_reference_step, c=c, t=t,
                                          n=n, cd=cd),
                        axis_name="w", devices=devs,
                        in_axes=(0, 0, 0, 0, 0, None),
                        donate_argnums=(0, 1, 2))
        losses, g0 = [], None
        for k in range(self.k_check):
            b = self._batch(k)
            shard = lambda a: a.reshape((n, -1) + a.shape[1:])  # noqa
            params, own, mean, loss = step(params, own, mean,
                                           shard(b["tokens"]),
                                           shard(b["labels"]), k)
            losses.append(float(loss[0]))
            if k == 0:
                g0 = np.asarray([float(jnp.linalg.norm(
                    a[0].astype(F32).ravel())) for a in jax.tree.leaves(
                        mean)]) / t["gamma"]
                g0_sample = jax.device_get(jax.jit(
                    lambda m: leaf_samples([a[0] for a in jax.tree.leaves(
                        m)], 1.0 / t["gamma"]))(mean))
        p0 = jax.jit(lambda k: make_params(k, abstract, c))(self.key)
        change = np.asarray([float(jnp.linalg.norm((a[0] - b).ravel()))
                             for a, b in zip(jax.tree.leaves(params),
                                             jax.tree.leaves(p0))])
        return {"loss": losses, "g0": g0, "g0_sample": g0_sample,
                "change": change}

    def readings(self, which: str = "program") -> dict:
        """Every candidate number of the program's first steps or (the
        control) of the reference in bfloat16 put in its place, against
        the float32 reference.  The program is freed first: its state
        and its loaded step leave no room for the reference."""
        self.release()
        ref = self.reference_run(F32)
        got = self.prog if which == "program" else self.reference_run(BF16)
        loss_p = np.asarray(got["loss"], np.float64)
        loss_r = np.asarray(ref["loss"], np.float64)
        out = {"loss_rel": float(np.max(np.abs(loss_p - loss_r)
                                        / np.abs(loss_r))),
               "g0_gap": leaf_gaps(got["g0"], ref["g0"]),
               "change_gap": leaf_gaps(got["change"], ref["change"],
                                       floor=ref["g0"]),
               "g0_dist": float(np.mean(sample_dists(got["g0_sample"],
                                                     ref["g0_sample"])))}
        if which == "program":
            want = uplink_mbits(self.abstract, self.t["s_levels"], self.n)
            out["uplink_gap"] = float(max(abs(np.float64(m) - np.float64(
                want)) for m in self.prog["mbits"]))
        return out

    def check(self):
        got = self.readings()
        return [(k, got[k], float(v)) for k, v in self.limits.items()]
