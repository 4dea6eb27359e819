"""Cross-silo federation on L2-regularised logistic regression.

Sizes are in ``xsilo-gisette-n20.json`` (LIBSVM gisette's shape over 20
clients, drawn from the seed); a traffic file picks the
method (``flecs_cgd`` or ``diana``), its grid and the rounds per call.

The timed path is the federated engine's plan program: the method's
sweep step (``api.get_method(...).sweep_step``) scanned by
``driver.sweep_program`` over ``rounds_per_call`` rounds, vmapped over the
hyperparameter grid, with the objective recorded every round — what
``api.run_plan`` compiles for a one-run plan.  One difference: the
problem's data are arguments of the compiled program.  ``run_plan`` closes
over them, so they are embedded as constants, and every seed would be a
new program and a new compile.

This file also holds the data generator (the draws of
``repro.data.logreg.make_problem``), the per-round FLOP and byte counts,
and the plain reference: Algorithm 1 of the FLECS-CGD paper and DIANA,
written out in ``jax.numpy`` at ``HIGHEST`` precision, drawing the same
random numbers from the same keys as the engine (the sketch from
``fold_in(key(17), k)``, each client's compressor uniforms from its row
of ``split(k_q, n)``).  It imports nothing of ``repro.core``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.seeds import base_key

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
SKETCH_SEED = 17           # the engine draws S_k from fold_in(key(17), k)
OMEGA_LO, OMEGA_HI = 1e-5, 1e8      # FlecsConfig's omega, Omega
PINV_RCOND = 1e-10


def make_data(seed: int, n: int, r: int, d: int, heterogeneity: float,
              label_noise: float):
    """The draws of ``repro.data.logreg.make_problem``: per-client
    Gaussian features shifted per client, labels from a shared w* with
    ``label_noise`` flips.  Returns (A [n, r, d], b [n, r]) float32."""
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=d) / np.sqrt(d)
    shift = rng.normal(size=(n, d)) * heterogeneity / np.sqrt(d)
    A = rng.normal(size=(n, r, d)) / np.sqrt(d) + shift[:, None, :]
    p = 1.0 / (1.0 + np.exp(-(A @ w_true)))
    b = np.where(rng.uniform(size=p.shape) < p, 1.0, -1.0)
    flip = rng.uniform(size=b.shape) < label_noise
    b = np.where(flip, -b, b)
    return A.astype(np.float32), b.astype(np.float32)


def make_keys(key, G: int, T: int):
    """[G, T] round keys: point g's round t takes ``split(split(key, G)[g],
    T)[t]``, the stream the engine's sweep gives a one-run plan.  The
    harness makes them; program and reference both take them as inputs."""
    return jax.vmap(lambda k: jax.random.split(k, T))(
        jax.random.split(key, G))


# ---------------------------------------------------------------------------
# Counts from shapes
# ---------------------------------------------------------------------------

def round_counts(method: str, G: int, n: int, r: int, d: int, m: int):
    """Least FLOPs and HBM bytes of one plan round (all G grid points).

    Data: the gradient and the m Hessian-vector products need two passes
    over A (A·[w, S], then Aᵀ·[...]); recording F and |∇F|² needs two
    more.  FLECS-CGD's curvature state B [n, d, d] is read for B·S, and
    read again and written by the update B⁺ = (1-β)B + βỸM†Ỹᵀ, which
    depends on B·S: three passes.  DIANA has no B."""
    data = 4 * n * r * d * 4
    flops = 4 * n * r * d + 6 * n * r * d
    nbytes = data
    if method == "flecs_cgd":
        nbytes += 3 * n * d * d * 4
        flops += (4 * n * r * d * m + 2 * n * d * m * m      # HVPs, M
                  + 2 * n * d * d * m                       # B·S
                  + 2 * n * d * m * m + 2 * n * d * d * m   # ỸM†Ỹᵀ
                  + 3 * n * d * d)                          # B update
    return {"flops": G * flops, "bytes": G * nbytes}


def kernel_elements(method: str, G: int, n: int, d: int, m: int):
    """Elements the fused compressor kernels take per round.  The
    compressor family is a traced grid axis, so under the grid's vmap the
    engine's ``lax.switch`` runs every branch at every point: both the
    dither and the top-k kernel see every message."""
    per_point = n * d + (n * d * m if method == "flecs_cgd" else 0)
    return {"dither": G * per_point, "topk": G * per_point}


#: Bytes per element a fused kernel must move: dither reads x and its
#: uniforms and writes Q(x); top-k reads x and writes its output.
KERNEL_BYTES_PER_ELEMENT = {"dither": 12, "topk": 8}


# ---------------------------------------------------------------------------
# Plain reference
# ---------------------------------------------------------------------------

def _split_bf16(a):
    hi = a.astype(BF16).astype(F32)
    return hi, (a - hi).astype(BF16).astype(F32)


def matmul(a, b, precision: str):
    """a @ b in float32 (``highest``), or as three bfloat16 passes
    (``high``: hi·hi + hi·lo + lo·hi, what a TPU's HIGH precision does),
    spelled out so the lower precision means the same on every backend."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if precision != "high":
        raise ValueError(precision)
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    mm = lambda x, y: jnp.matmul(x, y, precision=HIGHEST)   # noqa: E731
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def dither(key, x, s):
    """Random dithering with s levels of the ∞-norm: each |x_j|/‖x‖∞·s is
    rounded up with probability equal to its fractional part."""
    norm = jnp.max(jnp.abs(x))
    norm = jnp.where(norm == 0, 1.0, norm)
    y = jnp.abs(x) / norm * s
    lo = jnp.floor(y)
    level = lo + (jax.random.uniform(key, x.shape) < (y - lo))
    return jnp.sign(x) * level * norm / s


def topk(x, frac):
    """Keep the ⌈frac·size⌉ entries of largest magnitude (ties: lower
    index), zero the rest."""
    flat = x.reshape(-1)
    k = min(max(math.ceil(np.float32(frac) * np.float32(flat.size)), 1),
            flat.size)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    return jnp.zeros_like(flat).at[idx].set(flat[idx]).reshape(x.shape)


def compressor(name: str):
    """(Q(key, x), bits of compressing a size-``d`` message)."""
    if name.startswith("dither"):
        s = float(name[len("dither"):])
        bits = lambda size: math.ceil(math.log2(2 * s + 1)) * size  # noqa
        return (lambda key, x: dither(key, x, s)), bits
    if name.startswith("topk"):
        frac = float(name[len("topk"):])
        bits = lambda size: (min(max(math.ceil(np.float32(frac)            # noqa
                                               * np.float32(size)), 1), size)
                             * (32 + math.ceil(math.log2(size))))
        return (lambda key, x: topk(x, frac)), bits
    raise ValueError(name)


def _objective(A, b, mu, w, precision):
    """F(w) and ∇F(w) over all clients."""
    n, r, d = A.shape
    z = b * matmul(A.reshape(n * r, d), w, precision).reshape(n, r)
    F = jnp.mean(jnp.logaddexp(0.0, -z)) + 0.5 * mu * jnp.dot(
        w, w, precision=HIGHEST)
    coef = (-b * jax.nn.sigmoid(-z)).reshape(n * r) / (n * r)
    grad = matmul(coef, A.reshape(n * r, d), precision) + mu * w
    return F, grad


def _client_oracles(A_i, b_i, mu, w, S, precision):
    """Client i's gradient g_i and sketched Hessian Y_i = ∇²f_i(w) S."""
    r = A_i.shape[0]
    z = b_i * matmul(A_i, w, precision)
    g = matmul(-b_i * jax.nn.sigmoid(-z), A_i, precision) / r + mu * w
    curv = jax.nn.sigmoid(z) * jax.nn.sigmoid(-z)
    AS = matmul(A_i, S, precision)
    Y = matmul(A_i.T, curv[:, None] * AS, precision) / r + mu * S
    return g, Y


def _sym(a):
    return 0.5 * (a + a.T)


def _fedsonia(Yt, M, g, rho, precision):
    """Algorithm 5: truncated inverse of Ỹ M† Ỹᵀ on span(Ỹ), step ρ on
    the complement."""
    Q, R = jnp.linalg.qr(Yt)
    core = matmul(matmul(R, jnp.linalg.pinv(M, rcond=PINV_RCOND),
                         precision), R.T, precision)
    lam, V = jnp.linalg.eigh(_sym(core))
    a = jnp.abs(lam)
    lam_t = jnp.where(a >= OMEGA_LO, jnp.minimum(a, OMEGA_HI), OMEGA_HI)
    Vq = matmul(Q, V, precision)
    coef = matmul(Vq.T, g, precision)
    g_perp = g - matmul(Vq, coef, precision)
    return -matmul(Vq, coef / lam_t, precision) - rho * g_perp


def flecs_cgd_reference(A, b, mu, keys, *, m, alpha, grad_q, hess_q,
                        precision="highest", gamma=1.0, beta=1.0):
    """Algorithm 1 (FLECS-CGD, direct update, FedSONIA) at one grid point,
    one round per key of ``keys`` [T]; full participation, B⁰ = 0,
    h⁰ = 0, w⁰ = 0.  Returns the final (w, h, B, bits) and the per-round
    F and |∇F|² after each update."""
    n, r, d = A.shape
    (gq, gbits), (hq, hbits) = compressor(grad_q), compressor(hess_q)
    price = np.float32(gbits(d) + hbits(d * m) + 32 * m * m)
    rho = 1.0 / OMEGA_HI

    def round_(carry, key):
        w, h, B, bits, k = carry
        S = jax.random.rademacher(
            jax.random.fold_in(jax.random.key(SKETCH_SEED), k), (d, m),
            F32) / jnp.sqrt(F32(m))
        _, _, k_q, k_c, _ = jax.random.split(key, 5)
        kq, kc = jax.random.split(k_q, n), jax.random.split(k_c, n)

        def client(A_i, b_i, h_i, B_i, kq_i, kc_i):
            g, Y = _client_oracles(A_i, b_i, mu, w, S, precision)
            M = matmul(S.T, Y, precision)
            c = gq(kq_i, g - h_i)
            BS = matmul(B_i, S, precision)
            Yt = hq(kc_i, Y - BS) + BS
            Bt = matmul(matmul(Yt, jnp.linalg.pinv(M, rcond=PINV_RCOND),
                               precision), Yt.T, precision)
            return c, Yt, M, _sym((1.0 - beta) * B_i + beta * Bt)

        # one client at a time: only one [d, d] update is live at once
        c, Yt, M, B_new = jax.lax.map(lambda a: client(*a),
                                      (A, b, h, B, kq, kc))
        g_t = jnp.mean(c + h, axis=0)
        p = _fedsonia(jnp.mean(Yt, axis=0), jnp.mean(M, axis=0), g_t, rho,
                      precision)
        w_new = w + alpha * p
        F, grad = _objective(A, b, mu, w_new, precision)
        carry = (w_new, h + gamma * c, B_new, bits + price, k + 1)
        return carry, (F, jnp.dot(grad, grad, precision=HIGHEST))

    init = (jnp.zeros(d, F32), jnp.zeros((n, d), F32),
            jnp.zeros((n, d, d), F32), jnp.zeros((n,), F32),
            jnp.zeros((), jnp.int32))
    (w, h, B, bits, _), (F, gsq) = jax.lax.scan(round_, init, keys)
    return {"w": w, "h": h, "B": B, "bits": bits, "F": F, "grad_sq": gsq}


def diana_reference(A, b, mu, keys, *, alpha, gamma, q,
                    precision="highest"):
    """DIANA at one grid point: each client sends Q(g_i - h_i), the
    server steps along mean(Q(g_i - h_i) + h_i), every h_i moves by
    γ·Q(g_i - h_i)."""
    n, r, d = A.shape
    quant, qbits = compressor(q)
    price = np.float32(qbits(d))

    def round_(carry, key):
        w, h, bits = carry
        _, k_q, _ = jax.random.split(key, 3)
        kq = jax.random.split(k_q, n)

        def client(A_i, b_i, h_i, kq_i):
            g, _ = _client_oracles(A_i, b_i, mu, w, jnp.zeros((d, 1), F32),
                                   precision)
            return quant(kq_i, g - h_i)

        c = jax.vmap(client)(A, b, h, kq)
        w_new = w - alpha * jnp.mean(c + h, axis=0)
        F, grad = _objective(A, b, mu, w_new, precision)
        return ((w_new, h + gamma * c, bits + price),
                (F, jnp.dot(grad, grad, precision=HIGHEST)))

    init = (jnp.zeros(d, F32), jnp.zeros((n, d), F32), jnp.zeros((n,), F32))
    (w, h, bits), (F, gsq) = jax.lax.scan(round_, init, keys)
    return {"w": w, "h": h, "bits": bits, "F": F, "grad_sq": gsq}


def _rel(a, b):
    return jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel())


@jax.jit
def compare(prog: dict, ref: dict):
    """Gaps of one grid point's program outputs from the reference's: for
    F and |∇F|² the worst relative gap over the call's rounds, for w, h
    and B the relative gap of the state after the call, for the bit
    ledgers the largest absolute gap."""
    out = {}
    for name in ("F", "grad_sq"):
        out[f"{name}_rel"] = jnp.max(jnp.abs(prog[name] - ref[name])
                                     / jnp.abs(ref[name]))
    for name in ("w", "h", "B"):
        if name in ref:
            out[f"{name}_rel"] = _rel(prog[name], ref[name])
    out["bits_gap"] = jnp.max(jnp.abs(prog["bits"] - ref["bits"]))
    return out


# ---------------------------------------------------------------------------
# The cell
# ---------------------------------------------------------------------------

class Cell:
    """One cell: build in ``setup``, time ``call``, ``check`` after."""

    def __init__(self, config, traffic, limits, seed, devices):
        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed, self.devices = seed, devices
        self.method = traffic["method"]
        self.T = int(traffic["rounds_per_call"])
        c = config
        self.n, self.r, self.d = c["n_clients"], c["samples_per_client"], c["d"]
        self.mu = c["mu"]
        self.m = traffic.get("m", 0)
        if self.method == "flecs_cgd":
            self.points = list(traffic["grad_family"])
        elif self.method == "diana":
            self.points = [f"dither{int(s)}" for s in traffic["levels"]]
        else:
            raise ValueError(f"unknown method {self.method!r}")
        self.out = None

    # -- program side -------------------------------------------------------
    def _plan(self):
        from repro.core.api import get_method
        t = self.traffic
        spec = get_method(self.method)
        if self.method == "flecs_cgd":
            from repro.core.compressors import stack_specs
            from repro.core.flecs import FlecsConfig
            cfg = FlecsConfig(m=self.m, hessian_update=t["hessian_update"],
                              direction=t["direction"],
                              hess_compressor=t["hess_compressor"],
                              participation=t["participation"],
                              use_kernel=t["use_kernel"])
            hp = spec.grid(alphas=(t["alpha"],),
                           grad_specs=stack_specs(*t["grad_family"]))
        else:
            from repro.optim.baselines import DianaConfig
            cfg = DianaConfig(participation=t["participation"],
                              use_kernel=t["use_kernel"])
            hp = spec.grid(alphas=(t["alpha"],), gammas=(t["gamma"],),
                           levels=tuple(t["levels"]))
        return spec, cfg, hp

    def setup(self):
        from repro.core.driver import sweep_program
        from repro.data.logreg import FederatedLogReg
        c = self.config
        A, b = make_data(self.seed, self.n, self.r, self.d,
                         c["heterogeneity"], c["label_noise"])
        dev = self.devices[0]
        self.A, self.b = jax.device_put(A, dev), jax.device_put(b, dev)
        spec, cfg, hp = self._plan()
        G = len(self.points)
        self.keys = jax.device_put(
            make_keys(jax.random.fold_in(base_key(self.seed), 0), G,
                      self.T), dev)
        state = spec.init(FederatedLogReg(self.A, self.b, self.mu), self.n,
                          cfg)
        mu, T = self.mu, self.T

        def program(A, b, state, hp, keys):
            prob = FederatedLogReg(A, b, mu)
            fn = sweep_program(spec.sweep_step(prob, cfg), T,
                               record=lambda st: prob.metrics(st.w))
            return fn(hp, state, keys)

        self.args = jax.device_put((self.A, self.b, state, hp, self.keys),
                                   dev)
        self.compiled = jax.jit(program).lower(*self.args).compile()
        self.out = jax.block_until_ready(self.compiled(*self.args))

    def call(self):
        self.out = None                  # free the last call's outputs
        self.out = jax.block_until_ready(self.compiled(*self.args))
        return float(self.T), True

    def kernels(self):
        """The Pallas kernels of the plan are the compressor's: the fused
        dither and top-k kernels and their bits-only ledger kernels."""
        return {"compressor": ('custom_call_target="tpu_custom_call"',)}

    def counts(self):
        G = len(self.points)
        per = round_counts(self.method, G, self.n, self.r, self.d, self.m)
        el = kernel_elements(self.method, G, self.n, self.d, self.m)
        per["kernel_bytes"] = sum(KERNEL_BYTES_PER_ELEMENT[k] * v
                                  for k, v in el.items())
        per["rounds_per_call"] = self.T
        return per

    def release(self):
        self.compiled = None
        self.args = None

    # -- reference side -----------------------------------------------------
    def program_point(self, g: int) -> dict:
        st, tr = self.out
        out = {"w": st.w[g], "h": st.h[g], "bits": st.bits_per_node[g],
               "F": tr["F"][g], "grad_sq": tr["grad_sq"][g]}
        if self.method == "flecs_cgd":
            out["B"] = st.B[g]
        return out

    def reference_point(self, g: int, precision: str = "highest") -> dict:
        t = self.traffic
        if self.method == "flecs_cgd":
            fn = jax.jit(lambda A, b, keys: flecs_cgd_reference(
                A, b, self.mu, keys, m=self.m, alpha=t["alpha"],
                grad_q=self.points[g], hess_q=t["hess_compressor"],
                precision=precision))
        else:
            fn = jax.jit(lambda A, b, keys: diana_reference(
                A, b, self.mu, keys, alpha=t["alpha"], gamma=t["gamma"],
                q=self.points[g], precision=precision))
        return fn(self.A, self.b, self.keys[g])

    def readings(self, which: str = "program") -> dict:
        """Every candidate number, worst over the grid points, of the
        program's last timed call or (``control``) of the reference at
        ``high`` precision put in its place, against the reference."""
        worst = {}
        for g in range(len(self.points)):
            ref = self.reference_point(g, "highest")
            if which == "program":
                got = self.program_point(g)
            else:
                got = self.reference_point(g, "high")
            gaps = {k: float(v) for k, v in compare(got, ref).items()}
            del ref, got
            for k, v in gaps.items():
                worst[k] = max(worst.get(k, -math.inf), v)
        return worst

    def check(self):
        got = self.readings()
        return [(k, got[k], float(v)) for k, v in self.limits.items()]
