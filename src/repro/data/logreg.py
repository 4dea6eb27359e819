"""Synthetic heterogeneous federated logistic-regression shards.

The paper's experiments use LIBSVM datasets (a9a d=123, gisette d=5000,
real-sim d=20958).  Those files are not available offline, so we generate
synthetic binary-classification shards with MATCHING dimensionalities and
controllable heterogeneity: each worker draws features from its own
Gaussian (mean shifted per worker — ζ² > 0 in Assumption 5) and labels from
a shared ground-truth weight vector with label noise.

Loss (paper §5):  F(w) = (1/n) Σ_i (1/r) Σ_j log(1+exp(-b_ij a_ij^T w))
                  + (μ/2)||w||².
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.numerics import einsum, matmul

PAPER_DIMS = {"a9a": 123, "gisette": 5000, "real-sim": 20958}


@dataclasses.dataclass(frozen=True)
class FederatedLogReg:
    A: jnp.ndarray            # [n, r, d] features per worker
    b: jnp.ndarray            # [n, r]   labels in {-1, +1}
    mu: float                 # L2 regularization

    @property
    def n_workers(self):
        return self.A.shape[0]

    @property
    def d(self):
        return self.A.shape[2]

    # ---- objective ------------------------------------------------------
    def local_loss(self, w, i):
        z = self.b[i] * matmul(self.A[i], w)
        return jnp.mean(jnp.logaddexp(0.0, -z)) + 0.5 * self.mu * matmul(w, w)

    def global_loss(self, w):
        z = self.b * einsum("nrd,d->nr", self.A, w)
        return jnp.mean(jnp.logaddexp(0.0, -z)) + 0.5 * self.mu * matmul(w, w)

    def global_grad(self, w):
        return jax.grad(self.global_loss)(w)

    def solve(self, lr: float = 2.0, iters: int = 4000, w0=None):
        """Full-batch GD to (near-)optimum as ONE compiled fori_loop program.

        Replaces the Python reference-solution loops the tests used to run
        at import time (thousands of device dispatches); returns w*.
        """
        w = jnp.zeros(self.d) if w0 is None else w0
        return jax.lax.fori_loop(
            0, iters, lambda _, wk: wk - lr * self.global_grad(wk), w)

    def metrics(self, w):
        """Per-iteration trace entries for ``driver.run_experiment(record=)``:
        global objective and squared gradient norm, computed inside the scan
        so trajectory recording never re-enters the host."""
        return {"F": self.global_loss(w),
                "grad_sq": jnp.sum(jnp.square(self.global_grad(w)))}

    # ---- worker oracles (optionally stochastic) ---------------------------
    def make_oracles(self, batch: int = 0):
        """Returns (local_grad(w, i, key), local_hvp(w, S, i, key)).
        batch=0 => full local gradients (deterministic); batch=B => minibatch
        sampling (the stochastic setting of Theorems 4/5)."""

        def pick(i, key):
            if batch:
                idx = jax.random.randint(key, (batch,), 0, self.A.shape[1])
                return self.A[i][idx], self.b[i][idx]
            return self.A[i], self.b[i]

        def loss(w, Ai, bi):
            z = bi * matmul(Ai, w)
            return (jnp.mean(jnp.logaddexp(0.0, -z))
                    + 0.5 * self.mu * matmul(w, w))

        def local_grad(w, i, key):
            Ai, bi = pick(i, key)
            return jax.grad(loss)(w, Ai, bi)

        def local_hvp(w, S, i, key):
            Ai, bi = pick(i, key)
            g = lambda w_: jax.grad(loss)(w_, Ai, bi)
            return jax.vmap(lambda v: jax.jvp(g, (w,), (v,))[1],
                            in_axes=1, out_axes=1)(S)

        return local_grad, local_hvp


@dataclasses.dataclass(frozen=True)
class VirtualLogReg:
    """Population-scale federated logreg: shards are GENERATED, not stored.

    ``FederatedLogReg`` materializes [n, r, d] feature tensors — 3 GB at
    n=100k, d=123, r=64 — which caps how large a registered population the
    cohort/sharded engines can be driven against.  Here a client's shard is
    a pure function of ``fold_in(key(seed), client_id)``, re-derived inside
    the traced oracles each time the client is sampled: storage is O(d)
    (the shared ground-truth weights) regardless of the population, per-
    round compute is O(cohort · r · d), and the same client always sees the
    same data (the statistical model matches :func:`make_problem` — per-
    client Gaussian feature shift, shared w*, label noise).

    Metrics come from a fixed stratified PROBE of ``probe_clients`` clients
    (one per contiguous stratum, mirroring ``driver.cohort_indices``'
    strata): the exact population objective is an O(N·r·d) reduction per
    recorded round, so the trace reports the probe objective — an unbiased,
    N-independent estimate sufficient for the convergence curves the
    scaling benchmark records.
    """
    n_workers: int            # registered population N
    d: int
    r: int                    # samples per client shard
    mu: float
    heterogeneity: float
    label_noise: float
    seed: int
    probe_clients: int
    w_true: jnp.ndarray       # [d] shared ground truth

    def _shard(self, i):
        """(A_i [r, d], b_i [r]) for a (possibly traced) client id."""
        ki = jax.random.fold_in(jax.random.key(self.seed), i)
        k_a, k_s, k_b, k_f = jax.random.split(ki, 4)
        inv = 1.0 / np.sqrt(self.d)
        shift = (jax.random.normal(k_s, (self.d,))
                 * self.heterogeneity * inv)
        A = jax.random.normal(k_a, (self.r, self.d)) * inv + shift
        p = jax.nn.sigmoid(matmul(A, self.w_true))
        b = jnp.where(jax.random.uniform(k_b, (self.r,)) < p, 1.0, -1.0)
        flip = jax.random.uniform(k_f, (self.r,)) < self.label_noise
        return A, jnp.where(flip, -b, b)

    def _loss(self, w, Ai, bi):
        z = bi * matmul(Ai, w)
        return jnp.mean(jnp.logaddexp(0.0, -z)) + 0.5 * self.mu * matmul(w, w)

    def local_loss(self, w, i):
        return self._loss(w, *self._shard(i))

    @property
    def probe_ids(self):
        """One client per contiguous stratum — fixed across rounds."""
        return jnp.arange(self.probe_clients) * (self.n_workers
                                                 // self.probe_clients)

    def probe_loss(self, w):
        losses = jax.vmap(lambda i: self.local_loss(w, i))(self.probe_ids)
        return jnp.mean(losses)

    def metrics(self, w):
        """Probe-objective trace entries (same keys as ``FederatedLogReg.
        metrics``, so recorders/goldens share a schema)."""
        return {"F": self.probe_loss(w),
                "grad_sq": jnp.sum(jnp.square(
                    jax.grad(self.probe_loss)(w)))}

    def make_oracles(self, batch: int = 0):
        """(local_grad(w, i, key), local_hvp(w, S, i, key)) — the shard is
        re-generated from the client id inside the trace; the ``key``
        argument is accepted for interface parity and unused (full local
        gradients only)."""
        if batch:
            raise ValueError(
                "VirtualLogReg generates full shards per sampled client; "
                "minibatching within a virtual shard is not supported")

        def local_grad(w, i, key):
            Ai, bi = self._shard(i)
            return jax.grad(self._loss)(w, Ai, bi)

        def local_hvp(w, S, i, key):
            Ai, bi = self._shard(i)
            g = lambda w_: jax.grad(self._loss)(w_, Ai, bi)  # noqa: E731
            return jax.vmap(lambda v: jax.jvp(g, (w,), (v,))[1],
                            in_axes=1, out_axes=1)(S)

        return local_grad, local_hvp


def make_virtual_problem(d: int = 24, n_total: int = 100_000, r: int = 16,
                         mu: float = 1e-3, heterogeneity: float = 1.0,
                         label_noise: float = 0.05, seed: int = 0,
                         probe_clients: int = 16) -> VirtualLogReg:
    """Population-scale problem factory (see :class:`VirtualLogReg`)."""
    if not 1 <= probe_clients <= n_total:
        raise ValueError(
            f"probe_clients={probe_clients} must be in [1, {n_total}]")
    rng = np.random.default_rng(seed)
    w_true = jnp.asarray(rng.normal(size=d) / np.sqrt(d), jnp.float32)
    return VirtualLogReg(n_total, d, r, mu, heterogeneity, label_noise,
                         seed, probe_clients, w_true)


def make_problem(d: int = 123, n_workers: int = 20, r: int = 64,
                 mu: float = 1e-3, heterogeneity: float = 1.0,
                 label_noise: float = 0.05, seed: int = 0) -> FederatedLogReg:
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=d) / np.sqrt(d)
    shift = rng.normal(size=(n_workers, d)) * heterogeneity / np.sqrt(d)
    A = rng.normal(size=(n_workers, r, d)) / np.sqrt(d) + shift[:, None, :]
    logits = A @ w_true
    p = 1.0 / (1.0 + np.exp(-logits))
    b = np.where(rng.uniform(size=p.shape) < p, 1.0, -1.0)
    flip = rng.uniform(size=b.shape) < label_noise
    b = np.where(flip, -b, b)
    return FederatedLogReg(jnp.asarray(A, jnp.float32),
                           jnp.asarray(b, jnp.float32), mu)
