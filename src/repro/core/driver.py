"""Scan-based federated experiment engine (synchronous + async/buffered).

Every experiment surface in this repo (tests, examples, benchmarks) drives
federated optimization steps of the uniform shape

    step(state, key) -> (state, aux)

Historically each surface ran its own Python ``for`` loop around a jitted
step — hundreds of device dispatches per run and a fresh compile per call
site.  This module replaces all of those loops with **one** compiled
``lax.scan`` program per run:

* :func:`run_experiment` — scan a step for K rounds, stacking per-iteration
  traces (loss, gradient norm, bits/node, …) through the scan ys.  Extra
  quantities (e.g. the global objective) are recorded inside the scan via
  the ``record`` callback, so the host never re-enters the device between
  rounds.  ``record_every=E`` thins the stacked traces *inside* the scan
  (nested scan over E-round blocks), so a 100k-round run materializes
  ``iters // E`` rows instead of ``iters`` on device; ``trace_dtype``
  down-casts float trace entries (e.g. bf16 for long sweeps) while bit
  counters stay in :func:`bits_dtype`.
* :func:`run_sweep` — vmap a whole hyperparameter grid of independent runs
  (step sizes, compressor specs, beta) over the scan, so a Figure-1-style
  comparison grid is a single device program.
* :func:`run_async_sweep` — the same for the async engine: a (tau,
  buffer_k) staleness grid shares one max-delay :class:`MessageBuffer`
  shape and runs as one compiled vmap, with per-point delays traced
  (:func:`sample_delays`) and the step size optionally auto-damped
  (:func:`damped_alpha`).
* :func:`participation_mask` — per-round client-sampling masks (Bernoulli
  or exact-k choice), the partial-participation axis used by
  ``repro.core.flecs`` and ``repro.optim.baselines``.  Workers outside the
  sampled set neither contribute to the server aggregate nor pay
  communication bits that round.  The Bernoulli probability may itself be
  a **traced** sweep axis (see :func:`resolve_participation`), so a
  participation ablation is one vmapped program, not a Python loop.
  Under cohort subsampling (:func:`cohort_indices`) the mask is drawn over
  the COHORT axis only, so a 100k-client registered population never
  materializes an [N] mask per round.
* :func:`run_sharded_sweep` — the device-parallel form of
  :func:`run_sweep`: the worker axis of the scan state is laid over a 1-D
  device mesh (:func:`worker_mesh`) via ``repro.compat.shard_map``, and a
  shard-aware sweep step (``flecs.make_flecs_sharded_sweep_step`` /
  ``baselines.make_diana_sharded_sweep_step``) reconstructs the
  full-federation aggregates with ``lax.all_gather`` + replicated server
  math and reduces integer-exact totals with ``lax.psum`` — bit-for-bit
  equal to the single-device engine on the same key stream
  (tests/subproc/sharded_equiv.py pins this on forced host devices).
* :func:`freeze_on_bit_budget` — the budget-freeze scan mode behind
  plan-level bit budgets: hparams carrying a traced ``bit_budget`` run
  until their cumulative per-node bits reach it, then the whole state
  lax.select-freezes (no more iterate motion, no more bits charged) — so
  methods with *different wire prices* run "to the same budget" inside
  one fixed-length compiled program.  :func:`sweep_program` applies it
  automatically; :func:`iters_for_bit_budget` picks the scan length.

Buffered / asynchronous aggregation (FedBuff-style staleness)
-------------------------------------------------------------
Real federations are asynchronous: a sampled worker's compressed gradient
difference ``c_k^i`` (and Hessian delta) arrives ``tau`` rounds after it was
computed.  The engine models this with two pieces, both carried *inside*
the scan state:

* :class:`StalenessSchedule` — per-worker integer delays sampled each round
  (``fixed`` delay, ``uniform`` in [0, tau], or ``geometric`` stragglers
  capped at tau).
* :class:`MessageBuffer` — a bounded cyclic in-flight store with
  ``tau_max + 1`` slots.  :func:`buffer_send` files a sampled worker's
  message under its arrival round; :func:`buffer_receive` drains the
  messages arriving at the current round.  A worker with a message still in
  flight is *busy* (:func:`buffer_busy`) and is not handed new work — this
  keeps DIANA/FLECS shift algebra exact (a message is always reconstructed
  against the same shift ``h^i`` it was compressed against), and is how
  FedBuff-style systems treat slow clients.

Arrived updates accumulate in a FedBuff aggregation buffer; once ``K``
updates have buffered, the server applies one aggregate step and resets the
buffer.  Communication bits are charged at the *arrival* round.  This is
ONE composition, :func:`make_async_step`, for every method; a method
supplies only its :class:`AsyncHooks`.  With ``tau = 0`` and ``K = n``
(full participation) — or ``K = 1`` under client sampling — it collapses
to the synchronous round (tests/test_async_aggregation.py).

Async quickstart (FLECS-CGD, fixed 2-round delay, half the clients) — a
plan with a ``staleness`` runs every method on this engine::

    plan = ExperimentPlan(
        problem=prob, iters=600, buffer_k=4,
        runs=(MethodRun("flecs_cgd", cfg=FlecsConfig(
            m=2, alpha=0.5, participation=0.5, sampling="choice")),),
        staleness=StalenessSchedule(kind="fixed", tau=2))
    traces = run_plan(plan).traces["flecs_cgd"]
    # traces["bits_per_node"]: bits charged at the round each message
    #                          *arrives*, not when it was computed.
    # traces["staleness_mean"]: average age (rounds) of applied updates.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.compat import shard_map

# ---------------------------------------------------------------------------
# Phase names of a round
# ---------------------------------------------------------------------------
# Each phase of a federated round runs under a ``jax.named_scope`` of these
# names.  A scope is trace-time metadata only: it becomes the ``op_name``
# of the compiled instructions (``metadata={op_name="…/fed.oracle/…"}``)
# and adds no operation, so a profiler trace of the compiled program can
# be read by phase.  Inside ``fed.compress.*`` each compressor family
# opens ``compress.<family>`` (``compressors.COMPRESS_SCOPES``).

SCOPE_ORACLE = "fed.oracle"                 # local gradient, HVPs, M = SᵀY
SCOPE_COMPRESS_GRAD = "fed.compress.grad"   # Q(g - h), the gradient message
SCOPE_COMPRESS_HESS = "fed.compress.hess"   # Q(Y - BS), the Hessian message
SCOPE_CURVATURE = "fed.curvature"           # B·S, the B update, B̄
SCOPE_SERVER = "fed.server"                 # aggregation, direction, w/h/bits
SCOPE_RECORD = "fed.record"                 # the scan's record(state)

#: Every round phase, in round order.
ROUND_SCOPES = (SCOPE_ORACLE, SCOPE_COMPRESS_GRAD, SCOPE_COMPRESS_HESS,
                SCOPE_CURVATURE, SCOPE_SERVER, SCOPE_RECORD)


def bits_dtype():
    """Accumulator dtype for cumulative bit counters.

    float32 loses integer bit counts past 2^24 (reachable in long sweeps on
    the d=20958 problems), so use f64 whenever x64 is enabled.  All
    ``bits_per_node`` fields in ``flecs.py`` / ``baselines.py`` share this.
    """
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def _concrete_nonpositive(p) -> bool:
    """True iff ``p`` holds a concrete value <= 0.  Abstract tracers (whose
    values only exist at run time) report False — their grids are validated
    at construction instead."""
    try:
        return bool(jnp.any(p <= 0))  # repro-lint: disable=R2 -- trace-time probe of a concrete value; a tracer raises and takes the except path
    except jax.errors.ConcretizationTypeError:
        return False


def participation_mask(key, n: int, p=1.0, kind: str = "bernoulli",
                       cohort: Optional[int] = None) -> jnp.ndarray:
    """Per-round client-sampling mask, float32 in {0, 1}.

    p must be > 0; p >= 1 returns all-ones (full participation, key unused).
    kind="bernoulli": each worker participates independently w.p. p (the
        round may sample zero workers; aggregation guards handle that).
        ``p`` may be a **traced** jax scalar — a vmappable sweep axis: the
        mask is the same ``uniform(key) < p`` draw as the static path, so a
        traced-p grid point reproduces the static run mask-for-mask.
        A concrete sub-1 rate whose expected participant count over the
        registered population is below one client per round (``p * n < 1``)
        is rejected: such a run is degenerate — almost every round is a
        no-op — and at population scale it is always a mis-scaled config
        (p meant for n=20 reused at n=100k).
    kind="choice": exactly max(1, round(p*n)) workers, uniformly without
        replacement (FedLab-style client sampling) — every round samples at
        least one worker, even for arbitrarily small p.  The worker count is
        resolved at trace time, so choice has NO traced-p path (rejected).
    cohort: when cohort subsampling is active (:func:`cohort_indices`), the
        number of client rows the round actually materializes.  The mask is
        drawn over the COHORT axis only — shape [cohort], never [n] — so the
        registered population size stays out of per-round memory (analysis
        rule R7); ``n`` remains the full population, used by the degenerate-
        rate guard above.  With ``cohort == n`` every draw matches the dense
        [n] mask bit-for-bit (same key, same shape).
    Both kinds are pure functions of (key, n, p, kind, cohort) and trace
    cleanly under jit/vmap/scan.
    """
    rows = n if cohort is None else int(cohort)  # repro-lint: disable=R2 -- static Python cohort size
    if not isinstance(p, (int, float)):
        try:
            # any CONCRETE scalar (numpy/jax) stays on the static path
            p = float(p)  # repro-lint: disable=R2 -- trace-time probe of a concrete p; a tracer raises and takes the traced path
        except (TypeError, jax.errors.ConcretizationTypeError):
            # traced path: p only exists at run time (a jit argument or a
            # vmapped sweep axis)
            if kind != "bernoulli":
                raise ValueError(
                    f"traced participation p requires kind='bernoulli'; "
                    f"{kind!r} resolves its worker count at trace time")
            p = jnp.asarray(p, jnp.float32)
            if _concrete_nonpositive(p):
                raise ValueError(f"participation p must be > 0, got {p}")
            return (jax.random.uniform(key, (rows,)) < p).astype(jnp.float32)
    if p <= 0:
        raise ValueError(f"participation p must be > 0, got {p}")
    if p >= 1.0:
        return jnp.ones((rows,), jnp.float32)
    if kind == "bernoulli":
        if p * n < 1.0:
            raise ValueError(
                f"degenerate Bernoulli participation: p={p} over a "
                f"population of n={n} expects p*n={p * n:.3g} < 1 "
                f"participating client per round — raise p (or use "
                f"kind='choice', which always samples at least one worker)")
        return (jax.random.uniform(key, (rows,)) < p).astype(jnp.float32)
    if kind == "choice":
        k = max(1, int(round(p * rows)))  # repro-lint: disable=R2 -- p is a Python float here (the static path)
        perm = jax.random.permutation(key, rows)
        return (perm < k).astype(jnp.float32)
    raise ValueError(f"unknown sampling kind: {kind!r}")


def validate_ps(ps) -> None:
    """Grid-construction guard for a traced participation axis: the traced
    path cannot check p at run time (see :func:`_concrete_nonpositive`),
    so every ``ps=`` grid constructor validates here."""
    if ps is not None and any(p <= 0 for p in ps):
        raise ValueError(f"participation ps must be > 0, got {list(ps)}")


def resolve_participation(key, n: int, cfg_p, kind: str, hp_p=None,
                          cohort: Optional[int] = None):
    """The sweep steps' mask entry point: a per-point hparam probability
    ``hp_p`` (possibly TRACED — the participation sweep axis) overrides the
    static config ``cfg_p`` when present.  ``hp_p is None`` keeps the
    pre-axis behavior exactly; 'choice' sampling has no traced form, so
    combining it with an hp_p axis fails loudly instead of silently
    ignoring the axis.  ``cohort`` (cohort-subsampled steps) draws the mask
    over the cohort axis only — see :func:`participation_mask`."""
    if hp_p is None:
        return participation_mask(key, n, cfg_p, kind, cohort)
    if kind != "bernoulli":
        raise ValueError(
            "traced participation p requires sampling='bernoulli'; "
            f"sampling={kind!r} resolves its worker count statically — drop "
            "the p axis or switch the config to bernoulli")
    return participation_mask(key, n, hp_p, "bernoulli", cohort)


def masked_mean(x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Mean of x over the sampled workers (leading axis n).

    mask: [n] in {0,1}.  An all-zero mask yields zeros (no division by 0),
    which downstream direction computations map to a no-op round.
    """
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return masked_sum(x, mask) / denom


def masked_sum(x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Sum of x over the workers with mask == 1 — the numerator of
    :func:`masked_mean`, op-for-op.  The async steps accumulate FedBuff
    buffers with this so a tau=0 run matches the synchronous masked mean
    bit-for-bit."""
    shape = (-1,) + (1,) * (x.ndim - 1)
    return jnp.sum(mask.reshape(shape) * x, axis=0)


# fold_in salt for the async engine's per-round delay key.  Deriving the
# delay key via fold_in (not by widening the step key's split) keeps each
# method's synchronous key split untouched, which is what makes tau=0
# trace-exact (:func:`make_async_step`).
ASYNC_SALT = 0x5A17

# fold_in salt for the cohort steps' per-round selection key.  Like
# ASYNC_SALT, deriving the cohort draw from the participation key via
# fold_in keeps each method's dense key split untouched — a cohort == N
# run therefore consumes the identical mask/worker key stream as the
# dense engine (the exact-equivalence contract tests/test_cohort.py pins).
COHORT_SALT = 0xC040


def cohort_indices(key, n_total: int, cohort: int) -> jnp.ndarray:
    """Stratified distinct-client draw: [cohort] int32 indices into the
    registered population, one uniform draw per contiguous stratum of
    ``n_total // cohort`` clients.

    Distinctness is by construction (one client per stratum), so cohort
    scatter updates (``state.h.at[idx].add``) never collide and stay
    deterministic.  O(cohort) compute and memory: no [n_total] permutation
    or mask is ever materialized (analysis rule R7), which is what lets a
    100k-client registered population run with per-round state independent
    of N.  ``cohort == n_total`` degenerates to the identity ``arange`` —
    a full-population cohort run visits exactly the dense engine's worker
    set every round.
    """
    if not 1 <= cohort <= n_total:
        raise ValueError(
            f"cohort size must be in [1, n_total], got cohort={cohort} "
            f"for population n_total={n_total}")
    if n_total % cohort:
        raise ValueError(
            f"cohort {cohort} must divide the registered population "
            f"{n_total}: stratified sampling draws one client per "
            f"contiguous stratum of n_total // cohort")
    stride = n_total // cohort
    offs = jax.random.randint(key, (cohort,), 0, stride, dtype=jnp.int32)
    return jnp.arange(cohort, dtype=jnp.int32) * stride + offs


# ---------------------------------------------------------------------------
# Staleness: per-worker delay sampling
# ---------------------------------------------------------------------------

def sample_delays(kind: str, key, n: int, tau, q: float = 0.5) -> jnp.ndarray:
    """[n] int32 delays in [0, tau]; ``tau`` may be a *traced* scalar, which
    is what lets ``run_async_sweep`` vmap a (tau, buffer_k) grid through one
    compiled program.  Trace-safe under jit/vmap/scan; at tau=0 every model
    degenerates to all-zero delays, so the tau=0 grid point collapses to the
    synchronous engine regardless of ``kind``."""
    tau = jnp.asarray(tau, jnp.int32)
    if kind == "fixed":
        return jnp.full((n,), tau, jnp.int32)
    if kind == "uniform":
        return jax.random.randint(key, (n,), 0, tau + 1, dtype=jnp.int32)
    if kind == "geometric":
        # q is always a static Python float (StalenessSchedule.q or a maker
        # default); a degenerate q makes log(q) 0/-inf and every delay NaN
        if not 0.0 < q < 1.0:
            raise ValueError(f"geometric q must be in (0, 1), got {q}")
        # geometric: P(delay >= t) = q^t  <=>  floor(log(u) / log(q))
        u = jax.random.uniform(key, (n,), minval=jnp.finfo(jnp.float32).tiny)
        g = jnp.floor(jnp.log(u) / jnp.log(jnp.float32(q)))
        return jnp.minimum(g.astype(jnp.int32), tau)
    raise ValueError(f"unknown staleness kind: {kind!r}")


@dataclasses.dataclass(frozen=True)
class StalenessSchedule:
    """Per-worker integer round delays, sampled fresh each round.

    kind="fixed":     every message arrives exactly ``tau`` rounds after it
                      was computed (tau=0 == synchronous).
    kind="uniform":   delay ~ Uniform{0, …, tau}.
    kind="geometric": delay ~ min(Geometric straggler, tau): each round in
                      flight continues with probability ``q`` (so the mean
                      uncapped delay is q/(1-q) rounds).

    ``tau`` bounds the delay in all three models, which bounds the
    :class:`MessageBuffer` to ``tau + 1`` slots.  Sampling delegates to
    :func:`sample_delays`, the traced-tau form the async sweep vmaps over.
    """
    kind: str = "fixed"
    tau: int = 0
    q: float = 0.5     # geometric only: per-round straggle probability

    def __post_init__(self):
        if self.kind not in ("fixed", "uniform", "geometric"):
            raise ValueError(f"unknown staleness kind: {self.kind!r}")
        if self.tau < 0:
            raise ValueError(f"tau must be >= 0, got {self.tau}")
        if self.kind == "geometric" and not 0.0 < self.q < 1.0:
            raise ValueError(f"geometric q must be in (0, 1), got {self.q}")

    @property
    def max_delay(self) -> int:
        return self.tau

    def sample(self, key, n: int) -> jnp.ndarray:
        """[n] int32 delays in [0, tau]; trace-safe under jit/vmap/scan."""
        return sample_delays(self.kind, key, n, self.tau, self.q)


def damped_alpha(alpha0, sampled_frac, buffer_k, n_workers):
    """Variance-motivated auto-damped step size for async/buffered runs.

        alpha = alpha0 · min(1, p · K / n)

    Rationale (the PR-2 damped-step study, recorded in ROADMAP): a FedBuff
    flush averages K single-worker updates drawn from a p-fraction of the
    federation, so the subset-mean noise entering the server step grows by
    ~ n/(pK) relative to the synchronous full-participation mean over n
    workers — and the *preconditioned* update amplifies that noise along
    low-curvature directions by up to 1/omega_min.  Damping alpha linearly
    in pK/n (rather than the sqrt CLT rule) keeps alpha² × amplified
    variance at its full-participation level under that worst-case
    amplification; empirically it lands in the hand-tuned 0.1–0.2 band
    (p=0.5, K=n/4 → alpha0/8 = 0.125·alpha0).

    All arguments may be traced (``buffer_k`` typically a [G] grid axis),
    so the damped alpha is itself a vmappable sweep axis.
    """
    scale = (jnp.asarray(sampled_frac, jnp.float32)
             * jnp.asarray(buffer_k, jnp.float32) / n_workers)
    return jnp.asarray(alpha0, jnp.float32) * jnp.clip(scale, 0.0, 1.0)


# ---------------------------------------------------------------------------
# Bounded in-flight message buffer (carried through the scan state)
# ---------------------------------------------------------------------------

class MessageBuffer(NamedTuple):
    """Cyclic in-flight store: slot ``r % S`` holds messages arriving at
    round r (S = max_delay + 1 slots, so an arrival round is never
    overwritten before it is drained).

    slots:    pytree of [S, n, ...] arrays (one leaf per message field).
              Cells of workers with ``occupied == 0`` hold stale garbage —
              every consumer must gate on the arrival mask.
    occupied: [S, n] float32 in {0, 1}.
    """
    slots: Any
    occupied: jnp.ndarray


def init_buffer(proto, max_delay: int) -> MessageBuffer:
    """Empty buffer for per-worker message prototype ``proto`` (pytree of
    [n, ...] arrays) with capacity for delays in [0, max_delay]."""
    S = int(max_delay) + 1
    n = jax.tree.leaves(proto)[0].shape[0]
    slots = jax.tree.map(lambda x: jnp.zeros((S,) + x.shape, x.dtype), proto)
    return MessageBuffer(slots, jnp.zeros((S, n), jnp.float32))


def buffer_busy(buf: MessageBuffer) -> jnp.ndarray:
    """[n] {0,1}: worker has a message in flight (not yet drained).  Busy
    workers must not be handed new work — the shift-consistency lock."""
    return jnp.max(buf.occupied, axis=0)


def buffer_send(buf: MessageBuffer, msgs, mask: jnp.ndarray,
                delays: jnp.ndarray, k) -> MessageBuffer:
    """File ``msgs`` (pytree of [n, ...]) computed at round ``k`` by the
    workers with ``mask == 1`` under arrival slot ``(k + delay_i) % S``."""
    S = buf.occupied.shape[0]
    slot = (jnp.asarray(k, jnp.int32) + delays) % S              # [n]
    hit = ((jnp.arange(S)[:, None] == slot[None, :])
           .astype(jnp.float32) * mask[None, :])                 # [S, n]

    def write(cur, msg):
        h = hit.reshape(hit.shape + (1,) * (msg.ndim - 1))
        return cur * (1.0 - h) + h * msg[None].astype(cur.dtype)

    return MessageBuffer(jax.tree.map(write, buf.slots, msgs),
                         buf.occupied * (1.0 - hit) + hit)


def buffer_receive(buf: MessageBuffer, k):
    """Drain round ``k``'s arrivals: returns (buf', msgs, arrived) where
    msgs is a pytree of [n, ...] and arrived is the [n] {0,1} arrival mask.
    Message cells with ``arrived == 0`` are stale — gate every use."""
    S = buf.occupied.shape[0]
    s = jnp.asarray(k, jnp.int32) % S
    msgs = jax.tree.map(lambda a: a[s], buf.slots)
    arrived = buf.occupied[s]
    keep = (jnp.arange(S) != s).astype(jnp.float32)[:, None]     # [S, 1]
    return MessageBuffer(buf.slots, buf.occupied * keep), msgs, arrived


def fedbuff_accumulate(acc, acc_n, contributions, arrived, buffer_k: int):
    """One round of FedBuff server bookkeeping (:func:`make_async_step`).

    acc:           pytree of running sums since the last flush.
    contributions: matching pytree of per-worker [n, ...] values; rows with
                   ``arrived == 0`` are ignored.
    Returns (acc', acc_n', means, flush, reset): the updated sums and
    count, the buffered mean pytree (sum / max(count, 1) — the synchronous
    ``masked_mean`` algebra, so tau=0 stays trace-exact), the scalar bool
    "count reached buffer_k", and ``reset(tree)``, which zeroes a pytree on
    flush (apply it to acc'/acc_n' when building the next state).
    """
    acc = jax.tree.map(lambda a, x: a + masked_sum(x, arrived), acc,
                       contributions)
    acc_n = acc_n + jnp.sum(arrived)
    flush = acc_n >= buffer_k
    denom = jnp.maximum(acc_n, 1.0)
    means = jax.tree.map(lambda a: a / denom, acc)

    def reset(tree):
        return jax.tree.map(
            lambda a: jnp.where(flush, jnp.zeros_like(a), a), tree)

    return acc, acc_n, means, flush, reset


def applied_staleness(k, msg_t, arrived):
    """Mean age (rounds) of this round's applied updates: k - compute-round
    stamp, averaged over the arrival mask (0 when nothing arrived)."""
    return (jnp.sum(arrived * (jnp.float32(k) - msg_t))
            / jnp.maximum(jnp.sum(arrived), 1.0))


# ---------------------------------------------------------------------------
# The buffered-async engine: one FedBuff composition for every method
# ---------------------------------------------------------------------------

class AsyncHParams(NamedTuple):
    """Async sweep point: a method's synchronous hparams plus the staleness
    axes.

      hp       — the method's sync hparams (alpha possibly auto-damped; see
                 :func:`damped_alpha`)
      tau      — int32 delay-model bound (fixed delay / uniform-geometric
                 cap), traced per grid point
      buffer_k — float32 FedBuff flush threshold, traced per grid point
      traffic  — optional traced ``repro.core.traffic`` leaves (rate
                 tables, availability transitions, admission caps)
    """
    hp: Any
    tau: jnp.ndarray
    buffer_k: jnp.ndarray
    traffic: Optional[Any] = None


class AsyncState(NamedTuple):
    """A method's synchronous state plus the in-flight and FedBuff buffers.

    sync:    the method's own state (``FlecsState``, ``DianaState``, …);
             its fields read through, so ``state.w`` and
             ``state.bits_per_node`` work on the async state too.
    buf:     :class:`MessageBuffer` of the method's messages plus ``t``,
             the compute round (staleness accounting, sketch regeneration).
    acc:     FedBuff running sums of the arrival contributions since the
             last flush; ``acc_n`` counts the buffered updates.
    traffic: optional availability chain state (``repro.core.traffic``).
    """
    sync: Any
    buf: MessageBuffer
    acc: Any
    acc_n: jnp.ndarray
    traffic: Optional[Any] = None

    def __getattr__(self, name):
        return getattr(self.sync, name)


class AsyncHooks(NamedTuple):
    """What one method adds to :func:`make_async_step`.

    cfg:     the method's static config (``participation``, ``sampling``).
    n_keys:  how many ways the round key splits — the method's synchronous
             split; the last key draws participation, the others go to
             ``compute``.
    compute: (hp, state, keys) -> work, a no-argument function returning
             the dict of [n, ...] worker messages at the current iterate.
             The engine runs ``work`` only on rounds where someone sends;
             what ``compute`` does before returning it runs every round.
    arrive:  (hp, state, msg, arrived) -> (state', contributions): the
             per-worker server learning and billing of this round's
             arrivals, and the dict of [n, ...] values the FedBuff buffer
             averages (its ``"g"`` entry is the gradient estimate).
    flush:   (hp, state, means, flush) -> (w', aux): the server step from
             the buffered means where ``flush``, and its extra aux entries.
    """
    cfg: Any
    n_keys: int
    compute: Callable
    arrive: Callable
    flush: Callable


def init_async_state(hooks: AsyncHooks, state, hp,
                     max_delay: int) -> AsyncState:
    """The async state around a method's initial synchronous ``state``: an
    empty buffer shaped like its messages and zero FedBuff sums, both from
    ``jax.eval_shape`` of the hooks at the hparams point ``hp``."""
    n = state.bits_per_node.shape[0]

    def shapes():
        keys = jax.random.split(jax.random.key(0), hooks.n_keys)[:-1]
        msg = {**hooks.compute(hp, state, keys)(),
               "t": jnp.zeros((n,), jnp.float32)}
        return msg, hooks.arrive(hp, state, msg, msg["t"])[1]

    proto, contrib = jax.eval_shape(shapes)
    acc = jax.tree.map(lambda c: jnp.zeros(c.shape[1:], c.dtype), contrib)
    return AsyncState(state, init_buffer(proto, max_delay), acc,
                      jnp.zeros((), jnp.float32))


def make_async_step(hooks: AsyncHooks, delay_kind: str = "fixed",
                    q: float = 0.5, traffic=None):
    """Build step(ahp: AsyncHParams, state: AsyncState, key) -> (state,
    aux), the buffered round of the method ``hooks`` describe.  Per round:
    sample clients, excluding busy workers; the sampled workers compute
    at the current iterate (``hooks.compute``); messages are filed under
    arrival round ``k + delay``; this round's arrivals learn and are
    billed (``hooks.arrive``) and join the FedBuff buffer; once
    ``buffer_k`` updates have buffered the server steps from their means
    (``hooks.flush``) and the buffer resets.  tau, buffer_k and every sync
    hparam are traced (:func:`run_async_sweep`).  The method's own key
    split and ``fold_in(key, ASYNC_SALT)`` for the delays keep its
    synchronous key stream.  A ``traffic`` model (``repro.core.traffic``)
    layers arrivals, availability and admission on the same path — only
    admitted arrivals bill or learn; ``traffic=None`` is the plain engine.
    """
    # imported here: repro.core.traffic imports this module
    from repro.core.traffic import admit_arrivals, traffic_send
    cfg = hooks.cfg

    def step(ahp: AsyncHParams, state: AsyncState, key):
        hp, st = ahp.hp, state.sync
        n = st.bits_per_node.shape[0]
        *keys, k_p = jax.random.split(key, hooks.n_keys)   # == sync split
        k_tau = jax.random.fold_in(key, ASYNC_SALT)
        mask = resolve_participation(k_p, n, cfg.participation,
                                     cfg.sampling, hp.p)
        base_delays = sample_delays(delay_kind, k_tau, n, ahp.tau, q)
        if traffic is None:
            send_mask = mask * (1.0 - buffer_busy(state.buf))
            delays, tstate = base_delays, state.traffic
        else:
            send_mask, delays, tstate = traffic_send(
                traffic, ahp.traffic, state.traffic, state.buf, mask, key,
                st.k, ahp.tau, base_delays)

        # cond-gate the worker compute: in a fixed-delay cycle most rounds
        # send nothing (everyone is busy), so skip the n oracle calls on
        # those rounds — the results would be all-masked anyway
        work = hooks.compute(hp, st, keys)
        msgs = jax.lax.cond(
            jnp.any(send_mask > 0),
            lambda _: work(),
            lambda _: {name: jnp.zeros(s.shape[1:], s.dtype)
                       for name, s in state.buf.slots.items() if name != "t"},
            None)
        msgs = {**msgs, "t": jnp.full((n,), st.k, jnp.float32)}
        buf = buffer_send(state.buf, msgs, send_mask, delays, st.k)
        buf, msg, arrived = buffer_receive(buf, st.k)
        arrived = admit_arrivals(traffic, ahp.traffic, arrived, msg["t"],
                                 st.k)

        sync, contrib = hooks.arrive(hp, st, msg, arrived)
        acc, acc_n, means, flush, reset = fedbuff_accumulate(
            state.acc, state.acc_n, contrib, arrived, ahp.buffer_k)
        w, flush_aux = hooks.flush(hp, st, means, flush)
        new = AsyncState(sync._replace(w=w, k=st.k + 1), buf, reset(acc),
                         reset(acc_n), tstate)
        aux = {"g_tilde_norm": jnp.linalg.norm(means["g"]), **flush_aux,
               "n_active": jnp.sum(send_mask),
               "n_arrived": jnp.sum(arrived),
               "buffered": new.acc_n,
               "flushed": flush.astype(jnp.float32),
               "staleness_mean": applied_staleness(st.k, msg["t"], arrived),
               "bits_per_node": new.bits_per_node}
        return new, aux

    return step


# ---------------------------------------------------------------------------
# Scan plumbing
# ---------------------------------------------------------------------------

# Trace keys never down-cast by ``trace_dtype`` (bit ledgers must stay
# exact in bits_dtype() — f32/bf16 lose integer counts).  ``edge_bits`` is
# the hierarchical-aggregation backhaul ledger (repro.core.hierarchy).
TRACE_KEEP_DTYPE: Sequence[str] = ("bits_per_node", "edge_bits")


def _cast_traces(aux, trace_dtype, keep: Sequence[str]):
    if trace_dtype is None:
        return aux

    def cast(v):
        return jax.tree.map(
            lambda a: a.astype(trace_dtype)
            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a, v)

    if isinstance(aux, dict):
        return {k: (v if k in keep else cast(v)) for k, v in aux.items()}
    return cast(aux)


def _scan_body(step: Callable, record: Optional[Callable],
               trace_dtype=None, keep: Sequence[str] = TRACE_KEEP_DTYPE):
    """Shared scan body: one round + optional in-scan trace recording."""
    def body(st, k):
        st, aux = step(st, k)
        if record is not None:
            with jax.named_scope(SCOPE_RECORD):
                aux = {**aux, **record(st)}
        return st, _cast_traces(aux, trace_dtype, keep)
    return body


def _thinned(body: Callable, every: int):
    """Nested-scan wrapper: run ``every`` rounds per outer step, emit only
    the last round's aux — traces shrink by ``every`` on device."""
    def block(st, ks):
        st, aux = jax.lax.scan(body, st, ks)
        return st, jax.tree.map(lambda a: a[-1], aux)
    return block


def run_experiment(step: Callable, state, key, iters: int,
                   record: Optional[Callable] = None,
                   record_every: int = 1, trace_dtype=None):
    """Run ``step`` for ``iters`` rounds in one compiled lax.scan program.

    step:   (state, key) -> (state, aux) — aux is a pytree of per-round
            scalars/vectors; the scan stacks it into [iters, ...] traces.
    record: optional (state) -> dict of extra trace entries evaluated
            *inside* the scan after each round (e.g. global loss), merged
            into aux.  Keys shadow aux keys on collision.
    record_every: thin traces inside the scan — only every E-th round's aux
            is materialized (rows E-1, 2E-1, …), so traces have length
            ``iters // E`` (iters must divide evenly).  The final row is
            always the final state's aux.  Use for 100k-round async runs
            whose dense [iters, ...] traces would not fit on device.
    trace_dtype: optional down-cast dtype (e.g. ``jnp.bfloat16``) for float
            trace entries; keys in :data:`TRACE_KEEP_DTYPE` (the bit
            ledgers) always stay in their accumulator dtype.
    Returns (final_state, traces).
    """
    keys = jax.random.split(key, iters)
    body = _scan_body(step, record, trace_dtype)
    if record_every == 1:
        run = jax.jit(lambda st, ks: jax.lax.scan(body, st, ks))
        return run(state, keys)
    if record_every < 1 or iters % record_every:
        raise ValueError(
            f"record_every={record_every} must divide iters={iters}")
    kb = keys.reshape((iters // record_every, record_every) + keys.shape[1:])
    block = _thinned(body, record_every)
    run = jax.jit(lambda st, ks: jax.lax.scan(block, st, ks))
    return run(state, kb)


def sweep_keys(key, G: int, iters: int):
    """[G, iters] per-point scan key streams: point g steps with
    ``split(split(key, G)[g], iters)`` — the exact stream a standalone
    ``run_experiment(step_g, state, split(key, G)[g], iters)`` would use,
    so a sweep row reproduces the corresponding independent run
    bit-for-bit."""
    return jax.vmap(lambda k: jax.random.split(k, iters))(
        jax.random.split(key, G))


def sweep_program(sweep_step: Callable, iters: int,
                  record: Optional[Callable] = None,
                  record_every: int = 1, trace_dtype=None) -> Callable:
    """The UNJITTED vmapped-sweep program: fn(hparams, state, keys) ->
    (final_states, traces) with keys from :func:`sweep_keys`.

    :func:`run_sweep` is ``jax.jit`` of exactly this; ``repro.core.api``'s
    ``run_plan`` composes several of these (one per structurally distinct
    method segment) into ONE jitted program — the one-compile-per-figure
    invariant.

    Hparams carrying a traced ``bit_budget`` run in the budget-freeze
    scan mode (:func:`freeze_on_bit_budget`); budget-less hparams are
    untouched.
    """
    if record_every != 1 and (record_every < 1 or iters % record_every):
        raise ValueError(
            f"record_every={record_every} must divide iters={iters}")
    sweep_step = freeze_on_bit_budget(sweep_step)

    def one(hp, state, ks):
        body = _scan_body(lambda st, k: sweep_step(hp, st, k), record,
                          trace_dtype)
        if record_every == 1:
            return jax.lax.scan(body, state, ks)
        kb = ks.reshape((iters // record_every, record_every) + ks.shape[1:])
        return jax.lax.scan(_thinned(body, record_every), state, kb)

    return jax.vmap(one, in_axes=(0, None, 0))


def run_sweep(sweep_step: Callable, hparams, state, key, iters: int,
              record: Optional[Callable] = None,
              record_every: int = 1, trace_dtype=None):
    """Vmapped hyperparameter sweep: a grid of runs as ONE device program.

    sweep_step: (hp, state, key) -> (state, aux), e.g. from
                ``repro.core.flecs.make_flecs_sweep_step`` — hp fields
                (step sizes, dithering levels, participation p) are traced,
                so one compiled program serves the whole grid.
    hparams:    pytree whose leaves share a leading grid axis [G, ...]
                (e.g. a ``FlecsHParams`` of [G] arrays).
    state:      a single initial state, shared by every grid point.
    record_every / trace_dtype: as in :func:`run_experiment`.
    Returns (final_states, traces) with leading grid axis [G, ...] /
    [G, iters // record_every, ...].  Per-point key streams come from
    :func:`sweep_keys`, so a sweep row reproduces the corresponding
    independent run bit-for-bit.
    """
    G = jax.tree.leaves(hparams)[0].shape[0]
    fn = sweep_program(sweep_step, iters, record=record,
                       record_every=record_every, trace_dtype=trace_dtype)
    return jax.jit(fn)(hparams, state, sweep_keys(key, G, iters))


# ---------------------------------------------------------------------------
# Sharded sweeps: the worker axis over a device mesh
# ---------------------------------------------------------------------------

def worker_mesh(n_devices: Optional[int] = None,
                axis: str = "workers") -> Mesh:
    """1-D device mesh laying the federation's worker axis over devices.

    ``n_devices=None`` uses every visible device.  CPU CI forces host
    devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    (which must be set before jax imports — see tests/conftest.py's
    subprocess fixture)."""
    devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if not 1 <= n_devices <= len(devices):
        raise ValueError(
            f"n_devices={n_devices} outside [1, {len(devices)}] visible "
            "device(s)")
    return Mesh(np.asarray(devices[:n_devices]), (axis,))


def run_sharded_sweep(sweep_step: Callable, hparams, state, key, iters: int,
                      state_specs, mesh: Optional[Mesh] = None,
                      record: Optional[Callable] = None,
                      record_every: int = 1, trace_dtype=None,
                      axis: str = "workers",
                      worker_traces: Sequence[str] = ("bits_per_node",)):
    """Device-parallel :func:`run_sweep`: worker-axis state over a mesh.

    ``sweep_step`` must be SHARD-AWARE — built by a
    ``make_*_sharded_sweep_step`` factory (``repro.core.flecs`` /
    ``repro.optim.baselines``).  Inside the mesh each device holds one
    contiguous ``[n_local, ...]`` block of the worker-axis state leaves and
    computes its workers' messages against GLOBAL worker ids and the GLOBAL
    per-round key stream (``split(k, n)`` rows, gathered per block), then
    reconstructs the full-federation aggregates with
    ``lax.all_gather(tiled=True)`` and reduces integer-exact totals
    (participation counts, ledger sums) with ``lax.psum``.  The gathered
    arrays and the replicated server math are the same ops on the same
    values as the dense engine, so the result is **bit-for-bit identical**
    to :func:`run_sweep` on the same key stream — exact bit ledgers,
    identical objective traces (tests/subproc/sharded_equiv.py pins this on
    forced host devices; float psum would reassociate the sum, which is why
    the engine gathers and re-reduces instead of psum-ing partial means).
    One caveat bounds the contract: each device must hold at least TWO
    workers.  XLA lowers a batch-1 vmapped oracle as an unbatched dot
    whose reduction order can differ from the batched lowering by ~1 ulp,
    so at ``n_local == 1`` the equality degrades from bitwise to
    tight-tolerance (the server math itself stays exact either way).

    state:       the FULL (unsharded) initial state — worker-axis leaves
                 are laid over the mesh by jit from ``state_specs``.
    state_specs: pytree matching ``state`` whose leaves are the mesh axis
                 name (worker-sharded along dim 0) or ``""`` (replicated) —
                 e.g. ``flecs.sharded_state_specs()``.
    worker_traces: aux/trace keys carrying a trailing per-worker axis
                 (sharded in the output); every other trace is replicated.
    Returns (final_states, traces) exactly like :func:`run_sweep` — same
    shapes, same leading [G] grid axis, fully gathered.
    """
    if mesh is None:
        mesh = worker_mesh(axis=axis)
    n_dev = mesh.shape[axis]
    G = jax.tree.leaves(hparams)[0].shape[0]

    def _local(leaf, s):
        if s == axis:
            if leaf.ndim == 0 or leaf.shape[0] % n_dev:
                raise ValueError(
                    f"worker-sharded state leaf of shape {leaf.shape} does "
                    f"not divide over {n_dev} device(s) on mesh axis "
                    f"{axis!r}")
            return jax.ShapeDtypeStruct(
                (leaf.shape[0] // n_dev,) + leaf.shape[1:], leaf.dtype)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)

    local_state = jax.tree.map(_local, state, state_specs)
    keys = sweep_keys(key, G, iters)
    fn = sweep_program(sweep_step, iters, record=record,
                       record_every=record_every, trace_dtype=trace_dtype)
    # Discover the trace structure at LOCAL shapes with the mesh axis
    # bound, to spec the outputs: per-worker traces ([G, T, n_local] on
    # device) shard on the worker axis, everything else is replicated.
    _, out_shape = jax.make_jaxpr(
        fn, axis_env=[(axis, n_dev)], return_shape=True)(
            hparams, local_state, keys)
    _, trace_shapes = out_shape

    def _trace_spec(path, _leaf):
        name = getattr(path[-1], "key", None) if path else None
        return (PartitionSpec(None, None, axis) if name in worker_traces
                else PartitionSpec())

    trace_specs = jax.tree_util.tree_map_with_path(_trace_spec, trace_shapes)
    in_state = jax.tree.map(
        lambda s: PartitionSpec(axis) if s == axis else PartitionSpec(),
        state_specs)
    out_state = jax.tree.map(
        lambda s: PartitionSpec(None, axis) if s == axis else PartitionSpec(),
        state_specs)
    prog = shard_map(fn, mesh,
                     in_specs=(PartitionSpec(), in_state, PartitionSpec()),
                     out_specs=(out_state, trace_specs))
    return jax.jit(prog)(hparams, state, keys)


def check_buffer_slots(ahp: AsyncHParams, state: AsyncState) -> None:
    """Reject a tau grid whose largest delay does not fit the shared
    buffer: slot indices wrap modulo its size, so an oversized tau would
    silently run as a shorter delay."""
    slots, tau_max = state.buf.occupied.shape[0], int(jnp.max(ahp.tau))
    if tau_max + 1 > slots:
        raise ValueError(
            f"shared MessageBuffer has {slots} slot(s) but the grid "
            f"reaches tau={tau_max}; raise the schedule's max delay "
            f"(plan.staleness.tau, init_async_state's max_delay) to >= "
            f"{tau_max}")


def run_async_sweep(sweep_step: Callable, hparams, state, key, iters: int,
                    record: Optional[Callable] = None,
                    record_every: int = 1, trace_dtype=None):
    """Vmapped async/buffered sweep: a (tau, buffer_k, …) grid of
    :class:`AsyncHParams` through a :func:`make_async_step` step as ONE
    device program.  Every grid point runs in the shared ``state``'s
    max-delay buffer shape (shorter delays leave the later slots unused;
    a per-point shape would make the grid unvmappable), checked by
    :func:`check_buffer_slots`.  Key streams, record_every and trace_dtype
    follow :func:`run_sweep`, so point g reproduces the standalone async
    run with key ``split(key, G)[g]`` bit-for-bit.
    """
    check_buffer_slots(hparams, state)
    return run_sweep(sweep_step, hparams, state, key, iters, record=record,
                     record_every=record_every, trace_dtype=trace_dtype)


# ---------------------------------------------------------------------------
# Bit budgets: the budget-freeze scan mode
# ---------------------------------------------------------------------------

def hparams_bit_budget(hp):
    """The traced per-point bit budget carried by an hparam pytree, or
    None.  Sync hparams carry it as a ``bit_budget`` field;
    :class:`AsyncHParams` carry it on their inner sync ``hp`` — the budget
    gates *arrival-billed* bits the same way."""
    if isinstance(hp, AsyncHParams):
        hp = hp.hp
    return getattr(hp, "bit_budget", None)


# Aux trace keys zeroed on frozen rounds: once the budget is exhausted
# nothing is sent, arrives, flushes, aggregates, or moves — the discarded
# step's diagnostics (gradient/direction norms) must not leak into the
# frozen tail next to the zeroed activity counters.
_FROZEN_ZERO_KEYS: Sequence[str] = ("n_active", "n_arrived", "flushed",
                                    "staleness_mean", "g_tilde_norm",
                                    "dir_norm")


@jax.named_scope(SCOPE_SERVER)
def _freeze(active, new_state, state, aux):
    """The budget gate of :func:`freeze_on_bit_budget`: ``new_state``
    where ``active``, else ``state``; aux ledgers follow the frozen state
    and the activity counters read zero."""
    sel = lambda new, old: jnp.where(active, new, old)         # noqa: E731
    frozen = jax.tree.map(sel, new_state, state)
    if isinstance(aux, dict):
        aux = dict(aux)
        if "bits_per_node" in aux:
            aux["bits_per_node"] = frozen.bits_per_node
        if "edge_bits" in aux and getattr(frozen, "edge_bits",
                                          None) is not None:
            aux["edge_bits"] = frozen.edge_bits
        if "buffered" in aux and hasattr(frozen, "acc_n"):
            aux["buffered"] = frozen.acc_n
        for k in _FROZEN_ZERO_KEYS:
            if k in aux:
                aux[k] = sel(aux[k], jnp.zeros_like(aux[k]))
    return frozen, aux


def freeze_on_bit_budget(sweep_step: Callable) -> Callable:
    """Budget-freeze scan mode: wrap a sweep step so that once a grid
    point's cumulative per-node bits (``max_i state.bits_per_node[i]``)
    reach its traced ``bit_budget``, the ENTIRE state lax.select-freezes
    against the previous round and no further bits are charged.

    Semantics (what the tests pin): with per-round price ``c`` a budget
    ``B`` runs exactly ``iters_for_bit_budget(B, c)`` live rounds — rounds
    step while ``max bits < B`` — and every later round is a frozen no-op,
    so a T-round budget run is the matching truncated run padded with
    bit-stable rows.  Methods with different wire prices therefore run "to
    the same budget" inside ONE fixed-length compiled program: the budget
    is a traced vmappable axis, not a per-method iteration count.

    Applied automatically by :func:`sweep_program`; hparams without a
    budget (``bit_budget is None``, the default everywhere) pass through
    untouched — same ops, same traces, zero overhead.
    """
    def step(hp, state, key):
        budget = hparams_bit_budget(hp)
        if budget is None:
            return sweep_step(hp, state, key)
        bits = getattr(state, "bits_per_node", None)
        if bits is None:
            raise ValueError(
                "bit_budget requires a state carrying a bits_per_node "
                f"ledger, got {type(state).__name__}")
        with jax.named_scope(SCOPE_SERVER):
            active = jnp.max(bits) < budget
        new_state, aux = sweep_step(hp, state, key)
        return _freeze(active, new_state, state, aux)

    return step


def iters_for_bit_budget(budget, bits_per_round) -> int:
    """Upper-bound scan length of a budget run: the smallest round count
    whose cumulative per-node bits reach ``budget``, maxed over a grid.

    ``bits_per_round`` is the spec-aware per-participating-worker price of
    one round (``flecs.hparams_round_bits``, the registry ``round_bits``
    queries, or ``compressors.spec_bits`` directly — dimension-aware for
    top-k).  Both arguments may be [G] arrays (a budget × price grid); the
    bound then covers every point, which is how a whole budget-fair plan
    shares one scan length per structural segment.  A zero or sub-round
    budget yields 1: a scan needs at least one round, and the freeze gate
    (:func:`freeze_on_bit_budget`) holds / charges that round as the
    budget dictates.

    The bound is exact under full participation with synchronous billing
    (every round charges the max-bits worker the full price).  Client
    sampling and async arrival billing stretch the charging cadence —
    ``repro.core.api``'s plan lowering scales the bound by 1/p_min and
    (tau + 1) for those axes.
    """
    budget = np.asarray(budget, dtype=float)
    price = np.asarray(bits_per_round, dtype=float)
    if budget.size == 0 or price.size == 0:
        raise ValueError("empty bit-budget/price grid")
    if not np.all(np.isfinite(budget)):
        raise ValueError(
            f"bit budgets must be finite, got {budget}: an inf/nan budget "
            "has no derivable scan length — pin run.iters explicitly for "
            "unbounded runs instead")
    if np.any(price <= 0) or not np.all(np.isfinite(price)):
        raise ValueError(
            f"bits_per_round must be finite and > 0, got {price}")
    return max(1, int(np.ceil(np.max(budget / price))))
