"""Production traffic simulation: arrival processes, client availability
states, and server-side cohort admission for the async engine.

The async engine (``driver.make_async_step`` over a ``MessageBuffer``)
models staleness with fixed/uniform/geometric per-worker delays.
Real federations see *structured* traffic: bursty arrivals, diurnal load
cycles, clients that flip between available/busy/dropped, and servers that
bound their in-flight work and refuse hopelessly stale updates.  This
module adds those three surfaces as **traced axes** on the existing
buffered machinery — no new engine, no second code path:

* **Arrival processes** (:class:`ArrivalSchedule`): a message sent at
  round ``k`` completes at offset ``t`` with probability
  ``rate_table[(k + t) % P]`` — Poisson thinning of a per-round completion
  process by a piecewise-constant (diurnal) rate profile.  ``kind="poisson"``
  is the single-phase profile (P = 1), ``kind="diurnal"`` a P-phase rate
  table, ``kind="trace"`` replays a committed ``[T, n]`` delay trace, and
  ``kind="schedule"`` defers to the ``StalenessSchedule`` delays the async
  steps already draw.  All draws stay bounded by the traced ``tau`` (the
  ``MessageBuffer`` slot contract), and the rate table rides the hparam
  pytree — a vmappable sweep axis, never a Python-materialized schedule
  (analysis rule R8).
* **Availability states** (:class:`AvailabilityModel`): a small Markov
  chain over {AVAILABLE, BUSY, DROPPED} carried per worker in scan state
  (:class:`TrafficState`), stepped once per round from a traced
  row-stochastic transition matrix.  The chain composes with
  ``driver.resolve_participation``: unavailable clients are masked out of
  the send set, so they are never drawn, never compute, and never bill a
  bit — the availability analog of the cohort-sampling contract.
* **Cohort admission** (:class:`AdmissionPolicy`): layered on the
  buffered send/receive path.  ``max_in_flight`` caps the server's
  concurrent in-flight messages (excess senders wait for a later round);
  ``staleness_cutoff`` discards arrivals older than the cutoff **without
  billing them** — a discarded message frees its worker (the buffer slot
  was drained) but never touches the bit ledger, the shift/Hessian state,
  or the FedBuff accumulator.

Billing semantics (the contract tests/test_traffic.py pins): bits are
charged only to arrivals that SURVIVE admission, at the arrival round.  A
``staleness_cutoff`` of 0 admits exactly the age-0 messages, so at
``tau=0`` (where every message arrives fresh) the admission layer is
bitwise transparent and the async engine still collapses to the
synchronous one — the same contract as the existing tau=0 collapse.  At
``tau > 0`` with a 0 cutoff *everything* is discarded: the iterate never
moves and the ledgers stay exactly zero (the tau=∞-discard edge).

Key streams: traffic draws derive from the step key via ``fold_in`` with
dedicated salts (:data:`ARRIVAL_SALT`, :data:`AVAIL_SALT`), exactly like
``driver.ASYNC_SALT`` — the methods' synchronous splits are untouched, so
a traffic model never perturbs the underlying worker key streams.

Usage — thread a model through a plan (one compiled program, five
methods)::

    from repro.core.api import ExperimentPlan, MethodRun, run_plan
    from repro.core.driver import StalenessSchedule
    from repro.core.traffic import (AdmissionPolicy, ArrivalSchedule,
                                    AvailabilityModel, TrafficModel)

    plan = ExperimentPlan(
        problem=prob,
        runs=tuple(MethodRun(m) for m in
                   ("flecs", "flecs_cgd", "diana", "fednl", "gd")),
        staleness=StalenessSchedule(kind="fixed", tau=4), buffer_k=2.0,
        traffic=TrafficModel(
            arrival=ArrivalSchedule(kind="diurnal",
                                    rates=(0.9, 0.6, 0.2, 0.6)),
            availability=AvailabilityModel(),
            admission=AdmissionPolicy(staleness_cutoff=3.0,
                                      max_in_flight=8.0)))
    result = run_plan(plan)        # ONE compile, traffic axes traced
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.driver import buffer_busy

# fold_in salts for the traffic draws (see driver.ASYNC_SALT for the
# convention): deriving them from the step key via fold_in keeps every
# method's synchronous key split untouched.
ARRIVAL_SALT = 0x7AF1
AVAIL_SALT = 0xAB1E

#: Markov-chain availability states.  Only AVAILABLE clients may be drawn
#: into a round's send set; BUSY models a device doing local work (fast
#: return), DROPPED a churned client (slow return / never).
AVAILABLE, BUSY, DROPPED = 0, 1, 2


# ---------------------------------------------------------------------------
# Static model structure (the dataclasses a plan carries)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ArrivalSchedule:
    """Which arrival process generates per-worker per-round delay draws.

    kind="schedule": defer to the ``StalenessSchedule`` delays the async
        step already samples (``driver.sample_delays``) — the traffic
        model then only contributes availability/admission.
    kind="poisson":  Poisson-thinned completion at a single rate
        ``rates[0]``: a message in flight completes each round with that
        probability (a geometric service time — the discrete-time Poisson
        process), capped at the traced tau.
    kind="diurnal":  the same thinning against a P-phase piecewise-constant
        rate table ``rates``: the completion probability of the round
        ``k + t`` is ``rates[(k + t) % P]`` — load cycles, rush hours,
        nightly lulls.
    kind="trace":    replay a committed ``[T, n]`` integer delay trace:
        round k's per-worker delays are ``trace[k % T]`` clipped to tau —
        byte-reproducible replay of recorded production traffic.

    The rates become the traced ``rate_table`` leaf of
    :class:`TrafficHParams` (a vmappable sweep axis); the trace array is
    static structure (its shape fixes the replay horizon).
    """
    kind: str = "schedule"
    rates: Sequence[float] = (0.5,)
    trace: Any = None

    def __post_init__(self):
        if self.kind not in ("schedule", "poisson", "diurnal", "trace"):
            raise ValueError(f"unknown arrival kind: {self.kind!r}")
        if self.kind == "poisson" and len(self.rates) != 1:
            raise ValueError(
                f"poisson arrivals take a single rate, got {len(self.rates)}"
                " — use kind='diurnal' for a multi-phase rate table")
        if self.kind in ("poisson", "diurnal"):
            if len(self.rates) == 0:
                raise ValueError("arrival rate table must be non-empty")
            if any(not 0.0 < r <= 1.0 for r in self.rates):
                raise ValueError(
                    f"arrival rates must be in (0, 1], got {list(self.rates)}")
        if self.kind == "trace":
            if self.trace is None:
                raise ValueError("kind='trace' requires a [T, n] delay trace")
            t = np.asarray(self.trace)
            if t.ndim != 2 or t.size == 0:
                raise ValueError(
                    f"delay trace must be a non-empty [T, n] array, got "
                    f"shape {t.shape}")
            if np.any(t < 0):
                raise ValueError("delay trace entries must be >= 0")


@dataclasses.dataclass(frozen=True)
class AvailabilityModel:
    """Per-client availability Markov chain over
    (AVAILABLE, BUSY, DROPPED).

    ``transition[s]`` is the row-stochastic distribution of the next state
    given current state s, stepped once per round for every client.  The
    default models a federation where clients are mostly available, briefly
    busy, and occasionally churn with slow re-registration.  The matrix is
    traced (:class:`TrafficHParams` carries it), so an availability sweep
    is a vmappable axis.
    """
    transition: Sequence[Sequence[float]] = ((0.85, 0.10, 0.05),
                                             (0.60, 0.40, 0.00),
                                             (0.10, 0.00, 0.90))

    def __post_init__(self):
        t = np.asarray(self.transition, np.float64)
        if t.ndim != 2 or t.shape[0] != t.shape[1] or t.shape[0] < 2:
            raise ValueError(
                f"transition must be a square (>= 2-state) matrix, got "
                f"shape {t.shape}")
        if np.any(t < 0) or not np.allclose(t.sum(axis=1), 1.0, atol=1e-6):
            raise ValueError(
                "transition rows must be non-negative and sum to 1, got "
                f"{t.tolist()}")


@dataclasses.dataclass(frozen=True)
class AdmissionPolicy:
    """Server-side admission on the buffered path.

    max_in_flight:    cap on concurrent in-flight messages — a round's
                      senders beyond the remaining room (in worker order)
                      are deferred (they simply stay eligible next round).
                      ``inf`` = uncapped.
    staleness_cutoff: arrivals older than this many rounds are DISCARDED —
                      dropped from the arrival mask before billing, state
                      updates, and FedBuff accumulation, so a discarded
                      message costs nothing.  ``inf`` = admit everything;
                      0 admits only fresh (age-0) arrivals.
    Both are traced (:class:`TrafficHParams`), so admission is sweepable.
    """
    staleness_cutoff: float = float("inf")
    max_in_flight: float = float("inf")

    def __post_init__(self):
        if self.staleness_cutoff < 0:
            raise ValueError(
                f"staleness_cutoff must be >= 0, got {self.staleness_cutoff}")
        if self.max_in_flight < 0:
            raise ValueError(
                f"max_in_flight must be >= 0, got {self.max_in_flight}")


@dataclasses.dataclass(frozen=True)
class TrafficModel:
    """The composed traffic structure a plan/step carries (static): which
    arrival process runs, whether clients have availability dynamics, and
    what the server admits.  The traced numbers live in
    :class:`TrafficHParams` (built by :func:`traffic_hparams`)."""
    arrival: ArrivalSchedule = ArrivalSchedule()
    availability: Optional[AvailabilityModel] = None
    admission: Optional[AdmissionPolicy] = None


# ---------------------------------------------------------------------------
# Traced leaves + per-worker scan state
# ---------------------------------------------------------------------------

class TrafficHParams(NamedTuple):
    """The traced point of a :class:`TrafficModel` — scalars/tables or
    [G, ...] sweep-axis arrays riding the async hparam pytree
    (``driver.AsyncHParams.traffic``).

    rate_table:       [P] per-phase completion probabilities (poisson:
                      P = 1; unused by "schedule"/"trace" arrivals).
    avail_transition: [S, S] row-stochastic availability transitions
                      (identity when the model has no availability).
    staleness_cutoff: admission age cutoff in rounds (inf = admit all).
    max_in_flight:    in-flight message cap (inf = uncapped).
    """
    rate_table: jnp.ndarray
    avail_transition: jnp.ndarray
    staleness_cutoff: jnp.ndarray
    max_in_flight: jnp.ndarray


class TrafficState(NamedTuple):
    """Per-worker traffic state carried through the scan: the availability
    chain's current states, [n] int32 (all-AVAILABLE at init)."""
    avail: jnp.ndarray


def traffic_hparams(model: TrafficModel) -> TrafficHParams:
    """The traced hparam point of a model (broadcast over [G] by the plan
    lowering / ``_broadcast``-style helpers)."""
    if model.arrival.kind in ("poisson", "diurnal"):
        table = jnp.asarray(model.arrival.rates, jnp.float32)
    else:
        table = jnp.ones((1,), jnp.float32)
    if model.availability is not None:
        trans = jnp.asarray(model.availability.transition, jnp.float32)
    else:
        trans = jnp.eye(3, dtype=jnp.float32)
    adm = model.admission if model.admission is not None else AdmissionPolicy()
    return TrafficHParams(table, trans,
                          jnp.float32(adm.staleness_cutoff),
                          jnp.float32(adm.max_in_flight))


def init_traffic_state(n_workers: int) -> TrafficState:
    return TrafficState(jnp.zeros((n_workers,), jnp.int32))


# ---------------------------------------------------------------------------
# Arrival draws (traced)
# ---------------------------------------------------------------------------

def thinned_delays(rate_table, key, n: int, k, tau, slots: int):
    """[n] int32 Poisson-thinned delays for messages sent at round ``k``:
    offset t completes with probability ``rate_table[(k + t) % P]``; the
    first completing offset is the delay, capped at the traced ``tau`` (a
    message that completes nowhere within the buffer horizon is charged
    the full tau — the straggler cap, same convention as the geometric
    schedule).  ``slots`` (static) is the buffer's ``max_delay + 1`` slot
    count, the static bound the probe may scan; ``k``, ``tau``, and the
    rate table are all traced, so diurnal phase and rate profile are
    vmappable sweep axes."""
    P = rate_table.shape[0]
    offs = (jnp.asarray(k, jnp.int32)
            + jnp.arange(slots, dtype=jnp.int32)) % P
    r = rate_table[offs]                                       # [slots]
    u = jax.random.uniform(key, (n, slots))
    hit = u < r[None, :]
    first = jnp.argmax(hit, axis=1).astype(jnp.int32)
    tau = jnp.asarray(tau, jnp.int32)
    return jnp.minimum(jnp.where(jnp.any(hit, axis=1), first, tau), tau)


def replay_delays(trace, k, tau):
    """[n] int32 replay of a recorded ``[T, n]`` delay trace at round
    ``k`` (row ``k % T``, clipped to the traced ``tau`` so the buffer
    contract holds even against a trace recorded at a larger horizon)."""
    trace = jnp.asarray(trace, jnp.int32)
    row = trace[jnp.asarray(k, jnp.int32) % trace.shape[0]]
    return jnp.minimum(row, jnp.asarray(tau, jnp.int32))


# ---------------------------------------------------------------------------
# Availability chain (traced)
# ---------------------------------------------------------------------------

def availability_step(avail_transition, avail, key):
    """One Markov step of every client's availability state: gather each
    client's transition row, inverse-CDF sample the next state.  [n] int32
    in, [n] int32 out; the transition matrix is traced."""
    n_states = avail_transition.shape[-1]
    rows = avail_transition[avail]                             # [n, S]
    cum = jnp.cumsum(rows, axis=-1)
    u = jax.random.uniform(key, avail.shape)
    nxt = jnp.sum((u[:, None] >= cum).astype(jnp.int32), axis=-1)
    # float cumsum can land cum[-1] a ulp under 1.0: clamp into range
    return jnp.minimum(nxt, n_states - 1).astype(jnp.int32)


def available_mask(avail) -> jnp.ndarray:
    """[n] float32 {0,1}: clients currently in the AVAILABLE state."""
    return (avail == AVAILABLE).astype(jnp.float32)


def stationary_distribution(transition) -> np.ndarray:
    """Analytic stationary distribution pi (pi @ T = pi, sum 1) of a
    row-stochastic transition matrix — host-side numpy, the oracle the
    availability occupancy tests compare the empirical chain against."""
    t = np.asarray(transition, np.float64)
    s = t.shape[0]
    a = np.vstack([t.T - np.eye(s), np.ones((1, s))])
    b = np.zeros(s + 1)
    b[-1] = 1.0
    pi, *_ = np.linalg.lstsq(a, b, rcond=None)
    return pi


# ---------------------------------------------------------------------------
# The async-step plumbing (what ``driver.make_async_step`` calls)
# ---------------------------------------------------------------------------

def traffic_send(model: TrafficModel, thp: Optional[TrafficHParams],
                 tstate: Optional[TrafficState], buf, mask, key, k, tau,
                 base_delays):
    """Compose the traffic model into one round's send side.  Returns
    ``(send_mask, delays, tstate')``:

    1. availability: step the Markov chain (fold_in(key, AVAIL_SALT)) and
       mask out non-AVAILABLE clients — they are never drawn and never
       bill;
    2. busy exclusion: workers with a message in flight stay excluded
       (the shift-consistency lock, unchanged from the plain async path);
    3. in-flight cap: senders beyond ``max_in_flight - |in flight|``
       (in worker order) are deferred to a later round;
    4. arrival draws: the model's process (thinned / replay), or the
       caller's ``base_delays`` (the ``StalenessSchedule`` draw) for
       ``kind="schedule"``.

    With no availability and an uncapped admission the send mask is
    bitwise the plain async ``mask * (1 - busy)`` — the transparency the
    tau=0 collapse tests pin.
    """
    if thp is None:
        raise ValueError(
            "a TrafficModel needs its traced leaves: attach "
            "traffic_hparams(model) to the async hparams' traffic field")
    busy = buffer_busy(buf)
    if model.availability is not None:
        if tstate is None:
            raise ValueError(
                "an AvailabilityModel needs per-worker chain state: init "
                "with init_traffic_state(n) on the async state's traffic "
                "field")
        avail = availability_step(thp.avail_transition, tstate.avail,
                                  jax.random.fold_in(key, AVAIL_SALT))
        tstate = TrafficState(avail)
        mask = mask * available_mask(avail)
    send = mask * (1.0 - busy)
    if model.admission is not None:
        room = jnp.maximum(thp.max_in_flight - jnp.sum(busy), 0.0)
        send = send * (jnp.cumsum(send) <= room).astype(jnp.float32)
    kind = model.arrival.kind
    if kind == "schedule":
        delays = base_delays
    elif kind == "trace":
        delays = replay_delays(model.arrival.trace, k, tau)
    else:
        delays = thinned_delays(thp.rate_table,
                                jax.random.fold_in(key, ARRIVAL_SALT),
                                busy.shape[0], k, tau, buf.occupied.shape[0])
    return send, delays, tstate


def admit_arrivals(model: Optional[TrafficModel],
                   thp: Optional[TrafficHParams], arrived, msg_t, k):
    """Admission on the receive side: zero out of the arrival mask every
    message older than ``staleness_cutoff`` rounds.  Discarded messages
    were already drained from the buffer (their workers are free again)
    but are billed nothing, update nothing, and never enter the FedBuff
    accumulator — the unbilled-discard contract.  ``model=None`` (or no
    admission) is the identity."""
    if model is None or model.admission is None:
        return arrived
    age = jnp.float32(k) - msg_t
    return arrived * (age <= thp.staleness_cutoff).astype(jnp.float32)
