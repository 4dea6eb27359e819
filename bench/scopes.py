"""Device time of a federated round by phase, read from the program's scopes.

The program runs each phase of a round under a ``jax.named_scope``, and
each compressor family's implementation under one of its own.  The
reduction imports nothing of the program, so the names are listed here
(the program holds them in ``repro.core.driver.ROUND_SCOPES`` and
``repro.core.compressors.COMPRESS_SCOPES``):

* round phases: ``fed.oracle``, ``fed.compress.grad``, ``fed.compress.hess``,
  ``fed.curvature``, ``fed.server``, ``fed.record``;
* compressor families inside ``fed.compress.*``: ``compress.dither``,
  ``compress.natural``, ``compress.topk``, ``compress.count_sketch``,
  ``compress.minmax`` (identity has no operations).

A scope reaches the compiled program as a component of each instruction's
``metadata={op_name="…"}``, for example
``jit(program)/…/fed.compress.hess/compress.count_sketch/scatter-add``.
JAX wraps a scope in the transformations applied to it
(``transpose(jvp(fed.oracle))``); the wrapping is taken off.

Attribution.  A device op event is one run of one HLO instruction: the
event's ``hlo_op`` stat where it has one, else the ``%name =`` head of the
event's name, else the whole name.  The instruction's ``op_name`` comes
from the compiled executable's HLO text.  A fusion takes the ``op_name``
XLA wrote on the fusion instruction, which is XLA's choice, normally its
root's.  An instruction a compiler pass made with none takes one from
the computations it calls, else from the op that consumes it
(:func:`op_names`).  TPU v5e op events carry no ``op_name`` of their own
(their stats are the device offset and duration), so the HLO text is
the only source.  An op's round scope is the first path component that
starts with ``fed.``, cut at its second dot (``fed.compress.grad`` is
``fed.compress``, message ``grad``); its family is the component that
starts with ``compress.``.  An op with no round scope is ``unscoped``.

Every moment of device 0's busy time goes to one op: ops are taken in the
order they start, and each counts from where the ops before it ended, so
the scopes' seconds and the unscoped seconds add up to the busy seconds.
An idle gap takes the name of the host span that covers most of it
(``bench.trace.name_gap``) and, where the op after it has a round scope,
that scope: ``bench.call/fed.compress``.

The window.  The harness reduces its own trace and deletes it before the
metric readers run, so the scope readers record one of their own:
:func:`summary` traces a few calls of the cell's timed path
(``cell.call()``; ``WINDOW_S``, ``CALLS_MIN``, ``CALLS_MAX``) once per
run, and every reader of the run shares it.  A cell with no compiled
program, or a program whose HLO holds no ``fed.`` scope, is not traced
and its readers report nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Set, Tuple

from bench import trace as bt

ROUND_PREFIX = "fed."
FAMILY_PREFIX = "compress."
COMPRESS = "fed.compress"
UNSCOPED = "unscoped"
FAMILIES = ("identity", "dither", "natural", "topk", "count_sketch",
            "minmax")

#: The scope window traces calls until ``WINDOW_S`` seconds have passed
#: or ``CALLS_MAX`` calls have run, and at least ``CALLS_MIN`` calls.
WINDOW_S = 2.0
CALLS_MIN = 2
CALLS_MAX = 4

_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition)=\{?([^,}\s]+)")
_OPERAND = re.compile(r"%([\w.-]+)")
_EVENT = re.compile(r"^%?([^\s=]+)\s*=")
_WRAPPED = re.compile(r"^(?:[\w.-]*\()+([^()]*)\)+$")


# ---------------------------------------------------------------------------
# HLO: instruction -> op_name -> (round scope, message, family)
# ---------------------------------------------------------------------------

def scope_of(op_name: str) -> Tuple[Optional[str], Optional[str],
                                     Optional[str]]:
    """(round scope, message, family) of an ``op_name`` path; None where
    the path has none."""
    rnd = msg = fam = None
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        part = m.group(1) if m else part
        if rnd is None and part.startswith(ROUND_PREFIX):
            head, _, tail = part.partition(".")
            name, _, msg = tail.partition(".")
            rnd, msg = f"{head}.{name}", msg or None
        elif fam is None and part.startswith(FAMILY_PREFIX):
            fam = part[len(FAMILY_PREFIX):]
    return rnd, msg, fam


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` over every computation of a
    compiled module's HLO text.

    An instruction with no ``op_name`` of its own is one a compiler pass
    made: a fusion of such ops, the sort a scatter is expanded into, a
    layout copy, a prefetch.  It takes the first ``op_name`` with a round
    scope among the instructions of the computations it calls (searched
    depth first), else that of the first of its users that has one: such
    an instruction is made to serve the op that consumes it.  Users
    through a ``tuple`` are not followed (a tuple gathers unrelated
    values).  The text of a scheduled module defines each instruction
    before its users, so one pass in reverse text order resolves chains.
    An instruction that finds none is left out."""
    own: Dict[str, str] = {}
    called: Dict[str, List[str]] = {}
    body: Dict[str, List[str]] = defaultdict(list)
    users: Dict[str, List[str]] = defaultdict(list)
    order: List[str] = []
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            if line.rstrip().endswith("{"):
                comp = _HEAD.match(line).group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        body[comp].append(name)
        om = _OP_NAME.search(line)
        rest = line[m.end():om.start() if om else len(line)]
        if not name.startswith("tuple"):
            for operand in _OPERAND.findall(rest):
                users[operand].append(name)
        if om:
            own[name] = om.group(1)
        else:
            called[name] = [c.lstrip("%") for c in _CALLED.findall(rest)]
            order.append(name)

    def inner(name: str, seen: Set[str]) -> Optional[str]:
        for c in called.get(name, ()):
            if c in seen:
                continue
            seen.add(c)
            for op in body.get(c, ()):
                if op in own and scope_of(own[op])[0]:
                    return own[op]
            for op in body.get(c, ()):
                got = inner(op, seen)
                if got:
                    return got
        return None

    out = dict(own)
    for name in reversed(order):
        got = inner(name, set()) or next(
            (out[u] for u in users.get(name, ())
             if u in out and scope_of(out[u])[0]), None)
        if got:
            out[name] = got
    return out


# ---------------------------------------------------------------------------
# Trace: device op events keyed by their instruction
# ---------------------------------------------------------------------------

def _hlo_op(stats) -> Optional[str]:
    for stat in stats or ():
        try:
            key, value = stat
        except (TypeError, ValueError):
            continue
        if key == "hlo_op" and isinstance(value, str) and value:
            return value
    return None


def instruction(name: str, stats) -> str:
    """The HLO instruction an op event ran: its ``hlo_op`` stat, else the
    ``%name =`` head of its name, else its name."""
    op = _hlo_op(stats)
    if op:
        return op
    m = _EVENT.match(name)
    return m.group(1) if m else name


def from_profile(pd, device_plane=bt.is_tpu_plane,
                 op_line=bt.is_op_line, hlo_only: bool = False
                 ) -> bt.Trace:
    """``bench.trace.from_profile`` with each op event's instruction name
    in the metadata slot of its event.  ``hlo_only`` keeps only the events
    with an ``hlo_op`` stat: a CPU client's threads also log thread-pool
    waits and end-of-op markers, which sit inside the ops they follow."""
    devices, spans = [], []
    for plane in pd.planes:
        device = device_plane(plane.name)
        evs = []
        for line in plane.lines:
            ops = device and op_line(line.name)
            for ev in line.events:
                stats = getattr(ev, "stats", ())
                if (ops and ev.duration_ns > 0
                        and (not hlo_only or _hlo_op(stats))):
                    evs.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                instruction(ev.name, stats)))
                elif ev.name.startswith(bt.SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9))
        if device:
            devices.append(bt.DeviceOps(plane.name, bt.leaf_events(evs)))
    devices.sort(key=lambda d: bt._device_index(d.name))
    return bt.Trace(devices, spans)


def load(logdir: str, **kw) -> bt.Trace:
    """The newest ``.xplane.pb`` under ``logdir``, read by
    :func:`from_profile`."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return from_profile(ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime)),
                        **kw)


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScopeSummary:
    window_s: float
    busy_s: float                              # device 0
    scope_s: Dict[str, float]                  # round scope (or unscoped)
    branch_s: Dict[Tuple[str, str], float]     # (message, family), compress
    idle_by_span: List[Tuple[str, float]]      # "<span>/<scope> (n gaps)"
    top_unscoped: List[Tuple[str, float]]      # instruction, seconds
    rounds: float = 0.0


def by_scope(trace: bt.Trace, names: Dict[str, str],
             window: Optional[bt.Interval] = None, top: int = 10
             ) -> ScopeSummary:
    """Device 0's busy time of the window (default ``Trace.window()``) by
    round scope, and under ``fed.compress`` by (message, family); its idle
    gaps by host span and the scope of the op after each."""
    lo, hi = window or trace.window()
    if not trace.devices:
        raise ValueError("trace holds no device plane")
    events = sorted((max(s, lo), min(e, hi), instr)
                    for _n, s, e, instr in trace.devices[0].events
                    if min(e, hi) > max(s, lo))
    scope_s: Dict[str, float] = defaultdict(float)
    branch_s: Dict[Tuple[str, str], float] = defaultdict(float)
    unscoped: Dict[str, float] = defaultdict(float)
    scopes = []
    reach = lo
    for s, e, instr in events:
        rnd, msg, fam = scope_of(names.get(instr, ""))
        scopes.append(rnd)
        d = e - max(s, reach)
        if d <= 0:
            continue
        reach = e
        scope_s[rnd or UNSCOPED] += d
        if rnd is None:
            unscoped[instr] += d
        elif rnd == COMPRESS:
            branch_s[(msg or "", fam or "")] += d
    busy = bt.union((s, e) for s, e, _ in events)
    starts = [s for s, _, _ in events]
    idle: Dict[str, List[float]] = defaultdict(list)
    for g in bt.gaps(busy, lo, hi):
        name = bt.name_gap(g, trace.spans)
        i = bisect.bisect_left(starts, g[1])
        if i < len(scopes) and scopes[i]:
            name = f"{name}/{scopes[i]}"
        idle[name].append(g[1] - g[0])
    return ScopeSummary(
        window_s=hi - lo, busy_s=bt.total(busy),
        scope_s=dict(scope_s), branch_s=dict(branch_s),
        idle_by_span=sorted(
            ((f"{n} ({len(v)} gaps)", sum(v)) for n, v in idle.items()),
            key=lambda kv: -kv[1])[:top],
        top_unscoped=sorted(unscoped.items(),
                            key=lambda kv: -kv[1])[:top])


# ---------------------------------------------------------------------------
# The readers' window
# ---------------------------------------------------------------------------

#: On a CPU run the CPU client's XLA threads stand in for the device.
CPU_PLANES = {"device_plane": lambda name: name == "/host:CPU",
              "op_line": lambda name: name.startswith("tf_XLA"),
              "hlo_only": True}

_CACHE: Dict[int, Tuple[object, Optional[ScopeSummary]]] = {}


def summary(run) -> Optional[ScopeSummary]:
    """The scope reduction of a window the readers trace themselves, made
    once per traced run; None for an untraced run or a program without
    ``fed.`` scopes."""
    hit = _CACHE.get(id(run))
    if hit is not None and hit[0] is run:
        return hit[1]
    got = _record(run)
    _CACHE.clear()
    _CACHE[id(run)] = (run, got)
    return got


def _record(run) -> Optional[ScopeSummary]:
    cell = run.cell
    compiled = getattr(cell, "compiled", None)
    if run.summary is None or compiled is None:
        return None
    t0 = time.perf_counter()
    names = op_names(compiled.as_text())
    t_map = time.perf_counter() - t0
    if not any(scope_of(n)[0] for n in names.values()):
        return None
    import jax
    planes = CPU_PLANES if cell.devices[0].platform == "cpu" else {}
    logdir = tempfile.mkdtemp(prefix="bench_scopes_")
    rounds, calls = 0.0, 0
    t1 = time.perf_counter()
    jax.profiler.start_trace(logdir)
    try:
        while calls < CALLS_MIN or (calls < CALLS_MAX and
                                    time.perf_counter() - t1 < WINDOW_S):
            with jax.profiler.TraceAnnotation(bt.SPAN_PREFIX + "call"):
                work, _ok = cell.call()
            rounds += work
            calls += 1
    finally:
        jax.profiler.stop_trace()
    t2 = time.perf_counter()
    try:
        s = by_scope(load(logdir, **planes), names)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    s.rounds = rounds
    print(f"scopes: HLO map {t_map:.3f} s ({len(names)} instructions); "
          f"{calls} calls traced in {t2 - t1:.3f} s; reduction "
          f"{time.perf_counter() - t2:.3f} s; busy {s.busy_s:.6f} s of "
          f"{s.window_s:.6f} s over {rounds:g} rounds", file=sys.stderr)
    print(f"scopes: by scope {sorted(s.scope_s.items())}", file=sys.stderr)
    print(f"scopes: fed.compress by (message, family) "
          f"{sorted(s.branch_s.items())}", file=sys.stderr)
    print(f"scopes: idle gaps {s.idle_by_span}", file=sys.stderr)
    print(f"scopes: top unscoped {s.top_unscoped}", file=sys.stderr)
    return s


def scope_ms(run, scope: str) -> Optional[float]:
    """Device-0 milliseconds a round under ``scope`` (a round scope such
    as ``fed.compress``, or ``unscoped``)."""
    s = summary(run)
    if s is None or not s.rounds:
        return None
    return 1e3 * s.scope_s.get(scope, 0.0) / s.rounds


def family_of(name: str) -> str:
    """A compressor's family from its registry name (``topk0.1``)."""
    for fam in sorted(FAMILIES, key=len, reverse=True):
        if name.startswith(fam):
            return fam
    raise ValueError(f"unknown compressor name {name!r}")


def selected_branches(traffic: dict) -> Optional[Set[Tuple[str, str]]]:
    """The (message, family) branches some grid point of the traffic's
    plan selects; None for a method without compressed messages."""
    method = traffic.get("method")
    if method == "flecs_cgd":
        return ({("grad", family_of(n)) for n in traffic["grad_family"]}
                | {("hess", family_of(traffic["hess_compressor"]))})
    if method == "diana":
        return {("grad", "dither")}
    return None


def selected_share(s: ScopeSummary, selected: Sequence[Tuple[str, str]]
                   ) -> Optional[float]:
    """Per cent of ``fed.compress`` time in the selected branches."""
    total = s.scope_s.get(COMPRESS, 0.0)
    if total <= 0:
        return None
    return 100.0 * sum(s.branch_s.get(b, 0.0) for b in selected) / total
