"""Reduction of a profiler trace to the numbers the per-layer metrics read.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read through
``jax.profiler.ProfileData``.  Device planes are ``/device:TPU:<i>``; their
``XLA Ops`` line holds one event per operation the device ran.  The
harness's own host spans (``jax.profiler.TraceAnnotation`` named
``bench.*``) sit on the host plane, on the same clock.

From these it computes, per device and over the traced window:

* the union of the intervals in which an operation ran (busy time), and
  the gaps between them (idle time), each gap named by the host span that
  covers most of it;
* the summed time of the operations whose name or metadata matches a
  kernel's name;
* the all-reduce time, and the part of it during which no other operation
  ran on that device (exposed).

Times are in seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float, str]        # name, start_s, end_s, metadata

OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
COLLECTIVE_MARKS = ("all-reduce", "allreduce", "all_reduce")


def is_tpu_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def is_op_line(name: str) -> bool:
    return name == OP_LINE


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` between the disjoint ``busy`` ones."""
    return subtract([(lo, hi)], busy)


def is_collective(name: str) -> bool:
    return any(k in name for k in COLLECTIVE_MARKS)


def leaf_events(events: Sequence[Event]) -> List[Event]:
    """Drop events that enclose another event of the same line (a loop or
    call op spanning its body), so summed op time counts each moment once.
    Collectives stay: compute may run while they do."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(evs)
    stack: List[int] = []
    for i, (n, s, e, _m) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2]:
            parent[stack[-1]] = not is_collective(evs[stack[-1]][0])
        if not is_collective(n):
            stack.append(i)
    return [ev for ev, p in zip(evs, parent) if not p]


@dataclasses.dataclass
class DeviceOps:
    name: str
    events: List[Event]              # leaf operations, seconds

    def busy(self, lo: float, hi: float) -> List[Interval]:
        return clip(union((s, e) for _, s, e, _m in self.events), lo, hi)

    def matching(self, marks: Sequence[str], lo: float, hi: float
                 ) -> List[Interval]:
        """Intervals of the operations whose name or metadata contains
        one of ``marks``."""
        return clip(union((s, e) for n, s, e, meta in self.events
                          if any(k in n or k in meta for k in marks)),
                    lo, hi)


@dataclasses.dataclass
class Trace:
    devices: List[DeviceOps]
    spans: List[Tuple[str, float, float]]     # bench.* host spans

    def window(self, span: str = SPAN_PREFIX + "call") -> Interval:
        """From the first to the last end of the host spans ``span``."""
        own = [(s, e) for n, s, e in self.spans if n == span]
        if not own:
            raise ValueError(f"trace holds no {span!r} span")
        return min(s for s, _ in own), max(e for _, e in own)


def _meta(event) -> str:
    parts = []
    for stat in getattr(event, "stats", ()):
        try:
            key, value = stat
        except (TypeError, ValueError):
            continue
        if isinstance(value, str):
            parts.append(value)
    return " ".join(parts)


def from_profile(pd, device_plane: Callable[[str], bool] = is_tpu_plane,
                 op_line: Callable[[str], bool] = is_op_line) -> Trace:
    """Read a ``jax.profiler.ProfileData``.  ``device_plane``/``op_line``
    choose which planes are devices and which of their lines hold ops (the
    CPU test points them at the CPU client's threads)."""
    devices, spans = [], []
    for plane in pd.planes:
        device = device_plane(plane.name)
        evs = []
        for line in plane.lines:
            ops = device and op_line(line.name)
            for ev in line.events:
                if ops and ev.duration_ns > 0:
                    evs.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                _meta(ev)))
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9))
        if device:
            devices.append(DeviceOps(plane.name, leaf_events(evs)))
    devices.sort(key=lambda d: _device_index(d.name))
    return Trace(devices, spans)


def _device_index(name: str) -> int:
    tail = name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else 0


def load(logdir: str, **kw) -> Trace:
    """The newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return from_profile(ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime)),
                        **kw)


def name_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]
             ) -> str:
    """The host span that covers most of ``gap``; the innermost (shortest)
    among equals.  ``host.unannotated`` where none does."""
    best, best_key = "host.unannotated", (0.0, 0.0)
    for n, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        key = (cover, -(e - s))
        if key > best_key:
            best, best_key = n, key
    return best


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                          # mean over devices
    busy_by_device: List[float]
    kernel_s: Dict[str, float]             # device 0, per kernel name
    kernel_events: Dict[str, int]
    allreduce_s: Optional[float]           # device 0; None: no device
    allreduce_exposed_s: Optional[float]
    top_ops: List[Tuple[str, float]]       # device 0
    idle_by_span: List[Tuple[str, float]]  # device 0, summed by host span


_LAYOUT = re.compile(r"\{[^{}]*\}")
OP_NAME_CHARS = 160


def op_name(name: str) -> str:
    """An op's event name is its HLO instruction; keep it without layouts,
    cut to ``OP_NAME_CHARS``: the instruction, its shapes and its kind."""
    return _LAYOUT.sub("", name)[:OP_NAME_CHARS]


def summarize(trace: Trace, window: Optional[Interval] = None,
              kernels: Optional[Dict[str, Sequence[str]]] = None,
              top: int = 10) -> Summary:
    """Numbers of the traced window (default: ``Trace.window()``).

    ``kernels`` maps a kernel's reported name to the marks that find its
    events.  Device 0 stands for the rest in kernel, collective, op and
    gap numbers; busy time is averaged over all devices."""
    lo, hi = window or trace.window()
    if not trace.devices:
        raise ValueError("trace holds no device plane")
    busy = [dev.busy(lo, hi) for dev in trace.devices]
    dev0 = trace.devices[0]
    kernel_s, kernel_n = {}, {}
    for name, marks in (kernels or {}).items():
        iv = dev0.matching(marks, lo, hi)
        kernel_s[name] = total(iv)
        kernel_n[name] = sum(
            1 for n, s, e, meta in dev0.events
            if s < hi and e > lo and any(k in n or k in meta for k in marks))
    coll = clip(union((s, e) for n, s, e, _m in dev0.events
                      if is_collective(n)), lo, hi)
    other = clip(union((s, e) for n, s, e, _m in dev0.events
                       if not is_collective(n)), lo, hi)
    by_op: Dict[str, float] = defaultdict(float)
    for n, s, e, _m in dev0.events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            by_op[op_name(n)] += d
    idle: Dict[str, List[float]] = defaultdict(list)
    for g in gaps(busy[0], lo, hi):
        idle[name_gap(g, trace.spans)].append(g[1] - g[0])
    return Summary(
        window_s=hi - lo,
        busy_s=sum(total(b) for b in busy) / len(busy),
        busy_by_device=[total(b) for b in busy],
        kernel_s=kernel_s, kernel_events=kernel_n,
        allreduce_s=total(coll),
        allreduce_exposed_s=total(subtract(coll, other)),
        top_ops=sorted(by_op.items(), key=lambda kv: -kv[1])[:top],
        idle_by_span=sorted(
            ((f"{n} ({len(v)} gaps)", sum(v)) for n, v in idle.items()),
            key=lambda kv: -kv[1])[:top])
