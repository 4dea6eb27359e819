"""Device time under a scope of the model's own, wherever it nests.

A round scope (``bench/scopes.py``) is an op's first ``fed.`` path
component.  A model's scope, such as ``ssm.ssd`` around Mamba-2's chunked
SSD scan, sits inside the round scopes at any depth, and JAX wraps it in
the transformations applied to it (``transpose(jvp(ssm.ssd))``).  An op is
under ``scope`` when a component of its ``op_name`` path, with that
wrapping taken off, is ``scope``.  Instructions map to their ``op_name``
through the compiled HLO text, as ``scopes.op_names`` maps them.

Every moment of device 0's busy time goes to one op, as in
``scopes.by_scope``: ops are taken in the order they start, and each
counts from where the ops before it ended.

The window: a few calls of the cell's timed path traced by the reader
itself (``scopes.WINDOW_S``, ``CALLS_MIN``, ``CALLS_MAX``), since the
harness deletes its own trace before the readers run.
"""
from __future__ import annotations

import re
import shutil
import sys
import tempfile
import time
from typing import Dict, Optional

from bench import scopes
from bench import trace as bt

_WRAPPED = re.compile(r"^(?:[\w.-]*\()+([^()]*)\)+$")


def under(op_name: str, scope: str) -> bool:
    """True where a component of the ``op_name`` path, unwrapped, is
    ``scope``."""
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        if (m.group(1) if m else part) == scope:
            return True
    return False


def scope_seconds(trace: bt.Trace, names: Dict[str, str], scope: str
                  ) -> float:
    """Device 0's busy seconds of the trace's window under ``scope``."""
    lo, hi = trace.window()
    events = sorted((max(s, lo), min(e, hi), instr)
                    for _n, s, e, instr in trace.devices[0].events
                    if min(e, hi) > max(s, lo))
    total, reach = 0.0, lo
    for s, e, instr in events:
        d = e - max(s, reach)
        if d <= 0:
            continue
        reach = e
        if under(names.get(instr, ""), scope):
            total += d
    return total


def scope_ms(run, scope: str) -> Optional[float]:
    """Device-0 milliseconds a round under ``scope`` over a few traced
    calls; None for an untraced run or a program without the scope."""
    cell = run.cell
    compiled = getattr(cell, "compiled", None)
    if run.summary is None or compiled is None:
        return None
    names = scopes.op_names(compiled.as_text())
    if not any(under(n, scope) for n in names.values()):
        return None
    import jax
    planes = scopes.CPU_PLANES if cell.devices[0].platform == "cpu" else {}
    logdir = tempfile.mkdtemp(prefix="bench_inner_scopes_")
    rounds, calls = 0.0, 0
    t0 = time.perf_counter()
    jax.profiler.start_trace(logdir)
    try:
        while calls < scopes.CALLS_MIN or (
                calls < scopes.CALLS_MAX
                and time.perf_counter() - t0 < scopes.WINDOW_S):
            with jax.profiler.TraceAnnotation(bt.SPAN_PREFIX + "call"):
                work, _ok = cell.call()
            rounds += work
            calls += 1
    finally:
        jax.profiler.stop_trace()
    try:
        secs = scope_seconds(scopes.load(logdir, **planes), names, scope)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    print(f"inner_scopes: {scope} {secs:.6f} s over {rounds:g} rounds "
          f"({calls} calls traced)", file=sys.stderr)
    if not rounds:
        return None
    return 1e3 * secs / rounds
