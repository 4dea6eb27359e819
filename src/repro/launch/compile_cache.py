"""Persistent compilation cache for the entry points.

Called by the scripts a user runs (``chip_smoke.py``, ``repro.launch.
train``, the examples, ``benchmarks/run.py``) — never when a library
module is imported, so importing ``repro`` changes no jax setting.

Turning the cache on also starts the process's compile clock
(:func:`compile_seconds`): the host seconds jax spends tracing, lowering
and compiling, or loading a compiled program from the cache.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Dict, List, Tuple

import jax
from jax._src import dispatch

#: The checkout's own cache directory (listed in .gitignore).  A fixed
#: path: the directory is part of the cache key, so one that moved
#: between runs would never hit.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


#: jax's compile-path events: a function traced to a jaxpr, the jaxpr
#: lowered to an MLIR module, and the backend compile, which holds the
#: persistent-cache lookup and, on a hit, the load.
COMPILE_EVENTS = (dispatch.JAXPR_TRACE_EVENT,
                  dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
                  dispatch.BACKEND_COMPILE_EVENT)

_spans: List[Tuple[str, float, float]] = []
_lock = threading.Lock()
_listening = False


def _on_span(event: str, start: float, end: float, **kw) -> None:
    if event in COMPILE_EVENTS:
        with _lock:
            _spans.append((event, start, end))


def span_seconds(spans) -> Dict[str, float]:
    """Seconds of ``(event, start, end)`` spans: summed per event of
    :data:`COMPILE_EVENTS`, and ``total``, the length of their union (a
    function traced inside another's trace counts once)."""
    out = {ev: 0.0 for ev in COMPILE_EVENTS}
    total, reach = 0.0, float("-inf")
    for ev, s, e in sorted(spans, key=lambda x: x[1]):
        out[ev] += e - s
        if e > reach:
            total += e - max(s, reach)
            reach = e
    out["total"] = total
    return out


def start_compile_clock() -> None:
    """Start recording jax's compile-path spans (once per process)."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_time_span_listener(_on_span)
            _listening = True


def compile_seconds() -> Dict[str, float]:
    """:func:`span_seconds` of the compile-path spans since the clock
    started (:func:`enable_compile_cache` starts it)."""
    with _lock:
        spans = list(_spans)
    return span_seconds(spans)


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and the compile clock;
    returns the cache's directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself
    and nothing is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``."""
    start_compile_clock()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
