"""Persistent compilation cache for the entry points.

Called by the scripts a user runs (``chip_smoke.py``, ``repro.launch.
train``, the examples, ``benchmarks/run.py``) — never when a library
module is imported, so importing ``repro`` changes no jax setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory (listed in .gitignore).  A fixed
#: path: the directory is part of the cache key, so one that moved
#: between runs would never hit.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself
    and nothing is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
