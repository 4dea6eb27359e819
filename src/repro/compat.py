"""The one routing point for jax's mesh primitives.

Every shard_map call site in this repo goes through :func:`shard_map`,
and every mapped-axis size query through :func:`axis_size` (analysis
rule R4 checks this), so a change of jax's surface lands in one file.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map``.

    axis_names: mesh axes to run manually (None => all of them); the
    others stay under GSPMD.
    """
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)


def axis_size(axis_name):
    """``jax.lax.axis_size``: the size of a mapped mesh axis from inside
    shard_map."""
    return jax.lax.axis_size(axis_name)
