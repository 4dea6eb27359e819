"""Faults planted under the timed path, to show that ``correct`` catches
them.  Each is a patch of the program's module attributes, active while
the cell is built and compiled (``with planted(name): cell.setup()``):

* ``state_unchanged``: the step or round returns the state it was given;
* ``half_batch``: half of the batch (the second half of the clients, or
  of the rows) is left out and the mean taken over the rest;
* ``exchange_dropped``: the all-reduce between chips returns each chip's
  own value;
* ``answer_altered``: the objective the plan records is moved by one part
  in a thousand where it is computed;
* ``curvature_stale``: FLECS-CGD's curvature update returns B as it was
  (B stays at B⁰);
* ``curvature_half_beta``: the curvature update takes half its β.

The tests under ``bench/tests`` plant each one a cell can have at a small
size on the CPU; ``bench/calibrate.py --fault`` reads them on the chip.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "exchange_dropped",
          "answer_altered", "curvature_stale", "curvature_half_beta")


class _Namespace:
    """``base`` with some attributes replaced: confines a patch to the one
    module that looks them up through it."""

    def __init__(self, base, **replaced):
        self._base, self._replaced = base, replaced

    def __getattr__(self, attr):
        if attr in self._replaced:
            return self._replaced[attr]
        return getattr(self._base, attr)


def _patches(name):
    import jax
    import jax.numpy as jnp
    from repro.core import dl_flecs, flecs
    from repro.data import logreg
    from repro.optim import baselines

    if name == "state_unchanged":
        orig_f, orig_d = flecs._flecs_round, baselines._diana_round
        orig_sm = dl_flecs.shard_map

        def flecs_round(cfg, lg, lh, hp, state, key, *a, **k):
            return state, orig_f(cfg, lg, lh, hp, state, key, *a, **k)[1]

        def diana_round(cfg, lg, hp, state, key, *a, **k):
            return state, orig_d(cfg, lg, hp, state, key, *a, **k)[1]

        def shard_map(body, **kw):
            def frozen(params, shifts, batch, step):
                return params, shifts, body(params, shifts, batch, step)[2]
            return orig_sm(frozen, **kw)

        return [(flecs, "_flecs_round", flecs_round),
                (baselines, "_diana_round", diana_round),
                (dl_flecs, "shard_map", shard_map)]
    if name == "half_batch":
        orig_loss = dl_flecs._loss_fn

        def masked_mean(x, mask):
            n = mask.shape[0]
            mask = mask * (jnp.arange(n) < (n + 1) // 2)
            shape = (-1,) + (1,) * (x.ndim - 1)
            return (jnp.sum(mask.reshape(shape) * x, axis=0)
                    / jnp.maximum(jnp.sum(mask), 1.0))

        def loss_fn(params, batch, cfg, ctx):
            half = jax.tree.map(lambda a: a[:max(a.shape[0] // 2, 1)], batch)
            return orig_loss(params, half, cfg, ctx)

        return [(flecs, "masked_mean", masked_mean),
                (baselines, "masked_mean", masked_mean),
                (dl_flecs, "_loss_fn", loss_fn)]
    if name == "exchange_dropped":
        lax = _Namespace(jax.lax, psum=lambda x, axis_name, **kw: x)
        return [(dl_flecs, "jax", _Namespace(jax, lax=lax))]
    if name == "answer_altered":
        orig_metrics = logreg.FederatedLogReg.metrics

        def metrics(self, w):
            out = dict(orig_metrics(self, w))
            out["F"] = out["F"] * (1.0 + 1e-3)
            return out

        return [(logreg.FederatedLogReg, "metrics", metrics)]
    if name in ("curvature_stale", "curvature_half_beta"):
        orig_update = flecs.direct_update

        def direct_update(B, Y_tilde, M, beta):
            if name == "curvature_stale":
                return B
            return orig_update(B, Y_tilde, M, 0.5 * beta)

        return [(flecs, "direct_update", direct_update)]
    raise ValueError(f"unknown fault {name!r}; faults: {FAULTS}")


@contextlib.contextmanager
def planted(name):
    """Apply fault ``name`` (None: none) for the duration of the block."""
    if name is None:
        yield
        return
    patches = _patches(name)
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    try:
        for obj, attr, new in patches:
            setattr(obj, attr, new)
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)
