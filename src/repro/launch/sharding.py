"""Divisibility-aware logical-axis sharding rules.

Every parameter/cache leaf gets a PartitionSpec from a name-based rule
table; a rule assigning mesh axis A to tensor dim d only applies if
``shape[d] % mesh.shape[A] == 0`` — otherwise that dim falls back to
replication.  This resolves e.g. kv_heads=8 on a 16-way model axis or
vocab=50280 not divisible by 16, uniformly across all 10 architectures.

Dims are indexed FROM THE END so the leading scan-repeat dim of stacked
block params never shifts the rules.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import numpy as np

P = jax.sharding.PartitionSpec

MODEL = "model"
DATA = "data"   # FSDP axis for weights; batch axis for activations

# leaf-name -> {negative_dim: logical_axis}
_RULES: Dict[str, Dict[int, str]] = {
    # embeddings / head
    "embed": {-2: MODEL, -1: DATA},
    "head": {-1: MODEL, -2: DATA},
    # attention
    "wq": {-2: MODEL, -3: DATA},
    "wk": {-2: MODEL, -3: DATA},
    "wv": {-2: MODEL, -3: DATA},
    "wo": {-3: MODEL, -1: DATA},
    # MLA
    "wq_a": {-1: MODEL, -2: DATA},
    "wq_b": {-2: MODEL, -3: DATA},
    # wkv_a output is split into latent/rope parts at an offset not aligned
    # to model-axis shards -> keep its output dim replicated.
    "wkv_a": {-2: DATA},
    "wk_b": {-2: MODEL, -3: DATA},
    "wv_b": {-2: MODEL, -3: DATA},
    # dense FFN
    "w_gate": {-1: MODEL, -2: DATA},
    "w_up": {-1: MODEL, -2: DATA},
    "w_down": {-2: MODEL, -1: DATA},
    # MoE expert weights (path-dispatched below): [E, D, F] / [E, F, D]
    "moe/w_gate": {-3: MODEL, -2: DATA},
    "moe/w_up": {-3: MODEL, -2: DATA},
    "moe/w_down": {-3: MODEL, -1: DATA},
    # router [*, D, E]: FSDP the D dim (at deepseek scale the stacked router
    # is ~100M params — replicating it wastes 0.4 GB/chip); gathered on use
    # by the MoE shard_map in_spec.
    "router": {-2: DATA},
    # SSM
    "in_z": {-1: MODEL, -2: DATA},
    "in_x": {-1: MODEL, -2: DATA},
    "in_B": {-2: DATA},
    "in_C": {-2: DATA},
    "in_dt": {-2: DATA},
    "conv_x": {-1: MODEL},
    "conv_B": {},
    "conv_C": {},
    "conv_x_bias": {-1: MODEL},
    "conv_B_bias": {},
    "conv_C_bias": {},
    "out_proj": {-2: MODEL, -1: DATA},
    # RG-LRU
    "w_in": {-1: MODEL, -2: DATA},
    "w_gate_branch": {-1: MODEL, -2: DATA},
    "w_r": {-1: MODEL, -2: DATA},
    "w_i": {-1: MODEL, -2: DATA},
    "w_out": {-2: MODEL, -1: DATA},
    "conv_w": {-1: MODEL},
    "lam": {},
}


def _leaf_rule(path) -> Dict[int, str]:
    names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
    names = [n for n in names if isinstance(n, str)]
    if not names:
        return {}
    leaf = names[-1]
    if "moe" in names and f"moe/{leaf}" in _RULES and "shared" not in names:
        return _RULES[f"moe/{leaf}"]
    return _RULES.get(leaf, {})


def _axis_size(mesh, logical: str) -> int:
    return mesh.shape.get(logical, 1)


def spec_for(shape: Tuple[int, ...], rule: Dict[int, str], mesh) -> P:
    spec = [None] * len(shape)
    for neg_dim, axis in rule.items():
        d = len(shape) + neg_dim
        if d < 0:
            continue
        size = _axis_size(mesh, axis)
        if size > 1 and shape[d] % size == 0 and spec[d] is None:
            spec[d] = axis
    return P(*spec)


# Serve-mode overrides: decode keeps expert weights fully resident in the
# fshard layout [E(model), D, F(data)] (see moe.moe_fshard / EXPERIMENTS.md
# §Perf deepseek decode iteration).
_SERVE_OVERRIDES: Dict[str, Dict[int, str]] = {
    "moe/w_gate": {-3: MODEL, -1: DATA},
    "moe/w_up": {-3: MODEL, -1: DATA},
    "moe/w_down": {-3: MODEL, -2: DATA},
}


def param_specs(abstract_params, mesh, mode: str = "train"):
    """PartitionSpec pytree for a param pytree (abstract or concrete)."""
    def leaf(path, x):
        rule = _leaf_rule(path)
        if mode == "serve":
            names = [getattr(k, "key", None) for k in path]
            leaf_name = next((n for n in reversed(names)
                              if isinstance(n, str)), "")
            if "moe" in names and f"moe/{leaf_name}" in _SERVE_OVERRIDES \
                    and "shared" not in names:
                rule = _SERVE_OVERRIDES[f"moe/{leaf_name}"]
            else:
                # Decode is latency-bound: keep dense weights RESIDENT
                # (model-sharded only) — a ZeRO-3 gather per step is pure
                # wire cost with no optimizer-state memory to amortize it.
                rule = {d: a for d, a in rule.items() if a != DATA}
        return spec_for(x.shape, rule, mesh)

    return jax.tree_util.tree_map_with_path(leaf, abstract_params)


def named_shardings(abstract_tree, mesh, specs=None):
    specs = specs if specs is not None else param_specs(abstract_tree, mesh)
    return jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), specs)


# ---------------------------------------------------------------------------
# Cache / batch specs
# ---------------------------------------------------------------------------

def batch_specs(batch_abstract, mesh, data_axes: Tuple[str, ...]):
    """Shard dim 0 (global batch) of every batch leaf over the data axes."""
    n = int(np.prod([mesh.shape[a] for a in data_axes]))

    def leaf(x):
        if x.shape and x.shape[0] % n == 0:
            return P(data_axes, *([None] * (len(x.shape) - 1)))
        # Fall back to a prefix of the data axes that divides the batch.
        for cut in range(len(data_axes) - 1, 0, -1):
            m = int(np.prod([mesh.shape[a] for a in data_axes[:cut]]))
            if x.shape and x.shape[0] % m == 0:
                return P(data_axes[:cut], *([None] * (len(x.shape) - 1)))
        return P(*([None] * len(x.shape)))

    return jax.tree.map(leaf, batch_abstract)


def cache_specs(cache_abstract, mesh, data_axes: Tuple[str, ...],
                seq_shard: bool = False):
    """Decode-cache sharding: batch over data axes (kv-heads/width over
    model where divisible).  With ``seq_shard`` (long_500k, batch=1) the
    sequence dim of attention caches is sharded over the data axes instead
    (flash-decode)."""
    n_data = int(np.prod([mesh.shape[a] for a in data_axes]))
    n_model = _axis_size(mesh, MODEL)

    def leaf(path, x):
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        names = [n for n in names if isinstance(n, str)]
        leaf_name = names[-1] if names else ""
        shape = x.shape
        spec = [None] * len(shape)
        # dim layout reminders:
        #   k/v:  [reps, B, S, KV, dh];  c_kv/k_rope: [reps, B, S, r]
        #   state(ssm): [reps, B, H, P, N]; state(rglru): [reps, B, W]
        #   conv_*: [reps, B, K-1, C]
        bdim = 1 if len(shape) >= 2 else 0
        if seq_shard and leaf_name in ("k", "v", "c_kv", "k_rope"):
            sdim = bdim + 1
            if shape[sdim] % n_data == 0:
                spec[sdim] = data_axes if len(data_axes) > 1 else data_axes[0]
        elif shape[bdim] % n_data == 0:
            spec[bdim] = data_axes if len(data_axes) > 1 else data_axes[0]
        # model axis on heads/width dims
        if leaf_name in ("k", "v") and len(shape) >= 4:
            if shape[-2] % n_model == 0 and n_model > 1:
                spec[-2] = MODEL
        elif leaf_name == "state" and len(shape) >= 4:      # ssm [.., H, P, N]
            if shape[-3] % n_model == 0 and n_model > 1:
                spec[-3] = MODEL
        elif leaf_name in ("state", "conv_x") and len(shape) >= 2:
            if shape[-1] % n_model == 0 and n_model > 1:
                spec[-1] = MODEL
        elif leaf_name == "conv" and shape[-1] % n_model == 0 and n_model > 1:
            spec[-1] = MODEL
        return P(*spec)

    return jax.tree_util.tree_map_with_path(leaf, cache_abstract)
