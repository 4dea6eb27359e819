"""Compile the fused compressor kernels and the SSD kernels for a
described TPU v5e.

Interpret mode (tests/test_kernels.py) proves the kernels bit-identical
to the jnp reference, but it cannot show what the TPU compiler refuses:
scalar stores to VMEM, primitives Mosaic cannot lower, or a block that
does not fit the kernel's scoped VMEM.  These tests compile each kernel
for one chip of a described ``v5e:2x2`` topology (no chip is attached)
at the widths the federated engine uses — unbatched, at the size limit
``ops.MAX_FUSED_ELEMS``, and vmapped over 16 workers as the sweep engine
calls them — and assert that the Mosaic kernel is in the compiled
program (``tpu_custom_call``), not an interpret-mode lowering.  The SSD
intra-chunk kernels are compiled inside ``jax.grad`` of ``ssd_scan`` at
the ``mamba2.flecs-cgd`` cell's shapes, forward and backward.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, so under several
pytest workers only the worker that runs this file loads it.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.compat import shard_map
from repro.kernels.compressor import ops
from repro.kernels.ssd import ops as ssd_ops
from repro.models import ssm


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off (a TPU executable written here could not be read back)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


FUSED = {
    "dither": lambda key, x, s: ops.fused_dither(key, x, s,
                                                 interpret=False),
    "topk": lambda key, x, frac: ops.fused_topk(key, x, frac,
                                                interpret=False),
}


@pytest.mark.parametrize("workers", [0, 16])
@pytest.mark.parametrize("d", [4096, ops.MAX_FUSED_ELEMS])
@pytest.mark.parametrize("kernel", sorted(FUSED))
def test_fused_kernel_compiles_for_v5e(one_chip, kernel, d, workers):
    """One [d] message, or [16, d] messages vmapped with per-worker keys
    and a shared traced level/fraction (the sweep engine's call)."""
    fn = FUSED[kernel]
    key_dtype = jax.random.key(0).dtype
    if workers:
        fn = jax.vmap(fn, in_axes=(0, 0, None))
        key = _sds((workers,), key_dtype, one_chip)
        x = _sds((workers, d), jnp.float32, one_chip)
    else:
        key = _sds((), key_dtype, one_chip)
        x = _sds((d,), jnp.float32, one_chip)
    _assert_mosaic(fn, key, x, _sds((), jnp.float32, one_chip))


@pytest.mark.parametrize("name", ["dither_bits_fused", "topk_bits_fused"])
def test_bits_kernel_compiles_for_v5e(one_chip, name):
    """The bits-only ledger kernels, with the parameter and d traced."""
    fn = getattr(ops, name)
    scalar = _sds((), jnp.float32, one_chip)
    _assert_mosaic(lambda p, d: fn(p, d, interpret=False), scalar, scalar)


@pytest.mark.parametrize("context", ["jit", "dl_shard_map"])
def test_ssd_kernels_compile_for_v5e_under_the_ssd_scope(one_chip,
                                                        monkeypatch,
                                                        context):
    """``jax.grad`` of ``ssd_scan`` at mamba2-1.3b's widths and the cell's
    batch (B 8, S 2048, H 64, P 64, N 128, Q 256), under a plain jit and
    inside the DL trainer's shard_map (data manual, model automatic, where
    Mosaic lowers only in a shard_map of its own): the forward and the
    backward Mosaic kernels are in the compiled program, and the
    ``op_name`` of each carries the ``ssm.ssd`` scope (the backward's
    through its own ``named_scope``), where ``ssm.ssd_ms`` reads it."""
    B, S, H, P, N, Q = 8, 2048, 64, 64, 128, 256
    monkeypatch.setattr(ssd_ops, "on_tpu", lambda: True)

    def loss(xh, dt, A_log, B_mat, C_mat):
        y, state = ssm.ssd_scan(xh, dt, A_log, B_mat, C_mat, Q)
        return jnp.sum(y * y) + jnp.sum(state)

    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    sharding = one_chip
    if context == "dl_shard_map":
        mesh = Mesh(np.asarray(list(one_chip.device_set)).reshape(1, 1),
                    ("data", "model"))
        fn = shard_map(fn, mesh, in_specs=PartitionSpec(),
                       out_specs=PartitionSpec(), axis_names={"data"})
        sharding = NamedSharding(mesh, PartitionSpec())
    args = [_sds(s, jnp.float32, sharding) for s in
            ((B, S, H, P), (B, S, H), (H,), (B, S, N), (B, S, N))]
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(names) == 2, names
    for name in names:
        assert ssm.SCOPE_SSD in [re.sub(r"^(?:[\w.-]*\()+|\)+$", "", part)
                                 for part in name.split("/")], name
    bwd, = [n for n in names if "transpose(" in n]
    assert ssm.SCOPE_SSD in bwd.split("/"), bwd       # its own named_scope
