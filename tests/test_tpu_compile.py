"""Compile the fused compressor kernels for a described TPU v5e.

Interpret mode (tests/test_kernels.py) proves the kernels bit-identical
to the jnp reference, but it cannot show what the TPU compiler refuses:
scalar stores to VMEM, primitives Mosaic cannot lower, or a block that
does not fit the kernel's scoped VMEM.  These tests compile each kernel
for one chip of a described ``v5e:2x2`` topology (no chip is attached)
at the widths the federated engine uses — unbatched, at the size limit
``ops.MAX_FUSED_ELEMS``, and vmapped over 16 workers as the sweep engine
calls them — and assert that the Mosaic kernel is in the compiled
program (``tpu_custom_call``), not an interpret-mode lowering.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, so under several
pytest workers only the worker that runs this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.compressor import ops


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
