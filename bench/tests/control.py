"""Readings of a cell's control at its small size."""
import jax

from bench import harness
from bench.tests import tiny


def control_fails(cell: str, seed: int = 5) -> dict:
    """The control's numbers that exceed the cell's limits."""
    jax.config.update("jax_enable_compilation_cache", False)
    spec = tiny.spec_with_open_cells()
    config, traffic = tiny.SIZES[cell]
    w = next(x for x in spec["workloads"] if x["name"] == cell)
    c, _ = harness.build_cell(spec, cell, seed, jax.devices()[:w["chips"]],
                              config, traffic)
    c.setup()
    c.call()
    c.release()
    got = c.readings("control")
    print("control readings", got)
    return {k: v for k, v in got.items()
            if k in c.limits and not v <= c.limits[k]}
