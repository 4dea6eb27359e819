"""The published mamba2-1.3b's one-chip FLECS-CGD cell at a small size:
``correct`` on a sound run, false with a fault under the timed path and
with either departure of the older ``mamba2-1.3b-l16`` model planted in
the program; the control's readings; the traced run's per-layer
metrics."""
import math
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, inner_scopes
from bench import trace as bt
from bench.tests import tiny

CELL = "mamba2.flecs-cgd"
SIZE = (tiny.MAMBA, {"batch_per_worker": 2, "seq_len": 32})


def _run(fault=None, traced=False, config=None):
    from bench import faults
    jax.config.update("jax_enable_compilation_cache", False)
    config = dict(SIZE[0], **(config or {}))
    with faults.planted(fault):
        return harness.run_cell(
            CELL, 2**31 + 7, 0.5, traced, time.perf_counter(),
            require_chip=False, spec=tiny.spec_with_open_cells(),
            config_overrides=config, traffic_overrides=SIZE[1],
            peaks=tiny.CPU_PEAKS, trace_kw=tiny.CPU_TRACE)


#: On the CPU the program computes in float32 and reads under these
#: (loss_rel under 1e-7, g0_gap under 1e-8, change_gap under 2e-6,
#: g0_dist under 1e-4).
CPU_EXACT = {"loss_rel": 1e-5, "g0_gap": 1e-5, "change_gap": 1e-4,
             "g0_dist": 1e-3}


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["compared"]
    assert r["compared"]["uplink_gap"]["value"] == 0
    assert set(r["metrics"]) == {"rounds_per_s", "setup_s"}
    for k in CPU_EXACT:
        assert r["compared"][k]["value"] <= CPU_EXACT[k], r["compared"]


@pytest.mark.parametrize("fault", ("state_unchanged", "half_batch"))
def test_fault_is_caught(fault):
    r = _run(fault=fault)
    assert not r["correct"], r["compared"]
    for k in ("loss_rel", "g0_dist"):
        assert r["compared"][k]["value"] > r["compared"][k]["limit"], (
            k, r["compared"])


def test_control_is_not_correct():
    """The reference in bfloat16 put in the program's place fails the
    cell's comparison, and passes the norms of the state, which hardly see
    the precision.  At this size the loss catches it; at full size on the
    chip, where the program's products take bfloat16 operands, ``g0_dist``
    does (PERF.md)."""
    jax.config.update("jax_enable_compilation_cache", False)
    spec = tiny.spec_with_open_cells()
    c, _ = harness.build_cell(spec, CELL, 5, jax.devices()[:1], *SIZE)
    c.setup()
    ctrl = c.readings("control")
    failed = {k for k, v in c.limits.items() if k in ctrl
              and not harness.Check(k, ctrl[k], v).ok}
    assert failed, (ctrl, c.limits)
    assert not failed & {"g0_gap", "change_gap"}, (ctrl, c.limits)
    for k in CPU_EXACT:
        assert ctrl[k] > 10 * CPU_EXACT[k], (k, ctrl)


def _zero_conv_bias(monkeypatch):
    """The program's mixer with its conv bias zeroed (the older model has
    none); the reference keeps it."""
    from repro.models import ssm
    orig = ssm.ssm_forward

    def no_bias(params, x, cfg, **kw):
        params = {k: (jnp.zeros_like(v) if k.endswith("_bias")
                      and k.startswith("conv_") else v)
                  for k, v in params.items()}
        return orig(params, x, cfg, **kw)

    monkeypatch.setattr(ssm, "ssm_forward", no_bias)


@pytest.mark.parametrize("departure", ("conv_bias_zeroed",
                                       "embedding_scaled"))
def test_older_departure_is_not_correct(monkeypatch, departure):
    config = None
    if departure == "conv_bias_zeroed":
        _zero_conv_bias(monkeypatch)
    else:          # the legacy rule for a tied embedding, made explicit
        config = {"embed_multiplier": math.sqrt(tiny.MAMBA["d_model"])}
    r = _run(config=config)
    assert not r["correct"], r["compared"]


def test_traced_run_reads_every_layer():
    r = _run(traced=True)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for name in ("fed.oracle_ms", "fed.compress_ms", "fed.server_ms",
                 "ssm.ssd_ms", "mfu.train", "fed.unscoped_share",
                 "idle_share.fed"):
        assert name in m, (name, sorted(m))
    assert 0 < m["ssm.ssd_ms"] < m["fed.oracle_ms"]
    assert m["mfu.train"] > 0
    assert r["correct"], r["compared"]


def test_inner_scope_attribution():
    names = {"f.1": "jit(s)/fed.oracle/while/body/ssm.ssd/exp",
             "f.2": "jit(s)/fed.oracle/transpose(jvp(ssm.ssd))/dot_general",
             "f.3": "jit(s)/fed.oracle/while/body/dot_general",
             "f.4": "jit(s)/fed.server/ssm.ssdx/add"}
    assert inner_scopes.under(names["f.2"], "ssm.ssd")
    assert not inner_scopes.under(names["f.4"], "ssm.ssd")
    dev = bt.DeviceOps("/device:TPU:0", [
        ("f.1", 0.0, 2.0, "f.1"), ("f.2", 1.5, 3.0, "f.2"),
        ("f.3", 3.0, 4.0, "f.3"), ("f.4", 4.0, 6.0, "f.4")])
    tr = bt.Trace([dev], [("bench.call", 0.0, 5.0)])
    # f.2 counts from where f.1 ended; f.4 is cut at the window's end
    assert inner_scopes.scope_seconds(tr, names, "ssm.ssd") == 3.0
