"""The trace reduction, on intervals and on a trace the test records."""
import json
import os
import subprocess
import sys

import pytest

from bench import trace as bt
from bench.spec import ROOT


def test_union_gaps_subtract():
    busy = bt.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert busy == [(0, 3), (5, 7)]
    assert bt.total(busy) == 5
    assert bt.gaps(busy, -1, 10) == [(-1, 0), (3, 5), (7, 10)]
    assert bt.subtract([(0, 10)], [(2, 3), (5, 8)]) == [(0, 2), (3, 5),
                                                         (8, 10)]
    assert bt.clip([(0, 4), (6, 9)], 1, 7) == [(1, 4), (6, 7)]


def test_leaf_events_drop_enclosing_ops():
    evs = [("while.1", 0.0, 10.0, ""), ("fusion.1", 1.0, 2.0, ""),
           ("all-reduce.1", 3.0, 6.0, ""), ("fusion.2", 4.0, 5.0, ""),
           ("copy.1", 11.0, 12.0, "")]
    assert [e[0] for e in bt.leaf_events(evs)] == [
        "fusion.1", "all-reduce.1", "fusion.2", "copy.1"]


def test_summary_kernels_collectives_and_named_gaps():
    dev = bt.DeviceOps("/device:TPU:0", [
        ("fusion.1", 0.0, 1.0, ""),
        ("custom-call.3", 1.0, 1.5, "_fused_dither_kernel"),
        ("all-reduce.2", 2.0, 3.0, ""),
        ("fusion.4", 2.5, 2.75, ""),
        ("fusion.5", 4.0, 5.0, "")])
    spans = [("bench.call", 0.0, 5.0), ("bench.fetch", 3.0, 4.0)]
    s = bt.summarize(bt.Trace([dev], spans),
                     kernels={"compressor": ("fused_dither",)})
    assert s.window_s == 5.0
    assert s.busy_s == 3.5                     # idle: [1.5, 2] and [3, 4]
    assert s.kernel_s == {"compressor": 0.5}
    assert s.kernel_events == {"compressor": 1}
    assert s.allreduce_s == 1.0
    assert s.allreduce_exposed_s == 0.75       # fusion.4 hides a quarter
    assert dict(s.top_ops)["fusion.1"] == 1.0
    assert bt.op_name("%fusion.9 = f32[8]{0:T(1024)S(1)} fusion(f32[8]{0} "
                      "%p)") == "%fusion.9 = f32[8] fusion(f32[8] %p)"
    idle = dict(s.idle_by_span)
    assert idle["bench.fetch (1 gaps)"] == 1.0
    assert idle["bench.call (1 gaps)"] == 0.5


RECORD = r"""
import json, sys, tempfile
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from bench import trace as bt
f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
x = jnp.ones((512, 512))
f(x).block_until_ready()
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
for _ in range(3):
    with jax.profiler.TraceAnnotation("bench.call"):
        f(x).block_until_ready()
jax.profiler.stop_trace()
tr = bt.load(d, device_plane=lambda n: n == "/host:CPU",
             op_line=lambda n: n.startswith("tf_XLA"))
s = bt.summarize(tr, kernels={"dot": ("dot",)})
print(json.dumps({"spans": len([n for n, _, _ in tr.spans
                                if n == "bench.call"]),
                  "window": s.window_s, "busy": s.busy_s,
                  "dot": s.kernel_s["dot"], "dot_n": s.kernel_events["dot"],
                  "ops": [n for n, _ in s.top_ops],
                  "idle": [n for n, _ in s.idle_by_span]}))
"""


def test_reduction_of_a_recorded_cpu_trace():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", RECORD, str(ROOT)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["spans"] == 3
    assert 0 < got["busy"] <= got["window"]
    assert got["dot_n"] >= 3 and 0 < got["dot"] <= got["busy"]
    assert any("dot" in n for n in got["ops"])
    assert all(n.startswith(("bench.", "host.")) for n in got["idle"])


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        bt.summarize(bt.Trace([], [("bench.call", 0.0, 1.0)]))
