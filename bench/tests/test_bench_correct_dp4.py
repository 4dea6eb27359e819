"""``correct`` in the four-worker training cell (not yet in
BENCHMARK.json), on four virtual CPU devices in a process of its own: true
on a sound run, false with a fault under the timed path (the exchange
between chips among them)."""
import json
import os
import subprocess
import sys

from bench.spec import ROOT

SCRIPT = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
from bench.tests import tiny
out = {}
for fault in (None, "state_unchanged", "half_batch", "exchange_dropped"):
    r = tiny.run("mamba2.cgd-dp4", fault=fault)
    out[str(fault)] = [r["correct"], r["compared"]]
print(json.dumps(out))
"""


def test_dp4_sound_and_faults():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["None"][0], got["None"][1]
    for fault in ("state_unchanged", "half_batch", "exchange_dropped"):
        assert not got[fault][0], (fault, got[fault][1])
