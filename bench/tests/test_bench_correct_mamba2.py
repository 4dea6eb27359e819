"""``correct`` in the one-chip training cell: true on a sound run, false
with a fault under the timed path, and false for the control."""
import pytest

from bench.tests import tiny
from bench.tests.control import control_fails


def test_sound_run_is_correct():
    r = tiny.run("mamba2.cgd")
    assert r["correct"], r["compared"]
    assert r["compared"]["uplink_gap"]["value"] == 0


@pytest.mark.parametrize("fault", ("state_unchanged", "half_batch"))
def test_fault_is_caught(fault):
    r = tiny.run("mamba2.cgd", fault=fault)
    assert not r["correct"], r["compared"]


def test_control_is_not_correct():
    assert control_fails("mamba2.cgd")
