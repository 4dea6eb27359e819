"""The data-driven harness: everything found by name, the rules of
BENCHMARK.json enforced, and a run refused without a chip."""
import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, spec as bspec

from bench.tests import tiny


@pytest.fixture(scope="module")
def spec():
    return bspec.load()


def test_open_cells_keep_the_rules():
    spec = tiny.spec_with_open_cells()
    assert {w["name"] for w in spec["workloads"]} >= {
        "xsilo.diana", "mamba2.cgd", "mamba2.cgd-dp4"}


def test_every_name_finds_its_files(spec):
    for c in spec["configs"]:
        assert (bspec.ROOT / c["file"]).is_file()
        assert bspec.config_module_path(c["name"]).is_file()
    for w in spec["workloads"]:
        assert bspec.traffic_path(w["traffic"]).is_file()
        assert bspec.limits_path(w["name"]).is_file()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert hasattr(bspec.load_module(bspec.reader_path(m["name"]),
                                         "r_" + m["name"].replace(".", "_")),
                       "read")


def test_each_cell_reports_setup_another_metric_and_a_layer(spec):
    for w in spec["workloads"]:
        e2e = [m["name"] for m in bspec.cell_metrics(spec, w["name"], False)]
        layer = bspec.cell_metrics(spec, w["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2 and layer


def _broken(spec, edit):
    bad = copy.deepcopy(spec)
    edit(bad)
    with pytest.raises(bspec.SpecError):
        bspec.validate(bad)


def test_rules_refuse_a_broken_spec(spec):
    _broken(spec, lambda b: b["per_layer"][0].pop("workloads"))
    _broken(spec, lambda b: b["per_layer"][0].update(unit="per cent"))
    _broken(spec, lambda b: b["end_to_end"][0].update(unit="tokens per s"))
    _broken(spec, lambda b: b["per_layer"][0].update(name="mfu/fed"))
    _broken(spec, lambda b: b["workloads"][0].update(name="a cell"))
    _broken(spec, lambda b: b["per_layer"][0].update(name="no_reader_x"))
    _broken(spec, lambda b: b["per_layer"][0].update(why="a key too many"))
    _broken(spec, lambda b: b["workloads"][0].update(traffic="no_such"))
    _broken(spec, lambda b: b["workloads"][0].update(chips=2))
    _broken(spec, lambda b: b["end_to_end"][0].update(bound=0.3))
    _broken(spec, lambda b: b["per_layer"][0].update(moves="not_e2e"))
    _broken(spec, lambda b: b["configs"][0].update(name="no-builder"))
    _broken(spec, lambda b: b.update(run_seconds=52))
    _broken(spec, lambda b: b["end_to_end"].pop(
        [m["name"] for m in b["end_to_end"]].index("setup_s")))


def test_result_line_keys_untraced_and_traced():
    plain = tiny.run("xsilo.diana")
    assert list(plain) == ["correct", "attempted", "failed", "metrics",
                           "device", "compared"]
    assert set(plain["metrics"]) == {"rounds_per_s", "setup_s"}
    assert plain["correct"] is True and plain["attempted"] >= 1
    assert set(plain["device"]) == {"platform", "kind", "count",
                                    "memory_peak_bytes"}
    traced = tiny.run("xsilo.diana", traced=True)
    assert list(traced) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "compared"]
    assert set(traced["metrics"]) <= {"mfu.fed", "compressor.roofline_share",
                                      "idle_share.fed"}
    assert "mfu.fed" in traced["metrics"]
    assert traced["device"]["busy_s"] > 0
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(traced["breakdown"]["device_ops"]) <= 10


def _run_command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "xsilo.flecs-cgd",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing():
    out = _run_command(bspec.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(bspec.ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((bspec.ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(bspec.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_command(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_too_few_devices_is_refused():
    with pytest.raises(harness.NoChip):
        tiny.run("mamba2.cgd-dp4")
