"""Device time by round phase: the HLO map, the attribution of a trace to
the program's scopes, the readers, and a traced CPU run of the cell."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness, scopes, spec as bspec, trace as bt
from bench.spec import ROOT
from bench.tests import tiny

HLO = """HloModule jit_program, entry_computation_layout={()->f32[8]{0}}

FileNames
1 "program.py"

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %sine.1 = f32[8]{0} sine(%param_0), metadata={op_name="jit(program)/vmap()/while/body/closed_call/fed.compress.hess/compress.count_sketch/sin" stack_frame_id=1}
}

%region_0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="reduce_sum"}
  %b = f32[] parameter(1), metadata={op_name="reduce_sum"}
  ROOT %add.2 = f32[] add(%a, %b), metadata={op_name="jit(program)/fed.record/reduce_sum"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %dot.1 = f32[8]{0} dot(%x, %x), metadata={op_name="jit(program)/while/body/transpose(jvp(fed.oracle))/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%dot.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(program)/while/body/fed.compress.hess/compress.count_sketch/sin"}
  %fusion.4 = f32[8]{0} fusion(%dot.1), kind=kLoop, calls=%fused_computation
  %reduce.5 = f32[] reduce(%fusion.3, %x), dimensions={0}, to_apply=%region_0
  %copy.6 = f32[8]{0} copy(%x)
  %fusion.7 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(program)/fed.compress.grad/compress.dither/floor"}
  %fusion.8 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(program)/fed.server/mul"}
  %sort.11 = f32[8]{0} sort(%x), dimensions={0}, to_apply=%region_0
  %copy.12 = f32[8]{0} copy(%sort.11)
  %fusion.13 = f32[8]{0} fusion(%copy.12), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(program)/fed.compress.grad/compress.topk/sort"}
  %tuple.14 = (f32[8]{0}, f32[8]{0}) tuple(%copy.6, %fusion.8)
  ROOT %fusion.9 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(program)/fed.curvature/add"}
}
"""

#: (instruction, start, end) on device 0; the host spans around them.
EVENTS = [("dot.1", 0.0, 1.0), ("fusion.3", 1.0, 3.0),
          ("fusion.4", 2.5, 4.0), ("fusion.7", 4.5, 5.0),
          ("copy.6", 5.0, 5.5), ("reduce.5", 6.0, 6.25),
          ("fusion.8", 6.25, 7.0), ("fusion.9", 7.0, 8.0),
          ("copy.10", 9.0, 9.5)]
SPANS = [("bench.call", 0.0, 10.0), ("bench.fetch", 8.0, 9.5)]


def _trace():
    dev = bt.DeviceOps("/device:TPU:0", [
        (f"%{n} = f32[8] op()", s, e, n) for n, s, e in EVENTS])
    return bt.Trace([dev], SPANS)


def test_scope_of_takes_off_transformations():
    assert scopes.scope_of(
        "jit(p)/vmap()/while/body/transpose(jvp(fed.oracle))/dot") == (
            "fed.oracle", None, None)
    assert scopes.scope_of(
        "jit(p)/fed.compress.hess/compress.count_sketch/jit(sort)/sort") == (
            "fed.compress", "hess", "count_sketch")
    assert scopes.scope_of("jit(p)/fed.server/compress.dither/floor") == (
        "fed.server", None, "dither")
    assert scopes.scope_of("jit(p)/while/body/dynamic_update_slice") == (
        None, None, None)


def test_hlo_map_fusions_and_fallback():
    names = scopes.op_names(HLO)
    assert "fed.compress.hess" in names["fusion.3"]
    # no metadata of its own: the first scoped op of what it calls
    assert "compress.count_sketch" in names["fusion.4"]
    assert "fed.record" in names["reduce.5"]
    # made by a pass: the scope of its own computation, else its consumer's
    assert "fed.record" in names["sort.11"]
    assert "compress.topk" in names["copy.12"]
    assert "copy.6" not in names                   # a tuple is no consumer
    assert names["x"] == "x"


def test_instruction_from_stat_or_event_name():
    assert scopes.instruction("anything", [("hlo_op", "fusion.3"),
                                           ("hlo_module", "jit_p")]) == (
        "fusion.3")
    assert scopes.instruction("%fusion.916 = f32[8]{0} fusion(%p)", []) == (
        "fusion.916")
    assert scopes.instruction("dot_general.1", None) == "dot_general.1"


def test_attribution_adds_up_to_busy_and_names_gaps():
    tr = _trace()
    s = scopes.by_scope(tr, scopes.op_names(HLO))
    assert s.window_s == 10.0 and s.busy_s == 7.5
    assert s.scope_s == {"fed.oracle": 1.0, "fed.compress": 3.5,
                         "fed.record": 0.25, "fed.server": 0.75,
                         "fed.curvature": 1.0, "unscoped": 1.0}
    assert sum(s.scope_s.values()) == pytest.approx(s.busy_s)
    assert s.branch_s == {("hess", "count_sketch"): 3.0,
                          ("grad", "dither"): 0.5}
    assert dict(s.top_unscoped) == {"copy.6": 0.5, "copy.10": 0.5}
    idle = dict(s.idle_by_span)
    assert idle == {"bench.fetch (1 gaps)": 1.0,
                    "bench.call/fed.compress (1 gaps)": 0.5,
                    "bench.call/fed.record (1 gaps)": 0.5,
                    "bench.call (1 gaps)": 0.5}
    # the same trace through the harness's reduction: same busy and idle
    plain = bt.summarize(tr)
    assert plain.busy_s == s.busy_s
    assert sum(v for _, v in plain.idle_by_span) == pytest.approx(
        sum(idle.values()))


def test_selected_branches_from_traffic_files():
    read = lambda name: bspec.read_json(bspec.traffic_path(name))  # noqa
    assert scopes.selected_branches(read("flecs-cgd-m8-t2")) == {
        ("grad", "dither"), ("grad", "topk"), ("hess", "dither")}
    assert scopes.selected_branches(read("diana-t16")) == {
        ("grad", "dither")}
    assert scopes.selected_branches(read("cgd-b8-s2048")) is None
    assert scopes.family_of("count_sketch64") == "count_sketch"
    with pytest.raises(ValueError):
        scopes.family_of("gzip")


class _Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


class _Cell:
    def __init__(self, hlo):
        self.compiled = _Compiled(hlo)
        self.traffic = bspec.read_json(bspec.traffic_path("flecs-cgd-m8-t2"))

    def call(self):
        raise AssertionError("the readers traced a program without scopes")


def _reader(name):
    return bspec.load_module(bspec.reader_path(name),
                             "t_" + name.replace(".", "_"))


NEW = ("fed.oracle_ms", "fed.compress_ms", "fed.curvature_ms",
       "fed.server_ms", "fed.record_ms", "fed.unscoped_share",
       "compress.selected_share")


def test_readers_on_a_synthetic_run(monkeypatch):
    s = scopes.by_scope(_trace(), scopes.op_names(HLO))
    s.rounds = 4.0
    run = harness.Run("xsilo.flecs-cgd", _Cell(HLO), 1, setup_s=1.0,
                      summary=object())
    monkeypatch.setattr(scopes, "summary", lambda r: s if r is run else None)
    got = {n: _reader(n).read(run) for n in NEW}
    assert got["fed.oracle_ms"] == pytest.approx(250.0)
    assert got["fed.compress_ms"] == pytest.approx(875.0)
    assert got["fed.curvature_ms"] == pytest.approx(250.0)
    assert got["fed.server_ms"] == pytest.approx(187.5)
    assert got["fed.record_ms"] == pytest.approx(62.5)
    assert got["fed.unscoped_share"] == pytest.approx(100.0 / 7.5)
    # selected: (grad, dither) 0.5 of fed.compress's 3.5
    assert got["compress.selected_share"] == pytest.approx(100.0 / 7.0)
    five = sum(got[n] for n in NEW[:5])
    assert five + 1e3 * s.scope_s["unscoped"] / s.rounds == pytest.approx(
        1e3 * s.busy_s / s.rounds)


def test_readers_report_nothing_without_scopes_or_trace():
    plain = HLO.replace("fed.", "step.")
    traced = harness.Run("xsilo.flecs-cgd", _Cell(plain), 1, setup_s=1.0,
                         summary=object())
    untraced = harness.Run("xsilo.flecs-cgd", _Cell(HLO), 1, setup_s=1.0)
    for run in (traced, untraced):
        for n in NEW:
            assert _reader(n).read(run) is None, n


def test_setup_compile_reader(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.launch import compile_cache
    compile_cache.start_compile_clock()
    jax.jit(lambda x: jnp.cos(x) * 2.5).lower(jnp.ones((3, 11))).compile()
    run = harness.Run("xsilo.flecs-cgd", None, 1, setup_s=1.0)
    assert _reader("setup.compile_s").read(run) > 0
    monkeypatch.delattr(compile_cache, "compile_seconds")
    assert _reader("setup.compile_s").read(run) is None


RECORD = r"""
import json, sys, tempfile
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from bench import scopes

def f(x):
    with jax.named_scope("fed.oracle"):
        y = x @ x
    with jax.named_scope("fed.server"):
        return jnp.tanh(y).sum(axis=0)

x = jnp.ones((512, 512))
c = jax.jit(f).lower(x).compile()
c(x).block_until_ready()
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
for _ in range(3):
    with jax.profiler.TraceAnnotation("bench.call"):
        c(x).block_until_ready()
jax.profiler.stop_trace()
s = scopes.by_scope(scopes.load(d, **scopes.CPU_PLANES),
                    scopes.op_names(c.as_text()))
print(json.dumps({"scope_s": s.scope_s, "busy": s.busy_s,
                  "window": s.window_s}))
"""


def test_attribution_of_a_recorded_cpu_trace():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", RECORD, str(ROOT)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["scope_s"]["fed.oracle"] > 0
    assert got["scope_s"]["fed.server"] > 0
    assert sum(got["scope_s"].values()) == pytest.approx(got["busy"])
    assert 0 < got["busy"] <= got["window"]


def test_traced_cell_reports_every_new_metric():
    result = tiny.run("xsilo.flecs-cgd", traced=True)
    assert result["correct"] is True
    metrics = result["metrics"]
    for name in NEW + ("setup.compile_s", "mfu.fed", "idle_share.fed"):
        assert name in metrics, name
    assert 0 <= metrics["compress.selected_share"]["value"] <= 100
    assert 0 <= metrics["fed.unscoped_share"]["value"] <= 100
    assert metrics["fed.compress_ms"]["value"] > 0
    assert metrics["setup.compile_s"]["value"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    (_run, s), = scopes._CACHE.values()
    assert sum(s.scope_s.values()) == pytest.approx(s.busy_s)
    five = sum(metrics[n]["value"] for n in NEW[:5])
    unscoped = 1e3 * s.scope_s.get("unscoped", 0.0) / s.rounds
    assert five + unscoped == pytest.approx(1e3 * s.busy_s / s.rounds)
