"""The share of the SSD scan's device time in Mosaic kernels: which
instructions are Mosaic kernels, the attribution of a trace, and what the
reader reports for a program with no kernel or no scope."""
import pytest

from bench import harness, scopes, spec as bspec, trace as bt

HLO = """HloModule jit_step, entry_computation_layout={()->f32[8]{0}}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(step)/fed.oracle/ssm.ssd/cumsum"}
  %jvp_ssm.ssd_.2 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fed.oracle/jvp(ssm.ssd)/pallas_call"}
  %ssm.ssd.3 = (f32[8]{0}, f32[8]{0}) custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fed.oracle/transpose(jvp(ssm.ssd))/ssm.ssd/pallas_call"}
  %custom-call.4 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fed.compress.grad/pallas_call"}
  %dot.5 = f32[8]{0} dot(%x, %x), metadata={op_name="jit(step)/fed.oracle/transpose(jvp(ssm.ssd))/dot_general"}
  ROOT %fusion.6 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(step)/fed.server/mul"}
}
"""

#: (instruction, start, end) on device 0.
EVENTS = [("fusion.1", 0.0, 1.0), ("jvp_ssm.ssd_.2", 1.0, 3.0),
          ("ssm.ssd.3", 2.5, 6.0), ("custom-call.4", 6.0, 7.0),
          ("dot.5", 7.0, 8.0), ("fusion.6", 8.0, 9.0)]


def _reader():
    return bspec.load_module(bspec.reader_path("ssm.ssd_kernel_share"),
                             "t_ssm_ssd_kernel_share")


def _trace():
    dev = bt.DeviceOps("/device:TPU:0", [
        (f"%{n} = f32[8] op()", s, e, n) for n, s, e in EVENTS])
    return bt.Trace([dev], [("bench.call", 0.0, 9.0)])


class _Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


class _Cell:
    def __init__(self, text):
        self.compiled = _Compiled(text)


def test_mosaic_instructions_of_an_hlo_text():
    assert _reader().mosaic_instructions(HLO) == {
        "jvp_ssm.ssd_.2", "ssm.ssd.3", "custom-call.4"}


def test_share_of_the_scope_in_its_kernels():
    r = _reader()
    names = scopes.op_names(HLO)
    kernels = {"jvp_ssm.ssd_.2", "ssm.ssd.3"}
    # under ssm.ssd: 1 + 2 + 3 (from 3.0, where the forward kernel ended)
    # + 1 s; in its kernels 2 + 3 s; the compressor kernel is not under it
    assert r.share(_trace(), names, kernels) == pytest.approx(100 * 5 / 7)
    assert r.share(_trace(), names, set()) == 0.0
    empty = bt.Trace([bt.DeviceOps("/device:TPU:0", [])],
                     [("bench.call", 0.0, 9.0)])
    assert r.share(empty, names, kernels) is None


def test_reader_without_kernel_scope_or_trace():
    """0 with no window traced where the scope holds no Mosaic kernel (an
    XLA-only scan); nothing without the scope or the trace."""
    r = _reader()
    xla_only = HLO.replace('custom_call_target="tpu_custom_call", '
                           'metadata={op_name="jit(step)/fed.oracle/',
                           'metadata={op_name="jit(step)/fed.oracle/')
    traced = lambda text: harness.Run("mamba2.flecs-cgd",  # noqa: E731
                                      _Cell(text), 1, setup_s=1.0,
                                      summary=object())
    assert r.read(traced(xla_only)) == 0.0
    assert r.read(traced(HLO.replace("ssm.ssd", "ssm.scan"))) is None
    assert r.read(harness.Run("mamba2.flecs-cgd", _Cell(HLO), 1,
                              setup_s=1.0)) is None
