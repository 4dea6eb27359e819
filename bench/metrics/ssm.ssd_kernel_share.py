"""Per cent of the device time under the program's ``ssm.ssd`` scope (the
chunked SSD scan: forward, remat recompute, backward) that runs in Mosaic
kernels, the ``custom_call_target="tpu_custom_call"`` instructions of the
compiled program: the Pallas SSD intra-chunk kernel, forward and backward.
The rest is what XLA still runs of the scan.

Both sums come from one traced window of the reader's own, attributed as
``bench/inner_scopes.py`` attributes (each moment of device 0's busy time
to one op).  Nothing where the program has no such scope; 0, with no
window traced, where no Mosaic kernel sits under it."""
from __future__ import annotations

import re
import shutil
import sys
import tempfile
import time
from typing import Dict, Optional, Set

from bench import inner_scopes, scopes
from bench import trace as bt

SCOPE = "ssm.ssd"
MOSAIC = 'custom_call_target="tpu_custom_call"'
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s*=")


def mosaic_instructions(hlo_text: str) -> Set[str]:
    """Names of the Mosaic custom-call instructions of an HLO text."""
    out = set()
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and MOSAIC in line:
            out.add(m.group(1))
    return out


def share(trace: bt.Trace, names: Dict[str, str], kernels: Set[str]
          ) -> Optional[float]:
    """Per cent of the window's device-0 time under ``SCOPE`` spent in
    ``kernels``; None where nothing ran under it."""
    total = inner_scopes.scope_seconds(trace, names, SCOPE)
    if total <= 0:
        return None
    mosaic = inner_scopes.scope_seconds(
        trace, {i: n for i, n in names.items() if i in kernels}, SCOPE)
    return 100.0 * mosaic / total


def read(run):
    cell = run.cell
    compiled = getattr(cell, "compiled", None)
    if run.summary is None or compiled is None:
        return None
    text = compiled.as_text()
    names = scopes.op_names(text)
    if not any(inner_scopes.under(n, SCOPE) for n in names.values()):
        return None
    kernels = {i for i in mosaic_instructions(text)
               if inner_scopes.under(names.get(i, ""), SCOPE)}
    if not kernels:
        return 0.0
    import jax
    planes = scopes.CPU_PLANES if cell.devices[0].platform == "cpu" else {}
    logdir = tempfile.mkdtemp(prefix="bench_ssd_kernel_share_")
    calls = 0
    t0 = time.perf_counter()
    jax.profiler.start_trace(logdir)
    try:
        while calls < scopes.CALLS_MIN or (
                calls < scopes.CALLS_MAX
                and time.perf_counter() - t0 < scopes.WINDOW_S):
            with jax.profiler.TraceAnnotation(bt.SPAN_PREFIX + "call"):
                cell.call()
            calls += 1
    finally:
        jax.profiler.stop_trace()
    try:
        got = share(scopes.load(logdir, **planes), names, kernels)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    print(f"ssd_kernel_share: {got} % of {SCOPE} in {len(kernels)} Mosaic "
          f"kernels ({calls} calls traced)", file=sys.stderr)
    return got
