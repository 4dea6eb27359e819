"""The benchmark harness: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run builds the cell's timed path from its configuration's builder
(``bench/configs/<config>.py``) and traffic file, warms every shape the
window uses (set-up), calls the timed path back to back for ``--seconds``
(the window), reads the peak device memory, frees the program's state,
and then checks what the timed calls produced against the configuration's
plain reference.  ``--trace 1`` records a profiler trace of the window and
reports the cell's per-layer metrics instead of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``compared``: each number the correctness check
compared, with its limit.  The same numbers are the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from bench import spec as bspec

#: A traced window holds at most this many seconds of calls: traces grow
#: with every operation, and the trace is read inside the run's time.
TRACE_SECONDS_MAX = 10.0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with the plain reference, and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a metric reader sees: the cell, its window and its trace."""
    cell_name: str
    cell: Any
    chips: int
    setup_s: float
    window_s: float = 0.0
    durations: List[float] = dataclasses.field(default_factory=list)
    work: List[float] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    window_compiles: int = 0
    summary: Any = None                 # bench.trace.Summary when traced
    peaks: Optional[dict] = None


def peaks_for(kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; an unknown
    ``device_kind`` is an error, never a default."""
    table = bspec.read_json(bspec.BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


class CompileCounter:
    """Counts jaxpr traces and backend compiles (persistent-cache lookups
    included), and persistent-cache hits and misses."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.counts = {"trace": 0, "compile": 0, "hits": 0, "misses": 0}
        events = {dispatch.JAXPR_TRACE_EVENT: "trace",
                  dispatch.BACKEND_COMPILE_EVENT: "compile"}

        def on_duration(event, duration, **kw):
            if event in events:
                self.counts[events[event]] += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.counts["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.counts["misses"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def compiles(self) -> int:
        return self.counts["trace"] + self.counts["compile"]


def _find_cell(spec: dict, name: str):
    for w in spec["workloads"]:
        if w["name"] == name:
            conf = next(c for c in spec["configs"] if c["name"] == w["config"])
            return w, conf
    raise bspec.SpecError(f"no workload {name!r} in BENCHMARK.json")


def build_cell(spec: dict, workload: str, seed: int, devices,
               config_overrides: Optional[dict] = None,
               traffic_overrides: Optional[dict] = None):
    """The configuration builder's cell object for ``workload``."""
    w, conf = _find_cell(spec, workload)
    config = bspec.read_json(bspec.ROOT / conf["file"])
    config.update(config_overrides or {})
    traffic = bspec.read_json(bspec.traffic_path(w["traffic"]))
    traffic.update(traffic_overrides or {})
    limits = bspec.read_json(bspec.limits_path(workload))
    mod = bspec.load_module(bspec.config_module_path(conf["name"]),
                            "bench_config_" + conf["name"].replace(
                                "-", "_").replace(".", "_"))
    return mod.Cell(config=config, traffic=traffic, limits=limits,
                    seed=seed, devices=devices), w


def tpu_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"cell asks for {chips} chips; JAX found {len(devs)}")
    return devs


def peak_memory(devices) -> Optional[int]:
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             t_start: float, require_chip: bool = True,
             spec: Optional[dict] = None, config_overrides=None,
             traffic_overrides=None, peaks: Optional[dict] = None,
             trace_kw: Optional[dict] = None) -> Dict[str, Any]:
    """One run; returns the result object (``compared`` last).

    ``require_chip=False`` (tests) skips the look for a TPU and runs on
    whatever JAX has; ``peaks`` then stands in for ``bench/peaks.json`` and
    ``trace_kw`` tells the trace reduction which planes are devices."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    spec = spec or bspec.load()
    w, _ = _find_cell(spec, workload)
    devs = tpu_devices(w["chips"]) if require_chip else jax.devices()
    if not require_chip and len(devs) < w["chips"]:
        raise NoChip(f"cell asks for {w['chips']} devices; "
                     f"JAX found {len(devs)}")
    devices = devs[:w["chips"]]
    kind = devices[0].device_kind
    if peaks is None:
        peaks = peaks_for(kind)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    cell, _ = build_cell(spec, workload, seed, devices, config_overrides,
                         traffic_overrides)
    cell.setup()
    run = Run(workload, cell, w["chips"], setup_s=time.perf_counter()
              - t_start, peaks=peaks)
    setup_counts = dict(counter.counts)
    print(f"bench: setup {run.setup_s:.3f} s; compile cache "
          f"{setup_counts['hits']} hits, {setup_counts['misses']} misses",
          file=sys.stderr, flush=True)

    logdir = None
    window = min(seconds, TRACE_SECONDS_MAX) if traced else seconds
    before = counter.compiles()
    if traced:
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(logdir)
    try:
        t0 = time.perf_counter()
        while True:
            c0 = time.perf_counter()
            run.attempted += 1
            try:
                with jax.profiler.TraceAnnotation("bench.call"):
                    work, ok = cell.call()
            except Exception as e:               # a failed call, counted
                print(f"bench: call {run.attempted} raised "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
                work, ok = 0.0, False
            c1 = time.perf_counter()
            run.durations.append(c1 - c0)
            run.work.append(work if ok else 0.0)
            run.failed += 0 if ok else 1
            if c1 - t0 >= window:
                break
        run.window_s = c1 - t0
    finally:
        if traced:
            jax.profiler.stop_trace()
    run.window_compiles = counter.compiles() - before
    mem = peak_memory(devices)
    if traced:
        from bench import trace as btrace
        try:
            tr = btrace.load(logdir, **(trace_kw or {}))
            run.summary = btrace.summarize(tr, kernels=cell.kernels())
        finally:
            shutil.rmtree(logdir, ignore_errors=True)

    metrics = {}
    for m in bspec.cell_metrics(spec, workload, traced):
        reader = bspec.load_module(bspec.reader_path(m["name"]),
                                   "bench_metric_" + m["name"].replace(
                                       ".", "_").replace("-", "_"))
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    cell.release()
    checks = [Check("window_compiles", float(run.window_compiles), 0.0),
              Check("failed_calls", float(run.failed), 0.0)]
    t_check = time.perf_counter()
    checks += [Check(*c) for c in cell.check()]
    print(f"bench: window {run.window_s:.3f} s, {run.attempted} calls; "
          f"reference check {time.perf_counter() - t_check:.3f} s",
          file=sys.stderr, flush=True)
    correct = run.attempted > 0 and all(c.ok for c in checks)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if traced:
        s = run.summary
        device["busy_s"] = s.busy_s
        device["window_s"] = s.window_s
        result["breakdown"] = {"device_ops": [list(x) for x in s.top_ops],
                               "idle_gaps": [list(x)
                                             for x in s.idle_by_span]}
    result["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                          for c in checks}
    return result


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    if not (bspec.ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {bspec.ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(bspec.ROOT / "src"))
    try:
        spec = bspec.load()
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start, spec=spec)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except bspec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
