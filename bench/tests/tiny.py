"""Small sizes at which the cells run on the CPU in tests."""
import sys
import time

from bench import spec as bspec

sys.path.insert(0, str(bspec.ROOT / "src"))

XSILO = {"n_clients": 4, "samples_per_client": 16, "d": 64}
MAMBA = {"d_model": 64, "n_layer": 2, "vocab_size": 256, "d_state": 16,
         "headdim": 16, "chunk_size": 8}
SIZES = {
    "xsilo.flecs-cgd": (XSILO, {"rounds_per_call": 2}),
    "xsilo.diana": (XSILO, {"rounds_per_call": 8}),
    "mamba2.cgd": (MAMBA, {"batch_per_worker": 2, "seq_len": 32}),
    "mamba2.cgd-dp4": (MAMBA, {"batch_per_worker": 2, "seq_len": 32}),
}
#: Stand-in peaks for a CPU run: the table holds only real chips.
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
#: The CPU client's threads stand in for a device in a CPU trace.
CPU_TRACE = {"device_plane": lambda name: name == "/host:CPU",
             "op_line": lambda name: name.startswith("tf_XLA")}


def spec_with_open_cells() -> dict:
    """BENCHMARK.json with the cells not yet proved on the chip added, with
    the configurations and metrics only they use (``open_cells.json``;
    PERF.md, open questions).  Their files and CPU tests stay for the PR
    that proves them."""
    spec = bspec.load()
    extra = bspec.read_json(bspec.BENCH / "tests" / "open_cells.json")
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        spec[key] += extra[key]
    for cell, metrics in extra["reports"].items():
        for m in spec["end_to_end"] + spec["per_layer"]:
            if m["name"] in metrics:
                m["workloads"].append(cell)
    return bspec.validate(spec)


def run(cell, fault=None, seed=2**31 + 7, traced=False, seconds=0.5):
    """One harness run of ``cell`` at its small size, skipping the look
    for a chip, with ``fault`` planted under the timed path."""
    import jax
    from bench import faults, harness
    # tests leave the persistent compile cache alone
    jax.config.update("jax_enable_compilation_cache", False)
    config, traffic = SIZES[cell]
    with faults.planted(fault):
        return harness.run_cell(
            cell, seed, seconds, traced, time.perf_counter(),
            require_chip=False, spec=spec_with_open_cells(),
            config_overrides=config,
            traffic_overrides=traffic, peaks=CPU_PEAKS,
            trace_kw=CPU_TRACE)
