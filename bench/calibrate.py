"""Readings that set a cell's correctness limits (run on the chip).

    python3 bench/calibrate.py --workload <cell> --seeds 1-12 \
        --control-seeds 1-3 [--fault <name>[,<name>] --fault-seeds 1-3] \
        [--out f.json]

For each seed it builds the cell exactly as a benchmark run does, makes one
timed call, and reads every candidate number of the program against the
plain reference; for the control seeds it reads the control (the
reference in the next precision down, put in the program's place); for
the fault seeds, the program with one fault of ``bench/faults.py``
planted.  One process, so the compiled programs are shared.  The limits
in ``bench/limits/<cell>.json`` are set from these readings: above the
program's largest, below the smallest of the control and the faults.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness, spec as bspec  # noqa: E402


def _seeds(text):
    out = []
    for part in text.split(","):
        if "-" in part:
            lo, hi = part.split("-")
            out += range(int(lo), int(hi) + 1)
        elif part:
            out.append(int(part))
    return out


def readings(workload, seeds, which, devices, spec, fault=None):
    from bench import faults
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        cell, _ = harness.build_cell(spec, workload, seed, devices)
        with faults.planted(fault):
            cell.setup()
            cell.call()
        got = cell.readings(which)
        cell.release()
        del cell
        rows.append({"seed": seed, "which": which, "fault": fault,
                     "seconds": time.perf_counter() - t0, **got})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default=None,
                    help="comma-separated faults of bench/faults.py")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bspec.ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    spec = bspec.load()
    w = next(x for x in spec["workloads"] if x["name"] == args.workload)
    devices = harness.tpu_devices(w["chips"])[:w["chips"]]
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    rows = readings(args.workload, _seeds(args.seeds), "program", devices,
                    spec)
    rows += readings(args.workload, _seeds(args.control_seeds), "control",
                     devices, spec)
    for fault in filter(None, (args.fault or "").split(",")):
        rows += readings(args.workload, _seeds(args.fault_seeds), "program",
                         devices, spec, fault=fault)
    summary = {}
    for r in rows:
        key = r["fault"] or r["which"]
        for k, v in r.items():
            if k in ("seed", "which", "fault", "seconds"):
                continue
            lo, hi = summary.setdefault(key, {}).get(k, (v, v))
            summary[key][k] = (min(lo, v), max(hi, v))
    print(json.dumps({"summary (min, max)": summary}, indent=1))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows,
                                              "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
