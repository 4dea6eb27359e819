"""Benchmark harness — one section per paper table/figure + roofline.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick]
Prints ``name,us_per_call,derived`` CSV at the end (harness contract).
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import kernel_bench, paper_experiments, roofline
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    csv_rows: list = []
    paper_experiments.run(csv_rows)
    kernel_bench.run(csv_rows)
    roofline.render(csv_rows)

    print("\n=== CSV (name,us_per_call,derived) ===")
    print("name,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == '__main__':
    main()
