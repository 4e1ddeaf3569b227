"""Readings that a cell's limits are set from, several seeds in one process.

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,3,... \
        [--controls 3] [--rehearse]

Prints what the cell's driver yields from ``calibrate(cell, seeds,
n_controls)``, one JSON line per seed: the program's numbers against the
reference (the lower readings) and, for the first ``--controls`` seeds, the
control's and the planted faults' (the upper readings). The benchmark's own
runs never come here.
"""
import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    from benchmark import run as bench_run

    cell = bench_run.load_cell(argparse.Namespace(
        workload=args.workload, seed=seeds[0], seconds=0, trace=0,
        rehearse=args.rehearse))
    bench_run.set_cache_env(args.rehearse)
    from benchmark.lib import harness

    harness.device_record(cell.chips, cell.rehearse)
    driver = importlib.import_module(f"benchmark.drivers.{cell.driver}")
    for row in driver.calibrate(cell, seeds, args.controls):
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
