"""What of a sparse cell's gaps is routing and what is rounding, several
seeds in one process.

    python3 benchmark/tools/routing.py --workload <cell> --seeds 1,2,3 \
        [--rehearse]

Prints what the cell's driver yields from ``routing(cell, seeds)``, one JSON
line per seed: the share of the first step's expert choices that differ from
the reference's, for the program and for the control, and the first step's
numbers with each side routing for itself and with both sides of a comparison
given the same choice. The benchmark's own runs never come here.
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
    for row in driver.routing(cell, seeds):
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
