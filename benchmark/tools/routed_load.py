"""A sparse cell's routed load by layer over the first steps of a run,
several seeds in one process.

    python3 benchmark/tools/routed_load.py --workload <cell> --seeds 1,2 \
        [--steps 46] [--short] [--rehearse]

Prints what the cell's driver yields from ``routed_load(cell, seeds, steps)``,
one JSON line per seed: each layer's ``routed_slots`` at every fifth step,
their smallest and largest over the steps, a step's total and the rows the
grouped products ran. ``--short`` keeps the configuration's widths and depth
and cuts the stream to 256 tokens a row and the vocabulary to 1,024 rows, on
whatever backend there is: where a load wanders because the weights do (a
few minutes on the CPU; counts, never a time). The benchmark's own runs never
come here.
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
    ap.add_argument("--steps", type=int, default=46)
    ap.add_argument("--short", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    from benchmark import run as bench_run

    cell = bench_run.load_cell(argparse.Namespace(
        workload=args.workload, seed=seeds[0], seconds=0, trace=0,
        rehearse=args.rehearse))
    if args.short:
        cell.rehearse = True
        cell.sizes["vocab_size"] = 1024
        cell.traffic["seq"] = 256
    bench_run.set_cache_env(cell.rehearse)
    from benchmark.lib import harness

    harness.device_record(cell.chips, cell.rehearse)
    driver = importlib.import_module(f"benchmark.drivers.{cell.driver}")
    for row in driver.routed_load(cell, seeds, args.steps):
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
