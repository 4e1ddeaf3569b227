"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that builds the cell from its files (``workloads/<cell>.json``,
the configuration file ``BENCHMARK.json`` names, ``drivers/<driver>.py``,
``metrics/<metric>.py``), makes weights and traffic from the seed, warms the
cell's shapes, measures for ``--seconds`` and prints one JSON line last.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics. Without a TPU it exits non-zero and prints no result;
``--rehearse`` runs the same control flow at the cell's tiny rehearsal size on
whatever backend there is and names that backend in ``device``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(args):
    """Everything the cell's files say, before JAX is touched."""
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    spec = load_json(HERE, "workloads", args.workload + ".json")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    sizes = load_json(ROOT, config["file"])
    traffic = dict(spec["traffic"])
    if args.rehearse:
        sizes.update(spec["rehearse"]["sizes"])
        traffic.update(spec["rehearse"]["traffic"])
    kind = "per_layer" if args.trace else "end_to_end"
    wanted = [m for m in bench[kind]
              if args.workload in m.get("workloads", [args.workload])]
    for m in wanted:
        path = os.path.join(HERE, "metrics", m["name"] + ".py")
        if not os.path.exists(path):
            raise SystemExit(f"metric {m['name']} has no reader at {path}")
    if not os.path.exists(os.path.join(HERE, "drivers",
                                       spec["driver"] + ".py")):
        raise SystemExit(f"no driver {spec['driver']!r}")
    return types.SimpleNamespace(
        name=args.workload, root=ROOT, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearse=args.rehearse,
        chips=entry["chips"], sizes=sizes,
        traffic=traffic, limits=spec["limits"], driver=spec["driver"],
        wanted=wanted, t_process=T_PROCESS)


def set_cache_env(rehearse=False):
    """Every program, small ones too, stays in the persistent compile cache,
    at the fixed path the program itself would choose. A rehearsal on the
    CPU keeps no cache: XLA:CPU reloads its entries with warnings."""
    if rehearse:
        os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
        return
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on whatever backend is there")
    args = ap.parse_args(argv)
    cell = load_cell(args)
    set_cache_env(args.rehearse)
    sys.path.insert(0, ROOT)
    from benchmark.lib import harness

    driver = importlib.import_module(f"benchmark.drivers.{cell.driver}")
    try:
        record = driver.run(cell)
    except harness.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    record.update(sizes=cell.sizes, traffic=cell.traffic, chips=cell.chips)

    metrics = {}
    for m in cell.wanted:
        reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
        try:
            value = reader.read(record)
        except KeyError as e:  # an unknown device has no peaks
            if not cell.rehearse:
                raise
            print(f"rehearsal: {m['name']} left out: {e}")
            continue
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(record["device"],
                  memory_peak_bytes=record["memory_peak_bytes"])
    line = {"correct": record["checks"].ok,
            "attempted": record["attempted"], "failed": record["failed"],
            "metrics": metrics, "device": device}
    reduced = record.get("trace")
    if reduced:
        from benchmark.lib import xplane

        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = xplane.breakdown(reduced)
    line["checks"] = record["checks"].as_dict()
    sys.stdout.flush()
    for text in record["checks"].lines():
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
