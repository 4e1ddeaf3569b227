"""Where the device time of a benchmark cell's step goes, by scope.

    python tools/profile_cell.py --workload qwen3next-train-s8192 [--depth 3]

Builds the cell's program as ``benchmark/run.py`` does (its driver's
``Program``, batches from the cell's traffic), warms three steps, takes
``--steps`` more under ``paddle.profiler.Profiler`` and prints
``summary(layer_depth=--depth)``: device seconds by section, by layer path
(``layers.*/mixer/short_conv`` at depth 3) and by named kernel, then the
``mixer_pass``, ``gdn_chunks``, ``ssd_chunks``, ``mixer_share`` and
``moe_combine`` events the trace left. Chip only for times (through the
builder's chip tool); ``--rehearse`` drives the same flow at the cell's
rehearsal size on the CPU. Scope names are part of the compile cache's
key here, so a renamed scope shows at once.
"""
import argparse
import importlib
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--seed", type=int, default=7)
    a = ap.parse_args()
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)

    import jax

    import paddle_tpu as paddle
    from benchmark import run
    from benchmark.lib import harness, traffic

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    cell = run.load_cell(types.SimpleNamespace(
        workload=a.workload, seed=a.seed, seconds=1.0, trace=0,
        rehearse=a.rehearse))
    driver = importlib.import_module(f"benchmark.drivers.{cell.driver}")
    program = driver.Program(cell, harness.Setup(time.perf_counter()))
    ring = traffic.train_batches(cell.traffic, cell.seed,
                                 cell.sizes["vocab_size"])

    def steps(first, n):
        for batch in ring[first:first + n]:
            loss = program.step(*program.feed(batch))
        jax.block_until_ready(loss._value)

    steps(0, 3)
    with paddle.profiler.Profiler() as prof:
        steps(3, a.steps)
    prof.summary(layer_depth=a.depth)
    for kind in ("mixer_pass", "gdn_chunks", "ssd_chunks", "mixer_share",
                 "moe_combine"):
        for event in paddle.profiler.trace.events(kind=kind):
            print(kind, event.site, event.attrs)


if __name__ == "__main__":
    main()
