"""Static cost of the flash attention kernels, without a chip.

Compiles forward and backward of ``flash_attention`` for a described v5e with
the TPU compiler's bundle dumps on, and prints for each of the three kernels
its scheduled VLIW bundles, the bundles of each predicated region (a kernel
whose grid has several blocks holds one copy of its walk per block offset),
and the least bundles each unit of the core would need for the work (MXU
occupancy, vector ALU, loads, stores with spills apart). The kernels are
straight-line code, so bundles follow device time: 0.79-0.84 ns a bundle on
a TPU v5 lite for the kernels of PR 26 and of its parent (PERF.md §5). A
count is no measurement: it ranks variants before a chip call, no more.

    JAX_PLATFORMS=cpu python tools/flash_bundles.py 128,1024,64
    JAX_PLATFORMS=cpu python tools/flash_bundles.py 32,4096,64 --block-q 512 --full

Each shape is ``heads*batch,seq,head_dim``; every shape compiles in a child
process, because the dump flags are read once when the compiler loads (and
the compiler aborts on exit with them set, after the files are written).
"""
import argparse
import glob
import os
import re
import subprocess
import sys
import tempfile

UNITS = ("MXU", "XLU", "VALU", "EUP", "VLOAD", "VLOAD:FILL", "VSTORE",
         "VSTORE:SPILL", "SALU")


def _child(shape, causal, blocks):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    jax.config.update("jax_enable_compilation_cache", False)
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    fa._interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bh, s, d = shape
    x = jax.ShapeDtypeStruct((1, s, bh, d), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=causal, **blocks)
        return (out.astype(jnp.float32) ** 2).sum()

    jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).compile()


def _regions(path):
    """Bundles between the control targets of a final-bundles dump."""
    marks, last = [], 0
    for line in open(path):
        m = re.match(r"\s*(0x[0-9a-f]+|\d+)\s+([A-Z]{2})?\s*:", line)
        if m:
            last = int(m.group(1), 0)
            if m.group(2):
                marks.append(last)
    marks.append(last + 1)
    return [b - a for a, b in zip(marks, marks[1:])]


def _unit_floors(path):
    lines = open(path).read().split("\n")
    cap = [int(x) for x in lines[2].split()]
    rows = [[int(x) for x in l.split()] for l in lines[4:] if l.strip()]
    return {u: round(sum(col) / c) for u, col, c in zip(UNITS, zip(*rows), cap)}


def report(shape, causal, blocks):
    with tempfile.TemporaryDirectory(prefix="flash_bundles") as d:
        env = dict(os.environ, JAX_PLATFORMS="cpu", LIBTPU_INIT_ARGS=(
            f"--xla_jf_dump_to={d} --xla_jf_dump_llo_text=true"))
        args = [sys.executable, os.path.abspath(__file__), "--child",
                ",".join(map(str, shape))] + (["--full"] if not causal else [])
        for k, v in blocks.items():
            args += ["--" + k.replace("_", "-"), str(v)]
        subprocess.run(args, env=env, capture_output=True)
        found = sorted(glob.glob(os.path.join(d, "*flash_attention_*-final_bundles.txt")))
        found = [f for f in found if "schedule-analysis" not in f]
        if not found:
            raise SystemExit(f"{shape}: the compile left no bundle dump")
        for f in found:
            name = re.search(r"(flash_attention_\w+)\.", f).group(1)
            util = glob.glob(os.path.join(
                d, f"*{name}.*final_hlo-static-per-bundle-utilization.txt"))
            regions = _regions(f)
            print(f"{shape} causal={causal} {blocks or ''} {name.rstrip('_')}: "
                  f"{sum(regions)} bundles, regions {regions}")
            if util:
                print("    least bundles by unit:", _unit_floors(util[0]))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("shapes", nargs="+", help="heads*batch,seq,head_dim")
    ap.add_argument("--full", action="store_true", help="causal=False")
    ap.add_argument("--block-q", type=int)
    ap.add_argument("--block-k", type=int)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    a = ap.parse_args()
    blocks = {k: v for k, v in (("block_q", a.block_q), ("block_k", a.block_k)) if v}
    for shape in a.shapes:
        shape = tuple(int(x) for x in shape.split(","))
        if a.child:
            _child(shape, not a.full, blocks)
        else:
            report(shape, not a.full, blocks)


if __name__ == "__main__":
    main()
