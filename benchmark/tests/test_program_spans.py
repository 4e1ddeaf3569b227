"""The four per-layer metrics that read what the program records about its
own step (``lib/program_spans.py``): against a hand-made record and ring, and
through a traced rehearsal of both cells."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.metrics import (train_attn_kernel_share, train_step_host_self_ms,
                               train_step_launch_ms, train_step_stall_ms)
from paddle_tpu.profiler import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1_000_000


def record(steps, traced_steps=None, op_seconds=None, busy_s=4.0):
    return {"window": {"steps": steps, "traced_steps": traced_steps or steps},
            "trace": None if op_seconds is None else {
                "op_seconds": op_seconds, "busy_s": busy_s}}


def fill_ring(steps):
    """One root span with its three children per (start_ms, args_ms,
    launch_ms, writeback_ms, own_ms), as compile_train_step closes them:
    children first, the root last."""
    trace.clear()
    ids = iter(range(1, 10_000))
    for n, (start, args, launch, writeback, own) in enumerate(steps):
        root = next(ids)
        at = start * MS
        for name, ms in (("args", args), ("launch", launch),
                         ("writeback", writeback)):
            trace.emit("span", site=f"compile_train_step/{name}",
                       start_ns=at, dur_ns=ms * MS, id=next(ids), parent=root)
            at += ms * MS
        trace.emit("span", site="compile_train_step", step=n,
                   start_ns=start * MS, id=root, parent=None,
                   dur_ns=(args + launch + writeback + own) * MS)


def test_host_metrics_split_the_root_span():
    # two set-up steps that the window's count leaves out, then three steps
    fill_ring([(0, 50, 900, 5, 1), (1000, 50, 900, 5, 1),
               (2000, 2, 6, 1, 0.5), (2180, 3, 8, 1, 0.5),
               (2360, 4, 7, 1, 0.5)])
    rec = record(steps=3)
    launch = train_step_launch_ms.read(rec)
    host = train_step_host_self_ms.read(rec)
    assert launch == pytest.approx((6 + 8 + 7) / 3)
    assert host == pytest.approx((3.5 + 4.5 + 5.5) / 3)
    roots = [e.attrs["dur_ns"] for e in trace.events(kind="span")
             if e.site == "compile_train_step"][-3:]
    assert launch + host == pytest.approx(sum(roots) / 3 / MS)


def test_stall_is_read_over_the_traced_steps_only():
    # steady 180 ms steps, one 400 ms late, then the profiler's stop: a pause
    # of seconds that is the window's own and no stall
    starts = [0, 180, 360, 940, 1120, 1300, 9000, 9180]
    fill_ring([(s, 2, 6, 1, 0.5) for s in starts])
    assert train_step_stall_ms.read(record(8, traced_steps=6)) == \
        pytest.approx(580 - 180)
    assert train_step_stall_ms.read(record(8, traced_steps=3)) == \
        pytest.approx(0)
    assert train_step_stall_ms.read(record(8, traced_steps=2)) is None


def test_nothing_to_read_is_none():
    trace.clear()  # the ring off, or a program from before the spans
    rec = record(steps=3, op_seconds={"fusion": 1.0, "copy": 0.5})
    for reader in (train_step_launch_ms, train_step_host_self_ms,
                   train_step_stall_ms, train_attn_kernel_share):
        assert reader.read(rec) is None
    # a root whose launch the ring has lost reads nothing rather than less
    fill_ring([(0, 2, 6, 1, 0.5)])
    trace.emit("span", site="compile_train_step", step=1, start_ns=200 * MS,
               dur_ns=9 * MS, id=77, parent=None)
    assert train_step_launch_ms.read(record(steps=2)) is None
    assert train_attn_kernel_share.read(record(3)) is None  # untraced run


def test_attention_share_goes_by_the_kernels_names():
    ops = {"flash_attention_fwd": 0.3, "flash_attention_bwd_dkv": 0.5,
           "flash_attention_bwd_dq": 0.2, "fusion": 2.0,
           "jvp_jit__unknown___": 0.7}
    assert train_attn_kernel_share.read(record(3, op_seconds=ops)) == \
        pytest.approx(25.0)


@pytest.mark.parametrize("workload", ["gpt2m-train-s1024", "gpt2l-train-s1024"])
def test_traced_rehearsal_prints_the_ring_metrics(workload):
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "3000000019", "--seconds", "2", "--trace", "1",
         "--rehearse"], cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"]
    # no TPU plane on the CPU, so the device metric has nothing to read
    assert "train_attn_kernel_share" not in m
    assert m["train_step_launch_ms"] > 0 and m["train_step_host_self_ms"] > 0
    assert m["train_step_stall_ms"] >= 0
    # the two host metrics sum to the root span, which train_dispatch_ms
    # times from outside (a rehearsal's times, held loosely)
    assert m["train_step_launch_ms"] + m["train_step_host_self_ms"] == \
        pytest.approx(m["train_dispatch_ms"], rel=0.5)
