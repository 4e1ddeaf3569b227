"""The six per-layer metrics that read what the program records about its
own set-up (``lib/program_setup.py``): against a hand-made ring, and through
a traced rehearsal of one cell."""
import json
import os
import re
import subprocess
import sys

import pytest

import paddle_tpu as paddle
from benchmark.metrics import (setup_first_step_s, setup_param_init_s,
                               setup_programs_built, setup_step_compile_s,
                               setup_step_lower_s, setup_step_trace_s)
from paddle_tpu.profiler import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1_000_000
T0 = 1_700_000_000_000 * MS  # a wall clock in ns
READERS = (setup_step_trace_s, setup_step_lower_s, setup_step_compile_s,
           setup_first_step_s, setup_param_init_s, setup_programs_built)


def built(span=None, site="", trace_s=0.1, lower_s=0.01, seconds=0.2,
          phases=True):
    attrs = {"seconds": seconds}
    if phases:  # a program from before the phases records seconds alone
        attrs.update(trace_s=trace_s, lower_s=lower_s, fetch_s=0.0,
                     cache_hit=False, fun="jit(f)", start_ns=T0)
    if span is not None:
        attrs["span"] = span
    trace.emit("compile", site=site, **attrs)


def step(n, root, start_ms, dur_ms, compiles_in_args=0, compiles_in_launch=0,
         phases=True):
    """A root span with its three children, as ``compile_train_step`` closes
    them: children first (each after the programs built inside it)."""
    at = T0 + start_ms * MS
    for k, (name, n_built) in enumerate((("args", compiles_in_args),
                                         ("launch", compiles_in_launch),
                                         ("writeback", 0))):
        kid = root + 1 + k
        for _ in range(n_built):
            built(kid, f"compile_train_step/{name}", trace_s=2.0,
                  lower_s=0.5, seconds=3.0, phases=phases)
        trace.emit("span", site=f"compile_train_step/{name}",
                   start_ns=at, dur_ns=dur_ms * MS // 4, id=kid, parent=root)
    trace.emit("span", site="compile_train_step", step=n,
               start_ns=T0 + start_ms * MS, dur_ns=dur_ms * MS, id=root,
               parent=None)


def fill_ring(phases=True, first_step=True):
    trace.clear()
    # set-up: three leaves, the first two build a program each
    for i, start_ms in enumerate((0, 100, 200)):
        if i < 2:
            built(i + 1, "create_parameter", seconds=0.05, phases=phases)
        trace.emit("span", site="create_parameter", start_ns=T0 + start_ms * MS,
                   dur_ns=40 * MS, id=i + 1, parent=None, shape=(8, 8),
                   dtype="float32")
    built(phases=phases)  # the benchmark's seeding program: no span open
    # the first step builds the optimizer state's 2 programs and the step
    if first_step:
        step(0, 10, 1000, 9000, compiles_in_args=2, compiles_in_launch=1,
             phases=phases)
    built(phases=phases)  # the checks' norms
    step(1, 20, 10_100, 200)
    for n in range(2, 6):  # the window
        step(n, 10 * (n + 1), 10_300 + 180 * n, 170)
    # after it: the memory analysis's re-lowering and the reference
    for _ in range(3):
        built(trace_s=5.0, lower_s=1.0, seconds=7.0, phases=phases)


def test_each_reader_counts_what_it_should():
    fill_ring()
    rec = {}
    assert setup_step_trace_s.read(rec) == pytest.approx(3 * 2.0)
    assert setup_step_lower_s.read(rec) == pytest.approx(3 * 0.5)
    assert setup_step_compile_s.read(rec) == pytest.approx(3 * 3.0)
    assert setup_first_step_s.read(rec) == pytest.approx(9.0)
    assert setup_param_init_s.read(rec) == pytest.approx(3 * 0.040)
    # two leaves' programs and the first step's three; not the seeding, the
    # norms, the reference's or the re-lowering
    assert setup_programs_built.read(rec) == 5


def test_a_leaf_made_after_the_first_step_opened_is_not_set_up():
    fill_ring()
    trace.emit("span", site="create_parameter", start_ns=T0 + 20_000 * MS,
               dur_ns=40 * MS, id=99, parent=None, shape=(2,), dtype="float32")
    assert setup_param_init_s.read({}) == pytest.approx(3 * 0.040)


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__)
def test_nothing_to_read_is_none(reader):
    trace.clear()  # the ring off
    assert reader.read({}) is None
    fill_ring(first_step=False)  # no root of step 0
    assert reader.read({}) is None
    fill_ring(phases=False)  # a program from before the phases
    assert reader.read({}) is None


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__)
def test_a_ring_that_dropped_events_is_none(reader):
    size = paddle.get_flags("FLAGS_trace_ring_size")["FLAGS_trace_ring_size"]
    try:
        fill_ring()
        paddle.set_flags({"FLAGS_trace_ring_size": 24})
        trace.emit("span", site="tick", start_ns=T0, dur_ns=1, id=500,
                   parent=None)  # the ring now keeps the newest 24
        assert not [e for e in trace.events() if e.step == 0
                    and e.site == "compile_train_step"]
        assert reader.read({}) is None
        paddle.set_flags({"FLAGS_trace_ring_size": size})
        fill_ring()  # root 0 still held, but the leaves before it are gone
        paddle.set_flags({"FLAGS_trace_ring_size": 35})
        trace.emit("span", site="tick", start_ns=T0, dur_ns=1, id=500,
                   parent=None)
        assert [e for e in trace.events() if e.step == 0
                and e.site == "compile_train_step"]
        assert reader.read({}) is None
    finally:
        paddle.set_flags({"FLAGS_trace_ring_size": size})
        trace.clear()


def test_traced_rehearsal_prints_the_setup_metrics():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2m-train-s1024",
         "--seed", "3000000019", "--seconds", "2", "--trace", "1",
         "--rehearse"], cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    m = {k: v["value"] for k, v in json.loads(lines[-1])["metrics"].items()}
    split = {k: float(v) for k, v in re.findall(
        r"(\w+) ([0-9.]+)s", next(l for l in lines
                                  if l.startswith("setup split:")))}
    phases = (m["setup_step_trace_s"] + m["setup_step_lower_s"]
              + m["setup_step_compile_s"])
    assert min(m["setup_step_trace_s"], m["setup_step_lower_s"],
               m["setup_step_compile_s"]) > 0
    assert phases <= m["setup_first_step_s"]
    # the set-up split's phases hold the root span (and the first loss's
    # wait) and the leaves; the split line prints hundredths
    assert m["setup_first_step_s"] <= \
        split["first_step_trace_compile_or_fetch"] + 0.005
    assert 0 < m["setup_param_init_s"] <= split["model_on_device"] + 0.005
    assert m["setup_programs_built"] > 1
