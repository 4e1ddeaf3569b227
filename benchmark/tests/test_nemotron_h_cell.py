"""The ``nemotron3nano-train-s8192`` cell at its rehearsal size on the CPU: a
sound run ends ``correct`` with every scan on its (interpreted) kernels over
the 8 groups; the control (the reference with float8_e4m3 operands in the
program's place) and each planted fault come out over what a sound run
reads; the three new readers on the run's own record and on one without
what they read."""
import argparse
import contextlib
import io
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.lib import counts_nemotron_h as counts
from benchmark.lib import peaks, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "nemotron3nano-train-s8192"
SEED = 2**31 + 40
NEW = ("train_mfu_nemotron3nano", "train_grouped_ssd_roofline",
       "train_ungated_expert_roofline")


def config():
    with open(os.path.join(HERE, "..", "configs",
                           "nemotron-3-nano-30b-a3b-ep16.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rehearsal():
    """(result line, everything printed, the driver's record) of one traced
    rehearsal run. The CPU is given the v5e's peaks, so that the shares of a
    peak are read from the run's own trace too (their values mean nothing
    here)."""
    from benchmark.drivers import train_step_nemotron_h as drv

    out, kept = io.StringIO(), {}
    v5e = peaks.peaks_for("TPU v5 lite")
    run = drv.run

    def keep(cell):
        kept.update(run(cell))
        return kept

    with contextlib.redirect_stdout(out), pytest.MonkeyPatch.context() as mp:
        mp.setattr(peaks, "peaks_for", lambda kind: v5e)
        mp.setattr(drv, "run", keep)
        rc = bench_run.main(["--workload", CELL, "--seed", str(SEED),
                             "--seconds", "1", "--trace", "1", "--rehearse"])
    assert rc == 0
    return (json.loads(out.getvalue().strip().splitlines()[-1]),
            out.getvalue(), kept)


def test_rehearsal_runs_and_reports_its_layers(rehearsal):
    """The limits were set on the chip at the cell's own size (PERF.md
    section 4), between the sound program and the float8 control; at this
    size the sound program reads within them."""
    line, printed, _ = rehearsal
    assert line["correct"] and line["failed"] == 0
    checks = line["checks"]
    assert set(checks) == {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap", "grad_sum_gap", "expert_grad_norm_gap",
        "delta_norm_gap", "bias_move_gap", "bc_group_grad_norm_gap"}
    assert "read, not compared: delta_sum_gap" in printed
    m = line["metrics"]
    # 4 of 16 experts held, 4 a token
    assert 0.7 < m["train_routed_slots_per_token"]["value"] < 1.3
    assert "routed slots a step" in printed
    for name in NEW:
        assert m[name]["value"] > 0.0, name


def test_the_new_readers_read_the_runs_record_and_nothing_without_it(
        rehearsal):
    from benchmark.metrics import (train_grouped_ssd_roofline,
                                   train_mfu_nemotron3nano,
                                   train_ungated_expert_roofline)

    line, _, record = rehearsal
    record = dict(record, device={"kind": "TPU v5 lite"})
    readers = (train_mfu_nemotron3nano, train_grouped_ssd_roofline,
               train_ungated_expert_roofline)
    for reader, name in zip(readers, NEW):
        assert reader.read(record) == pytest.approx(
            line["metrics"][name]["value"]), name
    routed = record["window"]["routed_slots"]
    per = sum(map(sum, routed)) / (len(routed) * 2 * 512)
    sizes = record["sizes"]
    assert train_mfu_nemotron3nano.read(record) == pytest.approx(
        100 * counts.train_flops_per_token(sizes, 256, per)
        * record["window"]["tokens"] / record["window"]["seconds"] / 197e12)
    # a program that keeps no such counter, scope or kernel (the parent),
    # and another cell's sizes: nothing, and nothing raised
    bare = dict(record, window={"tokens": 1, "seconds": 1.0, "steps": 1,
                                "traced_steps": 1}, trace=None)
    other = dict(record, sizes={k: v for k, v in sizes.items()
                                if k != "hybrid_override_pattern"})
    for reader in readers:
        assert reader.read(bare) is None
        assert reader.read(other) is None


def test_control_and_every_planted_fault_fail(rehearsal):
    """Each planted fault reads, at the rehearsal size too, three times or
    more what the sound program reads there on one of the compared numbers
    (the cell's limits were set on the chip at the cell's own size, PERF.md
    section 4); so does the control."""
    from benchmark.drivers import train_step_nemotron_h as drv

    cell = bench_run.load_cell(argparse.Namespace(
        workload=CELL, seed=SEED, seconds=1, trace=0, rehearse=True))
    ring = traffic.train_batches(cell.traffic, SEED, cell.sizes["vocab_size"])
    ref = drv.reference_readings(cell, ring)
    sound = {k: v["value"] for k, v in rehearsal[0]["checks"].items()
             if k.endswith("_gap") and not k.startswith("loss_")}

    def read(readings):
        return {k: v for k, (v, _) in drv.numbers(
            readings, ref, cell.sizes["router_bias_update_rate"]).items()}

    assert all(v == 0 for k, v in read(ref).items() if k in sound)
    assert [name for name, _ in drv.FAULTS] == [
        "control_fp8", "fault_bias_ignored", "fault_softmax_router",
        "fault_no_routed_scale", "fault_relu_not_squared",
        "fault_one_bc_group", "fault_norm_over_all_lanes",
        "fault_half_batch", "fault_state_unchanged"]
    for name, kw in drv.planted(cell):
        values = read(drv.reference_readings(cell, ring, **kw))
        assert any(values[k] > 3 * sound[k] for k in sound), (
            name, values, sound)


def test_the_attention_reader_on_a_record_made_by_hand():
    """The flash kernels handed q [2 x 32, 8192, 128] and k, v [2 x 2, 8192,
    128]: the one attention layer's least time over their seconds; nothing
    where no kernel takes those shapes (the CPU's interpreted kernels, a
    trace without them) or without a pattern of layers."""
    from benchmark.metrics import train_nope_gqa_attn_roofline as reader

    s = config()
    pk = peaks.peaks_for("TPU v5 lite")
    q, kv = "bf16[64,8192,128]", "bf16[4,8192,128]"
    record = {
        "sizes": s, "traffic": {"batch": 2, "seq": 8192},
        "device": {"kind": "TPU v5 lite"},
        "window": {"traced_steps": 3},
        "trace": {"op_seconds": {"fusion": 1.0}, "kernels": [
            {"name": "flash_attention_fwd", "seconds": 0.03,
             "operands": [q, kv, kv]},
            {"name": "flash_attention_bwd_dq", "seconds": 0.05,
             "operands": [q, kv, kv, q, q]},
            {"name": "ssd_scan_fwd", "seconds": 0.5,
             "operands": ["bf16[2,8192,4096]"]}]},
    }
    tokens = 2 * 8192
    flops = tokens * 4 * 32 * 128 * 8193 / 2
    least = max(flops / 197e12, tokens * (2 * 8192 + 1024) / 819e9) + max(
        2 * flops / 197e12, tokens * (4 * 8192 + 2 * 1024) / 819e9)
    assert counts.attention_roofline(s, 2, 8192, pk) == pytest.approx(least)
    assert reader.read(record) == pytest.approx(100 * least * 3 / 0.08)
    assert 0 < reader.read(record) < 100
    for bare in (dict(record, trace={"op_seconds": {}, "kernels": []}),
                 dict(record, trace=None),
                 dict(record, sizes={k: v for k, v in s.items()
                                     if k != "hybrid_override_pattern"})):
        assert reader.read(bare) is None


def test_counts_and_the_cell_agree_on_the_load():
    """768 slots a held expert a step under even routing: 2 x 8,192 tokens
    x 6 of 128 over the 8 held; the row buffer 1.2 times that over the 8 in
    whole 512-row tiles."""
    from paddle_tpu.incubate import moe

    s = config()
    assert 16384 * 6 * 8 / 128 == 6144 == 8 * 768
    assert moe.row_buffer_rows(16384, 6, 128, 8) == 7680
    assert counts.layer_kinds(s) == (4, 4, 1)
