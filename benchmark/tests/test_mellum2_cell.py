"""The ``mellum2-train-s8192`` cell at its rehearsal size on the CPU: a sound
run ends ``correct`` with the band walked by the (interpreted) flash
kernels; the control (the reference with float8_e4m3 operands in the
program's place) and each planted fault come out over what a sound run
reads; the new counts against hand arithmetic; the three new readers on a
record made by hand, and on one without what they read."""
import argparse
import contextlib
import io
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.lib import counts_mellum2 as counts
from benchmark.lib import peaks, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "mellum2-train-s8192"
SEED = 2**31 + 38


def config():
    with open(os.path.join(HERE, "..", "configs",
                           "mellum2-12b-a2.5b-ep8.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rehearsal():
    """(result line, everything printed) of one traced rehearsal run. The
    CPU is given the v5e's peaks, so that the shares of a peak are read from
    the run's own trace too (their values mean nothing here)."""
    out = io.StringIO()
    v5e = peaks.peaks_for("TPU v5 lite")
    with contextlib.redirect_stdout(out), pytest.MonkeyPatch.context() as mp:
        mp.setattr(peaks, "peaks_for", lambda kind: v5e)
        rc = bench_run.main(["--workload", CELL, "--seed", str(SEED),
                             "--seconds", "1", "--trace", "1", "--rehearse"])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), out.getvalue()


def test_rehearsal_runs_and_reports_its_layers(rehearsal):
    """The limits were set on the chip at the cell's own size (PERF.md
    section 4), between the sound program and the float8 control; at this
    size the sound program reads within twice them."""
    line, printed = rehearsal
    assert line["failed"] == 0
    checks = line["checks"]
    assert set(checks) == {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap", "grad_sum_gap", "expert_grad_norm_gap",
        "delta_norm_gap"}
    assert all(c["value"] < 2 * c["limit"] for c in checks.values()), checks
    assert "read, not compared: delta_sum_gap" in printed
    m = line["metrics"]
    # 2 of 8 experts held, 2 a token
    assert 0.2 < m["train_routed_slots_per_token"]["value"] < 0.8
    assert "routed slots a step" in printed
    # 256 tokens in sub-tiles of 128 under a window of 96: 3 of 4 run
    assert m["train_window_attn_tile_share"]["value"] == 0.75
    assert m["train_mfu_mellum2"]["value"] > 0.0
    # interpreted kernels carry no name
    assert "train_window_attn_roofline" not in m


def test_control_and_every_planted_fault_fail(rehearsal):
    """Each planted fault reads, at the rehearsal size too, three times or
    more what the sound program reads there on one of the compared numbers
    (the cell's limits were set on the chip at the cell's own size, PERF.md
    section 4); so does the control."""
    from benchmark.drivers import train_step_mellum2 as drv

    cell = bench_run.load_cell(argparse.Namespace(
        workload=CELL, seed=SEED, seconds=1, trace=0, rehearse=True))
    ring = traffic.train_batches(cell.traffic, SEED, cell.sizes["vocab_size"])
    ref = drv.reference_readings(cell, ring)
    sound = {k: v["value"] for k, v in rehearsal[0]["checks"].items()
             if k.endswith("_gap") and not k.startswith("loss_")}

    def read(readings):
        return {k: v for k, (v, _) in drv.numbers(readings, ref).items()}

    assert all(v == 0 for k, v in read(ref).items() if k in sound)
    assert [name for name, _ in drv.FAULTS] == [
        "control_fp8", "fault_no_window", "fault_default_rope_full",
        "fault_no_attention_factor", "fault_no_renorm",
        "fault_capacity_drop", "fault_half_batch", "fault_state_unchanged"]
    for name, kw in drv.FAULTS:
        kw = {"rows": slice(0, 1)} if kw is None else kw
        values = read(drv.reference_readings(cell, ring, **kw))
        assert any(values[k] > 3 * sound[k] for k in sound), (
            name, values, sound)


def test_counts_against_a_hand_count():
    s = config()
    attention = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304   # 21.23M
    router = 2304 * 64
    expert = 3 * 2304 * 896                                    # 6.19M
    head = 2304 * 12288
    band = 1024 * 1025 // 2 + (8192 - 1024) * 1024            # a head
    causal = 8192 * 8193 // 2
    attn = 4 * 32 * 128 * (3 * band + causal) / 8192          # a token
    want = 6 * (4 * (attention + router) + head) + 6 * 4 * 1.0 * expert \
        + 3 * attn
    got = counts.train_flops_per_token(s, 8192, 1.0)
    assert got == pytest.approx(want, rel=1e-12)
    assert 1.15e9 < got < 1.2e9
    assert counts.n_params(s) == (
        2 * 12288 * 2304 + 2304 + 4 * (
            2 * 2304 + attention + router + 8 * expert)) == 340_349_184
    pk = peaks.peaks_for("TPU v5 lite")
    flops = 4 * 2 * 32 * 128 * band
    q, kv = 2 * 8192 * 32 * 128 * 2, 2 * 2 * 8192 * 4 * 128 * 2
    fwd = max(flops / 197e12, (2 * q + kv) / 819e9)
    bwd = max(2 * flops / 197e12, (4 * q + 2 * kv) / 819e9)
    assert counts.window_attention_roofline(s, 2, 8192, pk) == \
        pytest.approx(fwd + bwd)
    assert fwd == flops / 197e12  # bound by its operations


def test_new_readers_on_a_record_made_by_hand():
    from benchmark.metrics import (train_mfu_mellum2,
                                   train_window_attn_roofline,
                                   train_window_attn_tile_share)

    s = config()
    pk = peaks.peaks_for("TPU v5 lite")
    routed = [[16384] * 4, [16000] * 4]
    tile = {"mask": "window", "window": 1024, "run": 540, "masked": 120,
            "total": 4096}
    record = {
        "sizes": s, "chips": 1, "device": {"kind": "TPU v5 lite"},
        "traffic": {"batch": 2, "seq": 8192},
        "window": {"tokens": 2 * 16384, "seconds": 1.0, "steps": 2,
                   "tokens_per_step": 16384, "traced_steps": 2,
                   "routed_slots": routed,
                   "flash_tiles": [tile, {"mask": "causal", "run": 36,
                                          "masked": 8, "total": 64}]},
        "trace": {"op_seconds": {"flash_attention_window_fwd": 0.1,
                                 "flash_attention_window_bwd_dq": 0.2,
                                 "flash_attention_fwd": 5.0,
                                 "fusion": 9.0}},
    }
    per = (16384 + 16000) / (2 * 16384)  # slots a token and layer
    assert train_mfu_mellum2.read(record) == pytest.approx(
        100 * counts.train_flops_per_token(s, 8192, per) * 32768 / 197e12)
    assert train_window_attn_roofline.read(record) == pytest.approx(
        100 * counts.window_attention_roofline(s, 2, 8192, pk) * 3 * 2 / 0.3)
    assert train_window_attn_tile_share.read(record) == 540 / 4096
    # a program that keeps no such counter, event or kernel (the parent),
    # and another cell's sizes: nothing, and nothing raised
    bare = dict(record, window={"tokens": 1, "seconds": 1.0, "steps": 1,
                                "traced_steps": 1},
                trace={"op_seconds": {"flash_attention_fwd": 1.0}})
    for reader in (train_mfu_mellum2, train_window_attn_roofline,
                   train_window_attn_tile_share):
        assert reader.read(bare) is None
    other = dict(record, sizes={k: v for k, v in s.items()
                                if k != "sliding_window"})
    assert train_mfu_mellum2.read(other) is None
    assert train_window_attn_roofline.read(other) is None
    assert train_window_attn_roofline.read(dict(record, trace=None)) is None
