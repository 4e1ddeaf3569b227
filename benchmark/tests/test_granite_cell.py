"""The ``granite4h-train-s8192`` cell at its rehearsal size on the CPU: a
sound run ends ``correct`` with the scan's kernels (interpreted) in the step;
the control (the reference with float8_e4m3 operands in the program's place)
and each planted fault come out over what a sound run reads; the new counts
against hand arithmetic; the two new readers on a record made by hand."""
import argparse
import contextlib
import io
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.lib import counts_granite_hybrid as counts
from benchmark.lib import peaks, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "granite4h-train-s8192"
SEED = 2**31 + 32


def config():
    with open(os.path.join(HERE, "..", "configs",
                           "granite-4.0-h-small-tp8ep8.json")) as f:
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


def test_rehearsal_is_correct_and_reports_its_layers(rehearsal):
    line, printed = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == {"grad_norm_gap", "grad_sum_gap",
                                   "expert_grad_norm_gap", "delta_norm_gap"}
    m = line["metrics"]
    # the counters need no device: 3 of 12 experts held, 4 a token
    assert 0.5 < m["train_routed_slots_per_token"]["value"] < 1.5
    assert m["train_expert_load_drift"]["value"] < 0.6
    assert m["train_expert_rows_waste"]["value"] >= 0.0
    assert "routed slots a step" in printed
    # the scan's share divides by what the trace books to its scope: the
    # driver found the ``ssd_scan`` scope in it
    assert m["train_ssd_scan_roofline"]["value"] > 0.0
    assert m["train_mfu_granite4h"]["value"] > 0.0


def test_the_rehearsal_runs_the_scans_kernels():
    """The rehearsal's state-space shapes (two 64-wide heads, state 128,
    chunk 128) are ones the kernels take: the cell's step is rehearsed with
    them (interpreted), not with the ``jax.numpy`` chunks."""
    from paddle_tpu.ops import state_space

    cell = bench_run.load_cell(argparse.Namespace(
        workload=CELL, seed=SEED, seconds=1, trace=0, rehearse=True))
    s = cell.sizes
    assert state_space._refusal(
        s["mamba_n_heads"], s["mamba_d_head"], s["mamba_n_groups"],
        s["mamba_d_state"], s["mamba_chunk_size"]) is None
    full = config()
    assert state_space._refusal(
        full["mamba_n_heads"], full["mamba_d_head"], full["mamba_n_groups"],
        full["mamba_d_state"], full["mamba_chunk_size"]) is None


def test_control_and_every_planted_fault_fail(rehearsal):
    """Each planted fault reads, at the rehearsal size too, five times or
    more what the sound program reads there on one of the compared numbers
    (the cell's limits were set on the chip at the cell's own size, PERF.md
    section 4, where a sound program reads more than here). The control is
    held to reading three times the sound program's on one of them."""
    from benchmark.drivers import train_step_granite as drv

    cell = bench_run.load_cell(argparse.Namespace(
        workload=CELL, seed=SEED, seconds=1, trace=0, rehearse=True))
    ring = traffic.train_batches(cell.traffic, SEED,
                                 cell.sizes["vocab_size"])
    ref = drv.reference_readings(cell, ring)
    sound = {k: v["value"] for k, v in rehearsal[0]["checks"].items()}

    def read(readings):
        return {k: v for k, (v, _) in drv.numbers(readings, ref).items()}

    assert all(v == 0 for k, v in read(ref).items() if k in sound)
    names = [name for name, _ in drv.planted(cell)]
    assert names == ["control_fp8", "fault_no_decay", "fault_no_skip",
                     "fault_norm_before_gate", "fault_residual_one",
                     "fault_attention_scale", "fault_capacity_drop",
                     "fault_half_batch", "fault_state_unchanged"]
    for name, kw in drv.planted(cell):
        values = read(drv.reference_readings(cell, ring, **kw))
        times = 3 if name == "control_fp8" else 5
        assert any(values[k] > times * sound[k] for k in sound), (
            name, values, sound)


def test_counts_against_a_hand_count():
    s = config()
    # per token, forward + backward, as ISSUE 32 reckons it
    mamba = 4096 * (1024 + 256 + 1024) + 4096 * 16 + 1024 * 4096  # 13.70M
    attention = 4096 * 512 + 2 * 4096 * 128 + 512 * 4096          # 5.24M
    shared_router = 3 * 4096 * 1536 + 4096 * 72                   # 19.17M
    head = 4096 * 12544
    conv = 1280 * 4
    dense = 9 * mamba + attention + 10 * shared_router + head + 9 * conv
    experts = 10 * 1.25 * 3 * 4096 * 768
    scan = 9 * 16 * 5 * 64 * 128
    attn = 4 * 4 * 128 * 8193 / 2
    want = 6 * dense + 6 * experts + 3 * scan + 3 * attn
    got = counts.train_flops_per_token(s, 8192, 1.25)
    assert got == pytest.approx(want, rel=1e-12)
    assert 2.9e9 < got < 3.05e9
    assert counts.layer_kinds(s) == (9, 1)
    assert counts.expert_weights(s) == 9_437_184
    # the program's own count of what it holds (tests/test_tpu_compile.py
    # builds the model): within 1% of the issue's 1,221M
    small = 1280 * 5 + 3 * 16 + 1024
    assert counts.n_params(s) == (
        12544 * 4096 + 4096 + 9 * (mamba + small) + attention
        + 10 * (2 * 4096 + shared_router + 9 * 9_437_184)) == 1_221_088_944

    pk = peaks.peaks_for("TPU v5 lite")
    flops = 8192 * 16 * 5 * 64 * 128
    fwd = 8192 * (2 * 1024 * 2 + 256 * 2 + 16 * 4)
    bwd = 8192 * (3 * 1024 * 2 + 2 * 256 * 2 + 2 * 16 * 4)
    one = max(flops / 197e12, fwd / 819e9)
    back = max(2 * flops / 197e12, bwd / 819e9)
    assert counts.scan_roofline(s, 1, 8192, pk) == pytest.approx(one + back)
    assert counts.scan_roofline(s, 1, 8192, pk, forwards=2) == pytest.approx(
        2 * one + back)
    assert one == fwd / 819e9  # bound by its bytes


def test_new_readers_on_a_record_made_by_hand():
    from benchmark.metrics import train_mfu_granite4h, train_ssd_scan_roofline

    s = config()
    pk = peaks.peaks_for("TPU v5 lite")
    routed = [[10240] * 10, [10000] * 10]
    record = {
        "sizes": s, "traffic": {"batch": 1, "seq": 8192}, "chips": 1,
        "device": {"kind": "TPU v5 lite"},
        "window": {"tokens": 2 * 8192, "seconds": 1.0, "steps": 2,
                   "tokens_per_step": 8192, "traced_steps": 2,
                   "routed_slots": routed,
                   "scope_seconds": {"layers.*/mixer/ssd_scan": 0.05,
                                     "layers.*/mixer/short_conv": 0.01,
                                     "layers.*/experts/experts": 0.07}},
        "trace": {"op_seconds": {"ssd_scan_fwd": 0.01, "fusion": 0.5}},
    }
    per = (10240 + 10000) / (2 * 8192)
    assert train_mfu_granite4h.read(record) == pytest.approx(
        100 * counts.train_flops_per_token(s, 8192, per) * 16384 / 197e12)
    assert train_ssd_scan_roofline.read(record) == pytest.approx(
        100 * counts.scan_roofline(s, 1, 8192, pk) * 9 * 2 / 0.05)
    remade = dict(record, sizes=dict(s, recompute_mixer=True))
    assert train_ssd_scan_roofline.read(remade) == pytest.approx(
        100 * counts.scan_roofline(s, 1, 8192, pk, forwards=2) * 9 * 2 / 0.05)
    # a program that keeps no such counter or scope, another model's sizes
    bare = dict(record, window={"tokens": 1, "seconds": 1.0, "steps": 1,
                                "traced_steps": 1})
    other = dict(record, sizes={"num_hidden_layers": 4})
    for reader in (train_mfu_granite4h, train_ssd_scan_roofline):
        assert reader.read(bare) is None
        assert reader.read(other) is None
