"""``counts.py`` against hand arithmetic for both configurations."""
import json
import os

import pytest

from benchmark.lib import counts, peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def sizes(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_parameters_345m():
    # 50304*1024 + 1024*1024 + 24*(12*1024^2 + 13*1024) + 2*1024
    assert counts.n_params(sizes("gpt2-medium-345m")) == 354_871_296
    assert round(counts.n_params(sizes("gpt2-medium-345m")) / 1e6) == 355


def test_parameters_774m():
    # 50304*1280 + 1024*1280 + 36*(12*1280^2 + 13*1280) + 2*1280
    assert counts.n_params(sizes("gpt2-large-774m")) == 774_090_240
    assert round(counts.n_params(sizes("gpt2-large-774m")) / 1e6) == 774


@pytest.mark.parametrize("name,dense,attn", [
    # dense: 6 * (24*12*1024^2 + 50304*1024); attn: 24 * 12 * 1024 * 1025/2
    ("gpt2-medium-345m", 6 * (301_989_888 + 51_511_296), 24 * 6 * 1024 * 1025),
    ("gpt2-large-774m", 6 * (707_788_800 + 64_389_120), 36 * 6 * 1280 * 1025),
])
def test_train_flops_per_token(name, dense, attn):
    got = counts.train_flops_per_token(sizes(name), 1024)
    assert got == dense + attn
    assert 2.2e9 < counts.train_flops_per_token(
        sizes("gpt2-medium-345m"), 1024) < 2.4e9


def test_attention_roofline_is_flop_bound_by_a_hair():
    pk = peaks.peaks_for("TPU v5 lite")
    flops = counts.attention_flops(8, 1024, 1024, backward=False)
    nbytes = counts.attention_bytes(8, 1024, 1024, backward=False)
    assert flops == 4 * 8 * 1024 * 1024 * 1025 / 2
    assert nbytes == 4 * 8 * 1024 * 1024 * 2
    secs, bound = counts.roofline_seconds(flops, nbytes, pk)
    assert bound == "flops" and secs == pytest.approx(flops / 197e12)
    assert counts.attention_flops(8, 1024, 1024, True) == 2 * flops


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
