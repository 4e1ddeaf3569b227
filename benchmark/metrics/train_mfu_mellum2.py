"""Forward + backward operations a token of the sliding-window sparse
decoder needs (``lib/counts_mellum2.py``: attention over each layer's
allowed pairs, the band or the causal triangle, the held experts by the
slots really routed, mean over the window's steps, the head slice) times the
run's tokens per second, over chips times the published bf16 peak."""
from ..lib import counts_mellum2 as counts
from ..lib import peaks


def read(record):
    w, sizes = record["window"], record["sizes"]
    routed = w.get("routed_slots")
    if not w.get("tokens") or not routed or "sliding_window" not in sizes:
        return None
    per_layer_token = sum(map(sum, routed)) / (
        len(routed) * sizes["num_hidden_layers"] * w["tokens_per_step"])
    flops = counts.train_flops_per_token(sizes, record["traffic"]["seq"],
                                         per_layer_token)
    peak = peaks.peaks_for(record["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops * w["tokens"] / w["seconds"] / (
        record["chips"] * peak)
