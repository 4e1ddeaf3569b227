"""Forward + backward operations a token of the state-space / attention
hybrid decoder needs (``lib/counts_granite_hybrid.py``; the held experts by
the slots really routed to them, mean over the window's steps) times the
run's tokens per second, over chips times the published bf16 peak."""
from ..lib import counts_granite_hybrid as counts
from ..lib import peaks


def read(record):
    w = record["window"]
    routed = w.get("routed_slots")
    sizes = record["sizes"]
    if not w.get("tokens") or not routed or "mamba_n_heads" not in sizes:
        return None
    per_layer_token = sum(map(sum, routed)) / (
        len(routed) * sizes["num_hidden_layers"] * w["tokens_per_step"])
    flops = counts.train_flops_per_token(
        sizes, record["traffic"]["seq"], per_layer_token)
    peak = peaks.peaks_for(record["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops * w["tokens"] / w["seconds"] / (
        record["chips"] * peak)
