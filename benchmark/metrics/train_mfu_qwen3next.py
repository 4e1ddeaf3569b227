"""Forward + backward operations a token of the sparse hybrid decoder needs
(``lib/counts_qwen3_next.py``; the held experts by the slots really routed to
them, mean over the window's steps) times the run's tokens per second, over
chips times the published bf16 peak."""
from ..lib import counts_qwen3_next as counts
from ..lib import peaks


def read(record):
    w = record["window"]
    routed = w.get("routed_slots")
    if not w.get("tokens") or not routed:
        return None
    sizes = record["sizes"]
    per_layer_token = sum(map(sum, routed)) / (
        len(routed) * sizes["num_hidden_layers"] * w["tokens_per_step"])
    flops = counts.train_flops_per_token(
        sizes, record["traffic"]["seq"], per_layer_token)
    peak = peaks.peaks_for(record["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops * w["tokens"] / w["seconds"] / (
        record["chips"] * peak)
