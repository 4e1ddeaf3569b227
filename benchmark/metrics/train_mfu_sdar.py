"""Forward + backward operations a clean token of the block-diffusion sparse
decoder needs (``lib/counts_sdar_moe.py``: two stream positions a token,
attention over the allowed pairs, the held experts by the slots really
routed, mean over the window's steps) times the run's tokens per second,
over chips times the published bf16 peak."""
from ..lib import counts_sdar_moe as counts
from ..lib import peaks


def read(record):
    w = record["window"]
    routed = w.get("routed_slots")
    mix = record["traffic"]
    if not w.get("tokens") or not routed or "block_length" not in mix:
        return None
    sizes = record["sizes"]
    per_layer_token = sum(map(sum, routed)) / (
        len(routed) * sizes["num_hidden_layers"] * w["tokens_per_step"])
    flops = counts.train_flops_per_token(
        sizes, mix["seq"], mix["block_length"], per_layer_token)
    peak = peaks.peaks_for(record["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops * w["tokens"] / w["seconds"] / (
        record["chips"] * peak)
