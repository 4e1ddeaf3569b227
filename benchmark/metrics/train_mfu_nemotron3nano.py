"""Forward + backward operations a token of the one-part-a-layer hybrid
decoder needs (``lib/counts_nemotron_h.py``: each layer by its kind, the
held experts by the slots really routed to them, mean over the window's
steps and expert layers, the untied head over the held rows) times the run's
tokens per second, over chips times the published bf16 peak."""
from ..lib import counts_nemotron_h as counts
from ..lib import peaks


def read(record):
    w, sizes = record["window"], record["sizes"]
    routed = w.get("routed_slots")
    if not w.get("tokens") or not routed \
            or "hybrid_override_pattern" not in sizes:
        return None
    per_layer_token = sum(map(sum, routed)) / (
        len(routed) * len(routed[0]) * w["tokens_per_step"])
    flops = counts.train_flops_per_token(sizes, record["traffic"]["seq"],
                                         per_layer_token)
    peak = peaks.peaks_for(record["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops * w["tokens"] / w["seconds"] / (
        record["chips"] * peak)
