"""Forward + backward operations a token needs (``lib/counts.py``) times the
run's tokens per second, over chips times the published bf16 peak."""
from ..lib import counts, peaks


def read(record):
    w = record["window"]
    if not w.get("tokens"):
        return None
    peak = peaks.peaks_for(record["device"]["kind"])["bf16_flops_per_s"]
    flops = counts.train_flops_per_token(record["sizes"], record["traffic"]["seq"])
    rate = w["tokens"] / w["seconds"]
    return 100.0 * flops * rate / (record["chips"] * peak)
