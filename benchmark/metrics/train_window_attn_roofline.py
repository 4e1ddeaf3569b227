"""Least time for every sliding-window layer's attention over the band's
pairs, forward and backward, k and v read once a group
(``lib/counts_mellum2.window_attention_roofline``), over the device seconds
of the kernels whose name holds ``flash_attention_window`` (the windowed
walk's three kernels). ``None`` where the trace holds no such kernel or the
configuration has no window."""
from ..lib import counts_mellum2 as counts
from ..lib import peaks

NAME = "flash_attention_window"


def read(record):
    t, steps = record["trace"], record["window"].get("traced_steps")
    sizes, mix = record["sizes"], record["traffic"]
    if not t or not steps or "sliding_window" not in sizes:
        return None
    spent = sum(s for name, s in t["op_seconds"].items() if NAME in name)
    if spent <= 0:
        return None
    layers = list(counts.layer_kinds(sizes)).count(counts.SLIDING)
    least = counts.window_attention_roofline(
        sizes, mix["batch"], mix["seq"],
        peaks.peaks_for(record["device"]["kind"]))
    return 100.0 * least * layers * steps / spent
