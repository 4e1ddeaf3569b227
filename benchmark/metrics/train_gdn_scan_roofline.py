"""Least time the chip could take for the gated delta rule of the
linear-attention layers, forward and backward (``lib/counts_qwen3_next.py``:
the recurrence's operations, q, k, v, the gates and o read and written once),
over the device seconds under the ``gated_delta_rule`` scope of the traced
steps (``window.scope_seconds``, which the driver reads from the trace with
the program's own ``profiler/statistic.py``): the two kernels
``gated_delta_rule_fwd`` / ``_bwd`` that carry the state AND what XLA prepares
for all chunks round them (the chunk's inverse, the decay tables, the chunk
products), recomputation included. Numerator and denominator cover the same
work, so mending the preparation moves it. ``None`` without such a scope."""
from ..lib import counts_qwen3_next as counts
from ..lib import peaks

SCOPE = "/gated_delta_rule"


def read(record):
    steps = record["window"].get("traced_steps")
    scopes = record["window"].get("scope_seconds")
    if not scopes or not steps:
        return None
    spent = sum(s for path, s in scopes.items() if path.endswith(SCOPE))
    if spent <= 0:
        return None
    sizes, mix = record["sizes"], record["traffic"]
    linear, _ = counts.layer_kinds(sizes)
    least = counts.delta_rule_roofline(
        sizes, mix["batch"], mix["seq"],
        peaks.peaks_for(record["device"]["kind"]))
    return 100.0 * least * linear * steps / spent
