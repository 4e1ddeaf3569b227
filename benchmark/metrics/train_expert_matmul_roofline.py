"""Least time the chip could take for the three grouped products of every
expert layer, forward and backward, for the slots really routed in the traced
steps (``lib/counts_qwen3_next.py``: each held expert's weights read once),
over the device seconds the layer spends on its held experts: everything under
the ``experts`` scope inside an expert layer (``window.scope_seconds``: the
pass loop with its gather, float32 scatter-add and weight-gradient sums) and
the grouped-product kernels themselves, the op names that hold ``ragged-dot``,
which XLA books to the layer, outside the scope that called them (my chip
run, PR 28). ``None`` without the scope."""
from ..lib import counts_qwen3_next as counts
from ..lib import peaks

SCOPE = "/experts/experts"


def read(record):
    steps = record["window"].get("traced_steps")
    routed = record["window"].get("routed_slots")
    scopes = record["window"].get("scope_seconds")
    if not steps or not routed or not scopes:
        return None
    under = sum(s for path, s in scopes.items() if path.endswith(SCOPE))
    if under <= 0:
        return None
    by_op = (record["trace"] or {}).get("op_seconds", {})
    spent = under + sum(s for name, s in by_op.items()
                        if "ragged-dot" in name)
    pk = peaks.peaks_for(record["device"]["kind"])
    least = sum(counts.expert_roofline(record["sizes"], slots, pk)
                for step in routed[:steps] for slots in step)
    return 100.0 * least / spent
