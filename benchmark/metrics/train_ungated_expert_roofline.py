"""Least time the chip could take for the relu^2 experts of every expert
layer, forward and backward, for the slots really routed in the traced
steps (``lib/counts_nemotron_h.py``: two grouped products forward and four
backward, each held expert's weights read once), over the device seconds the
layer spends on its held experts: everything under the ``experts`` scope
inside an expert layer (``window.scope_seconds``) and the grouped-product
kernels themselves, the op names that hold ``ragged-dot``, which XLA books
outside the scope that called them; as ``train_expert_matmul_roofline``
reads the SwiGLU experts, whose count prices three products a slot. ``None``
without the scope or without a pattern of layers."""
from ..lib import counts_nemotron_h as counts
from ..lib import peaks

SCOPE = "/experts/experts"


def read(record):
    steps = record["window"].get("traced_steps")
    routed = record["window"].get("routed_slots")
    scopes = record["window"].get("scope_seconds")
    sizes = record["sizes"]
    if not steps or not routed or not scopes \
            or "hybrid_override_pattern" not in sizes:
        return None
    under = sum(s for path, s in scopes.items() if path.endswith(SCOPE))
    if under <= 0:
        return None
    by_op = (record["trace"] or {}).get("op_seconds", {})
    spent = under + sum(s for name, s in by_op.items()
                        if "ragged-dot" in name)
    pk = peaks.peaks_for(record["device"]["kind"])
    least = sum(counts.expert_roofline(sizes, slots, pk)
                for step in routed[:steps] for slots in step)
    return 100.0 * least / spent
