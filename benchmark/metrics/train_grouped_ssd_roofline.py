"""Least time the chip could take for the Mamba-2 scan over 8 B / C groups
(``lib/counts_nemotron_h.py``: the recurrence's operations, x, B, C, dt and
y read and written once; the forward twice where the mixer is made again in
the backward, the backward once), over ALL device seconds under the
``ssd_scan`` scope of the traced steps (``window.scope_seconds``): the
kernels that carry the state AND whatever XLA does round them, as
``train_ssd_scan_roofline`` reads the one-group scan. ``None`` without such
a scope or without a pattern of layers."""
from ..lib import counts_nemotron_h as counts
from ..lib import peaks

SCOPE = "/ssd_scan"


def read(record):
    steps = record["window"].get("traced_steps")
    scopes = record["window"].get("scope_seconds")
    sizes = record["sizes"]
    if not scopes or not steps or "hybrid_override_pattern" not in sizes:
        return None
    spent = sum(s for path, s in scopes.items() if path.endswith(SCOPE))
    if spent <= 0:
        return None
    mix = record["traffic"]
    mamba, _, _ = counts.layer_kinds(sizes)
    least = counts.scan_roofline(
        sizes, mix["batch"], mix["seq"],
        peaks.peaks_for(record["device"]["kind"]),
        forwards=2 if sizes.get("recompute_mixer") else 1)
    return 100.0 * least * mamba * steps / spent
