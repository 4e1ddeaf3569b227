"""Least time for the attention layers' causal attention of the
one-part-a-layer hybrid decoder, forward and backward, grouped query heads
with no positional term (32 query heads on 2 KV heads of 128 in
``configs/nemotron-3-nano-*``; ``lib/counts_nemotron_h.py``: k and v read
once a group, the layers by the pattern's ``*`` letters), over the device
time of the ``tpu_custom_call``s handed q and the narrower k and v by shape,
as ``train_gqa_attn_roofline`` reads Qwen3-Next's. ``None`` without such
kernels or without a pattern of layers."""
from ..lib import counts_nemotron_h as counts
from ..lib import peaks, xplane


def read(record):
    t, steps = record["trace"], record["window"].get("traced_steps")
    sizes = record["sizes"]
    if not t or not steps or "hybrid_override_pattern" not in sizes:
        return None
    mix = record["traffic"]
    d = sizes["head_dim"]
    q = f"[{mix['batch'] * sizes['num_attention_heads']},{mix['seq']},{d}]"
    kv = f"[{mix['batch'] * sizes['num_key_value_heads']},{mix['seq']},{d}]"
    spent = xplane.kernel_time(t, [q, kv, kv])
    if spent <= 0:
        return None
    _, _, attention = counts.layer_kinds(sizes)
    least = counts.attention_roofline(
        sizes, mix["batch"], mix["seq"],
        peaks.peaks_for(record["device"]["kind"]))
    return 100.0 * least * attention * steps / spent
