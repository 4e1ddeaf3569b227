"""As ``train_flash_attn_roofline``, for grouped-query heads: least time for
the full-attention layers' causal attention, forward and backward, 16 query
heads on 2 KV heads of 256 at the cell's sequence (``lib/
counts_qwen3_next.py``: k and v read once a group), over the device time of
the ``tpu_custom_call``s handed q and the narrower k and v by shape."""
from ..lib import counts_qwen3_next as counts
from ..lib import peaks, xplane


def read(record):
    t, steps = record["trace"], record["window"].get("traced_steps")
    if not t or not steps:
        return None
    sizes, mix = record["sizes"], record["traffic"]
    d = sizes["head_dim"]
    q = f"[{mix['batch'] * sizes['num_attention_heads']},{mix['seq']},{d}]"
    kv = f"[{mix['batch'] * sizes['num_key_value_heads']},{mix['seq']},{d}]"
    spent = xplane.kernel_time(t, [q, kv, kv])
    if spent <= 0:
        return None
    _, full = counts.layer_kinds(sizes)
    least = counts.attention_roofline(
        sizes, mix["batch"], mix["seq"],
        peaks.peaks_for(record["device"]["kind"]))
    return 100.0 * least * full * steps / spent
