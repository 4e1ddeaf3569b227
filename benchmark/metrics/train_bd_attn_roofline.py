"""As ``train_gqa_attn_roofline``, under the two-stream block mask: least
time for every layer's attention over the allowed pairs, forward and
backward, k and v read once a group (``lib/counts_sdar_moe.py``), over the
device time of the ``tpu_custom_call``s handed the stream's q and the
narrower k and v by shape. ``None`` where the trace holds no such kernel."""
from ..lib import counts_sdar_moe as counts
from ..lib import peaks, xplane


def read(record):
    t, steps = record["trace"], record["window"].get("traced_steps")
    sizes, mix = record["sizes"], record["traffic"]
    if not t or not steps or "block_length" not in mix:
        return None
    d, stream = sizes["head_dim"], 2 * mix["seq"]
    q = f"[{mix['batch'] * sizes['num_attention_heads']},{stream},{d}]"
    kv = f"[{mix['batch'] * sizes['num_key_value_heads']},{stream},{d}]"
    spent = xplane.kernel_time(t, [q, kv, kv])
    if spent <= 0:
        return None
    least = counts.attention_roofline(
        sizes, mix["batch"], mix["seq"], mix["block_length"],
        peaks.peaks_for(record["device"]["kind"]))
    return 100.0 * least * sizes["num_hidden_layers"] * steps / spent
