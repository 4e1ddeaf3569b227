"""Least time the chip could take for the step's causal attention, forward
and backward (``lib/counts.py``: required operations and bytes only), over
the device time of the attention kernels in the trace. The kernels are found
by what they are handed: a ``tpu_custom_call`` with q, k and v among its
operands, three arrays of the cell's own attention shape."""
from ..lib import counts, peaks, xplane


def qkv_shapes(batch, seq, n_head, head_dim):
    """The layouts q, k or v can come in: heads folded into the batch (as
    ``ops/pallas/flash_attention.py`` takes them today), or apart."""
    return [f"[{batch * n_head},{seq},{head_dim}]",
            f"[{batch},{n_head},{seq},{head_dim}]",
            f"[{batch},{seq},{n_head},{head_dim}]"]


def read(record):
    t, steps = record["trace"], record["window"].get("traced_steps")
    if not t or not steps:
        return None
    sizes, mix = record["sizes"], record["traffic"]
    spent = sum(xplane.kernel_time(t, [shape] * 3) for shape in qkv_shapes(
        mix["batch"], mix["seq"], sizes["n_head"],
        sizes["n_embd"] // sizes["n_head"]))
    if spent <= 0:
        return None
    pk = peaks.peaks_for(record["device"]["kind"])
    least = 0.0
    for backward in (False, True):
        secs, _ = counts.roofline_seconds(
            counts.attention_flops(mix["batch"], mix["seq"], sizes["n_embd"],
                                   backward),
            counts.attention_bytes(mix["batch"], mix["seq"], sizes["n_embd"],
                                   backward), pk)
        least += secs
    return 100.0 * least * sizes["n_layer"] * steps / spent
