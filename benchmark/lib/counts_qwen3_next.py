"""Operations and bytes the sparse hybrid decoder needs, from shapes alone.

As ``lib/counts.py``: counted once, whatever implements it; a multiply-add is
2 operations; causal attention over the lower triangle only; recomputation
(the flash backward's second QK^T, the recomputed mixers, the expert
backward's second forward) is not counted. ``sizes`` is the configuration
file's dict. The held experts are counted by the slots really routed to
them, which the program's ``routed_slots`` buffers give per step.
"""
from . import counts


def _linear_layer_weights(s):
    n_qk = s["linear_num_key_heads"] * s["linear_key_head_dim"]
    n_v = s["linear_num_value_heads"] * s["linear_value_head_dim"]
    h = s["hidden_size"]
    return h * (2 * n_qk + 2 * n_v) + h * 2 * s["linear_num_value_heads"] \
        + n_v * h


def _full_layer_weights(s):
    h, d = s["hidden_size"], s["head_dim"]
    heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
    return h * heads * d * 2 + 2 * h * kv * d + heads * d * h


def _shared_and_router_weights(s):
    h = s["hidden_size"]
    return 3 * h * s["shared_expert_intermediate_size"] + h \
        + h * s["router_experts"]


def expert_weights(s):
    """Weights one routed slot is multiplied with."""
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def layer_kinds(s):
    """(linear-attention layers, full-attention layers)."""
    n = s["num_hidden_layers"]
    full = n // s["full_attention_interval"]
    return n - full, full


def delta_rule_flops_per_token(s):
    """The recurrence of one layer, forward, per token: per value head the
    decay of S (d_k d_v), S^T k, the rank-one update and S^T q (2 d_k d_v
    each)."""
    return s["linear_num_value_heads"] * 7 * s["linear_key_head_dim"] \
        * s["linear_value_head_dim"]


def attention_flops_per_token(s, seq, backward=False):
    """Causal attention of one full layer per token: QK^T and PV over the
    (seq + 1) / 2 keys a token sees on average; the backward twice that."""
    fwd = 4.0 * s["num_attention_heads"] * s["head_dim"] * (seq + 1) / 2
    return 2 * fwd if backward else fwd


def train_flops_per_token(s, seq, routed_slots_per_token):
    """Forward + backward of one token: 6 x the dense weights it meets (the
    projections, the shared expert and the router of every layer, the untied
    head over the held vocabulary), 6 x an expert's weights for each routed
    slot of each layer, 3 x the recurrence, 3 x attention's two products."""
    lin, full = layer_kinds(s)
    n = s["num_hidden_layers"]
    dense = lin * _linear_layer_weights(s) + full * _full_layer_weights(s) \
        + n * _shared_and_router_weights(s) \
        + s["hidden_size"] * s["vocab_size"]
    conv = lin * (2 * s["linear_num_key_heads"] * s["linear_key_head_dim"]
                  + s["linear_num_value_heads"] * s["linear_value_head_dim"]
                  ) * s["linear_conv_kernel_dim"]
    return 6 * (dense + conv) \
        + 6 * n * routed_slots_per_token * expert_weights(s) \
        + 3 * lin * delta_rule_flops_per_token(s) \
        + full * attention_flops_per_token(s, seq) * 3


def delta_rule_roofline(s, batch, seq, peaks, itemsize=2):
    """Least seconds for one layer's delta rule, forward + backward, over
    ``batch`` sequences: the recurrence's operations (the backward twice the
    forward), against reading q, k, v, g, beta and writing o (forward), and
    reading those with o and do and writing dq, dk, dv, dg, dbeta
    (backward)."""
    tokens = batch * seq
    hk, hv = s["linear_num_key_heads"], s["linear_num_value_heads"]
    dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
    qk, v, gates = 2 * hk * dk * itemsize, hv * dv * itemsize, 2 * hv * 4
    flops = tokens * delta_rule_flops_per_token(s)
    fwd, _ = counts.roofline_seconds(flops, tokens * (qk + 2 * v + gates),
                                     peaks)
    bwd, _ = counts.roofline_seconds(
        2 * flops, tokens * (2 * qk + 4 * v + 2 * gates), peaks)
    return fwd + bwd


def expert_roofline(s, routed_slots, peaks, itemsize=2):
    """Least seconds for one layer's three grouped products, forward +
    backward, for ``routed_slots`` rows: 6 x a slot's weights in operations;
    each held expert's weights read once forward, read and their gradient
    written backward; the rows' inputs, intermediates and outputs read and
    written once each way."""
    h, d = s["hidden_size"], s["moe_intermediate_size"]
    w_bytes = s["num_experts"] * expert_weights(s) * itemsize
    rows = routed_slots * (2 * h + 3 * d) * itemsize
    fwd, _ = counts.roofline_seconds(
        2 * routed_slots * expert_weights(s), w_bytes + rows, peaks)
    bwd, _ = counts.roofline_seconds(
        4 * routed_slots * expert_weights(s), 2 * w_bytes + 2 * rows, peaks)
    return fwd + bwd


def attention_roofline(s, batch, seq, peaks, itemsize=2):
    """Least seconds for one full layer's causal attention, forward +
    backward: k and v are read once a group of query heads."""
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    tokens = batch * seq
    q, kvb = heads * d * itemsize, 2 * kv * d * itemsize
    fwd, _ = counts.roofline_seconds(
        tokens * attention_flops_per_token(s, seq),
        tokens * (2 * q + kvb), peaks)
    bwd, _ = counts.roofline_seconds(
        tokens * attention_flops_per_token(s, seq, backward=True),
        tokens * (4 * q + 2 * kvb), peaks)
    return fwd + bwd
