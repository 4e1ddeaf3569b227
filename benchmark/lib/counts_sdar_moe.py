"""Operations and bytes the block-diffusion sparse decoder needs, from
shapes alone.

As ``lib/counts.py``: counted once, whatever implements it; a multiply-add
is 2 operations; recomputation (the flash backward's second QK^T, the expert
backward's second forward, a recomputed mixer) is not counted as work. A
TOKEN is a clean token: the model runs over a stream of two positions a
token (the clean one and the noised one), so a token meets the trunk's dense
weights twice and the head once. Attention is counted over the pairs the
two-stream block mask allows and no more: ``seq ** 2 + seq * block`` a head
and sequence (each half's rows see (seq / block) (seq / block + 1) / 2
blocks of block x block pairs). ``sizes`` is the configuration file's dict:
``num_experts`` and ``vocab_size`` are what this chip HOLDS, the router
keeps its published ``router_experts`` outputs. The held experts are counted
by the slots really routed to them, which the program's ``routed_slots``
buffers give per step.
"""
from . import counts


def attention_layer_weights(s):
    h, d = s["hidden_size"], s["head_dim"]
    heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
    return h * heads * d + 2 * h * kv * d + heads * d * h


def router_weights(s):
    return s["hidden_size"] * s["router_experts"]


def expert_weights(s):
    """Weights one routed slot is multiplied with."""
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def n_params(s):
    """Parameters this chip holds (embedding and head apart: untied)."""
    h, d = s["hidden_size"], s["head_dim"]
    per_layer = 2 * h + 2 * d + attention_layer_weights(s) \
        + router_weights(s) + s["num_experts"] * expert_weights(s)
    return 2 * s["vocab_size"] * h + h + s["num_hidden_layers"] * per_layer


def allowed_pairs(seq, block):
    """Query-key pairs of one head and one sequence of ``seq`` clean tokens
    under the two-stream block mask."""
    return seq * seq + seq * block


def attention_flops(s, batch, seq, block, backward=False):
    """One layer's attention over ``batch`` sequences, all heads: QK^T and
    PV over the allowed pairs; the backward is twice that."""
    fwd = 4.0 * batch * s["num_attention_heads"] * s["head_dim"] \
        * allowed_pairs(seq, block)
    return 2 * fwd if backward else fwd


def attention_bytes(s, batch, seq, backward=False, itemsize=2):
    """Least traffic over the stream's 2 seq positions, k and v read once a
    group of query heads: read q, k, v and write o (forward); read q, k, v,
    o, do and write dq, dk, dv (backward)."""
    positions = 2 * batch * seq
    q = s["num_attention_heads"] * s["head_dim"] * itemsize
    kv = 2 * s["num_key_value_heads"] * s["head_dim"] * itemsize
    return positions * ((4 * q + 2 * kv) if backward else (2 * q + kv))


def attention_roofline(s, batch, seq, block, peaks):
    """Least seconds for one layer's attention, forward + backward."""
    fwd, _ = counts.roofline_seconds(
        attention_flops(s, batch, seq, block),
        attention_bytes(s, batch, seq), peaks)
    bwd, _ = counts.roofline_seconds(
        attention_flops(s, batch, seq, block, backward=True),
        attention_bytes(s, batch, seq, backward=True), peaks)
    return fwd + bwd


def train_flops_per_token(s, seq, block, routed_slots_per_token):
    """Forward + backward of one clean token of a ``seq``-long sequence:
    6 x the dense weights of the trunk (projections and router) for each of
    its two stream positions, 6 x the head once (the noised position's), 6 x
    an expert's weights for each slot routed (``routed_slots_per_token``:
    per token and layer, both positions' slots), 3 x attention's two
    products over the token's share of the allowed pairs."""
    n = s["num_hidden_layers"]
    trunk = n * (attention_layer_weights(s) + router_weights(s))
    return 6 * (2 * trunk + s["hidden_size"] * s["vocab_size"]) \
        + 6 * n * routed_slots_per_token * expert_weights(s) \
        + 3 * n * attention_flops(s, 1, seq, block) / seq
