"""Operations and bytes the sliding-window sparse decoder needs, from shapes
alone.

As ``lib/counts.py``: counted once, whatever implements it; a multiply-add
is 2 operations; recomputation (the flash backward's second QK^T, the expert
backward's second forward, a recomputed mixer) is not counted as work.
Attention is counted over the pairs its mask allows and no more: a sliding
layer's band, sum over rows i of min(i + 1, W), and a full layer's causal
triangle, s (s + 1) / 2, a head and sequence. ``sizes`` is the
configuration file's dict: ``num_experts`` and ``vocab_size`` are what this
chip HOLDS, the router keeps its published ``router_experts`` outputs, the
layers' kinds are the first ``num_hidden_layers`` of ``layer_types``. The
held experts are counted by the slots really routed to them, which the
program's ``routed_slots`` buffers give per step.
"""
from . import counts
from .counts_sdar_moe import (attention_layer_weights, expert_weights,
                              router_weights)

SLIDING = "sliding_attention"


def layer_kinds(s):
    return s["layer_types"][:s["num_hidden_layers"]]


def n_params(s):
    """Parameters this chip holds (embedding and head apart: untied)."""
    h = s["hidden_size"]
    per_layer = 2 * h + attention_layer_weights(s) + router_weights(s) \
        + s["num_experts"] * expert_weights(s)
    return 2 * s["vocab_size"] * h + h + s["num_hidden_layers"] * per_layer


def band_pairs(seq, window):
    """Query-key pairs of one head and sequence of ``seq`` under a sliding
    window of ``window`` keys (its own included)."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def pairs(s, kind, seq):
    return band_pairs(seq, s["sliding_window"] if kind == SLIDING else seq)


def attention_flops(s, batch, seq, kind, backward=False):
    """One layer's attention over ``batch`` sequences, all heads: QK^T and
    PV over the allowed pairs; the backward is twice that."""
    fwd = 4.0 * batch * s["num_attention_heads"] * s["head_dim"] \
        * pairs(s, kind, seq)
    return 2 * fwd if backward else fwd


def attention_bytes(s, batch, seq, backward=False, itemsize=2):
    """Least traffic, k and v read once a group of query heads: read q, k, v
    and write o (forward); read q, k, v, o, do and write dq, dk, dv
    (backward)."""
    q = s["num_attention_heads"] * s["head_dim"] * itemsize
    kv = 2 * s["num_key_value_heads"] * s["head_dim"] * itemsize
    return batch * seq * ((4 * q + 2 * kv) if backward else (2 * q + kv))


def window_attention_roofline(s, batch, seq, peaks):
    """Least seconds for one sliding layer's attention, forward + backward:
    the band's pairs, k and v read once a group."""
    least = 0.0
    for backward in (False, True):
        t, _ = counts.roofline_seconds(
            attention_flops(s, batch, seq, SLIDING, backward),
            attention_bytes(s, batch, seq, backward), peaks)
        least += t
    return least


def train_flops_per_token(s, seq, routed_slots_per_token):
    """Forward + backward of one token of a ``seq``-long sequence: 6 x the
    dense weights (projections, router, head), 6 x an expert's weights for
    each slot routed (``routed_slots_per_token``, per token and layer), 3 x
    attention's two products over the token's share of each layer's allowed
    pairs."""
    kinds = layer_kinds(s)
    trunk = len(kinds) * (attention_layer_weights(s) + router_weights(s))
    return 6 * (trunk + s["hidden_size"] * s["vocab_size"]) \
        + 6 * len(kinds) * routed_slots_per_token * expert_weights(s) \
        + 3 * sum(attention_flops(s, 1, seq, kind) for kind in kinds) / seq
