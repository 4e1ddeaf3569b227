"""Operations and bytes the one-part-a-layer hybrid decoder
(``configs/nemotron-3-nano-*``) needs, from shapes alone.

As ``lib/counts.py``: counted once, whatever implements it; a multiply-add
is 2 operations; causal attention over the lower triangle only;
recomputation is not counted as work. ``sizes`` is the configuration file's
dict: ``n_routed_experts`` and ``vocab_size`` are what this chip HOLDS, the
router keeps its published ``router_experts`` outputs. The held experts are
counted by the slots really routed to them, which the program's
``routed_slots`` buffers give per step; the layers by their kinds, the first
``num_hidden_layers`` letters of ``hybrid_override_pattern``.
"""
from . import counts
from .weights_nemotron_h import kinds, mamba_widths


def layer_kinds(s):
    """(Mamba-2 layers, expert layers, attention layers) of those held."""
    held = kinds(s)
    return (held.count("mamba"), held.count("experts"),
            held.count("attention"))


def mamba_layer_weights(s):
    """in_proj (x B C | z and dt) and out_proj of one Mamba-2 mixer."""
    inner, _, channels = mamba_widths(s)
    h = s["hidden_size"]
    return h * (channels + inner) + h * s["mamba_num_heads"] + inner * h


def attention_layer_weights(s):
    h, d = s["hidden_size"], s["head_dim"]
    heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
    return h * heads * d + 2 * h * kv * d + heads * d * h


def shared_and_router_weights(s):
    h = s["hidden_size"]
    return 2 * h * s["moe_shared_expert_intermediate_size"] \
        + h * s["router_experts"]


def expert_weights(s):
    """Weights one routed slot is multiplied with: up and down, no gate."""
    return 2 * s["hidden_size"] * s["moe_intermediate_size"]


def n_params(s):
    """Parameters this chip holds (the embedding and the untied head each
    over the held rows)."""
    inner, _, channels = mamba_widths(s)
    h = s["hidden_size"]
    mamba, experts, attention = layer_kinds(s)
    small = channels * (s["conv_kernel"] + 1) + 3 * s["mamba_num_heads"] \
        + inner
    return 2 * s["vocab_size"] * h + h + s["num_hidden_layers"] * h \
        + mamba * (mamba_layer_weights(s) + small) \
        + attention * attention_layer_weights(s) \
        + experts * (shared_and_router_weights(s)
                     + s["n_routed_experts"] * expert_weights(s))


def scan_flops_per_token(s):
    """The recurrence of one layer, forward, per token: per head the decay
    of S (P N), the rank-one update dt x B^T and S C (2 P N each)."""
    return s["mamba_num_heads"] * 5 * s["mamba_head_dim"] \
        * s["ssm_state_size"]


def attention_flops_per_token(s, seq):
    """Causal attention of one layer per token, forward: QK^T and PV over
    the (seq + 1) / 2 keys a token sees on average."""
    return 4.0 * s["num_attention_heads"] * s["head_dim"] * (seq + 1) / 2


def train_flops_per_token(s, seq, routed_slots_per_token):
    """Forward + backward of one token: 6 x the dense weights it meets (the
    Mamba-2 and attention projections, the shared expert and the router of
    every expert layer, the untied head over the held vocabulary) and the
    conv's taps, 6 x an expert's weights for each routed slot of each expert
    layer, 3 x the recurrence, 3 x attention's two products."""
    mamba, experts, attention = layer_kinds(s)
    dense = mamba * mamba_layer_weights(s) \
        + attention * attention_layer_weights(s) \
        + experts * shared_and_router_weights(s) \
        + s["hidden_size"] * s["vocab_size"]
    conv = mamba * mamba_widths(s)[2] * s["conv_kernel"]
    return 6 * (dense + conv) \
        + 6 * experts * routed_slots_per_token * expert_weights(s) \
        + 3 * mamba * scan_flops_per_token(s) \
        + 3 * attention * attention_flops_per_token(s, seq)


def scan_roofline(s, batch, seq, peaks, forwards=1, itemsize=2):
    """Least seconds for one layer's scan over ``batch`` sequences, its
    forward ``forwards`` times (2 where the mixer is made again in the
    backward) and its backward once: the recurrence's operations (the
    backward twice the forward), against reading x, B, C, dt and writing y
    (forward), and reading those with dy and writing dx, dB, dC, d dt
    (backward). B and C are read once a token, for all 8 groups."""
    tokens = batch * seq
    inner, bc, _ = mamba_widths(s)
    x, bcb, dt = inner * itemsize, 2 * bc * itemsize, \
        s["mamba_num_heads"] * 4
    flops = tokens * scan_flops_per_token(s)
    fwd, _ = counts.roofline_seconds(flops, tokens * (2 * x + bcb + dt),
                                     peaks)
    bwd, _ = counts.roofline_seconds(
        2 * flops, tokens * (3 * x + 2 * bcb + 2 * dt), peaks)
    return forwards * fwd + bwd


def expert_roofline(s, routed_slots, peaks, itemsize=2):
    """Least seconds for one layer's relu^2 experts, forward + backward, for
    ``routed_slots`` rows: two grouped products forward and four backward,
    6 x a slot's weights in operations; each held expert's weights read
    once forward, read and their gradient written backward; the rows'
    inputs, up products, activations and outputs read and written once each
    way."""
    h, d = s["hidden_size"], s["moe_intermediate_size"]
    w_bytes = s["n_routed_experts"] * expert_weights(s) * itemsize
    rows = routed_slots * (2 * h + 2 * d) * itemsize
    fwd, _ = counts.roofline_seconds(
        2 * routed_slots * expert_weights(s), w_bytes + rows, peaks)
    bwd, _ = counts.roofline_seconds(
        4 * routed_slots * expert_weights(s), 2 * w_bytes + 2 * rows, peaks)
    return fwd + bwd


def attention_roofline(s, batch, seq, peaks, itemsize=2):
    """Least seconds for one attention layer's causal attention, forward +
    backward (the backward twice the forward's operations): q, k, v read
    and o written forward; q, k, v, o and do read and dq, dk, dv written
    backward; k and v read once a group of query heads."""
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    tokens = batch * seq
    q, kvb = heads * d * itemsize, 2 * kv * d * itemsize
    flops = tokens * attention_flops_per_token(s, seq)
    fwd, _ = counts.roofline_seconds(flops, tokens * (2 * q + kvb), peaks)
    bwd, _ = counts.roofline_seconds(2 * flops, tokens * (4 * q + 2 * kvb),
                                     peaks)
    return fwd + bwd
