"""Operations and bytes the state-space / attention hybrid decoder needs,
from shapes alone.

As ``lib/counts.py``: counted once, whatever implements it; a multiply-add is
2 operations; causal attention over the lower triangle only; recomputation
(the flash backward's second QK^T, the recomputed mixers, the expert
backward's second forward) is not counted as work. ``sizes`` is the
configuration file's dict: every count in it is what this chip HOLDS (its
share of the state-space heads, of the query and KV heads, of the vocabulary),
the router keeps its published ``router_experts`` outputs. The held experts
are counted by the slots really routed to them, which the program's
``routed_slots`` buffers give per step.
"""
from . import counts
from .weights_granite_hybrid import mamba_widths


def mamba_layer_weights(s):
    """in_proj (x B C | z and dt) and out_proj of one state-space mixer."""
    inner, _, channels = mamba_widths(s)
    h = s["hidden_size"]
    return h * (channels + inner) + h * s["mamba_n_heads"] + inner * h


def attention_layer_weights(s):
    h, d = s["hidden_size"], s["head_dim"]
    heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
    return h * heads * d + 2 * h * kv * d + heads * d * h


def shared_and_router_weights(s):
    h = s["hidden_size"]
    return 3 * h * s["shared_intermediate_size"] + h * s["router_experts"]


def expert_weights(s):
    """Weights one routed slot is multiplied with."""
    return 3 * s["hidden_size"] * s["intermediate_size"]


def layer_kinds(s):
    """(state-space layers, attention layers) of the layers held."""
    kinds = s["layer_types"][:s["num_hidden_layers"]]
    full = sum(kind == "attention" for kind in kinds)
    return len(kinds) - full, full


def n_params(s):
    """Parameters this chip holds (the tied embedding once)."""
    inner, _, channels = mamba_widths(s)
    h = s["hidden_size"]
    mamba, full = layer_kinds(s)
    small = channels * (s["mamba_d_conv"] + 1) + 3 * s["mamba_n_heads"] \
        + inner
    per_layer = 2 * h + shared_and_router_weights(s) \
        + s["num_local_experts"] * expert_weights(s)
    return s["vocab_size"] * h + h \
        + mamba * (mamba_layer_weights(s) + small) \
        + full * attention_layer_weights(s) \
        + s["num_hidden_layers"] * per_layer


def scan_flops_per_token(s):
    """The recurrence of one layer, forward, per token: per head the decay
    of S (P N), the rank-one update dt x B^T and S C (2 P N each)."""
    return s["mamba_n_heads"] * 5 * s["mamba_d_head"] * s["mamba_d_state"]


def attention_flops_per_token(s, seq, backward=False):
    """Causal attention of one attention layer per token: QK^T and PV over
    the (seq + 1) / 2 keys a token sees on average; the backward twice."""
    fwd = 4.0 * s["num_attention_heads"] * s["head_dim"] * (seq + 1) / 2
    return 2 * fwd if backward else fwd


def train_flops_per_token(s, seq, routed_slots_per_token):
    """Forward + backward of one token: 6 x the dense weights it meets (the
    projections, the shared expert and the router of every layer, the tied
    head over the held vocabulary) and the conv's taps, 6 x an expert's
    weights for each routed slot of each layer, 3 x the recurrence, 3 x
    attention's two products."""
    mamba, full = layer_kinds(s)
    n = s["num_hidden_layers"]
    dense = mamba * mamba_layer_weights(s) \
        + full * attention_layer_weights(s) \
        + n * shared_and_router_weights(s) \
        + s["hidden_size"] * s["vocab_size"]
    conv = mamba * mamba_widths(s)[2] * s["mamba_d_conv"]
    return 6 * (dense + conv) \
        + 6 * n * routed_slots_per_token * expert_weights(s) \
        + 3 * mamba * scan_flops_per_token(s) \
        + 3 * full * attention_flops_per_token(s, seq)


def scan_roofline(s, batch, seq, peaks, forwards=1, itemsize=2):
    """Least seconds for one layer's scan over ``batch`` sequences, its
    forward ``forwards`` times (2 where the mixer is made again in the
    backward) and its backward once: the recurrence's operations (the
    backward twice the forward), against reading x, B, C, dt and writing y
    (forward), and reading those with dy and writing dx, dB, dC, d dt
    (backward)."""
    tokens = batch * seq
    inner, _, channels = mamba_widths(s)
    x, bc, dt = inner * itemsize, (channels - inner) * itemsize, \
        s["mamba_n_heads"] * 4
    flops = tokens * scan_flops_per_token(s)
    fwd, _ = counts.roofline_seconds(flops, tokens * (2 * x + bc + dt),
                                     peaks)
    bwd, _ = counts.roofline_seconds(
        2 * flops, tokens * (3 * x + 2 * bc + 2 * dt), peaks)
    return forwards * fwd + bwd
