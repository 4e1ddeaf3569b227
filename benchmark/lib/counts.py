"""Operations and bytes the algorithm needs, from shapes alone.

Counted once, whatever implements it: a multiply-add is 2 operations, causal
attention is counted over the lower triangle only, recomputation (the flash
backward's second QK^T, activation remat) is not counted. ``sizes`` is a
configuration file's dict (``n_layer``, ``n_embd``, ``n_head``,
``n_positions``, ``padded_vocab``).
"""


def n_params(sizes):
    """Parameters of GPT-2 with a tied head and the padded vocabulary."""
    h, layers = sizes["n_embd"], sizes["n_layer"]
    per_layer = 12 * h * h + 13 * h  # qkv, proj, fc1, fc2 + biases + 2 LN
    return (sizes["padded_vocab"] + sizes["n_positions"]) * h \
        + layers * per_layer + 2 * h


def matmul_weights(sizes):
    """Weights a token is multiplied with in the trunk (head apart)."""
    return sizes["n_layer"] * 12 * sizes["n_embd"] ** 2


def train_flops_per_token(sizes, seq):
    """Forward + backward of one token of a ``seq``-long causal sequence:
    6 x (trunk weights + tied head), and attention's two products over the
    (seq + 1) / 2 keys a token sees on average, forward once and backward
    twice (dV, dP, dQ, dK are four products to the forward's two)."""
    h = sizes["n_embd"]
    dense = 6 * (matmul_weights(sizes) + sizes["padded_vocab"] * h)
    attn = sizes["n_layer"] * 3 * 4 * h * (seq + 1) / 2
    return dense + attn


def attention_flops(batch, seq, n_embd, backward):
    """Causal attention over ``batch`` sequences, all heads: QK^T and PV
    over seq (seq + 1) / 2 query-key pairs; the backward is twice that."""
    fwd = 4.0 * batch * n_embd * seq * (seq + 1) / 2
    return 2 * fwd if backward else fwd


def attention_bytes(batch, seq, n_embd, backward, itemsize=2):
    """Least traffic: read q, k, v and write o (forward); read q, k, v, o,
    do and write dq, dk, dv (backward)."""
    return (8 if backward else 4) * batch * seq * n_embd * itemsize


def roofline_seconds(flops, nbytes, peaks):
    """(least seconds the chip could take, which bound it is)."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
