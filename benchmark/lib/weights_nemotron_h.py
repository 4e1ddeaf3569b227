"""Seeded weights of the one-part-a-layer hybrid decoder
(``configs/nemotron-3-nano-*``), made on the device in one jitted call, in
the type the configuration states. The program and the reference each call
this with the same seed: neither is handed what the other made.

Layout: a flat dict, per-layer leaves named ``<leaf>.<layer>`` (a layer is
one of three kinds, from ``hybrid_override_pattern``); the held experts of a
layer are ONE leaf each, stacked ``[held, ...]``, as the program holds them.
``n_routed_experts`` and ``vocab_size`` in ``sizes`` are what this chip
HOLDS; the router keeps its published ``router_experts`` columns. The
expert layers' correction bias (``bias.<layer>``, float32 whatever the
type: the program holds it so) is a buffer, not a parameter. Column orders
are this repo's (``xbcz_w`` as x | B | C | z with ``dt_w`` apart): a
relabelling of the public ``in_proj`` under random weights.

Init (``assumed`` in the configuration file): N(0, 0.02) for every matrix,
the conv taps and the conv bias, but the embedding, whose rows are N(0,
4^2) (``weights_sdar_moe.EMBED_STD``: a residual stream that stays a token's
own embedding plus small branches, so that a position is routed by its
token), and the untied head, N(0, ``HEAD_STD``^2): a tenth, so that the
first logits are near-uniform (a loss 0.005 over ln vocab, where N(0, 0.02)
gives 0.5). From N(0, 0.02) on uniform random ids the first 90 steps learn
one thing, to flatten the logits: a gradient of one sign step after step,
which under Adam moved every token's router logits together and this
chip's routed load to 0.5x even whatever the correction bias's balancing
rate (PERF.md section 6); norm gains 1 + N(0, 0.02), so that each takes
part; ``A_log`` = log U(1, 16), ``dt_bias`` the inverse softplus of dt
log-uniform in [1e-3, 1e-1], ``D`` = 1 (the public init); the router's columns centred within each
chip's group of held experts (columns 0..7, 8..15, ...) and scaled to one
common norm, so that no expert and no chip's group starts favoured; the
experts' down projections (routed and shared) with each output column
centred over the expert's width: relu^2 activations are all positive, so an
uncentred W_d adds one token-independent vector to every token, which grew
to 0.24 of the last router input's rms (0.08 centred; PERF.md section 6)
and moved every token's logits together under Adam; the correction bias
N(0, ``BIAS_STD``^2) centred within each chip's group, of the size of the
gap between a token's sixth and seventh sigmoid scores, so that it changes
some choices and favours no chip's group.
"""
import functools
import math

from .weights_sdar_moe import EMBED_STD, PROJECTION_SEED, SIGNS, STD, key_data

BIAS_STD = 0.01
HEAD_STD = 0.002
KEYS = ("num_hidden_layers", "hidden_size", "vocab_size", "head_dim",
        "num_attention_heads", "num_key_value_heads", "mamba_num_heads",
        "mamba_head_dim", "ssm_state_size", "conv_kernel", "n_groups",
        "n_routed_experts", "router_experts", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "hybrid_override_pattern")
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def kinds(sizes):
    """Each held layer's kind: 'mamba', 'experts' or 'attention'."""
    pattern = sizes["hybrid_override_pattern"]
    return tuple(KINDS[c] for c in pattern[:sizes["num_hidden_layers"]])


def mamba_widths(sizes):
    """(inner = heads x P, the B and C groups' width, the conv's channels)."""
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    bc = sizes["n_groups"] * sizes["ssm_state_size"]
    return inner, bc, inner + 2 * bc


def leaf_table(sizes):
    """[(name, shape, kind)] in a fixed order; kind is how it is drawn."""
    h, v, d = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    mheads = sizes["mamba_num_heads"]
    inner, _, channels = mamba_widths(sizes)
    held, wide = sizes["n_routed_experts"], sizes["router_experts"]
    de = sizes["moe_intermediate_size"]
    ds = sizes["moe_shared_expert_intermediate_size"]
    out = [("embed", (v, h), "embed"), ("head_w", (h, v), "head"),
           ("norm_f", (h,), "round_one")]
    for i, kind in enumerate(kinds(sizes)):
        leaves = [("norm", (h,), "round_one")]
        if kind == "mamba":
            leaves += [("xbcz_w", (h, channels + inner), "normal"),
                       ("dt_w", (h, mheads), "normal"),
                       ("conv_w", (channels, sizes["conv_kernel"]), "normal"),
                       ("conv_b", (channels,), "normal"),
                       ("a_log", (mheads,), "a_log"),
                       ("dt_bias", (mheads,), "dt_bias"),
                       ("d_skip", (mheads,), "one"),
                       ("gnorm", (inner,), "round_one"),
                       ("out_w", (inner, h), "normal")]
        elif kind == "attention":
            leaves += [("q_w", (h, heads * d), "normal"),
                       ("k_w", (h, kv * d), "normal"),
                       ("v_w", (h, kv * d), "normal"),
                       ("o_w", (heads * d, h), "normal")]
        else:
            leaves += [("router", (h, wide), "router"),
                       ("bias", (wide,), "bias"),
                       ("eu_w", (held, h, de), "normal"),
                       ("ed_w", (held, de, h), "down"),
                       ("su_w", (h, ds), "normal"),
                       ("sd_w", (ds, h), "down")]
        out += [(f"{name}.{i}", shape, kind) for name, shape, kind in leaves]
    return out


def _centred(x, held):
    """x [.., wide] less its mean within each group of ``held`` columns."""
    groups = x.reshape(*x.shape[:-1], x.shape[-1] // held, held)
    return (groups - groups.mean(-1, keepdims=True)).reshape(x.shape)


def _draw(sizes_items, kd, dtype):
    import jax
    import jax.numpy as jnp

    sizes = dict(sizes_items)
    key = jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32),
                                   impl="threefry2x32")
    held = sizes["n_routed_experts"]
    out = {}
    for i, (name, shape, kind) in enumerate(leaf_table(sizes)):
        k = jax.random.fold_in(key, i)
        if dtype == SIGNS:
            out[name] = jax.random.rademacher(k, shape, jnp.int8)
            continue
        if kind == "a_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif kind == "dt_bias":  # softplus(dt_bias) = dt in [1e-3, 1e-1]
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            x = step + jnp.log(-jnp.expm1(-step))
        elif kind == "one":
            x = jnp.ones(shape, jnp.float32)
        else:
            x = STD * jax.random.normal(k, shape, jnp.float32)
            if kind == "round_one":
                x = 1.0 + x
            elif kind == "embed":
                x = x * (EMBED_STD / STD)
            elif kind == "head":
                x = x * (HEAD_STD / STD)
            elif kind == "router":
                # centred within each chip's group of held experts, then
                # every expert's column of one norm
                x = _centred(x, held)
                x = x * (STD * math.sqrt(shape[0])
                         / jnp.linalg.norm(x, axis=0, keepdims=True))
            elif kind == "down":
                # each output column centred over the expert's width
                x = x - x.mean(axis=-2, keepdims=True)
            elif kind == "bias":
                out[name] = _centred(x * (BIAS_STD / STD), held)
                continue
        out[name] = x.astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax

    return jax.jit(_draw, static_argnums=(0, 2))


def _static(sizes):
    return tuple((k, sizes[k]) for k in KEYS)


def make(sizes, seed, dtype):
    """{leaf name: array} for the seed, in ``dtype`` (the biases float32)."""
    return _jitted()(_static(sizes), key_data(seed), dtype)


def projection(sizes):
    """One fixed random direction of +-1 per leaf, the same for every seed:
    what a leaf is projected on where its element-wise error is read."""
    return make(sizes, PROJECTION_SEED, SIGNS)


def is_bias(name):
    return name.startswith("bias.")
