"""Seeded weights of the state-space / attention hybrid decoder
(``configs/granite-4.0-h-*``), made on the device in one jitted call, in the
type the configuration states. The program and the reference each call this
with the same seed: neither is handed what the other made.

Layout: a flat dict, per-layer leaves named ``<leaf>.<layer>`` (the layers
are of two kinds, so nothing is stacked over them); the held experts of a
layer are ONE leaf each, stacked ``[held, ...]``, as the program holds them.
Every count in ``sizes`` is what this chip HOLDS (state-space heads, query
and KV heads, experts, rows of the vocabulary); the router keeps its
published ``router_experts`` columns. Column orders are this repo's
(``xbcz_w`` as x | B | C | z with ``dt_w`` a matrix of its own; ``*gu_w`` as
gate | up): relabellings of the public implementation's under random weights.

Init (``assumed`` in the configuration file): N(0, 0.02) for every matrix,
the conv taps and the conv bias; norm gains 1 + N(0, 0.02), so that each
takes part; ``A_log`` = log U(1, 16), ``dt_bias`` the inverse softplus of dt
log-uniform in [1e-3, 1e-1] and ``D`` = 1 (the public init); the router's
columns centred within each chip's group of held experts (columns 0..8,
9..17, ...: a direction common to all tokens, which random mixers do give the
residual stream, then favours no chip's group to first order: without it the
load on this chip's nine moved by 2% from seed to seed, and the step's time
with it) and scaled to one common norm, so that no expert starts favoured.
"""
import functools
import math

import numpy as np

STD = 0.02
SIGNS = "signs"  # in place of a dtype: one fixed +-1 per entry, as int8
PROJECTION_SEED = 20261004

KEYS = ("num_hidden_layers", "hidden_size", "vocab_size", "head_dim",
        "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
        "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
        "num_local_experts", "router_experts", "intermediate_size",
        "shared_intermediate_size")


def is_attention(sizes, layer):
    return sizes["layer_types"][layer] == "attention"


def mamba_widths(sizes):
    """(inner = heads x P, the B or C group width, the conv's channels)."""
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    bc = sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return inner, bc, inner + 2 * bc


def leaf_table(sizes, kinds):
    """[(name, shape, kind)] in a fixed order; kind is how it is drawn;
    ``kinds[i]``: whether layer i is attention."""
    h, v = sizes["hidden_size"], sizes["vocab_size"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    inner, _, channels = mamba_widths(sizes)
    held, wide = sizes["num_local_experts"], sizes["router_experts"]
    de, ds = sizes["intermediate_size"], sizes["shared_intermediate_size"]
    out = [("embed", (v, h), "normal"), ("norm_f", (h,), "round_one")]
    for i, attention in enumerate(kinds):
        def leaf(name, shape, kind="normal"):
            out.append((f"{name}.{i}", shape, kind))

        leaf("norm1", (h,), "round_one")
        if attention:
            leaf("q_w", (h, heads * d))
            leaf("k_w", (h, kv * d))
            leaf("v_w", (h, kv * d))
            leaf("o_w", (heads * d, h))
        else:
            leaf("xbcz_w", (h, channels + inner))
            leaf("dt_w", (h, sizes["mamba_n_heads"]))
            leaf("conv_w", (channels, sizes["mamba_d_conv"]))
            leaf("conv_b", (channels,))
            leaf("a_log", (sizes["mamba_n_heads"],), "a_log")
            leaf("dt_bias", (sizes["mamba_n_heads"],), "dt_bias")
            leaf("d_skip", (sizes["mamba_n_heads"],), "one")
            leaf("gnorm", (inner,), "round_one")
            leaf("out_w", (inner, h))
        leaf("norm2", (h,), "round_one")
        leaf("router", (h, wide), "router")
        leaf("egu_w", (held, h, 2 * de))
        leaf("ed_w", (held, de, h))
        leaf("sgu_w", (h, 2 * ds))
        leaf("sd_w", (ds, h))
    return out


def key_data(seed):
    """Two uint32 words from any whole-number seed."""
    return np.random.SeedSequence([int(seed), 0]).generate_state(2)


def _draw(sizes_items, kinds, kd, dtype):
    import jax
    import jax.numpy as jnp

    key = jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32),
                                   impl="threefry2x32")
    out = {}
    for i, (name, shape, kind) in enumerate(
            leaf_table(dict(sizes_items), kinds)):
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
            elif kind == "router":
                # centred within each chip's group of held experts, then
                # every expert's column of one norm
                held = dict(sizes_items)["num_local_experts"]
                groups = x.reshape(shape[0], shape[1] // held, held)
                x = (groups - groups.mean(-1, keepdims=True)).reshape(shape)
                x = x * (STD * math.sqrt(shape[0])
                         / jnp.linalg.norm(x, axis=0, keepdims=True))
        out[name] = x.astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax

    return jax.jit(_draw, static_argnums=(0, 1, 3))


def _static(sizes):
    kinds = tuple(is_attention(sizes, i)
                  for i in range(sizes["num_hidden_layers"]))
    return tuple((k, sizes[k]) for k in KEYS), kinds


def make(sizes, seed, dtype):
    """{leaf name: array} for the seed, in ``dtype``."""
    return _jitted()(*_static(sizes), key_data(seed), dtype)


def projection(sizes):
    """One fixed random direction of +-1 per leaf, the same for every seed:
    what a leaf is projected on where its element-wise error is read."""
    return make(sizes, PROJECTION_SEED, SIGNS)
