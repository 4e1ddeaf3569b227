"""Seeded weights of the sparse hybrid decoder (``configs/qwen3-next-*``),
made on the device in one jitted call, in the type the configuration states.
The program and the reference each call this with the same seed: neither is
handed what the other made.

Layout: a flat dict, per-layer leaves named ``<leaf>.<layer>`` (the layers
are of two kinds, so nothing is stacked over them); the held experts of a
layer are ONE leaf each, stacked ``[held, ...]``, as the program holds them.
Column orders are this repo's (``qkvz_w`` as q | k | v | z; ``q_w`` as [head,
(q, gate)]; ``ba_w`` as b | a; ``*gu_w`` as gate | up): relabellings of the
public implementation's under random weights.

Init (``assumed`` in the configuration file): N(0, 0.02) for every matrix
and the conv taps; zero-centred norm gains N(0, 0.02) and the gated norm's
gain 1 + N(0, 0.02), so that each takes part; ``A_log`` = log U(0, 16) and
``dt_bias`` = 1 (the public init); the router's columns scaled to one common
norm, so that no expert starts favoured.
"""
import functools
import math

import numpy as np

STD = 0.02
SIGNS = "signs"  # in place of a dtype: one fixed +-1 per entry, as int8
PROJECTION_SEED = 20261002

KEYS = ("num_hidden_layers", "full_attention_interval", "hidden_size",
        "vocab_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "num_experts", "router_experts",
        "moe_intermediate_size", "shared_expert_intermediate_size")


def is_full_attention(sizes, layer):
    return (layer + 1) % sizes["full_attention_interval"] == 0


def leaf_table(sizes):
    """[(name, shape, kind)] in a fixed order; kind is how it is drawn."""
    h, v = sizes["hidden_size"], sizes["vocab_size"]
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    n_qk, n_v = hk * dk, hv * dv
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    held, wide = sizes["num_experts"], sizes["router_experts"]
    de, ds = (sizes["moe_intermediate_size"],
              sizes["shared_expert_intermediate_size"])
    out = [("embed", (v, h), "normal"), ("head_w", (h, v), "normal"),
           ("norm_f", (h,), "normal")]
    for i in range(sizes["num_hidden_layers"]):
        def leaf(name, shape, kind="normal"):
            out.append((f"{name}.{i}", shape, kind))

        leaf("norm1", (h,))
        if is_full_attention(sizes, i):
            leaf("q_w", (h, heads * d * 2))
            leaf("k_w", (h, kv * d))
            leaf("v_w", (h, kv * d))
            leaf("o_w", (heads * d, h))
            leaf("qnorm", (d,))
            leaf("knorm", (d,))
        else:
            leaf("qkvz_w", (h, 2 * n_qk + 2 * n_v))
            leaf("ba_w", (h, 2 * hv))
            leaf("conv_w", (2 * n_qk + n_v, sizes["linear_conv_kernel_dim"]))
            leaf("a_log", (hv,), "a_log")
            leaf("dt_bias", (hv,), "one")
            leaf("gnorm", (dv,), "round_one")
            leaf("out_w", (n_v, h))
        leaf("norm2", (h,))
        leaf("router", (h, wide), "router")
        leaf("egu_w", (held, h, 2 * de))
        leaf("ed_w", (held, de, h))
        leaf("sgu_w", (h, 2 * ds))
        leaf("sd_w", (ds, h))
        leaf("sg_w", (h, 1))
    return out


def key_data(seed):
    """Two uint32 words from any whole-number seed."""
    return np.random.SeedSequence([int(seed), 0]).generate_state(2)


def _draw(sizes_items, kd, dtype):
    import jax
    import jax.numpy as jnp

    key = jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32),
                                   impl="threefry2x32")
    out = {}
    for i, (name, shape, kind) in enumerate(leaf_table(dict(sizes_items))):
        k = jax.random.fold_in(key, i)
        if dtype == SIGNS:
            out[name] = jax.random.rademacher(k, shape, jnp.int8)
            continue
        if kind == "a_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1e-4, 16.0))
        elif kind == "one":
            x = jnp.ones(shape, jnp.float32)
        else:
            x = STD * jax.random.normal(k, shape, jnp.float32)
            if kind == "round_one":
                x = 1.0 + x
            elif kind == "router":  # every expert's column of one norm
                x = x * (STD * math.sqrt(shape[0])
                         / jnp.linalg.norm(x, axis=0, keepdims=True))
        out[name] = x.astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax

    return jax.jit(_draw, static_argnums=(0, 2))


def _static(sizes):
    return tuple((k, sizes[k]) for k in KEYS)


def make(sizes, seed, dtype):
    """{leaf name: array} for the seed, in ``dtype``."""
    return _jitted()(_static(sizes), key_data(seed), dtype)


def projection(sizes):
    """One fixed random direction of +-1 per leaf, the same for every seed:
    what a leaf is projected on where its element-wise error is read."""
    return make(sizes, PROJECTION_SEED, SIGNS)
