"""Seeded weights of the sliding-window sparse decoder (``configs/mellum2-*``),
made on the device in one jitted call, in the type the configuration states.
The program and the reference each call this with the same seed: neither is
handed what the other made.

Layout: a flat dict, per-layer leaves named ``<leaf>.<layer>``; the held
experts of a layer are ONE leaf each, stacked ``[held, ...]``, as the program
holds them. ``num_experts`` and ``vocab_size`` in ``sizes`` are what this
chip HOLDS; the router keeps its published ``router_experts`` columns.
``egu_w`` is gate | up: a relabelling of the public implementation's two
matrices under random weights.

Init (``assumed`` in the configuration file): ``lib/weights_sdar_moe.py``'s
recipe without its mask-token part. N(0, 0.02) for every matrix but the
embedding, whose rows are N(0, 4^2) (a residual stream that stays a token's
own embedding plus small branches, so that a position is routed by its token:
PERF.md section 6); norm gains 1 + N(0, 0.02), so that each takes
part; the router's columns centred within each chip's group of held experts
(columns 0..7, 8..15, ...) and scaled to one common norm, so that no expert
and no chip's group starts favoured.
"""
import functools
import math

from .weights_sdar_moe import EMBED_STD, PROJECTION_SEED, SIGNS, STD, key_data

KEYS = ("num_hidden_layers", "hidden_size", "vocab_size", "head_dim",
        "num_attention_heads", "num_key_value_heads", "num_experts",
        "router_experts", "moe_intermediate_size")


def leaf_table(sizes):
    """[(name, shape, kind)] in a fixed order; kind is how it is drawn."""
    h, v, d = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    held, wide = sizes["num_experts"], sizes["router_experts"]
    de = sizes["moe_intermediate_size"]
    out = [("embed", (v, h), "embed"), ("head_w", (h, v), "normal"),
           ("norm_f", (h,), "round_one")]
    for i in range(sizes["num_hidden_layers"]):
        out += [(f"{name}.{i}", shape, kind) for name, shape, kind in (
            ("norm1", (h,), "round_one"),
            ("q_w", (h, heads * d), "normal"),
            ("k_w", (h, kv * d), "normal"),
            ("v_w", (h, kv * d), "normal"),
            ("o_w", (heads * d, h), "normal"),
            ("norm2", (h,), "round_one"),
            ("router", (h, wide), "router"),
            ("egu_w", (held, h, 2 * de), "normal"),
            ("ed_w", (held, de, h), "normal"))]
    return out


def _draw(sizes_items, kd, dtype):
    import jax
    import jax.numpy as jnp

    sizes = dict(sizes_items)
    key = jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32),
                                   impl="threefry2x32")
    held = sizes["num_experts"]
    out = {}
    for i, (name, shape, kind) in enumerate(leaf_table(sizes)):
        k = jax.random.fold_in(key, i)
        if dtype == SIGNS:
            out[name] = jax.random.rademacher(k, shape, jnp.int8)
            continue
        x = STD * jax.random.normal(k, shape, jnp.float32)
        if kind == "round_one":
            x = 1.0 + x
        elif kind == "embed":
            x = x * (EMBED_STD / STD)
        elif kind == "router":
            # centred within each chip's group of held experts, then every
            # expert's column of one norm
            groups = x.reshape(shape[0], shape[1] // held, held)
            x = (groups - groups.mean(-1, keepdims=True)).reshape(shape)
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
