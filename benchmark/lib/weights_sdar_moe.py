"""Seeded weights of the block-diffusion sparse decoder
(``configs/sdar-*``), made on the device in one jitted call, in the type the
configuration states. The program and the reference each call this with the
same seed: neither is handed what the other made.

Layout: a flat dict, per-layer leaves named ``<leaf>.<layer>``; the held
experts of a layer are ONE leaf each, stacked ``[held, ...]``, as the program
holds them. ``num_experts`` and ``vocab_size`` in ``sizes`` are what this
chip HOLDS; the router keeps its published ``router_experts`` columns.
``egu_w`` is gate | up: a relabelling of the public implementation's two
matrices under random weights.

Init (``assumed`` in the configuration file): N(0, 0.02) for every matrix
but the embedding, whose rows are N(0, 4^2); norm gains 1 + N(0, 0.02), so
that each takes part; the router's columns centred within each chip's group
of held experts (columns 0..15, 16..31, ...: a direction common to all
tokens then favours no chip's group to first order, as PR 32 found necessary
for a steady load) and scaled to one common norm, so that no expert starts
favoured; the mask token's routing built in: eight directions drawn from the
seed, one a chip, the mask id's row of the embedding (the last) their sum at
a usual row's norm, and in every layer one column of each chip's group
(drawn from the seed) is its chip's direction, before the centring and the
common norm.

Why the last three (my chip runs and CPU simulations, PR 34, PERF.md section
6). A random all-attention decoder whose residual stream its branches
dominate loses its tokens' differences layer by layer (softmax over
thousands of keys with scores of deviation 1 is a mean, and a mean keeps
what is common): with every matrix at N(0, 0.02) the stream's positions
chose the same experts, a layer's load on the held 16 read 0, 16,384, 32,768
or 49,152 of 16,384 expected, from the first step in deep layers and within
5 to 20 Adam steps in all, on every seed. Gains round 3 on q and k (scores
of deviation 9, a row attends a few keys) kept the load within 3% of even
but made the gradient a hundred times larger and no more reproducible than
its float8 rounding (``grad_sum_gap`` 0.78-1.08 beside the control's
0.92-1.13). With large rows the stream stays the token's own embedding plus
small branches, so a position is routed by its token; and the masked
positions, a quarter of the stream with ONE embedding, would all take the
same eight experts, 4,300 slots a layer on each, on as many of this chip's
experts as the seed happens to give (0 to 3). A trained router spreads its
most frequent token over the chips; the mask token's directions do that:
one of its eight experts is this chip's, in every layer, on every seed. How
large the rows, and why shared directions: the masked positions share one
input, so under Adam from zero moments every linear map's output for the
mask token moves together, by up to lr x |x|_1 = 0.16 an entry a step. With
rows of N(0, 1) the branches' drift swamped the mask token's residual within
the window (the deeper the layer the sooner: a layer's load wandered between
0.4 and 1.7 of even over 45 steps, and ``train_tokens_per_s`` spread 0.58%
over six seeds with it); at N(0, 2^2) and above it does not (a residual
stream several times its branches is also what a trained pre-norm decoder's
middle layers hold). The router's own drift lowers the mask token's logit on
the HELD expert by about 1.3 in 46 steps (what this chip's expert adds is
noise to the loss); the sum of one column a layer and chip (48 columns)
stood 3 to 5 logits over the other columns, the eight shared directions
stand 11 over.
"""
import functools
import math

import numpy as np

STD = 0.02
SIGNS = "signs"  # in place of a dtype: one fixed +-1 per entry, as int8
PROJECTION_SEED = 20261005
EMBED_STD = 4.0

KEYS = ("num_hidden_layers", "hidden_size", "vocab_size", "head_dim",
        "num_attention_heads", "num_key_value_heads", "num_experts",
        "router_experts", "num_experts_per_tok", "moe_intermediate_size")


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
            ("qnorm", (d,), "round_one"),
            ("knorm", (d,), "round_one"),
            ("norm2", (h,), "round_one"),
            ("router", (h, wide), "router"),
            ("egu_w", (held, h, 2 * de), "normal"),
            ("ed_w", (held, de, h), "normal"))]
    return out


def key_data(seed):
    """Two uint32 words from any whole-number seed."""
    return np.random.SeedSequence([int(seed), 0]).generate_state(2)


def _draw(sizes_items, kd, dtype):
    import jax
    import jax.numpy as jnp

    sizes = dict(sizes_items)
    key = jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32),
                                   impl="threefry2x32")
    held = sizes["num_experts"]
    chips = min(sizes["num_experts_per_tok"], sizes["router_experts"] // held)
    # the mask token's directions, one a chip, and the column of each chip's
    # group that takes its direction in each layer
    toward = STD * jax.random.normal(jax.random.fold_in(key, 2**20),
                                     (sizes["hidden_size"], chips),
                                     jnp.float32)
    pick = held * jnp.arange(chips) + jax.random.randint(
        jax.random.fold_in(key, 2**20 + 1),
        (sizes["num_hidden_layers"], chips), 0, held)
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
            mask_row = toward.sum(-1)
            x = x.at[-1].set(mask_row * (EMBED_STD * math.sqrt(shape[1])
                                         / jnp.linalg.norm(mask_row)))
        elif kind == "router":
            x = x.at[:, pick[int(name.rsplit(".", 1)[1])]].set(toward)
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
