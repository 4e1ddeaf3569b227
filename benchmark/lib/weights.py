"""Seeded GPT-2 weights, made on the device in one jitted call, in the type
the configuration states. The program and the reference each call this with
the same seed: neither is handed what the other made.

Layout (the reference's): per-layer leaves stacked on a leading ``n_layer``
axis. ``qkv_w`` columns are heads-major ([head, (q, k, v), head_dim]), the
layout ``models/gpt.py`` documents; with random weights it is a relabelling.
Init is GPT-2's: N(0, 0.02), output projections scaled by 1/sqrt(2 n_layer);
biases and LayerNorm parameters are drawn too (N(0, 0.02), gains around 1)
so that a dropped bias or gain shows in the comparison.
"""
import functools
import math

import numpy as np

STD = 0.02


def leaf_table(sizes):
    """[(name, shape, mean, std)] in a fixed order."""
    h, layers = sizes["n_embd"], sizes["n_layer"]
    v, s = sizes["padded_vocab"], sizes["n_positions"]
    out_std = STD / math.sqrt(2.0 * layers)
    return [
        ("wte", (v, h), 0.0, STD),
        ("wpe", (s, h), 0.0, STD),
        ("ln1_g", (layers, h), 1.0, STD),
        ("ln1_b", (layers, h), 0.0, STD),
        ("qkv_w", (layers, h, 3 * h), 0.0, STD),
        ("qkv_b", (layers, 3 * h), 0.0, STD),
        ("proj_w", (layers, h, h), 0.0, out_std),
        ("proj_b", (layers, h), 0.0, STD),
        ("ln2_g", (layers, h), 1.0, STD),
        ("ln2_b", (layers, h), 0.0, STD),
        ("fc1_w", (layers, h, 4 * h), 0.0, STD),
        ("fc1_b", (layers, 4 * h), 0.0, STD),
        ("fc2_w", (layers, 4 * h, h), 0.0, out_std),
        ("fc2_b", (layers, h), 0.0, STD),
        ("lnf_g", (h,), 1.0, STD),
        ("lnf_b", (h,), 0.0, STD),
    ]


def key_data(seed):
    """Two uint32 words from any whole-number seed."""
    return np.random.SeedSequence([int(seed), 0]).generate_state(2)


SIGNS = "signs"  # in place of a dtype: one fixed +-1 per entry, as int8


def _draw(sizes_items, kd, dtype):
    import jax
    import jax.numpy as jnp

    key = jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32),
                                   impl="threefry2x32")
    out = {}
    for i, (name, shape, mean, std) in enumerate(
            leaf_table(dict(sizes_items))):
        k = jax.random.fold_in(key, i)
        if dtype == SIGNS:
            out[name] = jax.random.rademacher(k, shape, jnp.int8)
        else:
            x = jax.random.normal(k, shape, jnp.float32)
            out[name] = (mean + std * x).astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def _jitted(unstack):
    import jax

    def stacked(sizes_items, kd, dtype):
        return _draw(sizes_items, kd, dtype)

    def per_layer(sizes_items, kd, dtype):
        w = _draw(sizes_items, kd, dtype)
        layers = dict(sizes_items)["n_layer"]
        return {k: ([v[i] for i in range(layers)] if v.ndim > 1
                    and k not in ("wte", "wpe") else v)
                for k, v in w.items()}

    return jax.jit(per_layer if unstack else stacked, static_argnums=(0, 2))


def _static(sizes):
    return tuple(sorted((k, sizes[k]) for k in (
        "n_layer", "n_embd", "n_head", "n_positions", "padded_vocab")))


def stacked(sizes, seed, dtype):
    """{name: array} with layers stacked (the reference's layout)."""
    return _jitted(False)(_static(sizes), key_data(seed), dtype)


def per_layer(sizes, seed, dtype):
    """The same values, per-layer leaves as lists of ``n_layer`` arrays."""
    return _jitted(True)(_static(sizes), key_data(seed), dtype)


PROJECTION_SEED = 20260930


def projection(sizes, unstacked=False):
    """One fixed random direction of +-1 per leaf, the same for every seed:
    what a leaf is projected on where its element-wise error is read."""
    make = per_layer if unstacked else stacked
    return make(sizes, PROJECTION_SEED, SIGNS)
