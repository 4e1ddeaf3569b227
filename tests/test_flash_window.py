"""The sliding window inside the flash kernels (``flash_attention(window=)``)
against a dense band, the tiles its walk runs against a count over
positions, the dense path's band and its counted refusal, and pins of what
the window must leave as it was: the causal and block-mask kernels at the
accepted cells' shapes and the default rotary table, as programs."""
import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops import nn_ops
from paddle_tpu.profiler import trace

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def T(a):
    return paddle.Tensor(jnp.asarray(a), stop_gradient=True)


def band(s, window):
    """[s, s] bool: query i sees key j iff i - window < j <= i."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    return (j <= i) & (j > i - window)


def dense_attention(q, k, v, mask):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


CASES = {  # seq, query heads, KV heads, head_dim, window, blocks, sub-tile
    "gqa8_w1024_s2048": (2048, 8, 1, 128, 1024, {}, 128),
    "w300_not_whole_tiles": (1024, 2, 1, 64, 300,
                             {"block_q": 256, "block_k": 512}, 128),
    "band_narrower_than_a_strip": (512, 2, 2, 64, 100,
                                   {"block_q": 128, "block_k": 256}, 64),
    "q_blocks_wider_than_k": (512, 4, 2, 32, 200,
                              {"block_q": 256, "block_k": 128}, 64),
    "w5_one_block": (256, 4, 2, 32, 5, {}, 128),
}


@pytest.fixture(scope="module")
def results():
    """{case: (the kernels' out, dq, dk, dv; the dense band's)}, computed
    once a case."""
    done = {}

    def get(name):
        if name not in done:
            s, h, h_kv, d, window, blocks, sub = CASES[name]
            was = fa._SUB_TILE
            fa._SUB_TILE = sub
            try:
                rng = np.random.default_rng(s + window)
                q = jnp.asarray(rng.standard_normal((1, s, h, d)), jnp.float32)
                k, v = (jnp.asarray(rng.standard_normal((1, s, h_kv, d)),
                                    jnp.float32) for _ in range(2))
                ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
                mask = band(s, window)

                def kernels(*a):
                    return fa.flash_attention(*a, window=window, **blocks)

                def want(*a):
                    return dense_attention(*a, mask)

                done[name] = tuple(
                    (f(q, k, v),) + jax.grad(lambda *a: (f(*a) * ct).sum(),
                                             (0, 1, 2))(q, k, v)
                    for f in (kernels, want))
            finally:
                fa._SUB_TILE = was
        return done[name]

    return get


@pytest.mark.parametrize("part", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", list(CASES))
def test_window_kernels_match_a_dense_band(results, case, part):
    got, want = results(case)
    i = ("out", "dq", "dk", "dv").index(part)
    np.testing.assert_allclose(got[i], want[i], atol=3e-5)


def test_a_window_of_the_whole_sequence_is_the_causal_walk():
    """W >= s: the band holds the whole causal triangle, and the call is the
    causal kernels' (same result bit for bit, the event says causal)."""
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((2, 1, 256, 1, 64)), jnp.float32)
    causal = fa.flash_attention(q, kv[0], kv[1], causal=True)
    for window in (256, 5000):
        seen = len(trace.events(kind="flash_tiles"))
        got = fa.flash_attention(q, kv[0], kv[1], window=window)
        np.testing.assert_array_equal(got, causal)
        assert trace.events(kind="flash_tiles")[seen].attrs["mask"] == \
            "causal"
    with pytest.raises(ValueError, match="causal band"):
        fa.flash_attention(q, kv[0], kv[1], window=4, causal=False)


@pytest.mark.parametrize("s,bq,bk,sq,sk,window", [
    (8192, 512, 1024, 128, 128, 1024),   # the cell's walk
    (2048, 1024, 1024, 128, 128, 1024),
    (1024, 256, 512, 128, 128, 300),
    (512, 128, 256, 64, 64, 100),
    (512, 256, 128, 64, 64, 200),
    (256, 256, 256, 128, 128, 5),
    (1024, 512, 512, 128, 256, 700),
])
def test_window_tile_counts(s, bq, bk, sq, sk, window):
    """(run, masked, total) against a count over positions: the sub-tiles of
    the score square that hold a pair of the band, and those of them that
    also hold one outside it."""
    got = fa.causal_tile_counts(s, bq, bk, sq, sk, True, window=window)
    tiles = band(s, window).reshape(s // sq, sq, s // sk, sk)
    has_any, has_all = tiles.any((1, 3)), tiles.all((1, 3))
    assert got == (int(has_any.sum()), int((has_any & ~has_all).sum()),
                   (s // sq) * (s // sk))
    if (s, window, sq) == (8192, 1024, 128):
        assert got == (540, 120, 4096)  # 0.132 of the square


def test_window_event_and_grid():
    """A windowed trace leaves one ``flash_tiles`` event that names the mask
    and the window; the grid's key axis spans the band's key blocks only."""
    q = jnp.ones((1, 1024, 2, 32), jnp.float32)
    kv = jnp.ones((1, 1024, 1, 32), jnp.float32)
    seen = len(trace.events(kind="flash_tiles"))
    jax.eval_shape(lambda q, k, v: fa.flash_attention(
        q, k, v, window=200, block_q=128, block_k=256), q, kv, kv)
    event = trace.events(kind="flash_tiles")[seen].attrs
    assert event["mask"] == "window" and event["window"] == 200
    assert (event["run"], event["total"]) == fa.causal_tile_counts(
        1024, 128, 256, 128, 128, True, window=200)[::2]
    # a q block of 128 rows and its band of 200 keys touch 2 key blocks of
    # 256 at most, of the 4 the causal walk steps over; dkv's key block
    # reaches 4 q blocks of 128 of 8
    assert fa._key_steps(8, 4, 128, 256, 200) == 2
    assert fa._query_steps(8, 4, 128, 256, 200) == 4
    assert fa._key_steps(8, 4, 128, 256, None) == 4
    # the cell's: 16 q blocks of 512 over 8 key blocks of 1024
    assert fa._key_steps(16, 8, 512, 1024, 1024) == 2
    assert fa._query_steps(16, 8, 512, 1024, 1024) == 4


def test_a_window_the_kernels_refuse_falls_back_and_is_counted():
    """A sequence the kernels cannot tile: the dense path builds the band,
    gives the band's result, and the fallback is counted by its reason."""
    assert nn_ops.flash_attention_refusal(
        (2, 8192, 32, 128), (2, 8192, 4, 128), (2, 8192, 4, 128),
        window=1024) is None
    assert nn_ops.flash_attention_refusal(
        (1, 1500, 2, 16), (1, 1500, 1, 16), (1, 1500, 1, 16),
        window=300) == "window_not_tiled"
    assert not fa.supports(1024, 64, window=0)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 1500, 2, 16)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((2, 1, 1500, 1, 16)), jnp.float32)
    counters = paddle.profiler.dispatch_counters
    before = counters()["flash_attention_fallbacks"]
    seen = len(trace.events(kind="flash_fallback"))
    out = F.scaled_dot_product_attention(T(q), T(kv[0]), T(kv[1]),
                                         is_causal=True, window=300)
    assert counters()["flash_attention_fallbacks"] == before + 1
    assert trace.events(kind="flash_fallback")[seen].attrs["reason"] == \
        "window_not_tiled"
    np.testing.assert_allclose(
        out._value, dense_attention(q, kv[0], kv[1], band(1500, 300)),
        atol=2e-5)
    with pytest.raises(ValueError, match="causal band"):
        F.scaled_dot_product_attention(T(q), T(kv[0]), T(kv[1]), window=300)


def test_the_functional_window_takes_the_kernels():
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((1, 512, 4, 32)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((2, 1, 512, 2, 32)), jnp.float32)
    before = paddle.profiler.dispatch_counters()["flash_attention_fallbacks"]
    out = F.scaled_dot_product_attention(T(q), T(kv[0]), T(kv[1]),
                                         is_causal=True, window=77)
    assert paddle.profiler.dispatch_counters()[
        "flash_attention_fallbacks"] == before
    np.testing.assert_allclose(
        out._value, dense_attention(q, kv[0], kv[1], band(512, 77)),
        atol=2e-5)


# ---------------------------------------------------------------------------
# what the window leaves as it was
# ---------------------------------------------------------------------------
def program_text(fn, *specs):
    """The jaxpr's text, with every Pallas kernel's name and the jaxprs of
    its index maps (which the text leaves out)."""
    closed = jax.make_jaxpr(fn)(*specs)
    parts = [str(closed)]

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                parts.append(str(eqn.params["name"]))
                parts.extend(str(bm.index_map_jaxpr) for bm in
                             eqn.params["grid_mapping"].block_mappings)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if isinstance(sub, jcore.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jcore.Jaxpr):
                        walk(sub)

    walk(closed.jaxpr)
    return "\n".join(parts)


def sha(text):
    return hashlib.sha256(text.encode()).hexdigest()


# SHA-256 of ``program_text`` of the gradient of each accepted cell's
# attention, bf16, as the kernels stood before the window was added
PARENT = {
    "gpt2m": ((8, 1024, 16, 64), 16, {},
              "8019d027b0c582ccd8b44133c30bc2ad437ef79f8b3098b3a070a4c76ecc819b"),
    "gpt2l": ((4, 1024, 20, 64), 20, {},
              "888ee25d2634297a738f955fbbc1bb5e47673c6ccfdb56489dd1c2f4534aa88d"),
    "qwen3next": ((2, 8192, 16, 256), 2, {},
                  "e1b4380389d3c0736a1ce2610a29f2595a9e3b28ba32c7049a96bf74b4d2f297"),
    "granite4h": ((1, 8192, 4, 128), 1, {"scale": 1.0 / 128},
                  "821dcb4222f91178078b76a7f549df14bf1f419bb904ff1f64b734dc89ca7390"),
    "sdar": ((1, 16384, 32, 128), 4, {"block_mask": (8192, 4)},
             "b315fa8789e3fe07a0d36c92b23a2b73522b6d6c30b37491302adf48fea8416e"),
}


@pytest.mark.parametrize("cell", list(PARENT))
def test_accepted_cells_flash_programs_are_the_parents(cell):
    shape, kv_heads, kw, want = PARENT[cell]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct(shape[:2] + (kv_heads, shape[3]), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, **kw).astype(jnp.float32).sum()

    text = program_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert "window" not in text
    assert sha(text) == want


def test_default_rotary_programs_are_the_parents():
    x = jax.ShapeDtypeStruct((2, 8192, 16, 256), jnp.bfloat16)
    assert sha(program_text(lambda x: nn_ops.rotary_embedding(
        x, rotary_dim=64, theta=1e7), x)) == \
        "fe763780971dfda644a8bf95b0b3d25d5d2638a0c901ac984436f657524eca39"
    x = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16)
    p = jax.ShapeDtypeStruct((16384,), jnp.float32)
    assert sha(program_text(lambda x, p: nn_ops.rotary_embedding(
        x, p, rotary_dim=128, theta=1e6), x, p)) == \
        "9684b0bfb4c19a19391d2b5b06b12f370a0a7bc7bd3e14490ecb60404170c817"
