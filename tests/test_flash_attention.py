"""Pallas flash attention: numeric parity with the dense XLA path.

Reference analogue: the fused_attention_op tests
(test_fused_attention_op.py) which compare fused CUDA attention against a
composed baseline — same strategy here, on CPU in interpret mode.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops import nn_ops
from paddle_tpu.ops.pallas import flash_attention

# the package re-exports the flash_attention FUNCTION under the module's name
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def dense_ref(q, k, v, causal):
    d = q.shape[-1]
    s = 1.0 / np.sqrt(d)
    qf, kf, vf = [jnp.swapaxes(x, 1, 2) for x in (q, k, v)]
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * s
    if causal:
        ql = logits.shape[-2]
        m = jnp.tril(jnp.ones((ql, ql), bool))
        logits = jnp.where(m, logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vf), 1, 2)


@pytest.mark.parametrize(
    "b,s,h,d,causal,blocks",
    [
        (2, 256, 4, 64, True, {}),
        (1, 128, 2, 32, False, {}),
        (2, 384, 3, 64, True, {}),
        # more than one sub-tile each way inside one resident block
        (1, 1024, 2, 64, True, {}),
        (1, 1024, 2, 64, False, {}),
        # unequal blocks: the grid-level skip and the sub-tile skip together
        (1, 512, 1, 64, True, {"block_q": 256, "block_k": 512}),
        (1, 512, 1, 64, True, {"block_q": 512, "block_k": 256}),
        # no sub-tile size divides the block: one masked tile
        (1, 200, 1, 64, True, {}),
        # a 4 x 4 grid: the index maps' clamp on skipped steps
        (1, 512, 1, 64, True, {"block_q": 128, "block_k": 128}),
        # head_dim as wide as the MXU: dq, dk and dv as plain products
        (1, 256, 1, 128, True, {}),
    ],
)
def test_kernel_parity(b, s, h, d, causal, blocks):
    rng = np.random.default_rng(0)
    q, k, v = [
        jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32) for _ in range(3)
    ]
    out = flash_attention(q, k, v, causal=causal, **blocks)
    ref = dense_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    gf = jax.grad(
        lambda *a: (flash_attention(*a, causal=causal, **blocks) ** 2).sum(),
        (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (dense_ref(*a, causal) ** 2).sum(), (0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-3)


@pytest.mark.parametrize(
    "seq,bq,bk,sq,sk",
    [
        (1024, 1024, 1024, 256, 256),
        (1024, 1024, 1024, 128, 128),
        (1024, 1024, 1024, 512, 128),
        (1024, 1024, 1024, 128, 512),
        (1024, 1024, 1024, 1024, 1024),
        (4096, 1024, 1024, 256, 256),
        (4096, 512, 1024, 256, 128),
        (512, 256, 512, 128, 256),
        (512, 512, 256, 256, 128),
        (512, 128, 128, 128, 128),
        (384, 384, 384, 128, 128),
        (200, 200, 200, 200, 200),
        (600, 600, 600, 600, 600),
    ],
)
def test_causal_tile_counts(seq, bq, bk, sq, sk):
    """(run, masked, total) against a count over positions, for the walk the
    forward and dq kernels make and for the transposed one of dkv."""
    allowed = np.tril(np.ones((seq, seq), bool))
    tiles = allowed.reshape(seq // sq, sq, seq // sk, sk)
    has_any = tiles.any(axis=(1, 3))
    has_all = tiles.all(axis=(1, 3))
    want = (int(has_any.sum()), int((has_any & ~has_all).sum()), has_any.size)
    assert fa.causal_tile_counts(seq, bq, bk, sq, sk, True) == want
    run, masked, total = fa.causal_tile_counts(seq, bq, bk, sq, sk, False)
    assert (run, masked) == (total, 0) and total == want[2]

    # dkv walks q sub-tiles for each key sub-tile: the same tiles, counted
    # the other way round
    n_q, n_sq = seq // bq, bq // sq
    t_run = t_masked = 0
    for kk in range(seq // bk):
        j_first, _ = fa._query_walk(kk * bk, bk, 0, bq, n_q)
        for j in range(j_first, n_q):
            for c in range(bk // sk):
                r_first, r_full = fa._query_walk(
                    kk * bk + c * sk, sk, j * bq, sq, n_sq)
                t_run += n_sq - r_first
                t_masked += r_full - r_first
    assert (t_run, t_masked) == want[:2]


def test_functional_selects_flash_and_falls_back():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 256, 4, 64)).astype(np.float32)
    q = paddle.to_tensor(x)
    # eligible: flash path
    paddle.set_flags({"FLAGS_use_flash_attention": True})
    out_flash = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    paddle.set_flags({"FLAGS_use_flash_attention": False})
    out_dense = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    paddle.set_flags({"FLAGS_use_flash_attention": True})
    np.testing.assert_allclose(out_flash.numpy(), out_dense.numpy(), atol=2e-5)

    # mask given -> dense path even with the flag on (no error)
    mask = paddle.to_tensor(np.zeros((2, 4, 256, 256), np.float32))
    out_masked = F.scaled_dot_product_attention(q, q, q, attn_mask=mask, is_causal=True)
    np.testing.assert_allclose(out_masked.numpy(), out_dense.numpy(), atol=2e-5)

    # seq 600 <= 2048: a single full-row block covers it — eligible AND
    # numerically correct through the flash path
    assert nn_ops.flash_attention_eligible((1, 600, 2, 24), (1, 600, 2, 24), (1, 600, 2, 24))
    x2 = rng.standard_normal((1, 600, 2, 24)).astype(np.float32)
    q2 = paddle.to_tensor(x2)
    out2 = F.scaled_dot_product_attention(q2, q2, q2, is_causal=True)
    ref2 = dense_ref(jnp.asarray(x2), jnp.asarray(x2), jnp.asarray(x2), True)
    np.testing.assert_allclose(out2.numpy(), np.asarray(ref2), atol=2e-5)

    # ineligible: seq 3000 > 2048 and not divisible by the 512/1024 blocks
    assert not nn_ops.flash_attention_eligible((1, 3000, 2, 24), (1, 3000, 2, 24), (1, 3000, 2, 24))
    bad = jnp.asarray(rng.standard_normal((1, 3000, 2, 24)), jnp.float32)
    with pytest.raises(ValueError):
        flash_attention(bad, bad, bad, causal=True)


def test_tape_backward_through_flash():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((1, 128, 2, 32)).astype(np.float32)
    q = paddle.to_tensor(x, stop_gradient=False)
    k = paddle.to_tensor(x, stop_gradient=False)
    v = paddle.to_tensor(x, stop_gradient=False)
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    (out ** 2).sum().backward()
    gr = jax.grad(lambda a, b, c: (dense_ref(a, b, c, True) ** 2).sum(), (0, 1, 2))(
        jnp.asarray(x), jnp.asarray(x), jnp.asarray(x)
    )
    np.testing.assert_allclose(q.grad.numpy(), np.asarray(gr[0]), atol=2e-3)
    np.testing.assert_allclose(v.grad.numpy(), np.asarray(gr[2]), atol=2e-3)


def test_bf16_roundtrip():
    rng = np.random.default_rng(3)
    q, k, v = [
        jnp.asarray(rng.standard_normal((2, 128, 2, 64)), jnp.bfloat16)
        for _ in range(3)
    ]
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_ref(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2
    )
