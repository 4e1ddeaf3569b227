"""The block-diffusion sparse decoder (models/sdar_moe.py) and what it
brought — the two-stream block mask inside the flash kernels, rotary
positions that are given, the dropless expert layer without a shared expert
— against the plain reference kept with the benchmark
(benchmark/lib/reference_sdar_moe.py: a dense boolean mask, experts as
masks), at small sizes on the CPU in float32."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from benchmark.lib import program_sdar_moe as prog
from benchmark.lib import reference_sdar_moe as ref
from benchmark.lib import traffic_block_diffusion as traffic
from benchmark.lib import weights_sdar_moe as weights
from paddle_tpu.incubate import moe
from paddle_tpu.models import BlockDiffusionCriterion
from paddle_tpu.ops import nn_ops
from paddle_tpu.profiler import trace

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

SIZES = dict(
    num_hidden_layers=2, hidden_size=64, vocab_size=512,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    rope_theta=1e6, num_experts=4, router_experts=16, held_first=4,
    num_experts_per_tok=4, moe_intermediate_size=32, norm_topk_prob=True,
    rms_norm_eps=1e-6, recompute_mixer=False)
SEED = 2**31 + 13
BLOCK = 4
MIX = dict(ring=2, batch=2, seq=128, block_length=BLOCK, rate_low=0.05)
LEAVES = sorted({name.split(".")[0] for name, _, _ in
                 weights.leaf_table(SIZES)})


def T(a):
    return paddle.Tensor(jnp.asarray(a), stop_gradient=True)


def fed(batch):
    """The model's two inputs and the criterion's one, from a host batch."""
    targets = np.stack([batch.ids.astype(np.float32),
                        traffic.weights(batch, BLOCK)], axis=-1)
    return T(batch.ids), T(batch.masked.astype(np.int32)), T(targets)


@pytest.fixture(scope="module")
def both():
    """(model, seeded weights, a batch, the program's logits / loss /
    gradients by leaf, the reference's)."""
    _, model = prog.build_model(SIZES, BLOCK)
    prog.seed_weights(model, SIZES, SEED, "float32")
    w = weights.make(SIZES, SEED, "float32")
    batch = traffic.train_batches(MIX, SEED, SIZES["vocab_size"] - 1)[0]
    ids, masked, targets = fed(batch)
    out = model(ids, masked)
    loss = BlockDiffusionCriterion()(out, targets)
    loss.backward()
    got = {prog.flat_name(n): p.grad._value
           for n, p in model.named_parameters()}
    ref_loss, grads = ref.loss_and_grads(w, batch, SIZES, BLOCK)
    return dict(model=model, w=w, batch=batch, logits=out._value,
                loss=float(loss), grads=got, ref_loss=float(ref_loss),
                ref_grads=grads)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
def test_logits_and_loss_agree(both):
    want = ref.logits(both["w"], both["batch"], SIZES, BLOCK)
    assert both["logits"].shape == (2, 128, 512)  # the noised half only
    assert float(jnp.abs(both["logits"] - want).max()) < 2e-5
    assert both["loss"] == pytest.approx(both["ref_loss"], rel=1e-5)
    assert set(both["grads"]) == set(both["ref_grads"])


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_agrees(both, leaf):
    names = [k for k in both["ref_grads"] if k.split(".")[0] == leaf]
    assert names
    for name in names:
        g = both["ref_grads"][name]
        scale = float(jnp.abs(g).max())
        assert scale > 0, f"{name}: the reference gives it no gradient"
        assert float(jnp.abs(both["grads"][name] - g).max()) \
            < 2e-3 * scale + 1e-8, name


def test_the_loss_is_the_weighted_cross_entropy_of_masked_positions(both):
    """By hand from the program's logits: sum over masked positions of
    CE / t over batch x L; a clean position and an unmasked one add nothing."""
    batch, lg = both["batch"], np.asarray(both["logits"], np.float64)
    lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) \
        + lg.max(-1)
    ce = lse - np.take_along_axis(lg, batch.ids[..., None], -1)[..., 0]
    t = np.repeat(batch.rates, BLOCK, -1).astype(np.float64)
    want = (ce / t)[batch.masked].sum() / batch.ids.size
    assert both["loss"] == pytest.approx(want, rel=1e-5)
    assert (batch.rates >= 0.05).all() and (batch.rates <= 1).all()
    assert (batch.ids < SIZES["vocab_size"] - 1).all()


def test_recomputed_mixer_gives_the_same_step(both):
    """``use_recompute`` drops the attention's activations only: same
    losses, the counters still written once by the forward."""
    ring = traffic.train_batches(MIX, SEED, SIZES["vocab_size"] - 1)
    losses = []
    for recompute in (False, True):
        _, model = prog.build_model(dict(SIZES, recompute_mixer=recompute),
                                    BLOCK)
        prog.seed_weights(model, SIZES, SEED, "float32")
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        step = paddle.jit.compile_train_step(
            model, BlockDiffusionCriterion(), opt)
        losses.append([float(step(*fed(b))) for b in ring])
        assert all(r > 0 for _, r, _ in model.routed_load())
        assert int(model.loss_positions._value) == int(ring[-1].masked.sum())
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)


def test_counters_ride_the_compiled_step_and_events_are_left(both):
    """After a compiled step ``loss_positions`` holds the batch's count of
    masked positions and each expert layer's buffers its load; the trace of
    the step leaves one ``flash_tiles`` event a traced attention that names
    the mask, one ``moe_route`` event, and no fallback."""
    _, model = prog.build_model(SIZES, BLOCK)
    prog.seed_weights(model, SIZES, SEED, "float32")
    mix = dict(MIX, batch=1, seq=192)  # a shape no other test traces
    ring = traffic.train_batches(mix, 5, SIZES["vocab_size"] - 1)
    opt = paddle.optimizer.AdamW(learning_rate=0.0,
                                 parameters=model.parameters())
    step = paddle.jit.compile_train_step(model, BlockDiffusionCriterion(),
                                         opt)
    counters = paddle.profiler.dispatch_counters
    fallbacks = counters()["flash_attention_fallbacks"]
    seen = {k: len(trace.events(kind=k)) for k in ("flash_tiles", "moe_route")}
    for batch in ring:
        step(*fed(batch))
        assert int(model.loss_positions._value) == int(batch.masked.sum())
    tiles = trace.events(kind="flash_tiles")[seen["flash_tiles"]:]
    assert len(tiles) == 1  # two layers of one shape: one trace, one step
    assert tiles[0].attrs == dict(
        seq=384, block_q=192, block_k=192,
        sub_q=192, sub_k=192, run=3, masked=3, total=4,
        mask="block_diffusion", half=192, block=BLOCK)
    routes = trace.events(kind="moe_route")[seen["moe_route"]:]
    assert len(routes) == 1 and routes[0].attrs["tokens"] == 384
    assert counters()["flash_attention_fallbacks"] == fallbacks
    slots = 384 * SIZES["num_experts_per_tok"]
    for _, routed, ran in model.routed_load():
        assert 0 < routed < slots and ran >= routed


def test_a_block_mask_the_kernels_refuse_falls_back_and_is_counted():
    """Block length 3 is no power of two: the dense path builds the mask as
    an array, gives the same result as the reference's mask, and the
    fallback is counted by its reason."""
    assert nn_ops.flash_attention_refusal(
        (1, 16384, 32, 128), (1, 16384, 4, 128), (1, 16384, 4, 128),
        (8192, 4)) is None
    assert nn_ops.flash_attention_refusal(
        (1, 16384, 32, 128), (1, 16384, 4, 128), (1, 16384, 4, 128),
        (8192, 3)) == "block_mask_not_tiled"
    assert nn_ops.flash_attention_refusal(
        (1, 16000, 32, 128), (1, 16000, 4, 128), (1, 16000, 4, 128),
        (8192, 4)) == "block_mask_not_tiled"
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 96, 2, 16)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((2, 1, 96, 1, 16)), jnp.float32)
    counters = paddle.profiler.dispatch_counters
    before = counters()["flash_attention_fallbacks"]
    seen = len(trace.events(kind="flash_fallback"))
    out = F.scaled_dot_product_attention(T(q), T(kv[0]), T(kv[1]),
                                         block_mask=(48, 3))
    assert counters()["flash_attention_fallbacks"] == before + 1
    assert trace.events(kind="flash_fallback")[seen].attrs["reason"] == \
        "block_mask_not_tiled"
    want = dense_attention(q, kv[0], kv[1], allowed(48, 3))
    np.testing.assert_allclose(out._value, want, atol=2e-5)
    np.testing.assert_array_equal(nn_ops.block_diffusion_mask(48, 3),
                                  allowed(48, 3))


# ---------------------------------------------------------------------------
# the block mask inside the flash kernels
# ---------------------------------------------------------------------------
def allowed(half, block):
    """The reference's three lines, over the whole stream."""
    pos = jnp.arange(2 * half)
    return np.asarray(ref.allowed(pos, pos, half, block))


def dense_attention(q, k, v, mask):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


CASES = [  # half, block, query heads, KV heads, head_dim, blocks, sub-tile
    (256, 4, 2, 1, 64, {}, 128),                # one key step, group 2
    (256, 1, 2, 2, 64, {}, 128),                # block 1: causal by halves
    (512, 4, 4, 2, 128, {"block_q": 128, "block_k": 256}, 128),  # MXU-wide
    (512, 16, 8, 1, 32, {"block_q": 256, "block_k": 256}, 64),   # group 8
    (256, 128, 2, 1, 64, {"block_q": 128, "block_k": 256}, 128),  # block = tile
    (512, 8, 2, 2, 64, {"block_q": 128, "block_k": 128}, 128),   # 4 x 4 grid
    (64, 4, 2, 1, 64, {}, 128),                 # no sub-tile divides it
]


@pytest.mark.parametrize("part", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("half,block,h,h_kv,d,blocks,sub", CASES)
def test_block_mask_kernels_match_a_dense_mask(monkeypatch, half, block, h,
                                               h_kv, d, blocks, sub, part):
    monkeypatch.setattr(fa, "_SUB_TILE", sub)
    rng = np.random.default_rng(half + block)
    q = jnp.asarray(rng.standard_normal((1, 2 * half, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 2 * half, h_kv, d)),
                        jnp.float32) for _ in range(2))
    mask = allowed(half, block)
    if part == "out":
        got = fa.flash_attention(q, k, v, block_mask=(half, block), **blocks)
        want = dense_attention(q, k, v, mask)
        # no clean row attends a noised key: the clean half is untouched by
        # what the noised half holds
        other = fa.flash_attention(q, k.at[:, half:].add(1.0),
                                   v.at[:, half:].add(1.0),
                                   block_mask=(half, block), **blocks)
        np.testing.assert_array_equal(got[:, :half], other[:, :half])
        if block == 1:  # each half's clean part is plain causal attention
            causal = fa.flash_attention(q[:, :half], k[:, :half],
                                        v[:, :half], causal=True)
            np.testing.assert_allclose(got[:, :half], causal, atol=1e-6)
    else:
        ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
        arg = ("dq", "dk", "dv").index(part)
        got = jax.grad(lambda *a: (fa.flash_attention(
            *a, block_mask=(half, block), **blocks) * ct).sum(), arg)(q, k, v)
        want = jax.grad(lambda *a: (dense_attention(*a, mask) * ct).sum(),
                        arg)(q, k, v)
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.mark.parametrize("half,block,bq,bk,sq,sk", [
    (8192, 4, 512, 1024, 128, 128),   # the cell's walk
    (1024, 4, 512, 1024, 128, 128),
    (1024, 1, 1024, 1024, 128, 128),
    (1024, 16, 256, 512, 128, 128),
    (512, 8, 128, 128, 128, 128),
    (512, 64, 256, 256, 64, 64),
    (512, 4, 256, 512, 128, 256),
    (192, 4, 192, 192, 192, 192),
])
def test_block_mask_tile_counts(half, block, bq, bk, sq, sk):
    """(run, masked, total) against a count over positions: the sub-tiles of
    the stream's whole score square that hold an allowed pair, and those of
    them that also hold a forbidden one."""
    got = fa.causal_tile_counts(2 * half, bq, bk, sq, sk, True, (half, block))
    n_q, n_k = 2 * half // sq, 2 * half // sk
    run = masked = 0
    pos = jnp.arange(2 * half)
    for i in range(n_q):  # a strip of the square at a time: 16k x 16k is big
        strip = np.asarray(ref.allowed(pos[i * sq:(i + 1) * sq], pos, half,
                                       block)).reshape(sq, n_k, sk)
        has_any, has_all = strip.any((0, 2)), strip.all((0, 2))
        run += int(has_any.sum())
        masked += int((has_any & ~has_all).sum())
    if block >= sq:  # a noised row's own block fills its sub-tile: the
        masked += half // sq  # kernels build that mask all the same
    assert got == (run, masked, n_q * n_k)
    if (half, sq, sk) == (8192, 128, 128):
        assert got == (4224, 3 * 64, 16384)  # 0.2578 of the square


def test_mask_blocks_and_the_refusals():
    assert fa._mask_blocks(16384, (8192, 4)) == (512, 1024, 128, 128)
    assert fa._mask_blocks(16384, (8000, 4)) == "stream_is_not_two_halves"
    assert fa._mask_blocks(2 * 1536, (1536, 4)) == "half_not_tiled"
    assert fa._mask_blocks(16384, (8192, 3)) == "block_length"
    assert fa._mask_blocks(16384, (8192, 256)) == "block_length"
    assert fa.supports_block_mask(16384, 128, (8192, 4))
    assert not fa.supports_block_mask(16384, 100, (8192, 4))
    q = jnp.ones((1, 128, 1, 16), jnp.float32)
    with pytest.raises(ValueError, match="block_length"):
        fa.flash_attention(q, q, q, block_mask=(64, 3))


# ---------------------------------------------------------------------------
# rotary positions, the expert layer without a shared expert
# ---------------------------------------------------------------------------
def test_rotary_with_given_positions():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((2, 16, 3, 32)), jnp.float32)
    # 0 .. s - 1 given is what is taken when none is
    same = F.rotary_embedding(T(x), theta=1e6, positions=T(np.arange(16)))
    np.testing.assert_array_equal(
        same._value, F.rotary_embedding(T(x), theta=1e6)._value)
    # a stream of two halves: each half turned as a sequence of its own
    twice = np.tile(np.arange(8), 2)
    got = F.rotary_embedding(T(x), theta=1e6, positions=T(twice))._value
    np.testing.assert_allclose(got, ref.rotary(x, jnp.asarray(twice), 1e6),
                               atol=1e-6)
    half = F.rotary_embedding(T(x[:, 8:]), theta=1e6)._value
    np.testing.assert_allclose(got[:, 8:], half, atol=1e-6)
    # partly rotated heads keep their tail
    part = F.rotary_embedding(T(x), rotary_dim=8, theta=1e6,
                              positions=T(twice))._value
    np.testing.assert_array_equal(part[..., 8:], x[..., 8:])


def expert_weights(seed=3, h=32, d=16, wide=16, held=16):
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=0.3):
        return jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)

    return {"router": draw(h, wide, scale=1.0), "egu_w": draw(held, h, 2 * d),
            "ed_w": draw(held, d, h)}


def test_all_eight_shares_add_up_to_the_uncut_layer():
    """The parts that each of 8 chips computes for its own 2 of 16 experts
    are what the reference gives for the whole layer: there is no shared
    expert to count once."""
    wide, held, top_k, tokens, h = 16, 2, 4, 96, 32
    x = jnp.asarray(np.random.default_rng(5).standard_normal((tokens, h)),
                    jnp.float32)
    whole = expert_weights()
    sizes = dict(num_experts_per_tok=top_k, num_experts=wide)
    uncut = ref.experts(x, whole, sizes, held=(0, wide), shared=False)
    total, routed = 0.0, 0
    for chip in range(wide // held):
        first = chip * held
        y, n, _ = moe.dropless_experts(
            x, whole["router"], whole["egu_w"][first:first + held],
            whole["ed_w"][first:first + held], None, None, None, first=first,
            top_k=top_k, renormalize=True, rows=64)
        total, routed = total + y, routed + int(n)
    assert routed == tokens * top_k  # every slot is some chip's
    np.testing.assert_allclose(total, uncut, atol=2e-5)


def test_no_shared_expert_builds_no_leaf():
    layer = moe.DroplessExperts(16, 8, 4, 2, d_shared=0)
    assert [n for n, _ in layer.named_parameters()] == [
        "router", "w_gate_up", "w_down"]
    x = T(np.random.default_rng(0).standard_normal((2, 6, 16)).astype(
        np.float32))
    y = layer(x)
    routed, _, _ = moe.dropless_experts(
        x._value.reshape(-1, 16), layer.router._value,
        layer.w_gate_up._value, layer.w_down._value, None, None, None,
        first=0, top_k=2, renormalize=True, rows=layer.expert_rows._value)
    np.testing.assert_allclose(y._value.reshape(-1, 16), routed, atol=1e-6)
    # the default still builds one, of the experts' width
    assert moe.DroplessExperts(16, 8, 4, 2).shared_gate_up.shape == [16, 16]


def test_models_export_and_the_parameter_names():
    from paddle_tpu import models

    cfg = models.SDARMoEConfig()
    assert (cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.mask_id) == (48, 32, 4, 128, 128, 8, 768, 151935)
    _, model = prog.build_model(SIZES, BLOCK)
    assert isinstance(model, models.SDARMoEForBlockDiffusion)
    assert isinstance(model.model, models.SDARMoEModel)
    names = [n for n, _ in model.named_parameters()]
    assert not [n for n in names if "shared" in n]
    assert {prog.flat_name(n) for n in names} == {
        name for name, _, _ in weights.leaf_table(SIZES)}
