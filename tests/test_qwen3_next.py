"""The sparse hybrid decoder (models/qwen3_next.py) and the layers it brought
— the chunked gated delta rule, grouped-query flash attention, the dropless
held-expert layer — against the plain reference kept with the benchmark
(benchmark/lib/reference_qwen3_next.py: token-by-token recurrence, dense
masked softmax, experts as masks), at small sizes on the CPU in float32."""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from benchmark.lib import program_qwen3_next as prog
from benchmark.lib import reference_qwen3_next as ref
from benchmark.lib import weights_qwen3_next as weights
from paddle_tpu.incubate import moe
from paddle_tpu.models import GPTPretrainingCriterion
from paddle_tpu.ops import linear_attention as la
from paddle_tpu.profiler import trace

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

SIZES = dict(
    num_hidden_layers=4, full_attention_interval=4, hidden_size=64,
    vocab_size=512, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    partial_rotary_factor=0.25, rope_theta=1e7, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, num_experts=4,
    router_experts=16, held_first=4, num_experts_per_tok=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    norm_topk_prob=True, rms_norm_eps=1e-6, recompute_mixer=False)
SEED = 2**31 + 11


def batch(rows=2, seq=128, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, SIZES["vocab_size"], (rows, seq + 1)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


@pytest.fixture(scope="module")
def both():
    _, model = prog.build_model(SIZES)
    prog.seed_weights(model, SIZES, SEED, "float32")
    return model, weights.make(SIZES, SEED, "float32")


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
def test_logits_loss_and_every_leafs_gradient_agree(both):
    model, w = both
    x, y = batch()
    out = model(paddle.Tensor(x))
    want = ref.logits(w, x, SIZES)
    assert float(jnp.abs(out._value - want).max()) < 2e-5
    loss = GPTPretrainingCriterion()(out, paddle.Tensor(y))
    ref_loss, grads = ref.loss_and_grads(w, x, y, SIZES)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    loss.backward()
    seen = set()
    for name, p in model.named_parameters():
        leaf = prog.flat_name(name)
        seen.add(leaf)
        g = grads[leaf]
        scale = float(jnp.abs(g).max())
        assert scale > 0, f"{leaf}: the reference gives it no gradient"
        # float32 round-off: the decay's gradient is a sum of 1e-6 that
        # cancels to 1e-9 in places
        assert float(jnp.abs(p.grad._value - g).max()) < 2e-3 * scale + 1e-8, \
            leaf
    assert seen == set(grads)


def test_pinned_routing_and_each_sides_choice(both):
    """What ``benchmark/tools/routing.py`` stands on. In float32 the program's
    routers (walked eagerly) and the reference's choose the same experts; the
    reference pinned to its own choice is the reference; pinned to another
    choice it runs those experts, under its own probabilities of them."""
    model, w = both
    ids = np.asarray(batch()[0])
    x, y = batch()
    own = ref.routed_experts(SIZES, SEED, ids, "float32")
    chosen = prog.routed_experts(model, ids)
    top_k = SIZES["num_experts_per_tok"]
    assert len(own) == len(chosen) == SIZES["num_hidden_layers"]
    for a, b in zip(chosen, own):
        assert a.shape == b.shape == (ids.size, top_k)
        # a near-tie may swap: all but a handful of slots are the same
        assert (np.sort(a, -1) != np.sort(b, -1)).mean() < 2e-3
    loss, grads = ref.loss_and_grads(w, x, y, SIZES)
    pinned_loss, pinned = ref.loss_and_grads(w, x, y, SIZES, pinned=own)
    assert float(pinned_loss) == pytest.approx(float(loss), rel=1e-6)
    for leaf, g in grads.items():
        np.testing.assert_allclose(
            pinned[leaf], g, atol=1e-5 * float(jnp.abs(g).max()), err_msg=leaf)
    # every token sent to experts 0..k-1: another loss
    other = [jnp.broadcast_to(jnp.arange(top_k), a.shape) for a in own]
    other_loss, _ = ref.loss_and_grads(w, x, y, SIZES, pinned=other)
    assert abs(float(other_loss) - float(loss)) > 1e-6
    layer0 = {k[:-2]: a for k, a in w.items() if k.endswith(".0")}
    m = jnp.asarray(np.random.default_rng(3).standard_normal(
        (ids.size, SIZES["hidden_size"])), jnp.float32)
    held = (SIZES["held_first"], SIZES["num_experts"])
    we = ref.held_weights(m, layer0["router"], SIZES, held,
                          ref.exact_operands, None, other[0])
    assert we.shape == (ids.size, SIZES["num_experts"])
    assert int((we > 0).sum()) == 0  # ids 0..3 lie under the held 4..7


def test_recomputed_mixer_gives_the_same_step(both):
    """``use_recompute`` drops the mixers' activations only: same loss, and
    the counters still written once by the forward."""
    x, y = batch()
    losses = []
    for recompute in (False, True):
        _, model = prog.build_model(dict(SIZES, recompute_mixer=recompute))
        prog.seed_weights(model, SIZES, SEED, "float32")
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        crit = GPTPretrainingCriterion()
        step = paddle.jit.compile_train_step(model, crit, opt)
        losses.append([float(step(paddle.Tensor(x), paddle.Tensor(y)))
                       for _ in range(2)])
        assert all(r > 0 for _, r, _ in model.routed_load())
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)


# ---------------------------------------------------------------------------
# the chunked gated delta rule against the token recurrence
# ---------------------------------------------------------------------------
SMALL_HEADS = (2, 2, 4, 32, 16)  # batch, key heads, value heads, d_k, d_v
# the cell's head geometry (qwen3next-train-s8192: heads of 128 that the
# kernels read as blocks of lanes, two value heads to a key head), over two
# grid steps of eight chunks: the carried state and the reversed walk cross
CELL_HEADS = (1, 1, 2, 128, 128)
# one and four value heads to a key head, heads moved and in lanes
REP1_HEADS, REP4_HEADS = (2, 2, 2, 32, 16), (2, 1, 4, 32, 16)
REP1_LANES, REP4_LANES = (1, 2, 2, 128, 128), (1, 1, 4, 128, 128)


def delta_rule_inputs(seq, heads, decay, seed):
    rng = np.random.default_rng(seed)
    b, hk, hv, dk, dv = heads

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q, k = draw(b, seq, hk, dk), 3.0 * draw(b, seq, hk, dk)
    v, ct = draw(b, seq, hv, dv), draw(b, seq, hv, dv)
    g = -decay * jnp.asarray(rng.random((b, seq, hv)), jnp.float32)
    beta = jnp.asarray(rng.random((b, seq, hv)), jnp.float32)
    return (q, k, v, g, beta), ct


def token_recurrence(q, k, v, g, beta):
    """The rule on rows of q and k normalised as its kernels do it."""
    rep = v.shape[2] // q.shape[2]
    q = la.l2_normalize(q) * q.shape[-1] ** -0.5
    k = la.l2_normalize(k)
    q, k = (jnp.repeat(a, rep, axis=2) for a in (q, k))
    return ref.delta_rule(q, k, v, g, beta)


def output_and_gradients(rule, args, ct):
    """o and d (o . ct) / d (q, k, v, g, beta), float32."""
    def dot(*a):
        return (rule(*a).astype(jnp.float32) * ct).sum()

    return (rule(*args).astype(jnp.float32),) + jax.grad(dot, range(5))(*args)


@pytest.mark.parametrize("decay", [1e-3, 1.0, 12.0],
                         ids=["near_one", "middling", "near_zero"])
@pytest.mark.parametrize("seq,chunk,heads", [
    (192, 64, SMALL_HEADS), (64, 16, SMALL_HEADS), (48, 64, SMALL_HEADS),
    (1024, 64, CELL_HEADS), (192, 64, REP1_HEADS), (192, 64, REP4_HEADS),
    (1024, 64, REP1_LANES), (1024, 64, REP4_LANES)],
    ids=["192-64", "64-16", "48-64", "cell", "rep1", "rep4", "rep1-lanes",
         "rep4-lanes"])
def test_chunked_delta_rule_matches_recurrence(seq, chunk, heads, decay):
    args, ct = delta_rule_inputs(seq, heads, decay, seq + chunk)
    got = output_and_gradients(
        lambda *a: la.gated_delta_rule(*a, chunk=chunk), args, ct)
    want = output_and_gradients(jax.jit(token_recurrence), args, ct)
    np.testing.assert_allclose(got[0], want[0], atol=3e-6)
    for name, a, c in zip("q k v g beta".split(), got[1:], want[1:]):
        np.testing.assert_allclose(
            a, c, atol=5e-5 * float(jnp.abs(c).max()) + 1e-7, err_msg=name)


@pytest.mark.parametrize("seq,heads,step_bytes", [
    (1024, CELL_HEADS, None), (192, REP4_HEADS, None),
    (192, REP4_HEADS, 2 * 4 * 192 * 256)],
    ids=["cell", "rep4", "rep4-two-steps-of-two"])
def test_value_heads_walked_together_give_each_what_it_gets_alone(
        seq, heads, step_bytes, monkeypatch):
    """A grid step walks a key head's value heads side by side (or, where
    STEP_BYTES caps it, as many as fit); each value head gets exactly what
    a walk of it alone gives: the same call with q and k repeated to one key
    head a value head. o, dv, dg, dbeta bit for bit; dq and dk the lone
    walks' summed over a key head's value heads, head 0 first, within
    float32 rounding (the rows' norm is taken back once over the sum)."""
    if step_bytes:
        monkeypatch.setattr(la, "STEP_BYTES", step_bytes)
    (q, k, v, g, beta), ct = delta_rule_inputs(seq, heads, 1.0, 21)
    rep = heads[2] // heads[1]
    together = output_and_gradients(la.gated_delta_rule, (q, k, v, g, beta),
                                    ct)
    event = trace.events(kind="gdn_chunks")[-1].attrs
    assert (event["rep"], event["heads_per_step"]) == (
        rep, 2 if step_bytes else rep)
    alone = output_and_gradients(
        la.gated_delta_rule,
        (jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2), v, g, beta), ct)
    for name, i in (("o", 0), ("v", 3), ("g", 4), ("beta", 5)):
        np.testing.assert_array_equal(together[i], alone[i], err_msg=name)
    for name, i in (("q", 1), ("k", 2)):
        parts = alone[i].reshape(*q.shape[:3], rep, q.shape[3])
        summed = parts[:, :, :, 0]
        for r in range(1, rep):
            summed = summed + parts[:, :, :, r]
        np.testing.assert_allclose(
            together[i], summed, rtol=0,
            atol=1e-6 * float(jnp.abs(summed).max()), err_msg=name)


def test_bf16_delta_rule_stays_within_its_rounding_of_the_recurrence():
    """bf16 q, k, v as under AMP-O2 (the inverse then takes its three bf16
    passes; state, gates and sums stay float32), against the float32
    recurrence on the same rounded inputs: o and the five gradients within
    a few roundings to 8 bits (2^-8 = 0.004) by norm."""
    (q, k, v, g, beta), ct = delta_rule_inputs(1024, CELL_HEADS, 1.0, 5)
    args = tuple(a.astype(jnp.bfloat16) for a in (q, k, v)) + (g, beta)
    got = output_and_gradients(la.gated_delta_rule, args, ct)
    want = output_and_gradients(
        jax.jit(token_recurrence),
        tuple(a.astype(jnp.float32) for a in args), ct)
    for name, a, c in zip("o q k v g beta".split(), got, want):
        gap = float(jnp.linalg.norm(a.astype(jnp.float32) - c)
                    / jnp.linalg.norm(c))
        assert gap < 0.008, (name, gap)


def test_inverse_products_take_three_bf16_passes():
    """A float32 product from bf16 halves: high x high alone is one rounding
    to 8 bits off, with the two cross terms 2^-16; float32 operands are not
    split."""
    rng = np.random.default_rng(17)
    a, b = (jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
            for _ in range(2))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)

    def gap(x):
        return float(np.linalg.norm(x - exact) / np.linalg.norm(exact))

    ha, hb = la._halves(a, jnp.bfloat16), la._halves(b, jnp.bfloat16)
    assert [x.dtype for x in ha] == [jnp.bfloat16] * 2
    assert 1e-3 < gap(la._mm_halves(ha[:1], hb[:1])) < 5e-3
    assert gap(la._mm_halves(ha, hb)) < 2e-5
    assert gap(la._mm_halves(la._halves(a, jnp.float32),
                             la._halves(b, jnp.float32))) < 1e-6


def test_each_trace_of_the_rule_leaves_one_gdn_chunks_event():
    """At the sizes the cell rehearses with (2 x 128 tokens, 2 key and 4
    value heads of 16): forward and backward traced together leave ONE
    event, the chunks prepared in VMEM; a call that replays the program
    leaves none."""
    args, ct = delta_rule_inputs(128, (2, 2, 4, 16, 16), 1.0, 9)
    fn = jax.jit(jax.grad(
        lambda *a: (la.gated_delta_rule(*a) * ct).sum(), range(5)))
    before = len(trace.events(kind="gdn_chunks"))
    jax.block_until_ready(fn(*args))
    (event,) = trace.events(kind="gdn_chunks")[before:]
    assert event.site == "gated_delta_rule"
    assert event.attrs == dict(seq=128, chunk=64, chunks_per_step=2, rep=2,
                               heads_per_step=2, heads_in_lanes=False,
                               prepared="vmem")
    jax.block_until_ready(fn(*args))
    assert len(trace.events(kind="gdn_chunks")) == before + 1


def plain_conv(x, w):
    """The reference's short conv over x's first channels, in float32."""
    x, w = x[..., :w.shape[0]].astype(jnp.float32), w.astype(jnp.float32)
    taps = w.shape[-1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i:i + x.shape[1]] * w[:, i]
                           for i in range(taps)))


def test_short_conv_and_its_written_out_backward():
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((2, 37, 24)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((24, 4)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal(x.shape), jnp.float32)

    np.testing.assert_allclose(la.short_conv_silu(x, w), plain_conv(x, w),
                               atol=1e-6)
    got = jax.grad(lambda *a: (la.short_conv_silu(*a) * ct).sum(),
                   (0, 1))(x, w)
    want = jax.grad(lambda *a: (plain_conv(*a) * ct).sum(), (0, 1))(x, w)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], atol=1e-4)


def plain_norm(o, z, gain, eps=1e-6):
    """The reference's gated norm, z the last columns, in float32."""
    heads = o.astype(jnp.float32).reshape(*o.shape[:2], -1, gain.shape[0])
    gate = z[..., z.shape[-1] - o.shape[-1]:].astype(jnp.float32)
    unit = heads * jax.lax.rsqrt(
        jnp.mean(jnp.square(heads), -1, keepdims=True) + eps)
    return ((unit * gain.astype(jnp.float32)).reshape(o.shape)
            * jax.nn.silu(gate))


def mixer_pass_inputs(seq, dtype, seed=21):
    """Lane-aligned and small: 256 channels out of a 512-wide projection,
    2 heads of 128 gated by its last 256 columns."""
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(shift + scale * rng.standard_normal(shape), dtype)

    return dict(x=draw(2, seq, 512), w=draw(256, 4, scale=0.5),
                o=draw(2, seq, 256), gain=draw(128, scale=0.2, shift=1.0),
                ct=draw(2, seq, 256))


def assert_close(got, want, dtype, what, roundings=1):
    """To the rounding of ONE result in ``dtype`` (the kernels sum in
    float32 and round once), relative to the largest entry."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, what
    tol = roundings * (2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5)
    assert np.abs(got - want).max() <= tol * np.abs(want).max(), what


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("seq,row_block",
                         [(64, None), (72, None), (72, 24), (1536, None)],
                         ids=["s64", "s72", "s72_blocks_of_24", "s1536"])
@pytest.mark.parametrize("op", ["short_conv_silu", "short_conv_silu_in_two",
                                "gated_rms_norm"])
def test_mixer_pass_kernels_match_the_plain_expression(op, seq, row_block,
                                                       dtype, monkeypatch):
    """Forward and every gradient of the four kernels (interpret mode)
    against the plain float32 expression on the same inputs; a sequence of
    one row block and of three (1,536 rows; 72 rows in blocks of 24); the
    conv's result as one array and as two."""
    if row_block:
        monkeypatch.setattr(la, "ROW_BLOCK", row_block)
    a = mixer_pass_inputs(seq, dtype)
    before = len(trace.events(kind="mixer_pass"))
    if op == "short_conv_silu":
        args, got_fn, want_fn = (a["x"], a["w"]), la.short_conv_silu, plain_conv
    elif op == "short_conv_silu_in_two":  # each half written for itself
        def got_fn(x, w):
            halves = la.short_conv_silu(x, w, (128, 128))
            assert [h.shape[-1] for h in halves] == [128, 128]
            return jnp.concatenate(halves, axis=-1)

        args, want_fn = (a["x"], a["w"]), plain_conv
    else:
        args, got_fn, want_fn = ((a["o"], a["x"], a["gain"]),
                                 la.gated_rms_norm, plain_norm)
    ct = a["ct"].astype(jnp.float32)

    def both(fn, *args):
        return jax.value_and_grad(
            lambda *b: (fn(*b).astype(jnp.float32) * ct).sum(),
            tuple(range(len(args))))(*args)

    assert_close(got_fn(*args), want_fn(*args), dtype, "forward")
    event = trace.events(kind="mixer_pass")[before]
    assert op.startswith(event.site) and event.attrs["path"] == "vmem"
    assert event.attrs["row_block"] == (row_block or min(seq, 512))
    (_, got), (_, want) = both(got_fn, *args), both(
        want_fn, *(x.astype(jnp.float32) for x in args))
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == args[i].dtype
        assert_close(g, w, dtype, f"gradient {i}")
    if op == "gated_rms_norm":  # z's columns before the gate take no part
        assert not np.asarray(got[1][..., :256], np.float32).any()
    else:
        assert not np.asarray(got[0][..., 256:], np.float32).any()


@pytest.mark.parametrize("row_block", [None, 8], ids=["one_block", "blocks_of_8"])
def test_short_conv_kernels_do_not_read_across_the_batch(row_block,
                                                         monkeypatch):
    """The first three rows of the SECOND batch entry see zeros before them,
    not the first entry's last rows, and the last three rows of the FIRST
    entry's dx no dc of the second: each entry alone gives the same bits."""
    if row_block:
        monkeypatch.setattr(la, "ROW_BLOCK", row_block)
    a = mixer_pass_inputs(64, jnp.bfloat16, seed=22)

    def run(x, ct):
        y, pull = jax.vjp(la.short_conv_silu, x, a["w"])
        return y, pull(ct)[0]

    y, dx = run(a["x"], a["ct"])
    for i in range(2):
        y_alone, dx_alone = run(a["x"][i:i + 1], a["ct"][i:i + 1])
        np.testing.assert_array_equal(np.asarray(y[i], np.float32),
                                      np.asarray(y_alone[0], np.float32))
        np.testing.assert_array_equal(np.asarray(dx[i], np.float32),
                                      np.asarray(dx_alone[0], np.float32))
    # and the rows do depend on what is before them inside an entry
    assert np.asarray(y[1, 3:6], np.float32).any()


def test_published_widths_take_the_vmem_path_and_narrow_heads_say_why():
    """Under a shape-only trace at the cell's sizes both passes leave a
    ``mixer_pass`` event with ``path == "vmem"`` (the conv writing q, k and
    v apart, as the model asks it to); heads of 16 (the rehearsal's) leave
    ``"xla"`` with the reason."""
    def events(seq, wide, channels, lanes, d, splits=None):
        x, w, o, gain = (jax.ShapeDtypeStruct(shape, jnp.bfloat16) for shape in (
            (2, seq, wide), (channels, 4), (2, seq, lanes), (d,)))
        before = len(trace.events(kind="mixer_pass"))
        ys = jax.eval_shape(lambda x, w: la.short_conv_silu(x, w, splits),
                            x, w)
        assert [y.shape for y in jax.tree_util.tree_leaves(ys)] == [
            (2, seq, width) for width in splits or (channels,)]
        assert jax.eval_shape(la.gated_rms_norm, o, x, gain).shape == o.shape
        return [(e.site, e.attrs)
                for e in trace.events(kind="mixer_pass")[before:]]

    assert events(8192, 12288, 8192, 4096, 128, (2048, 2048, 4096)) == [
        ("short_conv_silu", dict(path="vmem", rows=16384, lanes=8192,
                                 row_block=512)),
        ("gated_rms_norm", dict(path="vmem", rows=16384, lanes=4096,
                                row_block=512))]
    assert events(128, 192, 128, 64, 16) == [
        ("short_conv_silu", dict(path="vmem", rows=256, lanes=128,
                                 row_block=128)),
        ("gated_rms_norm", dict(path="xla", rows=256, lanes=64, row_block=0,
                                why="head_not_128_lanes"))]
    assert events(100, 160, 96, 64, 16)[0] == (
        "short_conv_silu", dict(path="xla", rows=200, lanes=96, row_block=0,
                                why="lanes_not_blocks_of_128"))
    assert events(100, 384, 256, 128, 128)[1] == (
        "gated_rms_norm", dict(path="xla", rows=200, lanes=128, row_block=0,
                               why="seq_not_rows_of_8"))


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------
def expert_weights(seed=3, h=32, d=16, wide=16, held=4):
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=0.3):
        return jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)

    return {"router": draw(h, wide, scale=1.0), "egu_w": draw(held, h, 2 * d),
            "ed_w": draw(held, d, h), "sgu_w": draw(h, 2 * d),
            "sd_w": draw(d, h), "sg_w": draw(h, 1)}


def program_experts(x, w, first, top_k, rows):
    return moe.dropless_experts(
        x, w["router"], w["egu_w"], w["ed_w"], w["sgu_w"], w["sd_w"],
        w["sg_w"], first=first, top_k=top_k, renormalize=True, rows=rows)


def test_all_shares_add_up_to_the_uncut_layer():
    """The routed parts that each of 4 chips computes for its own 4 of 16
    experts, plus the shared expert counted once, are what the reference
    gives for the whole layer."""
    wide, held, top_k, tokens, h = 16, 4, 4, 96, 32
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((tokens, h)), jnp.float32)
    whole = expert_weights(held=wide)
    sizes = dict(num_experts_per_tok=top_k, num_experts=wide)
    uncut = ref.experts(x, whole, sizes, held=(0, wide))
    shared_only = uncut - ref.experts(x, whole, sizes, held=(0, wide),
                                      shared=False)
    total, routed = shared_only, 0
    for chip in range(wide // held):
        first = chip * held
        share = dict(whole, egu_w=whole["egu_w"][first:first + held],
                     ed_w=whole["ed_w"][first:first + held])
        y, n, _ = program_experts(x, share, first, top_k, rows=64)
        total = total + (y - shared_only)
        routed += int(n)
    assert routed == tokens * top_k  # every slot is some chip's
    np.testing.assert_allclose(total, uncut, atol=2e-5)


@pytest.mark.parametrize("case", ["every_slot_held", "no_slot_held",
                                  "one_expert_takes_all"])
def test_routing_extremes_drop_nothing(case):
    wide, held, top_k, tokens = 16, 4, 4, 64
    w = expert_weights()
    router = np.array(w["router"])
    first = 4
    if case == "every_slot_held":  # the 4 held experts are every token's 4
        router[:] = 0.0
        router[0, first:first + held] = 5.0
    elif case == "no_slot_held":
        router[:] = 0.0
        router[0, first:first + held] = -5.0
    else:  # every token's first choice is the one held expert
        router[0, first] += 9.0
    w["router"] = jnp.asarray(router)
    rng = np.random.default_rng(7)
    x = np.asarray(rng.standard_normal((tokens, 32)), np.float32)
    x[:, 0] = np.abs(x[:, 0]) + 1.0
    x = jnp.asarray(x)
    sizes = dict(num_experts_per_tok=top_k, num_experts=held)
    want = ref.experts(x, w, sizes, held=(first, held))
    rows = 80  # 1.2 x the even load of 64 slots: the skewed cases pass twice
    y, routed, ran = program_experts(x, w, first, top_k, rows)
    np.testing.assert_allclose(y, want, atol=2e-5)
    if case == "every_slot_held":
        assert int(routed) == tokens * top_k and int(ran) >= tokens * top_k
    elif case == "no_slot_held":
        assert int(routed) == 0
        assert int(ran) == rows  # one pass of the empty buffer
    else:
        assert int(routed) >= tokens
    # and the gradient of the skewed case, through more passes than one
    ct = jnp.asarray(rng.standard_normal(y.shape), jnp.float32)
    names = ["x"] + list(w)

    def loss(fn, *a):
        return (fn(a[0], dict(zip(list(w), a[1:]))) * ct).sum()

    got = jax.grad(lambda *a: loss(
        lambda x, w: program_experts(x, w, first, top_k, rows)[0], *a),
        range(len(names)))(x, *w.values())
    ref_grad = jax.grad(lambda *a: loss(
        lambda x, w: ref.experts(x, w, sizes, held=(first, held)), *a),
        range(len(names)))(x, *w.values())
    for name, a, c in zip(names, got, ref_grad):
        np.testing.assert_allclose(
            a, c, atol=2e-5 * float(jnp.abs(c).max()) + 1e-6, err_msg=name)


@pytest.fixture
def garbage_past_the_groups(monkeypatch):
    """The grouped product as the chip runs it: Mosaic's kernel skips the
    tiles past the groups, so those rows of its result hold whatever was in
    memory. Here they hold NaN, which no multiplication by zero removes."""
    plain = moe._grouped

    def grouped(rows, stack, sizes):
        out = plain(rows, stack, sizes)
        past = jnp.arange(out.shape[0]) >= sizes.sum()
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(moe, "_grouped", grouped)


@pytest.mark.parametrize("h", [32, 128], ids=["xla_combine", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("passes", [1, 2, 3])
def test_output_and_every_gradient_in_one_pass_and_in_three(
        garbage_past_the_groups, dtype, passes, h):
    """The layer's output and the gradient of x, of the router (which gets
    it through the slot weights), of both stacks and of the shared expert
    against the reference on the same numbers, within one rounding of the
    dtype: the slot weight's gradient from the d-wide cotangent, the stacks'
    gradients written by the first pass (from the gate-up product the
    forward kept) and added to by the later ones, which make theirs again.
    At a width of 128 lanes the combine is the kernel (interpreted), at 32
    XLA's scatter-add."""
    held, top_k, tokens, first = 4, 4, 64, 4
    w = {k: a.astype(dtype) for k, a in expert_weights(h=h).items()}
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((tokens, h)), dtype)
    ct = jnp.asarray(rng.standard_normal((tokens, h)), jnp.float32)
    sizes = dict(num_experts_per_tok=top_k, num_experts=held)
    routed = int(program_experts(x, w, first, top_k, 256)[1])
    rows = -(-routed // (8 * passes)) * 8
    names = ["x"] + list(w)

    def run(fn):
        def loss(*a):
            return (fn(a[0], dict(zip(list(w), a[1:]))).astype(jnp.float32)
                    * ct).sum()
        return jax.grad(loss, range(len(names)))

    y, _, ran = program_experts(x, w, first, top_k, rows)
    assert int(ran) == passes * rows and y.dtype == dtype
    got = run(lambda x, w: program_experts(x, w, first, top_k, rows)[0])(
        x, *w.values())
    wide = [a.astype(jnp.float32) for a in (x, *w.values())]
    want_y = ref.experts(wide[0], dict(zip(list(w), wide[1:])), sizes,
                         held=(first, held))
    want = run(lambda x, w: ref.experts(x, w, sizes, held=(first, held)))(
        *wide)
    assert_close(y, want_y, dtype, "y")
    for name, a, c in zip(names, got, want):
        assert a.dtype == dtype, name
        # the shared expert's bf16 chain (and x through it) rounds at every op
        routed_alone = name in ("router", "egu_w", "ed_w")
        assert_close(a, c, dtype, name, roundings=1 if routed_alone else 2)


HELD_EXPERTS_APPLY = moe.held_experts_apply


def _every_pass_in_the_loop_bwd(rows, act, res, dy):
    """The layer's written-out backward as it stood before its first pass
    took the forward's gate-up product, kept here to hold the new one to
    it: every pass inside the loop from zeros, each making its gate-up
    product again, and each stack's gradient taken out of a pass-index
    conditional."""
    x, wgt, w_gate_up, w_down, tok, offsets = res
    x = jax.lax.optimization_barrier(x)
    tokens = x.shape[0]
    block = moe._combine_plan("backward", rows, tokens, x.shape[1])

    def summed(c, total, part):
        return jax.lax.cond(
            c == 0, lambda: part,
            lambda: (total.astype(jnp.float32) + part).astype(total.dtype))

    def one_pass(c, carry):
        dx, dwgt, dgu, dd = carry
        t, kept, w, valid, sizes, lo = moe._pass_rows(
            c, rows, tok, wgt, offsets, tokens)
        xin, dyt = x[t], dy[t]
        gate, up = jnp.split(moe._grouped(xin, w_gate_up, sizes), 2, axis=-1)
        s, pull = jax.vjp(moe._swiglu, gate, up)
        g = moe._grouped(dyt, jnp.swapaxes(w_down, 1, 2), sizes)
        dh = jnp.where(valid, jnp.concatenate(pull(g * w), axis=-1),
                       0.0).astype(x.dtype)
        act = jnp.where(valid, s * w, 0.0).astype(x.dtype)
        dxin = moe._grouped(dh, jnp.swapaxes(w_gate_up, 1, 2), sizes)
        dgu_c = moe._grouped_outer(xin, dh, sizes, dgu.dtype)
        dd_c = moe._grouped_outer(act, dyt, sizes, dd.dtype)
        dw = jnp.where(valid[:, 0], (s * g).sum(-1), 0.0)
        return (moe._combine(dxin, kept, dx, tokens, block),
                jax.lax.dynamic_update_slice(dwgt, dw, (lo,)),
                summed(c, dgu, dgu_c), summed(c, dd, dd_c))

    dx, dwgt, dgu, dd = jax.lax.fori_loop(
        np.int32(0), moe._n_passes(offsets, rows), one_pass,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros(wgt.shape, jnp.float32),
         jnp.zeros_like(w_gate_up), jnp.zeros_like(w_down)))
    return dx.astype(x.dtype), dwgt.astype(wgt.dtype), dgu, dd, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def every_pass_in_the_loop(x, wgt, w_gate_up, w_down, tok, offsets, rows,
                           act="swiglu"):
    return HELD_EXPERTS_APPLY(x, wgt, w_gate_up, w_down, tok, offsets, rows,
                              act)


every_pass_in_the_loop.defvjp(
    lambda x, wgt, w_gate_up, w_down, tok, offsets, rows, act: (
        HELD_EXPERTS_APPLY(x, wgt, w_gate_up, w_down, tok, offsets, rows,
                           act),
        (x, wgt, w_gate_up, w_down, tok, offsets)),
    _every_pass_in_the_loop_bwd)


@pytest.mark.parametrize("h", [32, 128], ids=["xla_combine", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("passes", [1, 2, 3])
def test_saved_first_pass_is_bit_equal_to_every_pass_in_the_loop(
        garbage_past_the_groups, monkeypatch, dtype, passes, h):
    """The backward's first pass, made before the loop from the gate-up
    product the forward kept, gives the layer's output and every gradient
    (x, the router through the slot weights, both stacks, the shared
    expert) bit for bit as the backward that made that product again inside
    the loop: the same kernel on the same operands, NaN past the groups in
    both and removed by the same selects. Both are compiled, as a step is
    (run op by op, the first pass's float32 row sums are not the ones the
    compiled loop made, by an ulp), and without excess precision: the CPU
    makes a bf16 product in float32 and rounds it after, and may drop that
    rounding where only a float32 add reads it, which the pass-index
    conditional hid from it. On the chip the product is a kernel with a
    bf16 result, so there is no rounding to drop."""
    held, top_k, tokens, first = 4, 4, 64, 4
    w = {k: a.astype(dtype) for k, a in expert_weights(h=h, seed=5).items()}
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.standard_normal((tokens, h)), dtype)
    ct = jnp.asarray(rng.standard_normal((tokens, h)), jnp.float32)
    routed = int(program_experts(x, w, first, top_k, 256)[1])
    rows = -(-routed // (8 * passes)) * 8
    names = ["x"] + list(w)

    def vjp():
        def loss(*a):
            y = program_experts(a[0], dict(zip(list(w), a[1:])), first,
                                top_k, rows)[0]
            return (y.astype(jnp.float32) * ct).sum(), y

        args = (x, *w.values())
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, range(len(names)), has_aux=True)).lower(*args).compile(
                compiler_options={"xla_allow_excess_precision": False})(*args)
        return (y,) + grads

    assert int(program_experts(x, w, first, top_k, rows)[2]) == passes * rows
    got = vjp()
    monkeypatch.setattr(moe, "held_experts_apply", every_pass_in_the_loop)
    want = vjp()
    for name, a, b in zip(["y"] + names, got, want):
        assert a.dtype == b.dtype == dtype, name
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=name)


@pytest.mark.parametrize("h", [32, 128], ids=["xla_combine", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_no_slot_held_gives_exact_zeros(garbage_past_the_groups, dtype, h):
    """With no slot on a held expert every row of the buffer lies past the
    groups: the routed part and all four of its gradients are exactly zero,
    whatever the products left in those rows."""
    w = {k: a.astype(dtype) for k, a in expert_weights(h=h).items()}
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((64, h)), dtype)
    tok = jnp.asarray(rng.integers(0, 64, 96), jnp.int32)
    wgt = jnp.asarray(rng.random(96), jnp.float32)
    offsets = jnp.zeros(5, jnp.int32)

    def routed(x, wgt, gate_up, down):
        return moe.held_experts_apply(x, wgt, gate_up, down, tok, offsets, 32)

    y, vjp = jax.vjp(routed, x, wgt, w["egu_w"], w["ed_w"])
    for name, a in zip(("y", "x", "wgt", "egu_w", "ed_w"),
                       (y,) + vjp(jnp.ones_like(y))):
        assert not np.asarray(a, np.float32).any(), name


def test_counters_ride_the_compiled_step_and_one_event_a_compile(both):
    """After a compiled step the two buffers of each expert layer hold what
    a count made from the router's own output gives; each trace of the layer
    leaves one ``moe_route`` event, later steps none."""
    _, w = both
    _, model = prog.build_model(SIZES)
    prog.seed_weights(model, SIZES, SEED, "float32")
    x, y = batch(rows=1, seq=192, seed=4)  # a shape no other test traces
    first, held = SIZES["held_first"], SIZES["num_experts"]
    tokens, top_k = x.size, SIZES["num_experts_per_tok"]

    # the count, from the reference's routing of layer 0's input
    hid = w["embed"][x]
    l0 = {k[:-2]: a for k, a in w.items() if k.endswith(".0")}
    hid = hid + ref.linear_attention(
        ref.rms_norm(hid, 1.0 + l0["norm1"]), l0, SIZES)
    m = ref.rms_norm(hid, 1.0 + l0["norm2"]).reshape(tokens, -1)
    want = int((ref.held_weights(m, l0["router"], SIZES, (first, held),
                                 ref.exact_operands, None) > 0).sum())

    opt = paddle.optimizer.AdamW(learning_rate=0.0,
                                 parameters=model.parameters())
    step = paddle.jit.compile_train_step(model, GPTPretrainingCriterion(),
                                         opt)
    before = len(trace.events(kind="moe_route"))
    step(paddle.Tensor(x), paddle.Tensor(y))
    events = trace.events(kind="moe_route")[before:]
    assert len(events) == 1  # four layers of one shape: one trace
    rows = moe.row_buffer_rows(tokens, top_k, SIZES["router_experts"], held)
    assert events[0].attrs == dict(
        held=held, num_experts=SIZES["router_experts"], top_k=top_k,
        buffer_rows=rows, tokens=tokens,
        saved_first_product_bytes=(
            rows * 2 * SIZES["moe_intermediate_size"] * 4),
        act="swiglu", router="softmax", routed_scale=1.0)
    load = model.routed_load()
    assert load[0][1] == want
    assert all(ran % rows == 0 and ran >= routed for _, routed, ran in load)
    step(paddle.Tensor(x), paddle.Tensor(y))
    assert len(trace.events(kind="moe_route")) == before + 1


# ---------------------------------------------------------------------------
# grouped-query flash attention
# ---------------------------------------------------------------------------
def dense_attention(q, k, v):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("b,s,h,h_kv,d,blocks", [
    (1, 256, 8, 1, 256, None),        # group 8 at head_dim 256
    (2, 256, 4, 2, 32, (128, 128)),   # several q and k blocks: scratch path
    (1, 200, 6, 3, 24, None)], ids=["g8d256", "tiled", "odd"])
def test_grouped_query_flash_matches_dense(b, s, h, h_kv, d, blocks):
    rng = np.random.default_rng(s + d)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h_kv, d)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    kw = {} if blocks is None else dict(block_q=blocks[0], block_k=blocks[1])

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, **kw)

    np.testing.assert_allclose(flash(q, k, v), dense_attention(q, k, v),
                               atol=2e-5)
    got = jax.grad(lambda *a: (flash(*a) * ct).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (dense_attention(*a) * ct).sum(),
                    (0, 1, 2))(q, k, v)
    for name, a, c in zip("qkv", got, want):
        np.testing.assert_allclose(a, c, atol=3e-4, err_msg="d" + name)
    # a query head of a group computes what the equal-headed kernel computes
    # on k and v copied out to every query head, to the bit: the group only
    # changes which block the index map names
    group = h // h_kv
    kr, vr = (jnp.repeat(a, group, axis=2) for a in (k, v))
    assert jnp.array_equal(flash(q, k, v), flash(q, kr, vr))
    dq_equal = jax.grad(lambda q: (flash(q, kr, vr) * ct).sum())(q)
    assert jnp.array_equal(got[0], dq_equal)


def test_model_takes_the_flash_path_and_a_refusal_is_counted(both):
    from paddle_tpu.ops import nn_ops

    assert nn_ops.flash_attention_refusal(
        (2, 8192, 16, 256), (2, 8192, 2, 256), (2, 8192, 2, 256)) is None
    assert nn_ops.flash_attention_refusal(
        (2, 8192, 16, 256), (2, 8192, 3, 256),
        (2, 8192, 3, 256)) == "kv_heads_do_not_divide_q_heads"
    assert nn_ops.flash_attention_refusal(
        (1, 3000, 2, 24), (1, 3000, 2, 24),
        (1, 3000, 2, 24)) == "seq_or_head_dim_not_tiled"
    model, _ = both
    x, _ = batch()
    counters = paddle.profiler.dispatch_counters
    before = counters()["flash_attention_fallbacks"]
    model(paddle.Tensor(x))
    assert counters()["flash_attention_fallbacks"] == before
    # a shape the kernel cannot tile falls back, counted and named
    q = paddle.Tensor(jnp.ones((1, 3000, 2, 24), jnp.float32))
    seen = len(trace.events(kind="flash_fallback"))
    F.scaled_dot_product_attention(q, q, q, is_causal=True)
    after = counters()
    assert after["flash_attention_fallbacks"] == before + 1
    assert after["flash_attention_fallback_reasons"][
        "seq_or_head_dim_not_tiled"] >= 1
    event = trace.events(kind="flash_fallback")[seen]
    assert event.attrs["reason"] == "seq_or_head_dim_not_tiled"
    assert event.attrs["q_shape"] == (1, 3000, 2, 24)


def test_rms_norm_and_rotary_helpers():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((2, 16, 3, 32)), jnp.float32)
    w = jnp.asarray(0.1 * rng.standard_normal(32), jnp.float32)
    got = F.rms_norm(paddle.Tensor(x), paddle.Tensor(w), 1e-6, True)._value
    np.testing.assert_allclose(got, ref.rms_norm(x, 1.0 + w), atol=1e-6)
    got = F.rotary_embedding(paddle.Tensor(x), rotary_dim=8,
                             theta=1e7)._value
    np.testing.assert_allclose(got, ref.rotary(x, 8, 1e7), atol=1e-6)
    layer = paddle.nn.RMSNorm(32)
    np.testing.assert_allclose(layer(paddle.Tensor(x))._value,
                               ref.rms_norm(x, 1.0), atol=1e-6)
