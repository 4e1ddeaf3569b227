"""The state-space / attention hybrid decoder (models/granite_hybrid.py) and
what it brought — the chunked Mamba-2 scan, the short conv's bias, the
gate-then-norm, the ungated shared expert, mixers that hold a share of their
heads — against the plain reference kept with the benchmark
(benchmark/lib/reference_granite_hybrid.py: token-by-token recurrence, dense
masked softmax, experts as masks), at small sizes on the CPU in float32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from benchmark.lib import program_granite_hybrid as prog
from benchmark.lib import reference_granite_hybrid as ref
from benchmark.lib import weights_granite_hybrid as weights
from paddle_tpu.core.dispatch import dispatch_counters
from paddle_tpu.incubate import moe
from paddle_tpu.models import GPTPretrainingCriterion
from paddle_tpu.ops import linear_attention as la
from paddle_tpu.ops import state_space as ss
from paddle_tpu.profiler import trace

# the deployment: every count a whole; SHARE holds an eighth / a quarter
WHOLE = dict(
    num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
    hidden_size=64, vocab_size=512, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=32, mamba_d_conv=4, mamba_n_groups=1, mamba_chunk_size=32,
    num_local_experts=8, router_experts=8, num_experts_per_tok=3,
    intermediate_size=32, shared_intermediate_size=48,
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.0078125, logits_scaling=16, rms_norm_eps=1e-5,
    held_first=0, mamba_heads_first=0, attention_heads_first=0,
    vocab_first=0, recompute_mixer=False)
WHOLE["published"] = {k: WHOLE[k] for k in (
    "num_hidden_layers", "mamba_n_heads", "num_attention_heads",
    "num_key_value_heads", "num_local_experts", "vocab_size")}
SHARE = dict(WHOLE, mamba_n_heads=2, num_attention_heads=2,
             num_key_value_heads=1, num_local_experts=2, held_first=2,
             vocab_size=128)
SEED = 2**31 + 32


def batch(sizes, rows=2, seq=64, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], (rows, seq + 1)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sizes", [WHOLE, SHARE], ids=["whole", "held_share"])
def test_logits_loss_and_every_leafs_gradient_agree(sizes):
    _, model = prog.build_model(sizes)
    prog.seed_weights(model, sizes, SEED, "float32")
    w = weights.make(sizes, SEED, "float32")
    x, y = batch(sizes)
    out = model(paddle.Tensor(x))
    want = ref.logits(w, x, sizes)
    assert float(jnp.abs(out._value - want).max()) < 2e-5
    loss = GPTPretrainingCriterion()(out, paddle.Tensor(y))
    ref_loss, grads = ref.loss_and_grads(w, x, y, sizes)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    loss.backward()
    seen = set()
    for name, p in model.named_parameters():
        leaf = prog.flat_name(name)
        seen.add(leaf)
        g = grads[leaf]
        scale = float(jnp.abs(g).max())
        assert scale > 0, f"{leaf}: the reference gives it no gradient"
        assert float(jnp.abs(p.grad._value - g).max()) < 2e-3 * scale + 1e-8, \
            leaf
    assert seen == set(grads)
    # the tied embedding is one leaf; the shared expert has no gate
    assert "head_w" not in seen and not any(s.startswith("sg_w") for s in seen)


def test_recomputed_mixer_gives_the_same_step():
    x, y = batch(SHARE)
    losses = []
    for remake in (False, True):
        _, model = prog.build_model(dict(SHARE, recompute_mixer=remake))
        prog.seed_weights(model, SHARE, SEED, "float32")
        crit = GPTPretrainingCriterion()
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        step = paddle.jit.compile_train_step(model, crit, opt)
        losses.append([float(step(paddle.Tensor(x), paddle.Tensor(y)))
                       for _ in range(2)])
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)


def test_the_model_leaves_its_events_and_counts_its_routed_load():
    _, model = prog.build_model(SHARE)
    x, _ = batch(SHARE, rows=3)  # a shape no other test has traced
    seen = {k: len(trace.events(kind=k)) for k in ("mixer_share",
                                                   "ssd_chunks")}
    model(paddle.Tensor(x))
    shares = {e.site: e.attrs for e in trace.events(
        kind="mixer_share")[seen["mixer_share"]:]}
    assert shares == {"mamba": {"heads": 2, "heads_published": 8},
                      "attention": {"heads": 2, "heads_published": 4}}
    (event,) = trace.events(kind="ssd_chunks")[seen["ssd_chunks"]:]
    assert event.attrs["heads"] == 2 and event.attrs["heads_published"] == 8
    assert event.attrs["path"] == "xla"  # 16-wide heads: the kernels refuse
    load = model.routed_load()
    assert [layer for layer, _, _ in load] == [0, 1, 2]
    assert all(0 < routed <= rows for _, routed, rows in load)


# ---------------------------------------------------------------------------
# the share tied to the model: the parts of all shares add up
# ---------------------------------------------------------------------------
def _slice_mamba(w, first, count, sizes):
    """The leaves of a state-space mixer that holds heads [first, first +
    count) of the whole mixer's."""
    p = sizes["mamba_d_head"]
    inner = sizes["mamba_n_heads"] * p
    bc = 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    heads = slice(first, first + count)
    lanes = np.r_[first * p:(first + count) * p]
    channels = np.r_[lanes, inner:inner + bc]
    return {"norm1": w["norm1"],
            "xbcz_w": w["xbcz_w"][:, np.r_[channels, inner + bc + lanes]],
            "dt_w": w["dt_w"][:, heads], "conv_w": w["conv_w"][channels],
            "conv_b": w["conv_b"][channels], "a_log": w["a_log"][heads],
            "dt_bias": w["dt_bias"][heads], "d_skip": w["d_skip"][heads],
            "gnorm": w["gnorm"][lanes], "out_w": w["out_w"][lanes]}


def _bare(w, layer):
    tail = f".{layer}"
    return {k[:-len(tail)]: v for k, v in w.items() if k.endswith(tail)}


def test_all_shares_add_up_to_the_uncut_layer():
    """Over the shares (4 of 2 state-space heads, 2 of 2 query heads on one
    KV head, 4 of 2 experts), each mixer's and the expert layer's parts, the
    shared expert counted once, add up to the uncut reference's layer; the
    state-space mixer's do so when every share norms by the statistic of all
    heads, the one line in which reference(held) and reference(all)
    differ."""
    w = weights.make(WHOLE, SEED, "float32")
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64), jnp.float32)

    # state space: 4 shares of 2 heads
    whole = _bare(w, 0)
    want = ref.mamba_mixer(u, whole, WHOLE)
    statistic = jnp.square(ref.mamba_gated(u, whole, WHOLE)).mean(
        -1, keepdims=True)
    held = dict(WHOLE, mamba_n_heads=2)
    parts = [ref.mamba_mixer(u, _slice_mamba(whole, first, 2, WHOLE), held,
                             statistic=statistic) for first in (0, 2, 4, 6)]
    assert float(jnp.abs(sum(parts) - want).max()) < 1e-5 * float(
        jnp.abs(want).max()) + 1e-7
    own = ref.mamba_mixer(u, _slice_mamba(whole, 0, 2, WHOLE), held)
    assert float(jnp.abs(own - parts[0]).max()) > 1e-3 * float(
        jnp.abs(own).max())  # its own statistic is another number
    # and the program's mixer is the reference's share
    cfg, model = prog.build_model(SHARE)
    mixer = model.model.layers[0].mixer
    names = {"in_proj_xbcz.weight": "xbcz_w", "in_proj_dt.weight": "dt_w",
             "conv_weight": "conv_w", "conv_bias": "conv_b", "A_log": "a_log",
             "dt_bias": "dt_bias", "D": "d_skip", "norm_weight": "gnorm",
             "out_proj.weight": "out_w"}
    share = _slice_mamba(whole, 2, 2, WHOLE)
    for name, p in mixer.named_parameters():
        p._value = share[names[name]]
    got = mixer(paddle.Tensor(u))._value
    assert float(jnp.abs(got - ref.mamba_mixer(u, share, held)).max()) < 2e-6

    # attention: 2 shares of 2 query heads on 1 KV head
    whole = _bare(w, 1)
    want = ref.attention(u, whole, WHOLE)
    d, held = WHOLE["head_dim"], dict(WHOLE, num_attention_heads=2,
                                      num_key_value_heads=1)
    parts = []
    for kv in (0, 1):
        q, k = np.r_[2 * kv * d:(2 * kv + 2) * d], np.r_[kv * d:(kv + 1) * d]
        parts.append(ref.attention(u, {
            "q_w": whole["q_w"][:, q], "k_w": whole["k_w"][:, k],
            "v_w": whole["v_w"][:, k], "o_w": whole["o_w"][q]}, held))
    assert float(jnp.abs(sum(parts) - want).max()) < 1e-5 * float(
        jnp.abs(want).max()) + 1e-7

    # experts: 4 shares of 2, the shared expert once
    whole = _bare(w, 0)
    tokens = u.reshape(-1, 64)
    want = ref.experts(tokens, whole, WHOLE)
    parts = [ref.experts(
        tokens, dict(whole, egu_w=whole["egu_w"][first:first + 2],
                     ed_w=whole["ed_w"][first:first + 2]),
        dict(WHOLE, num_local_experts=2), held=(first, 2),
        shared=first == 0) for first in (0, 2, 4, 6)]
    assert float(jnp.abs(sum(parts) - want).max()) < 1e-5 * float(
        jnp.abs(want).max()) + 1e-7


# ---------------------------------------------------------------------------
# the scan
# ---------------------------------------------------------------------------
def scan_inputs(b, s, heads, p, groups, n, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    f32 = jnp.float32
    x = jax.random.normal(ks[0], (b, s, heads, p), f32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, heads), f32) - 2.0)
    a_log = jnp.log(jax.random.uniform(ks[2], (heads,), f32, 1.0, 16.0))
    bm = (0.3 * jax.random.normal(ks[3], (b, s, groups, n), f32)).astype(dtype)
    cm = (0.3 * jax.random.normal(ks[4], (b, s, groups, n), f32)).astype(dtype)
    d_skip = 1.0 + 0.1 * jax.random.normal(ks[5], (heads,), f32)
    return x, dt, a_log, bm, cm, d_skip


def recurrence(x, dt, a_log, bm, cm, d_skip):
    a = jnp.exp(-jnp.exp(a_log) * dt)
    return ref.ssd_recurrence(x, dt, a, bm, cm) + d_skip[:, None] * x


@pytest.mark.parametrize("b,s,heads,p,groups,n,chunk,path", [
    (2, 256, 4, 64, 1, 128, 128, "vmem"),   # two heads a lane block
    (1, 512, 2, 64, 1, 128, 128, "vmem"),   # two chunks a grid step
    (1, 256, 2, 128, 1, 128, 128, "vmem"),  # a head a lane block
    (1, 200, 4, 64, 1, 128, 128, "vmem"),   # not whole chunks: padded
    (2, 96, 4, 16, 2, 32, 32, "xla"),       # two groups, narrow heads
    (1, 70, 2, 16, 1, 32, 32, "xla"),       # not whole chunks
    (1, 512, 8, 64, 2, 128, 128, "vmem"),   # two groups of two lane blocks,
    #                                         four chunks a grid step
    (1, 256, 32, 64, 8, 128, 128, "vmem"),  # eight groups of two lane blocks
    (2, 256, 16, 64, 8, 128, 128, "vmem"),  # eight groups of one lane block
], ids=["pairs", "two_chunks_a_step", "wide_head", "padded", "groups",
        "xla_padded", "two_groups_kernels", "eight_groups_kernels",
        "eight_groups_a_block_each"])
def test_chunked_scan_matches_the_token_recurrence(b, s, heads, p, groups, n,
                                                   chunk, path):
    """Forward and every input's gradient, with the kernels interpreted
    where the shape fits them and the ``jax.numpy`` chunks where not."""
    args = scan_inputs(b, s, heads, p, groups, n)
    weight = jax.random.normal(jax.random.PRNGKey(9), (b, s, heads, p),
                               jnp.float32)
    before = len(trace.events(kind="ssd_chunks"))
    fallbacks = dispatch_counters()["ssd_scan_fallbacks"]
    got = ss.ssd_scan(*args, chunk=chunk)
    (event,) = trace.events(kind="ssd_chunks")[before:]
    assert event.attrs["path"] == path and ("why" in event.attrs) == (
        path == "xla")
    assert dispatch_counters()["ssd_scan_fallbacks"] == fallbacks + (
        path == "xla")
    want = recurrence(*args)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())
    grads = jax.grad(lambda *a: (ss.ssd_scan(*a, chunk=chunk) * weight).sum(),
                     argnums=tuple(range(6)))(*args)
    wants = jax.grad(lambda *a: (recurrence(*a) * weight).sum(),
                     argnums=tuple(range(6)))(*args)
    for name, g, want in zip(("x", "dt", "A_log", "B", "C", "D"), grads,
                             wants):
        assert float(jnp.abs(g - want).max()) < 1e-4 * float(
            jnp.abs(want).max()), name


def test_bf16_scan_stays_within_its_rounding_of_the_recurrence():
    args = scan_inputs(1, 256, 2, 64, 1, 128, seed=4, dtype=jnp.bfloat16)
    got = ss.ssd_scan(*args, chunk=128)
    assert got.dtype == jnp.bfloat16
    wide = [a.astype(jnp.float32) for a in args]
    want = recurrence(*wide)
    err = jnp.linalg.norm(got.astype(jnp.float32) - want)
    assert float(err / jnp.linalg.norm(want)) < 8e-3


def test_scan_refusals_name_their_reason():
    assert ss._refusal(16, 64, 1, 128, 256) is None
    assert ss._refusal(16, 64, 2, 128, 256) is None
    assert ss._refusal(64, 64, 8, 128, 128) is None
    assert ss._refusal(4, 64, 4, 128, 256) == "group_not_blocks_of_128_lanes"
    assert ss._refusal(3, 64, 1, 128, 256) == "heads_not_blocks_of_128_lanes"
    assert ss._refusal(8, 48, 1, 128, 256) == "heads_not_blocks_of_128_lanes"
    assert ss._refusal(16, 64, 1, 64, 256) == "state_not_blocks_of_128_lanes"
    assert ss._refusal(16, 64, 1, 128, 64) == "chunk_not_blocks_of_128_lanes"


# ---------------------------------------------------------------------------
# round the scan: the conv's bias, the gate-then-norm
# ---------------------------------------------------------------------------
def plain_conv(x, w, b, channels):
    k, s = w.shape[1], x.shape[1]
    xp = jnp.pad(x[..., :channels], ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, i:i + s] * w[:, i] for i in range(k)) + b)


@pytest.mark.parametrize("seq,channels,extra,splits,path", [
    (64, 384, 256, (128, 128, 128), "vmem"),
    (40, 96, 32, None, "xla")], ids=["kernels", "jax_numpy"])
def test_short_conv_with_a_bias(seq, channels, extra, splits, path):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (2, seq, channels + extra))
    w = 0.5 * jax.random.normal(ks[1], (channels, 4))
    b = jax.random.normal(ks[2], (channels,))
    weight = jax.random.normal(ks[3], (2, seq, channels))

    def ours(x, w, b):
        y = la.short_conv_silu(x, w, splits, bias=b)
        return ((jnp.concatenate(y, -1) if splits else y) * weight).sum()

    def plain(x, w, b):
        return (plain_conv(x, w, b, channels) * weight).sum()

    before = len(trace.events(kind="mixer_pass"))
    # a sum of 49,152 terms of size 1 that cancels to 1: float32's own noise
    assert float(ours(x, w, b)) == pytest.approx(float(plain(x, w, b)),
                                                 abs=1e-3)
    assert trace.events(kind="mixer_pass")[before].attrs["path"] == path
    for g, want in zip(jax.grad(ours, argnums=(0, 1, 2))(x, w, b),
                       jax.grad(plain, argnums=(0, 1, 2))(x, w, b)):
        assert float(jnp.abs(g - want).max()) < 1e-4 * float(
            jnp.abs(want).max())
    # without a bias it is the conv it was
    assert float(jnp.abs(
        la.short_conv_silu(x, w) - plain_conv(x, w, 0.0, channels)
    ).max()) < 1e-5


def test_gate_then_norm_takes_one_statistic_over_all_lanes():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    o = jax.random.normal(ks[0], (2, 16, 256))
    z = jax.random.normal(ks[1], (2, 16, 300))  # the gate: its last 256
    gain = 1.0 + 0.1 * jax.random.normal(ks[2], (256,))
    before = len(trace.events(kind="mixer_pass"))
    got = la.gated_rms_norm(o, z, gain, epsilon=1e-5, gate_first=True)
    event = trace.events(kind="mixer_pass")[before]
    assert event.attrs["why"] == "statistic_over_all_lanes"
    gated = o * jax.nn.silu(z[..., 44:])
    want = gated * jax.lax.rsqrt(
        jnp.square(gated).mean(-1, keepdims=True) + 1e-5) * gain
    assert float(jnp.abs(got - want).max()) < 1e-5
    # the other order, head by head, is another number
    other = la.gated_rms_norm(o, z, gain[:128], epsilon=1e-5)
    assert float(jnp.abs(other - want).max()) > 0.1
    with pytest.raises(ValueError):
        la.gated_rms_norm(o, z, gain[:128], gate_first=True)


# ---------------------------------------------------------------------------
# the expert layer and attention as this model asks for them
# ---------------------------------------------------------------------------
def test_top_k_then_softmax_is_softmax_top_k_renormalised():
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (64, 72))
    w, idx = moe.route_top_k(logits, 10, renormalize=True)
    top, want_idx = jax.lax.top_k(logits, 10)
    assert (idx == want_idx).all()
    assert float(jnp.abs(w - jax.nn.softmax(top, axis=-1)).max()) < 1e-6


def test_shared_expert_is_gated_unless_told_otherwise():
    gated = moe.DroplessExperts(16, 8, 4, 2, d_shared=8)
    assert tuple(gated.shared_gate.shape) == (16, 1)
    plain = moe.DroplessExperts(16, 8, 4, 2, d_shared=8, shared_gate=False)
    assert plain.shared_gate is None
    assert "shared_gate" not in dict(plain.named_parameters())
    for name, p in plain.named_parameters():
        p._value = dict(gated.named_parameters())[name]._value
    x = paddle.Tensor(jax.random.normal(jax.random.PRNGKey(6), (1, 8, 16)))
    flat = x._value.reshape(8, 16)
    g, u = jnp.split(flat @ plain.shared_gate_up._value, 2, axis=-1)
    shared = (jax.nn.silu(g) * u) @ plain.shared_down._value
    opened = jax.nn.sigmoid(flat @ gated.shared_gate._value)
    diff = (plain(x)._value - gated(x)._value).reshape(8, 16)
    assert float(jnp.abs(diff - shared * (1.0 - opened)).max()) < 1e-6


def test_attention_takes_a_scale_of_its_own():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 1, 64))
    v = jax.random.normal(ks[2], (1, 128, 1, 64))

    def dense(scale):
        scores = jnp.einsum("bqhd,bkd->bhqk", q, k[:, :, 0]) * scale
        seen = jnp.tril(jnp.ones((128, 128), bool))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkd->bqhd", probs, v[:, :, 0])

    T = paddle.Tensor
    got = F.scaled_dot_product_attention(T(q), T(k), T(v), is_causal=True,
                                         scale=1 / 128)
    assert float(jnp.abs(got._value - dense(1 / 128)).max()) < 2e-5
    default = F.scaled_dot_product_attention(T(q), T(k), T(v), is_causal=True)
    assert float(jnp.abs(default._value - dense(64 ** -0.5)).max()) < 2e-5
