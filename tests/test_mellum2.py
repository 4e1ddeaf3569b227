"""The sliding-window sparse decoder (models/mellum2.py) and what it brought —
YaRN's rotary table, layers of two kinds read from ``layer_types``, the
dropless expert layer at top-8 of 64 — against the plain reference kept with
the benchmark (benchmark/lib/reference_mellum2.py: a dense band and a dense
causal mask, the published YaRN form, experts as masks), at small sizes on
the CPU in float32."""
import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.lib import counts_mellum2 as counts
from benchmark.lib import program_mellum2 as prog
from benchmark.lib import reference_mellum2 as ref
from benchmark.lib import traffic
from benchmark.lib import weights_mellum2 as weights
from paddle_tpu.incubate import moe
from paddle_tpu.models import GPTPretrainingCriterion
from paddle_tpu.ops import nn_ops
from paddle_tpu.profiler import trace

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "benchmark", "configs",
                       "mellum2-12b-a2.5b-ep8.json")) as _f:
    CELL = json.load(_f)
SIZES = dict(
    CELL, num_hidden_layers=4, hidden_size=64, vocab_size=512,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    num_experts=4, router_experts=16, held_first=4, num_experts_per_tok=4,
    moe_intermediate_size=32, sliding_window=96)
SEED = 2**31 + 38
MIX = dict(ring=3, batch=2, seq=256)
LEAVES = sorted({name.split(".")[0] for name, _, _ in
                 weights.leaf_table(SIZES)})


def T(a):
    return paddle.Tensor(jnp.asarray(a), stop_gradient=True)


@pytest.fixture(scope="module")
def both():
    """(seeded weights, a batch, the program's logits / loss / gradients by
    leaf, the reference's)."""
    _, model = prog.build_model(SIZES)
    prog.seed_weights(model, SIZES, SEED, "float32")
    w = weights.make(SIZES, SEED, "float32")
    ids = traffic.train_batches(MIX, SEED, SIZES["vocab_size"])[0]
    x, y = ids[:, :-1], ids[:, 1:]
    out = model(T(x))
    loss = GPTPretrainingCriterion()(out, T(y))
    loss.backward()
    got = {prog.flat_name(n): p.grad._value
           for n, p in model.named_parameters()}
    ref_loss, grads = ref.loss_and_grads(w, jnp.asarray(x), jnp.asarray(y),
                                         SIZES)
    return dict(w=w, x=x, logits=out._value, loss=float(loss), grads=got,
                ref_loss=float(ref_loss), ref_grads=grads)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
def test_logits_and_loss_agree(both):
    want = ref.logits(both["w"], jnp.asarray(both["x"]), SIZES)
    assert both["logits"].shape == (2, 256, 512)
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


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_moves_the_reference(both, fault):
    """Every fault the cell is calibrated against changes what the reference
    computes at this size (its gradient), so that none is a fault in name
    only."""
    y = traffic.train_batches(MIX, SEED, SIZES["vocab_size"])[0][:, 1:]
    _, grads = ref.loss_and_grads(both["w"], jnp.asarray(both["x"]),
                                  jnp.asarray(y), SIZES, fault=fault)
    moved = max(float(jnp.abs(g - both["ref_grads"][k]).max()
                      / jnp.abs(both["ref_grads"][k]).max())
                for k, g in grads.items())
    assert moved > 1e-2


def test_yarn_table_against_an_independent_transcription():
    """The published keys: low 18, high 35; the program's table against
    numpy in float64 from the equations, and the reference's."""
    rope = CELL["rope_parameters"]["full_attention"]
    theta, dim = rope["rope_theta"], 128
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2 * i / dim)

    def edge(beta, rnd):
        return rnd(dim * math.log(8192 / (beta * 2 * math.pi))
                   / (2 * math.log(theta)))

    low, high = edge(32, math.floor), edge(1, math.ceil)
    assert (low, high) == (18, 35)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = extra / 16 * ramp + extra * (1 - ramp)
    got = nn_ops.rope_inv_freq(theta, dim, rope)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(
        ref.yarn_inv_freq(theta, dim, 16, 8192, 32, 1), want, rtol=2e-6)
    # below low the default table, from high on the default over 16
    np.testing.assert_allclose(got[:19], extra[:19], rtol=2e-6)
    np.testing.assert_allclose(got[35:], extra[35:] / 16, rtol=2e-6)
    np.testing.assert_array_equal(nn_ops.rope_inv_freq(theta, dim),
                                  np.float32(1.0) / np.float32(theta) ** (
                                      np.arange(64, dtype=np.float32)
                                      * np.float32(2.0 / dim)))


def test_the_attention_factor_is_on_the_scores():
    """The full layers fold YaRN's attention factor into the kernels' scale
    as its square: q k^T times factor^2 / sqrt(d), what cos and sin each
    times the factor give."""
    _, model = prog.build_model(SIZES)
    kinds = [layer.mixer for layer in model.model.layers]
    assert [m.kind for m in kinds] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert [m.window for m in kinds] == [96, 96, 96, None]
    factor = CELL["rope_parameters"]["full_attention"]["attention_factor"]
    assert kinds[3].scale == pytest.approx(factor ** 2 / math.sqrt(32))
    assert kinds[0].scale is None and kinds[0].yarn is None


# ---------------------------------------------------------------------------
# the compiled step, its counters and events
# ---------------------------------------------------------------------------
def test_the_compiled_step_walks_the_window_and_leaves_its_events():
    """A compiled step of the model: the expert layers' buffers hold their
    load, the attention leaves one ``flash_tiles`` event a kind of layer
    (the three sliding layers share one trace), none falls back, and the
    two scopes are in the compiled program."""
    _, model = prog.build_model(SIZES)
    prog.seed_weights(model, SIZES, SEED, "float32")
    mix = dict(MIX, batch=1, seq=384)  # a shape no other test traces
    ring = traffic.train_batches(mix, 5, SIZES["vocab_size"])
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    crit = GPTPretrainingCriterion()
    step = paddle.jit.compile_train_step(model, crit, opt)
    counters = paddle.profiler.dispatch_counters
    fallbacks = counters()["flash_attention_fallbacks"]
    seen = len(trace.events(kind="flash_tiles"))
    losses = [float(step(T(ids[:, :-1]), T(ids[:, 1:]))) for ids in ring]
    assert all(math.isfinite(v) for v in losses)
    tiles = [e.attrs for e in trace.events(kind="flash_tiles")[seen:]]
    assert sorted(t["mask"] for t in tiles) == ["causal", "window"]
    window = next(t for t in tiles if t["mask"] == "window")
    assert window["window"] == 96 and window["seq"] == 384
    assert (window["run"], window["masked"], window["total"]) == \
        counts_band_tiles(384, 96, window["sub_q"])
    assert counters()["flash_attention_fallbacks"] == fallbacks
    slots = 384 * SIZES["num_experts_per_tok"]
    for _, routed, ran in model.routed_load():
        assert 0 < routed < slots and ran >= routed
    text = step._step.lower(*step._arg_specs).compile().as_text()
    assert "sliding_attention" in text and "full_attention" in text


def counts_band_tiles(s, window, sub):
    """(run, masked, total) sub-tiles of the band, over positions."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = ((j <= i) & (j > i - window)).reshape(s // sub, sub, s // sub, sub)
    has_any, has_all = seen.any((1, 3)), seen.all((1, 3))
    return (int(has_any.sum()), int((has_any & ~has_all).sum()),
            (s // sub) ** 2)


def test_recomputed_mixer_gives_the_same_step():
    """``recompute_mixer`` drops the attention's activations only: the same
    losses, the counters still written once by the forward."""
    ring = traffic.train_batches(MIX, SEED, SIZES["vocab_size"])
    losses = []
    for recompute in (False, True):
        _, model = prog.build_model(dict(SIZES, recompute_mixer=recompute))
        prog.seed_weights(model, SIZES, SEED, "float32")
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        step = paddle.jit.compile_train_step(
            model, GPTPretrainingCriterion(), opt)
        losses.append([float(step(T(b[:, :-1]), T(b[:, 1:]))) for b in ring])
        assert all(r > 0 for _, r, _ in model.routed_load())
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)


# ---------------------------------------------------------------------------
# the expert share, the counts, the exports
# ---------------------------------------------------------------------------
def test_all_eight_shares_add_up_to_the_uncut_layer():
    """The parts that each of 8 chips computes for its own 8 of the 64
    experts, top-8 renormalised, are what the reference gives for the whole
    layer: there is no shared expert to count once."""
    wide, held, top_k, tokens, h, d = 64, 8, 8, 96, 32, 16
    rng = np.random.default_rng(7)

    def draw(*shape, scale=0.3):
        return jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)

    x = draw(tokens, h, scale=1.0)
    whole = {"router": draw(h, wide, scale=1.0),
             "egu_w": draw(wide, h, 2 * d), "ed_w": draw(wide, d, h)}
    sizes = dict(num_experts_per_tok=top_k, num_experts=wide)
    uncut = ref.experts(x, whole, sizes, held=(0, wide), shared=False)
    total, routed = 0.0, 0
    for chip in range(wide // held):
        first = chip * held
        y, n, _ = moe.dropless_experts(
            x, whole["router"], whole["egu_w"][first:first + held],
            whole["ed_w"][first:first + held], None, None, None, first=first,
            top_k=top_k, renormalize=True, rows=128)
        total, routed = total + y, routed + int(n)
    assert routed == tokens * top_k  # every slot is some chip's
    np.testing.assert_allclose(total, uncut, atol=2e-5)


def test_counts_at_the_cell():
    assert counts.n_params(CELL) == 340_349_184
    assert counts.band_pairs(8192, 1024) == 1024 * 1025 // 2 + 7168 * 1024
    assert counts.band_pairs(300, 1024) == 300 * 301 // 2
    i = np.arange(700)
    assert counts.band_pairs(700, 100) == int(
        np.minimum(i + 1, 100).sum())
    assert list(counts.layer_kinds(CELL)) == ["sliding_attention"] * 3 + [
        "full_attention"]


def test_models_export_and_the_parameter_names():
    from paddle_tpu import models

    cfg = models.Mellum2Config()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.sliding_window, cfg.vocab_size) == (
        28, 2304, 32, 4, 128, 64, 8, 896, 1024, 98304)
    assert cfg.layer_types == tuple(CELL["layer_types"])
    assert cfg.rope_parameters == CELL["rope_parameters"]
    _, model = prog.build_model(SIZES)
    assert isinstance(model, models.Mellum2ForCausalLM)
    assert isinstance(model.model, models.Mellum2Model)
    names = [n for n, _ in model.named_parameters()]
    assert not [n for n in names if "shared" in n or "norm.weight" in n
                and "mixer" in n]
    assert {prog.flat_name(n) for n in names} == {
        name for name, _, _ in weights.leaf_table(SIZES)}
