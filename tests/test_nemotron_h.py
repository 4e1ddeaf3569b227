"""The one-part-a-layer hybrid decoder (models/nemotron_h.py) and what it
brought — the Mamba-2 scan's kernels over several B / C groups, the gated
norm with a statistic per group, relu^2 experts under a sigmoid router with
a correction bias and a routed scale — against the plain reference kept with
the benchmark (benchmark/lib/reference_nemotron_h.py: token-by-token
recurrence, dense masked softmax, experts as masks), at small sizes on the
CPU in float32. The mixers' shapes take the scan's kernels (interpreted):
heads of 64, state 128, chunk 128, 8 groups of two heads (one lane block
each)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.lib import counts_nemotron_h as counts
from benchmark.lib import peaks
from benchmark.lib import program_nemotron_h as prog
from benchmark.lib import reference_nemotron_h as ref
from benchmark.lib import weights_nemotron_h as weights
from paddle_tpu.core.dispatch import dispatch_counters
from paddle_tpu.incubate import moe
from paddle_tpu.models import GPTPretrainingCriterion
from paddle_tpu.ops import linear_attention as la
from paddle_tpu.profiler import trace

# the deployment: every count a whole; SHARE holds a quarter of the experts
# and of the vocabulary
WHOLE = dict(
    num_hidden_layers=4, hybrid_override_pattern="ME*E", hidden_size=64,
    vocab_size=512, head_dim=16, num_attention_heads=4, num_key_value_heads=2,
    mamba_num_heads=16, mamba_head_dim=64, ssm_state_size=128, n_groups=8,
    conv_kernel=4, chunk_size=128, n_routed_experts=8, router_experts=8,
    num_experts_per_tok=3, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5,
    norm_topk_prob=True, layer_norm_epsilon=1e-5, held_first=0,
    vocab_first=0, recompute_mixer=False, router_bias_update_rate=1e-3)
WHOLE["published"] = {"num_hidden_layers": 52, "n_routed_experts": 8,
                      "vocab_size": 512}
SHARE = dict(WHOLE, n_routed_experts=2, held_first=2, vocab_size=128)
SEED = 2**31 + 40


def batch(sizes, rows=2, seq=128, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], (rows, seq + 1)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def _bare(w, layer):
    tail = f".{layer}"
    return {k[:-len(tail)]: v for k, v in w.items() if k.endswith(tail)}


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sizes", [WHOLE, SHARE], ids=["whole", "held_share"])
def test_logits_loss_and_every_leafs_gradient_agree(sizes):
    """Logits to 2e-6 (entries of about 0.03: the head is drawn at a tenth
    of the other matrices) and the loss to 1e-5 relative (float32 against
    float32 at HIGHEST: what is left is the order of the sums); every leaf's
    gradient to 2e-3 of its largest entry (the chunked scan's products are
    taken in another order than the token recurrence's, and a gradient is
    a difference of such sums). A bf16 operand anywhere moves these by
    1e-3 or more, and a planted fault by far more
    (``test_each_planted_fault_moves_the_reference``)."""
    _, model = prog.build_model(sizes)
    prog.seed_weights(model, sizes, SEED, "float32")
    w = weights.make(sizes, SEED, "float32")
    x, y = batch(sizes)
    out = model(paddle.Tensor(x))
    want = ref.logits(w, x, sizes)
    assert float(jnp.abs(out._value - want).max()) < 2e-6
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
    assert not any(weights.is_bias(k) for k in grads)
    for layer in (1, 3):  # the correction bias: a buffer, no gradient
        bias = model.model.layers[layer].experts.score_bias
        assert bias._value.dtype == jnp.float32 and bias.grad is None


def test_recomputed_mixers_give_the_same_step():
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


def test_the_compiled_step_leaves_its_events_and_no_scan_fallback():
    """A bf16 AMP-O2 step: every layer's event says what ran, the scan took
    its kernels over the 8 groups, the norm took one statistic a group,
    and the expert layers say which activation and router they ran."""
    _, model = prog.build_model(SHARE)
    prog.seed_weights(model, SHARE, SEED, "float32")
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    prog.seed_weights(model, SHARE, SEED, "bfloat16")
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = paddle.jit.compile_train_step(
        model, lambda lg, lb: crit(lg.astype("float32"), lb), opt)
    x, y = batch(SHARE, rows=3)  # a shape no other test has traced
    kinds = ("ssd_chunks", "moe_route", "mixer_pass", "mixer_share")
    seen = {k: len(trace.events(kind=k)) for k in kinds}
    fallbacks = dispatch_counters()["ssd_scan_fallbacks"]
    loss = float(step(paddle.Tensor(x), paddle.Tensor(y)))
    assert np.isfinite(loss)
    assert dispatch_counters()["ssd_scan_fallbacks"] == fallbacks
    new = {k: [e.attrs for e in trace.events(kind=k)[seen[k]:]]
           for k in kinds}
    assert new["ssd_chunks"] and all(
        e["path"] == "vmem" and e["groups"] == 8 and "why" not in e
        for e in new["ssd_chunks"])
    assert any(e.get("why") == "statistic_per_group"
               for e in new["mixer_pass"])
    assert new["moe_route"] and all(
        (e["act"], e["router"], e["routed_scale"], e["held"],
         e["num_experts"]) == ("relu2", "sigmoid", 2.5, 2, 8)
        for e in new["moe_route"])
    # the kept first product is [rows, d] float32 for relu^2
    assert all(e["saved_first_product_bytes"] == e["buffer_rows"] * 32 * 4
               for e in new["moe_route"])
    load = model.routed_load()
    assert [layer for layer, _, _ in load] == [1, 3]
    assert all(0 < routed <= rows for _, routed, rows in load)
    for layer in (1, 3):  # float32 still, and one balancing step away
        bias = model.model.layers[layer].experts.score_bias._value
        assert bias.dtype == jnp.float32
        start = weights.make(SHARE, SEED, "float32")[f"bias.{layer}"]
        steps = np.asarray(bias - start) / 1e-3
        assert set(np.round(steps, 3)) <= {-1.0, 0.0, 1.0}
        assert steps.min() == pytest.approx(-1) and steps.max() == \
            pytest.approx(1)


def test_amp_o2_leaves_the_correction_bias_float32():
    """``amp.decorate`` (level O2) casts every parameter and float buffer to
    bfloat16 but the correction bias, which the layer keeps in float32:
    values 0.01 apart are not rounded to bfloat16's steps."""
    _, model = prog.build_model(SHARE)
    prog.seed_weights(model, SHARE, SEED, "float32")
    want = {i: np.asarray(b._value) for i, b in
            prog.score_biases(model).items()}
    assert any(np.any(w != w.astype(jnp.bfloat16).astype(np.float32))
               for w in want.values())
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    assert all(p._value.dtype == jnp.bfloat16 for p in model.parameters())
    for i, bias in prog.score_biases(model).items():
        assert bias._value.dtype == jnp.float32
        assert np.array_equal(np.asarray(bias._value), want[i])
    layer = moe.DroplessExperts(8, 4, 4, 2, router="sigmoid").to(
        dtype="float16")
    assert layer.score_bias._value.dtype == jnp.float32
    assert layer.router._value.dtype == jnp.float16


def test_the_balancing_step_in_the_compiled_step_is_the_references():
    """One compiled float32 step moves each expert layer's correction bias
    by the rate toward even load over all router outputs, as the
    reference's written-out rule does from the same first batch; with no
    rate, or out of training mode, the bias stays where it was set."""
    sizes = dict(WHOLE, router_bias_update_rate=0.01)
    _, model = prog.build_model(sizes)
    prog.seed_weights(model, sizes, SEED, "float32")
    w = weights.make(sizes, SEED, "float32")
    x, y = batch(sizes)
    moved = {}
    ref.hidden(w, x, sizes, moved=moved)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = paddle.jit.compile_train_step(model, GPTPretrainingCriterion(),
                                         opt)
    step(paddle.Tensor(x), paddle.Tensor(y))
    for i, bias in prog.score_biases(model).items():
        got = np.asarray(bias._value)
        assert np.array_equal(got, np.asarray(moved[f"bias.{i}"]))
        steps = np.round((got - np.asarray(w[f"bias.{i}"])) / 0.01)
        assert {-1.0, 1.0} <= set(steps) <= {-1.0, 0.0, 1.0}
    for rate, training in ((0.0, True), (0.01, False)):
        _, model = prog.build_model(dict(sizes, router_bias_update_rate=rate))
        prog.seed_weights(model, sizes, SEED, "float32")
        if not training:
            model.eval()
        model(paddle.Tensor(x))
        for i, bias in prog.score_biases(model).items():
            assert np.array_equal(np.asarray(bias._value),
                                  np.asarray(w[f"bias.{i}"]))


def test_the_balancing_rule_counts_every_router_output():
    """Loads 3, 1, 2, 2 of 8 slots: the first expert's bias goes down, the
    second's up, the even ones stay; the slots of experts this chip does
    not hold count as much as its own."""
    idx = jnp.asarray([[0, 1], [0, 2], [0, 3], [2, 3]], jnp.int32)
    load = moe.expert_load(idx, 4)
    assert load.tolist() == [3, 1, 2, 2]
    bias = jnp.asarray([0.5, 0.25, 0.0, -0.25], jnp.float32)
    got = moe.balance_score_bias(bias, load, 0.125)
    assert got.tolist() == [0.375, 0.375, 0.0, -0.25]
    assert got.dtype == jnp.float32
    want = ref.moved_bias(bias, jnp.eye(4), jnp.asarray(
        [[9.0, 8, 0, 0], [9, 0, 8, 0], [9, 0, 0, 8], [0, 0, 9, 8]]),
        dict(WHOLE, num_experts_per_tok=2, router_bias_update_rate=0.125),
        ref.exact_operands, "bias_ignored")
    assert want.tolist() == got.tolist()


def test_export_and_parameter_names():
    import paddle_tpu.models as models

    assert models.NemotronHForCausalLM is prog.NemotronHForCausalLM
    assert {"NemotronHConfig", "NemotronHModel"} <= set(dir(models))
    cfg = models.NemotronHConfig()
    assert (cfg.kind(0), cfg.kind(1), cfg.kind(5)) == (
        "mamba", "experts", "attention")
    _, model = prog.build_model(SHARE)
    names = [n for n, _ in model.named_parameters()]
    assert names[:2] == ["model.embed_tokens.weight",
                         "model.layers.0.norm.weight"]
    assert "lm_head.weight" in names
    assert {n.split(".", 3)[3] for n in names if ".layers.1." in n} == {
        "norm.weight", "experts.router", "experts.w_up", "experts.w_down",
        "experts.shared_up", "experts.shared_down"}
    assert {n.split(".", 3)[3] for n in names if ".layers.2." in n} == {
        "norm.weight", "mixer.q_proj.weight", "mixer.k_proj.weight",
        "mixer.v_proj.weight", "mixer.o_proj.weight"}
    experts = model.model.layers[1].experts
    assert tuple(experts.w_up.shape) == (2, 64, 32)
    assert tuple(experts.shared_up.shape) == (64, 48)
    assert tuple(model.lm_head.weight.shape) == (64, 128)
    assert sorted(n for n, _ in model.named_buffers()) == sorted(
        f"model.layers.{i}.experts.{b}" for i in (1, 3)
        for b in ("score_bias", "routed_slots", "expert_rows"))
    with pytest.raises(ValueError):
        moe.DroplessExperts(64, 32, 8, 2, act="gelu")
    with pytest.raises(ValueError):  # no routed scale under softmax
        moe.DroplessExperts(64, 32, 8, 2, routed_scale=2.5)
    with pytest.raises(ValueError):  # nor a bias to balance
        moe.DroplessExperts(64, 32, 8, 2, bias_update_rate=1e-3)
    with pytest.raises(ValueError):
        moe.DroplessExperts(64, 32, 8, 2, router="topk")
    x = jnp.ones((8, 64), jnp.float32)
    with pytest.raises(ValueError):  # a sigmoid router takes a bias
        moe.dropless_experts(
            x, experts.router._value, experts.w_up._value,
            experts.w_down._value, None, None, None, first=0, top_k=2,
            renormalize=True, rows=8, act="relu2", router_kind="sigmoid")


# ---------------------------------------------------------------------------
# the expert layer: relu^2 experts under the biased sigmoid router
# ---------------------------------------------------------------------------
def test_the_bias_changes_a_choice_and_the_weights_are_the_unbiased_scores():
    """Four experts, two a token. By s alone the token takes experts 0 and
    1; the bias pulls 1 below 2, so it takes 0 and 2, weighted by their
    UNBIASED scores, renormalised and times 2.5; the bias gets no
    gradient. The reference's equations give the same weights."""
    logits = jnp.asarray([[2.0, 1.8, 1.7, -1.0]], jnp.float32)
    bias = jnp.asarray([0.0, -0.2, 0.0, 0.0], jnp.float32)
    s = jax.nn.sigmoid(logits[0])
    w, idx = moe.route_sigmoid_top_k(logits, 2, True, bias, 2.5)
    assert idx.tolist() == [[0, 2]]
    want = 2.5 * jnp.asarray([s[0], s[2]]) / (s[0] + s[2])
    assert float(jnp.abs(w[0] - want).max()) < 1e-6
    _, unbiased = moe.route_sigmoid_top_k(logits, 2, True, 0.0 * bias, 2.5)
    assert unbiased.tolist() == [[0, 1]]
    grad = jax.grad(lambda b: moe.route_sigmoid_top_k(
        logits, 2, True, b, 2.5)[0].sum())(bias)
    assert float(jnp.abs(grad).max()) == 0.0
    # the reference, with an identity router over the four logits
    sizes = dict(WHOLE, num_experts_per_tok=2)
    held = ref.held_weights(logits, jnp.eye(4), bias, sizes, (0, 4),
                            ref.exact_operands, None)
    assert float(jnp.abs(held[0] - jnp.asarray(
        [want[0], 0.0, want[1], 0.0])).max()) < 1e-6
    blind = ref.held_weights(logits, jnp.eye(4), bias, sizes, (0, 4),
                             ref.exact_operands, "bias_ignored")
    assert float(blind[0, 1]) > 0.0 and float(blind[0, 2]) == 0.0


@pytest.mark.parametrize("held", [(0, 8), (2, 3)], ids=["all", "a_share"])
def test_relu2_experts_match_the_reference(held):
    """The layer's output and the gradients of its tokens, router, stacks and
    shared expert against the reference's masks, with a correction bias
    that changes choices (forward to 1e-5 of the largest entry, gradients
    to 1e-4: float32 both sides, sums in other orders)."""
    sizes = dict(WHOLE, num_experts_per_tok=3)
    w = _bare(weights.make(sizes, SEED + 1, "float32"), 1)
    w["bias"] = w["bias"] * 30.0  # choices that the bias moves
    first, count = held
    mine = {k: w[k][first:first + count] if k in ref.STACKED else w[k]
            for k in w}
    x = jax.random.normal(jax.random.PRNGKey(5), (256, 64), jnp.float32)
    rows = moe.row_buffer_rows(256, 3, 8, count)

    def layer(x, router, eu, ed, su, sd):
        return moe.dropless_experts(
            x, router, eu, ed, su, sd, None, mine["bias"], first=first,
            top_k=3, renormalize=True, rows=rows, act="relu2",
            router_kind="sigmoid", routed_scale=2.5)[0]

    def plain(x, router, eu, ed, su, sd):
        return ref.experts(x, dict(mine, router=router, eu_w=eu, ed_w=ed,
                                   su_w=su, sd_w=sd), sizes, held=held)

    args = (x, mine["router"], mine["eu_w"], mine["ed_w"], mine["su_w"],
            mine["sd_w"])
    got, want = layer(*args), plain(*args)
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())
    ct = jax.random.normal(jax.random.PRNGKey(6), got.shape, jnp.float32)
    grads = jax.grad(lambda *a: (layer(*a) * ct).sum(),
                     argnums=tuple(range(6)))(*args)
    wants = jax.grad(lambda *a: (plain(*a) * ct).sum(),
                     argnums=tuple(range(6)))(*args)
    for name, g, want in zip(("x", "router", "w_up", "w_down", "shared_up",
                              "shared_down"), grads, wants):
        assert float(jnp.abs(g - want).max()) < 1e-4 * float(
            jnp.abs(want).max()), name
    # the bias changed some choices here
    plain_choice = ref.held_weights(x, mine["router"], mine["bias"], sizes,
                                    held, ref.exact_operands, "bias_ignored")
    biased = ref.held_weights(x, mine["router"], mine["bias"], sizes, held,
                              ref.exact_operands, None)
    assert bool(jnp.any((plain_choice > 0) != (biased > 0)))


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """Four chips of 2 of the 8 experts each: their parts, the shared expert
    counted once, add up to the uncut reference's layer."""
    w = _bare(weights.make(WHOLE, SEED, "float32"), 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (128, 64), jnp.float32)
    want = ref.experts(x, w, WHOLE)
    parts = [ref.experts(x, dict(w, eu_w=w["eu_w"][first:first + 2],
                                 ed_w=w["ed_w"][first:first + 2]),
                         dict(WHOLE, n_routed_experts=2), held=(first, 2),
                         shared=first == 0) for first in (0, 2, 4, 6)]
    assert float(jnp.abs(sum(parts) - want).max()) < 1e-5 * float(
        jnp.abs(want).max()) + 1e-7


# ---------------------------------------------------------------------------
# the gated norm, one statistic a group
# ---------------------------------------------------------------------------
def test_the_per_group_norm_is_its_equation():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    o = jax.random.normal(ks[0], (2, 16, 512), jnp.float32)
    z = jax.random.normal(ks[1], (2, 16, 512 + 256), jnp.float32)
    gain = 1.0 + 0.1 * jax.random.normal(ks[2], (512,), jnp.float32)
    before = len(trace.events(kind="mixer_pass"))
    got = la.gated_rms_norm(o, z, gain, epsilon=1e-5, gate_first=True,
                            group=128)
    (event,) = trace.events(kind="mixer_pass")[before:]
    assert event.attrs["why"] == "statistic_per_group"
    g = o * jax.nn.silu(z[..., 256:])
    runs = g.reshape(2, 16, 4, 128)
    want = (runs / jnp.sqrt(jnp.mean(runs ** 2, -1, keepdims=True) + 1e-5)
            ).reshape(g.shape) * gain
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(got - ref.gated_norm(o, z[..., 256:], gain, 128,
                                              1e-5)).max()) < 1e-5
    whole = la.gated_rms_norm(o, z, gain, epsilon=1e-5, gate_first=True)
    assert float(jnp.abs(whole - ref.gated_norm(o, z[..., 256:], gain, 512,
                                                1e-5)).max()) < 1e-5
    assert float(jnp.abs(whole - got).max()) > 1e-3
    with pytest.raises(ValueError):
        la.gated_rms_norm(o, z, gain, gate_first=True, group=100)


# ---------------------------------------------------------------------------
# the reference's planted faults, the counts
# ---------------------------------------------------------------------------
def test_each_planted_fault_moves_the_reference():
    """Each of ``FAULTS`` moves the loss or a gradient by far more than the
    program's agreement with the reference (2e-3 of a leaf's largest entry,
    ``test_logits_loss_and_every_leafs_gradient_agree``)."""
    w = weights.make(WHOLE, SEED, "float32")
    x, y = batch(WHOLE)
    loss, grads = ref.loss_and_grads(w, x, y, WHOLE)
    for fault in ref.FAULTS:
        bad_loss, bad = ref.loss_and_grads(w, x, y, WHOLE, fault=fault)
        moved = max(float(jnp.abs(bad[k] - g).max() / jnp.abs(g).max())
                    for k, g in grads.items())
        assert moved > 2e-2 or abs(float(bad_loss - loss)) > 1e-3, fault


def _bf16_operands(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def test_bf16_operands_fail_the_tolerances():
    """The reference with bf16 operands where float32 is stated misses the
    logits' 2e-6 and a leaf's 2e-3 of its largest gradient entry by ten
    times or more: the tolerances of
    ``test_logits_loss_and_every_leafs_gradient_agree`` see the precision."""
    w = weights.make(WHOLE, SEED, "float32")
    x, y = batch(WHOLE)
    exact = ref.logits(w, x, WHOLE)
    rounded = ref.logits(w, x, WHOLE, _bf16_operands)
    assert float(jnp.abs(rounded - exact).max()) > 10 * 2e-6
    _, grads = ref.loss_and_grads(w, x, y, WHOLE)
    _, bad = ref.loss_and_grads(w, x, y, WHOLE, _bf16_operands)
    assert max(float(jnp.abs(bad[k] - g).max() / jnp.abs(g).max())
               for k, g in grads.items()) > 10 * 2e-3


def test_counts_at_the_cell_sizes():
    import json
    import os

    here = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    with open(os.path.join(here, "configs",
                           "nemotron-3-nano-30b-a3b-ep16.json")) as f:
        s = json.load(f)
    assert counts.layer_kinds(s) == (4, 4, 1)
    h = 2688
    mamba = h * (6144 + 4096) + h * 64 + 4096 * h              # 38.70M
    attention = h * 4096 + 2 * h * 256 + 4096 * h              # 23.40M
    shared = 2 * h * 3712 + h * 128                             # 20.30M
    expert = 2 * h * 1856                                       # 9.98M
    small = 6144 * 5 + 3 * 64 + 4096
    # the AOT compile's leaves (tests/test_tpu_compile.py) count the same
    assert counts.n_params(s) == (
        2 * 16384 * h + h + 9 * h + 4 * (mamba + small) + attention
        + 4 * (shared + 8 * expert)) == 666_962_944
    scan = 64 * 5 * 64 * 128
    attn = 4 * 32 * 128 * 8193 / 2
    want = 6 * (4 * mamba + attention + 4 * shared + h * 16384
                + 4 * 6144 * 4) + 6 * 4 * 0.375 * expert + 3 * 4 * scan \
        + 3 * attn
    got = counts.train_flops_per_token(s, 8192, 0.375)
    assert got == pytest.approx(want, rel=1e-12)
    assert 2.1e9 < got < 2.2e9
    pk = peaks.peaks_for("TPU v5 lite")
    tokens = 2 * 8192
    x, bc = 4096 * 2, 2 * 1024 * 2
    fwd = max(tokens * scan / 197e12, tokens * (2 * x + bc + 256) / 819e9)
    bwd = max(2 * tokens * scan / 197e12,
              tokens * (3 * x + 2 * bc + 512) / 819e9)
    assert counts.scan_roofline(s, 2, 8192, pk) == pytest.approx(fwd + bwd)
    rows = 768 * (2 * h + 2 * 1856) * 2
    w_bytes = 8 * expert * 2
    fwd = max(2 * 768 * expert / 197e12, (w_bytes + rows) / 819e9)
    bwd = max(4 * 768 * expert / 197e12, (2 * w_bytes + 2 * rows) / 819e9)
    assert counts.expert_roofline(s, 768, pk) == pytest.approx(fwd + bwd)
