"""The one train-step program (paddle_tpu/jit/step.py) under each of its
front ends: every compiled builder traces the same closure, carries its
scopes, and takes one step to where ``compile_train_step`` on one device
takes it; ``make_step_fn``'s update is the eager optimizer's; its options
(gradient merge, loss scaling, input gradients) are the plain step's math.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import parallel
from paddle_tpu.core import random as _random
from paddle_tpu.distributed import fleet
from paddle_tpu.incubate import asp
from paddle_tpu.jit import step as step_core
from paddle_tpu.models import GPTConfig, GPTForPretraining, GPTPretrainingCriterion
from paddle_tpu.parallel import topology

M = 4  # microbatches of the pipelined step
VOCAB, HID, LAYERS, HEADS, SEQ = 128, 32, 4, 4, 16


@pytest.fixture(autouse=True)
def _no_mesh_left_behind():
    topology.set_mesh(None)
    yield
    topology.set_mesh(None)


# ---------------------------------------------------------------------------
# (a) one step of every builder against compile_train_step on one device
# ---------------------------------------------------------------------------
def _gpt(seed=7):
    """The trainer tests/test_pipeline.py builds, with a global-norm clip."""
    paddle.seed(seed)
    cfg = GPTConfig(
        vocab_size=VOCAB, hidden_size=HID, num_layers=LAYERS, num_heads=HEADS,
        max_seq_len=SEQ * 2, dropout=0.0, attn_dropout=0.0,
    )
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters(), weight_decay=0.01,
        grad_clip=nn.ClipGradByGlobalNorm(1.0),
    )
    return model, GPTPretrainingCriterion(cfg), opt


def _gpt_batch():
    ids = np.random.default_rng(0).integers(0, VOCAB, (8, SEQ + 1))
    return (paddle.to_tensor(ids[:, :-1].astype(np.int32)),
            paddle.to_tensor(ids[:, 1:].astype(np.int64)))


def _build_compiled():
    model, crit, opt = _gpt()
    return model, paddle.jit.compile_train_step(model, crit, opt)


def _build_compiled_mesh():
    mesh = topology.init_mesh(dp=2, mp=2)
    model, crit, opt = _gpt()
    parallel.shard_params(model, mesh)
    return model, paddle.jit.compile_train_step(
        model, crit, opt, mesh=mesh, in_shardings=[P("dp"), P("dp")])


def _build_sharded(zero_stage, **degrees):
    mesh = topology.init_mesh(**degrees)
    model, crit, opt = _gpt()
    parallel.shard_params(model, mesh, zero_stage=zero_stage)
    return model, parallel.sharded_train_step(
        model, crit, opt, mesh=mesh, zero_stage=zero_stage)


def _build_pipelined():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1, "pp_degree": 4}
    strategy.pipeline_configs = {"accumulate_steps": M}
    fleet.init(is_collective=True, strategy=strategy)
    model, crit, opt = _gpt()
    step = fleet.distributed_train_step(fleet.distributed_model(model), crit,
                                        opt)
    return model, step


BUILDERS = {
    "compile_train_step": _build_compiled,
    "compile_train_step_mesh": _build_compiled_mesh,
    "sharded_zero0": lambda: _build_sharded(0, dp=2, mp=2),
    # dp x sharding with ZeRO: the front end's gradient pin is on
    "sharded_zero1": lambda: _build_sharded(1, dp=2, sharding=2),
    "pipelined": _build_pipelined,
}


def _scope_names(step, x, y):
    """The op names of the built step's lowering (no compile, nothing runs)."""
    if isinstance(step, paddle.jit.CompiledTrainStep):
        lowered = step._step.lower(*step._arg_specs)
    elif hasattr(step, "_shardings"):
        batch_sh = step._shardings()[3]
        lowered = step._step.lower(
            tuple(p._value for p in step._params), tuple(step._opt_state),
            tuple(b._value for b in step._buffers), jax.random.PRNGKey(0),
            jnp.asarray(1e-3, jnp.float32),
            *(jax.device_put(t._value, batch_sh) for t in (x, y)))
    else:
        lowered = step._step.lower(
            tuple(p._value for p in step._repl_params), tuple(step._stacked),
            tuple(step._repl_state), tuple(step._stacked_state), (),
            jax.random.PRNGKey(0), jnp.asarray(1e-3, jnp.float32),
            *(jax.device_put(t._value, NamedSharding(
                step.mesh, P(("dp", "sharding")))) for t in (x, y)))
    text = lowered.as_text(debug_info=True)
    return "\n".join(line.split('"')[1] for line in text.splitlines()
                     if line.startswith("#loc") and '"' in line)


@pytest.fixture(scope="module")
def one_device_step():
    """Loss and updated parameters of one compile_train_step step on one
    device, from the seed every builder's case starts from."""
    topology.set_mesh(None)
    model, step = _build_compiled()
    x, y = _gpt_batch()
    loss = float(step(x, y))
    return loss, {n: np.asarray(p._value) for n, p in model.named_parameters()}


@pytest.mark.parametrize("builder", list(BUILDERS))
def test_builder_carries_the_scopes_and_matches_one_device(builder,
                                                           one_device_step):
    want_loss, want_params = one_device_step
    model, step = BUILDERS[builder]()
    x, y = _gpt_batch()
    loss = float(step(x, y))
    names = _scope_names(step, x, y)
    assert "optimizer/" in names and "grad_clip/" in names
    if builder != "pipelined":  # its staged loss is its own program
        assert "jvp(forward)" in names and "jvp(loss)" in names
        assert "transpose(jvp(forward))" in names
    # the tolerance tests/test_pipeline.py::test_pp4_matches_single_device
    # holds a mesh step to
    np.testing.assert_allclose(loss, want_loss, rtol=3e-4)
    if builder == "pipelined":
        step.sync_params()
    for n, p in model.named_parameters():
        # AdamW's first step moves every entry by lr * g / (|g| + eps): an
        # entry whose gradient is rounding noise may land anywhere within lr
        np.testing.assert_allclose(np.asarray(p._value), want_params[n],
                                   rtol=3e-4, atol=2.5e-3, err_msg=n)
        moved = np.abs(np.asarray(p._value) - want_params[n]) > 1e-5
        assert moved.mean() < 0.01, (n, moved.mean())


# ---------------------------------------------------------------------------
# (b) make_step_fn's update is the eager optimizer.step()'s
# ---------------------------------------------------------------------------
def _mlp(seed=3):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    for n, p in net.named_parameters():
        p.name = n  # "0.weight", "0.bias": what apply_decay_param_fun reads
    return net


def _mse(out, y):
    return ((out - y) ** 2).mean()


def _mlp_batch(n=8):
    rng = np.random.default_rng(1)
    return (paddle.to_tensor(rng.standard_normal((n, 8)).astype(np.float32)),
            paddle.to_tensor(rng.standard_normal((n, 4)).astype(np.float32)))


OPTIMIZERS = {
    "sgd": lambda ps: paddle.optimizer.SGD(
        learning_rate=0.1, parameters=ps, weight_decay=0.01),
    "momentum": lambda ps: paddle.optimizer.Momentum(
        learning_rate=0.1, momentum=0.9, parameters=ps, use_nesterov=True),
    "adamw_no_decay_on_bias": lambda ps: paddle.optimizer.AdamW(
        learning_rate=1e-2, parameters=ps, weight_decay=0.1,
        apply_decay_param_fun=lambda name: "bias" not in (name or "")),
}


def _run_step_fn(model, opt, batch, **options):
    """One step through make_loss_core / make_step_fn alone (no front end):
    (loss, in_grads, new_p, new_s)."""
    params = [p for p in model.parameters() if not p.stop_gradient]
    buffers = [b for _, b in model.named_buffers()]
    core = step_core.make_loss_core(
        model, _mse, params, buffers,
        grad_input_idx=options.get("grad_input_idx", ()))
    fn = jax.jit(step_core.make_step_fn(core, opt, params, **options))
    loss, in_grads, new_p, new_s, _ = fn(
        tuple(p._value for p in params),
        tuple(step_core.init_opt_state(opt, params)),
        tuple(b._value for b in buffers), _random.next_key(),
        jnp.asarray(opt.get_lr(), jnp.float32), *(t._value for t in batch))
    return loss, in_grads, new_p, new_s


@pytest.mark.parametrize("name", list(OPTIMIZERS))
def test_step_fn_update_equals_eager_step(name):
    batch = _mlp_batch()
    eager = _mlp()
    opt_e = OPTIMIZERS[name](eager.parameters())
    _mse(eager(batch[0]), batch[1]).backward()
    opt_e.step()

    traced = _mlp()
    opt_t = OPTIMIZERS[name](traced.parameters())
    if name.startswith("adamw"):  # the case must see both decays
        decays = {opt_t._per_param_hyper(p).get("wd", opt_t._wd_coeff)
                  for p in traced.parameters()}
        assert decays == {0.0, 0.1}
    _, _, new_p, new_s = _run_step_fn(traced, opt_t, batch)
    for p, got, st in zip(eager.parameters(), new_p, new_s):
        np.testing.assert_allclose(np.asarray(got), np.asarray(p._value),
                                   rtol=1e-5, atol=1e-7)
        want = opt_e._accumulators.get(id(p), {})
        assert sorted(st) == sorted(want)
        for k in st:
            np.testing.assert_allclose(np.asarray(st[k]), np.asarray(want[k]),
                                       rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# (c) the options of make_step_fn against the plain step on the same batch
# ---------------------------------------------------------------------------
def _momentum(model):
    return paddle.optimizer.Momentum(
        learning_rate=0.1, momentum=0.9, parameters=model.parameters(),
        grad_clip=nn.ClipGradByGlobalNorm(0.5))


@pytest.mark.parametrize("options", [
    {"accumulate_steps": 2}, {"loss_scale": 128.0}, {"grad_input_idx": (0,)},
], ids=lambda o: next(iter(o)))
def test_step_fn_options_match_the_plain_step(options):
    batch = _mlp_batch()
    plain = _mlp()
    loss0, no_grads, p0, s0 = _run_step_fn(plain, _momentum(plain), batch)
    assert no_grads == ()
    model = _mlp()
    loss, in_grads, new_p, new_s = _run_step_fn(model, _momentum(model),
                                                batch, **options)
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-5)
    for got, want in zip(new_p, p0):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    for got, want in zip(new_s, s0):
        np.testing.assert_allclose(np.asarray(got["velocity"]),
                                   np.asarray(want["velocity"]),
                                   rtol=1e-5, atol=1e-6)
    if "grad_input_idx" in options:
        fresh = _mlp()
        x = paddle.to_tensor(batch[0].numpy(), stop_gradient=False)
        _mse(fresh(x), batch[1]).backward()
        (g,) = in_grads
        np.testing.assert_allclose(np.asarray(g), x.grad.numpy(),
                                   rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("where", ["make_step_fn", "sharded_train_step"])
def test_input_grads_are_refused_under_gradient_merge(where):
    model = _mlp()
    params = list(model.parameters())
    with pytest.raises(ValueError, match="gradient merge"):
        if where == "make_step_fn":
            core = step_core.make_loss_core(model, _mse, params, [],
                                            grad_input_idx=(0,))
            step_core.make_step_fn(core, _momentum(model), params,
                                   grad_input_idx=(0,), accumulate_steps=2)
        else:  # the front end refuses when it is built, not at its first call
            parallel.sharded_train_step(
                model, _mse, _momentum(model), mesh=topology.init_mesh(dp=2),
                grad_input_idx=(0,), accumulate_steps=2)


# ---------------------------------------------------------------------------
# (d) ASP masks hold under the sharded front end
# ---------------------------------------------------------------------------
def test_asp_pruned_layers_stay_sparse_under_sharded_train_step():
    mesh = topology.init_mesh(dp=2)
    paddle.seed(3)
    asp.reset_asp_state()
    try:
        net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
        asp.prune_model(net)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        step = parallel.sharded_train_step(net, _mse, opt, mesh=mesh)
        x, y = _mlp_batch(4)
        before = [np.asarray(l.weight._value) for l in net
                  if isinstance(l, nn.Linear)]
        for _ in range(3):
            float(step(x, y))
        linears = [l for l in net if isinstance(l, nn.Linear)]
        for layer, w0 in zip(linears, before):
            assert asp.check_sparsity(layer.weight)
            assert not np.array_equal(np.asarray(layer.weight._value), w0)
    finally:
        asp.reset_asp_state()
