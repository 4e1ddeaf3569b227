"""paddle_tpu.analysis — graph verifier & lint-pass framework.

Fixture programs with deliberately injected defects, one per pass:
dtype mismatch (silent f64 upcast, bf16/f32 mixing), dead op / unused
feed / unused parameter, redundant pairs (transpose∘transpose, x*1,
broadcast-then-reduce, log∘softmax), numeric hazards (unguarded log/div,
fp16 long-axis sum), and the launch-budget counter audit. Plus the
FLAGS_check_programs enforcement hooks (Executor compile time,
lazy-segment flush) and the satellite fixes that ride along this PR
(Program.clone sharing, _flat_eqns control-flow recursion, flags
parsing/describe_flags).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import analysis, nn, static
from paddle_tpu.analysis import Diagnostic, ProgramVerificationError, Severity
from paddle_tpu.core import flags as core_flags
from paddle_tpu.core import lazy


def hits(diags, pass_name, severity=None, needle=None):
    out = [d for d in diags if d.pass_name == pass_name]
    if severity is not None:
        out = [d for d in out if d.severity == severity]
    if needle is not None:
        out = [d for d in out
               if needle in d.message or needle in d.op or needle in d.hint]
    return out


# ---------------------------------------------------------------------------
# pass 1: shape/dtype verifier
# ---------------------------------------------------------------------------
def test_dtype_pass_flags_silent_float64_upcast():
    def f(x):
        return jnp.asarray(x, jnp.float64) * 2.0  # injected f32 -> f64

    with jax.enable_x64(True):
        diags = analysis.check(f, [((4,), "float32")])
    found = hits(diags, "dtype_check", Severity.ERROR, "float64")
    assert found, diags
    assert found[0].severity == Severity.ERROR
    assert "float64" in str(found[0])


def test_dtype_pass_ignores_rng_double_trick():
    # dropout's uniform derives f64 from integer bits — framework lowering,
    # not a user upcast; the example models must lint f64-clean
    m = nn.Dropout(0.5)
    diags = analysis.check(m, [((8, 8), "float32")])
    assert not hits(diags, "dtype_check", Severity.ERROR), diags


def test_dtype_pass_flags_bf16_f32_mixing():
    def f(x, w):
        a = paddle.matmul(x, w)  # f32 matmul
        b = paddle.matmul(x.astype("bfloat16"), w.astype("bfloat16"))
        return a.sum() + b.astype("float32").sum()

    diags = analysis.check(f, [((4, 8), "float32"), ((8, 4), "float32")])
    found = hits(diags, "dtype_check", Severity.WARNING, "mixed-precision")
    assert found, diags


def test_dtype_pass_flags_feed_declared_wrong_width():
    def f(x):
        return x.astype("bfloat16").sum()

    diags = analysis.check(f, [((4,), "float32")])
    assert hits(diags, "dtype_check", Severity.WARNING, "casts"), diags


# ---------------------------------------------------------------------------
# pass 2: dead code / unused feeds / unused parameters
# ---------------------------------------------------------------------------
def test_dead_op_and_unused_feed_detected_on_program():
    prog = static.Program()
    with static.program_guard(prog):
        static.data("x", [4, 8], "float32")
        static.data("unused", [4], "float32")

    def builder(feed):
        h = static.nn.fc(feed["x"], 16, name="fc_da")
        _dead = feed["x"] * 2.0  # injected dead op
        return h.sum()

    prog.set_builder(builder)
    diags = paddle.static.analysis.check(prog)
    assert hits(diags, "dead_code", Severity.WARNING, "dead op"), diags
    assert hits(diags, "dead_code", Severity.WARNING, "unused feed"), diags


def test_unused_parameter_detected_on_layer():
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.used = nn.Linear(8, 4)
            self.orphan = nn.Linear(8, 4)  # never called

        def forward(self, x):
            return self.used(x)

    diags = analysis.check(Net(), [((2, 8), "float32")])
    found = hits(diags, "dead_code", Severity.WARNING, "unused parameter")
    assert any("orphan" in d.op for d in found), diags


def test_clean_program_is_quiet():
    prog = static.Program()
    with static.program_guard(prog):
        static.data("x", [4, 8], "float32")
    prog.set_builder(lambda feed: static.nn.fc(feed["x"], 16, name="fc_cq").sum())
    assert paddle.static.analysis.check(prog) == []


# ---------------------------------------------------------------------------
# pass 3: redundant-op patterns
# ---------------------------------------------------------------------------
def test_redundant_pair_and_identity_arith_detected():
    def f(x):
        y = paddle.transpose(paddle.transpose(x, [1, 0]), [1, 0])
        return y * 1.0 + 0.0

    diags = analysis.check(f, [((3, 4), "float32")])
    pair = hits(diags, "redundant_ops", Severity.WARNING, "transpose∘transpose")
    assert pair, diags
    assert hits(diags, "redundant_ops", Severity.WARNING, "x*1"), diags
    assert hits(diags, "redundant_ops", Severity.WARNING, "x+0"), diags


def test_broadcast_then_reduce_detected():
    def f(x):
        big = paddle.expand(x.reshape([1, 4]), [512, 4])
        return big.sum(axis=0)

    diags = analysis.check(f, [((4,), "float32")])
    assert hits(diags, "redundant_ops", Severity.WARNING,
                "broadcast-then-reduce"), diags


def test_log_softmax_pattern_detected():
    def f(x):
        return paddle.log(F.softmax(x, axis=-1))

    diags = analysis.check(f, [((2, 5), "float32")])
    assert hits(diags, "redundant_ops", Severity.WARNING, "log_softmax"), diags


# ---------------------------------------------------------------------------
# pass 4: numerical hazards
# ---------------------------------------------------------------------------
def test_unguarded_log_is_error_guarded_is_quiet():
    diags = analysis.check(lambda x: paddle.log(x), [((4,), "float32")])
    found = hits(diags, "numeric_hazards", Severity.ERROR, "unguarded log")
    assert found, diags

    def guarded(x):
        return paddle.log(paddle.clip(x, min=1e-6))

    assert not hits(analysis.check(guarded, [((4,), "float32")]),
                    "numeric_hazards")


def test_unguarded_div_warned_epsilon_div_quiet():
    def bad(x, d):
        return x / d

    diags = analysis.check(bad, [((4,), "float32"), ((4,), "float32")])
    assert hits(diags, "numeric_hazards", Severity.WARNING, "division"), diags

    def good(x, d):
        return x / (paddle.abs(d) + 1e-6)

    assert not hits(
        analysis.check(good, [((4,), "float32"), ((4,), "float32")]),
        "numeric_hazards",
    )


def test_batchnorm_style_rsqrt_div_is_quiet():
    m = nn.BatchNorm2D(3)
    diags = analysis.check(m, [((2, 3, 4, 4), "float32")])
    assert not hits(diags, "numeric_hazards"), diags


def test_fp16_long_axis_reduction_warned():
    def f(x):
        # cumsum keeps the f16 accumulator (jnp.sum silently upcasts halves
        # to f32 — which is exactly the fix this lint teaches)
        return jnp.cumsum(jnp.asarray(x, jnp.float16))

    diags = analysis.check(f, [((4096,), "float32")])
    found = hits(diags, "numeric_hazards", Severity.WARNING, "float16")
    assert found and "4096" in found[0].message, diags


# ---------------------------------------------------------------------------
# pass 5: launch budget (reuses the PR 1 dispatch counters)
# ---------------------------------------------------------------------------
def test_launch_budget_over_and_under():
    over = analysis.check_launch_budget(
        counters={"programs": 13, "op_programs": 11, "backward_programs": 1,
                  "optimizer_programs": 1},
        budget=3,
    )
    assert hits(over, "launch_budget", Severity.WARNING, "13"), over
    assert analysis.check_launch_budget(counters={"programs": 3}, budget=3) == []


def test_launch_budget_measures_live_step():
    model = paddle.vision.models.LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((2, 1, 28, 28)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, (2,)))

    def step():
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return float(loss)

    diags = analysis.check_launch_budget(step, budget=3)
    # per-op dispatch blows the 3-program budget (~13 programs)
    assert hits(diags, "launch_budget", Severity.WARNING), diags


# ---------------------------------------------------------------------------
# acceptance: every injected defect class, one program, correct severities
# ---------------------------------------------------------------------------
def test_fixture_suite_flags_all_injected_defects():
    def broken(x):
        t = paddle.transpose(paddle.transpose(x, [1, 0]), [1, 0])  # redundant
        _dead = x * 3.0                                            # dead op
        return paddle.log(t).sum()                                 # hazard

    diags = analysis.check(broken, [((3, 4), "float32")])
    assert hits(diags, "numeric_hazards", Severity.ERROR, "unguarded log")
    assert hits(diags, "dead_code", Severity.WARNING, "dead op")
    assert hits(diags, "redundant_ops", Severity.WARNING,
                "transpose∘transpose")
    # sorted most-severe first; records carry op path + structured fields
    assert diags[0].severity == Severity.ERROR
    assert diags == sorted(diags, key=lambda d: -int(d.severity))
    assert all(d.op and d.message for d in diags)


# ---------------------------------------------------------------------------
# FLAGS_check_programs enforcement hooks
# ---------------------------------------------------------------------------
@pytest.fixture
def check_flag():
    def setter(level):
        paddle.set_flags({"FLAGS_check_programs": level})

    try:
        yield setter
    finally:
        paddle.set_flags({"FLAGS_check_programs": 0})


def _log_program():
    prog = static.Program()
    with static.program_guard(prog):
        static.data("x", [4], "float32")
    prog.set_builder(lambda feed: paddle.log(feed["x"]).sum())
    return prog


def test_executor_warns_then_raises_per_flag_level(check_flag):
    feed = {"x": np.full(4, 2.0, np.float32)}
    exe = static.Executor()

    check_flag(1)
    prog = _log_program()
    exe.run(prog, feed=feed)  # first run warms eagerly, no compile yet
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        exe.run(prog, feed=feed)  # compile time -> verifier
    assert any("unguarded log" in str(w.message) for w in seen), [
        str(w.message) for w in seen
    ]

    check_flag(2)
    prog2 = _log_program()
    exe.run(prog2, feed=feed)
    with pytest.raises(ProgramVerificationError) as ei:
        exe.run(prog2, feed=feed)
    assert any(d.severity == Severity.ERROR for d in ei.value.diagnostics)


@pytest.fixture
def lazy_mode():
    paddle.set_flags({"FLAGS_eager_lazy_dispatch": True})
    try:
        yield
    finally:
        lazy.flush_if_pending("test_teardown")
        paddle.set_flags({"FLAGS_eager_lazy_dispatch": False})


def test_lazy_flush_warns_and_raises_per_flag_level(lazy_mode, check_flag):
    check_flag(1)
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        x = paddle.to_tensor(np.ones(4, np.float32))
        float((x * 1.0).sum())  # x*1 -> warning at segment flush
    assert any("x*1" in str(w.message) for w in seen)

    check_flag(2)
    y = paddle.log(paddle.to_tensor(np.full(3, 2.0, np.float32)))
    with pytest.raises(ProgramVerificationError):
        y.numpy()  # flush verifies, unguarded log is error severity
    # the failed segment keeps reporting its root cause on later reads
    with pytest.raises(RuntimeError):
        y.numpy()


def test_check_pending_segment_does_not_flush(lazy_mode):
    x = paddle.to_tensor(np.ones(4, np.float32))
    _y = x * 1.0
    diags = analysis.check_pending_segment()
    assert hits(diags, "redundant_ops", Severity.WARNING, "x*1"), diags
    assert lazy.pending_op_count() == 1  # still pending


def test_check_programs_keeps_lazy_parity_green(lazy_mode, check_flag):
    """Regression: FLAGS_check_programs=1 must not perturb lazy-dispatch
    numerics — same scenario as test_lazy_dispatch numeric parity."""
    from tests.test_lazy_dispatch import _make_inputs, _mlp_forward

    check_flag(1)
    paddle.set_flags({"FLAGS_eager_lazy_dispatch": False})
    ins_ref = _make_inputs()
    loss_ref = _mlp_forward(*ins_ref)
    loss_ref.backward()

    paddle.set_flags({"FLAGS_eager_lazy_dispatch": True})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # verifier warnings are expected
        ins_lazy = [paddle.to_tensor(t.numpy()) for t in ins_ref]
        for t in ins_lazy:
            t.stop_gradient = False
        loss_lazy = _mlp_forward(*ins_lazy)
        loss_lazy.backward()
    np.testing.assert_allclose(loss_lazy.numpy(), loss_ref.numpy(),
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(ins_lazy, ins_ref):
        np.testing.assert_allclose(a.grad.numpy(), b.grad.numpy(),
                                   rtol=1e-6, atol=1e-6)


def test_to_static_function_check():
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 4)

        def forward(self, x):
            _dead = x * 5.0
            return self.fc(x)

    net = paddle.jit.to_static(Net())
    diags = net.forward.check([static.InputSpec([2, 8], "float32")])
    assert hits(diags, "dead_code", Severity.WARNING, "dead op"), diags


# ---------------------------------------------------------------------------
# satellite: Program.clone shares parameters + honors for_test
# ---------------------------------------------------------------------------
def test_program_clone_shares_parameters_and_eval_mode():
    prog = static.Program()
    with static.program_guard(prog):
        static.data("x", [4, 3], "float32")

    def builder(feed):
        h = static.nn.fc(feed["x"], 8, name="clone_fc")
        return static.nn.batch_norm(h)

    prog.set_builder(builder)
    exe = static.Executor()
    feed = {"x": np.full((4, 3), 5.0, np.float32)}
    train_out = exe.run(prog, feed=feed)[0]

    clone = prog.clone(for_test=True)
    # all_parameters on the clone sees the SOURCE's parameter objects
    src_ids = [id(p) for p in prog.all_parameters()]
    assert src_ids and [id(p) for p in clone.all_parameters()] == src_ids

    eval_out = exe.run(clone, feed=feed)[0]
    # train-mode BN normalizes with batch stats (≈0 everywhere); eval mode
    # uses the running stats, so the outputs must differ decisively
    assert not np.allclose(train_out, eval_out, atol=1e-3)
    # and the source program's layers are restored to train mode
    assert all(
        layer.training
        for layer in prog._iter_layers()
        if hasattr(layer, "training")
    )


def test_program_clone_before_first_run_still_shares_parameters():
    """Cloning BEFORE the source ever ran must still share the (lazily
    created) layer cache — the classic train/test-program idiom clones
    before the first Executor.run."""
    prog = static.Program()
    with static.program_guard(prog):
        static.data("x", [4, 3], "float32")
    prog.set_builder(
        lambda feed: static.nn.fc(feed["x"], 8, name="clone_early").sum()
    )
    clone = prog.clone(for_test=True)  # source not warmed yet
    exe = static.Executor()
    feed = {"x": np.ones((4, 3), np.float32)}
    exe.run(prog, feed=feed)  # first run creates the parameters
    assert [id(p) for p in clone.all_parameters()] == [
        id(p) for p in prog.all_parameters()
    ]
    assert clone.all_parameters() != []


def test_program_clone_without_builder_or_layers():
    prog = static.Program()
    with static.program_guard(prog):
        static.data("x", [2], "float32")
    clone = prog.clone()
    assert clone.builder is None
    assert list(clone.feed_vars) == ["x"]
    assert clone.all_parameters() == []


# ---------------------------------------------------------------------------
# satellite: _flat_eqns recurses into control-flow primitives
# ---------------------------------------------------------------------------
def test_program_ops_see_through_control_flow():
    import jax.lax as lax

    from paddle_tpu.core.tensor import Tensor

    prog = static.Program()
    with static.program_guard(prog):
        static.data("x", [4], "float32")

    def builder(feed):
        v = feed["x"]._value
        out = lax.while_loop(
            lambda c: c[0] < 3, lambda c: (c[0] + 1, c[1] * 2.0), (0, v)
        )[1]
        out = lax.cond(out.sum() > 0.0, lambda o: o + 1.0,
                       lambda o: o - 1.0, out)
        return Tensor(out, stop_gradient=True)

    prog.set_builder(builder)
    names = [op.type for op in prog.ops]
    # the real primitives inside the loop/branches are listed...
    assert "mul" in names and "add" in names and "sub" in names
    # ...instead of opaque control-flow nodes
    assert "while" not in names and "cond" not in names


# ---------------------------------------------------------------------------
# satellite: flags — strict parsing, writability error, describe_flags
# ---------------------------------------------------------------------------
def test_set_flags_rejects_non_writable_with_clear_error():
    core_flags.define_flag("_test_frozen_flag", 7, "test-only", writable=False)
    with pytest.raises(ValueError, match="read-only"):
        paddle.set_flags({"FLAGS__test_frozen_flag": 8})
    assert core_flags.flag("_test_frozen_flag") == 7


def test_bool_flag_string_parsing_is_strict_and_consistent():
    for text, expect in [("0", False), ("off", False), ("no", False),
                         ("1", True), ("on", True), ("TRUE", True)]:
        paddle.set_flags({"FLAGS_check_nan_inf": text})
        got = paddle.get_flags("FLAGS_check_nan_inf")["FLAGS_check_nan_inf"]
        assert got is expect, (text, got)
    with pytest.raises(ValueError, match="invalid boolean"):
        paddle.set_flags({"FLAGS_check_nan_inf": "maybe"})
    paddle.set_flags({"FLAGS_check_nan_inf": False})
    # int flags coerce env-style strings too
    paddle.set_flags({"FLAGS_check_programs": "2"})
    assert paddle.get_flags("FLAGS_check_programs")["FLAGS_check_programs"] == 2
    paddle.set_flags({"FLAGS_check_programs": 0})


def test_describe_flags_lists_analysis_flags():
    rows = core_flags.describe_flags("check")
    names = [r["name"] for r in rows]
    assert "FLAGS_check_programs" in names
    row = next(r for r in rows if r["name"] == "FLAGS_check_programs")
    assert set(row) == {"name", "value", "default", "doc", "writable"}
    assert "analysis" in row["doc"]
    assert len(core_flags.describe_flags()) >= len(rows)
