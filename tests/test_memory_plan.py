"""Static memory planner (paddle_tpu.analysis.memory).

Covers the liveness arithmetic (exact byte goldens on a hand-checked
program), the memory_budget / donation_safety passes, the three eager
regimes of a LeNet train step (per-op 13-program, lazy 3-program, captured
1-program with and without donation), the estimated-vs-measured live-buffer
comparison (MEMORY_PLAN.md methodology — within +-10% on CPU, exact for
programs whose outputs all escape), and the use-after-donate repro that
previously only failed (TPU) or silently did nothing (CPU) at runtime.
"""
import gc
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import ProgramVerificationError, Severity
from paddle_tpu.analysis import memory as mem
from paddle_tpu.core import lazy

MB = 1 << 20


def hits(diags, pass_name, severity=None, needle=None):
    out = [d for d in diags if d.pass_name == pass_name]
    if severity is not None:
        out = [d for d in out if d.severity == severity]
    if needle is not None:
        out = [d for d in out
               if needle in d.message or needle in d.op or needle in d.hint]
    return out


def live_bytes():
    gc.collect()
    return sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays())


@pytest.fixture
def lazy_capture_mode():
    # async compile pinned off: these tests inspect the captured program
    # right after a fixed number of steps, and must not race the background
    # build thread (tests/test_step_capture.py covers the async pipeline)
    paddle.set_flags({"FLAGS_eager_lazy_dispatch": True,
                      "FLAGS_eager_step_capture": True,
                      "FLAGS_eager_async_compile": False})
    try:
        yield
    finally:
        lazy.flush_if_pending("test_teardown")
        paddle.set_flags({"FLAGS_eager_lazy_dispatch": False,
                          "FLAGS_eager_step_capture": True,
                          "FLAGS_eager_capture_donate": True,
                          "FLAGS_eager_async_compile": True,
                          "FLAGS_check_programs": 0})


def _lenet_step(bsz=8, seed=0):
    paddle.seed(seed)
    model = paddle.vision.models.LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    rng = np.random.default_rng(seed)
    x = paddle.to_tensor(rng.standard_normal((bsz, 1, 28, 28)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, (bsz,)))

    def step():
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return model, opt, loss_fn, x, y, step


# ---------------------------------------------------------------------------
# liveness arithmetic: exact golden on a hand-checked program
# ---------------------------------------------------------------------------
def _golden_ctx():
    def f(x, w):
        return jnp.sum(jnp.maximum(jnp.dot(x, w), 0.0))

    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((64, 128), "float32"),
        jax.ShapeDtypeStruct((128, 256), "float32"),
    )
    return analysis.Context(closed, [("feed", "x"), ("param", "w")], "golden")


def test_plan_golden_bytes_exact():
    # x 32768B + w 131072B + dot 65536B + max 65536B + sum 4B; the dot
    # output dies at the max op, so the peak is at max: x+w+dot+max
    plan = mem.plan_memory(_golden_ctx())
    assert plan.n_ops == 3
    assert plan.peak_bytes == 32768 + 131072 + 65536 + 65536
    assert "max" in plan.peak_op_path
    assert plan.input_bytes == 32768 + 131072
    assert plan.output_bytes == 4
    assert plan.boundary_bytes == 32768 + 131072 + 4
    assert plan.donation_credit_bytes == 0  # nothing donated
    # buffer records carry shapes/dtypes and credited live ranges
    labels = {b.label() for b in plan.buffers}
    assert "feed:x" in labels and "param:w" in labels


def test_plan_donation_credit_exact():
    # donating w frees its buffer entering its last read (the dot): the
    # peak drops by exactly w's 131072 bytes
    ctx = _golden_ctx()
    plan = mem.plan_memory(ctx, donated=(1,))
    base = mem.plan_memory(ctx, donated=())
    assert plan.peak_bytes == base.peak_bytes - 131072
    assert plan.donation_credit_bytes == 131072
    w = next(b for b in plan.buffers if b.label() == "param:w")
    assert w.donated and w.dies < 0  # freed entering op 0


def test_shared_inner_const_counted_once():
    # the inliner mints a fresh ConstAtom per call site of a cached jitted
    # inner fn, but the closed-over constant is ONE buffer — dedupe by value
    c = np.arange(1000, dtype=np.float32)  # 4000 bytes
    inner = jax.jit(lambda x: x + jnp.asarray(c))

    def f(a):
        return inner(inner(a)).sum()

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((1000,), "float32"))
    plan = mem.plan_memory(analysis.Context(closed, [("feed", "a")], "t"))
    assert plan.const_bytes == 4000, plan.const_bytes


def test_plan_output_copies_counted_per_position():
    # an output position that passes an input through (or repeats another
    # output) materializes its own buffer in an un-donated XLA program
    def f(x):
        y = x * 2.0
        return x, y, y

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((256,), "float32"))
    plan = mem.plan_memory(analysis.Context(closed, [("feed", "x")], "t"))
    copies = [b for b in plan.buffers if b.kind == "out-copy"]
    assert len(copies) == 2  # the x passthrough + the repeated y
    assert plan.boundary_bytes == 1024 * 4  # x + y + 2 copies


# ---------------------------------------------------------------------------
# memory_budget pass
# ---------------------------------------------------------------------------
def _relu_net(x, w):
    return paddle.nn.functional.relu(paddle.matmul(x, w)).sum()


_SPECS = [((64, 128), "float32"), ((128, 256), "float32")]


def test_memory_budget_quiet_by_default():
    assert analysis.check(_relu_net, _SPECS) == []


def test_memory_budget_reports_peak_and_top_live():
    diags = analysis.check(_relu_net, _SPECS, memory_budget_mb=16)
    info = hits(diags, "memory_budget", Severity.INFO, "estimated peak HBM")
    assert info, diags
    d = info[0]
    assert d.data["peak_bytes"] == 294912
    assert d.data["top_live"], d.data
    assert d.data["top_live"][0]["nbytes"] >= d.data["top_live"][-1]["nbytes"]
    assert not hits(diags, "memory_budget", Severity.ERROR)


def test_memory_budget_errors_over_budget():
    diags = analysis.check(_relu_net, _SPECS, memory_budget_mb=0.01)
    over = hits(diags, "memory_budget", Severity.ERROR, "exceeds the declared")
    assert over, diags
    assert over[0].data["peak_bytes"] == 294912
    # and the flag wires the same budget through every check() call
    paddle.set_flags({"FLAGS_memory_budget_mb": 0.01})
    try:
        flagged = analysis.check(_relu_net, _SPECS)
        assert hits(flagged, "memory_budget", Severity.ERROR), flagged
    finally:
        paddle.set_flags({"FLAGS_memory_budget_mb": 0.0})


# ---------------------------------------------------------------------------
# donation_safety pass: static verdicts over donated invar positions
# ---------------------------------------------------------------------------
def test_donation_safety_flags_returned_unchanged_input():
    def f(a, b):
        return a, (a * b).sum()

    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((8,), "float32"),
        jax.ShapeDtypeStruct((8,), "float32"),
    )
    ctx = analysis.Context(closed, [("param", "a"), ("feed", "b")], "t",
                           donated=(0,))
    diags = analysis.run_passes(ctx, ["donation_safety"])
    assert hits(diags, "donation_safety", Severity.ERROR,
                "returned unchanged"), diags


def test_donation_safety_flags_double_bound_buffer_and_external_refs():
    def f(a, b):
        return (a + b).sum()

    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((8,), "float32"),
        jax.ShapeDtypeStruct((8,), "float32"),
    )
    ctx = analysis.Context(closed, [("param", "a"), ("feed", "b")], "t",
                           donated=(0,), alias_groups=[(0, 1)])
    diags = analysis.run_passes(ctx, ["donation_safety"])
    assert hits(diags, "donation_safety", Severity.ERROR, "bound to"), diags

    ctx = analysis.Context(closed, [("param", "a"), ("feed", "b")], "t",
                           donated=(0,),
                           alias_refs={0: ["Tensor held_copy shape=(8,)"]})
    diags = analysis.run_passes(ctx, ["donation_safety"])
    assert hits(diags, "donation_safety", Severity.ERROR,
                "use-after-donate"), diags


def test_donated_buffer_diags_flags_tied_buffers():
    # one runtime array bound to two donated positions (tied weights):
    # XLA cannot donate the same buffer twice — flagged by the runtime scan
    arr = jnp.zeros((16,), jnp.float32)
    other = jnp.ones((16,), jnp.float32)
    diags = mem.donated_buffer_diags(
        [("param:tied_a", arr), ("param:tied_b", arr), ("param:c", other)]
    )
    dup = [d for d in diags if "donate the same buffer twice" in d.message]
    assert len(dup) == 1 and dup[0].severity == Severity.ERROR, diags
    assert mem.donated_buffer_diags([("param:c", other)]) == []


def test_donation_safety_clean_verdict_and_unused_credit():
    # a and b are read (a donated, safely); donated c is never read
    closed = jax.make_jaxpr(
        lambda a, b, c: (a * 2.0).sum() + b.sum()
    )(
        jax.ShapeDtypeStruct((8,), "float32"),
        jax.ShapeDtypeStruct((8,), "float32"),
        jax.ShapeDtypeStruct((8,), "float32"),
    )
    ctx = analysis.Context(
        closed, [("param", "a"), ("feed", "b"), ("param", "c")], "t",
        donated=(0, 2),
    )
    diags = analysis.run_passes(ctx, ["donation_safety"])
    assert not hits(diags, "donation_safety", Severity.ERROR), diags
    assert hits(diags, "donation_safety", Severity.INFO, "verified"), diags
    assert hits(diags, "donation_safety", Severity.INFO, "never read"), diags


# ---------------------------------------------------------------------------
# the three eager regimes of a LeNet step + golden estimates
# ---------------------------------------------------------------------------
def test_lenet_regime_plans_and_donation_credit(lazy_capture_mode):
    model, opt, loss_fn, x, y, step = _lenet_step(bsz=8)

    # lazy regime forward program: trace the pending segment pre-flush
    paddle.set_flags({"FLAGS_eager_step_capture": False})
    loss = loss_fn(model(x), y)
    seg_closed = lazy.pending_segment_jaxpr()
    assert seg_closed is not None
    seg_plan = mem.plan_memory(analysis.Context(seg_closed, [], "segment"))
    lazy.flush_if_pending("test")
    # golden window for LeNet b8 forward+loss (exact value 1526772 on the
    # current lowering; the window absorbs minor jax lowering drift)
    assert 1.2 * MB < seg_plan.peak_bytes < 1.9 * MB, seg_plan.peak_bytes

    # captured regime: ONE donated program for the whole step
    paddle.set_flags({"FLAGS_eager_step_capture": True})
    for _ in range(6):
        step()
    prog = lazy.captured_step_program()
    assert prog is not None
    closed, donated, roles = prog
    assert donated, "params+state must be donated by default"
    ctx = analysis.Context(closed, roles, "captured-step")
    cap_don = mem.plan_memory(ctx, donated=donated)
    cap_nodon = mem.plan_memory(ctx, donated=())
    # donation credit is real and exactly the peak difference
    assert cap_don.peak_bytes < cap_nodon.peak_bytes
    assert cap_don.donation_credit_bytes == (
        cap_nodon.peak_bytes - cap_don.peak_bytes
    )
    # the whole-step program subsumes the forward segment
    assert cap_nodon.peak_bytes > seg_plan.peak_bytes
    # donated buffers stop being resident at the boundary
    assert cap_don.boundary_bytes < cap_nodon.boundary_bytes

    # FLAGS_eager_capture_donate=0 keeps 1-program capture, drops donation:
    # the planner sees no donated positions and the plans coincide
    paddle.set_flags({"FLAGS_eager_capture_donate": False})
    for _ in range(6):
        step()
    closed2, donated2, roles2 = lazy.captured_step_program()
    assert donated2 == ()
    nd = mem.plan_memory(
        analysis.Context(closed2, roles2, "captured-step"), donated=donated2
    )
    assert nd.donation_credit_bytes == 0
    assert abs(nd.peak_bytes - cap_nodon.peak_bytes) <= 0.02 * cap_nodon.peak_bytes


# ---------------------------------------------------------------------------
# estimated vs measured (jax.live_arrays on CPU): the acceptance criterion
# ---------------------------------------------------------------------------
def test_estimate_matches_measured_lazy_segment(lazy_capture_mode):
    """Lazy regime: the fused forward segment's outputs all escape, so the
    plan's peak equals measured live bytes (inputs + outputs) exactly."""
    paddle.set_flags({"FLAGS_eager_step_capture": False})
    model, opt, loss_fn, x, y, step = _lenet_step(bsz=8)
    loss_fn(model(x), y)
    closed = lazy.pending_segment_jaxpr()
    seg = lazy._tls.segment
    ext = list(seg.ext_vals)
    plan = mem.plan_memory(analysis.Context(closed, [], "segment"))
    lazy.flush_if_pending("test")

    input_bytes = sum(int(v.nbytes) for v in ext)
    fn = jax.jit(jax.extend.core.jaxpr_as_fun(closed))
    base = live_bytes()
    outs = jax.tree_util.tree_leaves(fn(*ext))
    measured = input_bytes + (live_bytes() - base)
    assert measured > 0
    assert abs(plan.peak_bytes - measured) <= 0.10 * measured, (
        plan.peak_bytes, measured,
    )
    del outs


def test_estimate_matches_measured_per_op_forward(lazy_capture_mode):
    """Per-op regime: 13 programs, but the tape holds the same residual
    set the fused segment returns — measured live growth across an eager
    per-op forward matches the segment plan within 10% (here: exactly)."""
    paddle.set_flags({"FLAGS_eager_lazy_dispatch": True,
                      "FLAGS_eager_step_capture": False})
    model, opt, loss_fn, x, y, step = _lenet_step(bsz=8)
    loss_fn(model(x), y)
    seg_closed = lazy.pending_segment_jaxpr()
    seg_plan = mem.plan_memory(analysis.Context(seg_closed, [], "segment"))
    lazy.flush_if_pending("test")

    paddle.set_flags({"FLAGS_eager_lazy_dispatch": False})
    for _ in range(2):  # warm per-op compile caches out of the measurement
        l = loss_fn(model(x), y)
        l.backward()
    for p in model.parameters():
        p.grad = None
    base = live_bytes()
    loss = loss_fn(model(x), y)
    delta = live_bytes() - base
    inputs = (
        sum(int(p._value.nbytes) for p in model.parameters())
        + int(x._value.nbytes) + int(y._value.nbytes)
    )
    measured = inputs + delta
    assert abs(seg_plan.peak_bytes - measured) <= 0.10 * measured, (
        seg_plan.peak_bytes, measured,
    )
    loss.backward()  # release the tape before teardown


def test_estimate_matches_measured_captured_step(lazy_capture_mode):
    """Captured regime: running the whole-step program un-donated and
    holding every output, measured live bytes equal the plan's boundary
    estimate (inputs + consts + escaping outputs) within 10%; the peak adds
    only backward transients XLA frees before exit."""
    model, opt, loss_fn, x, y, step = _lenet_step(bsz=8)
    for _ in range(6):
        step()
    closed, donated, roles = lazy.captured_step_program()
    plan = mem.plan_memory(
        analysis.Context(closed, roles, "captured-step"), donated=()
    )
    entry = lazy._tls.last_capture_entry()  # weakref — entry still cached
    args = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), entry.arg_specs
    )
    input_bytes = sum(
        int(a.nbytes) for a in jax.tree_util.tree_leaves(args)
    )
    fn = jax.jit(entry.step_fn)  # fresh jit WITHOUT donation
    base = live_bytes()
    outs = jax.tree_util.tree_leaves(fn(*args))
    measured = input_bytes + (live_bytes() - base)
    assert abs(plan.boundary_bytes - measured) <= 0.10 * measured, (
        plan.boundary_bytes, measured,
    )
    assert plan.peak_bytes >= plan.boundary_bytes
    del outs


# ---------------------------------------------------------------------------
# use-after-donate: statically flagged before XLA fails (or silently
# ignores donation, as CPU does)
# ---------------------------------------------------------------------------
def test_use_after_donate_flagged_statically(lazy_capture_mode):
    model, opt, loss_fn, x, y, step = _lenet_step(bsz=8)
    for _ in range(6):
        step()
    assert lazy.step_capture_state()["armed"]

    # a detach() alias held across the next donated captured step: without
    # the checker this only surfaces as a runtime XLA error on TPU (and
    # silently "works" on CPU, where donation is a no-op)
    held = list(model.parameters())[0].detach()

    # level 1: the replay proceeds, every finding becomes a Python warning
    paddle.set_flags({"FLAGS_check_programs": 1})
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        step()
    assert any("use-after-donate" in str(w.message) for w in seen), [
        str(w.message)[:80] for w in seen
    ]

    # the level-1 replay donated and rebound the param, so `held` now
    # dangles on the PREVIOUS buffer (the runtime failure a TPU run would
    # hit on its next read) — take a FRESH alias of the live buffer for
    # the level-2 verdict
    del held
    held = list(model.parameters())[0].detach()

    # level 2: the deferred step resolves on the safe 3-program path and
    # the verdict raises BEFORE any buffer is donated
    paddle.set_flags({"FLAGS_check_programs": 2})
    import paddle_tpu.profiler as prof

    with pytest.raises(ProgramVerificationError) as ei:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            step()
    assert any(
        d.pass_name == "donation_safety" and d.severity == Severity.ERROR
        for d in ei.value.diagnostics
    )
    counters = prof.dispatch_counters()
    assert counters["capture_fallback_reasons"].get("donation_unsafe", 0) >= 1
    assert counters["donation_alias_flags"] >= 1

    # dropping the alias clears the verdict: re-warm and replay clean
    del held
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for _ in range(6):
            step()
    assert lazy.step_capture_state()["armed"]
    paddle.set_flags({"FLAGS_check_programs": 0})
    before = float(step())
    assert np.isfinite(before)


# ---------------------------------------------------------------------------
# profiler + compile_train_step wiring
# ---------------------------------------------------------------------------
def test_measure_programs_reports_memory_snapshot(lazy_capture_mode):
    import paddle_tpu.profiler as prof

    model, opt, loss_fn, x, y, step = _lenet_step(bsz=8)
    counters = prof.measure_programs(step, warmup=5)
    assert counters["capture_replays"] >= 1
    snap = counters["_memory"]
    assert snap["live_buffer_bytes"] > 0
    assert snap["live_buffer_count"] > 0
    assert snap["estimated_captured_peak_bytes"] > 0
    assert (snap["estimated_captured_boundary_bytes"]
            <= snap["estimated_captured_peak_bytes"])
    assert snap["estimated_donation_credit_bytes"] >= 0


def test_compile_train_step_memory_plan_and_alias_check():
    model, opt, loss_fn, x, y, _ = _lenet_step(bsz=4)
    step = paddle.jit.compile_train_step(model, loss_fn, opt)
    with pytest.raises(RuntimeError, match="one executed step"):
        step.memory_plan()
    float(step(x, y))
    plan = step.memory_plan()
    assert plan.peak_bytes > 0
    assert plan.donation_credit_bytes >= 0
    nodon = step.memory_plan(donated=())
    assert nodon.peak_bytes >= plan.peak_bytes

    # a held param alias is flagged before the donated step runs
    held = list(model.parameters())[0].detach()
    paddle.set_flags({"FLAGS_check_programs": 2})
    try:
        with pytest.raises(ProgramVerificationError):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                step(x, y)
        del held
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            loss = step(x, y)
        assert np.isfinite(float(loss))
    finally:
        paddle.set_flags({"FLAGS_check_programs": 0})
