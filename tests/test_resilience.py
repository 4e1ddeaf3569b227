"""Fault-tolerant training runtime (paddle.resilience).

Matrix over {fault site × execution tier} proving the ISSUE-5 contract:
(a) transient faults recover to the BITWISE fault-free final loss (retry at
the faulted tier, or per-op re-execution of a failed segment — every tier
is numerics-identical to per-op, so recovery never changes results);
(b) the degradation ladder demotes a repeatedly-faulting tier
(captured→lazy→per-op) and re-promotes it after the cooldown, with the
demotion/promotion counters visible in dispatch_counters();
(c) numeric rescue's non-finite sentinel adds ZERO program launches
(programs-per-step stays 3/1 per tier under measure_programs) and the
skip / lr_backoff / abort policies + GradScaler handshake behave;
(d) a SIGTERM mid-run emergency-saves at the step boundary and
train_step_range resume loses at most one step.

Subprocess cases (chaos CLI, kill -9 checkpoint) are marked slow.
"""
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.profiler as prof
import paddle_tpu.resilience as res
from paddle_tpu.core import lazy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_resilience():
    """Reset harness/ladder state and restore every resilience flag."""
    res.reset()
    prof.reset_dispatch_counters()
    paddle.set_flags({
        "FLAGS_fault_inject": "",
        "FLAGS_retry_backoff_ms": 0.0,  # keep the suite fast
        "FLAGS_numeric_rescue": "",
        # synchronous compiles: these tests assert exact per-step capture /
        # program counts; the async pipeline has its own regression below
        # (test_async_compile_keeps_faults_and_ladder_working)
        "FLAGS_eager_async_compile": False,
    })
    try:
        yield
    finally:
        lazy.flush_if_pending("test_teardown")
        lazy.drain_async()
        paddle.set_flags({
            "FLAGS_fault_inject": "",
            "FLAGS_retry_max": 2,
            "FLAGS_retry_backoff_ms": 5.0,
            "FLAGS_numeric_rescue": "",
            "FLAGS_numeric_rescue_lr_factor": 0.5,
            "FLAGS_ladder_demote_after": 2,
            "FLAGS_ladder_cooldown_steps": 8,
            "FLAGS_check_nan_inf": False,
            "FLAGS_eager_lazy_dispatch": False,
            "FLAGS_eager_step_capture": True,
            "FLAGS_eager_async_compile": True,
        })
        res.reset()


def _make(seed=0):
    paddle.seed(seed)
    net = nn.Linear(4, 3)
    opt = paddle.optimizer.Adam(learning_rate=1e-2, parameters=net.parameters())
    return net, opt


_rng = np.random.default_rng(0)
_X = _rng.standard_normal((8, 4)).astype(np.float32)
_Y = _rng.standard_normal((8, 3)).astype(np.float32)


def _step(net, opt, X=None, Y=None):
    loss = ((net(paddle.to_tensor(_X if X is None else X))
             - paddle.to_tensor(_Y if Y is None else Y)) ** 2).mean()
    loss.backward()
    opt.step()
    opt.clear_grad()
    return float(loss)


def _run(steps=3, seed=0):
    net, opt = _make(seed)
    return [_step(net, opt) for _ in range(steps)], net


def _set_tier(tier):
    paddle.set_flags({
        "FLAGS_eager_lazy_dispatch": tier in ("lazy", "captured"),
        "FLAGS_eager_step_capture": tier == "captured",
    })


# ---------------------------------------------------------------------------
# fault spec + classification
# ---------------------------------------------------------------------------
def test_fault_spec_parsing():
    clauses = res.parse_fault_spec("execute:p=0.2,compile:step>=3,nan:grads")
    assert [c.kind for c in clauses] == ["execute", "compile", "nan"]
    assert clauses[0].p == 0.2
    assert clauses[1].step_lo == 3
    assert clauses[2].target == "grads"
    c = res.parse_fault_spec("execute:captured:p=1:x=5:step=2")[0]
    assert (c.target, c.repeat, c.step_eq) == ("captured", 5, 2)
    with pytest.raises(ValueError):
        res.parse_fault_spec("frobnicate:p=1")
    with pytest.raises(ValueError):
        res.parse_fault_spec("execute:segmet:p=1")  # typo'd site: fail loud
    with pytest.raises(ValueError):
        res.parse_fault_spec("execute:op:segment")  # at most one site
    with pytest.raises(ValueError):
        res.parse_fault_spec("execute:q<3")


def test_fault_plan_deterministic_replay():
    plan_a = res.FaultPlan(res.parse_fault_spec("execute:p=0.3"), seed=7)
    plan_b = res.FaultPlan(res.parse_fault_spec("execute:p=0.3"), seed=7)
    decisions_a = [plan_a._fires("execute", "op", s) is not None for s in range(50)]
    decisions_b = [plan_b._fires("execute", "op", s) is not None for s in range(50)]
    assert decisions_a == decisions_b
    assert any(decisions_a) and not all(decisions_a)
    plan_c = res.FaultPlan(res.parse_fault_spec("execute:p=0.3"), seed=8)
    decisions_c = [plan_c._fires("execute", "op", s) is not None for s in range(50)]
    assert decisions_a != decisions_c  # seed actually matters


def test_transient_classification():
    assert res.is_transient(res.InjectedExecuteError("x"))
    assert res.is_transient(ConnectionResetError("peer"))
    assert res.is_transient(OSError("disk briefly gone"))
    assert res.is_transient(RuntimeError("UNAVAILABLE: device preempted"))
    assert not res.is_transient(ValueError("bad shape"))
    assert not res.is_transient(FloatingPointError("nan"))
    assert not res.is_transient(KeyboardInterrupt())
    assert not res.is_transient(RuntimeError("some deterministic bug"))


def test_deterministic_os_errors_are_fatal():
    """A read-only mount / full disk / bad path cannot be retried away —
    backing off retry_max times would only delay the real error."""
    import errno

    assert not res.is_transient(PermissionError(errno.EACCES, "denied"))
    assert not res.is_transient(FileNotFoundError(errno.ENOENT, "gone"))
    assert not res.is_transient(OSError(errno.ENOSPC, "no space"))
    assert not res.is_transient(OSError(errno.EROFS, "read-only fs"))
    # ...but a flaky-mount style EIO stays worth one retry
    assert res.is_transient(OSError(errno.EIO, "io error"))


def test_active_plan_resets_on_toggle():
    """Toggling injection off and back on with the SAME spec replays the
    scenario from scratch — consumed x= budgets must not persist."""
    from paddle_tpu.resilience import faults

    step = faults.current_step()
    paddle.set_flags({"FLAGS_fault_inject": "execute:op:p=1:x=1"})
    plan = faults.active_plan()
    assert plan._fires("execute", "op", step) is not None
    # x=1 budget consumed for this (site, step): no second fire
    assert plan._fires("execute", "op", step) is None
    paddle.set_flags({"FLAGS_fault_inject": ""})
    assert faults.active_plan() is None
    paddle.set_flags({"FLAGS_fault_inject": "execute:op:p=1:x=1"})
    fresh = faults.active_plan()
    assert fresh is not plan
    assert fresh._fires("execute", "op", step) is not None


def test_retry_unsafe_skips_in_place_retry():
    """A donated executable is never re-invoked on a REAL transient fault
    (its inputs may already be consumed) — the fault records as disruptive
    and propagates to the caller's fallback; injected faults still retry."""
    from paddle_tpu.resilience import runtime

    calls = []

    def real_transient_thunk():
        calls.append(1)
        raise RuntimeError("UNAVAILABLE: connection dropped mid-execute")

    with pytest.raises(RuntimeError):
        runtime.execute("captured", real_transient_thunk, retry_unsafe=True)
    assert len(calls) == 1  # no in-place replay with consumed buffers
    c = prof.dispatch_counters()
    assert c["transient_faults"] == 1
    assert c["retry_attempts"] == 0

    # an injected fault raises BEFORE the thunk runs, so retrying is safe
    # even with donation on: the thunk eventually executes exactly once
    prof.reset_dispatch_counters()
    paddle.set_flags({"FLAGS_fault_inject": "execute:captured:p=1:x=1"})
    ran = []
    out = runtime.execute("captured", lambda: ran.append(1) or "ok",
                          retry_unsafe=True)
    assert out == "ok" and len(ran) == 1
    assert prof.dispatch_counters()["retry_attempts"] == 1


# ---------------------------------------------------------------------------
# (a) transient faults recover to the fault-free final loss, per tier
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tier", ["per_op", "lazy", "captured"])
def test_transient_faults_recover_bitwise(tier):
    _set_tier(tier)
    steps = 6 if tier == "captured" else 3
    clean, _ = _run(steps)
    res.reset()
    prof.reset_dispatch_counters()
    # every site faults once per step; one retry always recovers (x=1 < max)
    paddle.set_flags({"FLAGS_fault_inject": "execute:p=1:x=1,compile:p=1:x=1"})
    faulted, _ = _run(steps)
    c = prof.dispatch_counters()
    assert faulted == clean  # bitwise: the retried program is the same program
    assert c["retry_attempts"] > 0
    assert c["injected_faults"] > 0
    assert c["transient_faults"] > 0
    assert c["fault_sites"]  # per-site attribution populated


def test_segment_retry_exhaustion_degrades_to_per_op():
    """Lazy tier, retries exhausted: the flush re-executes the plan per-op —
    the step completes with identical numerics, one rung down."""
    _set_tier("lazy")
    clean, _ = _run(3)
    res.reset()
    prof.reset_dispatch_counters()
    paddle.set_flags({
        "FLAGS_fault_inject": "execute:segment:p=1:x=9",
        "FLAGS_retry_max": 1,
    })
    faulted, _ = _run(3)
    c = prof.dispatch_counters()
    assert faulted == clean
    assert c["segment_per_op_fallbacks"] >= 1
    assert c["retry_exhausted"] >= 1


def test_fatal_fault_propagates_without_retry():
    _set_tier("per_op")
    net, opt = _make()

    def bad_op(x):
        raise ValueError("deterministic bug")

    from paddle_tpu.core import dispatch

    with pytest.raises(ValueError):
        dispatch.apply(bad_op, net.weight, jit=False)
    c = prof.dispatch_counters()
    assert c["fatal_faults"] >= 1
    assert c["retry_attempts"] == 0


# ---------------------------------------------------------------------------
# (b) degradation ladder: demote on repeated faults, re-promote on cooldown
# ---------------------------------------------------------------------------
def test_ladder_demotes_and_repromotes_captured_tier():
    _set_tier("captured")
    paddle.set_flags({
        "FLAGS_retry_max": 1,
        "FLAGS_ladder_demote_after": 2,
        "FLAGS_ladder_cooldown_steps": 3,
    })
    net, opt = _make()
    for _ in range(6):  # arm + capture (stale armed state from a previous
        _step(net, opt)  # test costs one counted fallback + re-warmup)
    assert prof.dispatch_counters()["capture_replays"] >= 1
    # unrecoverable faults at the captured replay (x=9 > retry budget):
    # each faulted replay falls back to the 3-program path AND records one
    # disruptive ladder fault; after demote_after of them the signature is
    # demoted (the controller re-warms between fallbacks, so allow a few
    # steps for the second faulted replay to happen)
    paddle.set_flags({"FLAGS_fault_inject": "execute:captured:p=1:x=9"})
    for _ in range(8):
        _step(net, opt)
        if prof.dispatch_counters()["ladder_demotions"]:
            break
    c = prof.dispatch_counters()
    assert c["capture_fallbacks"] >= 2
    assert c["ladder_demotions"] == 1
    assert res.state()["ladder"]["demoted"]  # signature-keyed demotion
    paddle.set_flags({"FLAGS_fault_inject": ""})
    # demoted: the step runs the 3-program path (no new replays)
    replays_before = prof.dispatch_counters()["capture_replays"]
    _step(net, opt)
    assert prof.dispatch_counters()["capture_replays"] == replays_before
    # cooldown passes -> re-promoted -> capture replays again
    for _ in range(6):
        _step(net, opt)
    c = prof.dispatch_counters()
    assert c["ladder_promotions"] == 1
    prof.reset_dispatch_counters()
    _step(net, opt)
    c = prof.dispatch_counters()
    assert c["programs"] == 1 and c["capture_replays"] == 1


def test_ladder_demotes_lazy_tier_to_per_op():
    _set_tier("lazy")
    paddle.set_flags({
        "FLAGS_retry_max": 0,
        "FLAGS_ladder_demote_after": 1,
        "FLAGS_ladder_cooldown_steps": 2,
    })
    net, opt = _make()
    _step(net, opt)  # warm caches
    # one unrecoverable segment fault (retry_max=0) -> per-op re-execution of
    # the flush AND a ladder demotion of the lazy tier
    paddle.set_flags({"FLAGS_fault_inject": "execute:segment:p=1:x=9"})
    _step(net, opt)
    paddle.set_flags({"FLAGS_fault_inject": ""})
    c = prof.dispatch_counters()
    assert c["ladder_demotions"] == 1
    assert not res.runtime.lazy_tier_ok()
    # while demoted, ops dispatch per-op (no segment programs)
    prof.reset_dispatch_counters()
    _step(net, opt)
    c = prof.dispatch_counters()
    assert c["segment_programs"] == 0 and c["op_programs"] > 0
    # cooldown -> re-promotion -> fused segments return
    _step(net, opt)
    _step(net, opt)
    assert res.runtime.lazy_tier_ok()
    prof.reset_dispatch_counters()
    _step(net, opt)
    assert prof.dispatch_counters()["segment_programs"] > 0


# ---------------------------------------------------------------------------
# (c) numeric rescue: sentinel semantics, zero extra programs, policies
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tier,expected", [("lazy", 3), ("captured", 1)])
def test_rescue_sentinel_adds_no_programs(tier, expected):
    _set_tier(tier)
    paddle.set_flags({"FLAGS_numeric_rescue": "skip"})
    net, opt = _make()
    counters = prof.measure_programs(lambda: _step(net, opt), warmup=5)
    assert counters["programs"] == expected
    assert counters["_resilience"]["numeric_rescue"] == "skip"


def test_rescue_sentinel_per_op_program_count_unchanged():
    _set_tier("per_op")
    net, opt = _make()
    base = prof.measure_programs(lambda: _step(net, opt), warmup=2)["programs"]
    paddle.set_flags({"FLAGS_numeric_rescue": "skip"})
    net, opt = _make()
    with_rescue = prof.measure_programs(lambda: _step(net, opt), warmup=2)["programs"]
    assert with_rescue == base


@pytest.mark.parametrize("tier", ["per_op", "lazy"])
def test_rescue_skip_leaves_params_untouched(tier):
    _set_tier(tier)
    paddle.set_flags({
        "FLAGS_numeric_rescue": "skip",
        "FLAGS_fault_inject": "nan:grads:step=1",
    })
    net, opt = _make()
    _step(net, opt)  # step 0 clean
    w = net.weight.numpy().copy()
    m1 = {k: np.asarray(v) for k, v in
          opt._accumulators[id(net.weight)].items()}
    _step(net, opt)  # step 1: poisoned grads -> rescued
    c = prof.dispatch_counters()
    assert c["numeric_rescues"] == 1
    np.testing.assert_array_equal(net.weight.numpy(), w)
    for k, v in opt._accumulators[id(net.weight)].items():
        np.testing.assert_array_equal(np.asarray(v), m1[k])  # state frozen too
    assert np.isfinite(_step(net, opt))  # training continues


def test_rescue_under_captured_tier_fires_via_fallback():
    """nan:grads cannot poison a gradient inside the captured 1-program
    replay (no gradient is ever materialized there) — the capture
    controller must resolve that step on the 3-program path so the
    injection and its rescue actually fire (regression: the clause
    silently never fired under capture, validating rescue vacuously)."""
    from paddle_tpu.resilience import faults

    _set_tier("captured")
    paddle.set_flags({"FLAGS_numeric_rescue": "skip"})
    net, opt = _make()
    for _ in range(6):  # reach steady captured replay
        _step(net, opt)
    assert prof.dispatch_counters()["capture_replays"] >= 1
    paddle.set_flags(
        {"FLAGS_fault_inject": f"nan:grads:step={faults.current_step()}"}
    )
    w = net.weight.numpy().copy()
    _step(net, opt)  # poisoned -> routed to the 3-program path -> rescued
    c = prof.dispatch_counters()
    assert c["numeric_rescues"] == 1
    assert c["capture_fallback_reasons"].get("nan_injected") == 1
    np.testing.assert_array_equal(net.weight.numpy(), w)  # step skipped
    paddle.set_flags({"FLAGS_fault_inject": ""})
    assert np.isfinite(_step(net, opt))  # training continues


def test_rescue_lr_backoff_policy():
    _set_tier("per_op")
    paddle.set_flags({
        "FLAGS_numeric_rescue": "lr_backoff",
        "FLAGS_numeric_rescue_lr_factor": 0.5,
        "FLAGS_fault_inject": "nan:grads:step=1",
    })
    net, opt = _make()
    _step(net, opt)
    lr0 = opt.get_lr()
    _step(net, opt)  # rescued -> lr backed off
    assert opt.get_lr() == pytest.approx(lr0 * 0.5)
    assert prof.dispatch_counters()["rescue_lr_backoffs"] == 1


def test_rescue_abort_policy():
    _set_tier("per_op")
    paddle.set_flags({
        "FLAGS_numeric_rescue": "abort",
        "FLAGS_fault_inject": "nan:grads:step=0",
    })
    net, opt = _make()
    with pytest.raises(FloatingPointError):
        _step(net, opt)


def test_rescue_integrates_with_grad_scaler():
    """A rescued step marks the driving GradScaler's found_inf so dynamic
    loss scaling backs off — and the scaler skips its own host scan."""
    _set_tier("per_op")
    paddle.set_flags({
        "FLAGS_numeric_rescue": "skip",
        "FLAGS_fault_inject": "nan:grads:step=1",
    })
    net, opt = _make()
    scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0,
                                   decr_every_n_nan_or_inf=1)

    def scaled_step():
        loss = ((net(paddle.to_tensor(_X)) - paddle.to_tensor(_Y)) ** 2).mean()
        scaler.scale(loss).backward()
        scaler.step(opt)
        scaler.update()
        opt.clear_grad()

    scaled_step()  # step 0 clean
    assert scaler._scale == 1024.0
    scaled_step()  # step 1 rescued -> scale halves via the sentinel handshake
    assert scaler._scale == 512.0
    assert prof.dispatch_counters()["numeric_rescues"] == 1


# ---------------------------------------------------------------------------
# lazy-aware FLAGS_check_nan_inf (fused finite scan, satellite task)
# ---------------------------------------------------------------------------
def test_lazy_nan_check_fused_into_segment():
    _set_tier("lazy")
    paddle.set_flags({"FLAGS_check_nan_inf": True})
    net, opt = _make()
    for _ in range(2):
        _step(net, opt)
    # regression guard: checking must NOT force per-op dispatch — the step
    # still runs 3 fused programs, with the scan folded into the segment
    counters = prof.measure_programs(lambda: _step(net, opt), warmup=1)
    assert counters["programs"] == 3
    assert counters["segment_nan_checks"] >= 1
    assert "fallback_debug" not in counters["flush_reasons"]
    # a NaN input is caught at flush and names the op
    bad = np.full((8, 4), np.nan, np.float32)
    with pytest.raises(FloatingPointError, match="linear"):
        _step(net, opt, X=bad)


def test_lazy_nan_check_parity_with_per_op_path():
    paddle.set_flags({"FLAGS_check_nan_inf": True})
    bad = np.full((8, 4), np.nan, np.float32)
    _set_tier("per_op")
    net, opt = _make()
    with pytest.raises(FloatingPointError):
        _step(net, opt, X=bad)
    _set_tier("lazy")
    net, opt = _make()
    with pytest.raises(FloatingPointError):
        _step(net, opt, X=bad)


# ---------------------------------------------------------------------------
# (d) preemption: SIGTERM resume loses at most one step
# ---------------------------------------------------------------------------
def test_sigterm_resume_loses_at_most_one_step(tmp_path):
    from paddle_tpu.distributed.checkpoint import (
        AsyncCheckpointer,
        train_step_range,
        training_state,
    )
    from paddle_tpu.resilience import Preempted, PreemptionGuard

    rng = np.random.default_rng(3)
    batches = [rng.standard_normal((8, 4)).astype(np.float32) for _ in range(8)]

    def run_step(net, opt, i):
        return _step(net, opt, X=batches[i])

    net, opt = _make()
    clean = [run_step(net, opt, i) for i in range(8)]

    net, opt = _make()
    ck = AsyncCheckpointer(str(tmp_path / "ck"), max_to_keep=2)
    state = training_state(net, opt)
    done = []
    with pytest.raises(Preempted):
        for step in train_step_range(8, ck, state, guard=PreemptionGuard()):
            run_step(net, opt, step)
            done.append(step)
            if step == 3:
                os.kill(os.getpid(), signal.SIGTERM)
    assert done == [0, 1, 2, 3]  # the in-flight step finished
    c = prof.dispatch_counters()
    assert c["preemptions"] == 1 and c["emergency_saves"] == 1

    # relaunch: fresh model resumes at step 4 — zero completed steps lost
    net2, opt2 = _make(seed=777)
    ck2 = AsyncCheckpointer(str(tmp_path / "ck"), max_to_keep=2)
    state2 = training_state(net2, opt2)
    resumed, losses = [], []
    for step in train_step_range(8, ck2, state2, guard=PreemptionGuard()):
        losses.append(run_step(net2, opt2, step))
        resumed.append(step)
    assert resumed == [4, 5, 6, 7]
    assert losses[-1] == clean[-1]  # bitwise: exact state round-trip


def test_train_epoch_range_guard(tmp_path):
    from paddle_tpu.distributed.checkpoint import (
        AsyncCheckpointer,
        train_epoch_range,
        training_state,
    )
    from paddle_tpu.resilience import Preempted, PreemptionGuard

    net, opt = _make()
    ck = AsyncCheckpointer(str(tmp_path / "ck"), max_to_keep=2)
    state = training_state(net, opt)
    seen = []
    with pytest.raises(Preempted):
        for epoch in train_epoch_range(5, ck, state, guard=PreemptionGuard()):
            seen.append(epoch)
            _step(net, opt)
            if epoch == 1:
                os.kill(os.getpid(), signal.SIGTERM)
    assert seen == [0, 1]
    net2, opt2 = _make(seed=9)
    ck2 = AsyncCheckpointer(str(tmp_path / "ck"), max_to_keep=2)
    resumed = [e for e in train_epoch_range(5, ck2, training_state(net2, opt2),
                                            guard=PreemptionGuard())
               if _step(net2, opt2) is not None]
    assert resumed == [2, 3, 4]


# ---------------------------------------------------------------------------
# surface / introspection
# ---------------------------------------------------------------------------
def test_describe_flags_covers_resilience():
    from paddle_tpu.core.flags import describe_flags

    names = {e["name"] for e in describe_flags()}
    for flag in ("FLAGS_fault_inject", "FLAGS_fault_seed", "FLAGS_retry_max",
                 "FLAGS_retry_backoff_ms", "FLAGS_retry_backoff_max_ms",
                 "FLAGS_ladder_demote_after", "FLAGS_ladder_cooldown_steps",
                 "FLAGS_numeric_rescue", "FLAGS_numeric_rescue_lr_factor",
                 "FLAGS_fault_hang_ms"):
        assert flag in names
    for e in describe_flags("fault_inject"):
        assert e["doc"]


def test_public_surface():
    assert paddle.resilience is res
    for name in ("PreemptionGuard", "Preempted", "LadderPolicy",
                 "DegradationLadder", "RetryPolicy", "FaultPlan",
                 "SkipStep", "LRBackoff", "Abort"):
        assert hasattr(res, name)
    st = res.state()
    assert {"step", "retry_max", "numeric_rescue", "ladder"} <= set(st)


def test_hang_injection_is_transient():
    _set_tier("per_op")
    paddle.set_flags({
        "FLAGS_fault_inject": "hang:optimizer:p=1:x=1",
        "FLAGS_fault_hang_ms": 1.0,
    })
    clean, _ = _run(2)
    paddle.set_flags({"FLAGS_fault_inject": ""})
    res.reset()
    paddle.set_flags({"FLAGS_fault_inject": "hang:optimizer:p=1:x=1",
                      "FLAGS_fault_hang_ms": 1.0})
    # rerun identical: hang raised after the stall, retried, same numerics
    res.reset()
    faulted, _ = _run(2)
    assert faulted == clean


# ---------------------------------------------------------------------------
# chaos CLI (subprocess — slow)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_chaos_probe_cli():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_probe.py"),
         "--steps", "5", "--batch", "8"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALL SCENARIOS PASSED" in out.stdout


# ---------------------------------------------------------------------------
# PR 6: the async host pipeline must not bypass resilience — fault injection
# and ladder demotion act on the MAIN thread even while fresh programs
# compile on the background thread
# ---------------------------------------------------------------------------
def test_async_compile_keeps_faults_and_ladder_working():
    _set_tier("lazy")
    paddle.set_flags({
        "FLAGS_eager_async_compile": True,
        "FLAGS_retry_max": 1,
        "FLAGS_ladder_demote_after": 2,
        "FLAGS_ladder_cooldown_steps": 3,
    })
    lazy._segment_cache.clear()
    lazy._pending_seg_compiles.clear()
    # clean async run: bitwise-identical to the synchronous path
    clean, _ = _run(4)
    lazy.drain_async()
    paddle.set_flags({"FLAGS_eager_async_compile": False})
    lazy._segment_cache.clear()
    sync_run, _ = _run(4)
    assert clean == sync_run
    # injected segment faults with retries exhausted: every bridged/joined
    # flush still routes through the resilience executor on the main thread
    # — the per-op fallback completes each step with identical numerics and
    # the ladder demotes the lazy tier after demote_after disruptive faults
    paddle.set_flags({"FLAGS_eager_async_compile": True})
    lazy._segment_cache.clear()
    lazy._pending_seg_compiles.clear()
    prof.reset_dispatch_counters()
    paddle.set_flags({"FLAGS_fault_inject": "execute:segment:p=1:x=9"})
    faulted, _ = _run(4)
    lazy.drain_async()
    c = prof.dispatch_counters()
    assert faulted == clean
    assert c["segment_per_op_fallbacks"] >= 1, c
    assert c["retry_exhausted"] >= 1, c
    assert c["ladder_demotions"] >= 1, c
    paddle.set_flags({"FLAGS_fault_inject": ""})
