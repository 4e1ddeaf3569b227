"""Sharded whole-step capture (mesh-aware FLAGS_eager_step_capture).

The capture controller (core/lazy.py) re-arming on a NamedSharding-carrying
trainer and replaying ONE donated multi-chip program per step on the
8-virtual-device CPU mesh (conftest forces
--xla_force_host_platform_device_count=8):

- dp2×mp2: steady state is 1 captured-sharded replay per step, params +
  optimizer state donated only because the analysis.sharding per-shard
  donation_safety pass proved every donated position at build time;
- numerics are BITWISE identical to ShardedTrainStep at matched specs
  (same mesh, same param specs, same dp-sharded batch) — the captured
  program is the same GSPMD program, fused;
- a world=1 mesh routes through the plain single-chip captured tier
  (capture_sharded_* counters stay 0) with numerics bitwise-equal to the
  unmeshed capture;
- an unprovable donation verdict is a COUNTED non-donated fallback
  (capture_donation_fallbacks), never a crash or a tier loss;
- the resilience ladder demotes the sharded captured tier on repeated
  replay faults and re-promotes after cooldown, final numerics bitwise
  equal to the fault-free run;
- a pipelined (pp>1) mesh refuses capture structurally
  (pipelined_mesh) and trains on at the lazy tier.
"""
import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.profiler as prof
import paddle_tpu.resilience as res
from paddle_tpu.core import lazy
from paddle_tpu.parallel import topology
from paddle_tpu.parallel.sharding import ShardedTrainStep, shard_params


@pytest.fixture
def sharded_capture_mode():
    """dp2×mp2 mesh + synchronous capture, fully restored on exit — the
    global mesh is cleared so unrelated tests never see NamedShardings."""
    mesh = topology.init_mesh(dp=2, mp=2)
    lazy._tls.observer = None
    lazy._capture_cache.clear()
    res.reset()
    prof.reset_dispatch_counters()
    paddle.set_flags({
        "FLAGS_eager_lazy_dispatch": True,
        "FLAGS_eager_step_capture": True,
        "FLAGS_eager_async_compile": False,
        "FLAGS_fault_inject": "",
        "FLAGS_retry_backoff_ms": 0.0,
    })
    try:
        yield mesh
    finally:
        lazy.flush_if_pending("test_teardown")
        lazy.drain_async()
        paddle.set_flags({
            "FLAGS_eager_lazy_dispatch": False,
            "FLAGS_eager_step_capture": True,
            "FLAGS_eager_async_compile": True,
            "FLAGS_fault_inject": "",
            "FLAGS_retry_max": 2,
            "FLAGS_retry_backoff_ms": 5.0,
            "FLAGS_ladder_demote_after": 2,
            "FLAGS_ladder_cooldown_steps": 8,
        })
        lazy._tls.observer = None
        res.reset()
        topology.set_mesh(None)


def _trainer(mesh=None, seed=0, bsz=4):
    """MLP trainer; with a mesh: TP spec on the first weight, params
    sharded, and BOTH batch tensors dp-placed (the capture contract — jax
    refuses differently-committed args in one program)."""
    paddle.seed(seed)
    model = paddle.nn.Sequential(
        paddle.nn.Linear(8, 16), paddle.nn.ReLU(), paddle.nn.Linear(16, 4)
    )
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    rng = np.random.default_rng(7)
    x = paddle.to_tensor(rng.standard_normal((bsz, 8)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 4, (bsz,)))
    if mesh is not None:
        if topology.axis_size("mp", mesh) > 1:
            model[0].weight.dist_spec = (None, "mp")
        shard_params(model, mesh)
        batch_sh = NamedSharding(mesh, P(("dp",)))
        x._value = jax.device_put(x._value, batch_sh)
        y._value = jax.device_put(y._value, batch_sh)

    def step():
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return model, opt, step, (x, y)


def _snapshot(model, opt):
    params = [np.asarray(p.numpy()) for p in model.parameters()]
    states = []
    for p in model.parameters():
        st = opt._accumulators.get(id(p)) or {}
        states.append({k: np.asarray(v) for k, v in st.items()})
    return params, states


def _assert_bitwise(a, b):
    pa, sa = a
    pb, sb = b
    for i, (x, y) in enumerate(zip(pa, pb)):
        assert np.array_equal(x, y), f"param {i} differs"
    for i, (x, y) in enumerate(zip(sa, sb)):
        assert sorted(x) == sorted(y)
        for k in x:
            assert np.array_equal(x[k], y[k]), f"state {i}/{k} differs"


# ---------------------------------------------------------------------------
# steady state: ONE donated sharded replay per step on dp2×mp2
# ---------------------------------------------------------------------------
def test_sharded_capture_one_donated_replay_per_step(sharded_capture_mode):
    _model, _opt, step, _ = _trainer(sharded_capture_mode)
    for _ in range(4):  # warmup: 2 observed steps arm, then build + replay
        step()
    c0 = prof.dispatch_counters()
    assert c0["capture_sharded_builds"] == 1, c0
    assert c0["capture_sharded_replays"] >= 1, c0
    assert c0["capture_donation_fallbacks"] == 0, c0
    st = lazy.step_capture_state()
    assert st["tier"] == "captured-sharded", st
    assert st["mesh"], st  # mesh tag published (dp2mp2 fingerprint family)
    assert st["donated"] is True, st  # per-shard donation proof carried
    # steady state: exactly one program, and it is the sharded replay
    c = prof.measure_programs(step, warmup=1)
    assert c["programs"] == 1, c
    assert c["capture_sharded_replays"] == 1, c
    assert c["capture_builds"] == 0, c  # cached executable, no rebuild
    assert c["_capture_state"]["armed"] is True
    # the donation verdicts the proof ran on are queryable post-hoc
    verdicts = lazy.captured_step_donation_verdicts()
    assert verdicts and all(v["proven"] for v in verdicts)


def test_sharded_capture_bitwise_vs_sharded_train_step(sharded_capture_mode):
    mesh = sharded_capture_mode
    N = 6
    model, opt, step, _ = _trainer(mesh)
    for _ in range(N):
        step()
    assert prof.dispatch_counters()["capture_sharded_replays"] >= 1
    captured = _snapshot(model, opt)
    # reference: the explicit GSPMD step at matched specs, capture off
    lazy.flush_if_pending("swap_to_reference")
    paddle.set_flags({"FLAGS_eager_lazy_dispatch": False})
    model2, opt2, _step2, (x2, y2) = _trainer(mesh)
    sts = ShardedTrainStep(model2, paddle.nn.CrossEntropyLoss(), opt2,
                           mesh=mesh)
    for _ in range(N):
        sts(x2, y2)
    _assert_bitwise(captured, _snapshot(model2, opt2))


def test_world1_mesh_is_single_chip_capture(sharded_capture_mode):
    """A 1-device mesh carries NamedShardings but no multi-chip layout:
    capture must take the plain single-chip tier, bitwise equal to the
    unmeshed capture of the same trainer."""
    topology.set_mesh(None)
    mesh1 = topology.init_mesh(dp=1)
    assert int(mesh1.devices.size) == 1
    N = 6
    model, opt, step, _ = _trainer(mesh1)
    for _ in range(N):
        step()
    c = prof.dispatch_counters()
    assert c["capture_replays"] >= 1, c
    assert c["capture_sharded_builds"] == 0, c  # world=1: no sharded tier
    assert lazy.step_capture_state()["tier"] == "captured"
    meshed = _snapshot(model, opt)
    # reference: same trainer, no mesh at all
    lazy.flush_if_pending("swap_to_reference")
    lazy._capture_cache.clear()
    topology.set_mesh(None)
    prof.reset_dispatch_counters()
    model2, opt2, step2, _ = _trainer(mesh=None)
    for _ in range(N):
        step2()
    assert prof.dispatch_counters()["capture_replays"] >= 1
    _assert_bitwise(meshed, _snapshot(model2, opt2))


# ---------------------------------------------------------------------------
# donation is proof-carrying: unprovable -> counted non-donated fallback
# ---------------------------------------------------------------------------
def test_donation_unproven_is_counted_nondonated_fallback(
        sharded_capture_mode, monkeypatch):
    from paddle_tpu.analysis import memory as amem

    real = amem.donation_verdicts

    def unproven(ctx):
        out = []
        for v in real(ctx):
            v = dict(v)
            v["proven"] = False
            v.setdefault("diags", []).append("test_forced_unproven")
            out.append(v)
        return out

    monkeypatch.setattr(amem, "donation_verdicts", unproven)
    _model, _opt, step, _ = _trainer(sharded_capture_mode)
    for _ in range(4):
        step()
    c = prof.dispatch_counters()
    assert c["capture_donation_fallbacks"] >= 1, c
    assert c["capture_sharded_replays"] >= 1, c  # tier kept, donation off
    st = lazy.step_capture_state()
    assert st["tier"] == "captured-sharded", st
    assert st["donated"] is False, st
    # still one program per step — losing the proof costs memory, not tier
    c = prof.measure_programs(step, warmup=1)
    assert c["programs"] == 1, c
    assert c["capture_sharded_replays"] == 1, c


# ---------------------------------------------------------------------------
# resilience ladder at the sharded captured tier
# ---------------------------------------------------------------------------
def test_ladder_demotion_at_sharded_tier_recovers_bitwise(
        sharded_capture_mode):
    mesh = sharded_capture_mode
    paddle.set_flags({
        "FLAGS_retry_max": 1,
        "FLAGS_ladder_demote_after": 2,
        "FLAGS_ladder_cooldown_steps": 3,
    })
    model, opt, step, _ = _trainer(mesh)
    total = 0
    for _ in range(4):  # arm + replay at the sharded tier
        step()
        total += 1
    assert prof.dispatch_counters()["capture_sharded_replays"] >= 1
    # unrecoverable faults at the captured replay (x=9 > retry budget):
    # each faulted replay is a counted fallback to the 3-program path plus
    # one disruptive ladder fault; demote_after of them demote the
    # (signature, mesh) rung
    paddle.set_flags({"FLAGS_fault_inject": "execute:captured:p=1:x=9"})
    for _ in range(8):
        step()
        total += 1
        if prof.dispatch_counters()["ladder_demotions"]:
            break
    c = prof.dispatch_counters()
    assert c["capture_fallbacks"] >= 2, c
    assert c["ladder_demotions"] >= 1, c
    assert res.state()["ladder"]["demoted"]
    paddle.set_flags({"FLAGS_fault_inject": ""})
    replays_at_demotion = c["capture_sharded_replays"]
    # cooldown passes -> re-promoted -> the SHARDED replay resumes
    for _ in range(8):
        step()
        total += 1
    assert (prof.dispatch_counters()["capture_sharded_replays"]
            > replays_at_demotion)
    faulted = _snapshot(model, opt)
    # fault-free reference over the same number of steps: bitwise — the
    # fallback path and the demoted rungs are the same numerics
    lazy.flush_if_pending("swap_to_reference")
    lazy._capture_cache.clear()
    res.reset()
    prof.reset_dispatch_counters()
    model2, opt2, step2, _ = _trainer(mesh)
    for _ in range(total):
        step2()
    _assert_bitwise(faulted, _snapshot(model2, opt2))


# ---------------------------------------------------------------------------
# pipelined mesh: structural refusal, training continues at the lazy tier
# ---------------------------------------------------------------------------
def test_pp_mesh_refuses_capture_and_trains_on(sharded_capture_mode):
    topology.set_mesh(None)
    mesh = topology.init_mesh(pp=2, dp=2)
    model, opt, step, _ = _trainer(mesh)
    losses = [float(step()) for _ in range(4)]
    c = prof.dispatch_counters()
    assert c["capture_sharded_builds"] == 0, c
    assert c["capture_sharded_replays"] == 0, c
    reasons = dict(c["capture_fallback_reasons"])
    assert reasons.get("pipelined_mesh", 0) >= 1, reasons
    assert all(np.isfinite(l) for l in losses)  # still trains, lazy tier
