"""Proof-carrying parity: the cross-tier equivalence prover + the new
determinism / collective_schedule passes (paddle_tpu/analysis/equivalence.py).

Covers the ISSUE-19 surface end-to-end:

- prover units: alpha-rename + commutative-operand ordering + literal
  folding + stop_gradient insertion prove rewritten programs EQUIVALENT;
  a genuinely different program yields a structured first-divergence
  diagnostic; declared extra trailing outputs; scan-body canonicalization;
  remat (jax.checkpoint) duplicates under prevent_cse canonicalize away;
- custom_vjp/custom_jvp call jaxprs are flat-inlined (satellite 1), so the
  prover sees through the wrapper;
- the pass registry lists all 12 passes in order, run_passes sorts by
  severity and rejects unknown names (satellite 3);
- determinism pass seeded positives AND negatives: duplicate-capable float
  scatter-add vs unique_indices / gather-transpose exemption, non-pow2 vs
  pow2 psum groups, reused vs split PRNG keys, host callbacks;
- collective_schedule: a collective under an axis_index-derived cond is an
  ERROR, a rank-invariant predicate is silent; schedule_of ordering;
- FLAGS_check_programs=2 certifies captured-step ≡ 3-program composition
  for the MLP, LeNet, and GPT probes (single-chip AND dp2×mp2
  sharded-captured) BEFORE the first donated replay; a forced-divergence
  fixture produces the counted verification_failed fallback + structured
  diagnostic; an unprovable reference falls through the counted
  _CaptureIneligible ladder with the step still completing;
- the serving ladder certifies donated rung ≡ plain retry rung once per
  bucket; planner-guided remat certifies planned ≡ unplanned
  (step._plan_certificate).

All CPU (conftest pins JAX_PLATFORMS=cpu with 8 virtual devices).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.profiler as prof
from paddle_tpu import analysis as A
from paddle_tpu.analysis import ProgramVerificationError, Severity
from paddle_tpu.analysis import equivalence as eq
from paddle_tpu.analysis.sharding import schedule_of
from paddle_tpu.core import lazy
from paddle_tpu.parallel import topology
from paddle_tpu.parallel.sharding import shard_params

F32 = jnp.float32
SPECS2 = [jax.ShapeDtypeStruct((4, 3), F32)] * 2


# ---------------------------------------------------------------------------
# prover units
# ---------------------------------------------------------------------------
def _f(x, y):
    a = x * 2.0
    return a + y, jnp.max(a, axis=0)


def test_prover_commutes_folds_and_elides_stop_gradient():
    def g(x, y):  # same function: commuted operands, folded literal, sg
        a = jax.lax.stop_gradient(x * (1.0 + 1.0))
        a = x * (1.0 + 1.0)
        return y + a, jnp.max(a, axis=0)

    cert = eq.certify_callables(_f, g, SPECS2, label_a="f", label_b="g")
    assert cert.equivalent, cert.divergence
    s = cert.summary()
    assert "EQUIVALENT" in s and "f ≡ g" in s
    assert cert.divergence is None


def test_prover_divergence_is_a_structured_diagnostic():
    def h(x, y):  # diverges: scale 3.0 instead of 2.0
        a = x * 3.0
        return a + y, jnp.max(a, axis=0)

    cert = eq.certify_callables(_f, h, SPECS2, label_a="f", label_b="h")
    assert not cert.equivalent
    assert "DIVERGENT" in cert.summary()
    d = cert.divergence
    assert d is not None
    assert d.pass_name == "equivalence"
    assert d.severity == Severity.ERROR
    assert "diverge" in d.message


def test_prover_allows_declared_extra_trailing_outputs():
    def f3(x, y):  # the telemetry-triple shape: 3 extra trailing outputs
        r = _f(x, y)
        return r + (jnp.sum(x), F32(0.0), F32(1.0))

    cert = eq.certify_callables(f3, _f, SPECS2, extra_outputs_a=3)
    assert cert.equivalent, cert.divergence
    # but NOT undeclared: the output arities genuinely differ
    cert2 = eq.certify_callables(f3, _f, SPECS2)
    assert not cert2.equivalent


def test_prover_canonicalizes_scan_bodies():
    def s1(x, y):
        def body(c, _):
            return c * 2.0 + y.sum(), None

        return jax.lax.scan(body, x, None, length=3)[0]

    def s2(x, y):  # commuted + folded inside the scan body
        def body(c, _):
            return y.sum() + (1.0 + 1.0) * c, None

        return jax.lax.scan(body, x, None, length=3)[0]

    def s3(x, y):  # diverges inside the body
        def body(c, _):
            return c * 2.5 + y.sum(), None

        return jax.lax.scan(body, x, None, length=3)[0]

    assert eq.certify_callables(s1, s2, SPECS2).equivalent
    cert = eq.certify_callables(s1, s3, SPECS2)
    assert not cert.equivalent
    assert cert.divergence is not None


def test_prover_canonicalizes_remat_duplicates():
    def inner(x):
        return jnp.tanh(x @ x.T)

    def plain(x, y):
        return jax.grad(lambda v: inner(v).sum())(x)

    def remat(x, y):
        return jax.grad(lambda v: jax.checkpoint(inner)(v).sum())(x)

    cert = eq.certify_callables(plain, remat, SPECS2,
                                label_a="plain", label_b="remat")
    assert cert.equivalent, cert.divergence


# ---------------------------------------------------------------------------
# satellite 1: custom_vjp call jaxprs flat-inline, the prover sees through
# ---------------------------------------------------------------------------
@jax.custom_vjp
def _cv(x):
    return jnp.sin(x) * 2.0


def _cv_fwd(x):
    return _cv(x), jnp.cos(x)


def _cv_bwd(res, g):
    return (res * g * 2.0,)


_cv.defvjp(_cv_fwd, _cv_bwd)


def test_custom_vjp_jaxprs_are_flat_inlined():
    def loss_grad(x):
        return jax.grad(lambda v: _cv(v).sum())(x)

    spec = jax.ShapeDtypeStruct((4,), F32)
    closed = jax.make_jaxpr(loss_grad)(spec)
    ctx = A.Context(closed, [("arg", "a0")], "probe")
    names = [op.name for op in ctx.ops]
    # the primal sin AND the custom-bwd cos both reach the flat IR — no
    # opaque custom_vjp_call op survives inlining
    assert "sin" in names and "cos" in names, names
    assert not any("custom_vjp" in n for n in names), names


def test_prover_sees_through_custom_vjp_wrapper():
    def plain(x):
        return jnp.sin(x) * 2.0

    spec = jax.ShapeDtypeStruct((4,), F32)
    cert = eq.certify_callables(_cv, plain, [spec])
    assert cert.equivalent, cert.divergence


# ---------------------------------------------------------------------------
# satellite 3: the pass registry
# ---------------------------------------------------------------------------
EXPECTED_PASSES = [
    "dtype_check", "dead_code", "redundant_ops", "numeric_hazards",
    "launch_budget", "determinism", "memory_budget", "donation_safety",
    "collective_cost", "resharding_lint", "collective_schedule",
    "equivalence",
]


def test_pass_registry_lists_all_passes_in_order():
    assert A.pass_names() == EXPECTED_PASSES


def test_run_passes_sorts_by_severity_then_pass():
    def fn(x):
        dead = x * 1.0  # redundant_ops WARNING; result unused -> dead_code
        return jnp.log(x)  # unguarded log over a raw feed -> ERROR

    closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((4,), F32))
    ctx = A.Context(closed, [("arg", "a0")], "probe")
    diags = A.run_passes(
        ctx, ["memory_budget", "dead_code", "numeric_hazards"])
    assert len(diags) >= 2
    sevs = [int(d.severity) for d in diags]
    assert sevs == sorted(sevs, reverse=True)
    assert int(diags[0].severity) == int(Severity.ERROR)
    assert diags[0].pass_name == "numeric_hazards"
    assert any(d.pass_name == "dead_code" for d in diags)
    # ties broken by pass name (stable CI output)
    for a, b in zip(diags, diags[1:]):
        if a.severity == b.severity:
            assert a.pass_name <= b.pass_name


def test_run_passes_rejects_unknown_pass():
    closed = jax.make_jaxpr(lambda x: x + 1.0)(jax.ShapeDtypeStruct((4,), F32))
    ctx = A.Context(closed, [("arg", "a0")], "probe")
    with pytest.raises(ValueError, match="unknown analysis pass"):
        A.run_passes(ctx, ["no_such_pass"])


# ---------------------------------------------------------------------------
# determinism pass: seeded positives AND negatives
# ---------------------------------------------------------------------------
def _diags_of(fn, *specs, passes):
    closed = jax.make_jaxpr(fn)(*specs)
    ctx = A.Context(
        closed,
        [("arg", f"a{i}") for i in range(len(closed.jaxpr.invars))],
        "probe",
    )
    return A.run_passes(ctx, list(passes))


def test_determinism_flags_duplicate_capable_float_scatter_add():
    def bad(x, idx):
        return jnp.zeros((8,), F32).at[idx].add(x)

    d = _diags_of(bad, jax.ShapeDtypeStruct((16,), F32),
                  jax.ShapeDtypeStruct((16,), jnp.int32),
                  passes=["determinism"])
    assert any("duplicate" in x.message for x in d), d


def test_determinism_unique_indices_scatter_is_silent():
    def ok(x):
        return jnp.zeros((16,), F32).at[jnp.arange(16)].add(
            x, unique_indices=True)

    assert _diags_of(ok, jax.ShapeDtypeStruct((16,), F32),
                     passes=["determinism"]) == []


def test_determinism_exempts_gather_transpose_scatter():
    # the embedding-gradient idiom: autodiff transposes take/gather into a
    # scatter-add whose indices are the gather's own — not a new hazard
    def emb_grad(table, idx):
        return jax.grad(
            lambda t, i: jnp.take(t, i, axis=0).sum())(table, idx)

    assert _diags_of(emb_grad, jax.ShapeDtypeStruct((32, 4), F32),
                     jax.ShapeDtypeStruct((16,), jnp.int32),
                     passes=["determinism"]) == []


def test_determinism_flags_non_pow2_psum_group():
    devs = np.array(jax.devices())
    mesh6 = Mesh(devs[:6], ("dp",))

    def psum6(x):
        return shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh6,
                         in_specs=P("dp"), out_specs=P())(x)

    d = _diags_of(psum6, jax.ShapeDtypeStruct((12,), F32),
                  passes=["determinism"])
    assert any("power-of-two" in x.message for x in d), d

    mesh8 = Mesh(devs, ("dp",))

    def psum8(x):
        return shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh8,
                         in_specs=P("dp"), out_specs=P())(x)

    assert _diags_of(psum8, jax.ShapeDtypeStruct((16,), F32),
                     passes=["determinism"]) == []


def test_determinism_flags_reused_rng_key():
    def reuse(key):
        return jax.random.normal(key, (4,)) + jax.random.uniform(key, (4,))

    d = _diags_of(reuse, jax.random.PRNGKey(0), passes=["determinism"])
    assert any("IDENTICAL random streams" in x.message for x in d), d

    def split(key):
        k1, k2 = jax.random.split(key)
        return jax.random.normal(k1, (4,)) + jax.random.uniform(k2, (4,))

    assert _diags_of(split, jax.random.PRNGKey(0),
                     passes=["determinism"]) == []


def test_determinism_flags_host_callbacks():
    def cb(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct((4,), F32), x)

    d = _diags_of(cb, jax.ShapeDtypeStruct((4,), F32),
                  passes=["determinism"])
    assert any("callback" in x.message for x in d), d


# ---------------------------------------------------------------------------
# collective_schedule pass: SPMD rank-divergence
# ---------------------------------------------------------------------------
def _mesh4():
    return Mesh(np.array(jax.devices())[:4].reshape(2, 2), ("dp", "mp"))


def test_collective_under_rank_variant_cond_is_an_error():
    mesh = _mesh4()

    def rank_variant(x):
        def body(v):
            r = jax.lax.axis_index("dp")
            return jax.lax.cond(r == 0,
                                lambda u: jax.lax.psum(u, "mp"),
                                lambda u: u, v)

        return shard_map(body, mesh=mesh, in_specs=P("dp", "mp"),
                         out_specs=P("dp", "mp"), check_vma=False)(x)

    d = _diags_of(rank_variant, jax.ShapeDtypeStruct((4, 4), F32),
                  passes=["collective_schedule"])
    errs = [x for x in d if x.severity == Severity.ERROR]
    assert errs, d
    assert any("axis_index" in x.message for x in errs), errs


def test_collective_under_rank_invariant_cond_is_silent():
    mesh = _mesh4()

    def rank_invariant(x, n):
        def body(v, m):
            return jax.lax.cond(m > 0,
                                lambda u: jax.lax.psum(u, "mp"),
                                lambda u: u, v)

        return shard_map(body, mesh=mesh, in_specs=(P("dp", "mp"), P()),
                         out_specs=P("dp", "mp"), check_vma=False)(x, n)

    assert _diags_of(rank_invariant, jax.ShapeDtypeStruct((4, 4), F32),
                     jax.ShapeDtypeStruct((), jnp.int32),
                     passes=["collective_schedule"]) == []


def test_schedule_of_orders_the_collective_schedule():
    mesh = _mesh4()

    def two_colls(x):
        def body(v):
            return jax.lax.all_gather(jax.lax.psum(v, "mp"), "dp", tiled=True)

        return shard_map(body, mesh=mesh, in_specs=P("dp", "mp"),
                         out_specs=P(None, "mp"), check_vma=False)(x)

    closed = jax.make_jaxpr(two_colls)(jax.ShapeDtypeStruct((4, 4), F32))
    ctx = A.Context(closed, [("arg", "a0")], "probe")
    sched = schedule_of(ctx.ops)
    assert [r["kind"] for r in sched] == ["psum", "all_gather"], sched
    assert all(r["group_size"] >= 2 for r in sched)


# ---------------------------------------------------------------------------
# captured-step certification (FLAGS_check_programs=2)
# ---------------------------------------------------------------------------
@pytest.fixture
def certify_mode():
    """Synchronous capture with the prover armed; fully restored on exit."""
    # several suite files (fleet/auto_parallel/distributed) leave a global
    # mesh set; a single-chip certification drive must not inherit it
    topology.set_mesh(None)
    lazy._tls.observer = None
    lazy._capture_cache.clear()
    prof.reset_dispatch_counters()
    paddle.set_flags({
        "FLAGS_eager_lazy_dispatch": True,
        "FLAGS_eager_step_capture": True,
        "FLAGS_eager_async_compile": False,
        "FLAGS_check_programs": 2,
    })
    try:
        yield
    finally:
        lazy.flush_if_pending("test_teardown")
        lazy.drain_async()
        paddle.set_flags({
            "FLAGS_eager_lazy_dispatch": False,
            "FLAGS_eager_step_capture": True,
            "FLAGS_eager_async_compile": True,
            "FLAGS_check_programs": 0,
        })
        lazy._tls.observer = None


@pytest.fixture
def sharded_certify_mode(certify_mode):
    mesh = topology.init_mesh(dp=2, mp=2)
    try:
        yield mesh
    finally:
        topology.set_mesh(None)


def _mlp_trainer(seed=0, mesh=None, bsz=4):
    paddle.seed(seed)
    model = paddle.nn.Sequential(
        paddle.nn.Linear(8, 16), paddle.nn.ReLU(), paddle.nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    rng = np.random.default_rng(7)
    x = paddle.to_tensor(rng.standard_normal((bsz, 8)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 4, (bsz,)))
    if mesh is not None:
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

    return step


def _assert_certified(c, sharded=False):
    assert c["capture_equivalence_checks"] >= 1, c
    assert c["capture_equivalence_certified"] >= 1, c
    assert c["capture_equivalence_divergences"] == 0, c
    assert c["capture_replays"] >= 1, c
    if sharded:
        assert c["capture_sharded_builds"] >= 1, c
        assert c["capture_sharded_replays"] >= 1, c
    cert = lazy.captured_step_certificate()
    assert cert is not None and cert.equivalent
    assert "captured-step ≡ 3-program-composition" in cert.summary()
    return cert


def test_captured_mlp_step_is_certified_before_replay(certify_mode):
    step = _mlp_trainer()
    for _ in range(6):
        step()
    c = prof.dispatch_counters()
    # certification happened exactly once (first un-warmed replay attempt),
    # replays after the proof do not re-check
    assert c["capture_equivalence_checks"] == 1, c
    _assert_certified(c)


def test_captured_lenet_step_is_certified(certify_mode):
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((4, 1, 28, 28)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, (4,)))
    for _ in range(5):
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
    _assert_certified(prof.dispatch_counters())


def test_captured_gpt_step_is_certified(certify_mode):
    from paddle_tpu.models import (GPTConfig, GPTForPretraining,
                                   GPTPretrainingCriterion)

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4,
                    num_heads=8, max_seq_len=64, dropout=0.0,
                    attn_dropout=0.0)
    model = GPTForPretraining(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.integers(0, 1024, (1, 32)))
    y = paddle.to_tensor(rng.integers(0, 1024, (1, 32)))
    for _ in range(5):
        loss = crit(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
    cert = _assert_certified(prof.dispatch_counters())
    # the GPT step is a real program: the proof had work to do
    assert cert.outputs_compared > 100


def test_captured_sharded_mlp_step_is_certified(sharded_certify_mode):
    step = _mlp_trainer(mesh=sharded_certify_mode, bsz=8)
    for _ in range(8):
        step()
        if prof.dispatch_counters()["capture_sharded_replays"] >= 1:
            break
    _assert_certified(prof.dispatch_counters(), sharded=True)


def test_captured_sharded_gpt_step_is_certified(sharded_certify_mode):
    from paddle_tpu.models import (GPTConfig, GPTForPretraining,
                                   GPTPretrainingCriterion)

    mesh = sharded_certify_mode
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=32, dropout=0.0,
                    attn_dropout=0.0)
    model = GPTForPretraining(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    shard_params(model, mesh)
    batch_sh = NamedSharding(mesh, P(("dp",)))
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.integers(0, 256, (2, 16)))
    y = paddle.to_tensor(rng.integers(0, 256, (2, 16)))
    x._value = jax.device_put(x._value, batch_sh)
    y._value = jax.device_put(y._value, batch_sh)
    for _ in range(8):
        loss = crit(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        if prof.dispatch_counters()["capture_sharded_replays"] >= 1:
            break
    _assert_certified(prof.dispatch_counters(), sharded=True)


# ---------------------------------------------------------------------------
# seeded negative fixtures: forced divergence + unprovable reference
# ---------------------------------------------------------------------------
def _patched_build(mutate):
    """Wrap lazy._build_captured_step so the fresh entry's reference
    composition is sabotaged — the captured program itself stays intact,
    so any surviving replay would be numerically correct."""
    orig = lazy._build_captured_step

    def patched(rec, opt):
        entry = orig(rec, opt)
        mutate(entry)
        return entry

    return orig, patched


def test_forced_divergence_is_a_counted_fallback_with_diagnostic(
        certify_mode, monkeypatch):
    def mutate(entry):
        real_ref = entry.ref_fn

        def doubled_ref(*args):
            out = real_ref(*args)
            return jax.tree_util.tree_map(
                lambda a: a * 2.0
                if jnp.issubdtype(jnp.result_type(a), jnp.floating) else a,
                out)

        entry.ref_fn = doubled_ref

    orig, patched = _patched_build(mutate)
    monkeypatch.setattr(lazy, "_build_captured_step", patched)
    step = _mlp_trainer()
    with pytest.raises(ProgramVerificationError) as ei:
        for _ in range(6):
            step()
    c = prof.dispatch_counters()
    assert c["capture_equivalence_divergences"] == 1, c
    assert c["capture_equivalence_certified"] == 0, c
    assert dict(c["capture_fallback_reasons"]).get(
        "verification_failed") == 1, c
    # the step that tripped the wire still resolved on the 3-program path
    assert c["capture_fallbacks"] >= 1, c
    diags = ei.value.diagnostics
    assert diags and diags[0].pass_name == "equivalence"
    assert diags[0].severity == Severity.ERROR
    assert "divergence" in diags[0].message
    # no divergent certificate is ever exposed as "the captured step's"
    assert lazy.captured_step_certificate() is None


def test_unprovable_reference_falls_through_counted_ladder(
        certify_mode, monkeypatch):
    def mutate(entry):
        def broken_ref(*args):
            raise RuntimeError("reference composition unavailable")

        entry.ref_fn = broken_ref

    orig, patched = _patched_build(mutate)
    monkeypatch.setattr(lazy, "_build_captured_step", patched)
    step = _mlp_trainer()
    losses = [float(step().numpy()) for _ in range(6)]
    assert len(losses) == 6 and all(np.isfinite(losses))
    c = prof.dispatch_counters()
    assert c["capture_equivalence_unprovable"] >= 1, c
    assert c["capture_equivalence_certified"] == 0, c
    assert c["capture_replays"] == 0, c
    assert dict(c["capture_fallback_reasons"]).get(
        "equivalence_unprovable", 0) >= 1, c


# ---------------------------------------------------------------------------
# serving ladder: donated rung ≡ plain retry rung
# ---------------------------------------------------------------------------
def test_serve_rung_certified_once_per_bucket():
    lazy.reset_serve_programs()
    prof.reset_dispatch_counters()
    paddle.set_flags({"FLAGS_check_programs": 2})
    try:
        def decode_step(kv, x):
            return kv + x, (kv * x).sum()

        prog = lazy.serve_program(("decode", 16), decode_step,
                                  donate_argnums=(0,))
        kv = jnp.zeros((4, 16), F32)
        x = jnp.ones((4, 16), F32)
        kv2, _ = prog.run((kv, x), donate=True)
        c = prof.dispatch_counters()
        assert c["serve_equivalence_checks"] == 1, c
        assert c["serve_equivalence_certified"] == 1, c
        assert prog.certificate is not None and prog.certificate.equivalent
        assert "serve-donated ≡ serve-plain" in prog.certificate.summary()
        # replay: proven once, never re-checked
        prog.run((jnp.asarray(np.asarray(kv2)), x), donate=True)
        c = prof.dispatch_counters()
        assert c["serve_equivalence_checks"] == 1, c
        assert c["serve_capture_replays"] == 1, c
    finally:
        paddle.set_flags({"FLAGS_check_programs": 0})
        lazy.reset_serve_programs()


# ---------------------------------------------------------------------------
# planner-guided remat: planned ≡ unplanned (jit.compile_train_step)
# ---------------------------------------------------------------------------
def test_planned_step_certified_equivalent_to_unplanned():
    from paddle_tpu import jit, nn
    from paddle_tpu.analysis import plan as plan_mod

    plan_mod._reset_state()

    def build():
        paddle.seed(0)
        layers = []
        for _ in range(6):
            layers += [nn.Linear(256, 256), nn.GELU(approximate=True)]
        layers += [nn.Linear(256, 16)]
        m = nn.Sequential(*layers)
        o = paddle.optimizer.Adam(parameters=m.parameters(),
                                  learning_rate=1e-3)
        return m, o

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((512, 256)).astype("float32"))
    y = paddle.to_tensor(rng.integers(0, 16, (512,)).astype("int64"))
    m0, o0 = build()
    step0 = jit.compile_train_step(m0, nn.CrossEntropyLoss(), o0)
    unplanned = float(step0(x, y))
    peak_mb = step0.memory_plan().peak_bytes / (1 << 20)
    plan = step0.plan_remat(budget_mb=0.6 * peak_mb)
    assert plan.has_cuts

    paddle.set_flags({"FLAGS_check_programs": 2})
    try:
        m1, o1 = build()
        step1 = jit.compile_train_step(m1, nn.CrossEntropyLoss(), o1,
                                       memory_plan=plan)
        planned = float(step1(x, y))
        cert = step1._plan_certificate
        assert cert is not None and cert.equivalent, cert
        assert "planned-step ≡ unplanned-step" in cert.summary()
        # the proof canonicalized real remat duplicates away
        assert cert.n_ops[0] > cert.n_ops[1]
    finally:
        paddle.set_flags({"FLAGS_check_programs": 0})
    np.testing.assert_allclose(planned, unplanned, rtol=0, atol=0)
