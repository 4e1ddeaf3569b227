"""The SPMD sharding analyzer (analysis.sharding): per-shard memory &
donation proofs, the ring-ICI collective cost model, and resharding lints.

Golden byte counts here are exact integers — pure functions of shapes,
dtypes, and partition specs (no timing, no device measurement except the
one estimated-vs-measured contract test at the bottom). The meshes are the
CPU-simulated 8-device platform from conftest.
"""
import os
import sys
from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu._jax_compat import shard_map
from paddle_tpu.analysis.sharding import (
    check_sharded_step,
    collective_stats,
    parse_mesh,
    pipelined_step_context,
    ring_wire_bytes,
    shard_context,
    sharded_step_context,
)
from paddle_tpu.distributed import fleet

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))


def _dryrun():
    import multichip_dryrun

    return multichip_dryrun


# ---------------------------------------------------------------------------
# ring-ICI cost model: pure-function goldens
# ---------------------------------------------------------------------------
def test_ring_wire_bytes_golden():
    # psum: 2·B·(n-1)/n (reduce-scatter + all-gather ring phases)
    assert ring_wire_bytes("psum", 1024, 4) == 1536
    assert ring_wire_bytes("psum", 128, 2) == 128
    # all_gather: B_shard·(n-1)
    assert ring_wire_bytes("all_gather", 128, 2) == 128
    assert ring_wire_bytes("all_gather", 100, 8) == 700
    # reduce_scatter / all_to_all: B·(n-1)/n
    assert ring_wire_bytes("reduce_scatter", 256, 2) == 128
    assert ring_wire_bytes("all_to_all", 128, 2) == 64
    # ppermute: one hop, the full payload
    assert ring_wire_bytes("ppermute", 4096, 2) == 4096
    # degenerate groups and free collectives cost nothing
    assert ring_wire_bytes("psum", 1024, 1) == 0
    assert ring_wire_bytes("all_gather", 0, 8) == 0
    assert ring_wire_bytes("pbroadcast", 1024, 4) == 0


def test_parse_mesh():
    assert parse_mesh("dp=2,mp=2") == {"dp": 2, "mp": 2}
    assert parse_mesh({"pp": 2}) == {"pp": 2}


# ---------------------------------------------------------------------------
# the analysis IR sees through shard_map (scope inline, per-shard avals)
# ---------------------------------------------------------------------------
def _mesh22():
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    return Mesh(devs, ("dp", "mp"))


def _smap(body, out_specs=P("dp", "mp"), in_specs=(P("dp", "mp"),)):
    f = shard_map(body, mesh=_mesh22(), in_specs=in_specs,
                  out_specs=out_specs, axis_names={"dp", "mp"},
                  check_vma=False)
    return jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8, 16), jnp.float32))


def test_dead_op_inside_shard_map_reported():
    """Regression for the _sub_jaxprs shard_map fix: a dead op inside the
    shard_map body must be visible to the base analyzer (the body is
    recursed scope-style, not skipped or unsoundly call-inlined)."""
    def body(x):
        _dead = jnp.exp(x) * 3.0  # noqa: F841 — never used
        return x * 2.0

    closed = _smap(body)
    ctx = analysis.Context(closed, [("feed", "x")], "t")
    diags = analysis.run_passes(ctx, passes=["dead_code"])
    assert any(d.pass_name == "dead_code" and "shard_map" in d.op
               for d in diags), [str(d) for d in diags]
    # and the body's avals are per-shard, not global
    inner = [op for op in ctx.ops if "shard_map" in op.scope]
    assert inner and all(
        tuple(op.outvars[0].aval.shape) == (4, 8)
        for op in inner if op.name in ("exp", "mul")
    )


# ---------------------------------------------------------------------------
# collective classification: exact bytes for every kind (f32, so 4B/elem
# even though paddle_tpu enables x64 globally)
# ---------------------------------------------------------------------------
def _five_kind_program():
    def body(x):  # x per-shard f32[4, 8] = 128B
        a = jax.lax.psum(x, "dp")
        g = jax.lax.all_gather(a, "mp")  # -> [2, 4, 8]
        s = jax.lax.psum_scatter(g, "mp", scatter_dimension=0, tiled=True)
        t = jax.lax.all_to_all(s, "dp", split_axis=1, concat_axis=0,
                               tiled=True)
        return jax.lax.ppermute(t, "dp", perm=[(0, 1), (1, 0)])

    return _smap(body, out_specs=P(("dp",), None, ("mp",)))


def test_collective_golden_bytes_all_kinds():
    closed = _five_kind_program()
    ctx = shard_context(closed, [("feed", "x")], mesh="dp=2,mp=2",
                        in_specs=[P("dp", "mp")])
    got = {(r.kind, r.axes): (r.group_size, r.payload_bytes, r.wire_bytes)
           for r in ctx.collectives}
    assert got == {
        ("psum", ("dp",)): (2, 128, 128),            # 2·128·(2-1)/2
        ("all_gather", ("mp",)): (2, 128, 128),      # 128·(2-1)
        ("reduce_scatter", ("mp",)): (2, 256, 128),  # 256·(2-1)/2
        ("all_to_all", ("dp",)): (2, 128, 64),       # 128·(2-1)/2
        ("ppermute", ("dp",)): (2, 128, 128),        # one hop
    }
    assert sum(r.total_wire_bytes for r in ctx.collectives) == 576
    # the standalone helper agrees (classifies from shard_map mesh params,
    # no ShardContext required)
    assert collective_stats(closed) == {"comm_bytes": 576,
                                        "collective_count": 5}


def test_collective_cost_pass_reports_and_ratio_warns():
    closed = _five_kind_program()
    ctx = shard_context(closed, [("feed", "x")], mesh="dp=2,mp=2",
                        in_specs=[P("dp", "mp")])
    diags = analysis.run_passes(ctx, passes=["collective_cost"])
    info = [d for d in diags if d.severity == analysis.Severity.INFO]
    assert len(info) == 1
    assert info[0].data["comm_bytes"] == 576
    assert info[0].data["collective_count"] == 5
    assert info[0].data["comm_compute_ratio"] > 0
    assert len(info[0].data["collectives"]) == 5
    # a configured bytes/flop ceiling turns the report into a warning
    paddle.set_flags({"FLAGS_comm_ratio_warn": 1e-9})
    try:
        diags = analysis.run_passes(ctx, passes=["collective_cost"])
        assert any(d.severity == analysis.Severity.WARNING
                   and d.pass_name == "collective_cost" for d in diags)
    finally:
        paddle.set_flags({"FLAGS_comm_ratio_warn": 0.0})


# ---------------------------------------------------------------------------
# collective idioms: redundant_ops (base mode) / resharding_lint (mesh mode)
# ---------------------------------------------------------------------------
def test_redundant_psum_of_psum_base_mode():
    closed = _smap(lambda x: jax.lax.psum(jax.lax.psum(x, "dp"), "dp"))
    diags = analysis.run_passes(
        analysis.Context(closed, [("feed", "x")], "t"),
        passes=["redundant_ops", "resharding_lint"])
    assert [d.pass_name for d in diags] == ["redundant_ops"]
    assert "psum∘psum over the same axis" in diags[0].message


def test_staged_two_axis_psum_not_flagged():
    """A staged reduction psum(psum(x, dp), mp) is the canonical way to
    all-reduce over two axes — const-fold-style suppression, no warning."""
    closed = _smap(lambda x: jax.lax.psum(jax.lax.psum(x, "dp"), "mp"))
    for ctx in (
        analysis.Context(closed, [("feed", "x")], "t"),
        shard_context(closed, [("feed", "x")], mesh="dp=2,mp=2",
                      in_specs=[P("dp", "mp")]),
    ):
        diags = analysis.run_passes(
            ctx, passes=["redundant_ops", "resharding_lint"])
        assert not [d for d in diags if "psum" in d.message], \
            [str(d) for d in diags]


def test_gather_then_slice_round_trip_flagged():
    def body(x):
        g = jax.lax.all_gather(x, "mp", axis=1, tiled=True)  # [4, 16]
        return jax.lax.slice(g, (0, 0), (4, 8))  # back to the local shard

    closed = _smap(body)
    base = analysis.run_passes(
        analysis.Context(closed, [("feed", "x")], "t"),
        passes=["redundant_ops", "resharding_lint"])
    assert [d.pass_name for d in base] == ["redundant_ops"]
    mesh = analysis.run_passes(
        shard_context(closed, [("feed", "x")], mesh="dp=2,mp=2",
                      in_specs=[P("dp", "mp")]),
        passes=["redundant_ops", "resharding_lint"])
    assert [d.pass_name for d in mesh] == ["resharding_lint"]
    assert "round trip" in mesh[0].message


def test_loop_invariant_collective_in_scan_flagged():
    def body(x):
        def sbody(c, _):
            return c + jax.lax.psum(x, "dp").sum(), None

        out, _ = jax.lax.scan(sbody, 0.0, None, length=4)
        return x + out

    closed = _smap(body)
    diags = analysis.run_passes(
        shard_context(closed, [("feed", "x")], mesh="dp=2,mp=2",
                      in_specs=[P("dp", "mp")]),
        passes=["resharding_lint"])
    hoist = [d for d in diags if "loop-invariant" in d.message]
    assert len(hoist) == 1 and "scan" in hoist[0].op


def test_replicated_output_with_sharded_declared_spec_flagged():
    def body(x):
        return jax.lax.psum(x, ("dp", "mp"))

    f = shard_map(body, mesh=_mesh22(), in_specs=(P("dp", "mp"),),
                  out_specs=P(), axis_names={"dp", "mp"}, check_vma=False)
    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8, 16), jnp.float32))
    diags = analysis.run_passes(
        shard_context(closed, [("feed", "x")], mesh="dp=2,mp=2",
                      in_specs=[P("dp", "mp")], out_specs=[P("dp", None)]),
        passes=["resharding_lint"])
    assert any("replicated inside the program" in d.message for d in diags)


# ---------------------------------------------------------------------------
# per-shard memory: plan_memory(mesh=...) shrinks the estimate
# ---------------------------------------------------------------------------
def test_plan_memory_mesh_kwarg_reports_per_shard():
    from paddle_tpu.analysis import memory as mem

    def fn(x):
        return jnp.tanh(x) * 2.0

    closed = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((8, 1024), jnp.float32))
    ctx = analysis.Context(closed, [("feed", "x")], "t")
    global_plan = mem.plan_memory(ctx)
    shard_plan = mem.plan_memory(ctx, mesh="dp=8",
                                 in_specs=[P("dp", None)])
    assert shard_plan.peak_bytes * 8 == global_plan.peak_bytes
    # the memory_budget report is labeled per device under a mesh
    sctx = shard_context(closed, [("feed", "x")], mesh="dp=8",
                         in_specs=[P("dp", None)], memory_budget_mb=64.0)
    diags = analysis.run_passes(sctx, passes=["memory_budget"])
    assert any("per device" in d.message for d in diags)


# ---------------------------------------------------------------------------
# GPT hybrid steps: golden collective bytes and per-shard proofs (the
# multichip_dryrun builders — same fleet bootstrap as the CLI gate)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def gpt_dp2mp2():
    md = _dryrun()
    step, specs = md.build_model({"dp": 2, "mp": 2})
    ctx = sharded_step_context(step, specs)
    return md, step, specs, ctx


def test_gpt_dp2mp2_collective_goldens(gpt_dp2mp2):
    """Exact bytes-on-wire for the dp=2×mp=2 hybrid GPT step: TP activation
    all-reduces over mp, dp grad all-reduces, embedding gathers. Pure
    function of shapes+specs — any drift means the sharding propagation or
    the cost model changed."""
    _, _, _, ctx = gpt_dp2mp2
    kinds = Counter((r.kind, r.axes) for r in ctx.collectives)
    assert kinds == {
        ("psum", ("mp",)): 12,
        ("all_gather", ("mp",)): 8,
        ("all_gather", ("dp", "sharding")): 7,
        ("psum", ("dp", "sharding", "sep")): 7,
        ("all_gather", ("sep",)): 7,
        ("psum", ("sep",)): 2,
        ("psum", ("dp", "sharding")): 1,
    }
    assert sum(r.total_wire_bytes for r in ctx.collectives) == 341632
    assert sum(r.count for r in ctx.collectives) == 44
    # every record obeys the ring model exactly
    for r in ctx.collectives:
        assert r.wire_bytes == ring_wire_bytes(
            r.kind, r.payload_bytes, r.group_size)


def test_gpt_dp2mp2_donation_and_per_shard_budget(gpt_dp2mp2):
    md, step, specs, ctx = gpt_dp2mp2
    diags = check_sharded_step(step, specs)
    assert not [d for d in diags
                if d.severity == analysis.Severity.ERROR], \
        [str(d) for d in diags]
    ver = [d for d in diags if d.pass_name == "donation_safety"]
    # a static verdict for every donated position (params + opt state)
    assert len(ctx.donated) > 0
    assert any(
        f"all {len(ctx.donated)} donated argument positions verified"
        in d.message for d in ver), [str(d) for d in ver]
    mb = [d for d in diags if d.pass_name == "memory_budget"]
    assert any("per device" in d.message for d in mb)
    cc = [d for d in diags if d.pass_name == "collective_cost"]
    assert cc and cc[0].data["comm_bytes"] == 341632


def test_gpt_dp2mp2_estimate_matches_measured_per_device(gpt_dp2mp2):
    """The ±10% contract, per shard: the analyzer's boundary estimate
    (per-shard inputs + consts + escaping outputs) matches the bytes one
    device actually holds after a real step on the simulated mesh (same
    methodology as the PR 4 single-chip captured-step test; the peak adds
    only backward transients XLA frees before exit)."""
    from paddle_tpu.analysis import memory as mem

    md, step, specs, ctx = gpt_dp2mp2
    plan = mem.plan_memory(ctx)
    x = paddle.randint(0, md.VOCAB, [int(specs[0].shape[0]), md.SEQ])
    y = paddle.randint(0, md.VOCAB, [int(specs[0].shape[0]), md.SEQ])
    loss = step(x, y)
    jax.block_until_ready(loss._value)
    # measure only THIS step's arrays (state + batch + loss), not
    # jax.live_arrays() — under the full suite other test modules keep
    # arrays alive on device 0 and would inflate the measurement
    dev0 = jax.devices()[0]
    measured, seen = 0, set()
    for leaf in jax.tree_util.tree_leaves(
            (step._params, step._buffers, step._opt_state, x, y, loss)):
        # Tensor._value is the jax array; but on a raw jax ArrayImpl
        # ._value is a numpy conversion, so prefer the leaf itself
        arr = leaf if hasattr(leaf, "addressable_shards") \
            else getattr(leaf, "_value", leaf)
        if id(arr) in seen:
            continue
        seen.add(id(arr))
        for sh in getattr(arr, "addressable_shards", []):
            if sh.device == dev0 and sh.data is not None:
                measured += int(sh.data.size * sh.data.dtype.itemsize)
    assert measured > 0
    assert abs(plan.boundary_bytes - measured) <= 0.10 * measured, (
        plan.boundary_bytes, measured)
    assert plan.peak_bytes >= plan.boundary_bytes


def test_gpt_pp2_collective_goldens():
    """The full GPipe pipeline step under pp=2 (fleet back-fills dp=4 on the
    8-device platform). Forward: per-microbatch stage-boundary ppermute of
    the per-shard hidden, the pp loss-sum, the dp loss-mean. Backward: the
    transposes of those three, then one grad all-reduce per parameter —
    over dp for the pp-stacked block params, over (pp, dp) for the
    replicated embedding / final-norm params."""
    md = _dryrun()
    step, specs = md.build_model_pp({"pp": 2})
    ctx = pipelined_step_context(step, specs)
    assert ctx.mesh_axes["pp"] == 2 and ctx.mesh_axes["dp"] == 4
    assert len(ctx.donated) == 80  # the full step, not the forward program
    got = Counter((r.kind, r.axes, r.group_size, r.payload_bytes,
                   r.wire_bytes, r.count) for r in ctx.collectives)
    schedule = {
        # hidden per shard: f32[2, 16, 32] = 4096B, once per microbatch;
        # forward and its transpose
        ("ppermute", ("pp",), 2, 4096, 4096, 2): 2,
        ("psum", ("pp",), 2, 4, 4, 1): 2,   # scalar loss sum over stages
        ("psum", ("dp",), 4, 4, 6, 1): 2,   # loss pmean: 2·4·(4-1)/4
    }
    for k, n in schedule.items():
        assert got[k] == n, (k, got)
    grads = Counter({k: n for k, n in got.items() if k not in schedule})
    by_axes = Counter()
    for (kind, axes, *_), n in grads.items():
        assert kind == "psum"
        by_axes[axes] += n
    assert by_axes == {("dp",): 12, ("pp", "dp"): 4}
    assert sum(r.total_wire_bytes for r in ctx.collectives) == 298324
    diags = analysis.run_passes(ctx)
    assert not [d for d in diags if d.severity == analysis.Severity.ERROR]


# ---------------------------------------------------------------------------
# attribution integration: static profiles carry the comm fields
# ---------------------------------------------------------------------------
def test_attribution_static_profile_carries_comm_bytes():
    from paddle_tpu.profiler.attribution import _jaxpr_profile

    prof = _jaxpr_profile(_five_kind_program())
    assert prof["comm_bytes"] == 576
    assert prof["collective_count"] == 5
    # a collective-free program reports zeros, not missing keys
    plain = jax.make_jaxpr(lambda x: x * 2.0)(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    prof0 = _jaxpr_profile(plain)
    assert prof0["comm_bytes"] == 0 and prof0["collective_count"] == 0


def test_check_programs_gate_warns_on_sharded_step(gpt_dp2mp2):
    """FLAGS_check_programs=1 surfaces the per-shard findings as Python
    warnings before the step's first compile (same enforcement point as
    Executor.run) — exercised directly so no XLA compile is paid here."""
    import warnings

    md, step, specs, _ = gpt_dp2mp2
    x = paddle.randint(0, md.VOCAB, [int(specs[0].shape[0]), md.SEQ])
    y = paddle.randint(0, md.VOCAB, [int(specs[0].shape[0]), md.SEQ])
    paddle.set_flags({"FLAGS_check_programs": 1})
    try:
        with warnings.catch_warnings(record=True) as seen:
            warnings.simplefilter("always")
            step._check_programs((x, y))
    finally:
        paddle.set_flags({"FLAGS_check_programs": 0})
    # the hybrid GPT step carries known warning-severity findings (Adam
    # sqrt/div hazards), so the gate must have surfaced at least one
    assert any("sharded" in str(w.message) or "numeric" in str(w.message)
               for w in seen), [str(w.message) for w in seen]
