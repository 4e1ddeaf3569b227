"""AOT compiles for the chip — the one file that holds them.

The TPU compiler is installed here and compiles for a DESCRIBED v5e:2x2
topology with no chip attached (on-chip-measurement guide, section 2): what
Mosaic or XLA:TPU refuses in these tests it would refuse on the chip. Nothing
runs, so these say nothing about results or times.

Rules this file keeps: the topology is described inside the ``topo`` fixture
(never at import, in a skipif, in parametrize or in conftest.py); the fixture
is not autouse; every compile happens in the test's own process; the
persistent compile cache is off around them (such an entry cannot be read
back without a chip). The kernels choose interpret mode from
``jax.default_backend()``, which is still ``cpu`` here — the tests steer that
by monkeypatch, not through an option of the program.
"""
import importlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import paddle_tpu as paddle
# the package re-exports the flash_attention FUNCTION under the module's name
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
fu = importlib.import_module("paddle_tpu.ops.pallas.fused_update")
la = importlib.import_module("paddle_tpu.ops.linear_attention")
ssm = importlib.import_module("paddle_tpu.ops.state_space")
mc = importlib.import_module("paddle_tpu.ops.pallas.moe_combine")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Every kernel takes its compiled (non-interpret) branch, as on the
    chip."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(fu, "_interpret", lambda: False)
    monkeypatch.setattr(la, "_interpret", lambda: False)
    monkeypatch.setattr(ssm, "_interpret", lambda: False)
    monkeypatch.setattr(mc, "_interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "shape", [(8, 1024, 16, 64), (2, 4096, 16, 64), (4, 1024, 20, 64)],
    ids=["b8s1024", "b2s4096", "b4s1024h20"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles_for_v5e(one_chip, compiled_kernels, shape,
                                          grad):
    spec = _sds(shape, jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return fwd(q, k, v).astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_bwd if grad else fwd, spec, spec, spec)
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# fused optimizer update
# ---------------------------------------------------------------------------
_HYPER = {
    "sgd": {},
    "momentum": {"mu": 0.9, "nesterov": False},
    "adam": {"b1": 0.9, "b2": 0.999, "eps": 1e-8},
}


def _state_specs(kind, shape, sharding):
    buf = _sds(shape, jnp.float32, sharding)
    scalar = _sds((), jnp.float32, sharding)
    if kind == "momentum":
        return {"velocity": buf}
    if kind == "adam":
        return {"moment1": buf, "moment2": buf,
                "beta1_pow": scalar, "beta2_pow": scalar}
    return {}


@pytest.mark.parametrize("shape", [(1024, 4096), (50304, 1024)],
                         ids=["1024x4096", "50304x1024"])
@pytest.mark.parametrize("gate", [False, True], ids=["nogate", "gate"])
@pytest.mark.parametrize("kind", ["adam", "momentum", "sgd"])
def test_fused_update_compiles_for_v5e(one_chip, compiled_kernels, kind,
                                       gate, shape):
    buf = _sds(shape, jnp.float32, one_chip)
    lr = _sds((), jnp.float32, one_chip)
    bad = _sds((), jnp.bool_, one_chip)
    state = _state_specs(kind, shape, one_chip)

    def update(p, g, lr, state, bad):
        return fu.param_update(kind, p, g, lr, state, _HYPER[kind],
                               wd=0.01, bad=bad if gate else None)

    text = _compiled_text(update, buf, buf, lr, state, bad)
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# the attention path a full-width GPT layer takes, through the functional API
# ---------------------------------------------------------------------------
def test_gpt_attention_path_compiles_with_flash_kernel(one_chip,
                                                       compiled_kernels):
    """hidden 1024 = 16 heads x 64, s1024, b8, bf16, causal, fwd+bwd through
    ``nn.functional.scaled_dot_product_attention``: the flash kernel must be
    in the compiled text — neither the dense path nor interpret mode."""
    import paddle_tpu.nn.functional as F

    spec = _sds((8, 1024, 16, 64), jnp.bfloat16, one_chip)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            out = F.scaled_dot_product_attention(
                paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v),
                is_causal=True, training=True)
            return out._value.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_bwd, spec, spec, spec)
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# a whole compile_train_step program (full width, depth 2)
# ---------------------------------------------------------------------------
def test_compile_train_step_program_compiles_for_v5e(one_chip,
                                                     compiled_kernels):
    """GPT-2 345M width at depth 2, AMP O2 bf16, AdamW, b8 x s1024: the jitted
    step ``CompiledTrainStep`` builds, lowered over shapes placed on the
    described chip. Fits 16 GB with room, and carries the flash kernel."""
    from paddle_tpu.core import random as _random
    from paddle_tpu.models import (GPTForPretraining, GPTPretrainingCriterion,
                                   gpt2_345m)

    cfg = gpt2_345m(max_seq_len=1024, dropout=0.0, attn_dropout=0.0)
    cfg.num_layers = 2  # depth cut; every width as published
    paddle.seed(0)
    model = paddle.amp.decorate(GPTForPretraining(cfg), level="O2",
                                dtype="bfloat16")
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    step = paddle.jit.compile_train_step(
        model, lambda lg, lb: crit(lg.astype("float32"), lb), opt)
    step._opt_state = step._init_opt_state()
    ids = _sds((8, 1024), jnp.int32, one_chip)
    key = _random.next_key()
    args = (tuple(p._value for p in step._params), tuple(step._opt_state),
            tuple(b._value for b in step._buffers), key,
            jnp.asarray(1e-4, jnp.float32), ids, ids)
    specs = jax.tree_util.tree_map(
        lambda a: _sds(tuple(a.shape), a.dtype, one_chip), args)
    step._arg_specs = specs
    compiled = step._build().lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16 * 2**30, mem


# ---------------------------------------------------------------------------
# the sparse hybrid decoder's kernels and step, at the shapes of the cell
# qwen3next-train-s8192 (batch 2 x 8,192; benchmark/configs/qwen3-next-*)
# ---------------------------------------------------------------------------
def test_grouped_query_flash_compiles_for_v5e(one_chip, compiled_kernels):
    """16 query heads on 2 KV heads of 256 at s = 8,192, forward and
    backward: the three kernels, k and v never copied out to the group."""
    q = _sds((2, 8192, 16, 256), jnp.bfloat16, one_chip)
    kv = _sds((2, 8192, 2, 256), jnp.bfloat16, one_chip)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return fa.flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_bwd, q, kv, kv)
    for name in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq"):
        assert name in text
    assert "bf16[2,8192,16,256]{3,2,1,0} broadcast" not in text


def test_gated_delta_rule_compiles_for_v5e(one_chip, compiled_kernels):
    """16 key and 32 value heads of 128 at s = 8,192, chunk 64, forward and
    backward: the three kernels are in the compiled text, and what a chunk
    is prepared from stays in VMEM: no float32 [.., 64, 64] table of all
    chunks (the parent's ``f32[2,32,128,64,64]`` inverse and decay tables,
    134 MB each) in any shape, and the program's temporaries under 1 GiB
    (832 MiB with a key head's two value heads in one grid step; 896 MiB
    with one a step, 2,960 MiB before the preparation moved into the
    kernels; AOT compiles)."""
    qk = _sds((2, 8192, 16, 128), jnp.bfloat16, one_chip)
    v = _sds((2, 8192, 32, 128), jnp.bfloat16, one_chip)
    gate = _sds((2, 8192, 32), jnp.float32, one_chip)

    def fwd_bwd(q, k, v, g, beta):
        def loss(*a):
            return la.gated_delta_rule(*a).astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    compiled = jax.jit(fwd_bwd).lower(qk, qk, v, gate, gate).compile()
    text = compiled.as_text()
    for name in (r"gated_delta_rule_fwd_inverse", r"gated_delta_rule_fwd(?!_)",
                 r"gated_delta_rule_bwd"):
        assert re.search(name, text), name
    # [batch, value heads, chunks, 64, 64] whole, or with axes merged
    assert not re.findall(r"f32\[[0-9,]*64,64\]", text)
    assert not re.findall(r"f32\[(2,32|64),8192,64\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_qwen3_next_train_step_compiles_for_v5e(one_chip, compiled_kernels,
                                                monkeypatch):
    """The whole compile_train_step program of the cell: published widths,
    one period of four layers, 64 of 512 experts held, an eighth of the
    vocabulary, AMP O2 bf16, AdamW, batch 2 x 8,192, mixers recomputed. It
    fits the chip, and holds the flash kernels, the delta rule's kernels and
    the grouped products, the short conv and the gated norm as their four
    kernels: no dense attention, no capacity-bucketed dispatch.
    (PERF.md section 4 has the memory it reads, and what it reads without
    the recomputation.)"""
    import paddle_tpu.nn.initializer as I
    from paddle_tpu.core import random as _random
    from paddle_tpu.models import (GPTPretrainingCriterion, Qwen3NextConfig,
                                   Qwen3NextForCausalLM)

    # shapes are all that matter here: skip drawing a billion normals
    monkeypatch.setattr(I.Normal, "_generate",
                        lambda self, shape, dtype: jnp.zeros(shape, dtype))
    cfg = Qwen3NextConfig(vocab_size=18992, num_hidden_layers=4,
                          held_experts=(0, 64), use_recompute=True)
    model = paddle.amp.decorate(Qwen3NextForCausalLM(cfg), level="O2",
                                dtype="bfloat16")
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    step = paddle.jit.compile_train_step(
        model, lambda lg, lb: crit(lg.astype("float32"), lb), opt)
    step._opt_state = step._init_opt_state()
    ids = _sds((2, 8192), jnp.int32, one_chip)
    args = (tuple(p._value for p in step._params), tuple(step._opt_state),
            tuple(b._value for b in step._buffers), _random.next_key(),
            jnp.asarray(1e-4, jnp.float32), ids, ids)
    specs = jax.tree_util.tree_map(
        lambda a: _sds(tuple(a.shape), a.dtype, one_chip), args)
    step._arg_specs = specs
    compiled = step._build().lower(*specs).compile()
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq", "gated_delta_rule_fwd",
                 "gated_delta_rule_bwd", "ragged-dot",
                 "short_conv_silu_fwd", "short_conv_silu_bwd",
                 "gated_rms_norm_fwd", "gated_rms_norm_bwd"):
        assert name in text, name
    # the conv reads no padded copy (3 rows more than the sequence) and the
    # gated norm writes no float32 factor of o's size (PR 31)
    assert "8195" not in text
    assert not [line for line in text.split("\n") if "gated_norm" in line
                and re.search(r"= f32\[2,8192,(4096|32,128)\]", line)]
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 14.5 * 2**30, mem
    # the expert layers' combine kernel (PR 35) keeps no more than the
    # parent's scatter-add did, plus the gate-up products the four layers'
    # forwards keep for their backwards (207.1 MB more: recomputed, a
    # block's product lives through its own backward alone)
    assert mem.temp_size_in_bytes <= 5_750_582_784, mem
    assert len(step._params) == 62  # expert weights are stacked leaves


# ---------------------------------------------------------------------------
# the dropless expert layer at both sparse cells' shapes (PERF.md section 4)
# ---------------------------------------------------------------------------
# tokens a step, model width, expert width, router width, experts held, the
# shared expert's width, whether it is gated, and the compiled layer's
# temporary bytes on the parent of PR 35 (XLA's scatter-add in both loops)
EXPERT_LAYERS = {
    "granite4h": (8192, 4096, 768, 72, 9, 1536, False, 730_508_800),
    "qwen3next": (16384, 2048, 512, 512, 64, 512, True, 1_190_467_584),
}


@pytest.mark.parametrize("cell", list(EXPERT_LAYERS))
def test_expert_layer_pass_moves_its_rows_in_bf16(one_chip, compiled_kernels,
                                                  cell):
    """One expert layer, forward and gradient, bf16, ten slots a token. The
    first pass of each direction is made before its loop: the forward's 2
    grouped products, then the backward's 4 on the gate-up product the
    forward kept, so a one-pass step runs 6 (a derived backward ran 8 with
    the forward's, the backward that made the gate-up product again 7); the
    later passes' loops hold the forward's 2 and the backward's 5 besides.
    Every operand of the buffer's length is bf16, the weight-gradient
    products return bf16 stacks, no dense product under a [held, rows] mask
    stands in for a grouped one, and nothing of the buffer's length and the
    model's width is written in float32 but the products' own results. Each
    pass's combine is the ``moe_combine`` kernel, nothing scatters into a
    float32 [tokens, h] array, and the layer's temporary bytes stay within
    0.1% of the parent's plus the kept float32 [rows, 2 d] product."""
    from paddle_tpu.incubate import moe

    tokens, h, d, wide, held, d_shared, shared_gate, parent_temp = (
        EXPERT_LAYERS[cell])
    rows = moe.row_buffer_rows(tokens, 10, wide, held)
    x_and_leaves = [(tokens, h), (h, wide), (held, h, 2 * d), (held, d, h),
                    (h, 2 * d_shared), (d_shared, h)] + [(h, 1)] * shared_gate

    def fwd_bwd(ct, *a):
        y, vjp = jax.vjp(lambda *a: moe.dropless_experts(
            *a, *[None] * (not shared_gate), first=0, top_k=10,
            renormalize=True, rows=rows)[0], *a)
        return (y,) + vjp(ct)

    compiled = jax.jit(fwd_bwd).lower(*[
        _sds(shape, jnp.bfloat16, one_chip)
        for shape in [(tokens, h)] + x_and_leaves]).compile()
    text = compiled.as_text()
    entry = text.split("\nENTRY ", 1)[1].split("\n}", 1)[0]

    def grouped(text):
        return [line for line in text.split("\n") if re.match(
            r"\s*%ragged-dot-none\S* = .* custom-call\(", line)]

    def combines(text):
        return re.findall(r"%%moe_combine\S* = f32\[%d,%d\]\S* custom-call\("
                          % (tokens, h), text)

    calls = grouped(text)
    assert (len(grouped(entry)), len(calls)) == (6, 13), (
        len(grouped(entry)), len(calls))
    for call in calls:  # the operands' shapes stand in the layout constraints
        operands = call.split("operand_layout_constraints={")[1].split(
            "}, frontend_attributes")[0]
        assert f"[{rows}," in operands or f"[{held}," in operands, call
        assert not re.search(r"f32\[%d," % rows, operands), operands
    results = [re.match(r"\s*%\S+ = (\w+\[[\d,]*\])", c).group(1)
               for c in calls]
    assert sorted(r for r in results if "f32" not in r) == sorted(
        [f"bf16[{held},{h},{2 * d}]", f"bf16[{held},{d},{h}]"] * 2), results
    # the down product's and dx's rows, each before its loop and inside it
    assert results.count(f"f32[{rows},{h}]") == 4, results
    assert f"pred[{held},{rows}]" not in text
    # no weight, mask or rounding is applied on a float32 array of the
    # buffer's length and the model's width, fused or not
    assert not re.findall(
        r"= f32\[%d,%d\]\S* (?:select|multiply|convert)\(" % (rows, h), text)
    assert (len(combines(entry)), len(combines(text))) == (2, 4)
    assert not re.findall(r"= f32\[%d,%d\]\S* scatter\(" % (tokens, h), text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    saved = rows * 2 * d * 4
    assert temp <= 1.001 * parent_temp + saved, (temp, parent_temp, saved)


# ---------------------------------------------------------------------------
# granite4h-train-s8192 (batch 1 x 8,192; benchmark/configs/granite-4.0-h-*)
# ---------------------------------------------------------------------------
def test_ssd_scan_compiles_for_v5e(one_chip, compiled_kernels):
    """16 heads of 64 on one B / C group of 128 at s = 8,192, chunk 256,
    forward and backward: the two kernels are in the compiled text, and no
    table of all chunks leaves VMEM (no float32 [.., 256, 256] array)."""
    x = _sds((1, 8192, 16, 64), jnp.bfloat16, one_chip)
    gate = _sds((1, 8192, 16), jnp.float32, one_chip)
    head = _sds((16,), jnp.bfloat16, one_chip)
    bc = _sds((1, 8192, 1, 128), jnp.bfloat16, one_chip)

    def fwd_bwd(*args):
        def loss(*a):
            return ssm.ssd_scan(*a).astype(jnp.float32).sum()

        return jax.grad(loss, argnums=tuple(range(6)))(*args)

    compiled = jax.jit(fwd_bwd).lower(x, gate, head, bc, bc, head).compile()
    text = compiled.as_text()
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    assert not re.findall(r"f32\[[0-9,]*256,256\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**28


def test_grouped_ssd_scan_compiles_for_v5e(one_chip, compiled_kernels):
    """nemotron3nano-train-s8192's scan: 64 heads of 64 on 8 B / C groups of
    128 (8 heads, 4 lane blocks a group) at 2 x 8,192, chunk 128 (4 chunks a
    grid step), forward and backward: the two kernels are in the compiled
    text, no fallback, and no table of all chunks leaves VMEM."""
    from paddle_tpu.core.dispatch import dispatch_counters

    x = _sds((2, 8192, 64, 64), jnp.bfloat16, one_chip)
    gate = _sds((2, 8192, 64), jnp.float32, one_chip)
    head = _sds((64,), jnp.bfloat16, one_chip)
    bc = _sds((2, 8192, 8, 128), jnp.bfloat16, one_chip)

    def fwd_bwd(*args):
        def loss(*a):
            return ssm.ssd_scan(*a, chunk=128).astype(jnp.float32).sum()

        return jax.grad(loss, argnums=tuple(range(6)))(*args)

    fallbacks = dispatch_counters()["ssd_scan_fallbacks"]
    compiled = jax.jit(fwd_bwd).lower(x, gate, head, bc, bc, head).compile()
    assert dispatch_counters()["ssd_scan_fallbacks"] == fallbacks
    text = compiled.as_text()
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    assert not re.findall(r"f32\[[0-9,]*128,128\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_granite_hybrid_train_step_compiles_for_v5e(one_chip,
                                                    compiled_kernels,
                                                    monkeypatch):
    """The whole compile_train_step program of the cell, built from the
    cell's own configuration file: published widths, one period of ten
    layers, 16 of 128 state-space heads, 4 of 32 query heads on 1 KV head, 9
    of 72 experts, an eighth of the vocabulary, AMP O2 bf16, AdamW, batch 1 x
    8,192, nothing recomputed. It fits the chip and holds the scan's and the
    conv's kernels, the flash kernels and the grouped products. (PERF.md
    section 4 has the memory it reads, and what it reads with the mixers
    recomputed.)"""
    import json

    import paddle_tpu.nn.initializer as I
    from benchmark.lib import program_granite_hybrid as prog
    from paddle_tpu.core import random as _random
    from paddle_tpu.models import GPTPretrainingCriterion

    # shapes are all that matter here: skip drawing a billion normals
    monkeypatch.setattr(I.Normal, "_generate",
                        lambda self, shape, dtype: jnp.zeros(shape, dtype))
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "granite-4.0-h-small-tp8ep8.json")) as f:
        sizes = json.load(f)
    assert sizes["recompute_mixer"] is False
    _, model = prog.build_model(sizes)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    step = paddle.jit.compile_train_step(
        model, lambda lg, lb: crit(lg.astype("float32"), lb), opt)
    step._opt_state = step._init_opt_state()
    ids = _sds((1, 8192), jnp.int32, one_chip)
    args = (tuple(p._value for p in step._params), tuple(step._opt_state),
            tuple(b._value for b in step._buffers), _random.next_key(),
            jnp.asarray(1e-4, jnp.float32), ids, ids)
    specs = jax.tree_util.tree_map(
        lambda a: _sds(tuple(a.shape), a.dtype, one_chip), args)
    step._arg_specs = specs
    compiled = step._build().lower(*specs).compile()
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq", "ssd_scan_fwd", "ssd_scan_bwd",
                 "ragged-dot", "short_conv_silu_fwd", "short_conv_silu_bwd"):
        assert name in text, name
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 14.0 * 2**30, mem
    # the expert layers' combine kernel (PR 35) keeps no more than the
    # parent's scatter-add did, plus the gate-up products the ten layers'
    # forwards keep for their backwards: 731.4 MB more, where nine of the
    # ten (679.5 MB) are live at the peak, the first backward layer's
    # combine
    assert mem.temp_size_in_bytes <= 6_805_145_088, mem
    assert sum(int(np.prod(p.shape)) for p in step._params) == 1_221_088_944
    assert len(step._params) == 157


# ---------------------------------------------------------------------------
# the block-diffusion sparse decoder at the shapes of the cell
# sdar-train-s8192 (1 x 8,192 clean tokens = 16,384 stream positions;
# benchmark/configs/sdar-*)
# ---------------------------------------------------------------------------
def test_block_mask_flash_compiles_for_v5e(one_chip, compiled_kernels):
    """32 query heads on 4 KV heads of 128 over a stream of 2 x 8,192 under
    the two-stream block mask, block length 4, forward and backward: the
    three kernels, k and v never copied out to the group or to a half, no
    array of positions squared."""
    q = _sds((1, 16384, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, 16384, 4, 128), jnp.bfloat16, one_chip)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return fa.flash_attention(q, k, v, block_mask=(8192, 4)).astype(
                jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(fwd_bwd).lower(q, kv, kv).compile()
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq"):
        assert name in text
    assert "16384,16384]" not in text
    assert "bf16[1,16384,32,128]{3,2,1,0} broadcast" not in text
    # q, o, do, dq, lse and delta, and the halves of dk and dv joined
    assert compiled.memory_analysis().temp_size_in_bytes < 2**29


def test_sdar_moe_train_step_compiles_for_v5e(one_chip, compiled_kernels,
                                              monkeypatch):
    """The whole compile_train_step program of the cell, built from the
    cell's own configuration and traffic files: published widths, six
    layers, 16 of 128 experts and no shared expert, an eighth of the
    vocabulary, AMP O2 bf16, AdamW, 1 x 8,192 clean tokens as a stream of
    16,384 under the block mask. It fits the chip (under 15.75 GB) and holds
    the flash kernels under the attention's scope, the noise's scope and the
    grouped products: no dense attention, no [positions, positions] array.
    (PERF.md section 4 has the memory it reads.)"""
    import json

    import paddle_tpu.nn.initializer as I
    from benchmark.lib import program_sdar_moe as prog
    from paddle_tpu.core import random as _random
    from paddle_tpu.models import BlockDiffusionCriterion

    # shapes are all that matter here: skip drawing a billion normals
    monkeypatch.setattr(I.Normal, "_generate",
                        lambda self, shape, dtype: jnp.zeros(shape, dtype))
    here = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    with open(os.path.join(here, "configs",
                           "sdar-30b-a3b-chat-ep8.json")) as f:
        sizes = json.load(f)
    with open(os.path.join(here, "workloads", "sdar-train-s8192.json")) as f:
        mix = json.load(f)["traffic"]
    assert (sizes["num_hidden_layers"], mix["batch"], mix["seq"]) == (
        6, 1, 8192)
    _, model = prog.build_model(sizes, mix["block_length"])
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    crit = BlockDiffusionCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    step = paddle.jit.compile_train_step(
        model, lambda lg, tg: crit(lg.astype("float32"), tg), opt)
    step._opt_state = step._init_opt_state()
    ids = _sds((1, 8192), jnp.int32, one_chip)
    args = (tuple(p._value for p in step._params), tuple(step._opt_state),
            tuple(b._value for b in step._buffers), _random.next_key(),
            jnp.asarray(1e-4, jnp.float32), ids, ids,
            _sds((1, 8192, 2), jnp.float32, one_chip))
    specs = jax.tree_util.tree_map(
        lambda a: _sds(tuple(a.shape), a.dtype, one_chip), args)
    step._arg_specs = specs
    compiled = step._build().lower(*specs).compile()
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq", "block_diffusion_attention",
                 "diffusion_noise", "ragged-dot"):
        assert name in text, name
    assert "16384,16384]" not in text
    assert "shared_expert" not in text
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print("sdar step bytes", total, mem)
    assert total < 15.75e9, mem
    # the expert layers' combine kernel (PR 35) keeps no more than the
    # parent's scatter-add did, plus the gate-up products the six layers'
    # forwards keep for their backwards (752.0 MB more; they hold 736.1 MB,
    # all live at the peak, the loss)
    assert mem.temp_size_in_bytes <= 9_101_826_560, mem
    assert sum(int(np.prod(p.shape)) for p in step._params) == 645_623_296
    assert len(step._params) == 69


# ---------------------------------------------------------------------------
# the sliding-window sparse decoder at the shapes of the cell
# mellum2-train-s8192 (2 x 8,192 tokens; benchmark/configs/mellum2-*)
# ---------------------------------------------------------------------------
def test_window_flash_compiles_for_v5e(one_chip, compiled_kernels):
    """32 query heads on 4 KV heads of 128 over 2 x 8,192 under a window of
    1,024 keys, forward and backward: the three windowed kernels by name, k
    and v never copied out to the group, no array of positions squared."""
    q = _sds((2, 8192, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((2, 8192, 4, 128), jnp.bfloat16, one_chip)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return fa.flash_attention(q, k, v, window=1024).astype(
                jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(fwd_bwd).lower(q, kv, kv).compile()
    text = compiled.as_text()
    for name in ("flash_attention_window_fwd",
                 "flash_attention_window_bwd_dkv",
                 "flash_attention_window_bwd_dq"):
        assert name in text
    assert "8192,8192]" not in text
    assert "bf16[2,8192,32,128]{3,2,1,0} broadcast" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**29


def test_mellum2_train_step_compiles_for_v5e(one_chip, compiled_kernels,
                                             monkeypatch):
    """The whole compile_train_step program of the cell, built from the
    cell's own configuration and traffic files: published widths, one period
    of four layers (three sliding-window, one full with YaRN), 8 of 64
    experts and no shared expert, an eighth of the vocabulary, AMP O2 bf16,
    AdamW, 2 x 8,192 tokens, nothing recomputed. It fits the chip and holds
    both kinds of flash kernels under the two scopes and the grouped
    products: no dense attention. (PERF.md section 4 has the memory it
    reads.)"""
    import json

    import paddle_tpu.nn.initializer as I
    from benchmark.lib import program_mellum2 as prog
    from paddle_tpu.core import random as _random
    from paddle_tpu.models import GPTPretrainingCriterion

    # shapes are all that matter here: skip drawing a billion normals
    monkeypatch.setattr(I.Normal, "_generate",
                        lambda self, shape, dtype: jnp.zeros(shape, dtype))
    here = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    with open(os.path.join(here, "configs",
                           "mellum2-12b-a2.5b-ep8.json")) as f:
        sizes = json.load(f)
    with open(os.path.join(here, "workloads",
                           "mellum2-train-s8192.json")) as f:
        mix = json.load(f)["traffic"]
    assert (sizes["num_hidden_layers"], sizes["recompute_mixer"],
            mix["batch"], mix["seq"]) == (4, False, 2, 8192)
    _, model = prog.build_model(sizes)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    step = paddle.jit.compile_train_step(
        model, lambda lg, lb: crit(lg.astype("float32"), lb), opt)
    step._opt_state = step._init_opt_state()
    ids = _sds((2, 8192), jnp.int32, one_chip)
    args = (tuple(p._value for p in step._params), tuple(step._opt_state),
            tuple(b._value for b in step._buffers), _random.next_key(),
            jnp.asarray(1e-4, jnp.float32), ids, ids)
    specs = jax.tree_util.tree_map(
        lambda a: _sds(tuple(a.shape), a.dtype, one_chip), args)
    step._arg_specs = specs
    compiled = step._build().lower(*specs).compile()
    text = compiled.as_text()
    for name in ("flash_attention_window_fwd",
                 "flash_attention_window_bwd_dkv",
                 "flash_attention_window_bwd_dq", "flash_attention_fwd",
                 "flash_attention_bwd_dkv", "flash_attention_bwd_dq",
                 "sliding_attention", "full_attention", "ragged-dot"):
        assert name in text, name
    assert "8192,8192]" not in text
    assert "shared_expert" not in text
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # 6.85 GB of 15.75: 2.04 GB of state, 4.81 GB of temporaries, 573.9 MB
    # of them since the four layers' forwards keep their gate-up products
    # (572.5 MB) for their backwards
    assert total < 15.75e9, mem
    assert mem.argument_size_in_bytes == 2_042_308_096, mem
    assert mem.temp_size_in_bytes <= 4_805_358_592, mem
    assert sum(int(np.prod(p.shape)) for p in step._params) == 340_349_184
    assert len(step._params) == 39


# ---------------------------------------------------------------------------
# the one-part-a-layer hybrid decoder at the shapes of the cell
# nemotron3nano-train-s8192 (2 x 8,192; benchmark/configs/nemotron-3-nano-*)
# ---------------------------------------------------------------------------
def test_nemotron_h_train_step_compiles_for_v5e(one_chip, compiled_kernels,
                                                monkeypatch):
    """The whole compile_train_step program of the cell, built from the
    cell's own configuration and traffic files: published widths, the first
    9 layers of the pattern (4 Mamba-2 on 8 B / C groups, 4 relu^2 expert
    layers of 8 held experts under the sigmoid router, 1 attention layer),
    an eighth of the vocabulary with an untied head, AMP O2 bf16, AdamW, 2 x
    8,192 tokens, nothing recomputed. It fits the chip and holds the scan's
    and the flash kernels, the grouped products and the scopes the cell's
    readers time, with no scan fallen back. (PERF.md section 4 has the
    memory it reads.)"""
    import json

    import paddle_tpu.nn.initializer as I
    from benchmark.lib import program_nemotron_h as prog
    from paddle_tpu.core import random as _random
    from paddle_tpu.core.dispatch import dispatch_counters
    from paddle_tpu.models import GPTPretrainingCriterion

    # shapes are all that matter here: skip drawing a billion normals
    monkeypatch.setattr(I.Normal, "_generate",
                        lambda self, shape, dtype: jnp.zeros(shape, dtype))
    here = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    with open(os.path.join(here, "configs",
                           "nemotron-3-nano-30b-a3b-ep16.json")) as f:
        sizes = json.load(f)
    with open(os.path.join(here, "workloads",
                           "nemotron3nano-train-s8192.json")) as f:
        mix = json.load(f)["traffic"]
    assert (sizes["num_hidden_layers"], sizes["recompute_mixer"],
            mix["batch"], mix["seq"]) == (9, False, 2, 8192)
    _, model = prog.build_model(sizes)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    step = paddle.jit.compile_train_step(
        model, lambda lg, lb: crit(lg.astype("float32"), lb), opt)
    step._opt_state = step._init_opt_state()
    ids = _sds((2, 8192), jnp.int32, one_chip)
    args = (tuple(p._value for p in step._params), tuple(step._opt_state),
            tuple(b._value for b in step._buffers), _random.next_key(),
            jnp.asarray(1e-4, jnp.float32), ids, ids)
    specs = jax.tree_util.tree_map(
        lambda a: _sds(tuple(a.shape), a.dtype, one_chip), args)
    step._arg_specs = specs
    fallbacks = dispatch_counters()["ssd_scan_fallbacks"]
    compiled = step._build().lower(*specs).compile()
    assert dispatch_counters()["ssd_scan_fallbacks"] == fallbacks
    text = compiled.as_text()
    for name in ("ssd_scan_fwd", "ssd_scan_bwd", "flash_attention_fwd",
                 "flash_attention_bwd_dkv", "flash_attention_bwd_dq",
                 "ragged-dot", "short_conv_silu_fwd", "short_conv_silu_bwd",
                 "ssd_scan", "short_conv", "gated_norm", "router", "experts",
                 "shared_expert"):
        assert name in text, name
    assert "8192,8192]" not in text
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # 12.79 GiB of 15.75 (13.73 GB): 4.00 GB of state, 9.73 GB of
    # temporaries, with every activation kept; the correction biases'
    # balancing step (each expert layer's count of slots by router output)
    # adds 1,896,448 bytes
    assert total < 14.5 * 2**30, mem
    assert mem.argument_size_in_bytes == 4_002_021_888, mem
    assert mem.temp_size_in_bytes <= 9_730_076_672, mem
    assert sum(int(np.prod(p.shape)) for p in step._params) == 666_962_944
    assert len(step._params) == 72
