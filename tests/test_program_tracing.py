"""What the program says about its own training step (ISSUE 25): named scopes
and kernel names in the lowered step, host spans on the profiler's clock and
in the flight recorder's ring, the `compiled` launch counter, the compile
listener, and Profiler.summary()'s device view. CPU, tiny GPT."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import dispatch
from paddle_tpu.models import (GPTConfig, GPTForPretraining,
                               GPTPretrainingCriterion)
from paddle_tpu.profiler import statistic, trace

ROOT = "compile_train_step"
LAUNCH = ROOT + "/launch"


def tiny_trainer():
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                    max_seq_len=128, dropout=0.0, attn_dropout=0.0)
    model = paddle.amp.decorate(GPTForPretraining(cfg), level="O2",
                                dtype="bfloat16")
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    step = paddle.jit.compile_train_step(
        model, lambda logits, labels: crit(logits.astype("float32"), labels),
        opt)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 129)),
                      jnp.int32)
    x = paddle.Tensor(ids[:, :-1], stop_gradient=True)
    y = paddle.Tensor(ids[:, 1:], stop_gradient=True)
    return model, step, x, y


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Three steps of a fresh step under jax.profiler.start_trace: the trace's
    planes, the ring's events, and the step."""
    model, step, x, y = tiny_trainer()
    d = str(tmp_path_factory.mktemp("trace"))
    traces = []  # (end on time.time_ns(), seconds) of every jax trace event

    def on_trace(name, secs, **kw):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            traces.append((time.time_ns(), secs))

    trace.clear()
    dispatch.reset_dispatch_counters()
    jax.monitoring.register_event_duration_secs_listener(on_trace)
    jax.profiler.start_trace(d)
    try:
        for _ in range(3):
            loss = step(x, y)
        loss._value.block_until_ready()
    finally:
        jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(on_trace)
    (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return {"path": path, "events": trace.events(), "step": step,
            "counters": dict(dispatch.dispatch_counters()), "model": model,
            "traces": traces}


def test_layers_are_scoped_by_their_registered_names():
    model, _, _, _ = tiny_trainer()
    block = model.gpt.layers[1]
    assert block._scope_name == "layers.1"
    assert block.attn._scope_name == "attn"
    assert block.attn.qkv_proj._scope_name == "qkv_proj"
    assert model._scope_name is None  # a root falls back to its class name
    grown = nn.LayerList([nn.Linear(2, 2)])
    holder = nn.Layer()
    holder.blocks = grown
    grown.append(nn.Linear(2, 2))  # added after the list was registered
    assert [l._scope_name for l in grown] == ["blocks.0", "blocks.1"]
    seq = nn.Sequential(nn.Linear(2, 2))  # called itself: children unprefixed
    holder.seq = seq
    assert seq._scope_name == "seq" and seq[0]._scope_name == "0"


def test_lowered_step_names_sections_layers_and_kernels(traced_run):
    step = traced_run["step"]
    text = step._step.lower(*step._arg_specs).as_text(debug_info=True)
    names = {line.split('"')[1] for line in text.splitlines()
             if line.startswith("#loc") and '"' in line}
    joined = "\n".join(names)
    for scope in ("jvp(forward)", "jvp(loss)", "optimizer/",
                  "layers.0/attn", "lm_head"):
        assert scope in joined, scope
    assert any(n.startswith("jit(step_fn)/transpose(jvp(forward))/")
               and "layers.0/attn" in n for n in names)
    assert "<unknown>" not in joined
    # ops dispatched through the per-op jit carry the op's name
    assert "jit(linear)" in joined and "jit(layer_norm)" in joined


def test_flash_attention_kernels_are_named():
    from paddle_tpu.ops.pallas import flash_attention

    q = jnp.ones((1, 128, 2, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v).sum(),
        argnums=(0, 1, 2)))(q, q, q)
    for name in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq"):
        assert name in str(jaxpr), name


def test_flash_attention_leaves_one_flash_tiles_event_per_compile():
    """The walk's tile counts reach the ring when the calls are built (trace
    time), never from a step that runs the compiled program again."""
    from paddle_tpu.ops.pallas import flash_attention

    q = jnp.ones((1, 1024, 1, 64), jnp.float32)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    trace.clear()
    fn(q, q, q).block_until_ready()
    (ev,) = trace.events(kind="flash_tiles")
    assert ev.site == "flash_attention"
    a = ev.attrs
    assert (a["seq"], a["block_q"], a["block_k"]) == (1024, 1024, 1024)
    assert 0 < a["masked"] <= a["run"] < a["total"]
    assert a["total"] == (1024 // a["sub_q"]) * (1024 // a["sub_k"])
    fn(q, q, q).block_until_ready()
    assert len(trace.events(kind="flash_tiles")) == 1


def test_fused_update_kernel_is_named():
    from paddle_tpu.ops.pallas import fused_update

    p = jnp.ones((8, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, g: fused_update.param_update(
        "sgd", p, g, jnp.float32(1e-3), {}, {}, wd=0.0, bad=None))(p, p)
    assert "fused_update" in str(jaxpr)


def test_scope_keys_no_cache():
    """The same op under two differently named layers is one cached program."""
    class Two(nn.Layer):
        def __init__(self):
            super().__init__()
            self.first = nn.Linear(13, 13)  # a shape no other test traces
            self.second = nn.Linear(13, 13)

        def forward(self, x):
            return self.second(self.first(x))

    def traces():
        return sum(f._cache_size() for cache in (dispatch._jit_cache,
                                                 dispatch._vjp_cache)
                   for f in cache.values())

    net = Two()
    x = paddle.ones([2, 13])
    x.stop_gradient = False  # as `second`'s input: one set of diff positions
    before = traces()
    net(x)
    assert traces() == before + 1  # `first` and `second`: one linear program
    net(x)
    assert traces() == before + 1


def host_spans(path):
    """[(name, start, end, stats)] of compile_train_step spans on /host:CPU."""
    data = jax.profiler.ProfileData.from_file(path)
    (plane,) = [p for p in data.planes if p.name == "/host:CPU"]
    return sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                   dict(ev.stats))
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith(ROOT))


def test_spans_on_the_trace_and_in_the_ring(traced_run):
    spans = host_spans(traced_run["path"])
    roots = [s for s in spans if s[2] == ROOT]
    launches = [s for s in spans if s[2] == LAUNCH]
    assert [r[3]["step_num"] for r in roots] == [0, 1, 2]
    assert len(launches) == 3
    for (rs, re_, _, _), (ls, le, _, _) in zip(roots, launches):
        assert rs <= ls and le <= re_

    ring = [e for e in traced_run["events"] if e.kind == "span"]
    ring_roots = [e for e in ring if e.site == ROOT]
    assert [e.step for e in ring_roots] == [0, 1, 2]
    assert all(e.attrs["parent"] is None for e in ring_roots)
    for root in ring_roots:
        kids = [e.site for e in ring if e.attrs["parent"] == root.attrs["id"]]
        assert kids == [ROOT + "/args", LAUNCH, ROOT + "/writeback"]
    # the same spans on the same clock: the ring's start_ns is absolute, the
    # trace's relative to profile_start_time
    data = jax.profiler.ProfileData.from_file(traced_run["path"])
    (env,) = [p for p in data.planes if p.name == "Task Environment"]
    t0 = int(dict(env.stats)["profile_start_time"])
    for root, (start, end, _, _) in zip(ring_roots, roots):
        assert abs(root.attrs["start_ns"] - (t0 + start)) < 1e6  # within 1 ms
        assert abs(root.attrs["dur_ns"] - (end - start)) < 1e6


def test_compile_event_names_the_step_that_compiled(traced_run):
    events = traced_run["events"]
    launches = [e.attrs["id"] for e in events
                if e.kind == "span" and e.site == LAUNCH]
    compiles = [e for e in events if e.kind == "compile"]
    assert any(e.site == LAUNCH and e.attrs["span"] == launches[0]
               and e.attrs["seconds"] > 0 for e in compiles)
    later = {e.attrs["id"] for e in events if e.kind == "span"
             and e.attrs["start_ns"] >= next(
                 s.attrs["start_ns"] for s in events
                 if s.kind == "span" and s.site == ROOT and s.step == 1)}
    assert not [e for e in compiles if e.attrs.get("span") in later]
    assert traced_run["counters"]["backend_compiles"] == len(compiles)
    assert traced_run["counters"]["backend_compile_s"] > 0


def first_launch(events):
    root = next(e for e in events
                if e.kind == "span" and e.site == ROOT and e.step == 0)
    return next(e.attrs for e in events if e.kind == "span"
                and e.site == LAUNCH and e.attrs["parent"] == root.attrs["id"])


def test_step_program_carries_its_trace_lowering_and_compile(traced_run):
    """One `compile` event for the step's program, sited in root 0's
    `/launch`, its three phases laid inside that span on its clock."""
    events = traced_run["events"]
    launch = first_launch(events)
    (built,) = [e.attrs for e in events if e.kind == "compile"
                and e.attrs["span"] == launch["id"]]
    assert "step_fn" in built["fun"]
    assert built["trace_s"] > 0 and built["lower_s"] > 0
    assert built["seconds"] > 0
    assert built["cache_hit"] is False and built["fetch_s"] == 0
    end = launch["start_ns"] + launch["dur_ns"]
    phases = (built["trace_s"] + built["lower_s"] + built["seconds"]) * 1e9
    assert launch["start_ns"] <= built["start_ns"]
    assert built["start_ns"] + phases <= end + 1e6  # two clocks: 1 ms
    # the outermost trace: every jit nested in the step finished inside it
    nested = [s for t, s in traced_run["traces"]
              if launch["start_ns"] <= t <= end]
    assert len(nested) > 1
    assert built["trace_s"] >= max(nested)
    assert built["trace_s"] <= launch["dur_ns"] / 1e9


@pytest.mark.parametrize("field", ["trace_s", "lower_s", "fetch_s",
                                   "cache_hit", "fun", "start_ns"])
def test_every_compile_event_carries_the_phases(traced_run, field):
    compiles = [e for e in traced_run["events"] if e.kind == "compile"]
    assert len(compiles) == traced_run["counters"]["backend_compiles"]
    assert all(field in e.attrs for e in compiles)
    # steps 1 and 2 build nothing
    roots = {e.attrs["id"]: e.step for e in traced_run["events"]
             if e.kind == "span" and e.site == ROOT}
    parent = {e.attrs["id"]: e.attrs["parent"] for e in traced_run["events"]
              if e.kind == "span"}
    for e in compiles:
        span = e.attrs.get("span")
        while span is not None and span not in roots:
            span = parent.get(span)
        assert span is None or roots[span] == 0


def test_create_parameter_is_a_span_per_leaf():
    trace.clear()
    cfg = GPTConfig(vocab_size=256, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=64, dropout=0.0, attn_dropout=0.0)
    model = GPTForPretraining(cfg)
    events = trace.events()
    spans = [e.attrs for e in events
             if e.kind == "span" and e.site == "create_parameter"]
    params = model.parameters()
    assert len(spans) == len(params)
    assert [tuple(s["shape"]) for s in spans] == \
        [tuple(p.shape) for p in params]
    assert all(s["dtype"] == "float32" and s["parent"] is None for s in spans)
    # the programs the initializers build are sited under the leaf's span
    ids = {s["id"] for s in spans}
    built = [e for e in events if e.kind == "compile"]
    assert all(e.site == "create_parameter" and e.attrs["span"] in ids
               for e in built)


def test_a_persistent_cache_fetch_is_a_field_of_its_compile(tmp_path):
    """The retired `cache_hit` ring kind: a fetch is the `compile` event's
    `cache_hit`, with the retrieval's seconds; the counter still counts."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), True, 0, 0)):
        jax.config.update(k, v)
    cc.reset_cache()
    try:
        def build():
            def probe(x):
                return jnp.sin(x) * 3.0 + 1.0
            return jax.jit(probe)

        x = jnp.ones((7, 5), jnp.float32)
        hits = dispatch.dispatch_counters()["compile_cache_hits"]
        trace.clear()
        for _ in range(2):  # a new jit each time: no in-memory cache
            build()(x).block_until_ready()
        miss, hit = [e.attrs for e in trace.events(kind="compile")]
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert (miss["cache_hit"], hit["cache_hit"]) == (False, True)
    assert miss["fetch_s"] == 0 and hit["fetch_s"] > 0
    assert "probe" in hit["fun"]
    assert dispatch.dispatch_counters()["compile_cache_hits"] == hits + 1
    assert not [e for e in trace.events() if e.kind == "cache_hit"]


def test_compiled_launch_is_counted(traced_run):
    # (the first step's eager optimizer-state set-up counts `op` programs too)
    assert traced_run["counters"]["compiled_programs"] == 3
    programs = [e for e in traced_run["events"]
                if e.kind == "program" and e.site == "compiled"]
    assert len(programs) == 3
    step = traced_run["step"]
    x = paddle.Tensor(jnp.zeros((2, 128), jnp.int32), stop_gradient=True)
    counted = paddle.profiler.measure_programs(step, x, x, warmup=1)
    assert counted["programs"] == counted["compiled_programs"] == 1


def test_record_event_is_span_and_the_ring_stays_bounded():
    assert paddle.profiler.RecordEvent is paddle.profiler.span is trace.span
    assert not hasattr(paddle.profiler, "_host_events")
    trace.clear()
    for i in range(10_000):
        with paddle.profiler.span("tick", i=i):
            pass
    kept = trace.events(kind="span")
    size = int(paddle.get_flags("FLAGS_trace_ring_size")["FLAGS_trace_ring_size"])
    assert len(kept) == min(size, 10_000)
    assert kept[-1].attrs["i"] == 9_999
    ev = paddle.profiler.RecordEvent("region")  # Paddle's begin() / end()
    ev.begin()
    with paddle.profiler.span("inner"):
        pass
    ev.end()
    inner, region = trace.events(kind="span")[-2:]
    assert (inner.site, region.site) == ("inner", "region")
    assert inner.attrs["parent"] == region.attrs["id"]
    paddle.set_flags({"FLAGS_trace_ring_size": 0})
    try:
        trace.clear()
        with paddle.profiler.span("off"):
            pass
        assert trace.events() == []
    finally:
        paddle.set_flags({"FLAGS_trace_ring_size": size})


def test_profiler_device_view_sums_to_busy_time(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_PROFILER_DIR", str(tmp_path))
    _, step, x, y = tiny_trainer()
    step(x, y)._value.block_until_ready()
    with paddle.profiler.Profiler() as prof:
        for _ in range(3):
            loss = step(x, y)
        loss._value.block_until_ready()
    view = prof.device_view()
    assert view["busy_s"] > 0
    assert sum(view["sections"].values()) == pytest.approx(view["busy_s"])
    assert set(view["sections"]) == set(statistic.SECTIONS)
    for section in ("forward", "backward", "loss", "optimizer"):
        assert view["sections"][section] > 0, section
    assert view["layers"]["layers.*/attn"]["backward"] > 0
    assert view["layers"]["lm_head"]["forward"] > 0
    table = prof.summary()
    for text in ("Device Summary", "unscoped", "layers.*/mlp",
                 "compile_train_step/launch", "root op's scope"):
        assert text in table, text
    # the host view holds the stretch's spans only, not the warm-up step's
    line = next(l for l in table.splitlines() if l.startswith(LAUNCH))
    assert line.split()[1] == "3"


def test_profiler_raises_when_the_trace_cannot_start(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_PROFILER_DIR", str(tmp_path))
    with paddle.profiler.Profiler():
        with pytest.raises(RuntimeError):
            paddle.profiler.Profiler().start()  # one trace at a time


TF_OP = "jit(step_fn)/{}/GPT/layers.{}/attn/jit(linear)/dot_general:"
TPU_TRACE = """
planes {{
  id: 1 name: "/device:TPU:0"
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "hlo_category" }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = bf16[8]{{0}} fusion()"
    stats {{ metadata_id: 2 str_value: "loop fusion" }}
    stats {{ metadata_id: 1 str_value: "{fwd}" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%flash_attention_bwd_dq.7 = bf16[8]{{0}} custom-call(), custom_call_target=\\"tpu_custom_call\\""
    stats {{ metadata_id: 1 str_value: "{bwd}" }} }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%copy-done.2 = bf16[8]{{0}} copy-done()" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%fusion.9 = bf16[8]{{0}} fusion()"
    stats {{ metadata_id: 1 str_value: "jit(step_fn)/optimizer/sub:" }} }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%while.1 = bf16[8]{{0}} while()"
    stats {{ metadata_id: 1 str_value: "jit(step_fn)/jvp(loss)/while:" }} }} }}
  lines {{
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 300000000 }}
    events {{ metadata_id: 2 offset_ps: 300000000 duration_ps: 200000000 }}
    events {{ metadata_id: 3 offset_ps: 600000000 duration_ps: 100000000 }}
    events {{ metadata_id: 5 offset_ps: 700000000 duration_ps: 300000000 }}
    events {{ metadata_id: 4 offset_ps: 800000000 duration_ps: 100000000 }}
  }}
}}
""".format(fwd=TF_OP.format("jvp(forward)", 0),
           bwd=TF_OP.format("transpose(jvp(forward))", 11))


def test_device_view_of_a_tpu_shaped_trace(tmp_path):
    """The chip's layout: `XLA Ops` events named by HLO text, the scope in
    the event METADATA's `tf_op` stat; an op nested in another (a while's
    body) takes its time out of the outer one's."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(TPU_TRACE))
    view = statistic.device_view([str(path)])
    us = 1e-6
    assert view["busy_s"] == pytest.approx(900 * us)
    assert view["window_s"] == pytest.approx(1000 * us)
    want = {"forward": 300 * us, "backward": 200 * us, "unscoped": 100 * us,
            "loss": 200 * us, "optimizer": 100 * us}
    for section in statistic.SECTIONS:
        assert view["sections"][section] == pytest.approx(
            want.get(section, 0.0)), section
    assert view["layers"] == {"layers.*/attn": {
        "forward": pytest.approx(300 * us),
        "backward": pytest.approx(200 * us)}}
    assert view["kernels"] == {
        "flash_attention_bwd_dq": pytest.approx(200 * us)}
    assert view["unscoped_ops"] == {"copy-done": pytest.approx(100 * us)}


@pytest.mark.parametrize("op_name, want", [
    ("jit(step_fn)/jvp(forward)/GPT/layers.3/attn/qkv_proj/jit(linear)/dot_general",
     ("forward", "layers.*/attn", "forward")),
    ("jit(step_fn)/transpose(jvp(forward))/GPT/embeddings/word_embeddings/jit(embedding)/gather:",
     ("backward", "embeddings/word_embeddings", "backward")),
    # a recomputed sub-layer keeps its name: checkpoint and
    # rematted_computation are stepped over, as a jit(...) is
    ("jit(step_fn)/transpose(jvp(forward))/GPT/layers.0/checkpoint/rematted_computation/mlp/mul",
     ("recompute", "layers.*/mlp", "backward")),
    ("jit(step_fn)/jvp(forward)/LM/layers.1/mixer/jit(_gated_delta_mixer)/short_conv/mul",
     ("forward", "layers.*/mixer", "forward")),
    ("jit(step_fn)/jvp(forward)/GPT/lm_head/jit(matmul)/dot_general",
     ("forward", "lm_head", "forward")),
    ("jit(step_fn)/jvp(forward)/Seq/0/jit(linear)/dot_general",
     ("forward", "*", "forward")),
    ("jit(step_fn)/jvp(forward)/GPT/jit(add)/add", ("forward", "GPT", "forward")),
    ("jit(step_fn)/transpose(jvp(loss))/jit(log_softmax)/sub",
     ("loss", None, "backward")),
    ("jit(step_fn)/grad_clip/mul", ("grad_clip", None, "forward")),
    ("jit(step_fn)/optimizer/sub", ("optimizer", None, "forward")),
    ("jit(_threefry_fold_in)/threefry2x32", ("other", None, "forward")),
    ("jit(forward)/add", ("other", None, "forward")),
])
def test_classify_scope(op_name, want):
    assert statistic.classify_scope(op_name) == want


def test_classify_scope_depth():
    name = ("jit(step_fn)/transpose(jvp(forward))/LM/layers.1/"
            "jit(recompute:mix)/checkpoint/rematted_computation/mixer/"
            "jit(_gated_delta_mixer)/gated_delta_rule/dot_general")
    assert statistic.classify_scope(name, 3) == (
        "recompute", "layers.*/mixer/gated_delta_rule", "backward")
    assert statistic.classify_scope(name, 1) == (
        "recompute", "layers.*", "backward")
    # the backward of a recomputed segment repeats the path inside itself
    name = ("jit(step_fn)/transpose(jvp(forward))/LM/layers.1/jvp(forward)/"
            "LM/layers.1/checkpoint/mixer/in_proj_qkvz/jit(linear)/"
            "dot_general")
    assert statistic.classify_scope(name, 3) == (
        "backward", "layers.*/mixer/in_proj_qkvz", "backward")
