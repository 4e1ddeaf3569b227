"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py              one TPU chip: device, kernels, train,
                                      serve, eager — GPT-2 345M, full width
                                      and depth, through the public API
    python chip_smoke.py --chips 4    four chips: ONLY the mesh4 phase
                                      (dp2 x mp2 sharded_train_step) and the
                                      one-chip step it is compared with
    python chip_smoke.py --rehearse   the same phases at a tiny config on
                                      whatever backend is there (CPU
                                      rehearsal); never prints the ok line
    python chip_smoke.py --profile 345m|774m
                                      ONLY device + train, on that GPT-2 at
                                      the benchmark's batch, with three more
                                      steps under paddle.profiler.Profiler
                                      and its summary(): where the device
                                      time of the step goes, by section,
                                      layer and kernel

One process, which touches JAX once and spawns nothing. No accelerator means
failure at once — there is no CPU carry-on. Every check raises; nothing
catches. Times and memory printed here are a smoke run's, NOT a benchmark.
The last line of a passing run is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
import argparse
import gc
import importlib
import importlib.metadata
import json
import math
import sys
import time

import numpy as np

# stated tolerances ---------------------------------------------------------
# bf16 kernel vs the dense f32 reference: max |a-b| over max |b|. bf16 keeps
# 8 mantissa bits (2^-8 = 0.4% per rounding); outputs and grads are rounded
# to bf16 once after f32 accumulation, inputs once before.
KERNEL_BF16_REL = 2e-2
# fused Adam kernel vs the lax composition, both f32: same formulas, another
# fusion and another sqrt/divide lowering
UPDATE_F32_RTOL, UPDATE_F32_ATOL = 1e-5, 1e-6
# serving logits (paged cache, bucketed programs) vs a plain full-context
# forward, f32 weights: on the TPU an f32 matmul at default precision
# multiplies in bf16, and the two paths round in different places
SERVE_LOGIT_ATOL = 5e-2
# per-step training loss, dp2 x mp2 mesh (dense attention, GSPMD reductions)
# vs one chip (flash kernel), both AMP O2 bf16, loss ~ ln(vocab) = 10.8
MESH_LOSS_ATOL = 5e-2


def say(phase, msg, kind):
    print(f"[{phase}] {msg} (device_kind={kind})", flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def mem_stats(dev):
    return dev.memory_stats() or {}


def fmt_gib(n):
    return f"{n / 2**30:.3f} GiB"


# ---------------------------------------------------------------------------
# configurations
# ---------------------------------------------------------------------------
def model_cfg(rehearse, max_seq_len, size="345m"):
    from paddle_tpu.models import GPTConfig, gpt2_345m

    if rehearse:
        return GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                         num_heads=4, max_seq_len=max_seq_len, dropout=0.0,
                         attn_dropout=0.0)
    if size == "774m":  # gpt2-large, as benchmark/configs/gpt2-large-774m.json
        return GPTConfig(hidden_size=1280, num_layers=36, num_heads=20,
                         max_seq_len=max_seq_len, dropout=0.0,
                         attn_dropout=0.0)
    return gpt2_345m(max_seq_len=max_seq_len, dropout=0.0, attn_dropout=0.0)


def build_trainer(paddle, cfg):
    """AMP O2 bf16 + AdamW over GPTForPretraining(cfg), seed 0."""
    from paddle_tpu.models import GPTForPretraining, GPTPretrainingCriterion

    paddle.seed(0)
    model = paddle.amp.decorate(GPTForPretraining(cfg), level="O2",
                                dtype="bfloat16")
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)

    def loss_fn(logits, labels):
        return crit(logits.astype("float32"), labels)

    return model, loss_fn, opt


def make_batch(paddle, cfg, bsz, seq, seed=0):
    import jax.numpy as jnp

    ids = np.random.default_rng(seed).integers(0, cfg.vocab_size,
                                               (bsz, seq + 1))
    ids = jnp.asarray(ids, jnp.int32)
    return (paddle.Tensor(ids[:, :-1], stop_gradient=True),
            paddle.Tensor(ids[:, 1:], stop_gradient=True))


def timed_steps(step, x, y, n):
    """n steps on one batch, each ending in block_until_ready."""
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = step(x, y)
        loss._value.block_until_ready()
        secs.append(time.perf_counter() - t0)
        losses.append(loss)
    return [float(l) for l in losses], secs


# ---------------------------------------------------------------------------
# phases (one chip)
# ---------------------------------------------------------------------------
def phase_device(jax, kind):
    d = jax.devices()[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    import jaxlib

    say("device", f"platform={d.platform} count={len(jax.devices())} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"compile_cache_dir={jax.config.jax_compilation_cache_dir}", kind)
    st = mem_stats(d)
    if st:
        say("device", f"bytes_limit={fmt_gib(st['bytes_limit'])} "
            f"bytes_in_use={fmt_gib(st['bytes_in_use'])}", kind)


def phase_kernels(jax, paddle, kind, rehearse):
    import jax.numpy as jnp

    from paddle_tpu.ops import nn_ops

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    fu = importlib.import_module("paddle_tpu.ops.pallas.fused_update")
    if jax.default_backend() != "tpu":  # rehearsal: Pallas interpreter
        paddle.set_flags({"FLAGS_pallas_update_interpret": True})
    paddle.set_flags({"FLAGS_pallas_fused_update": True})
    try:
        if not rehearse:
            check(not fa._interpret() and not fu._interpret(),
                  "a Pallas kernel would run in interpret mode on the chip")

        # -- flash attention fwd+bwd, bf16 causal, vs dense f32 ------------
        shape = (1, 128, 2, 64) if rehearse else (8, 1024, 16, 64)
        rng = np.random.default_rng(0)
        q, k, v, w = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                      for _ in range(4))

        def flash(q, k, v):
            bf = jnp.bfloat16
            out = fa.flash_attention(q.astype(bf), k.astype(bf), v.astype(bf),
                                     causal=True)
            return (out.astype(jnp.float32) * w).sum(), out

        def dense(q, k, v):
            out = nn_ops.scaled_dot_product_attention(q, k, v,
                                                      is_causal=True)
            return (out * w).sum(), out

        flash_j = jax.jit(jax.value_and_grad(flash, argnums=(0, 1, 2),
                                             has_aux=True))
        if not rehearse:
            check("tpu_custom_call" in flash_j.lower(q, k, v).as_text(),
                  "flash attention did not lower to a TPU custom call")
        (_, out_f), grads_f = flash_j(q, k, v)
        with jax.default_matmul_precision("highest"):
            (_, out_d), grads_d = jax.jit(jax.value_and_grad(
                dense, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        for name, a, b in [("out", out_f, out_d)] + [
                (n, a, b) for n, a, b in zip(("dq", "dk", "dv"),
                                             grads_f, grads_d)]:
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            check(np.isfinite(a).all(), f"flash {name} not finite")
            rel = float(np.abs(a - b).max() / np.abs(b).max())
            say("kernels", f"flash_attention {shape} bf16 causal {name}: "
                f"max|a-b|/max|b| = {rel:.2e} (tol {KERNEL_BF16_REL})", kind)
            check(rel <= KERNEL_BF16_REL, f"flash {name} off by {rel}")

        # -- fused Adam update, gate on, vs the lax composition ------------
        from paddle_tpu.optimizer.optimizer import make_fused_update

        pshape = (8, 128) if rehearse else (1024, 4096)
        p = paddle.create_parameter(list(pshape), "float32")
        opt = paddle.optimizer.Adam(learning_rate=1e-3, parameters=[p])
        state = opt._create_state(p)
        pv = jnp.asarray(rng.standard_normal(pshape), jnp.float32)
        gv = jnp.asarray(rng.standard_normal(pshape), jnp.float32)
        state = dict(state,
                     moment1=0.1 * jnp.abs(gv), moment2=0.01 * gv * gv)
        lr = jnp.asarray(1e-3, jnp.float32)
        kernel_j = jax.jit(make_fused_update(opt, [p], sentinel=True))
        check(fu.supported("adam", pv, gv, state), "update shape ineligible")
        if not rehearse:
            check("tpu_custom_call" in kernel_j.lower(
                [pv], [gv], lr, [state]).as_text(),
                "fused update did not lower to a TPU custom call")
        paddle.set_flags({"FLAGS_pallas_fused_update": False})
        lax_j = jax.jit(make_fused_update(opt, [p], sentinel=True))
        for tag, g in (("finite grad", gv),
                       ("nan grad (gate holds the update)",
                        gv.at[0, 0].set(jnp.nan))):
            got = kernel_j([pv], [g], lr, [state])
            want = lax_j([pv], [g], lr, [state])
            check(bool(got[2]) == bool(want[2]) == (tag != "finite grad"),
                  "sentinel disagrees")
            flat_g = jax.tree_util.tree_leaves(got[:2])
            flat_w = jax.tree_util.tree_leaves(want[:2])
            worst = 0.0
            for a, b in zip(flat_g, flat_w):
                a, b = np.asarray(a), np.asarray(b)
                check(np.allclose(a, b, rtol=UPDATE_F32_RTOL,
                                  atol=UPDATE_F32_ATOL),
                      f"fused Adam update differs ({tag})")
                worst = max(worst, float(np.abs(a - b).max()))
            say("kernels", f"fused Adam update {pshape} f32, {tag}: "
                f"max|a-b| = {worst:.2e} (rtol {UPDATE_F32_RTOL}, "
                f"atol {UPDATE_F32_ATOL})", kind)
    finally:
        paddle.set_flags({"FLAGS_pallas_fused_update": False,
                          "FLAGS_pallas_update_interpret": False})


def compile_counts(paddle):
    """(backend compiles, persistent-cache hits) so far, as the program's
    own jax.monitoring listener counted them."""
    c = paddle.profiler.dispatch_counters()
    return c["backend_compiles"], c["compile_cache_hits"]


def phase_train(jax, paddle, kind, rehearse, profile):
    # 774M at batch 8 needs 14.3 GiB of the chip's 15.75 (PERF.md, section 4)
    bsz, seq = (2, 64) if rehearse else (4 if profile == "774m" else 8, 1024)
    cfg = model_cfg(rehearse, seq, profile or "345m")
    model, loss_fn, opt = build_trainer(paddle, cfg)
    step = paddle.jit.compile_train_step(model, loss_fn, opt)
    x, y = make_batch(paddle, cfg, bsz, seq)
    dev = jax.devices()[0]

    paddle.profiler.trace.clear()
    losses, secs = timed_steps(step, x, y, 1)
    compile_s = secs[0]
    # a first step whose program itself came from the persistent cache (the
    # machine kept it from an earlier call) has no compile time to collapse:
    # the compile event inside that step's launch says it came from the cache
    cold = not any(e.attrs["cache_hit"] for e in paddle.profiler.trace.events(
        kind="compile", site="compile_train_step/launch"))
    check(step._step._cache_size() == 1, "first step compiled != 1 program")
    compiles_before, _ = compile_counts(paddle)
    more, steady = timed_steps(step, x, y, 4)
    losses += more
    say("train", f"GPT-2 {'tiny (rehearsal)' if rehearse else profile or '345m'} "
        f"L{cfg.num_layers} h{cfg.hidden_size} b{bsz} x s{seq} AMP-O2 bf16 "
        f"AdamW: losses {[round(l, 4) for l in losses]}", kind)
    say("train", f"first step (trace + {'compile' if cold else 'cache fetch'} "
        f"+ run) {compile_s:.1f} s; "
        f"steps 2-5 {[round(s, 4) for s in steady]} s/step "
        "(smoke run, not a benchmark)", kind)
    check(all(math.isfinite(l) for l in losses), "a loss is not finite")
    check(abs(losses[0] - math.log(cfg.vocab_size)) < 0.5,
          f"first loss {losses[0]} not within 0.5 of ln(vocab)")
    check(losses[4] < losses[0], "loss did not fall over 5 steps")
    check(step._step._cache_size() == 1
          and compile_counts(paddle)[0] == compiles_before,
          "steps 2-5 compiled something")
    if not rehearse:
        for t in list(model.parameters()):
            check(isinstance(t.place, paddle.TPUPlace), "param not on TPU")
        for st in step._opt_state:
            for v in st.values():
                check(next(iter(v.devices())).platform == "tpu",
                      "optimizer state not on TPU")
    st = mem_stats(dev)
    if st:
        say("train", f"peak_bytes_in_use={fmt_gib(st['peak_bytes_in_use'])} "
            f"of bytes_limit={fmt_gib(st['bytes_limit'])}", kind)

    if profile:
        # where the step's device time goes, by the program's own tool: the
        # scopes compile_train_step and Layer.__call__ wrote, read back from
        # the trace the Profiler itself took
        with paddle.profiler.Profiler() as prof:
            for loss in [step(x, y) for _ in range(3)]:
                loss._value.block_until_ready()
        view = prof.device_view()
        prof.summary()
        check(view is not None and view["busy_s"] > 0,
              "the profiled stretch holds no device op")
        total = sum(view["sections"].values())
        check(abs(total - view["busy_s"]) <= 0.01 * view["busy_s"],
              f"sections sum to {total}, busy time is {view['busy_s']}")
        if not rehearse:
            check(set(view["kernels"]) >= {"flash_attention_fwd",
                                           "flash_attention_bwd_dkv",
                                           "flash_attention_bwd_dq"},
                  f"attention kernels not named in the trace: "
                  f"{sorted(view['kernels'])}")

    # the same program again with every in-memory cache dropped: trace,
    # lower, and the executable must come back from the PERSISTENT compile
    # cache; its HLO must carry the flash kernel
    jax.clear_caches()
    _, hits = compile_counts(paddle)
    t0 = time.perf_counter()
    compiled = step._step.lower(*step._arg_specs).compile()
    again_s = time.perf_counter() - t0
    hits_after = compile_counts(paddle)[1]
    say("train", f"same program traced and compiled again in {again_s:.1f} s "
        f"(first step {compile_s:.1f} s); persistent cache hits "
        f"+{hits_after - hits}", kind)
    if not rehearse:
        check("tpu_custom_call" in compiled.as_text(),
              "compiled train step has no flash kernel (dense or interpret)")
        check(hits_after > hits,
              "recompile did not hit the persistent compile cache")
        check(not cold or again_s < 0.5 * compile_s,
              "compile seconds did not collapse on the repeat")


def phase_serve(jax, paddle, kind, rehearse):
    import paddle_tpu.profiler as prof
    from paddle_tpu import serving
    from paddle_tpu.models import GPTForPretraining

    cfg = model_cfg(rehearse, 256 if rehearse else 2048)
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    dev = jax.devices()[0]
    weight_bytes = sum(int(np.prod(p.shape)) * p._value.dtype.itemsize
                       for p in model.parameters())
    # the default config but for the buckets; keep_logits is the instrument
    # the logits comparison below reads. Pool sizing is left to the planner.
    eng = serving.Engine(model, serving.ServingConfig(
        prompt_buckets=[32, 64, 128], keep_logits=True))
    plan = eng._pool_plan
    stats = eng.stats()
    pool_bytes = stats["pool_blocks"] * plan.block_bytes
    say("serve", f"planner: pool_blocks={stats['pool_blocks']} "
        f"pool={fmt_gib(pool_bytes)} overhead={fmt_gib(plan.overhead_bytes)} "
        f"budget={fmt_gib(plan.budget_bytes) if plan.budget_bytes else None} "
        f"weights={fmt_gib(weight_bytes)}", kind)
    st = mem_stats(dev)
    if not rehearse:
        check(plan.num_blocks == stats["pool_blocks"] and plan.budget_bytes,
              "pool was not sized by the planner from the device's limit")
        say("serve", f"bytes_in_use={fmt_gib(st['bytes_in_use'])} "
            f"bytes_limit={fmt_gib(st['bytes_limit'])}", kind)
        check(pool_bytes + weight_bytes <= st["bytes_in_use"]
              <= st["bytes_limit"], "pool + weights are not resident")
        check(pool_bytes + plan.overhead_bytes <= st["bytes_limit"],
              "planned pool + traced overhead exceed the device's limit")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (int(n),))
               for n in rng.integers(24, 129, (8,))]
    new = 16
    t0 = time.perf_counter()
    warm = eng.serve(prompts, max_new_tokens=new)
    warm_s = time.perf_counter() - t0
    check(all(r.ok for r in warm), "a warm-up request failed")
    prof.reset_dispatch_counters()
    t0 = time.perf_counter()
    out = eng.serve(prompts, max_new_tokens=new)
    serve_s = time.perf_counter() - t0
    c = prof.dispatch_counters()
    say("serve", f"8 requests x {new} new tokens: warm-up (compiles) "
        f"{warm_s:.1f} s, warm {serve_s:.2f} s; prefills "
        f"{c['serve_prefills']} decode steps {c['serve_decode_steps']} "
        f"replays {c['serve_capture_replays']} "
        "(smoke run, not a benchmark)", kind)
    check(all(r.ok and len(r.tokens) == new for r in out),
          "a request did not come back ok")
    check([r.tokens for r in out] == [r.tokens for r in warm],
          "the second serve gave other tokens than the first")
    check(c["serve_requests_dropped"] == 0, "requests dropped")
    check(c["serve_capture_replays"] > 0, "the captured rung served nothing")
    for key in ("serve_capture_fallbacks", "ladder_demotions",
                "serve_engine_restarts", "serve_capture_builds"):
        check(c[key] == 0, f"{key} = {c[key]} after warm-up")
    per_step = ((c["serve_capture_replays"] - c["serve_prefills"])
                / c["serve_decode_steps"])
    check(per_step == 1.0 and c["op_programs"] == 0,
          f"{per_step} programs per decode step, {c['op_programs']} per-op")

    # two requests against a plain full-context forward, no cache, no flash
    import jax.numpy as jnp

    pad_to = 256
    paddle.set_flags({"FLAGS_use_flash_attention": False})
    try:
        # weights go in as arguments: closed over, they would be baked into
        # the reference's HLO as a second copy of the model on the device
        state = dict(model.named_parameters())
        state.update(dict(model.named_buffers()))
        names = list(state)

        @jax.jit
        def fwd(vals, ids):
            return paddle.jit.functional_call(
                model, dict(zip(names, vals)), ids)._value

        vals = [state[n]._value for n in names]
        for i in (0, len(out) - 1):
            r, prompt = out[i], prompts[i]
            ctx = np.concatenate([prompt, np.asarray(r.tokens[:-1])])
            ids = np.zeros((1, pad_to), np.int64)
            ids[0, :ctx.size] = ctx  # causal: the padding is never seen
            ref = np.asarray(fwd(vals, jnp.asarray(ids))[0], np.float32)
            rows = ref[prompt.size - 1: prompt.size - 1 + new]
            for pos in (0, new - 1):
                err = float(np.abs(rows[pos] - r.logits[pos]).max())
                say("serve", f"request {i} (prompt {prompt.size}) generated "
                    f"position {pos}: max|logits - reference| = {err:.2e} "
                    f"(tol {SERVE_LOGIT_ATOL})", kind)
                check(err <= SERVE_LOGIT_ATOL, "logits off the reference")
            top2 = np.sort(rows, axis=-1)[:, -2:]
            sure = (top2[:, 1] - top2[:, 0]) > SERVE_LOGIT_ATOL
            agree = rows.argmax(-1) == np.asarray(r.tokens)
            say("serve", f"request {i}: tokens agree at "
                f"{int(agree.sum())}/{new} positions, "
                f"{int(sure.sum())} of them with a top-2 margin over the "
                "tolerance", kind)
            check(bool(agree[sure].all()),
                  "a token differs where the reference's margin is clear")
    finally:
        paddle.set_flags({"FLAGS_use_flash_attention": True})
    st = mem_stats(dev)
    if st:
        say("serve", f"after serving: bytes_in_use="
            f"{fmt_gib(st['bytes_in_use'])} peak_bytes_in_use="
            f"{fmt_gib(st['peak_bytes_in_use'])} bytes_limit="
            f"{fmt_gib(st['bytes_limit'])}", kind)
    eng.close()


def phase_eager(jax, paddle, kind, rehearse):
    bsz, seq = (1, 64) if rehearse else (1, 512)
    cfg = model_cfg(rehearse, seq)
    model, loss_fn, opt = build_trainer(paddle, cfg)
    x, y = make_batch(paddle, cfg, bsz, seq)
    losses, secs = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
        secs.append(time.perf_counter() - t0)
    say("eager", f"2 eager steps b{bsz} x s{seq} at default flags: losses "
        f"{[round(l, 4) for l in losses]}, {[round(s, 1) for s in secs]} s "
        "(the first compiles every op; smoke run, not a benchmark)", kind)
    check(all(math.isfinite(l) for l in losses), "a loss is not finite")
    check(losses[1] < losses[0], "eager loss did not fall")
    if not rehearse:
        check(isinstance(loss.place, paddle.TPUPlace)
              and isinstance(next(iter(model.parameters())).place,
                             paddle.TPUPlace), "eager tensors not on TPU")


# ---------------------------------------------------------------------------
# the four-chip phase
# ---------------------------------------------------------------------------
def phase_mesh4(jax, paddle, kind, rehearse):
    from paddle_tpu import parallel
    from paddle_tpu.parallel import topology

    devs = jax.devices()
    check(len(devs) == 4, f"mesh4 needs exactly four devices, got {len(devs)}")
    bsz, seq = (4, 64) if rehearse else (8, 1024)
    cfg = model_cfg(rehearse, seq)

    # -- dp2 x mp2 over the four chips --------------------------------------
    mesh = topology.init_mesh(dp=2, mp=2)
    model, loss_fn, opt = build_trainer(paddle, cfg)
    parallel.shard_params(model, mesh)
    step = parallel.sharded_train_step(model, loss_fn, opt, mesh=mesh)
    x, y = make_batch(paddle, cfg, bsz, seq)
    mesh_losses, secs = timed_steps(step, x, y, 3)
    say("mesh4", f"dp2 x mp2 sharded_train_step b{bsz} x s{seq}: losses "
        f"{[round(l, 4) for l in mesh_losses]}; first step {secs[0]:.1f} s, "
        f"then {[round(s, 4) for s in secs[1:]]} s/step "
        "(smoke run, not a benchmark)", kind)
    check(all(math.isfinite(l) for l in mesh_losses), "mesh loss not finite")

    # nothing sits on the first chip alone
    w = model.gpt.layers[0].attn.qkv_proj.weight._value
    mp_dim = w.ndim - 1
    shard_devs = {s.device for s in w.addressable_shards}
    shapes = {tuple(s.data.shape) for s in w.addressable_shards}
    want = tuple(d // 2 if i == mp_dim else d for i, d in enumerate(w.shape))
    say("mesh4", f"qkv_proj.weight {tuple(w.shape)} spec {w.sharding.spec}: "
        f"shards {sorted(shapes)} on {len(shard_devs)} devices", kind)
    check(len(shard_devs) == 4 and shapes == {want},
          "the mp-sharded weight is not split in half over four devices")
    gc.collect()  # the unsharded originals of the weights are garbage by now
    in_use = [mem_stats(d).get("bytes_in_use", 0) for d in devs]
    if not rehearse:
        say("mesh4", f"bytes_in_use per device: "
            f"{[fmt_gib(b) for b in in_use]}", kind)
        check(min(in_use) > 0 and max(in_use) <= 1.5 * min(in_use),
              "device memory is not spread evenly over the four chips")
    args = (tuple(p._value for p in step._params), tuple(step._opt_state),
            tuple(b._value for b in step._buffers),
            jax.random.PRNGKey(0), jax.numpy.asarray(1e-4, jax.numpy.float32),
            *(jax.device_put(t._value, step._shardings()[3])
              for t in (x, y)))
    t0 = time.perf_counter()
    hlo = step._step.lower(*args).compile().as_text()
    say("mesh4", f"compiled step fetched again in "
        f"{time.perf_counter() - t0:.1f} s; all-reduce ops in HLO: "
        f"{hlo.count('all-reduce(') + hlo.count('all-reduce-start(')}", kind)
    check("all-reduce" in hlo, "no all-reduce in the sharded step's HLO")

    # -- the same seed and batch through compile_train_step on one chip -----
    del step, model, opt, args, w
    gc.collect()
    topology.set_mesh(None)
    model, loss_fn, opt = build_trainer(paddle, cfg)
    one = paddle.jit.compile_train_step(model, loss_fn, opt)
    one_losses, secs = timed_steps(one, x, y, 3)
    say("mesh4", f"one-chip compile_train_step, same seed and batch: losses "
        f"{[round(l, 4) for l in one_losses]}; first step {secs[0]:.1f} s, "
        f"then {[round(s, 4) for s in secs[1:]]} s/step", kind)
    diffs = [abs(a - b) for a, b in zip(mesh_losses, one_losses)]
    say("mesh4", f"per-step |mesh - one chip| = "
        f"{[round(d, 5) for d in diffs]} (tol {MESH_LOSS_ATOL})", kind)
    check(max(diffs) <= MESH_LOSS_ATOL, "mesh losses differ from one chip's")


# ---------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny config on whatever backend is there; never "
                         "prints the ok line")
    ap.add_argument("--profile", choices=("345m", "774m"),
                    help="only device + train on that model, with a profiled "
                         "stretch and the profiler's summary()")
    args = ap.parse_args()

    import jax

    import paddle_tpu as paddle

    d0 = jax.devices()[0]
    kind = d0.device_kind
    if not args.rehearse and d0.platform != "tpu":
        print(f"chip_smoke: no accelerator (platform={d0.platform}); "
              "this script does not carry on on the CPU", file=sys.stderr)
        return 1
    if args.chips == 4:
        phases = [("mesh4", lambda: phase_mesh4(jax, paddle, kind,
                                                args.rehearse))]
    else:
        phases = [
            ("device", lambda: phase_device(jax, kind)),
            ("kernels", lambda: phase_kernels(jax, paddle, kind,
                                              args.rehearse)),
            ("train", lambda: phase_train(jax, paddle, kind, args.rehearse,
                                          args.profile)),
            ("serve", lambda: phase_serve(jax, paddle, kind, args.rehearse)),
            ("eager", lambda: phase_eager(jax, paddle, kind, args.rehearse)),
        ]
        if args.profile:
            phases = [p for p in phases if p[0] in ("device", "train")]
    t_all = time.perf_counter()
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        # a phase's arrays and executables leave the device before the next
        gc.collect()
        jax.clear_caches()
        say(name, f"phase ok in {time.perf_counter() - t0:.1f} s", kind)
    print(f"all phases ok in {time.perf_counter() - t_all:.1f} s "
          f"(device_kind={kind})", flush=True)
    if args.rehearse:
        print("rehearsal only: no result line", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": kind, "count": len(jax.devices())}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
