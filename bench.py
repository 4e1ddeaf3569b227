"""Benchmark: GPT-2 345M pretraining tokens/sec/chip (BASELINE config 4).

Prints ONE JSON line: {"metric", "value", "unit"}. The reference repo
publishes no numbers (BASELINE.md: `published: {}`), so there is no baseline
ratio. Exits non-zero when no backend comes up, and when any trajectory block
or extra config failed.

Also measures (as '#'-prefixed stderr/commented stdout lines, keeping the
one-JSON-line stdout contract):
  - BASELINE config 2: ResNet-50 AMP-O2 imgs/sec/chip (synthetic data)
  - BASELINE config 1: MNIST LeNet eager-dispatch steps/sec (per-op path)

Env knobs: BENCH_STEPS (default 10), BENCH_BATCH (default 8),
BENCH_SEQ (default 1024), BENCH_MODEL (345m|small|tiny),
BENCH_EXTRA=0 to skip the ResNet/MNIST configs,
BENCH_REPS (default 3; 4 for eager) timed windows per config — best
window is reported (min-of-N: host load only ever slows a window).
"""
import json
import os
import sys
import time

import numpy as np


def _tb_tail(e, n=4):
    """Last `n` traceback lines of an exception, one stderr-friendly line —
    a failed bench block must say WHERE it died, not just the repr."""
    import traceback

    lines = traceback.format_exception(type(e), e, e.__traceback__)
    tail = [ln.strip().replace("\n", " | ") for ln in lines[-n:]]
    return f"{type(e).__name__}: {e} [tb: " + " | ".join(tail) + "]"


def _best_window(run_window, reps=None):
    """Run a self-syncing timed window `reps` times, return the best (min)
    duration. Ambient host load only ever slows a window down, so a single
    window samples that noise and min-of-N rejects it."""
    reps = int(os.environ.get("BENCH_REPS", 3)) if reps is None else reps
    best = float("inf")
    for _ in range(max(1, reps)):
        best = min(best, run_window())
    return best


def _median_best_window(run_window, reps=None):
    """Median of the best half of N timed windows. Pure min-of-N tracks the
    single luckiest window, which makes a short-step (eager) number jitter
    from run to run. Median-of-best keeps the congestion-rejection
    property of min-of-N but anchors the report on several good windows, so
    run-to-run noise stops masking real wins. Used by the eager configs;
    compiled-step configs keep min-of-N (their windows are long and stable).
    """
    reps = int(os.environ.get("BENCH_REPS", 6)) if reps is None else reps
    times = sorted(run_window() for _ in range(max(1, reps)))
    best = times[: max(1, len(times) // 2)]
    return best[len(best) // 2]


def _timed(step_fn, steps, reps=None, sync=float, median_best=False):
    """Best-of-N (or median-of-best-half) duration of `steps` calls to
    step_fn. `sync` forces the async chain (host read via float by default;
    None for host-only work) so the timer covers real execution, not
    queueing."""

    def window():
        t0 = time.time()
        last = None
        for _ in range(steps):
            last = step_fn()
        if sync is not None:
            sync(last)
        return time.time() - t0

    if median_best:
        return _median_best_window(window, reps)
    return _best_window(window, reps)


def _host_breakdown(step_fn, steps, sync=float):
    """Host-side time breakdown of `steps` steady-state calls, from the
    dispatch_counters timers (PR 6): trace ms (aval inference), compile ms
    (main-thread-blocking fresh compiles), replay ms (cached replays +
    async joins), and async_compile ms (background-thread compile time that
    left the critical path). Per-step milliseconds."""
    import paddle_tpu.profiler as prof

    prof.reset_dispatch_counters()
    t0 = time.time()
    last = None
    for _ in range(steps):
        last = step_fn()
    if sync is not None:
        sync(last)
    wall = (time.time() - t0) * 1000.0 / steps
    c = prof.dispatch_counters()
    return {
        "trace_ms": round(c["trace_time_ms"] / steps, 3),
        "compile_ms": round(c["compile_time_ms"] / steps, 3),
        "replay_ms": round(c["replay_time_ms"] / steps, 3),
        "async_compile_ms": round(c["async_compile_ms"] / steps, 3),
        "wall_ms": round(wall, 3),
    }


def bench_resnet50(steps=8, bsz=256):
    """BASELINE config 2: ResNet-50, AMP O2 bf16, compiled train step.

    b256 is the batch a builder's sweep from before PR 1 settled on (not in
    the ledger; not measured on the current chip).
    """
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = paddle.amp.decorate(resnet50(num_classes=1000), level="O2", dtype="bfloat16")
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    step = paddle.jit.compile_train_step(
        model, lambda out, y: loss_fn(out.astype("float32"), y), opt
    )
    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(rng.standard_normal((bsz, 3, 224, 224)), jnp.float32))
    y = jax.device_put(jnp.asarray(rng.integers(0, 1000, (bsz,)), jnp.int64))
    xt = paddle.Tensor(x, stop_gradient=True)
    yt = paddle.Tensor(y, stop_gradient=True)
    float(step(xt, yt))  # compile
    float(step(xt, yt))
    dt = _timed(lambda: step(xt, yt), steps)
    return {"metric": "resnet50_amp_o2_imgs_per_sec_per_chip",
            "value": round(bsz * steps / dt, 1), "unit": "imgs/s/chip"}


def bench_bert(steps=6, bsz=8, seq=512):
    """BASELINE config 3: BERT-base pretraining (MLM+NSP), AMP O2."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.bert import (
        BertConfig,
        BertForPretraining,
        BertPretrainingCriterion,
    )

    paddle.seed(0)
    cfg = BertConfig(max_seq_len=seq, dropout=0.0, attn_dropout=0.0)
    model = paddle.amp.decorate(BertForPretraining(cfg), level="O2", dtype="bfloat16")
    crit = BertPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(out, packed):
        mlm_logits, nsp_logits = out
        return crit(
            mlm_logits.astype("float32"), nsp_logits.astype("float32"),
            packed[:, :-1], packed[:, -1],
        )

    step = paddle.jit.compile_train_step(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    ids = jax.device_put(jnp.asarray(rng.integers(0, cfg.vocab_size, (bsz, seq)), jnp.int32))
    packed = jax.device_put(jnp.asarray(
        np.concatenate(
            [rng.integers(0, cfg.vocab_size, (bsz, seq)), rng.integers(0, 2, (bsz, 1))],
            axis=1,
        ), jnp.int64,
    ))
    x = paddle.Tensor(ids, stop_gradient=True)
    y = paddle.Tensor(packed, stop_gradient=True)
    float(step(x, y))
    float(step(x, y))
    dt = _timed(lambda: step(x, y), steps)
    return {"metric": "bert_base_pretrain_tokens_per_sec_per_chip",
            "value": round(bsz * seq * steps / dt, 1), "unit": "tokens/s/chip"}


def bench_ps_table(iters=10, batch=65536, dim=64):
    """BASELINE config 5 slice: host sparse-table pull+push throughput."""
    from paddle_tpu.distributed.ps import MemorySparseTable

    t = MemorySparseTable(dim, shard_num=32, init_range=0.01)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 10_000_000, batch)
    grads = rng.standard_normal((batch, dim)).astype(np.float32)
    t.pull(keys)  # warm (creates entries)
    dt = _timed(lambda: (t.pull(keys), t.push(keys, grads)), iters,
                sync=None)
    return {"metric": "ps_sparse_pull_push_m_lookups_per_sec",
            "value": round(batch * iters * 2 / dt / 1e6, 2), "unit": "M lookups/s"}


def bench_ps_wire(iters=10, batch=65536, dim=64):
    """PS WIRE path: DistributedSparseTable pull+push through PsClient's
    framed-TCP protocol against 2 local servers (the r3 verdict's point:
    the in-process table number never touched the wire)."""
    from paddle_tpu.distributed.ps import (
        DistributedSparseTable, PsClient, PsServer,
    )

    s0 = PsServer(port=0, server_id=0, n_servers=2, n_trainers=1)
    s1 = PsServer(port=0, server_id=1, n_servers=2, n_trainers=1)
    c = PsClient([f"127.0.0.1:{s0.port}", f"127.0.0.1:{s1.port}"],
                 trainer_id=0)
    try:
        t = DistributedSparseTable(c, 1, emb_dim=dim, shard_num=32,
                                   init_range=0.01)
        rng = np.random.default_rng(0)
        keys = rng.integers(0, 10_000_000, batch)
        grads = rng.standard_normal((batch, dim)).astype(np.float32)
        t.pull(keys)  # warm (creates entries, opens connections)
        dt = _timed(lambda: (t.pull(keys), t.push(keys, grads)), iters,
                    sync=None)
        return {"metric": "ps_wire_pull_push_m_lookups_per_sec",
                "value": round(batch * iters * 2 / dt / 1e6, 2),
                "unit": "M lookups/s"}
    finally:
        c.stop_servers()


def bench_gpt_longseq(steps=6, bsz=2, seq=4096):
    """Long-context GPT: seq 4096 through the Pallas flash-attention path —
    the capability the reference lacks (SURVEY §5). Recompute off: 345M at
    seq 4k, batch 2 fits HBM; BENCH_RECOMPUTE=1 turns recompute on for
    longer contexts."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTPretrainingCriterion, gpt2_345m, GPTForPretraining

    paddle.seed(0)
    cfg = gpt2_345m(max_seq_len=seq)
    cfg.dropout = 0.0
    cfg.attn_dropout = 0.0
    cfg.use_recompute = os.environ.get("BENCH_RECOMPUTE", "0") == "1"
    model = paddle.amp.decorate(GPTForPretraining(cfg), level="O2", dtype="bfloat16")
    criterion = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = paddle.jit.compile_train_step(
        model, lambda o, t: criterion(o.astype("float32"), t), opt
    )
    rng = np.random.default_rng(0)
    ids = jax.device_put(
        jnp.asarray(rng.integers(0, cfg.vocab_size, (bsz, seq + 1)), jnp.int32)
    )
    x = paddle.Tensor(ids[:, :-1], stop_gradient=True)
    y = paddle.Tensor(ids[:, 1:], stop_gradient=True)
    float(step(x, y))
    float(step(x, y))
    dt = _timed(lambda: step(x, y), steps)
    return {"metric": f"gpt2_345m_seq{seq}_tokens_per_sec_per_chip",
            "value": round(bsz * seq * steps / dt, 1), "unit": "tokens/s/chip"}


def bench_dataloader(n=1024, bsz=64, workers=4):
    """Input-pipeline throughput: multiprocess DataLoader feeding
    ResNet-shaped batches. TPU-native input discipline: workers do the CPU work
    (decode-style gather + crop) and ship uint8 HWC — 4x less bytes than
    f32; normalize/cast runs on-device inside the compiled step.
    return_numpy: upload belongs to the train step. The loader must beat
    the compiled step's consumption so input never starves it."""
    from paddle_tpu.io import DataLoader, Dataset

    class SynthImages(Dataset):
        def __len__(self):
            return n

        def __getitem__(self, i):
            # stand-in for decode+augment: deterministic pixel synthesis +
            # random-crop-style slicing, all CPU-side in the worker
            base = np.empty((240, 240, 3), np.uint8)
            base[...] = (i * 37) % 251
            base[::7, :, 0] ^= np.uint8(i % 17)
            off = i % 16
            img = base[off:off + 224, off:off + 224]
            return np.ascontiguousarray(img), np.int64(i % 1000)

    loader = DataLoader(SynthImages(), batch_size=bsz, num_workers=workers,
                        return_numpy=True)
    it = iter(loader)
    next(it)  # pool warmup
    t0 = time.time()
    cnt = 0
    for xb, yb in it:
        cnt += int(xb.shape[0])
    dt = time.time() - t0
    return {"metric": "dataloader_mp_imgs_per_sec", "value": round(cnt / dt, 1),
            "unit": "imgs/s"}


def bench_ernie_ctr(steps=8, bsz=32):
    """BASELINE config 5 end-to-end: ERNIE-style sparse CTR training —
    host PS sparse pull → compiled dense transformer step (row grads out)
    → host push with the C++ AdaGrad accessor. Measures the full
    interleaved loop, not an isolated table slice."""
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "examples"))
    from ernie_ctr import (ErnieCtrConfig, build, synthetic_batch,
                           train_pipelined, train_step)

    cfg = ErnieCtrConfig()
    table, model, step = build(cfg)
    rng = np.random.default_rng(0)
    batches = [synthetic_batch(cfg, bsz, rng) for _ in range(steps)]
    train_step(table, step, cfg, *batches[0])  # compile + warm the table

    def window():
        # the async-communicator loop: next-batch pulls + queued pushes
        # overlap the device step (examples/ernie_ctr.train_pipelined)
        t0 = time.time()
        train_pipelined(table, step, cfg, batches)
        return time.time() - t0

    dt = _best_window(window)
    return {"metric": "ernie_ctr_sparse_ps_tokens_per_sec_per_chip",
            "value": round(bsz * cfg.seq_len * steps / dt, 1),
            "unit": "tokens/s/chip"}


def bench_mnist_eager(steps=30, bsz=64):
    """BASELINE config 1: LeNet MNIST pure-eager — per-op dispatch overhead."""
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3, parameters=model.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((bsz, 1, 28, 28)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, (bsz,)))
    # warmup (per-op jit caches fill)
    for _ in range(3):
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
    float(loss)

    def eager_step():
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    # eager per-op dispatch is the noisiest config (one program launch per
    # op): use more windows (BENCH_REPS default 6 here) and report the
    # median of the best half so one lucky window stops deciding the number
    dt = _timed(eager_step, steps, median_best=True)

    # programs-per-step accounting: count one
    # steady-state step per mode via the dispatch counters, and time lazy /
    # captured windows for comparison. '#'-prefixed on stderr — the
    # one-JSON-line stdout contract stays intact.
    import paddle_tpu.profiler as prof

    prof.reset_dispatch_counters()
    float(eager_step())
    per_op_programs = prof.dispatch_counters()["programs"]
    paddle.set_flags({"FLAGS_eager_lazy_dispatch": True,
                      "FLAGS_eager_step_capture": False})
    try:
        for _ in range(3):  # warm the segment/tape/optimizer compile caches
            loss = eager_step()
        float(loss)
        prof.reset_dispatch_counters()
        float(eager_step())
        lazy_programs = prof.dispatch_counters()["programs"]
        lazy_dt = _timed(eager_step, steps, median_best=True)
        lazy_host = _host_breakdown(eager_step, steps)
        # whole-step capture: after FLAGS_eager_capture_warmup stable steps
        # the step replays as ONE donated XLA program (forward + backward +
        # optimizer update in place)
        paddle.set_flags({"FLAGS_eager_step_capture": True})
        for _ in range(4):  # arm the controller + compile the captured step
            loss = eager_step()
        # join the background capture build (FLAGS_eager_async_compile):
        # the measured step must replay the finished executable, not race
        # the compile thread into another pending-resolution step
        paddle.device.synchronize()
        float(loss)
        loss = eager_step()  # join + first replay
        float(loss)
        prof.reset_dispatch_counters()
        float(eager_step())
        cap_counters = prof.dispatch_counters()
        cap_programs = cap_counters["programs"]
        cap_dt = _timed(eager_step, steps, median_best=True)
        cap_host = _host_breakdown(eager_step, steps)
    finally:
        paddle.set_flags({"FLAGS_eager_lazy_dispatch": False,
                          "FLAGS_eager_step_capture": True})
    from paddle_tpu.core.lazy import step_capture_state

    # estimated peak HBM per regime (analysis.memory liveness planner over
    # the captured whole-step program): the captured regime gets donation
    # credit; per-op and lazy run the same op set with no donation, so the
    # no-donation plan is their shared estimate (MEMORY_PLAN.md) — this is
    # the memory trajectory BENCH_* files track
    est_mem = None
    try:
        from paddle_tpu.analysis import memory as _mem

        plans = _mem.captured_step_plans()
        if plans is not None:
            cap_plan, nodon_plan = plans
            mb = lambda n: round(n / 2**20, 2)  # noqa: E731
            est_mem = {
                "per_op": mb(nodon_plan.peak_bytes),
                "lazy": mb(nodon_plan.peak_bytes),
                "captured": mb(cap_plan.peak_bytes),
                "donation_credit": mb(cap_plan.donation_credit_bytes),
            }
            print(f"# mnist est peak HBM (MB): per-op/lazy={est_mem['lazy']} "
                  f"captured={est_mem['captured']} "
                  f"(donation credit {est_mem['donation_credit']})",
                  file=sys.stderr)
    except Exception as e:
        print(f"# mnist memory estimate FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)

    cap_state = step_capture_state()
    print(f"# mnist eager programs/step: per-op={per_op_programs} "
          f"lazy={lazy_programs} captured={cap_programs} "
          f"(FLAGS_eager_lazy_dispatch / FLAGS_eager_step_capture); "
          f"lazy {round(steps / lazy_dt, 1)} steps/s, "
          f"captured {round(steps / cap_dt, 1)} steps/s "
          f"(median-of-best windows)",
          file=sys.stderr)
    print(f"# mnist capture state: armed={cap_state['armed']} "
          f"cached_steps={cap_state['cached_steps']} "
          f"replays={cap_counters['capture_replays']} "
          f"builds={cap_counters['capture_builds']} "
          f"fallbacks={cap_counters['capture_fallbacks']} "
          f"evictions={cap_counters['capture_evictions']}",
          file=sys.stderr)
    print(f"# mnist host breakdown (ms/step, steady state): "
          f"lazy trace={lazy_host['trace_ms']} "
          f"compile={lazy_host['compile_ms']} "
          f"replay={lazy_host['replay_ms']} of {lazy_host['wall_ms']}; "
          f"captured trace={cap_host['trace_ms']} "
          f"compile={cap_host['compile_ms']} "
          f"replay={cap_host['replay_ms']} of {cap_host['wall_ms']} "
          f"(async_compile_ms off the critical path: "
          f"lazy={lazy_host['async_compile_ms']} "
          f"captured={cap_host['async_compile_ms']})",
          file=sys.stderr)

    rec = {"metric": "mnist_lenet_eager_steps_per_sec",
           "value": round(steps / dt, 1), "unit": "steps/s",
           # timing discipline (PR 6 de-noise): median of the best half of
           # BENCH_REPS windows, not min-of-N
           "window_report": "median_of_best",
           "lazy_steps_per_sec": round(steps / lazy_dt, 1),
           "captured_steps_per_sec": round(steps / cap_dt, 1),
           # host-side per-step time breakdown from dispatch_counters()
           # timers (trace / blocking-compile / replay; async_compile_ms is
           # background-thread work that left the critical path)
           "host_breakdown": {"lazy": lazy_host, "captured": cap_host}}
    if est_mem is not None:
        rec["est_peak_hbm_mb"] = est_mem
    return rec


def bench_serving(n_requests=12, max_new=24):
    """The serving row (ROADMAP open item 2): the paddle.serving
    continuous-batching engine over a small GPT — p50/p99 per-token latency,
    requests/s/chip, tokens/s/chip, programs-per-decode-step (must be 1.0:
    each decode step is one captured donated replay), and KV block-pool
    occupancy. BENCH_SERVING_MODEL=345m scales the model up."""
    import paddle_tpu as paddle
    import paddle_tpu.profiler as prof
    from paddle_tpu import serving
    from paddle_tpu.models import GPTConfig, GPTForPretraining, gpt2_345m

    paddle.seed(0)
    which = os.environ.get("BENCH_SERVING_MODEL", "tiny")
    if which == "345m":
        cfg = gpt2_345m(max_seq_len=2048)
    else:
        cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4,
                        num_heads=8, max_seq_len=512)
    cfg.dropout = 0.0
    cfg.attn_dropout = 0.0
    model = GPTForPretraining(cfg)
    model.eval()
    engine = serving.Engine(model, serving.ServingConfig(
        block_size=16, prompt_buckets=[32, 64, 128]))
    rng = np.random.default_rng(0)
    lens = [32, 64, 48, 128, 64, 32]
    prompts = [rng.integers(1, cfg.vocab_size, lens[i % len(lens)])
               for i in range(n_requests)]
    # warm with the SAME mix: every (prompt bucket, batch bucket, context
    # bucket) signature the measured window will hit compiles here, so the
    # window is pure steady-state replay (capture_builds_steady must be 0)
    engine.serve(prompts, max_new_tokens=max_new)
    prof.reset_dispatch_counters()
    engine.reset_stats()  # percentiles must not include warm-window compiles
    t0 = time.time()
    resps = engine.serve(prompts, max_new_tokens=max_new)
    dt = time.time() - t0
    c = prof.dispatch_counters()
    st = engine.stats()
    completed = sum(1 for r in resps if r.ok)
    tokens = sum(len(r.tokens) for r in resps if r.ok)
    programs_per_decode = (
        (c["serve_capture_replays"] - c["serve_prefills"])
        / max(1, c["serve_decode_steps"]))
    rec = {
        "metric": "serving_requests_per_sec_per_chip",
        "value": round(completed / dt, 2), "unit": "requests/s/chip",
        "tokens_per_sec_per_chip": round(tokens / dt, 1),
        "token_lat_p50_ms": st["token_lat_p50_ms"],
        "token_lat_p99_ms": st["token_lat_p99_ms"],
        "programs_per_decode_step": round(programs_per_decode, 3),
        "decode_steps": c["serve_decode_steps"],
        "capture_builds_steady": c["serve_capture_builds"],
        "kv_pool_blocks": st["pool_blocks"],
        "kv_pool_peak_occupancy": st["pool_peak_occupancy"],
        "requests": n_requests, "completed": completed,
        "dropped": c["serve_requests_dropped"],
    }
    if "est_decode_peak_hbm_mb" in st:
        rec["est_decode_peak_hbm_mb"] = st["est_decode_peak_hbm_mb"]
    return rec


def bench_serving_overload(n=12, max_new=16):
    """The overload row (ISSUE 11): the engine under a 2× sustained
    oversubmit with the queue-wait p99 trip wire open — goodput (completed
    requests/s), shed rate, and interactive p99 latency vs its deadline.
    The engine must keep interactive goodput while batch sheds with
    structured retriable responses: zero drops, zero leaked KV blocks."""
    import paddle_tpu as paddle
    import paddle_tpu.profiler as prof
    from paddle_tpu import serving
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4,
                    num_heads=8, max_seq_len=512, dropout=0.0,
                    attn_dropout=0.0)
    model = GPTForPretraining(cfg)
    model.eval()
    paddle.set_flags({"FLAGS_serving_queue_wait_p99_ms": 1.0,
                      "FLAGS_serving_queue_max": 64})
    try:
        engine = serving.Engine(model, serving.ServingConfig(
            block_size=16, prompt_buckets=[32, 64]))
        rng = np.random.default_rng(0)
        warm = [rng.integers(1, cfg.vocab_size, 32) for _ in range(10)]
        # warm: compile + seed cost EMAs + arm the trip wire's sample gate
        engine.serve(warm, max_new_tokens=max_new)
        prof.reset_dispatch_counters()
        engine.reset_stats()
        deadline_ms = 120_000.0
        subs = []
        t0 = time.time()
        for _ in range(n):  # 2x: every interactive has a batch twin
            for prio in ("interactive", "batch"):
                rid = engine.submit(
                    rng.integers(1, cfg.vocab_size, 32),
                    max_new_tokens=max_new, deadline_ms=deadline_ms,
                    priority=prio)
                subs.append((rid, prio))
        engine.run_until_idle()
        dt = time.time() - t0
        resps = {rid: engine.pop_response(rid) for rid, _ in subs}
        c = prof.dispatch_counters()
    finally:
        paddle.set_flags({"FLAGS_serving_queue_wait_p99_ms": 0.0,
                          "FLAGS_serving_queue_max": 256})
    inter = [resps[r] for r, p in subs if p == "interactive"]
    lat = [r.latency_ms for r in inter if r is not None and r.ok]
    completed = sum(1 for r in resps.values() if r is not None and r.ok)
    shed = sum(1 for r in resps.values()
               if r is not None and r.status == "overloaded")
    return {
        "metric": "serving_overload_goodput_req_per_sec",
        "value": round(completed / dt, 2), "unit": "requests/s/chip",
        "offered": len(subs), "completed": completed,
        "shed": shed, "shed_rate": round(shed / len(subs), 3),
        "interactive_completed": sum(1 for r in inter if r.ok),
        "interactive_p99_ms": (
            round(float(np.percentile(lat, 99)), 1) if lat else None),
        "interactive_deadline_ms": deadline_ms,
        "expired": c["serve_deadline_expired"],
        "dropped": c["serve_requests_dropped"],
        "block_leaks": c["serve_block_leaks"],
        "engine_health": engine.stats()["health"],
    }


def _serving_fleet_block(n=12, max_new=16, reps=3):
    """The fleet front-door row (ISSUE 20): requests/s over a two-replica
    FrontDoor at a 2x oversubmit, TTFT p99, reroute/shed counts, autoscale
    proposals against a MemoryKv coordinator — and the router-overhead
    gate: a single-replica FrontDoor must stay within 1% of the bare
    engine's tokens/s (the router is dict work between decode steps, not
    a serving-path tax). Best-of-``reps`` windows on both sides so the
    gate measures the router, not scheduler jitter."""
    import paddle_tpu as paddle
    import paddle_tpu.profiler as prof
    from paddle_tpu import serving
    from paddle_tpu.distributed.fleet.elastic import RescaleCoordinator
    from paddle_tpu.distributed.fleet.obs import MemoryKv
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4,
                    num_heads=8, max_seq_len=512, dropout=0.0,
                    attn_dropout=0.0)
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, 32) for _ in range(n)]
    mk = lambda: serving.Engine(model, serving.ServingConfig(
        block_size=16, prompt_buckets=[32, 64]))

    def fd_window(fd):
        t0 = time.time()
        frids = [fd.submit(p, max_new_tokens=max_new) for p in prompts]
        fd.run_until_idle(timeout_s=300.0)
        dt = time.time() - t0
        out = [fd.pop_response(f) for f in frids]
        return dt, out

    # -- overhead gate: router bookkeeping as a fraction of wall time ----
    # a throughput A/B against the bare engine reads scheduler noise as
    # router overhead (±5% window-to-window on a shared CPU); instead
    # time the engine's own step() inside the front-door window and
    # attribute the remainder — refresh/poll/redispatch/emit/audit, i.e.
    # THE ROUTER — to overhead. Best (min) of ``reps`` windows.
    eng = mk()
    eng.serve(prompts, max_new_tokens=max_new)  # warm: compile everything
    fd1 = serving.FrontDoor([eng])
    rep0 = fd1._replicas[0]
    engine_step, orig_step = [0.0], rep0.step

    def timed_step():
        t = time.perf_counter()
        ran = orig_step()
        engine_step[0] += time.perf_counter() - t
        return ran

    rep0.step = timed_step
    fd_window(fd1)  # warm the router path too (tracking dicts, emits)
    overhead_pct, fd_tps = 100.0, 0.0
    for _ in range(reps):
        engine_step[0] = 0.0
        dt, out = fd_window(fd1)
        toks = sum(len(r.tokens) for r in out if r is not None and r.ok)
        fd_tps = max(fd_tps, toks / dt)
        overhead_pct = min(overhead_pct,
                           (dt - engine_step[0]) / dt * 100.0)
    rep0.step = orig_step
    fd1.close(close_replicas=False)

    # -- two-replica fleet at 2x, autoscaler armed against MemoryKv ------
    paddle.set_flags({"FLAGS_router_autoscale_p99_ms": 1.0,
                      "FLAGS_router_autoscale_sustain_s": 0.0,
                      "FLAGS_router_autoscale_cooldown_s": 3600.0,
                      "FLAGS_router_autoscale_idle_s": 0.0})
    try:
        kv = MemoryKv()
        coord = RescaleCoordinator(kv=kv, job_id="bench-fleet",
                                   node_id="router", np_min=2, np_max=8)
        eng2 = mk()
        eng2.serve(prompts, max_new_tokens=max_new)  # warm replica 2 too
        fd = serving.FrontDoor([eng, eng2], coordinator=coord)
        prof.reset_dispatch_counters()
        storm = prompts * 2  # 2x the single-engine working set
        t0 = time.time()
        frids = [fd.submit(p, max_new_tokens=max_new) for p in storm]
        fd.run_until_idle(timeout_s=600.0)
        dt = time.time() - t0
        out = [fd.pop_response(f) for f in frids]
        c = prof.dispatch_counters()
        fd.close()
    finally:
        paddle.set_flags({"FLAGS_router_autoscale_p99_ms": 0.0,
                          "FLAGS_router_autoscale_sustain_s": 5.0,
                          "FLAGS_router_autoscale_cooldown_s": 30.0,
                          "FLAGS_router_autoscale_idle_s": 30.0})
    ok = [r for r in out if r is not None and r.ok]
    ttft = [(r.first_token_time - r.submit_time) * 1000.0 for r in ok
            if r.first_token_time is not None]
    return {
        "fleet_requests_per_sec": round(len(ok) / dt, 2),
        "fleet_size": 2,
        "offered": len(storm), "completed": len(ok),
        "ttft_p99_ms": (round(float(np.percentile(ttft, 99)), 1)
                        if ttft else None),
        "reroutes": c["router_reroutes"],
        "shed_reroutes": c["router_shed_reroutes"],
        "autoscale_grow_proposals": c["router_autoscale_grow_proposals"],
        "dropped": c["router_requests_dropped"],
        "frontdoor_tokens_per_sec": round(fd_tps, 1),
        "router_overhead_pct": round(overhead_pct, 2),
        "router_overhead_ok": bool(overhead_pct < 1.0),
    }


def _resilience_block(steps=8, bsz=16):
    """Resilience micro-probe for the BENCH_* trajectory (ISSUE 5): retries/
    fallbacks under an injected fault plan, per-step recovery overhead, and
    proof the numeric-rescue sentinel is free — steps/s with and without it
    on the lazy LeNet step (programs-per-step must not change)."""
    import paddle_tpu as paddle
    import paddle_tpu.profiler as prof
    import paddle_tpu.resilience as res
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((bsz, 1, 28, 28)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, (bsz,)))

    def step():
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    paddle.set_flags({"FLAGS_eager_lazy_dispatch": True,
                      "FLAGS_eager_step_capture": False})
    try:
        for _ in range(3):  # warm the segment/tape/optimizer caches
            loss = step()
        float(loss)
        clean_dt = _timed(step, steps)
        # sentinel on: one extra fused scalar, zero extra programs
        paddle.set_flags({"FLAGS_numeric_rescue": "skip"})
        for _ in range(2):
            loss = step()
        float(loss)
        rescue_dt = _timed(step, steps)
        rescue_programs = prof.measure_programs(step)["programs"]
        paddle.set_flags({"FLAGS_numeric_rescue": ""})
        # faulted window: every site faults once per step, retry recovers
        res.reset()
        prof.reset_dispatch_counters()
        paddle.set_flags({"FLAGS_fault_inject": "execute:p=1:x=1",
                          "FLAGS_retry_backoff_ms": 0.5})
        fault_dt = _timed(step, steps)
        c = prof.dispatch_counters()
    finally:
        paddle.set_flags({"FLAGS_fault_inject": "",
                          "FLAGS_numeric_rescue": "",
                          "FLAGS_eager_lazy_dispatch": False,
                          "FLAGS_eager_step_capture": True,
                          "FLAGS_retry_backoff_ms": 5.0})
        res.reset()
    return {
        "steps_per_s_clean": round(steps / clean_dt, 1),
        "steps_per_s_rescue": round(steps / rescue_dt, 1),
        "sentinel_overhead_pct": round((rescue_dt - clean_dt) / clean_dt * 100, 1),
        "rescue_programs_per_step": rescue_programs,
        "retries": c["retry_attempts"],
        "injected_faults": c["injected_faults"],
        "capture_fallbacks": c["capture_fallbacks"],
        "segment_per_op_fallbacks": c["segment_per_op_fallbacks"],
        "recovery_overhead_ms_per_step": round(
            (fault_dt - clean_dt) / steps * 1000, 2),
        "retry_backoff_ms": round(c["retry_backoff_ms"], 1),
    }


def _checkpoint_block(steps=120, bsz=16):
    """Checkpoint-overhead probe for the BENCH_* trajectory (ISSUE 8):
    steady LeNet steps/s with checkpointing off vs save_freq='auto' on
    (CheckFreq cadence tuning + pipelined snapshots), the measured overhead
    % against the FLAGS_ckpt_overhead_pct budget, and the per-phase
    snapshot/transfer/commit ms — proof the persist overlaps compute."""
    import tempfile

    import paddle_tpu as paddle
    import paddle_tpu.profiler as prof
    from paddle_tpu.distributed.checkpoint import (
        AsyncCheckpointer,
        train_step_range,
        training_state,
    )
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((bsz, 1, 28, 28)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, (bsz,)))

    def step():
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return float(loss)

    paddle.set_flags({"FLAGS_eager_lazy_dispatch": True,
                      "FLAGS_eager_step_capture": True})
    try:
        for _ in range(5):  # warm + arm + replay the captured step
            step()
        paddle.device.synchronize()
        off_dt = _timed(step, steps, median_best=True)

        with tempfile.TemporaryDirectory() as ckdir:
            prof.reset_dispatch_counters()
            ck = AsyncCheckpointer(ckdir, max_to_keep=2)
            state = training_state(model, opt)
            # per-boundary wall times (the boundary includes the cadenced
            # snapshot when one fires), reported with the same
            # median-of-best-half discipline as the off window so the
            # bootstrap save's one-time costs (copy-program compile,
            # backend init) don't masquerade as steady-state overhead
            laps = []
            t0 = time.perf_counter()
            for _ in train_step_range(steps, ck, state, save_freq="auto"):
                step()
                t1 = time.perf_counter()
                laps.append(t1 - t0)
                t0 = t1
            tuner_state = ck.tuner.state()
            c = prof.dispatch_counters()
        best = sorted(laps)[: max(1, len(laps) // 2)]
        on_step_s = sorted(best)[len(best) // 2]  # median of best half
    finally:
        paddle.set_flags({"FLAGS_eager_lazy_dispatch": False,
                          "FLAGS_eager_step_capture": True})
    saves = max(1, c["ckpt_snapshots"])
    return {
        "steps_per_s_ckpt_off": round(steps / off_dt, 1),
        "steps_per_s_ckpt_auto": round(1.0 / on_step_s, 1),
        "overhead_budget_pct": tuner_state["budget_pct"],
        "overhead_measured_pct": tuner_state["measured_overhead_pct"],
        "auto_save_freq": tuner_state["save_freq"],
        "saves": c["ckpt_snapshots"],
        "async_saves": c["ckpt_async_saves"],
        # steady-state phase costs from the tuner EMAs (the bootstrap
        # save's one-time compile/init costs are discarded there)
        "snapshot_ms_steady": tuner_state["snapshot_ms"],
        "persist_ms_steady": tuner_state["persist_ms"],
        # raw aggregate means INCLUDING the compile-heavy bootstrap save
        "snapshot_ms_mean": round(c["ckpt_snapshot_ms"] / saves, 3),
        "transfer_ms_mean": round(c["ckpt_transfer_ms"] / saves, 3),
        "commit_ms_mean": round(c["ckpt_commit_ms"] / saves, 3),
        "pipeline_stall_ms": round(c["ckpt_pipeline_stall_ms"], 2),
    }


def _elastic_block(train_steps=24):
    """Elastic-rescale probe for the BENCH_* trajectory (ISSUE 14):
    in-place rescale downtime (lease death -> survivors' new WorldView
    installed, the epoch-bump + barrier cost), grow rebind latency, the
    steps/s cost of accumulation compensation (the same global batch run
    at world-2 share vs the doubled post-shrink factor), and straggler
    detection latency (slowdown start -> fleet-median detector trip).
    All in-process over the MemoryKv lease double — the real TCP wire +
    bitwise guarantees are gated by chaos_fleet_probe --scenario elastic."""
    import threading

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.elastic import (
        RescaleCoordinator,
        deterministic_tree_sum,
    )
    from paddle_tpu.distributed.fleet.obs import (
        MemoryKv,
        ObsPublisher,
        StragglerDetector,
    )
    from paddle_tpu.io import GlobalStepSampler

    out = {}
    kv = MemoryKv()
    mk = lambda n: RescaleCoordinator(
        kv=kv, job_id="bench", node_id=n, np_min=1, np_max=4,
        poll_interval=0.002, barrier_timeout_s=10.0, debounce=1)
    a, b = mk("A"), mk("B")
    a.register(), b.register()
    got = {}
    t = threading.Thread(target=lambda: got.update(v=a.form(expected=2)))
    t.start()
    b.form(expected=2)
    t.join()

    # shrink downtime: lease death -> survivor's installed WorldView
    t0 = time.perf_counter()
    kv.kv_del("elastic/bench/B")
    ev = None
    while ev is None:
        ev = a.poll()
    out["rescale_downtime_ms"] = round(
        (time.perf_counter() - t0) * 1000.0, 3)

    # grow rebind: join proposal -> survivor installs the grown world
    b2 = mk("B")
    t0 = time.perf_counter()
    t = threading.Thread(target=lambda: b2.join(timeout=10))
    t.start()
    ev = None
    while ev is None:
        ev = a.poll()
    t.join()
    out["grow_rebind_ms"] = round((time.perf_counter() - t0) * 1000.0, 3)

    # accumulation compensation: steps/s at the world-2 share (k=2
    # microbatches/step) vs the post-shrink doubled factor (k=4) — the
    # honest cost of holding global batch constant with half the fleet
    paddle.seed(0)
    net = paddle.nn.Linear(16, 8)
    params = list(net.parameters())
    opt = paddle.optimizer.Adam(learning_rate=1e-3, parameters=params)
    X = np.random.default_rng(0).standard_normal((256, 16)).astype(np.float32)
    sampler = GlobalStepSampler(256, 32, microbatch_size=8, seed=1,
                                rank=0, world=2)

    def run(world, steps):
        sampler.set_world(0, world)
        t0 = time.perf_counter()
        for s in range(steps):
            mbg = []
            for ids in sampler.microbatches(s):
                opt.clear_grad()
                loss = (net(paddle.to_tensor(X[ids])) ** 2).mean()
                loss.backward()
                mbg.append([np.asarray(p.grad.numpy()) for p in params])
            total = [deterministic_tree_sum([g[i] for g in mbg])
                     for i in range(len(params))]
            for p, g in zip(params, total):
                p.grad = paddle.to_tensor(
                    g / np.float32(sampler.num_microbatches))
            opt.step()
            opt.clear_grad()
        return steps / (time.perf_counter() - t0)

    run(2, 4)  # warm the jit caches
    out["steps_per_s_world2_share"] = round(run(2, train_steps), 2)
    out["steps_per_s_post_shrink"] = round(run(1, train_steps), 2)

    # straggler detection latency: slowdown start -> detector trip
    pf = ObsPublisher(kv=kv, job_id="bench", node_id="F")
    ps = ObsPublisher(kv=kv, job_id="bench", node_id="S")
    for i in range(6):
        pf.note_step(i, 10.0), ps.note_step(i, 10.0)
        pf.publish(), ps.publish()
    det = StragglerDetector(ps, pct=50.0, sustain=3, evict=False)
    t0 = time.perf_counter()
    checks = 0
    trip = None
    while trip is None and checks < 50:
        ps.note_step(6 + checks, 100.0)  # the sustained slowdown
        pf.note_step(6 + checks, 10.0)
        ps.publish(), pf.publish()
        trip = det.check()
        checks += 1
    out["straggler_detection_ms"] = round(
        (time.perf_counter() - t0) * 1000.0, 3)
    out["straggler_detection_checks"] = checks
    out["straggler_tripped"] = trip is not None
    try:
        from paddle_tpu.profiler import sentinel as _sent

        _sent.clear_external("straggler[S]")
    except Exception:
        pass
    return out


def _observability_block(steps=6, bsz=8):
    """Observability probe for the BENCH_* trajectory (ISSUE 9 + 13):
    tracing-on overhead of the flight recorder at its default ring size
    (gated <1% by tools/obs_probe.py; recorded here per round), events/step
    at the captured steady state, the per-emit cost split (on-mode vs the
    off-mode fast path), the diagnostics server's /metrics scrape latency
    (client p50/p99 + server-side exposition build p50), and the
    perf-regression sentinel's false-positive count over the benched
    steady window (must be 0 — a clean run never pages). Delegates to the
    one measurement definition in tools/obs_probe.py."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import paddle_tpu as paddle
    import paddle_tpu.profiler as prof
    import paddle_tpu.resilience as res
    from obs_probe import _batches as _obs_batches
    from obs_probe import _build, _one_step, measure_trace_overhead

    try:
        batches = _obs_batches(steps, bsz)
        out = measure_trace_overhead(batches)

        # -- /metrics scrape latency (ISSUE 13 ops plane; the one
        # measurement definition lives in obs_probe) ------------------------
        from obs_probe import measure_scrape_latency
        from paddle_tpu.profiler import diag

        addr = diag.start(port=0)
        try:
            out.update(measure_scrape_latency(addr, n=30))
        finally:
            diag.stop()

        # -- sentinel false positives over a clean steady window ------------
        paddle.set_flags({"FLAGS_eager_lazy_dispatch": True,
                          "FLAGS_eager_step_capture": True})
        net, opt, loss_fn = _build()
        for xy in batches * 3:  # settle into the captured steady state
            _one_step(net, opt, loss_fn, xy)
        from paddle_tpu.core import lazy as _lazy

        _lazy.drain_async()
        paddle.set_flags({"FLAGS_sentinel_pct": 20.0,
                          "FLAGS_sentinel_warmup_steps": 5,
                          "FLAGS_sentinel_sustain_steps": 3})
        prof.sentinel.reset()
        before = prof.dispatch_counters()["perf_regressions"]
        n_window = 40
        for i in range(n_window):
            _one_step(net, opt, loss_fn, batches[i % len(batches)])
        out["sentinel_false_positives"] = int(
            prof.dispatch_counters()["perf_regressions"] - before)
        out["sentinel_window_steps"] = n_window

        # -- attribution layer (ISSUE 15): telemetry overhead + top program
        # cost. Overhead is analytic — the marginal host record cost (the
        # one measurement definition, attribution.measure_record_cost_ms)
        # over the measured steady step — and the fleet-visible top-1
        # program by measured EMA rides along so the BENCH_* trajectory
        # shows WHERE the step time goes, not just how much there is.
        from paddle_tpu.profiler import attribution as _attr

        paddle.set_flags({"FLAGS_sentinel_pct": 0.0,
                          "FLAGS_telemetry": True})
        for i in range(10):
            _one_step(net, opt, loss_fn, batches[i % len(batches)])
        pnames = _attr.group_names(list(net.parameters()))
        rec_ms = _attr.measure_record_cost_ms(pnames)
        out["telemetry_record_cost_ms"] = round(rec_ms, 4)
        out["telemetry_overhead_pct"] = round(
            rec_ms / max(out["step_ms"], 1e-9) * 100.0, 4)
        paddle.set_flags({"FLAGS_telemetry": False})
        # top EXECUTABLE program by measured EMA (the step-lap keys are
        # host-inclusive and would always win — not the question here)
        top = [r for r in _attr.costs_summary(8) if r["category"] != "step"]
        out["program_cost_top1"] = top[0] if top else None
        return out
    finally:
        paddle.set_flags({"FLAGS_fault_inject": "",
                          "FLAGS_trace_ring_size": 4096,
                          "FLAGS_sentinel_pct": 0.0,
                          "FLAGS_telemetry": False,
                          "FLAGS_eager_lazy_dispatch": False,
                          "FLAGS_eager_step_capture": True,
                          "FLAGS_retry_backoff_ms": 5.0})
        prof.sentinel.reset()
        res.reset()


def _multichip_capture_child():
    """Child process for the multichip_capture block: 8 simulated CPU
    devices, dp2×mp2 mesh, one MLP trainer run twice — through the eager
    whole-step capture tier (ISSUE 18) and through ShardedTrainStep — and
    ONE JSON line on stdout with programs/step, steps/s for both, the
    donation verdict, bitwise parity, and the per-device peak-HBM estimate
    from the per-shard analyzer."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.profiler as prof
    from paddle_tpu.core import lazy
    from paddle_tpu.parallel import topology
    from paddle_tpu.parallel.sharding import ShardedTrainStep, shard_params

    mesh = topology.init_mesh(dp=2, mp=2)
    steps = int(os.environ.get("BENCH_MULTICHIP_CAPTURE_STEPS", 30))

    def make_trainer(seed=0):
        paddle.seed(seed)
        model = paddle.nn.Sequential(
            paddle.nn.Linear(64, 128), paddle.nn.ReLU(),
            paddle.nn.Linear(128, 16))
        model[0].weight.dist_spec = (None, "mp")
        opt = paddle.optimizer.Adam(
            learning_rate=1e-2, parameters=model.parameters())
        return model, opt, paddle.nn.CrossEntropyLoss()

    rng = np.random.default_rng(7)
    xb = rng.standard_normal((8, 64)).astype(np.float32)
    yb = rng.integers(0, 16, (8,))
    batch_sh = NamedSharding(mesh, P(("dp",)))

    # -- captured eager tier -------------------------------------------------
    model, opt, loss_fn = make_trainer()
    shard_params(model, mesh)
    x, y = paddle.to_tensor(xb), paddle.to_tensor(yb)
    x._value = jax.device_put(x._value, batch_sh)
    y._value = jax.device_put(y._value, batch_sh)
    paddle.set_flags({"FLAGS_eager_lazy_dispatch": True,
                      "FLAGS_eager_step_capture": True,
                      "FLAGS_eager_async_compile": False})

    def one_step():
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for _ in range(6):  # warmup: arm + build + first replays
        one_step()
    c0 = prof.dispatch_counters()
    t0 = time.time()
    for _ in range(steps):
        one_step()
    lazy.flush_if_pending("bench")
    cap_dt = time.time() - t0
    c1 = prof.dispatch_counters()
    programs_per_step = (c1["programs"] - c0["programs"]) / steps
    replays = c1["capture_sharded_replays"] - c0["capture_sharded_replays"]
    state = lazy.step_capture_state()
    paddle.set_flags({"FLAGS_eager_lazy_dispatch": False})
    cap_params = [np.asarray(p._value) for p in model.parameters()]

    # per-device peak HBM of the captured sharded program (per-shard
    # liveness plan over the capture registry's traced step)
    est_peak_mb = None
    try:
        from paddle_tpu.analysis.memory import plan_memory
        from paddle_tpu.analysis.sharding import captured_step_context

        est_peak_mb = round(
            plan_memory(captured_step_context()).peak_bytes / 2**20, 3)
    except Exception:
        pass

    # -- ShardedTrainStep reference ------------------------------------------
    model2, opt2, loss_fn2 = make_trainer()
    shard_params(model2, mesh)
    sts = ShardedTrainStep(model2, loss_fn2, opt2, mesh=mesh)
    x2, y2 = paddle.to_tensor(xb), paddle.to_tensor(yb)
    for _ in range(6):
        sts(x2, y2)
    t0 = time.time()
    for _ in range(steps):
        loss = sts(x2, y2)
    float(loss)
    sts_dt = time.time() - t0
    # parity at matched step count (both trainers ran 6 + steps updates)
    ref_params = [np.asarray(p._value) for p in model2.parameters()]
    bitwise = all(a.tobytes() == b.tobytes()
                  for a, b in zip(cap_params, ref_params))

    print(json.dumps({
        "mesh": "dp2mp2",
        "devices": len(jax.devices()),
        "programs_per_step_captured": round(programs_per_step, 3),
        "captured_replays_per_step": round(replays / steps, 3),
        "captured_steps_per_s": round(steps / cap_dt, 2),
        "sharded_train_step_steps_per_s": round(steps / sts_dt, 2),
        "tier": state.get("tier"),
        "donated": bool(state.get("donated")),
        "donation_fallbacks": c1["capture_donation_fallbacks"],
        "bitwise_equal_sharded_train_step": bitwise,
        "est_peak_hbm_per_device_mb": est_peak_mb,
    }), flush=True)


def _multichip_capture_block():
    """Spawn the dp2×mp2 capture-vs-ShardedTrainStep comparison in a
    subprocess: the simulated 8-device mesh needs XLA_FLAGS set before jax
    initializes, so it cannot run in the bench main process (which is
    already bound to the real backend). The child is CPU-ONLY
    (JAX_PLATFORMS=cpu below) and must stay so: the parent holds the chip,
    and a chip belongs to one process at a time. What it reports are
    counts and parity, never a device time."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_MULTICHIP_CAPTURE_CHILD="1")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env,
        capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(
            f"multichip_capture child rc={out.returncode}: "
            + (out.stderr or "")[-800:])
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    return json.loads(lines[-1])


def _require_backend():
    """Probe the backend before any model builds: enumerate the devices and
    run one op (a backend can enumerate yet fail at its first compile). A
    failure propagates — a bench that cannot reach its device exits
    non-zero, it does not print a "skipped" record."""
    import jax
    import jax.numpy as jnp

    jax.devices()
    (jnp.zeros(()) + 1.0).block_until_ready()


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models import (
        GPTConfig,
        GPTForPretraining,
        GPTPretrainingCriterion,
        gpt2_345m,
        gpt2_small,
    )

    steps = int(os.environ.get("BENCH_STEPS", 10))
    bsz = int(os.environ.get("BENCH_BATCH", 8))
    seq = int(os.environ.get("BENCH_SEQ", 1024))
    which = os.environ.get("BENCH_MODEL", "345m")

    if which == "tiny":
        cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4,
                        num_heads=8, max_seq_len=seq)
    elif which == "small":
        cfg = gpt2_small(max_seq_len=seq)
    else:
        cfg = gpt2_345m(max_seq_len=seq)
    cfg.dropout = 0.0
    cfg.attn_dropout = 0.0
    cfg.use_recompute = os.environ.get("BENCH_RECOMPUTE", "0") == "1"

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    # bf16 weights: MXU-native matmul precision (AMP O2)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    criterion = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(), weight_decay=0.01
    )

    def loss_fn(logits, labels):
        return criterion(logits.astype("float32"), labels)

    step = paddle.jit.compile_train_step(model, loss_fn, opt)

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (bsz, seq + 1)), jnp.int32)
    ids = jax.device_put(ids)  # device-resident: exclude host upload
    x = paddle.Tensor(ids[:, :-1], stop_gradient=True)
    y = paddle.Tensor(ids[:, 1:], stop_gradient=True)

    t0 = time.time()
    loss = step(x, y)
    loss._value.block_until_ready()  # block_until_ready is the sync
    compile_s = time.time() - t0
    first_loss = float(loss)

    # warmup one more (cache hit path)
    step(x, y)._value.block_until_ready()

    synced = []

    def device_sync(t):
        synced.append(t._value.block_until_ready())

    dt = _timed(lambda: step(x, y), steps, sync=device_sync)
    last_loss = float(synced[-1])

    tokens_per_step = bsz * seq
    tps = tokens_per_step * steps / dt
    result = {
        "metric": f"gpt2_{which}_pretrain_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
    }
    # a failing trajectory block must name itself IN the JSON record —
    # silently omitting the key made a broken block indistinguishable from
    # a BENCH_*=0 skip when reading BENCH_*.json files later
    def _block_failed(name, e):
        tail = _tb_tail(e)
        result.setdefault("failed_blocks", {})[name] = tail
        print(f"# {name} block FAILED: {tail}", file=sys.stderr)

    # estimated peak HBM of the donated whole-step program (static liveness
    # plan, analysis.memory) — the memory-trajectory entry for BENCH_* files
    try:
        plan = step.memory_plan()
        result["est_peak_hbm_mb"] = round(plan.peak_bytes / 2**20, 1)
        result["est_donation_credit_mb"] = round(
            plan.donation_credit_bytes / 2**20, 1
        )
    except Exception as e:
        _block_failed("memory_plan", e)
    # planner-chosen remat at a 60%-of-unplanned budget: record the
    # planned-vs-unplanned est_peak_hbm_mb pair so BENCH_*.json trajectories
    # show what the planner buys — BENCH_MEMORY_PLAN=0 skips it
    if os.environ.get("BENCH_MEMORY_PLAN", "1") == "1":
        try:
            unplanned_mb = result["est_peak_hbm_mb"]
            rplan = step.plan_remat(budget_mb=0.6 * unplanned_mb)
            result["memory_plan"] = {
                "budget_mb": round(0.6 * unplanned_mb, 1),
                "est_peak_hbm_unplanned_mb": unplanned_mb,
                "est_peak_hbm_planned_mb": round(
                    rplan.peak_after_bytes / 2**20, 1),
                "recompute_pct": round(rplan.recompute_pct, 1),
                "cut_points": list(rplan.cut_points),
                "feasible": rplan.feasible,
            }
        except Exception as e:
            _block_failed("memory_plan_remat", e)
    # resilience trajectory block (retries / fallbacks / recovery overhead /
    # sentinel-is-free proof) — BENCH_RESILIENCE=0 skips it
    if os.environ.get("BENCH_RESILIENCE", "1") == "1":
        try:
            result["resilience"] = _resilience_block()
        except Exception as e:
            _block_failed("resilience", e)
    # checkpoint-overhead trajectory block (auto cadence vs off, overhead %
    # vs budget, snapshot/commit split) — BENCH_CHECKPOINT=0 skips it
    if os.environ.get("BENCH_CHECKPOINT", "1") == "1":
        try:
            result["checkpoint"] = _checkpoint_block()
        except Exception as e:
            _block_failed("checkpoint", e)
    # observability trajectory block (flight-recorder overhead %, events/
    # step, per-emit cost, telemetry overhead, top program cost) —
    # BENCH_OBSERVABILITY=0 skips it
    if os.environ.get("BENCH_OBSERVABILITY", "1") == "1":
        try:
            result["observability"] = _observability_block()
        except Exception as e:
            _block_failed("observability", e)
    # elastic-rescale trajectory block (rescale downtime, steps/s before/
    # after shrink, straggler detection latency) — BENCH_ELASTIC=0 skips it
    if os.environ.get("BENCH_ELASTIC", "1") == "1":
        try:
            result["elastic"] = _elastic_block()
        except Exception as e:
            _block_failed("elastic", e)
    # sharded whole-step capture trajectory block (ISSUE 18): programs/step
    # on the simulated dp2×mp2 mesh, captured vs ShardedTrainStep steps/s,
    # donation state, est per-device peak HBM — joins the MULTICHIP rows;
    # BENCH_MULTICHIP_CAPTURE=0 skips it
    if os.environ.get("BENCH_MULTICHIP_CAPTURE", "1") == "1":
        try:
            result["multichip_capture"] = _multichip_capture_block()
        except Exception as e:
            _block_failed("multichip_capture", e)
    # fleet front-door trajectory block (ISSUE 20): requests/s/fleet at
    # 2x, TTFT p99, reroutes, autoscale proposals, router-overhead <1%
    # gate — BENCH_SERVING_FLEET=0 skips it
    if os.environ.get("BENCH_SERVING_FLEET", "1") == "1":
        try:
            result["serving_fleet"] = _serving_fleet_block()
        except Exception as e:
            _block_failed("serving_fleet", e)
    # primary result first: a hard failure in the extra configs must not
    # lose the main measurement (one-JSON-line stdout contract)
    print(json.dumps(result), flush=True)
    failed_extra = []
    if os.environ.get("BENCH_EXTRA", "1") == "1":
        for name, fn in (
            ("resnet50", bench_resnet50),
            ("bert", bench_bert),
            ("gpt_longseq", bench_gpt_longseq),
            ("serving", bench_serving),
            ("serving_overload", bench_serving_overload),
            ("mnist", bench_mnist_eager),
            ("ernie_ctr", bench_ernie_ctr),
            ("ps_table", bench_ps_table),
            ("ps_wire", bench_ps_wire),
            ("dataloader", bench_dataloader),
        ):
            try:
                extra = fn()
                print(f"# config {name}: {json.dumps(extra)}", file=sys.stderr)
            except Exception as e:
                failed_extra.append(name)
                print(f"# config {name} FAILED: {_tb_tail(e)}",
                      file=sys.stderr)

    print(
        f"# {which}: {steps} steps x {tokens_per_step} tok in {dt:.2f}s "
        f"({dt/steps*1000:.0f} ms/step); first loss {first_loss:.3f} -> "
        f"{last_loss:.3f}; compile {compile_s:.0f}s; "
        f"devices={jax.devices()}",
        file=sys.stderr,
    )
    # every block and config still runs and reports, but a run in which any
    # of them failed is a failed run
    failed = sorted(result.get("failed_blocks", {})) + failed_extra
    if failed:
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    if os.environ.get("BENCH_MULTICHIP_CAPTURE_CHILD") == "1":
        _multichip_capture_child()
        sys.exit(0)
    _require_backend()
    main()
