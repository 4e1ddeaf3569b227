"""paddle.serving — continuous batching, paged KV cache, decode-mode capture.

ISSUE 7 acceptance:
  - bitwise parity of paged-cache decode vs the existing fixed-shape cache
    path (op level AND engine level at the matched execution tier);
  - bucket-signature capture reuse: zero recompiles in steady state, ONE
    captured program per decode step (dispatch_counters);
  - admission refusal at a tight FLAGS_memory_budget_mb instead of OOM;
  - a fault-injection serve (execute:p=0.2) that completes every request
    bitwise-identically to the fault-free run;
  - CacheOverflow is a request-level reject the scheduler converts into an
    error/rejected response, not a run-killer.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.profiler as prof
import paddle_tpu.resilience as res
from paddle_tpu import serving
from paddle_tpu.models import CacheOverflow, GPTConfig, GPTForPretraining

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB = 64


def tiny_model(seed=7, max_seq_len=32):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=max_seq_len, dropout=0.0,
                    attn_dropout=0.0)
    m = GPTForPretraining(cfg)
    m.eval()
    return m


def make_engine(model, **kw):
    kw.setdefault("block_size", 8)
    kw.setdefault("prompt_buckets", [8, 16])
    kw.setdefault("num_blocks", 24)
    return serving.Engine(model, serving.ServingConfig(**kw))


def fixed_reference(model, prompt, n):
    """The existing fixed-shape cache path (models/gpt.py dict caches),
    harvesting the greedy tokens AND the per-step logits rows."""
    caches = [{"k": None, "v": None, "len": 0} for _ in model.gpt.layers]
    plen = len(prompt)
    logits = model(
        paddle.to_tensor(np.asarray(prompt, np.int64)[None, :]),
        caches=caches, pos_offset=0,
    )
    rows = [logits.numpy()[0, -1, :]]
    toks = [int(rows[-1].argmax())]
    for i in range(1, n):
        lg = model(
            paddle.to_tensor(np.asarray([[toks[-1]]], np.int64)),
            caches=caches, pos_offset=plen + i - 1,
        )
        rows.append(lg.numpy()[0, 0, :])
        toks.append(int(rows[-1].argmax()))
    return toks, rows


@pytest.fixture(autouse=True)
def _serving_isolation():
    from paddle_tpu.core.lazy import reset_serve_programs

    res.reset()
    prof.reset_dispatch_counters()
    yield
    paddle.set_flags({"FLAGS_fault_inject": "", "FLAGS_retry_backoff_ms": 5.0,
                      "FLAGS_serving_capture": True,
                      "FLAGS_serving_capture_donate": True})
    res.reset()
    reset_serve_programs()


# ---------------------------------------------------------------------------
# op-level parity: paged_decode_attention vs cached_attention, same inputs
# ---------------------------------------------------------------------------
def test_paged_op_bitwise_parity_decode_and_prefill():
    import jax.numpy as jnp

    from paddle_tpu.ops.nn_ops import cached_attention, paged_decode_attention

    rng = np.random.default_rng(0)
    b, H, D, bs, nblk = 2, 2, 8, 8, 4
    L = nblk * bs
    # a fixed cache holding `cur` tokens per row, and the equivalent pool
    cur = 13
    k_hist = rng.standard_normal((b, cur, H, D)).astype(np.float32)
    v_hist = rng.standard_normal((b, cur, H, D)).astype(np.float32)
    k_cache = np.zeros((b, L, H, D), np.float32)
    v_cache = np.zeros((b, L, H, D), np.float32)
    k_cache[:, :cur], v_cache[:, :cur] = k_hist, v_hist
    # pool: row i owns blocks [2+i*nblk, ...); scratch ids 0..1 unused
    tables = np.asarray(
        [[2 + i * nblk + j for j in range(nblk)] for i in range(b)], np.int32)
    n_total = 2 + b * nblk
    k_pool = np.zeros((n_total, bs, H, D), np.float32)
    v_pool = np.zeros((n_total, bs, H, D), np.float32)
    for i in range(b):
        k_pool[tables[i]] = k_cache[i].reshape(nblk, bs, H, D)
        v_pool[tables[i]] = v_cache[i].reshape(nblk, bs, H, D)
    q = rng.standard_normal((b, 1, H, D)).astype(np.float32)
    k_new = rng.standard_normal((b, 1, H, D)).astype(np.float32)
    v_new = rng.standard_normal((b, 1, H, D)).astype(np.float32)

    ref_out, ref_k, ref_v = cached_attention(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(k_new), jnp.asarray(v_new), jnp.int32(cur), scale=0.25)
    out, nk, nv = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(np.full((b,), cur, np.int32)),
        jnp.asarray(k_new), jnp.asarray(v_new), scale=0.25, block_size=bs)
    assert np.array_equal(np.asarray(ref_out), np.asarray(out))
    # the written pool rows equal the fixed cache rows, bit for bit
    for i in range(b):
        gathered = np.asarray(nk)[tables[i]].reshape(L, H, D)
        assert np.array_equal(gathered, np.asarray(ref_k)[i])
        gathered_v = np.asarray(nv)[tables[i]].reshape(L, H, D)
        assert np.array_equal(gathered_v, np.asarray(ref_v)[i])

    # prefill (chunk from position 0, vectorized block writes)
    s = 16
    qc = rng.standard_normal((b, s, H, D)).astype(np.float32)
    kc = rng.standard_normal((b, s, H, D)).astype(np.float32)
    vc = rng.standard_normal((b, s, H, D)).astype(np.float32)
    zero_cache = np.zeros((b, L, H, D), np.float32)
    ref_out, ref_k, _ = cached_attention(
        jnp.asarray(qc), jnp.asarray(zero_cache), jnp.asarray(zero_cache),
        jnp.asarray(kc), jnp.asarray(vc), jnp.int32(0), scale=0.25)
    out, nk, _ = paged_decode_attention(
        jnp.asarray(qc), jnp.asarray(np.zeros_like(k_pool)),
        jnp.asarray(np.zeros_like(v_pool)), jnp.asarray(tables),
        jnp.asarray(np.zeros((b,), np.int32)), jnp.asarray(kc),
        jnp.asarray(vc), scale=0.25, block_size=bs, prefill=True)
    assert np.array_equal(np.asarray(ref_out), np.asarray(out))
    for i in range(b):
        gathered = np.asarray(nk)[tables[i]].reshape(L, H, D)
        assert np.array_equal(gathered, np.asarray(ref_k)[i])


def test_paged_op_rejects_unaligned_prefill():
    import jax.numpy as jnp

    from paddle_tpu.ops.nn_ops import paged_decode_attention

    with pytest.raises(ValueError, match="multiple of"):
        paged_decode_attention(
            jnp.zeros((1, 5, 2, 4)), jnp.zeros((3, 8, 2, 4)),
            jnp.zeros((3, 8, 2, 4)), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1, 5, 2, 4)),
            jnp.zeros((1, 5, 2, 4)), scale=0.5, block_size=8, prefill=True)


# ---------------------------------------------------------------------------
# engine-level parity vs the fixed-shape cache path
# ---------------------------------------------------------------------------
def test_engine_tokens_match_generate():
    model = tiny_model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, p) for p in (8, 8, 16, 5)]
    eng = make_engine(model)
    resps = eng.serve(prompts, max_new_tokens=8)
    for p, r in zip(prompts, resps):
        assert r.ok
        ref = model.generate(
            paddle.to_tensor(np.asarray(p, np.int64)[None, :]),
            max_new_tokens=8,
        ).numpy()[0, len(p):]
        assert r.tokens == list(ref)


def test_engine_bitwise_parity_per_op_tier():
    # at the matched execution tier (per-op) the paged engine's logits are
    # bit-for-bit the fixed-shape cache path's — paging changes WHERE K/V
    # live, never a single bit of the math
    model = tiny_model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, p) for p in (8, 16, 5)]
    paddle.set_flags({"FLAGS_serving_capture": False})
    try:
        eng = make_engine(model, keep_logits=True)
        resps = eng.serve(prompts, max_new_tokens=6)
    finally:
        paddle.set_flags({"FLAGS_serving_capture": True})
    for p, r in zip(prompts, resps):
        toks, rows = fixed_reference(model, list(p), 6)
        assert r.tokens == toks
        assert all(np.array_equal(a, b) for a, b in zip(rows, r.logits))


def test_engine_captured_deterministic_and_tier_equal():
    # the captured tier replays deterministically, and the donated rung is
    # bitwise-equal to the non-donated middle rung (what a mid-run ladder
    # demotion switches between)
    model = tiny_model()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, VOCAB, 8) for _ in range(3)]
    eng = make_engine(model, keep_logits=True)
    a = eng.serve(prompts, max_new_tokens=6)
    b = eng.serve(prompts, max_new_tokens=6)
    for ra, rb in zip(a, b):
        assert ra.tokens == rb.tokens
        assert all(np.array_equal(x, y) for x, y in zip(ra.logits, rb.logits))
    paddle.set_flags({"FLAGS_serving_capture_donate": False})
    try:
        eng2 = make_engine(model, keep_logits=True)
        c = eng2.serve(prompts, max_new_tokens=6)
    finally:
        paddle.set_flags({"FLAGS_serving_capture_donate": True})
    for ra, rc in zip(a, c):
        assert ra.tokens == rc.tokens
        assert all(np.array_equal(x, y) for x, y in zip(ra.logits, rc.logits))


# ---------------------------------------------------------------------------
# capture reuse: zero recompiles, 1 program per decode step
# ---------------------------------------------------------------------------
def test_steady_state_one_program_per_decode_step():
    model = tiny_model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, 8) for _ in range(4)]
    eng = make_engine(model, prompt_buckets=[8])
    eng.serve(prompts, max_new_tokens=8)  # warm: builds the programs
    prof.reset_dispatch_counters()
    eng.serve(prompts, max_new_tokens=8)  # steady state
    c = prof.dispatch_counters()
    assert c["serve_capture_builds"] == 0, "steady state recompiled"
    assert c["serve_capture_fallbacks"] == 0
    # every decode step is exactly one captured replay; prefills add one each
    assert c["serve_capture_replays"] == (
        c["serve_decode_steps"] + c["serve_prefills"])
    assert c["serve_decode_steps"] > 0
    # and nothing leaked onto the per-op or segment paths
    assert c["op_programs"] == 0
    assert c["segment_programs"] == 0


def test_capture_cache_eviction_counted():
    from paddle_tpu.core import lazy as _lazy

    paddle.set_flags({"FLAGS_serving_capture_cache_size": 2})
    try:
        for i in range(4):
            _lazy.serve_program(("test-evict", i), lambda x: x)
        c = prof.dispatch_counters()
        assert c["serve_capture_evictions"] >= 2
    finally:
        paddle.set_flags({"FLAGS_serving_capture_cache_size": 16})


# ---------------------------------------------------------------------------
# admission: planner budget, refusal, backpressure, CacheOverflow
# ---------------------------------------------------------------------------
def test_admission_refusal_at_tight_budget():
    model = tiny_model()
    # pool capacity 3 blocks: a request needing 4 must be REFUSED up front
    eng = make_engine(model, num_blocks=3)
    rng = np.random.default_rng(0)
    rid = eng.submit(rng.integers(1, VOCAB, 16), max_new_tokens=16)
    r = eng.response(rid)
    assert r is not None and r.status == "rejected"
    assert "overflow" in r.error.lower() or "blocks" in r.error
    assert prof.dispatch_counters()["serve_admission_refusals"] == 1
    # a fitting request still serves fine afterwards
    rid2 = eng.submit(rng.integers(1, VOCAB, 8), max_new_tokens=4)
    eng.run_until_idle()
    assert eng.response(rid2).ok


def test_planner_budgeted_pool_sizing():
    model = tiny_model()
    eng = make_engine(model, num_blocks=0, memory_budget_mb=3.0)
    plan = eng._pool_plan
    assert plan is not None and plan.num_blocks is not None
    assert eng._pool.num_blocks == plan.num_blocks
    # the arithmetic: budget = overhead + pool
    assert plan.overhead_bytes + plan.num_blocks * plan.block_bytes <= (
        plan.budget_bytes)
    assert plan.est_peak_hbm_mb > 0
    # a budget smaller than the program overhead cannot build an engine
    tiny = plan.overhead_bytes / 2**20 * 0.5
    with pytest.raises(ValueError, match="budget"):
        make_engine(model, num_blocks=0, memory_budget_mb=tiny)


def test_planner_budget_caps_request_geometry():
    # the budget guarantee only covers decode signatures up to the traced
    # worst case: a request whose context bucket is WIDER must be refused
    # even when enough pool blocks happen to be free
    model = tiny_model(max_seq_len=128)
    eng = make_engine(model, num_blocks=0, memory_budget_mb=8.0,
                      max_new_tokens=8)
    assert eng._plan_ctx_blocks is not None
    assert eng._pool.num_blocks > eng._plan_ctx_blocks  # blocks DO fit
    rng = np.random.default_rng(0)
    # ctx bucket(8 + 40) = 48 tokens = 6 blocks > planned 4
    rid = eng.submit(rng.integers(1, VOCAB, 8), max_new_tokens=40)
    r = eng.response(rid)
    assert r is not None and r.status == "rejected"
    assert "admissible context" in r.error
    # within the planned geometry still serves
    rid2 = eng.submit(rng.integers(1, VOCAB, 8), max_new_tokens=8)
    eng.run_until_idle()
    assert eng.response(rid2).ok
    # an UNbudgeted engine does not cap geometry beyond the pool itself
    eng2 = make_engine(model, num_blocks=32)
    assert eng2._plan_ctx_blocks is None
    rid3 = eng2.submit(rng.integers(1, VOCAB, 8), max_new_tokens=40)
    eng2.run_until_idle()
    assert eng2.response(rid3).ok


def test_real_fault_mid_step_recovers_every_group():
    # a REAL (non-injected) fault escaping the donated rung rebuilds the
    # pool and requeues ALL in-flight sequences — including those in OTHER
    # context groups whose decode was still pending this tick
    from paddle_tpu.serving.engine import _PoolsConsumed

    model = tiny_model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, 8), rng.integers(1, VOCAB, 16)]
    eng = make_engine(model)
    ids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    orig = eng._run_tiered
    state = {"armed": True}

    def boom(kind, key, fn, args):
        if kind == "decode" and state["armed"]:
            state["armed"] = False
            raise _PoolsConsumed(RuntimeError("device died mid-replay"))
        return orig(kind, key, fn, args)

    eng._run_tiered = boom
    eng.run_until_idle()
    c = prof.dispatch_counters()
    assert c["serve_request_requeues"] == 2  # both groups torn down
    assert c["serve_requests_dropped"] == 0
    for p, i in zip(prompts, ids):
        r = eng.response(i)
        assert r.ok
        ref = model.generate(
            paddle.to_tensor(np.asarray(p, np.int64)[None, :]),
            max_new_tokens=4,
        ).numpy()[0, len(p):]
        assert r.tokens == list(ref)  # deterministic re-run, same tokens
    assert eng._pool.free_blocks == eng._pool.num_blocks


def test_engine_close_releases_captured_programs():
    from paddle_tpu.core.lazy import serve_capture_state

    model = tiny_model()
    rng = np.random.default_rng(0)
    eng = make_engine(model)
    eng.serve([rng.integers(1, VOCAB, 8)], max_new_tokens=4)
    eng2 = make_engine(model)
    eng2.serve([rng.integers(1, VOCAB, 8)], max_new_tokens=4)
    before = serve_capture_state()["cached_programs"]
    eng.close()
    after = serve_capture_state()["cached_programs"]
    assert after < before
    # the surviving engine still replays without rebuilding
    prof.reset_dispatch_counters()
    eng2.serve([rng.integers(1, VOCAB, 8)], max_new_tokens=4)
    assert prof.dispatch_counters()["serve_capture_builds"] == 0


def test_backpressure_queues_and_completes():
    model = tiny_model()
    eng = make_engine(model, prompt_buckets=[8], num_blocks=4)
    rng = np.random.default_rng(0)
    resps = eng.serve(
        [rng.integers(1, VOCAB, 8) for _ in range(6)], max_new_tokens=8)
    assert all(r.ok for r in resps)
    c = prof.dispatch_counters()
    assert c["serve_requests_completed"] == 6
    assert c["serve_requests_dropped"] == 0
    assert eng._pool.free_blocks == eng._pool.num_blocks  # all recycled


def test_cache_overflow_is_request_level():
    # fixed-shape path: the overflow is a structured CacheOverflow (a
    # ValueError subclass for backcompat) ...
    model = tiny_model(max_seq_len=8)
    caches = [{"k": None, "v": None, "len": 0} for _ in model.gpt.layers]
    ids = paddle.to_tensor(np.arange(8, dtype=np.int64)[None, :])
    model(ids, caches=caches, pos_offset=0)
    with pytest.raises(CacheOverflow) as ei:
        model(paddle.to_tensor(np.asarray([[1]], np.int64)),
              caches=caches, pos_offset=8)
    assert isinstance(ei.value, ValueError)
    assert ei.value.need == 9 and ei.value.capacity == 8
    # ... and the serving scheduler converts it into a per-request error
    # response instead of killing the run
    model2 = tiny_model()
    eng = make_engine(model2, num_blocks=2)
    rng = np.random.default_rng(0)
    bad = eng.submit(rng.integers(1, VOCAB, 16), max_new_tokens=8)  # 3 blocks
    ok = eng.submit(rng.integers(1, VOCAB, 8), max_new_tokens=4)    # 2 blocks
    eng.run_until_idle()
    rb, ro = eng.response(bad), eng.response(ok)
    assert rb.status == "rejected" and "overflow" in rb.error.lower()
    assert ro.ok


# ---------------------------------------------------------------------------
# resilience: fault injection, ladder demotion, preemption drain
# ---------------------------------------------------------------------------
def _serve_mix(model, spec, prompts, **kw):
    res.reset()
    prof.reset_dispatch_counters()
    paddle.set_flags({"FLAGS_fault_inject": spec,
                      "FLAGS_retry_backoff_ms": 0.5})
    try:
        eng = make_engine(model, keep_logits=True, **kw)
        resps = eng.serve(prompts, max_new_tokens=8)
        return resps, prof.dispatch_counters()
    finally:
        paddle.set_flags({"FLAGS_fault_inject": "",
                          "FLAGS_retry_backoff_ms": 5.0})
        res.reset()


def test_fault_injection_serve_completes_every_request():
    model = tiny_model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, p) for p in (8, 8, 16, 5)]
    clean, _ = _serve_mix(model, "", prompts)
    faulted, c = _serve_mix(model, "execute:p=0.2", prompts)
    assert all(r.ok for r in faulted)
    assert c["serve_requests_dropped"] == 0
    for a, b in zip(clean, faulted):
        assert a.tokens == b.tokens
        assert all(np.array_equal(x, y) for x, y in zip(a.logits, b.logits))


def test_decode_storm_demotes_ladder_and_recovers():
    # every decode replay faults until retries exhaust: the ladder demotes
    # the bucket's captured program and the batch finishes on the lower
    # rungs — zero drops, same tokens. (Token-level, not logits-bitwise:
    # a SUSTAINED per-step storm legitimately reaches the per-op floor,
    # where XLA's fused-program rounding can differ from the per-op
    # composition by 1 ULP; the single-demotion rung pair is proven
    # bitwise-identical in test_engine_captured_deterministic_and_tier_equal.)
    model = tiny_model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, p) for p in (8, 16)]
    clean, _ = _serve_mix(model, "", prompts)
    stormed, c = _serve_mix(model, "execute:p=1:x=3:decode", prompts)
    assert all(r.ok for r in stormed)
    assert c["serve_capture_fallbacks"] > 0
    assert c["ladder_demotions"] >= 1
    assert c["serve_requests_dropped"] == 0
    for a, b in zip(clean, stormed):
        assert a.tokens == b.tokens


def test_prefill_faults_recovered():
    model = tiny_model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, 8) for _ in range(3)]
    clean, _ = _serve_mix(model, "", prompts)
    faulted, c = _serve_mix(model, "execute:p=1:x=1:prefill", prompts)
    assert all(r.ok for r in faulted)
    assert c["retry_attempts"] > 0
    for a, b in zip(clean, faulted):
        assert a.tokens == b.tokens


def test_drain_completes_submitted_rejects_new():
    model = tiny_model()
    eng = make_engine(model, prompt_buckets=[8])
    rng = np.random.default_rng(0)
    ids = [eng.submit(rng.integers(1, VOCAB, 8), max_new_tokens=6)
           for _ in range(3)]
    eng.step()  # some sequences in flight
    eng.begin_drain()
    late = eng.submit(rng.integers(1, VOCAB, 8))
    eng.run_until_idle()
    assert all(eng.response(i).ok for i in ids)
    assert eng.response(late).status == "rejected"
    c = prof.dispatch_counters()
    assert c["serve_preempt_drains"] == 1
    assert c["serve_requests_dropped"] == 0


def test_request_requeue_on_floor_failure():
    # a non-targeted storm big enough to exhaust every rung INCLUDING the
    # per-op floor errors the request after the retry budget — an error
    # RESPONSE, never a drop or a hung engine
    model = tiny_model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, 8)]
    paddle.set_flags({"FLAGS_serving_request_retries": 1})
    try:
        resps, c = _serve_mix(model, "execute:p=1:x=9", prompts)
    finally:
        paddle.set_flags({"FLAGS_serving_request_retries": 2})
    (r,) = resps
    assert r.status == "error" and r.error
    assert c["serve_request_requeues"] >= 1
    assert c["serve_requests_dropped"] == 0


# ---------------------------------------------------------------------------
# satellites: embeddings offset tensor, predictor routing, stats, flags
# ---------------------------------------------------------------------------
def test_embeddings_accept_per_row_offset_tensor():
    model = tiny_model()
    ids = paddle.to_tensor(np.asarray([[3], [4]], np.int64))
    off = paddle.to_tensor(np.asarray([5, 9], np.int64))
    h = model.gpt.embeddings(ids, pos_offset=off)
    h0 = model.gpt.embeddings(ids[0:1], pos_offset=5)
    h1 = model.gpt.embeddings(ids[1:2], pos_offset=9)
    assert np.array_equal(h.numpy()[0], h0.numpy()[0])
    assert np.array_equal(h.numpy()[1], h1.numpy()[0])


def test_generative_predictor_routes_to_serving():
    from paddle_tpu import inference

    model = tiny_model()
    config = inference.Config()
    config.enable_generative_serving(
        model, block_size=8, prompt_buckets=[8], num_blocks=16,
        max_new_tokens=5,
    )
    pred = inference.create_predictor(config)
    assert isinstance(pred, inference.GenerativePredictor)
    rng = np.random.default_rng(0)
    ids = rng.integers(1, VOCAB, (2, 8))
    (out,) = pred.run([ids])
    assert out.shape == (2, 5)
    for i in range(2):
        ref = model.generate(
            paddle.to_tensor(ids[i:i + 1].astype(np.int64)),
            max_new_tokens=5,
        ).numpy()[0, 8:]
        assert list(out[i]) == list(ref)
    assert pred.engine.stats()["completed"] == 2


def test_config_bucket_lists_validated():
    model = tiny_model()
    with pytest.raises(ValueError, match="ascending"):
        make_engine(model, prompt_buckets=[128, 32])
    with pytest.raises(ValueError, match="ascending"):
        make_engine(model, decode_batch_buckets=[8, 2])


def test_generative_predictor_lens_not_stale():
    from paddle_tpu import inference

    model = tiny_model()
    config = inference.Config()
    config.enable_generative_serving(
        model, block_size=8, prompt_buckets=[8], num_blocks=32,
        max_new_tokens=3,
    )
    pred = inference.create_predictor(config)
    rng = np.random.default_rng(0)
    ids2 = rng.integers(1, VOCAB, (2, 8))
    pred.run([ids2, np.asarray([5, 6])])
    # a later list-style call WITHOUT lens must not inherit the stale
    # 2-element prompt_lens handle (here the batch is 3)
    ids3 = rng.integers(1, VOCAB, (3, 8))
    (out,) = pred.run([ids3])
    assert out.shape == (3, 3)
    # and an explicitly mismatched lens fails loud
    pred.get_input_handle("prompt_lens").copy_from_cpu(np.asarray([4]))
    pred.get_input_handle("input_ids").copy_from_cpu(ids2)
    with pytest.raises(ValueError, match="batch"):
        pred.run()


def test_serve_evicts_responses_and_counts_outcomes():
    model = tiny_model()
    eng = make_engine(model)
    rng = np.random.default_rng(0)
    rs = eng.serve([rng.integers(1, VOCAB, 8) for _ in range(2)],
                   max_new_tokens=3)
    assert all(r.ok for r in rs)
    # serve() evicted them — the response map must not grow with traffic —
    # while the lifetime outcome counts survive in stats()
    assert all(eng.response(r.request_id) is None for r in rs)
    assert eng.stats()["completed"] == 2


def test_tensorrt_mkldnn_knobs_deprecation_warn():
    from paddle_tpu import inference

    config = inference.Config()
    with pytest.warns(DeprecationWarning):
        config.enable_tensorrt_engine()
    with pytest.warns(DeprecationWarning):
        config.enable_mkldnn()


def test_engine_stats_and_flags_surface():
    model = tiny_model()
    eng = make_engine(model)
    rng = np.random.default_rng(0)
    eng.serve([rng.integers(1, VOCAB, 8)], max_new_tokens=4)
    st = eng.stats()
    assert st["completed"] == 1
    assert st["token_lat_p50_ms"] is not None
    assert st["token_lat_p99_ms"] >= st["token_lat_p50_ms"]
    assert 0.0 <= st["pool_peak_occupancy"] <= 1.0
    assert st["capture"]["cached_programs"] >= 2
    docs = paddle.core.flags.describe_flags("serving")
    names = {d["name"] for d in docs}
    assert {"FLAGS_serving_block_size", "FLAGS_serving_num_blocks",
            "FLAGS_serving_prompt_buckets", "FLAGS_serving_capture",
            "FLAGS_serving_capture_donate",
            "FLAGS_serving_capture_cache_size"} <= names
    assert all(d["doc"] for d in docs)


def test_fault_spec_accepts_serving_sites():
    plan = res.parse_fault_spec("execute:p=0.5:decode,compile:prefill")
    assert plan[0].target == "decode" and plan[1].target == "prefill"
    with pytest.raises(ValueError):
        res.parse_fault_spec("execute:p=0.5:decoder")


# ---------------------------------------------------------------------------
# serve probe CLI (subprocess — slow): chaos gate incl. mid-run SIGTERM
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_serve_probe_cli():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_probe.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALL SCENARIOS PASSED" in out.stdout


# ---------------------------------------------------------------------------
# weights are ARGUMENTS of every serving program (PR 21): a closed-over array
# is baked into the HLO, i.e. one copy of the model per bucket signature in
# device memory
# ---------------------------------------------------------------------------
def test_serving_programs_take_weights_as_arguments():
    import jax

    model = tiny_model()
    eng = make_engine(model)
    try:
        prompt = np.arange(1, 7)
        before = eng.serve([prompt], max_new_tokens=4)[0].tokens
        args = (tuple(eng._pool.k), tuple(eng._pool.v), eng._weight_vals(),
                np.zeros((1, 2), np.int32), np.zeros((1,), np.int32),
                np.zeros((1,), np.int32))
        closed = jax.make_jaxpr(eng._decode_fn)(*args)
        weight_bytes = sum(v.nbytes for v in eng._weight_vals())
        const_bytes = sum(getattr(c, "nbytes", 0) for c in closed.consts)
        assert const_bytes < weight_bytes / 10, (const_bytes, weight_bytes)
        # ... so a compiled program serves the weights the model has NOW
        builds = prof.dispatch_counters()["serve_capture_builds"]
        for p, q in zip(model.parameters(), tiny_model(seed=11).parameters()):
            p.set_value(q.numpy())
        after = eng.serve([prompt], max_new_tokens=4)[0].tokens
        assert prof.dispatch_counters()["serve_capture_builds"] == builds
        fresh = make_engine(model)
        try:
            assert after == fresh.serve([prompt], max_new_tokens=4)[0].tokens
        finally:
            fresh.close()
        assert after != before
    finally:
        eng.close()


def test_two_engines_trace_one_model_from_two_threads():
    """Tracing rebinds the shared model's weights to tracers; the rebinding
    is serialized, so two engines over one model may build their programs
    at the same time. More threads than signatures, a short switch interval,
    every join bounded."""
    import threading

    model = tiny_model()
    prompts = [np.arange(1, 1 + n) for n in (3, 6, 9, 12, 14)]
    base_eng = make_engine(model)
    try:
        want = [r.tokens for r in base_eng.serve(prompts, max_new_tokens=5)]
    finally:
        base_eng.close()
    from paddle_tpu.core.lazy import reset_serve_programs

    reset_serve_programs()  # every thread below builds its programs afresh
    got, errors = {}, []

    def worker(i):
        eng = make_engine(model)
        try:
            got[i] = [r.tokens for r in eng.serve(prompts, max_new_tokens=5)]
        except BaseException as e:  # reported by the assert below
            errors.append(e)
        finally:
            eng.close()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert all(got[i] == want for i in range(4)), got
