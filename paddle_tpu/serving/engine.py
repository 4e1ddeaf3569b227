"""The serving engine: continuous batching over a paged, planner-budgeted
KV cache, with prefill and decode captured as single donated XLA programs.

One ``Engine`` owns one model and runs a simple synchronous loop:

    admit (queue → blocks → prefill)  →  decode every active group once
    →  recycle completed sequences' blocks  →  repeat

Every program launch goes through three execution tiers, the serving
instance of the resilience ladder (captured → lazy → per-op):

  captured   ``jit(step_fn, donate_argnums=pools)`` — ONE donated program
             per bucket signature (decode-mode capture, ``core/lazy.py``),
             pool buffers updated in place;
  lazy       the same jitted program WITHOUT donation — the retry-safe
             middle rung (inputs retained, so a transient fault replays);
  per-op     the same Python function eagerly — the ladder floor, each op
             individually retried by the per-op resilience site.

All three tiers run the SAME function over the SAME buffers, so numerics
never change across rungs — a mid-decode fault demotes the bucket's
program and the batch retries without dropping a request. Injected faults
(FLAGS_fault_inject) raise before the program runs, so the fallback rungs
reuse the intact pool; a REAL fault on the donated rung conservatively
resets the pool and re-enqueues every in-flight sequence (greedy decode is
deterministic, so re-runs reproduce the same tokens).

Overload robustness (ISSUE 11) wraps that loop in three layers:

  deadlines   every request may carry ``deadline_ms``; expiry is enforced
              in queue (before wasting a prefill), at the admit pop, and
              mid-decode (partial 'timeout' response per
              FLAGS_serving_deadline_partial) — expired sequences recycle
              their blocks and leave the decode group without perturbing
              other rows;
  admission   the SLO-aware controller (serving/admission.py) predicts a
              request's completion from measured prefill/decode cost EMAs
              and sheds predicted deadline misses, over-cap submits
              (FLAGS_serving_queue_max), and — batch class first — storm
              arrivals past the queue-wait p99 trip wire, always with a
              structured retriable 'overloaded' response;
  health      the engine exposes warming/ready/degraded/draining/dead
              (``Engine.health``) so a Supervisor (serving/supervisor.py)
              and the inference PredictorPool can route traffic around an
              unhealthy replica, restart a wedged engine, or fail cleanly.
"""
from __future__ import annotations

import contextlib
import itertools
import signal as _signal
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence as Seq

import jax
import jax.numpy as jnp
import numpy as np

from ..core import flags
from ..core.dispatch import no_grad
from .admission import AdmissionController
from .cache import BlockPool, PagedCacheView, _BatchState, default_num_blocks
from .scheduler import (
    Request,
    RequestQueue,
    Response,
    Sequence,
    ServingBuckets,
    group_for_decode,
)

__all__ = ["Engine", "HEALTH_STATES", "ServingConfig"]

_ENGINE_IDS = itertools.count(1)

# the engine health lifecycle (Engine.health). 'degraded' still serves —
# it marks a replica the PredictorPool should deprioritize (fresh restart,
# pool rebuild) until _DEGRADED_COOLDOWN_TICKS clean ticks pass; 'dead'
# and 'draining' refuse new admissions.
HEALTH_STATES = ("warming", "ready", "degraded", "draining", "dead")
_DEGRADED_COOLDOWN_TICKS = 8


# -- module-level op helpers (cacheable tokens for the per-op jit cache) ----
def _decode_pick(logits):
    """Greedy next token from a decode chunk's last position."""
    row = logits[:, -1, :]
    return row, jnp.argmax(row, axis=-1).astype(jnp.int32)


def _prefill_pick(logits, plen):
    """Greedy next token from the TRUE last prompt position (the prompt is
    padded to its bucket; positions >= plen are pad lanes)."""
    idx = (plen.astype(jnp.int32) - 1)[:, None, None]
    row = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
    return row, jnp.argmax(row, axis=-1).astype(jnp.int32)


def _raw(t):
    """Tensor → raw (materialized) jax value; raw values pass through."""
    from ..core.lazy import materialize
    from ..core.tensor import Tensor

    return materialize(t._value if isinstance(t, Tensor) else t)


class _PoolsConsumed(RuntimeError):
    """A REAL (non-injected) fault escaped the donated rung: the pool
    buffers may have been consumed by XLA. Recovery resets the pool."""

    def __init__(self, cause: BaseException):
        super().__init__(str(cause))
        self.cause = cause


@dataclass
class ServingConfig:
    """Engine knobs. ``None``/0 fields fall back to their FLAGS_serving_*
    defaults (see ``paddle.describe_flags('serving')``)."""

    block_size: int = 0
    num_blocks: int = 0              # 0 = planner-budgeted (plan_block_pool)
    prompt_buckets: Optional[List[int]] = None
    decode_batch_buckets: Optional[List[int]] = None
    max_new_tokens: int = 0          # default per-request cap
    memory_budget_mb: Optional[float] = None  # None = FLAGS_memory_budget_mb
    keep_logits: bool = False        # responses carry per-token logits rows
    dtype: str = "float32"
    # model geometry — inferred from model.cfg when present
    layers: Optional[int] = None
    heads: Optional[int] = None
    head_dim: Optional[int] = None
    max_positions: Optional[int] = None


_WEIGHT_BIND_LOCK = threading.RLock()


@contextlib.contextmanager
def _bound_weights(weights, w_vals):
    """Run a model over ``w_vals`` (tracers while a program is being traced).
    Rebinding mutates the shared model, so it is serialized: two engines may
    serve one model from two threads. Replays of a compiled program never
    come here. Module-level on purpose: the step functions live in the
    serve-program cache and must not keep an Engine (and its pool) alive."""
    from ..jit import _bind_values

    with _WEIGHT_BIND_LOCK, _bind_values(weights, w_vals), no_grad():
        yield


class Engine:
    """Continuous-batching serving runtime over one generative model.

    ``model`` must accept ``model(ids, caches=views, pos_offset=tensor)``
    with a list of per-layer cache views and return ``[b, s, vocab]``
    logits — ``models.gpt.GPTForPretraining`` is the flagship shape.
    """

    def __init__(self, model, config: Optional[ServingConfig] = None):
        cfg = config or ServingConfig()
        self._uid = next(_ENGINE_IDS)
        self._model = model
        if hasattr(model, "eval"):
            model.eval()
        mcfg = getattr(model, "cfg", None)
        self._layers = cfg.layers or getattr(mcfg, "num_layers", None)
        heads = cfg.heads or getattr(mcfg, "num_heads", None)
        head_dim = cfg.head_dim
        if head_dim is None and mcfg is not None:
            head_dim = mcfg.hidden_size // mcfg.num_heads
        if not (self._layers and heads and head_dim):
            raise ValueError(
                "cannot infer model geometry; pass ServingConfig(layers=, "
                "heads=, head_dim=)"
            )
        self._max_positions = (
            cfg.max_positions or getattr(mcfg, "max_seq_len", None) or 1 << 30
        )
        self._block_size = int(cfg.block_size) or int(
            flags.flag("serving_block_size"))
        self._default_max_new = int(cfg.max_new_tokens) or int(
            flags.flag("serving_max_new_tokens"))
        self._keep_logits = bool(cfg.keep_logits)
        self._buckets = ServingBuckets(
            block_size=self._block_size,
            prompt_buckets=cfg.prompt_buckets,
            decode_batch_buckets=cfg.decode_batch_buckets,
        )
        scratch = self._buckets.max_decode_batch

        # the model's weights enter every serving program as an ARGUMENT
        # (position 2, after the donated pools): a closed-over array is
        # baked into the HLO as a constant, so each prefill bucket and
        # decode signature would carry its own copy of the model in device
        # memory
        self._weights = list(model.parameters()) + [
            b for _, b in model.named_buffers()]
        self._decode_fn = self._make_decode_fn()
        self._prefill_fn = self._make_prefill_fn()

        # -- block-pool sizing: explicit > planner budget > default --------
        self._pool_plan = None
        # planner-budgeted engines also bound per-request context by the
        # geometry the planner actually traced (set in _plan_pool): the
        # budget guarantee only covers signatures no larger than the traced
        # worst case, so bigger requests are refused at admission
        self._plan_ctx_blocks: Optional[int] = None
        num_blocks = int(cfg.num_blocks) or int(flags.flag("serving_num_blocks"))
        block_bytes = (
            2 * self._layers * self._block_size * int(heads) * int(head_dim)
            * np.dtype(cfg.dtype).itemsize
        )
        if num_blocks <= 0:
            self._pool_plan = self._plan_pool(
                heads=int(heads), head_dim=int(head_dim), dtype=cfg.dtype,
                scratch=scratch, block_bytes=block_bytes,
                budget_mb=cfg.memory_budget_mb,
            )
            if self._pool_plan.num_blocks is None:
                num_blocks = default_num_blocks()
                self._plan_ctx_blocks = None  # no budget — nothing to cap
            else:
                num_blocks = int(self._pool_plan.num_blocks)
                if num_blocks < 1:
                    raise ValueError(
                        "memory budget leaves no room for a KV block pool: "
                        f"decode-program overhead is ~"
                        f"{self._pool_plan.overhead_bytes / 2**20:.1f} MB of "
                        f"a {self._pool_plan.budget_bytes / 2**20:.1f} MB "
                        "budget (FLAGS_memory_budget_mb)"
                    )
        self._pool = BlockPool(
            layers=self._layers, heads=int(heads), head_dim=int(head_dim),
            block_size=self._block_size, num_blocks=num_blocks,
            scratch_slots=scratch, dtype=cfg.dtype,
        )

        self._queue = RequestQueue()
        self._active: List[Sequence] = []
        self._responses: Dict[int, Response] = {}
        # ids accepted into the queue but not yet answered — the drop
        # tripwire run_until_idle audits (every accepted request must end
        # with exactly one Response; anything else is a counted drop)
        self._accepted: set = set()
        self._draining = False
        # the drain BARRIER: the ids the preemption-drain contract covers
        # (snapshot at begin_drain). A concurrent Supervisor restart may
        # requeue in-flight work only from inside the barrier; anything
        # else lands as a terminal response, never re-admitted past it
        self._drain_barrier: Optional[set] = None
        self._prev_handlers: Dict[int, Any] = {}
        # set by a serving.frontdoor.ReplicaServer hosting this engine —
        # published in the obs lease so a cross-host FrontDoor can route
        # requests here
        self.serve_addr: Optional[str] = None
        # streaming log-bucketed histogram (paddle.profiler.metrics): O(1)
        # observe, fixed memory, LIFETIME coverage — replaces the old
        # 4096-entry recent-window reservoir whose stats() paid an
        # np.percentile over a copy on every call. Registered in the
        # default registry (labeled by engine uid) so Prometheus exposition
        # and postmortems see per-engine latency; close() unregisters.
        from ..profiler import metrics as _metrics

        self._token_lat = _metrics.default_registry().histogram(
            "serve_token_lat_ms",
            doc="per-token serving latency (first token incl. prefill, "
                "then one sample per decoded token), ms",
            labels={"engine": str(self._uid)},
        )
        self._decode_rows = 0
        # lifetime per-engine outcome counts (responses themselves are
        # evicted by serve()/pop_response, so stats can't scan them)
        self._n_completed = 0
        self._n_rejected = 0
        self._n_errors = 0
        self._n_shed = 0
        self._n_expired = 0
        # SLO-aware admission: measured prefill/decode cost EMAs + the
        # queue-wait trip wire (serving/admission.py)
        self._admission = AdmissionController(
            self._uid, bucket_of=self._buckets.prompt_bucket)
        # health lifecycle: warming until the first successful tick;
        # degraded after a restart/pool rebuild until a cooldown of clean
        # ticks; draining/dead refuse new admissions
        self._health = "warming"
        self._tick_no = 0
        self._degraded_until: Optional[int] = None
        self._restarts = 0
        self._last_restart_error: Optional[str] = None
        # ops plane (ISSUE 13): the diagnostics server aggregates every
        # live engine's health into /healthz + /readyz (weakly referenced;
        # close() unregisters eagerly)
        from ..profiler import diag as _diag

        _diag.register_engine(self)

    # ------------------------------------------------------------------
    # step functions (shared by all three execution tiers)
    # ------------------------------------------------------------------
    def _weight_vals(self):
        with _WEIGHT_BIND_LOCK:  # never read while another trace has them bound
            return tuple(t._value for t in self._weights)

    def _make_decode_fn(self) -> Callable:
        model, layers, bs = self._model, self._layers, self._block_size
        weights = self._weights

        def decode_fn(k_pools, v_pools, w_vals, tables, lens, tokens):
            from ..core.dispatch import apply as _apply
            from ..core.tensor import Tensor

            st = _BatchState(k_pools, v_pools, tables, lens, prefill=False)
            views = [PagedCacheView(st, i, bs) for i in range(layers)]
            ids = Tensor(tokens.astype(jnp.int64)[:, None], stop_gradient=True)
            pos = Tensor(lens, stop_gradient=True)
            with _bound_weights(weights, w_vals):
                logits = model(ids, caches=views, pos_offset=pos)
            row, nxt = _apply(_decode_pick, logits, op_name="serve_decode_pick")
            return (
                tuple(_raw(t) for t in st.k_pools),
                tuple(_raw(t) for t in st.v_pools),
                _raw(row), _raw(nxt),
            )

        return decode_fn

    def _make_prefill_fn(self) -> Callable:
        model, layers, bs = self._model, self._layers, self._block_size
        weights = self._weights

        def prefill_fn(k_pools, v_pools, w_vals, tables, ids, plen):
            from ..core.dispatch import apply as _apply
            from ..core.tensor import Tensor

            lens = jnp.zeros((ids.shape[0],), jnp.int32)
            st = _BatchState(k_pools, v_pools, tables, lens, prefill=True)
            views = [PagedCacheView(st, i, bs) for i in range(layers)]
            with _bound_weights(weights, w_vals):
                logits = model(Tensor(ids, stop_gradient=True),
                               caches=views, pos_offset=0)
            row, nxt = _apply(_prefill_pick, logits, plen,
                              op_name="serve_prefill_pick")
            return (
                tuple(_raw(t) for t in st.k_pools),
                tuple(_raw(t) for t in st.v_pools),
                _raw(row), _raw(nxt),
            )

        return prefill_fn

    # ------------------------------------------------------------------
    # planner-budgeted pool sizing
    # ------------------------------------------------------------------
    def _plan_pool(self, *, heads, head_dim, dtype, scratch, block_bytes,
                   budget_mb):
        """Trace the WORST-CASE decode signature once (largest batch bucket
        × largest context bucket) and hand the liveness planner the job of
        splitting the budget between program overhead and the pool."""
        from ..analysis import memory as _mem

        B = self._buckets.max_decode_batch
        nblk = self._buckets.ctx_blocks(
            self._buckets.prompt_spec.boundaries[-1], self._default_max_new)
        self._plan_ctx_blocks = nblk
        n_total = scratch + B * nblk
        pshape = (n_total, self._block_size, heads, head_dim)
        pool_spec = jax.ShapeDtypeStruct(pshape, np.dtype(dtype))
        k_specs = tuple(pool_spec for _ in range(self._layers))
        w_specs = tuple(
            jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
            for v in self._weight_vals())
        t_spec = jax.ShapeDtypeStruct((B, nblk), np.int32)
        l_spec = jax.ShapeDtypeStruct((B,), np.int32)
        roles = (
            [("buffer", f"k_pool{i}") for i in range(self._layers)]
            + [("buffer", f"v_pool{i}") for i in range(self._layers)]
            + [("param", getattr(t, "name", "") or f"weight{i}")
               for i, t in enumerate(self._weights)]
            + [("feed", "block_tables"), ("feed", "seq_lens"),
               ("feed", "tokens")]
        )
        donated = tuple(range(2 * self._layers))
        pool_bytes_in_trace = (
            2 * self._layers * int(np.prod(pshape)) * np.dtype(dtype).itemsize
        )
        return _mem.plan_block_pool(
            lambda: jax.make_jaxpr(self._decode_fn)(
                k_specs, k_specs, w_specs, t_spec, l_spec, l_spec),
            block_bytes=block_bytes,
            pool_bytes_in_trace=pool_bytes_in_trace,
            budget_mb=budget_mb,
            roles=roles, donated=donated,
        )

    # ------------------------------------------------------------------
    # health lifecycle
    # ------------------------------------------------------------------
    @property
    def health(self) -> str:
        """One of :data:`HEALTH_STATES` — what a Supervisor / the
        inference PredictorPool route on."""
        return self._health

    def serviceable(self) -> bool:
        """May this engine accept NEW work right now?"""
        return self._health not in ("draining", "dead")

    def _set_health(self, state: str, why: str):
        from ..core import dispatch

        if state == self._health:
            return
        if state not in HEALTH_STATES:
            raise ValueError(f"unknown health state {state!r}")
        prev, self._health = self._health, state
        dispatch._counters["serve_health_transitions"] += 1
        dispatch._emit("serve", site="engine", phase="health",
                       engine=self._uid, prev=prev, state=state,
                       why=why[:120])

    @staticmethod
    def _now() -> float:
        """Deadline clock (wall seconds). A method so tests and the probe
        can drive expiry with a virtual clock instead of sleeps."""
        return time.time()

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               priority: str = "interactive") -> int:
        """Queue one request; returns its request id. Requests that can
        NEVER be served (context exceeds the budgeted pool or the model's
        positions) are rejected immediately with a Response — admission
        refusal, not an OOM. ``deadline_ms`` (default
        FLAGS_serving_default_deadline_ms; 0/None = none) and ``priority``
        ('interactive' > 'batch') feed the SLO-aware admission controller:
        a submit the engine predicts it cannot serve in time — or one
        arriving past FLAGS_serving_queue_max / the queue-wait p99 trip
        wire — is shed with a structured retriable 'overloaded' response
        instead of queueing toward a timeout."""
        from ..core import dispatch

        if deadline_ms is None:
            default_dl = float(flags.flag("serving_default_deadline_ms"))
            deadline_ms = default_dl if default_dl > 0 else None
        req = Request(
            prompt=np.asarray(prompt),
            max_new_tokens=max_new_tokens or self._default_max_new,
            eos_token_id=eos_token_id,
            deadline_ms=deadline_ms,
            priority=priority,
        )
        if self._health == "dead":
            self._reject(req, "engine is dead (supervisor restarts "
                              "exhausted)")
            return req.request_id
        if self._draining:
            self._reject(req, "engine is draining (preemption)")
            return req.request_id
        plen = int(req.prompt.size)
        ctx = (self._buckets.prompt_bucket(plen) + req.max_new_tokens)
        if ctx > self._max_positions:
            self._reject(
                req,
                f"context {ctx} exceeds the model's max positions "
                f"{self._max_positions}",
            )
            return req.request_id
        n_blk = self._buckets.ctx_blocks(plen, req.max_new_tokens)
        cap = self._pool.num_blocks
        if self._plan_ctx_blocks is not None:
            # the memory budget was proven only for decode signatures up to
            # the planner's traced worst case — a wider context would gather
            # a bigger block view than the overhead estimate covers, exactly
            # the OOM the budget exists to prevent
            cap = min(cap, self._plan_ctx_blocks)
        if n_blk > cap:
            dispatch._counters["serve_admission_refusals"] += 1
            self._reject(
                req,
                f"KV cache overflow: request needs {n_blk} blocks > "
                f"admissible context {cap} "
                "(planner-budgeted by FLAGS_memory_budget_mb)",
            )
            return req.request_id
        shed = self._admission.decide(
            req, queue=self._queue, active=self._active, now=self._now())
        if shed is not None:
            self._shed(req, shed)
            return req.request_id
        self._queue.push(req)
        self._accepted.add(req.request_id)
        dispatch._emit("serve", site="engine", phase="admit",
                       rid=req.request_id, prompt_len=plen, blocks=n_blk,
                       priority=req.priority)
        return req.request_id

    def response(self, request_id: int) -> Optional[Response]:
        return self._responses.get(request_id)

    def pop_response(self, request_id: int) -> Optional[Response]:
        """``response()`` + evict — long-running callers retrieve results
        with this so the response map doesn't grow with total traffic.
        The id leaves the drop-audit set too: a retrieved response IS the
        answered contract, so a mid-run pop (the ReplicaServer poll
        pattern) must not read as a drop at the next idle edge."""
        r = self._responses.pop(request_id, None)
        if r is not None:
            self._accepted.discard(request_id)
        return r

    def step(self):
        """One scheduler tick: expire what already missed its deadline,
        admit + prefill what fits, then one decode step for every active
        group."""
        from ..resilience import runtime as _rt

        self._tick_no += 1
        self._expire_deadlines(stage="queued")
        self._admit()
        groups = group_for_decode(self._active)
        for n_blk in sorted(groups):
            seqs = groups[n_blk]
            cap = self._buckets.max_decode_batch
            for i in range(0, len(seqs), cap):
                # pool recovery (_recover_pools) tears down EVERY active
                # sequence mid-tick: drop stale snapshot entries and, if a
                # batch reports the pool was rebuilt, abort this tick —
                # the requeued sequences re-prefill on the next one
                chunk = [s for s in seqs[i:i + cap] if s in self._active]
                if chunk and not self._decode_batch(chunk, n_blk):
                    self._end_tick(_rt)
                    return
        self._end_tick(_rt)

    def _end_tick(self, _rt):
        # per-ENGINE source/key: a process-global 'serve' would interleave
        # every engine's tick cadence into one baseline (and one liveness
        # signal) — closing one engine would halve the other's measured
        # rate into a false perf_regression, and one engine draining would
        # erase a still-wedged sibling's stall signal
        _rt.on_step_end(source=f"serve[{self._uid}]")
        if self._health == "warming":
            self._set_health("ready", "first tick completed")
        elif (self._health == "degraded"
              and self._degraded_until is not None
              and self._tick_no >= self._degraded_until):
            self._degraded_until = None
            self._set_health("ready", "degraded cooldown elapsed")

    def _expire_deadlines(self, stage: str):
        """Answer every queued/active request whose deadline has passed.
        Queued expiry runs BEFORE admission so a dead-on-arrival request
        never wastes a prefill; active expiry removes the sequence from
        its decode group (the group is recomputed each tick, so the other
        rows are untouched) and recycles its blocks."""
        now = self._now()
        for req in self._queue.take_expired(now):
            self._expire(req, stage=stage)
        for seq in [s for s in self._active if s.req.expired(now)]:
            self._release(seq)
            self._expire(seq.req, stage="decode", seq=seq)

    def run_until_idle(self):
        """Drive the loop until every accepted request has a response."""
        from ..profiler import trace as _trace

        while self._queue or self._active:
            self.step()
        self._audit_drops()
        # an IDLE request-driven engine looks exactly like a stalled one
        # to the heartbeat-age liveness read (/healthz) and the stall
        # watchdog: stand THIS ENGINE's heartbeat down (the Supervisor /
        # train_step_range discipline) — the next tick re-arms it; the
        # training loop and any sibling engine are separate sources and
        # stay armed
        _trace.watchdog_disarm(f"serve[{self._uid}]")

    def _audit_drops(self):
        """The zero-drop tripwire: at idle, every accepted request must
        have produced exactly one Response, and — the pool-leak half —
        every KV block must be back on the free-list. Anything missing is
        counted (serve_requests_dropped / serve_block_leaks; the chaos
        gates fail on either), answered with an error response so no
        caller ever hangs on a lost id, and leaked blocks are reclaimed so
        the pool doesn't starve admission forever."""
        from ..core import dispatch

        missing = self._accepted - set(self._responses)
        for rid in missing:
            dispatch._counters["serve_requests_dropped"] += 1
            self._responses[rid] = Response(
                request_id=rid, status="error",
                error="request lost by the engine (dropped) — engine bug",
                done_time=time.time(),
            )
        self._accepted.clear()
        if not self._active and self._pool.used_blocks:
            leaked = self._pool.reclaim_all()
            dispatch._counters["serve_block_leaks"] += leaked
            dispatch._emit("serve", site="engine", phase="block_leak",
                           engine=self._uid, blocks=leaked)

    def serve(self, requests: Seq, **submit_kw) -> List[Response]:
        """Convenience: submit every prompt, run to completion, return (and
        evict) the responses in submit order."""
        ids = [self.submit(p, **submit_kw) for p in requests]
        self.run_until_idle()
        return [self.pop_response(i) for i in ids]

    # -- supervision -----------------------------------------------------
    def restart(self, err: BaseException):
        """Tear the runtime down to a known-good state after a wedge or a
        tick exception escaped the resilience ladder: evict this engine's
        captured programs (a wedged executable must not be replayed),
        requeue every in-flight sequence through the existing requeue path
        (greedy decode ⇒ the re-run reproduces bitwise-identical tokens),
        and rebuild the pool storage. The engine comes back 'degraded'
        until a cooldown of clean ticks. The Supervisor owns the restart
        BUDGET (FLAGS_serving_max_engine_restarts) and calls
        :meth:`fail_clean` past it."""
        from ..core import dispatch
        from ..core.lazy import reset_serve_programs

        self._restarts += 1
        self._last_restart_error = f"{type(err).__name__}: {err}"
        dispatch._counters["serve_engine_restarts"] += 1
        dispatch._emit("serve", site="engine", phase="restart",
                       engine=self._uid, restarts=self._restarts,
                       error=type(err).__name__)
        reset_serve_programs(owner=self._uid)
        for seq in list(self._active):
            if (self._draining and self._drain_barrier is not None
                    and seq.req.request_id not in self._drain_barrier):
                # restart racing an installed preemption drain: work that
                # landed AFTER the barrier snapshot (a submit or a router
                # dispatch racing the signal handler) must not be
                # re-admitted past the drain barrier — it answers a
                # terminal retriable response instead (the FrontDoor
                # re-dispatches it to a peer), never re-enters a draining
                # engine's queue where nothing may drive it again
                from ..core import dispatch as _dispatch

                self._release(seq)
                self._n_shed += 1
                _dispatch._counters["serve_requests_shed"] += 1
                self._responses[seq.req.request_id] = Response(
                    request_id=seq.req.request_id, status="overloaded",
                    error=("engine restarted while draining: request was "
                           "outside the drain barrier — retry on a peer"),
                    retriable=True,
                    prompt_len=int(seq.req.prompt.size),
                    submit_time=seq.req.submit_time, done_time=time.time(),
                    retry_after_ms=self._admission.retry_after_ms(),
                )
                _dispatch._emit("serve", site="engine",
                                phase="drain_barrier_refusal",
                                rid=seq.req.request_id, engine=self._uid)
                continue
            self._requeue_seq(seq, err, count_retry=False)
        self._pool.reset_storage()
        self._mark_degraded(f"engine restart: {type(err).__name__}")

    def fail_clean(self, err: BaseException):
        """The restart budget is exhausted: answer EVERY queued and
        in-flight request with a terminal error response (zero hangs, zero
        silent drops), release their blocks, and go 'dead' — submits from
        here on are rejected."""
        from ..profiler import trace as _trace

        why = (f"engine dead after {self._restarts} restarts "
               f"(FLAGS_serving_max_engine_restarts): {err}")
        for seq in list(self._active):
            self._release(seq)
            self._error(seq.req, why, seq)
        while True:
            req = self._queue.pop()
            if req is None:
                break
            self._error(req, why)
        self._set_health("dead", why)
        _trace.dump_postmortem("engine_dead", exc=err,
                               engine=self._uid, restarts=self._restarts)

    @property
    def pending(self) -> int:
        """Accepted-but-unanswered work (queued + in flight)."""
        return len(self._queue) + len(self._active)

    # -- preemption ------------------------------------------------------
    def begin_drain(self):
        """Stop admitting NEW requests; everything already submitted still
        completes (the SIGTERM drain contract — zero dropped requests)."""
        from ..core import dispatch

        if not self._draining:
            self._draining = True
            # snapshot the drain BARRIER: exactly the accepted-but-
            # unanswered ids the drain contract covers. A Supervisor
            # restart during the drain requeues in-flight work only from
            # inside this set; anything racing in past it (signal-handler
            # timing) terminal-errors instead of re-admitting
            self._drain_barrier = set(self._accepted) - set(self._responses)
            dispatch._counters["serve_preempt_drains"] += 1
            if self._health != "dead":
                self._set_health("draining", "preemption drain")

    def install_preemption_handler(self, signals=(_signal.SIGTERM,)):
        for s in signals:
            if s in self._prev_handlers:
                continue  # already installed — keep the ORIGINAL previous
            self._prev_handlers[s] = _signal.signal(
                s, lambda signum, frame: self.begin_drain())

    def uninstall_preemption_handler(self):
        for s, h in self._prev_handlers.items():
            _signal.signal(s, h)
        self._prev_handlers.clear()

    def drain(self) -> List[Response]:
        """begin_drain + run to idle; returns every retained response."""
        self.begin_drain()
        self.run_until_idle()
        return list(self._responses.values())

    def close(self):
        """Release this engine's captured programs from the decode-mode
        capture cache (their closures hold the model), unregister its
        latency histogram, and restore any signal handlers. Safe to call
        twice."""
        from ..core.lazy import reset_serve_programs
        from ..profiler import diag as _diag
        from ..profiler import metrics as _metrics
        from ..profiler import sentinel as _sentinel

        self.uninstall_preemption_handler()
        _diag.unregister_engine(self)
        reset_serve_programs(owner=self._uid)
        _metrics.default_registry().remove(
            "serve_token_lat_ms", labels={"engine": str(self._uid)})
        # retire this engine's sentinel baselines: a closed engine's keys
        # get no further observations, so a tripped one could never clear
        # and would degrade /healthz for a replica that no longer exists
        _sentinel.retire(f"serve[{self._uid}]")
        _sentinel.retire(f"serve_decode[{self._uid}:")
        _sentinel.retire(f"serve_queue_wait[{self._uid}]")
        # ... and its attribution cost-registry entries (program keys and
        # the step-lap key): registry state must not grow with replica
        # churn, and a dead engine's programs must drop out of /programz
        try:
            from ..profiler import attribution as _attribution

            _attribution.retire(f"serve:prefill:{self._uid}:")
            _attribution.retire(f"serve:decode:{self._uid}:")
            _attribution.retire(f"serve[{self._uid}]")
        except Exception:
            pass
        # ... and its heartbeat source: a closed-without-drain engine must
        # not leave a stale armed source pinning /healthz at 'stalled'
        try:
            from ..profiler import trace as _trace

            _trace.watchdog_disarm(f"serve[{self._uid}]")
        except Exception:
            pass
        self._admission.close()
        self._health = "dead"  # no transition event from __del__ paths

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass  # interpreter shutdown — caches are going away anyway

    # -- introspection ---------------------------------------------------
    def reset_stats(self):
        """Drop the latency histogram (e.g. after a warm-up window, so
        steady-state percentiles don't average in compile time). Counters
        in dispatch_counters() reset separately; pool peak occupancy is
        lifetime."""
        self._token_lat.reset()
        self._decode_rows = 0

    def stats(self) -> Dict[str, Any]:
        """Percentiles come from the streaming histogram: O(buckets), no
        reservoir copy, lifetime coverage (bounded relative error from the
        log bucketing — see profiler.metrics.Histogram)."""
        from ..core.lazy import serve_capture_state

        p50 = self._token_lat.quantile(0.5)
        p99 = self._token_lat.quantile(0.99)
        out = {
            "health": self._health,
            "completed": self._n_completed,
            "rejected": self._n_rejected,
            "shed": self._n_shed,
            "expired": self._n_expired,
            "errors": self._n_errors,
            "restarts": self._restarts,
            "admission": self._admission.state(),
            "pending": self.pending,
            "pool_blocks": self._pool.num_blocks,
            "pool_occupancy": round(self._pool.occupancy(), 4),
            "pool_peak_occupancy": round(self._pool.peak_occupancy, 4),
            "token_lat_p50_ms": None if p50 is None else round(p50, 3),
            "token_lat_p99_ms": None if p99 is None else round(p99, 3),
            "token_lat_count": self._token_lat.count,
            "capture": serve_capture_state(),
        }
        if self._pool_plan is not None:
            out["est_decode_peak_hbm_mb"] = round(
                self._pool_plan.est_peak_hbm_mb, 2)
            out["pool_overhead_mb"] = round(
                self._pool_plan.overhead_bytes / 2**20, 2)
        return out

    def routing_signals(self) -> Dict[str, Any]:
        """The cost/queue signals the fleet FrontDoor routes on — also
        what the obs lease publishes per engine (the ``serving`` section),
        so a cross-host router predicts completion from this replica's own
        measured costs instead of round-robining blind.
        ``prefill_ema_ms`` is the bucket-average scalar (the per-bucket
        table rides in ``admission``)."""
        adm = self._admission.state()
        pre = adm.get("prefill_ema_ms") or {}
        return {
            "engine": self._uid,
            "health": self._health,
            "queue_depth": len(self._queue),
            "inflight": len(self._active),
            "prefill_ema_ms": (round(sum(pre.values()) / len(pre), 3)
                               if pre else None),
            "tok_ema_ms": adm.get("decode_tok_ema_ms"),
            "admission": adm,
            "serve_addr": self.serve_addr,
        }

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _release(self, seq: Sequence):
        """The one teardown path every sequence exit goes through: out of
        the active set, blocks back on the free-list, exactly once — the
        leak audit in run_until_idle stays at zero because nothing frees
        by hand anymore."""
        if seq in self._active:
            self._active.remove(seq)
        if seq.blocks:
            self._pool.free(seq.blocks)
            seq.blocks = []

    def _reject(self, req: Request, why: str):
        from ..core import dispatch

        dispatch._counters["serve_requests_rejected"] += 1
        self._n_rejected += 1
        self._responses[req.request_id] = Response(
            request_id=req.request_id, status="rejected", error=why,
            prompt_len=int(req.prompt.size), submit_time=req.submit_time,
        )
        dispatch._emit("serve", site="engine", phase="reject",
                       rid=req.request_id, why=why[:120])

    def _shed(self, req: Request, decision):
        """Load shedding: a structured, retriable 'overloaded' response —
        the admission controller predicted this request cannot be served
        in time (or the queue is at cap / the trip wire is open), so the
        honest answer is 'retry elsewhere/later', not a queue slot that
        ends in a timeout."""
        from ..core import dispatch

        dispatch._counters["serve_requests_shed"] += 1
        reasons = dispatch._counters["serve_shed_reasons"]
        reasons[decision.reason] = reasons.get(decision.reason, 0) + 1
        self._n_shed += 1
        self._responses[req.request_id] = Response(
            request_id=req.request_id, status="overloaded",
            error=f"overloaded ({decision.reason}): {decision.detail}",
            retriable=True,
            prompt_len=int(req.prompt.size), submit_time=req.submit_time,
            done_time=time.time(),
            retry_after_ms=self._admission.retry_after_ms(),
        )
        dispatch._emit("serve", site="engine", phase="shed",
                       rid=req.request_id, reason=decision.reason,
                       priority=req.priority)

    def _expire(self, req: Request, stage: str,
                seq: Optional[Sequence] = None):
        """Deadline expiry: a terminal 'timeout' response. Mid-decode
        expiry keeps the partial output when FLAGS_serving_deadline_partial
        is on (greedy decode makes partials meaningful); the caller has
        already released the sequence's blocks."""
        from ..core import dispatch

        dispatch._counters["serve_deadline_expired"] += 1
        stages = dispatch._counters["serve_expire_stages"]
        stages[stage] = stages.get(stage, 0) + 1
        self._n_expired += 1
        partial = bool(flags.flag("serving_deadline_partial"))
        tokens = list(seq.tokens) if (seq is not None and partial) else []
        n_gen = 0 if seq is None else len(seq.tokens)
        self._responses[req.request_id] = Response(
            request_id=req.request_id, status="timeout",
            error=(f"deadline of {req.deadline_ms:.0f} ms exceeded at "
                   f"stage '{stage}' after {n_gen} tokens"),
            tokens=tokens,
            prompt_len=int(req.prompt.size), submit_time=req.submit_time,
            first_token_time=getattr(req, "_first_token_time", None),
            done_time=time.time(),
        )
        dispatch._emit("serve", site="engine", phase="expire",
                       rid=req.request_id, stage=stage, tokens=n_gen,
                       priority=req.priority)

    def _error(self, req: Request, why: str, seq: Optional[Sequence] = None):
        from ..core import dispatch

        self._n_errors += 1
        self._responses[req.request_id] = Response(
            request_id=req.request_id, status="error", error=why,
            tokens=list(seq.tokens) if seq is not None else [],
            prompt_len=int(req.prompt.size), submit_time=req.submit_time,
            done_time=time.time(),
        )
        dispatch._emit("serve", site="engine", phase="error",
                       rid=req.request_id, why=why[:120])

    def _complete(self, seq: Sequence):
        from ..core import dispatch

        self._release(seq)
        dispatch._counters["serve_requests_completed"] += 1
        dispatch._emit("serve", site="engine", phase="complete",
                       rid=seq.req.request_id, tokens=len(seq.tokens))
        self._n_completed += 1
        self._responses[seq.req.request_id] = Response(
            request_id=seq.req.request_id, status="ok",
            tokens=list(seq.tokens), prompt_len=int(seq.req.prompt.size),
            submit_time=seq.req.submit_time,
            first_token_time=getattr(seq.req, "_first_token_time", None),
            done_time=time.time(),
            logits=list(seq.logits) if self._keep_logits else None,
        )

    def _requeue_seq(self, seq: Sequence, err: BaseException,
                     count_retry: bool = True):
        """Tear one sequence down and re-run it from its prompt (greedy
        decode is deterministic — the re-run reproduces the same tokens).
        Past the retry budget, the request gets an error response.
        ``count_retry=False`` is the supervisor-restart path: the engine
        wedged, not the request, so innocent in-flight work must not burn
        its FLAGS_serving_request_retries budget — the restart budget
        (FLAGS_serving_max_engine_restarts → fail_clean) is the bound
        there."""
        from ..core import dispatch

        self._release(seq)
        req = seq.req
        if count_retry:
            req.retries += 1
            if req.retries > int(flags.flag("serving_request_retries")):
                self._error(
                    req,
                    f"failed after {req.retries - 1} retries: {err}", seq)
                return
        dispatch._counters["serve_request_requeues"] += 1
        dispatch._emit("serve", site="engine", phase="requeue",
                       rid=req.request_id, retries=req.retries,
                       error=type(err).__name__)
        self._queue.push_front(req)

    def _recover_pools(self, err: _PoolsConsumed):
        """A real fault escaped the donated rung: the pool buffers may be
        consumed. Rebuild the storage and restart every in-flight
        sequence."""
        self._pool.reset_storage()
        for seq in list(self._active):
            self._requeue_seq(seq, err.cause)
        self._mark_degraded(f"pool rebuilt after {type(err.cause).__name__}")

    def _mark_degraded(self, why: str):
        if self._health in ("draining", "dead"):
            return  # terminal-ish states outrank degraded
        self._degraded_until = self._tick_no + _DEGRADED_COOLDOWN_TICKS
        self._set_health("degraded", why)

    def _admit(self):
        from ..models.gpt import CacheOverflow

        while True:
            # pop-first, not peek-then-pop: a signal-handler submit landing
            # between the two could change which request pop() returns
            # (interactive jumps the batch head), so the engine always
            # operates on the request it actually popped and push_front
            # restores it on backpressure
            req = self._queue.pop()
            if req is None:
                return
            # last call before the expensive part: a request that expired
            # between the tick-start queue scan and this pop must not
            # burn a prefill (or the blocks behind it)
            if req.expired(self._now()):
                self._expire(req, stage="prefill")
                continue
            n_blk = self._buckets.ctx_blocks(
                int(req.prompt.size), req.max_new_tokens)
            try:
                blocks = self._pool.alloc(n_blk)
            except CacheOverflow as e:
                from ..core import dispatch

                dispatch._counters["serve_admission_refusals"] += 1
                self._reject(req, str(e))
                continue
            if blocks is None:
                # backpressure: wait for a completion to free blocks
                self._queue.push_front(req)
                return
            wait_ms = (self._now() - req.submit_time) * 1000.0
            self._admission.note_queue_wait(wait_ms)
            from ..profiler import sentinel as _sentinel

            _sentinel.observe(f"serve_queue_wait[{self._uid}]", wait_ms)
            seq = Sequence(req, blocks, n_blk)
            try:
                self._prefill(seq)
            except _PoolsConsumed as e:
                self._active.append(seq)  # so recovery requeues it too
                self._recover_pools(e)
                return
            except Exception as e:  # tiers exhausted — requeue just this one
                self._requeue_seq(seq, e)
                return

    def _prefill(self, seq: Sequence):
        from ..core import dispatch

        req = seq.req
        plen = int(req.prompt.size)
        padded = self._buckets.pad_prompt(req.prompt)
        P = int(padded.shape[-1])
        args = (
            tuple(self._pool.k), tuple(self._pool.v), self._weight_vals(),
            jnp.asarray(np.asarray([seq.table_row()], np.int32)),
            jnp.asarray(padded[None, :].astype(np.int64)),
            jnp.asarray(np.asarray([plen], np.int32)),
        )
        key = ("prefill", self._uid, P, seq.n_blk)
        t0 = time.perf_counter()
        k_pools, v_pools, row, nxt = self._run_tiered(
            "prefill", key, self._prefill_fn, args)
        self._pool.k, self._pool.v = list(k_pools), list(v_pools)
        tok = int(np.asarray(jax.device_get(nxt))[0])
        dispatch._counters["serve_prefills"] += 1
        prefill_ms = (time.perf_counter() - t0) * 1000.0
        self._token_lat.observe(prefill_ms)
        self._admission.note_prefill(P, prefill_ms)
        dispatch._emit("serve", site="engine", phase="prefill",
                       rid=req.request_id, bucket=P, blocks=seq.n_blk,
                       ms=round(prefill_ms, 3))
        seq.length = plen
        seq.tokens.append(tok)
        seq.last_token = tok
        req._first_token_time = time.time()
        if self._keep_logits:
            seq.logits.append(np.asarray(jax.device_get(row))[0])
        self._active.append(seq)
        if seq.done:
            self._complete(seq)

    def _decode_batch(self, seqs: List[Sequence], n_blk: int) -> bool:
        """One decode step for one batch. Returns False only when a real
        fault forced a pool rebuild (the caller must abort its group
        snapshot for this tick)."""
        from ..core import dispatch
        from ..models.gpt import CacheOverflow

        # sequences at context capacity can't take another token — finish
        # them with what they have rather than corrupting a neighbor block
        ready = []
        for s in seqs:
            if s.length + 1 > s.n_blk * self._block_size:
                self._release(s)
                self._error(
                    s.req,
                    str(CacheOverflow(s.length + 1,
                                      s.n_blk * self._block_size)),
                    s,
                )
            else:
                ready.append(s)
        if not ready:
            return True
        B = self._buckets.batch_bucket(len(ready))
        rows = [s.table_row() for s in ready]
        lens = [s.length for s in ready]
        toks = [s.last_token for s in ready]
        for slot in range(len(ready), B):  # pad rows → per-slot scratch block
            rows.append([slot] * n_blk)
            lens.append(0)
            toks.append(0)
        args = (
            tuple(self._pool.k), tuple(self._pool.v), self._weight_vals(),
            jnp.asarray(np.asarray(rows, np.int32)),
            jnp.asarray(np.asarray(lens, np.int32)),
            jnp.asarray(np.asarray(toks, np.int32)),
        )
        key = ("decode", self._uid, B, n_blk)
        t0 = time.perf_counter()
        try:
            k_pools, v_pools, row, nxt = self._run_tiered(
                "decode", key, self._decode_fn, args)
        except _PoolsConsumed as e:
            self._recover_pools(e)
            return False
        except Exception as e:  # every tier failed — requeue this batch only
            for s in ready:
                self._requeue_seq(s, e)
            return True
        self._pool.k, self._pool.v = list(k_pools), list(v_pools)
        out = np.asarray(jax.device_get(nxt))
        row_np = (
            np.asarray(jax.device_get(row)) if self._keep_logits else None)
        step_ms = (time.perf_counter() - t0) * 1000.0
        dispatch._counters["serve_decode_steps"] += 1
        dispatch._emit("serve", site="engine", phase="decode",
                       rids=tuple(s.req.request_id for s in ready),
                       batch=B, blocks=n_blk, ms=round(step_ms, 3))
        self._decode_rows += len(ready)
        self._admission.note_decode(step_ms, len(ready))
        # per-(decode-signature) regression baseline: one key per captured
        # bucket program, so only a genuinely slower replay drifts
        from ..profiler import sentinel as _sentinel

        _sentinel.observe(f"serve_decode[{self._uid}:{B}x{n_blk}]", step_ms)
        now = self._now()
        for i, s in enumerate(ready):
            tok = int(out[i])
            s.length += 1
            s.tokens.append(tok)
            s.last_token = tok
            if row_np is not None:
                s.logits.append(row_np[i])
            self._token_lat.observe(step_ms)
            if s.done:
                self._complete(s)
            elif s.req.expired(now):
                # mid-decode expiry: this row leaves the group here (the
                # group list is rebuilt every tick, so no other row moves)
                # and answers 'timeout' with its partial output
                self._release(s)
                self._expire(s.req, stage="decode", seq=s)
        return True

    def _run_tiered(self, kind: str, key, fn, args):
        """captured (donated) → lazy (same program, no donation) → per-op."""
        from ..core import dispatch
        from ..core import lazy as _lazy
        from ..resilience import faults as _faults
        from ..resilience import runtime as _rt

        if not flags.flag("serving_capture"):
            return _rt.execute(kind, lambda: fn(*args))
        donate = bool(flags.flag("serving_capture_donate"))
        prog = _lazy.serve_program(key, fn, donate_argnums=(0, 1))
        if donate and _rt.captured_tier_ok(key):
            from ..analysis import ProgramVerificationError

            try:
                return _rt.execute(
                    kind, lambda: prog.run(args, donate=True),
                    fresh=not prog.built(True), ladder_key=key,
                    retry_unsafe=True,
                )
            except ProgramVerificationError:
                # the donated rung failed its equivalence certificate
                # against the plain rung (FLAGS_check_programs=2). The
                # check runs at trace time, BEFORE the donated program
                # executes, so the pools are intact: take the retry-safe
                # rung with the same buffers
                dispatch._counters["serve_capture_fallbacks"] += 1
            except Exception as e:
                dispatch._counters["serve_capture_fallbacks"] += 1
                if not isinstance(e, _faults.InjectedFault):
                    # the donated program may have consumed the pool before
                    # failing — never reuse those buffers
                    raise _PoolsConsumed(e)
                # injected faults raise BEFORE the program runs: inputs are
                # intact, take the retry-safe rung with the same buffers
        try:
            return _rt.execute(
                kind, lambda: prog.run(args, donate=False),
                fresh=not prog.built(False), ladder_key=key,
            )
        except Exception:
            # the non-donated rung never consumed its inputs, so the floor
            # is safe for injected AND real faults alike (a fused-program-
            # only flake completes per-op; a deterministic bug fails again
            # below and propagates to the requeue/error path)
            dispatch._counters["serve_capture_fallbacks"] += 1
        # ladder floor: plain eager — every op is its own resilience site
        return fn(*args)
