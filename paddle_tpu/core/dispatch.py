"""Eager op dispatch + tape autograd recording.

This is the TPU-native replacement for the reference's eager execution core:
  - imperative::Tracer::TraceOpImpl (paddle/fluid/imperative/tracer.cc:185) —
    the per-op hot loop that picks a kernel and optionally wires the grad graph;
  - PreparedOp / PHI kernel dispatch (imperative/prepared_operator.cc:129,172) —
    replaced by one XLA lowering per op with a (fn, static-args) jit cache;
  - egr::GradNodeBase / autograd wiring (paddle/fluid/eager/grad_node_info.h:90).

Design: every op is a *pure jax function* `fn(*arrays, **static_kwargs)`.
`apply()` unwraps Tensor args, runs the op through a cached `jax.jit`, and —
when gradients are required — records a GradNode holding the `jax.vjp`
residual closure. There are no hand-written grad kernels: jax.vjp derives the
backward for every op (the reference needs ~350 GradOpMaker classes for this).
The backward engine (`run_backward`) is a dependency-counted topological sweep
equivalent to BasicEngine::Execute (imperative/basic_engine.cc:392) /
egr::Backward (eager/backward.cc:800).
"""
from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from types import MappingProxyType
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import flags

__all__ = [
    "apply",
    "no_grad",
    "enable_grad",
    "is_grad_enabled",
    "set_grad_enabled",
    "GradNode",
    "run_backward",
    "dispatch_counters",
    "reset_dispatch_counters",
]

_tls = threading.local()
_amp = None  # lazily bound paddle_tpu.amp module (circular at import time)
_res = None  # lazily bound paddle_tpu.resilience (same circularity)
_trace = None  # lazily bound paddle_tpu.profiler.trace (same circularity)


def _amp_module():
    global _amp
    if _amp is None:
        from .. import amp as _amp_mod

        _amp = _amp_mod
    return _amp


def _resilience_module():
    global _res
    if _res is None:
        from .. import resilience as _res_mod

        _res = _res_mod
    return _res


def _rexec(site, thunk, **kw):
    """Route one program launch through the resilience executor (fault
    injection + retry/backoff + ladder accounting; paddle.resilience)."""
    return _resilience_module().runtime.execute(site, thunk, **kw)


def _trace_module():
    global _trace
    if _trace is None:
        from ..profiler import trace as _trace_mod

        _trace = _trace_mod
    return _trace


def _emit(kind, site="", **attrs):
    """Flight-recorder emit (paddle.profiler.trace), lazily bound."""
    _trace_module().emit(kind, site=site, **attrs)


_attribution = None


def _attribution_module():
    global _attribution
    if _attribution is None:
        from ..profiler import attribution as a

        _attribution = a
    return _attribution


def _note_op_program(name, fn, kw_items, vals, t0):
    """Attribution hook for one per-op launch: register the op's static
    profile once per name (spec-only thunk — closure-holding fns are
    measured but never pinned) and feed the measured wall time into the
    per-key EMA (paddle.profiler.attribution)."""
    try:
        a = _attribution_module()
        key = "op:" + name
        if not a.known(key):
            # first sight of this op name = the call that traced+compiled
            # its jit wrapper: register the static side (spec-only thunk;
            # closure-holding fns register measured-only, never pinned)
            # and SKIP the measurement — compiles are never folded into
            # the measured EMA, same contract as the other categories
            thunk = None
            if _cache_token(fn) is not None:
                kw = dict(kw_items)
                specs = tuple(
                    jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
                    if isinstance(v, (jax.Array, np.ndarray)) else v
                    for v in vals
                )

                def thunk(_fn=fn, _kw=kw, _specs=specs):
                    return jax.make_jaxpr(
                        lambda *args: _fn(*args, **_kw))(*_specs)

            a.register(key, "op", jaxpr_thunk=thunk)
            return
        a.note_run(key, "op", (time.perf_counter() - t0) * 1000.0)
    except Exception:
        pass  # attribution must never break the op


# ---------------------------------------------------------------------------
# Dispatch counters: device-program launches by category, lazy-segment flush
# accounting, and compile-cache hit/miss/eviction counts. Readable via
# paddle_tpu.profiler.dispatch_counters(). Program counts are one per
# dispatched call (op / segment flush / backward sweep / fused optimizer
# update) — the unit the programs-per-step arithmetic uses.
# ---------------------------------------------------------------------------
_counters: Dict[str, Any] = {}
# serializes reset against off-thread counter updates (the background
# compile worker, checkpoint persist threads): a clear()+update() reset
# racing a worker's read-modify-write must neither drop the worker's sample
# into a half-rebuilt dict nor KeyError out of its finally block
_counters_lock = threading.Lock()


def _counter_add(key: str, n):
    """Race-free off-thread counter update (see _counters_lock)."""
    with _counters_lock:
        _counters[key] = _counters.get(key, type(n)()) + n


def _counter_set(key: str, v):
    """Race-free off-thread gauge write (see _counters_lock)."""
    with _counters_lock:
        _counters[key] = v


def _counter_add_labeled(family: str, key: str, n: int = 1):
    """Race-free update of one nested reason/site family entry — for
    writers that may run off the main thread (the perf-regression sentinel
    observes from the serving loop and the training thread alike)."""
    with _counters_lock:
        fam = _counters.get(family)
        if fam is None:
            fam = _counters[family] = {}
        fam[key] = fam.get(key, 0) + n


def reset_dispatch_counters():
    with _counters_lock:
        _reset_counters_locked()


def _reset_counters_locked():
    _counters.clear()
    _counters.update(
        programs=0,
        op_programs=0,
        segment_programs=0,
        backward_programs=0,
        optimizer_programs=0,
        segments_flushed=0,
        lazy_ops_deferred=0,
        segment_cache_hits=0,
        segment_cache_misses=0,
        segment_cache_evictions=0,
        jit_cache_evictions=0,
        vjp_cache_evictions=0,
        captured_programs=0,
        # one per paddle.jit.compile_train_step call (the jitted whole step)
        compiled_programs=0,
        # backend compiles (count, seconds) and persistent-cache hits, from
        # profiler/trace.py's jax.monitoring listener
        backend_compiles=0,
        backend_compile_s=0.0,
        compile_cache_hits=0,
        capture_builds=0,
        capture_replays=0,
        capture_fallbacks=0,
        capture_evictions=0,
        donation_alias_flags=0,
        # gradient-accumulation capture: accumulate-only microsteps replayed
        # as one captured program (forward + backward + grad accumulate)
        capture_accum_builds=0,
        capture_accum_replays=0,
        # mesh-aware capture (FLAGS_eager_capture_sharded): captured-step
        # builds/replays whose executable carries declared in/out shardings
        # over a multi-device mesh, and donated captures demoted to the
        # non-donated rung because the per-shard donation_safety proof did
        # not cover every donated position (capture still replays 1
        # program/step; only in-place buffer reuse is given up)
        capture_sharded_builds=0,
        capture_sharded_replays=0,
        capture_donation_fallbacks=0,
        # proof-carrying parity (analysis.equivalence, FLAGS_check_programs=2):
        # structural certification of the captured 1-program step against the
        # 3-program composition before the first donated replay — proofs run,
        # proofs passed, proven divergences (ProgramVerificationError), and
        # unprovable certificates demoted through the counted ladder
        capture_equivalence_checks=0,
        capture_equivalence_certified=0,
        capture_equivalence_divergences=0,
        capture_equivalence_unprovable=0,
        # decode-mode twin: donated-vs-plain serve rung certification
        serve_equivalence_checks=0,
        serve_equivalence_certified=0,
        serve_equivalence_divergences=0,
        # async host pipeline (FLAGS_eager_async_compile): background compile
        # submissions/joins, bridge flushes (fresh segments executed eagerly
        # while their fused program compiles off-thread), and captured steps
        # resolved on the 3-program path while their executable compiles
        async_compiles=0,
        async_compile_joins=0,
        async_compile_skipped=0,
        async_bridge_flushes=0,
        capture_async_builds=0,
        capture_build_pending_steps=0,
        # host-side time breakdown (ms): aval/trace work, main-thread-blocking
        # fresh compiles, cached replays, and background-thread compile time
        trace_time_ms=0.0,
        compile_time_ms=0.0,
        replay_time_ms=0.0,
        async_compile_ms=0.0,
        # resilience runtime (paddle.resilience): fault / retry / ladder /
        # rescue / preemption event accounting
        fault_events=0,
        injected_faults=0,
        transient_faults=0,
        fatal_faults=0,
        retry_attempts=0,
        retry_exhausted=0,
        retry_backoff_ms=0.0,
        ladder_demotions=0,
        ladder_promotions=0,
        numeric_rescues=0,
        rescue_lr_backoffs=0,
        segment_nan_checks=0,
        segment_per_op_fallbacks=0,
        preemptions=0,
        emergency_saves=0,
        # checkpoint pipeline (distributed/checkpoint.py): boundary device
        # snapshots, async vs sync persists, emergency saves that joined an
        # in-flight persist instead of redoing it, and the per-phase time
        # split (snapshot is the only step-path cost; transfer + commit run
        # on the background persist thread). ckpt_auto_save_freq is a gauge:
        # the cadence tuner's current save frequency.
        ckpt_snapshots=0,
        ckpt_async_saves=0,
        ckpt_sync_saves=0,
        ckpt_emergency_joined_inflight=0,
        ckpt_snapshot_ms=0.0,
        ckpt_transfer_ms=0.0,
        ckpt_commit_ms=0.0,
        ckpt_pipeline_stall_ms=0.0,
        ckpt_cadence_retunes=0,
        ckpt_auto_save_freq=0,
        # serving runtime (paddle.serving): decode-mode capture builds /
        # replays / tier fallbacks / LRU evictions, engine step + admission
        # accounting (serve_requests_dropped must stay 0 — the chaos serve
        # gate fails on anything else)
        serve_capture_builds=0,
        serve_capture_replays=0,
        serve_capture_fallbacks=0,
        serve_capture_evictions=0,
        serve_prefills=0,
        serve_decode_steps=0,
        serve_admission_refusals=0,
        serve_requests_completed=0,
        serve_requests_rejected=0,
        serve_requests_dropped=0,
        serve_request_requeues=0,
        serve_preempt_drains=0,
        # overload robustness (ISSUE 11): SLO-aware admission sheds
        # ('overloaded' responses, by reason — the queue-wait trip wire
        # is serve_shed_reasons['queue_p99']), deadline expiries (by
        # stage: queued/prefill/decode), supervisor-driven engine
        # restarts, engine health transitions, and the pool-leak tripwire
        # run_until_idle audits (must stay 0, like serve_requests_dropped)
        serve_requests_shed=0,
        serve_deadline_expired=0,
        serve_engine_restarts=0,
        serve_health_transitions=0,
        serve_block_leaks=0,
        # fleet front door (ISSUE 20): cross-replica routing, mid-decode
        # failover (router_reroutes never burns a request's own retry
        # budget), drain-to-peers handoffs, lease-plane refresh failures
        # (fail-soft: stale table, not an outage), and the router's own
        # zero-drop audit (router_requests_dropped must stay 0 — the
        # serve_fleet chaos gate fails on anything else)
        router_requests=0,
        router_routed=0,
        router_reroutes=0,
        router_shed_reroutes=0,
        router_replicas_lost=0,
        router_drain_handoffs=0,
        router_lease_read_failures=0,
        router_requests_dropped=0,
        router_autoscale_grow_proposals=0,
        router_autoscale_shrink_proposals=0,
        # ops plane (ISSUE 13): perf-regression sentinel trips (the
        # labeled family records WHICH step-signature / serving key
        # regressed) and clears (a tripped key recovering re-baselines)
        perf_regressions=0,
        perf_regression_clears=0,
        # attribution layer (ISSUE 15): program cost-registry
        # registrations, fused-telemetry steps/spikes (the labeled family
        # names WHICH parameter group spiked), and postmortem-directory
        # prunes (FLAGS_postmortem_keep)
        program_registrations=0,
        telemetry_steps=0,
        telemetry_spikes=0,
        postmortems_pruned=0,
        serve_shed_reasons={},
        serve_expire_stages={},
        flush_reasons={},
        capture_fallback_reasons={},
        # scaled_dot_product_attention calls that took the dense O(S^2)
        # path with FLAGS_use_flash_attention on, and why (nn/functional)
        flash_attention_fallbacks=0,
        flash_attention_fallback_reasons={},
        # traces of ops.state_space.ssd_scan that took the jax.numpy form
        # because the kernels refuse the shape, and why
        ssd_scan_fallbacks=0,
        ssd_scan_fallback_reasons={},
        fault_sites={},
        perf_regression_sites={},
        telemetry_spike_groups={},
    )


reset_dispatch_counters()


def _count_program(kind: str = "op"):
    _counters["programs"] += 1
    _counters[kind + "_programs"] += 1
    _emit("program", site=kind)
    if kind == "op":
        # per-op program launches make a step ineligible for whole-step
        # capture; the observer (when active) marks the step dirty
        _lazy._observe_op_program()


def _count_flash_fallback(reason: str, q_shape, k_shape):
    _counters["flash_attention_fallbacks"] += 1
    fam = _counters["flash_attention_fallback_reasons"]
    fam[reason] = fam.get(reason, 0) + 1
    _emit("flash_fallback", site="scaled_dot_product_attention",
          reason=reason, q_shape=q_shape, k_shape=k_shape)


def _count_ssd_chunks(why, **shape):
    """One ``ssd_chunks`` event a trace of the state-space scan; a shape the
    kernels refuse (``why``) is counted beside it."""
    if why is not None:
        _counters["ssd_scan_fallbacks"] += 1
        fam = _counters["ssd_scan_fallback_reasons"]
        fam[why] = fam.get(why, 0) + 1
    _emit("ssd_chunks", site="ssd_scan", path="xla" if why else "vmem",
          **shape, **({"why": why} if why else {}))


def dispatch_counters() -> Dict[str, Any]:
    """IMMUTABLE point-in-time snapshot of the dispatch counter family
    (nested reason/site dicts included). Callers needing a mutable or
    JSON-serializable copy must copy the nested maps too —
    ``{k: dict(v) if isinstance(v, Mapping) else v for k, v in c.items()}``
    (what ``measure_programs`` does); the live store is internal
    (``_counters``)."""
    # the copy takes _counters_lock so a concurrent reset (clear+update)
    # can never be observed half-rebuilt — a /metrics scrape racing
    # reset_dispatch_counters must see either the old families or the
    # fresh zeros, never a torn partial dict. Main-thread writers bump
    # entries WITHOUT the lock (that is the hot-path budget), so the
    # nested-dict copies retry the rare resize-during-copy race.
    for _ in range(8):
        try:
            with _counters_lock:
                out = dict(_counters)
                for k, v in out.items():
                    if isinstance(v, dict):  # reason/site/stage families
                        out[k] = MappingProxyType(dict(v))
            return MappingProxyType(out)
        except RuntimeError:
            continue
    with _counters_lock:  # sustained churn: per-family fallback. Main-
        # thread writers can still insert new family keys mid-copy, so
        # each nested copy retries on its own; a family that never copies
        # clean degrades to its last good attempt (or empty) — this
        # function's contract is a snapshot that NEVER raises, a /metrics
        # scrape must not 500 on counter churn
        out = {}
        for k in list(_counters):
            v = _counters.get(k)
            if isinstance(v, dict):
                fam = {}
                for _ in range(64):
                    try:
                        fam = dict(v)
                        break
                    except RuntimeError:
                        continue
                v = MappingProxyType(fam)
            out[k] = v
    return MappingProxyType(out)


def _grad_state():
    if not hasattr(_tls, "grad_enabled"):
        _tls.grad_enabled = True
    return _tls


def is_grad_enabled() -> bool:
    return _grad_state().grad_enabled


def set_grad_enabled(mode: bool):
    _grad_state().grad_enabled = bool(mode)


class _GradModeCtx:
    """Context manager + decorator, like paddle.no_grad (fluid/dygraph/base.py)."""

    def __init__(self, mode: bool):
        self._mode = mode

    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(self._mode)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False

    def __call__(self, func=None):
        if func is None:
            return self

        @functools.wraps(func)
        def wrapper(*args, **kwargs):
            with _GradModeCtx(self._mode):
                return func(*args, **kwargs)

        return wrapper


def no_grad(func=None):
    ctx = _GradModeCtx(False)
    return ctx(func) if func is not None else ctx


def enable_grad(func=None):
    ctx = _GradModeCtx(True)
    return ctx(func) if func is not None else ctx


# ---------------------------------------------------------------------------
# Per-op compile cache (the PHI KernelFactory analogue: kernel_factory.h:230).
# LRU-bounded by FLAGS_eager_jit_cache_size: long-running eager sessions with
# many op/static-kwarg combos must not grow compile caches (and their live
# jax.jit wrappers) without bound. Oldest entries evict first, counted.
# ---------------------------------------------------------------------------
_jit_cache: "OrderedDict[Tuple, Callable]" = OrderedDict()


def _lru_get(store: OrderedDict, key):
    hit = store.get(key)
    if hit is not None:
        store.move_to_end(key)
    return hit


def _lru_put(store: OrderedDict, key, value, evict_counter: Optional[str] = None,
             cap: Optional[int] = None):
    store[key] = value
    if cap is None:
        cap = int(flags.flag("eager_jit_cache_size"))
    if cap > 0:
        while len(store) > cap:
            store.popitem(last=False)
            if evict_counter is not None:
                _counters[evict_counter] += 1


def _cache_token(fn: Callable):
    """Stable cache identity for `fn`, or None if fn must not be cached.

    Ops are often passed as lambdas / nested defs created fresh on every
    call; caching by function identity would then grow _jit_cache (and pile
    up live jax.jit wrappers) without bound. A fresh function object still
    shares one code object with its siblings, and its behavior depends only
    on that code plus the (static) kwargs — *unless* it closes over
    call-specific values, in which case it is uncacheable.
    """
    if getattr(fn, "__closure__", None):
        return None
    code = getattr(fn, "__code__", None)
    return code if code is not None else fn


def _named_partial(fn: Callable, kw_items: Tuple) -> Callable:
    """`fn` with its static kwargs bound, under fn's own name: jax names a
    jitted callable by `__name__`, and a bare partial reads `jit(<unknown>)`
    in every op_name and device-trace kernel name below it."""
    bound = functools.partial(fn, **dict(kw_items))
    bound.__name__ = getattr(fn, "__name__", type(fn).__name__)
    return bound


def _jitted(fn: Callable, kw_items: Tuple, token=None) -> Optional[Callable]:
    if token is not None:
        # explicit token (to_static's per-config closures): store the jit
        # wrapper ON the token object so its lifetime follows the token —
        # module-global caching would pin the closure (and the params it
        # captures) forever after the model is dropped
        try:
            store = token.__dict__.setdefault("_jst_jit_cache", {})
        except AttributeError:
            store = None
        if store is not None:
            try:
                cached = store.get(kw_items)
            except TypeError:
                return None
            if cached is None:
                cached = jax.jit(_named_partial(fn, kw_items))
                store[kw_items] = cached
            return cached
    token = token if token is not None else _cache_token(fn)
    if token is None:
        return None
    key = (token, kw_items)
    try:
        cached = _lru_get(_jit_cache, key)
    except TypeError:  # unhashable static kwarg — run unjitted
        return None
    if cached is None:
        cached = jax.jit(_named_partial(fn, kw_items))
        _lru_put(_jit_cache, key, cached, "jit_cache_evictions")
    return cached


def _hashable(v):
    if isinstance(v, list):
        return tuple(_hashable(x) for x in v)
    return v


# ---------------------------------------------------------------------------
# Cached forward+vjp programs: jax.vjp re-linearizes the op on EVERY eager
# call (the dominant per-op dispatch cost — SURVEY §7 hard part 5). A jax
# vjp closure is a pytree, so `lambda *a: jax.vjp(f, *a)` can be jit-cached:
# the linearization happens once per (op, static-args, diff-positions,
# shapes) and later calls replay one compiled program. The closure's
# application is likewise jitted (_apply_vjp), cached by residual structure.
# ---------------------------------------------------------------------------
_vjp_cache: "OrderedDict[Tuple, Callable]" = OrderedDict()


def _jitted_vjp(fn: Callable, kw_items: Tuple, diff_idx: Tuple, token,
                attach_to_token: bool = False):
    store = _vjp_cache
    key = (token, kw_items, diff_idx)
    if attach_to_token:
        # explicit token (to_static closures): cache rides on the token so
        # dropping the model frees its compiled programs (see _jitted)
        try:
            store = token.__dict__.setdefault("_jst_vjp_cache", {})
            key = (kw_items, diff_idx)
        except AttributeError:
            pass  # token without __dict__ — fall back to the global store
    try:
        cached = (
            _lru_get(store, key) if store is _vjp_cache else store.get(key)
        )
    except TypeError:
        return None
    if cached is None:
        kw = dict(kw_items)

        def run(*all_vals):
            def partial_fn(*dv):
                full = list(all_vals)
                for i, v in zip(diff_idx, dv):
                    full[i] = v
                res = fn(*full, **kw)
                return tuple(res) if isinstance(res, list) else res

            return jax.vjp(partial_fn, *[all_vals[i] for i in diff_idx])

        cached = jax.jit(run)
        if store is _vjp_cache:
            _lru_put(store, key, cached, "vjp_cache_evictions")
        else:
            store[key] = cached
    return cached


@jax.jit
def _apply_vjp(vjp_fn, cts):
    return vjp_fn(cts)


# ---------------------------------------------------------------------------
# Autograd graph
# ---------------------------------------------------------------------------
class Edge:
    """Tape edge to one op input, frozen at record time.

    The producer (node, out_index) is snapshotted when the op is recorded so
    that later in-place mutation of the input tensor (which rebinds its
    _grad_node) cannot create cycles or corrupt history — this is the tape's
    answer to the reference's inplace_version counters
    (imperative/variable_wrapper.h)."""

    __slots__ = ("tensor", "node", "out_index")

    def __init__(self, tensor):
        self.tensor = tensor  # live object: leaf .grad accumulation + hooks
        self.node = tensor._grad_node
        self.out_index = tensor._out_index


class GradNode:
    """One recorded op. Holds the vjp closure and edges to producer nodes."""

    __slots__ = (
        "vjp_fn",
        "primal_fn",
        "jit_vjp",
        "inputs",
        "out_avals",
        "out_is_seq",
        "op_name",
        "__weakref__",
    )

    def __init__(self, vjp_fn, inputs, out_avals, op_name, out_is_seq=None):
        self.vjp_fn = vjp_fn
        # pure fn of the differentiable input values; when present, the
        # backward sweep can re-derive the vjp *as a recorded tape op* so
        # that create_graph=True (double grad) composes naturally
        self.primal_fn = None
        # True when vjp_fn is a jax-pytree closure safe to run through the
        # jitted applier (_apply_vjp); python-closure vjps (PyLayer, AMP
        # recast, host ops) stay on the direct-call path
        self.jit_vjp = False
        # List[Edge] — differentiable inputs in vjp order
        self.inputs = [a if isinstance(a, Edge) else Edge(a) for a in inputs]
        self.out_avals = out_avals  # [(shape, dtype)] per output
        # cotangent pytree structure must mirror the primal output exactly:
        # a 1-tuple output still needs a 1-tuple cotangent
        self.out_is_seq = len(out_avals) > 1 if out_is_seq is None else out_is_seq
        self.op_name = op_name

    def __repr__(self):
        return f"<GradNode {self.op_name}>"


_FLOAT_DTYPES = frozenset(
    np.dtype(d)
    for d in (
        jnp.float16, jnp.bfloat16, jnp.float32, jnp.float64,
        jnp.complex64, jnp.complex128,
    )
)


def _is_float_array(v) -> bool:
    dt = getattr(v, "dtype", None)
    if dt is not None:
        return dt in _FLOAT_DTYPES
    try:
        return jnp.issubdtype(jnp.result_type(v), jnp.floating) or jnp.issubdtype(
            jnp.result_type(v), jnp.complexfloating
        )
    except TypeError:
        return False


def apply(
    fn: Callable,
    *args,
    op_name: Optional[str] = None,
    differentiable: bool = True,
    cache_token=None,
    jit: bool = True,
    **kwargs,
):
    """Run op `fn` on Tensor/array args, recording autograd tape if needed.

    Positional args may be Tensors, jax arrays, numpy arrays, or scalars.
    Keyword args are static config and must be hashable (lists are tupled).
    """
    from .tensor import Tensor  # circular at import time only

    if kwargs:
        kwargs.pop("name", None)
        kw_items = tuple(sorted((k, _hashable(v)) for k, v in kwargs.items()))
    else:
        kw_items = ()

    # deferred-execution mode: append the op to the pending per-thread
    # segment instead of launching a program (see core/lazy.py). Ops the
    # segment can't host fall through to the per-op path below (the lazy
    # layer flushes first, preserving program order).
    if flags.flag("eager_lazy_dispatch"):
        if _resilience_module().runtime.lazy_tier_ok():
            out = _lazy.lazy_apply(
                fn,
                args,
                kw_items,
                op_name=op_name,
                differentiable=differentiable,
                jit=jit,
                cache_token=cache_token,
            )
            if out is not _lazy._FALLBACK:
                return out
        else:
            # degradation ladder demoted the lazy tier (repeated segment
            # faults): run per-op until the cooldown re-promotes it
            _lazy.flush_if_pending("ladder_demoted")

    # one pass over args: unwrap values AND find differentiable positions
    vals = []
    diff_idx: List[int] = []
    for i, a in enumerate(args):
        if isinstance(a, Tensor):
            v = a._value
            if type(v) is _lazy.LazyRef:
                v = v.materialize()
            vals.append(v)
            if not a.stop_gradient and getattr(v, "dtype", None) in _FLOAT_DTYPES:
                diff_idx.append(i)
        else:
            vals.append(a)

    # AMP O1 input casting (reference: tracer.cc:222-240 AMP auto-cast)
    if _amp_module().amp_active():
        vals = _amp.maybe_cast_inputs(
            op_name or getattr(fn, "__name__", "op"), vals
        )

    record = differentiable and bool(diff_idx) and _grad_state().grad_enabled

    if not record:
        # jit=False: ops with data-dependent output shapes (nonzero, unique,
        # masked_select, ...) cannot trace — they run concretely
        jfn = (
            _jitted(fn, kw_items, token=cache_token)
            if (jit and flags.flag("eager_op_jit"))
            else None
        )
        t0 = time.perf_counter()
        if jfn is not None:
            out_vals = _rexec("op", lambda: jfn(*vals))
        else:
            kw = dict(kw_items)
            out_vals = _rexec("op", lambda: fn(*vals, **kw))
        _count_program("op")
        _note_op_program(op_name or getattr(fn, "__name__", "op"),
                         fn, kw_items, vals, t0)
        return _wrap_outputs(out_vals, stop_gradient=True, node=None)

    # run the recorded primal through a CACHED forward+vjp program when the
    # op is cacheable: linearization is staged once per (op, statics, diff
    # positions, shapes) instead of on every eager call — this is what
    # keeps per-op dispatch overhead near one compiled-call dispatch
    token = cache_token if cache_token is not None else _cache_token(fn)
    jitted_vjp = (
        _jitted_vjp(fn, kw_items, tuple(diff_idx), token,
                    attach_to_token=cache_token is not None)
        if (flags.flag("eager_op_jit") and token is not None)
        else None
    )
    # partial_fn still routes through the jitted op: the first-order vjp
    # uses jitted_vjp, but create_graph's re-derivation replays partial_fn
    # and must keep the one-compiled-call primal
    jfn = (
        _jitted(fn, kw_items, token=cache_token)
        if flags.flag("eager_op_jit")
        else None
    )

    def partial_fn(*diff_vals):
        full = list(vals)
        for i, v in zip(diff_idx, diff_vals):
            full[i] = v
        if jfn is not None:
            res = jfn(*full)
        else:
            res = fn(*full, **dict(kw_items))
        # normalize list outputs to tuple so cotangent pytree structure is fixed
        return tuple(res) if isinstance(res, list) else res

    t0 = time.perf_counter()
    if jitted_vjp is not None:
        out_vals, vjp_fn = _rexec("op", lambda: jitted_vjp(*vals))
        is_jit_vjp = True
    else:
        out_vals, vjp_fn = _rexec(
            "op", lambda: jax.vjp(partial_fn, *[vals[i] for i in diff_idx])
        )
        is_jit_vjp = False
    _count_program("op")
    _note_op_program(op_name or getattr(fn, "__name__", "op"),
                     fn, kw_items, vals, t0)

    # AMP O1 casts inputs (e.g. fp32 weight → bf16) before the op; the
    # reference records the cast op so its backward restores fp32 grads
    # (tracer.cc AMP cast). Here the cast is fused into this node, so cast
    # cotangents back to each input's ORIGINAL dtype on the way out.
    orig_dtypes = [args[i]._value.dtype for i in diff_idx]
    if any(
        vals[i].dtype != od for i, od in zip(diff_idx, orig_dtypes)
    ):
        inner_vjp = vjp_fn
        is_jit_vjp = False  # wrapped in a python closure below

        def vjp_fn(cts, _inner=inner_vjp, _dts=orig_dtypes):
            gs = _inner(cts)
            return tuple(
                g.astype(dt)
                if hasattr(g, "dtype")
                and g.dtype != dt
                and g.dtype != jax.dtypes.float0
                else g
                for g, dt in zip(gs, _dts)
            )

    flat_outs, is_seq = _flatten_outputs(out_vals)
    out_avals = [(tuple(o.shape), o.dtype) for o in flat_outs]
    node = GradNode(
        vjp_fn,
        [args[i] for i in diff_idx],
        out_avals,
        op_name or getattr(fn, "__name__", "op"),
        out_is_seq=is_seq,
    )
    # AMP-recast nodes can't re-derive a clean vjp (the cast lives outside
    # partial_fn's dtype contract); everything else supports double grad
    if all(vals[i].dtype == od for i, od in zip(diff_idx, orig_dtypes)):
        node.primal_fn = partial_fn
    node.jit_vjp = is_jit_vjp
    outs = []
    for i, o in enumerate(flat_outs):
        t = Tensor(o, stop_gradient=not _is_float_array(o))
        if not t.stop_gradient:
            t._grad_node = node
            t._out_index = i
        outs.append(t)
    if flags.flag("check_nan_inf"):
        _check_nan_inf(node.op_name, flat_outs)
    return outs if is_seq else outs[0]


def _flatten_outputs(out_vals):
    if isinstance(out_vals, (tuple, list)):
        return list(out_vals), True
    return [out_vals], False


def _wrap_outputs(out_vals, stop_gradient, node):
    from .tensor import Tensor

    flat, is_seq = _flatten_outputs(out_vals)
    outs = [Tensor(o, stop_gradient=stop_gradient) for o in flat]
    return outs if is_seq else outs[0]


def _check_nan_inf(op_name, arrays):
    """FLAGS_check_nan_inf debug scan — reference: framework/operator.cc:1258,
    details/nan_inf_utils_detail.cc."""
    for i, a in enumerate(arrays):
        if _is_float_array(a):
            bad = bool(jnp.any(~jnp.isfinite(a)))
            if bad:
                raise FloatingPointError(
                    f"NaN/Inf detected in output {i} of op '{op_name}'"
                )


# ---------------------------------------------------------------------------
# Compiled-tape backward: when every node on the tape has a jax-pytree vjp
# closure, the whole dependency-counted sweep is pure jax and can be traced
# into ONE XLA program (cached by tape topology + residual structure). An
# eager training step then dispatches a single backward program instead of
# one per recorded op — the tape is, in effect, compiled. Falls back to the
# per-node sweep for hooks / create_graph / retain_graph / PyLayer vjps.
# ---------------------------------------------------------------------------
_tape_bwd_cache: Dict[Tuple, Callable] = {}


def _make_tape_backward(avals, seqflags, edges, n_leaves, root_key):
    def fn(vjp_fns, seed):
        cot = {root_key: seed}
        leaf_out = [None] * n_leaves
        for idx in range(len(avals)):
            cts = []
            for i, (shape, dtype) in enumerate(avals[idx]):
                c = cot.pop((idx, i), None)
                cts.append(jnp.zeros(shape, dtype) if c is None else c)
            packed = tuple(cts) if seqflags[idx] else cts[0]
            grads = vjp_fns[idx](packed)
            for (prod, oi, leaf_slot), g in zip(edges[idx], grads):
                if g is None or (
                    hasattr(g, "dtype") and g.dtype == jax.dtypes.float0
                ):
                    continue
                if prod >= 0:
                    k = (prod, oi)
                    prev = cot.get(k)
                    cot[k] = g if prev is None else prev + g
                elif leaf_slot >= 0:
                    prev = leaf_out[leaf_slot]
                    leaf_out[leaf_slot] = g if prev is None else prev + g
        return leaf_out

    return jax.jit(fn)


def _tape_structure(root, node_check=None):
    """Canonical structure of root's tape: (key, order_nodes, leaf_tensors),
    or None when the tape has features the caller can't cover.

    `node_check(node) -> bool` filters every discovered node (the compiled
    tape requires a live jitted vjp closure; the whole-step capture
    controller requires the opposite: unflushed nodes owned by the pending
    segment). Tapes with backward hooks or disconnected multi-root pieces
    are rejected for both callers. The key is deterministic across steps
    with identical topology/avals — it doubles as the capture controller's
    tape fingerprint."""
    root_node = root._grad_node
    if root_node is None:
        return None

    # discover graph + consumer counts (mirrors run_backward pass 1)
    nodes: List[GradNode] = []
    index: Dict[int, int] = {}
    pending: Dict[int, int] = {}
    stack = [root_node]
    while stack:
        node = stack.pop()
        if id(node) in index:
            continue
        if node_check is not None and not node_check(node):
            return None
        index[id(node)] = len(nodes)
        nodes.append(node)
        for edge in node.inputs:
            if edge.tensor._backward_hooks:
                return None
            prod = edge.node
            if prod is not None:
                pending[id(prod)] = pending.get(id(prod), 0) + 1
                if id(prod) not in index:
                    stack.append(prod)

    # topological order (consumers before producers), Kahn from the root
    order_nodes: List[GradNode] = []
    ready = [root_node] if pending.get(id(root_node), 0) == 0 else []
    counts = dict(pending)
    seen = set()
    while ready:
        node = ready.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        order_nodes.append(node)
        for edge in node.inputs:
            prod = edge.node
            if prod is not None:
                counts[id(prod)] -= 1
                if counts[id(prod)] == 0:
                    ready.append(prod)
    if len(order_nodes) != len(nodes):
        return None  # disconnected pieces (multi-root tape) — fall back

    node_pos = {id(n): i for i, n in enumerate(order_nodes)}
    leaf_slots: Dict[int, int] = {}
    leaf_tensors: List = []
    edges_rec = []
    avals_rec = []
    seq_rec = []
    for n in order_nodes:
        avals_rec.append(tuple(n.out_avals))
        seq_rec.append(n.out_is_seq)
        erec = []
        for edge in n.inputs:
            if edge.node is not None:
                erec.append((node_pos[id(edge.node)], edge.out_index, -1))
            else:
                t = edge.tensor
                if t.stop_gradient:
                    erec.append((-1, 0, -1))  # grad discarded
                else:
                    slot = leaf_slots.get(id(t))
                    if slot is None:
                        slot = len(leaf_tensors)
                        leaf_slots[id(t)] = slot
                        leaf_tensors.append(t)
                    erec.append((-1, 0, slot))
        edges_rec.append(tuple(erec))

    key = (tuple(avals_rec), tuple(seq_rec), tuple(edges_rec),
           len(leaf_tensors), root._out_index)
    return key, order_nodes, leaf_tensors


def _try_compiled_tape_backward(root, seed_val) -> bool:
    """Run root.backward() as one compiled program. Returns False when the
    tape has features the compiled path doesn't cover (caller falls back)."""
    from .tensor import Tensor

    struct = _tape_structure(
        root, node_check=lambda n: n.jit_vjp and n.vjp_fn is not None
    )
    if struct is None:
        return False
    key, order_nodes, leaf_tensors = struct
    avals_rec, seq_rec, edges_rec = key[0], key[1], key[2]
    fn = _tape_bwd_cache.get(key)
    if fn is None:
        fn = _make_tape_backward(
            avals_rec, seq_rec, edges_rec, len(leaf_tensors),
            (0, root._out_index),
        )
        _tape_bwd_cache[key] = fn
    vjp_fns = [n.vjp_fn for n in order_nodes]
    leaf_vals = _rexec("backward", lambda: fn(vjp_fns, seed_val))
    _count_program("backward")
    # step-capture observation: a compiled-tape backward is one of the two
    # events (fused segment flush + this) a capturable step consists of
    _lazy._observe_event(("bwd", key))
    for t, g in zip(leaf_tensors, leaf_vals):
        if g is None:
            continue
        if t.grad is None:
            t.grad = Tensor(g, stop_gradient=True)
        else:
            t.grad._value = t.grad._value + g
    for n in order_nodes:
        n.vjp_fn = None
        n.primal_fn = None
    return True


# ---------------------------------------------------------------------------
# Backward engine
# ---------------------------------------------------------------------------
def run_backward(
    tensors: Sequence,
    grad_tensors: Optional[Sequence] = None,
    retain_graph: bool = False,
    accumulate_into_grad: bool = True,
    inputs: Optional[Sequence] = None,
    create_graph: bool = False,
):
    """Dependency-counted reverse sweep over the GradNode graph.

    Mirrors BasicEngine::Execute (imperative/basic_engine.cc:392): init
    cotangents from `grad_tensors` (default ones), topologically count edges,
    run each node's vjp when all its output cotangents arrived, and either
    accumulate into leaf `.grad` (backward()) or collect grads for `inputs`
    (paddle.grad / eager general_grad).
    Returns a dict id(tensor)->grad value when `inputs` is given.

    With `create_graph=True` every node's backward is itself re-derived from
    the node's pure primal fn and *recorded on the tape* (as an `<op>_grad`
    op), so the returned grads carry grad nodes and a second sweep computes
    higher-order derivatives — the role of the reference's registered
    double-grad ops (e.g. matmul_double_grad) without writing any of them.
    """
    from .tensor import Tensor

    roots: List[Tensor] = list(tensors)
    if grad_tensors is None:
        grad_tensors = [None] * len(roots)

    # whole-step capture (FLAGS_eager_step_capture): when the controller is
    # armed and this backward matches the captured step's forward-segment +
    # tape signature, the backward is DEFERRED — the pending segment stays
    # unflushed and the whole step (forward + backward + optimizer update)
    # resolves at optimizer.step() as ONE donated XLA program. Any read of a
    # grad / pending tensor before then aborts back to the 3-program path.
    if (
        not retain_graph
        and not create_graph
        and inputs is None
        and accumulate_into_grad
        and len(roots) == 1
        and grad_tensors[0] is None
        and flags.flag("eager_tape_jit")
        and _lazy.step_capture_backward(roots[0])
    ):
        return None

    # backward is a materialization point: the pending forward segment (and
    # any lazy grad_tensors) must be concrete before the sweep reads values
    _lazy.flush_if_pending("backward")

    if create_graph:
        retain_graph = True

    # compiled-tape fast path: single root, plain accumulate-into-.grad
    # backward with no graph retention → one XLA program for the whole sweep
    if (
        not retain_graph
        and not create_graph
        and inputs is None
        and accumulate_into_grad
        and len(roots) == 1
        and roots[0]._grad_node is not None
        and flags.flag("eager_tape_jit")
    ):
        root = roots[0]
        g0 = grad_tensors[0]
        if g0 is None:
            if root._value.size == 1:
                seed = jnp.ones_like(root._value)
            else:
                seed = None  # shape error — the standard path raises it
        else:
            seed = g0._value if isinstance(g0, Tensor) else jnp.asarray(g0)
        if seed is not None and _try_compiled_tape_backward(root, seed):
            return None

    def _raw(g):
        return g._value if isinstance(g, Tensor) else g

    def _acc(a, g):
        # accumulate cotangents; under create_graph keep the result on-tape
        if a is None or (isinstance(a, int) and a == 0):
            return g
        if create_graph and (isinstance(a, Tensor) or isinstance(g, Tensor)):
            a = a if isinstance(a, Tensor) else Tensor(a, stop_gradient=True)
            g = g if isinstance(g, Tensor) else Tensor(g, stop_gradient=True)
            return apply(jnp.add, a, g, op_name="grad_accumulate")
        return _raw(a) + _raw(g)

    # cotangent accumulation keyed by (id(node), out_index)
    cotangents: Dict[Tuple[int, int], Any] = {}
    node_by_id: Dict[int, GradNode] = {}
    leaf_grads: Dict[int, Any] = {}
    want_inputs = None
    if inputs is not None:
        want_inputs = {id(t): t for t in inputs}

    def seed(t: Tensor, g):
        if g is None:
            if t._value.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {tuple(t._value.shape)}"
                )
            g = jnp.ones_like(t._value)
        elif isinstance(g, Tensor) and not create_graph:
            g = g._value
        if t._grad_node is not None:
            # non-leaf: capture for paddle.grad(inputs=...) AND keep flowing
            if want_inputs is not None and id(t) in want_inputs:
                leaf_grads[id(t)] = _acc(leaf_grads.get(id(t)), g)
            key = (id(t._grad_node), t._out_index)
            node_by_id[id(t._grad_node)] = t._grad_node
            cotangents[key] = _acc(cotangents.get(key), g)
        else:
            _store_leaf(t, g)

    def _store_leaf(t: Tensor, g):
        if t.stop_gradient:
            return
        g = _apply_hooks(t, g)
        if want_inputs is not None:
            if id(t) in want_inputs:
                leaf_grads[id(t)] = _acc(leaf_grads.get(id(t)), g)
            return
        if accumulate_into_grad:
            if t.grad is None:
                if isinstance(g, Tensor):
                    t.grad = g if create_graph else Tensor(g._value, stop_gradient=True)
                else:
                    t.grad = Tensor(g, stop_gradient=True)
            elif create_graph:
                t.grad = _acc(t.grad, g)
            else:
                t.grad._value = t.grad._value + _raw(g)

    def _apply_hooks(t: Tensor, g):
        for hook in t._backward_hooks:
            g_t = g if isinstance(g, Tensor) else Tensor(g, stop_gradient=True)
            out = hook(g_t)
            if out is not None:
                g = out if isinstance(out, Tensor) and create_graph else (
                    out._value if isinstance(out, Tensor) else out
                )
        return g

    # ---- pass 1: discover reachable graph, count consumer edges per node
    pending: Dict[int, int] = {}
    visited = set()
    stack = [t._grad_node for t in roots if t._grad_node is not None]
    for n in stack:
        node_by_id[id(n)] = n
    while stack:
        node = stack.pop()
        if id(node) in visited:
            continue
        visited.add(id(node))
        for edge in node.inputs:
            prod = edge.node
            if prod is not None:
                node_by_id[id(prod)] = prod
                pending[id(prod)] = pending.get(id(prod), 0) + 1
                if id(prod) not in visited:
                    stack.append(prod)

    for t, g in zip(roots, grad_tensors):
        seed(t, g)

    def _recorded_vjp(node: GradNode, cts):
        """Run node's backward as a *recorded* tape op (`<op>_grad`).

        Re-derives the vjp from node.primal_fn over the live input tensors
        (in-place-mutated inputs would use their current values — same caveat
        the reference guards with inplace_version counters) so the grad
        computation itself lands on the tape and supports another sweep.
        """
        in_ts = [e.tensor for e in node.inputs]
        ct_ts = [c if isinstance(c, Tensor) else Tensor(c, stop_gradient=True) for c in cts]
        n_in = len(in_ts)
        primal = node.primal_fn
        out_is_seq = node.out_is_seq

        def grad_op(*vals):
            ivals, cvals = vals[:n_in], vals[n_in:]
            _, vfn = jax.vjp(primal, *ivals)
            return tuple(vfn(tuple(cvals) if out_is_seq else cvals[0]))

        out = apply(grad_op, *in_ts, *ct_ts, op_name=node.op_name + "_grad")
        return out if isinstance(out, list) else [out]

    # ---- pass 2: execute ready nodes
    ready = [
        node_by_id[nid]
        for nid in {id(t._grad_node) for t in roots if t._grad_node is not None}
        if pending.get(nid, 0) == 0
    ]
    executed = set()
    while ready:
        node = ready.pop()
        if id(node) in executed:
            continue
        executed.add(id(node))
        cts = tuple(
            cotangents.pop((id(node), i), None) for i in range(len(node.out_avals))
        )
        cts = tuple(
            jnp.zeros(shape, dtype) if c is None else c
            for c, (shape, dtype) in zip(cts, node.out_avals)
        )
        if node.vjp_fn is None:
            raise RuntimeError(
                "trying to backward through the graph a second time "
                "(set retain_graph=True to allow this)"
            )
        if create_graph and node.primal_fn is not None:
            in_grads = _recorded_vjp(node, cts)
        else:
            raw_cts = tuple(_raw(c) for c in cts)
            packed = raw_cts if node.out_is_seq else raw_cts[0]
            if node.jit_vjp:
                # jitted application of the pytree vjp closure — the
                # transpose is compiled once per residual structure
                in_grads = _rexec(
                    "backward", lambda: _apply_vjp(node.vjp_fn, packed)
                )
            else:
                in_grads = node.vjp_fn(packed)
            _count_program("backward")
            if create_graph:
                # no primal fn (PyLayer / AMP-recast): grads are correct but
                # constant w.r.t. further differentiation
                import warnings

                warnings.warn(
                    f"create_graph=True through op '{node.op_name}' (no pure "
                    "primal available): its first-order grads are correct but "
                    "treated as constants by any further differentiation",
                    stacklevel=2,
                )
                in_grads = tuple(
                    Tensor(g, stop_gradient=True)
                    if g is not None
                    and not (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0)
                    else g
                    for g in in_grads
                )
        if not retain_graph:
            node.vjp_fn = None
            node.primal_fn = None
        for edge, g in zip(node.inputs, in_grads):
            gv = g._value if isinstance(g, Tensor) else g
            skip = gv is None or (hasattr(gv, "dtype") and gv.dtype == jax.dtypes.float0)
            prod = edge.node
            if prod is None:
                if not skip:
                    _store_leaf(edge.tensor, g)
            else:
                if not skip:
                    g = _apply_hooks(edge.tensor, g)
                    # capture grads of requested intermediates (paddle.grad
                    # w.r.t. non-leaf tensors) while still propagating
                    if want_inputs is not None and id(edge.tensor) in want_inputs:
                        leaf_grads[id(edge.tensor)] = _acc(
                            leaf_grads.get(id(edge.tensor)), g
                        )
                    key = (id(prod), edge.out_index)
                    cotangents[key] = _acc(cotangents.get(key), g)
                # edge consumed regardless of whether a cotangent flowed
                pending[id(prod)] -= 1
                if pending[id(prod)] == 0:
                    ready.append(prod)
        # non-leaf intermediate with its own retained grad (paddle
        # Tensor.retain_grads semantics): store when requested
        # (handled via _store_leaf for inputs without producer above)

    if want_inputs is not None:
        return leaf_grads
    return None


# imported last: lazy.py only references dispatch internals from inside its
# functions, so the cycle resolves here without a partial-module hazard
from . import lazy as _lazy  # noqa: E402
