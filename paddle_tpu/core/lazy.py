"""Deferred (lazy) eager dispatch: batch per-op launches into fused segments.

The per-op eager path (dispatch.apply) launches one XLA program per op, so
an eager LeNet train step costs ~13 device-program launches (a count from
profiler.dispatch_counters(); what each launch costs on the chip is not
measured yet). This module is the classic
LazyTensor-style fix proven by torch-xla (XLATensor + pending IR graph,
torch_xla/csrc/tensor.cpp) and by the reference's own to_static tracing:

  - with FLAGS_eager_lazy_dispatch on, `apply()` does not execute: the op is
    appended to a per-thread pending *segment* and the caller gets a Tensor
    backed by a `LazyRef` (shape/dtype known via jax.eval_shape, value
    pending);
  - materialization points — host reads (numpy/item/float/bool), backward,
    explicit paddle_tpu.device.synchronize(), uncacheable/jit=False ops, a
    mid-segment AMP region — flush the whole pending segment as ONE jitted
    program;
  - the compiled segment is cached by *segment signature* (sequence of op
    cache-tokens + static kwargs + input bindings + external input avals),
    so a steady-state eager train step replays a cached fused executable:
    1 forward segment + 1 compiled-tape backward + 1 fused optimizer update.

Autograd composes unchanged: recorded ops get their GradNode at defer time
(so later ops snapshot correct Edges), and the segment program computes each
recorded op's jax.vjp *inside the fused trace* — at flush the pytree vjp
closures come back as concrete residuals and are slotted into the pending
GradNodes, which then behave exactly like per-op-path nodes (including the
compiled-tape backward and create_graph re-derivation).
"""
from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import async_compile as _async
from . import flags

__all__ = [
    "LazyRef",
    "captured_step_donation_verdicts",
    "captured_step_handle",
    "captured_step_program",
    "captured_step_shard_info",
    "drain_async",
    "flush_if_pending",
    "materialize",
    "pending_op_count",
    "pending_segment_jaxpr",
    "reset_serve_programs",
    "serve_capture_state",
    "serve_program",
    "step_capture_state",
    "step_signature_id",
]


def _add_time(key: str, t0: float) -> float:
    from . import dispatch

    dt_ms = (time.perf_counter() - t0) * 1000.0
    dispatch._counters[key] += dt_ms
    return dt_ms


def _note_program(key: str, category: str, dt_ms: float):
    """Feed one measured program run into the attribution cost registry
    (paddle.profiler.attribution) — the same duration the dispatch timers
    book, so the per-key EMA and replay_time_ms agree."""
    try:
        from ..profiler import attribution as _attribution

        _attribution.note_run(key, category, dt_ms)
    except Exception:
        pass  # attribution must never break the program


def _register_program(key: str, category: str, **kw):
    try:
        from ..profiler import attribution as _attribution

        _attribution.register(key, category, **kw)
    except Exception:
        pass


def _sig_id(sig) -> str:
    try:
        return f"{hash(sig) & 0xFFFF:04x}"
    except TypeError:
        return "anon"


def drain_async():
    """Join every background compile job (FLAGS_eager_async_compile). An
    explicit sync point for benchmarks/tests; steady-state code never needs
    it — pending compiles install themselves at the next flush/replay of
    their signature."""
    _async.drain()

# sentinel returned by lazy_apply when the op must take the per-op path
_FALLBACK = object()

_tls = threading.local()

# binding kinds inside a segment: op input comes from an external array, a
# previous op's output, or an embedded python-scalar literal
_EXT, _RES, _LIT = 0, 1, 2


def _np_dtype(dt):
    """np.dtype when possible; jax extended dtypes (PRNG keys, float8 wrap
    types) pass through as-is — they are hashable and aval-comparable."""
    try:
        return np.dtype(dt)
    except TypeError:
        return dt


class LazyRef:
    """Pending value of one output of one deferred op.

    Carries the inferred aval so shape/dtype-dependent control flow does NOT
    flush; any other attribute access (or numpy/jax conversion) materializes
    by flushing the owning segment. After the flush `_concrete` holds the
    real array and all access delegates to it.
    """

    __slots__ = (
        "_segment",
        "_op_index",
        "_out_index",
        "_shape",
        "_dtype",
        "_concrete",
        "__weakref__",
    )

    def __init__(self, segment, op_index, out_index, shape, dtype):
        self._segment = segment
        self._op_index = op_index
        self._out_index = out_index
        self._shape = tuple(shape)
        self._dtype = _np_dtype(dtype)
        self._concrete = None

    # -- aval surface (no flush) -------------------------------------------
    @property
    def shape(self):
        return self._shape

    @property
    def dtype(self):
        return self._dtype

    @property
    def ndim(self):
        return len(self._shape)

    @property
    def size(self):
        return int(np.prod(self._shape)) if self._shape else 1

    # -- materialization ----------------------------------------------------
    def materialize(self):
        if self._concrete is None:
            _flush(self._segment, "sync")
            if self._concrete is None:
                # the owning segment's flush failed earlier (compile or
                # runtime error): surface the root cause on every read
                # instead of silently yielding None
                raise RuntimeError(
                    "lazy-dispatch segment flush failed; this tensor's value "
                    "is unavailable"
                ) from self._segment.error
        return self._concrete

    def __getattr__(self, name):
        # anything beyond the aval surface needs the real array
        return getattr(self.materialize(), name)

    def __jax_array__(self):
        return self.materialize()

    def __array__(self, dtype=None):
        arr = np.asarray(jax.device_get(self.materialize()))
        return arr.astype(dtype) if dtype is not None else arr

    def __repr__(self):
        state = "pending" if self._concrete is None else "materialized"
        return f"<LazyRef {state} shape={self._shape} dtype={self._dtype}>"


def _delegating(name):
    def method(self, *args, **kwargs):
        return getattr(self.materialize(), name)(*args, **kwargs)

    method.__name__ = name
    return method


# operators bypass instance __getattr__ — install explicit delegates so a
# LazyRef that leaks into raw jnp/python arithmetic still behaves like its
# (materialized) array instead of raising
for _name in (
    "__add__", "__radd__", "__sub__", "__rsub__", "__mul__", "__rmul__",
    "__truediv__", "__rtruediv__", "__floordiv__", "__rfloordiv__",
    "__mod__", "__rmod__", "__pow__", "__rpow__", "__matmul__",
    "__rmatmul__", "__neg__", "__pos__", "__abs__", "__getitem__",
    "__lt__", "__le__", "__gt__", "__ge__", "__eq__", "__ne__",
    "__float__", "__int__", "__bool__", "__len__", "__iter__",
):
    setattr(LazyRef, _name, _delegating(_name))
LazyRef.__hash__ = object.__hash__  # __eq__ delegate must not kill identity hash


def materialize(v):
    """Concrete value of `v` (flushes the pending segment for LazyRefs)."""
    return v.materialize() if type(v) is LazyRef else v


class _SegOp:
    """One deferred op inside a pending segment."""

    __slots__ = ("fn", "kw", "bindings", "diff_idx", "record", "node", "outs")

    def __init__(self, fn, kw, bindings, diff_idx, record, node):
        self.fn = fn
        self.kw = kw
        self.bindings = bindings
        self.diff_idx = diff_idx
        self.record = record
        self.node = node
        self.outs = []  # [(LazyRef, Tensor)] — filled by lazy_apply


class _Segment:
    """Per-thread pending op trace, flushed as one jitted program."""

    __slots__ = (
        "ops", "ext_vals", "ext_ids", "ext_specs", "sig_parts", "flushed",
        "error",
    )

    def __init__(self):
        self.ops: List[_SegOp] = []
        self.ext_vals: List[Any] = []
        self.ext_ids: Dict[int, int] = {}
        self.ext_specs: List[Tuple] = []
        self.sig_parts: List[Tuple] = []
        self.flushed = False
        self.error: Optional[BaseException] = None


def _current_segment() -> _Segment:
    seg = getattr(_tls, "segment", None)
    if seg is None or seg.flushed:
        seg = _Segment()
        _tls.segment = seg
    return seg


def pending_op_count() -> int:
    seg = getattr(_tls, "segment", None)
    return 0 if seg is None or seg.flushed else len(seg.ops)


def flush_if_pending(reason: str = "explicit_sync"):
    """Flush this thread's pending segment (no-op when nothing is pending).

    Also a resolution point for a deferred captured-step backward
    (FLAGS_eager_step_capture): anything that forces materialization before
    optimizer.step() replays the capture aborts it back to the normal
    3-program path first — numerics never change, only the program count."""
    if getattr(_tls, "capture_deferred", None) is not None:
        _abort_capture(reason)
    seg = getattr(_tls, "segment", None)
    if seg is not None and not seg.flushed and seg.ops:
        _flush(seg, reason)


# ---------------------------------------------------------------------------
# Output-aval inference, cached by (op token, statics, input specs): one
# host-side jax.eval_shape per new op configuration, dict lookups after.
# ---------------------------------------------------------------------------
_aval_cache: "OrderedDict[Tuple, Tuple]" = OrderedDict()


def _infer_out_specs(fn, kw, arg_specs):
    args = []
    for spec in arg_specs:
        if spec[0] == "arr":
            args.append(jax.ShapeDtypeStruct(spec[1], spec[2]))
        else:
            args.append(spec[1])
    out = jax.eval_shape(functools.partial(fn, **kw), *args)
    if isinstance(out, (tuple, list)):
        flat, is_seq = list(out), True
    else:
        flat, is_seq = [out], False
    return [(tuple(o.shape), _np_dtype(o.dtype)) for o in flat], is_seq


# ---------------------------------------------------------------------------
# Segment compile cache: signature -> jitted segment program (LRU-bounded).
# With FLAGS_eager_async_compile, a fresh signature's fused program compiles
# on the background thread first (_pending_seg_compiles holds the future)
# and is installed here at the next flush of the same signature.
# ---------------------------------------------------------------------------
_segment_cache: "OrderedDict[Tuple, Callable]" = OrderedDict()
_pending_seg_compiles: Dict[Tuple, Any] = {}
_pending_lock = threading.Lock()


def _segment_fn(plan, check=False):
    """Raw (unjitted) segment program over the external-input list.

    plan: [(fn, kw, bindings, diff_idx, record)] — deliberately stripped
    of _SegOp/GradNode/Tensor refs so the cached closure pins no user data.

    With `check=True` (FLAGS_check_nan_inf under lazy dispatch) the program
    additionally returns one bool per op — any(~isfinite) over that op's
    float outputs — folded INTO the fused trace: the finite scan costs zero
    extra program launches and is read once at flush."""

    def seg_fn(ext):
        results = []
        vjps = []
        bad_flags = []
        for fn, kw, bindings, diff_idx, record in plan:
            vals = []
            for kind, a, b in bindings:
                if kind == _EXT:
                    vals.append(ext[a])
                elif kind == _RES:
                    vals.append(results[a][b])
                else:
                    vals.append(a)
            if record:

                def partial(*dv, _fn=fn, _kw=kw, _vals=tuple(vals), _di=diff_idx):
                    full = list(_vals)
                    for i, v in zip(_di, dv):
                        full[i] = v
                    res = _fn(*full, **_kw)
                    return tuple(res) if isinstance(res, list) else res

                out, vjp = jax.vjp(partial, *[vals[i] for i in diff_idx])
                vjps.append(vjp)
            else:
                out = fn(*vals, **kw)
            outs = list(out) if isinstance(out, (tuple, list)) else [out]
            results.append(outs)
            if check:
                bad = jnp.asarray(False)
                for o in outs:
                    if jnp.issubdtype(jnp.result_type(o), jnp.inexact):
                        bad = bad | jnp.any(~jnp.isfinite(o))
                bad_flags.append(bad)
        if check:
            return results, vjps, jnp.stack(bad_flags)
        return results, vjps

    return seg_fn


def _build_segment_fn(plan, check=False):
    return jax.jit(_segment_fn(plan, check))


def _seg_signature(seg: _Segment) -> Tuple:
    """Canonical compile-cache / capture signature of a segment. The
    finite-check flag is part of it: a checking segment compiles a different
    program (one extra bool-vector output) than a non-checking one."""
    return (
        tuple(seg.sig_parts),
        tuple(seg.ext_specs),
        bool(flags.flag("check_nan_inf")),
    )


def _seg_plan(seg: _Segment):
    return [(op.fn, op.kw, op.bindings, op.diff_idx, op.record) for op in seg.ops]


def _segment_jaxpr(plan, ext_specs):
    """Closed jaxpr of the fused segment program (for the verifier).

    Preserves the recorded weak_type flags: weak scalars promote
    differently, and the verified jaxpr must match the jaxpr the segment
    actually compiles (a weak f64 literal is benign; a strong one is the
    upcast the dtype pass hunts)."""
    specs = [
        jax.ShapeDtypeStruct(
            shape, dtype, weak_type=bool(rest[0]) if rest else False
        )
        for shape, dtype, *rest in ext_specs
    ]
    return jax.make_jaxpr(_segment_fn(plan))(specs)


def pending_segment_jaxpr():
    """Trace this thread's pending segment WITHOUT flushing it; None when
    nothing is pending. Feeds paddle_tpu.analysis.check_pending_segment."""
    seg = getattr(_tls, "segment", None)
    if seg is None or seg.flushed or not seg.ops:
        return None
    return _segment_jaxpr(_seg_plan(seg), seg.ext_specs)


def _flush(seg: _Segment, reason: str):
    from . import dispatch

    if seg.flushed:
        return
    rec = getattr(_tls, "capture_deferred", None)
    if rec is not None and (seg is rec.segment or seg is rec.stub_seg):
        # a read reached a deferred captured step (the unflushed forward
        # segment or one of the placeholder grads) before optimizer.step()
        # replayed it: resolve by the normal flush + tape-backward path
        _abort_capture(reason)
        return
    seg.flushed = True
    if getattr(_tls, "segment", None) is seg:
        _tls.segment = None
    if not seg.ops:
        return

    check = bool(flags.flag("check_nan_inf"))
    n_ops = len(seg.ops)
    sig = _seg_signature(seg)
    skey = f"segment:{_sig_id(sig)}"
    jfn = dispatch._lru_get(_segment_cache, sig)
    fresh = jfn is None
    fut = None
    if fresh:
        with _pending_lock:
            fut = _pending_seg_compiles.get(sig)
    # the op plan is only needed to build a fresh segment fn, by the async
    # bridge, and by the per-op fault fallback below — cache-hit steady
    # state skips the O(num_ops) build entirely
    plan = _seg_plan(seg) if (fresh and fut is None) else None
    if fresh and fut is None:
        dispatch._counters["segment_cache_misses"] += 1
    elif not fresh:
        dispatch._counters["segment_cache_hits"] += 1

    fused = True
    bridged = False
    try:
        if plan is not None and int(flags.flag("check_programs")):
            # FLAGS_check_programs: verify the fused segment before its
            # first compile (cached replays were already verified). A
            # level-2 raise lands in the except path below, so reads of
            # this segment's tensors re-raise the verification error.
            from .. import analysis

            analysis.enforce(
                analysis.check(
                    _segment_jaxpr(plan, seg.ext_specs),
                    source="lazy-segment",
                ),
                where=f"lazy-segment flush ({reason})",
            )
        if not fresh:
            t0 = time.perf_counter()
            out = dispatch._rexec("segment", lambda: jfn(seg.ext_vals))
            _note_program(skey, "segment", _add_time("replay_time_ms", t0))
        elif fut is not None:
            # second flush of a signature whose fused program is compiling
            # in the background: join it (a compile-thread exception
            # re-raises HERE with its original traceback and lands in the
            # except path below, exactly like a synchronous compile error)
            t0 = time.perf_counter()
            with _pending_lock:
                # drop the pending entry up front: a compile-thread error
                # surfaces HERE once, and the next flush of this signature
                # starts a fresh compile instead of re-raising forever
                _pending_seg_compiles.pop(sig, None)
            jfn = fut.result()
            # any wait on a still-unfinished background compile is
            # main-thread-blocking compile time, not replay time
            _add_time("compile_time_ms", t0)
            dispatch._lru_put(
                _segment_cache, sig, jfn,
                evict_counter="segment_cache_evictions",
                cap=int(flags.flag("eager_segment_cache_size")),
            )
            dispatch._counters["async_compile_joins"] += 1
            dispatch._counters["segment_cache_hits"] += 1
            dispatch._emit("async_join", site="segment")
            t0 = time.perf_counter()
            out = dispatch._rexec("segment", lambda: jfn(seg.ext_vals))
            _note_program(skey, "segment", _add_time("replay_time_ms", t0))
        else:
            # attribution cost registry: a fresh segment signature
            # registers its static profile at build time (spec-only
            # thunk — the plan pins no user data, per _segment_fn)
            _register_program(
                skey, "segment",
                jaxpr_thunk=(
                    lambda _plan=plan, _specs=tuple(seg.ext_specs):
                    _segment_jaxpr(_plan, _specs)),
                ops=n_ops,
            )
            submitted = None
            if _async.enabled():
                jfn_bg = _build_segment_fn(plan, check)
                ext_snapshot = list(seg.ext_vals)

                def _compile_job(_jfn=jfn_bg, _ext=ext_snapshot):
                    # jax AOT: trace + compile from the snapshot's avals
                    # without EXECUTING the program (a plain first call
                    # would run the whole segment on device a second time,
                    # racing the main thread's bridged execution for the
                    # accelerator). The Compiled takes the place of the
                    # jitted wrapper in _segment_cache: avals — weak_type
                    # included — are part of the cache signature, so every
                    # later flush of this signature calls it with exactly
                    # the avals it was lowered for.
                    return _jfn.lower(_ext).compile()

                submitted = _async.submit(_compile_job)
            if submitted is not None:
                # async bridge: run the SAME op plan eagerly for immediate
                # results (identical ops and vjps — the rung the fault
                # fallback below already relies on) while the fused program
                # compiles off-thread. Fault injection, retries, and ladder
                # accounting wrap this main-thread execution as usual.
                with _pending_lock:
                    _pending_seg_compiles[sig] = submitted
                    # entries normally pop at the join; a signature-churning
                    # loop never joins, so bound the map (oldest first —
                    # dicts preserve insertion order) instead of pinning
                    # compiled programs for signatures that never recur
                    while len(_pending_seg_compiles) > 64:
                        _pending_seg_compiles.pop(
                            next(iter(_pending_seg_compiles))
                        )
                dispatch._counters["async_bridge_flushes"] += 1
                dispatch._emit("async_compile", site="segment",
                               phase="submit")
                t0 = time.perf_counter()
                out = dispatch._rexec(
                    "segment",
                    lambda: _segment_fn(plan, check)(seg.ext_vals),
                    fresh=True,
                )
                _add_time("replay_time_ms", t0)
                bridged = True
            else:
                jfn = _build_segment_fn(plan, check)
                t0 = time.perf_counter()
                out = dispatch._rexec(
                    "segment", lambda: jfn(seg.ext_vals), fresh=True
                )
                _add_time("compile_time_ms", t0)
    except BaseException as e:
        # a failed flush must leave no pending background compile keyed by
        # its signature: the submitted job compiled THIS segment's plan, and
        # a later (healthy) flush of the same signature joining it would
        # re-raise this flush's failure instead of compiling cleanly
        if fresh:
            with _pending_lock:
                _pending_seg_compiles.pop(sig, None)
        # graceful degradation (paddle.resilience): when the FUSED launch
        # keeps failing transiently (retries exhausted), re-execute the
        # same plan per-op — identical ops and vjps, one rung down the
        # ladder. Deterministic failures keep the fail-loud contract.
        out = None
        if isinstance(e, Exception) and dispatch._resilience_module().is_transient(e):
            try:
                if plan is None:
                    plan = _seg_plan(seg)  # cache-hit flush skipped the build
                out = _segment_fn(plan, check)(seg.ext_vals)
            except Exception:
                out = None
        if out is None:
            # record the root cause: every later materialize() of this
            # segment's refs re-raises it instead of silently yielding None.
            # A program that never ran successfully is never cached.
            seg.error = e
            seg.ops = []
            raise
        fused = False
        dispatch._counters["segment_per_op_fallbacks"] += 1
        for _ in plan:  # per-op programs, and the step is no longer capturable
            dispatch._count_program("op")
    if fused:
        if fresh and not bridged:
            # the bridged path has no jfn yet — its fused program installs
            # at the join (next flush of this signature), never a None here
            dispatch._lru_put(
                _segment_cache, sig, jfn,
                evict_counter="segment_cache_evictions",
                cap=int(flags.flag("eager_segment_cache_size")),
            )
        dispatch._count_program("segment")
    dispatch._counters["segments_flushed"] += 1
    reasons = dispatch._counters["flush_reasons"]
    reasons[reason] = reasons.get(reason, 0) + 1
    dispatch._emit(
        "flush", site="segment", reason=reason, ops=n_ops,
        cache=("join" if (fresh and fut is not None)
               else "miss" if fresh else "hit"),
        fused=fused, bridged=bridged,
    )
    if fused:
        _observe_event(("seg", sig))

    if check:
        results, vjps, bad_flags = out
        dispatch._counters["segment_nan_checks"] += 1
    else:
        results, vjps = out
        bad_flags = None
    bad_op = None
    if bad_flags is not None:
        badvec = np.asarray(bad_flags)
        if badvec.any():
            bad_op = getattr(
                seg.ops[int(np.argmax(badvec))].fn, "__name__", "op"
            )

    vi = 0
    for op, outs in zip(seg.ops, results):
        for (ref, t), val in zip(op.outs, outs):
            ref._concrete = val
            if t._value is ref:
                t._value = val
        if op.record:
            node = op.node
            node.vjp_fn = vjps[vi]
            vi += 1
            node.jit_vjp = True
            # replace predicted avals with the real ones (weak-type exactness)
            node.out_avals = [(tuple(v.shape), v.dtype) for v in outs]
    seg.ops = []  # drop op/node/tensor refs — the segment is spent
    if bad_op is not None:
        # the fused finite-check fired: same FloatingPointError contract as
        # the per-op FLAGS_check_nan_inf scan, raised once at flush (values
        # are already written back, so the bad tensors are inspectable)
        raise FloatingPointError(
            f"NaN/Inf detected in output of op '{bad_op}' "
            "(lazy-segment flush, FLAGS_check_nan_inf)"
        )


# ---------------------------------------------------------------------------
# The deferral entry point, called from dispatch.apply when the flag is on
# ---------------------------------------------------------------------------
def lazy_apply(
    fn: Callable,
    args: Tuple,
    kw_items: Tuple,
    *,
    op_name: Optional[str],
    differentiable: bool,
    jit: bool,
    cache_token,
):
    """Defer `fn` onto the pending segment; `_FALLBACK` sends the caller to
    the per-op path (after flushing, so program order is preserved)."""
    from . import dispatch
    from .tensor import Tensor

    # bail-outs: ops the segment trace cannot host take the per-op path.
    # jit=False ops have data-dependent output shapes; closure-captured fns
    # have no stable cache token; explicit cache_token ops (to_static
    # closures) manage their own compile caches; AMP casting and the debug
    # flags read per-call state the segment signature doesn't cover.
    if not jit:
        flush_if_pending("fallback_nojit")
        return _FALLBACK
    if cache_token is not None:
        flush_if_pending("fallback_token")
        return _FALLBACK
    token = dispatch._cache_token(fn)
    if token is None:
        flush_if_pending("fallback_uncacheable")
        return _FALLBACK
    if flags.flag("benchmark"):
        # FLAGS_check_nan_inf no longer forces the per-op path: the finite
        # scan is folded into the fused segment and read once at flush
        # (_segment_fn(check=True)), so programs-per-step is unchanged
        flush_if_pending("fallback_debug")
        return _FALLBACK
    amp = dispatch._amp_module()
    if amp.amp_active():
        flush_if_pending("fallback_amp")
        return _FALLBACK
    try:
        hash(kw_items)
    except TypeError:
        flush_if_pending("fallback_unhashable")
        return _FALLBACK

    # unwrap + classify args; tracer-backed values mean we are inside
    # someone's jit trace (to_static / recompute) — defer nothing there
    vals: List[Any] = []
    diff_idx: List[int] = []
    for i, a in enumerate(args):
        if isinstance(a, Tensor):
            v = a._value
            if isinstance(v, jax.core.Tracer):
                return _FALLBACK
            vals.append(v)
            if not a.stop_gradient and (
                getattr(v, "dtype", None) in dispatch._FLOAT_DTYPES
            ):
                diff_idx.append(i)
        else:
            if isinstance(a, jax.core.Tracer):
                return _FALLBACK
            vals.append(a)

    seg = _current_segment()

    # pass 1 — classify without mutating the segment, so any fallback below
    # leaves no stray external inputs in the signature
    pre: List[Tuple] = []
    arg_specs: List[Tuple] = []
    for v in vals:
        if type(v) is LazyRef:
            if v._concrete is not None:
                v = v._concrete
            elif v._segment is not seg:
                # pending ref from a stale/foreign segment: materialize it
                _flush(v._segment, "cross_segment")
                v = v._concrete
            else:
                pre.append((_RES, v._op_index, v._out_index))
                arg_specs.append(("arr", v._shape, v._dtype))
                continue
        if isinstance(v, (jax.Array, np.ndarray)):
            pre.append((_EXT, v, 0))
            arg_specs.append(
                ("arr", tuple(v.shape), _np_dtype(v.dtype),
                 bool(getattr(v, "weak_type", False)))
            )
        else:
            try:
                hash(v)
            except TypeError:
                flush_if_pending("fallback_unhashable")
                return _FALLBACK
            pre.append((_LIT, v, 0))
            arg_specs.append(("lit", v))

    record = (
        differentiable and bool(diff_idx) and dispatch._grad_state().grad_enabled
    )

    # output avals (cached eval_shape); failure → op is not traceable as-is
    kw = dict(kw_items)
    aval_key = (token, kw_items, tuple(arg_specs), record)
    hit = dispatch._lru_get(_aval_cache, aval_key)
    if hit is not None:
        out_specs, is_seq = hit
    else:
        t0 = time.perf_counter()
        try:
            out_specs, is_seq = _infer_out_specs(fn, kw, arg_specs)
        except Exception:
            # book only the failed inference itself — the fallback flush
            # below times its own work (replay/compile), and a finally here
            # would double-count it under trace_time_ms
            _add_time("trace_time_ms", t0)
            flush_if_pending("fallback_infer")
            return _FALLBACK
        _add_time("trace_time_ms", t0)
        # capped alongside the per-op compile caches (host-only metadata, no
        # jit wrappers, so no eviction counter)
        dispatch._lru_put(_aval_cache, aval_key, (out_specs, is_seq))

    # pass 2 — commit: intern external inputs, build final bindings
    bindings = []
    for kind, a, b in pre:
        if kind == _EXT:
            k = seg.ext_ids.get(id(a))
            if k is None:
                k = len(seg.ext_vals)
                seg.ext_vals.append(a)
                seg.ext_ids[id(a)] = k
                seg.ext_specs.append(
                    (tuple(a.shape), _np_dtype(a.dtype),
                     bool(getattr(a, "weak_type", False)))
                )
            bindings.append((_EXT, k, 0))
        else:
            bindings.append((kind, a, b))
    bindings = tuple(bindings)
    diff_t = tuple(diff_idx)

    node = None
    if record:
        node = dispatch.GradNode(
            None,
            [args[i] for i in diff_idx],
            list(out_specs),
            op_name or getattr(fn, "__name__", "op"),
            out_is_seq=is_seq,
        )

        # pure primal for create_graph double-grad re-derivation; non-diff
        # captures resolve at call time (post-flush they are concrete)
        def primal_fn(*dv, _fn=fn, _kw=kw, _vals=tuple(vals), _di=diff_t):
            full = [materialize(x) for x in _vals]
            for i, v in zip(_di, dv):
                full[i] = v
            res = _fn(*full, **_kw)
            return tuple(res) if isinstance(res, list) else res

        node.primal_fn = primal_fn

    op_index = len(seg.ops)
    op = _SegOp(fn, kw, bindings, diff_t, record, node)
    outs = []
    for i, (shape, dtype) in enumerate(out_specs):
        ref = LazyRef(seg, op_index, i, shape, dtype)
        # per-op parity: only RECORDED float outputs are differentiable;
        # non-recorded ops (no_grad, differentiable=False, int inputs) wrap
        # with stop_gradient=True exactly like _wrap_outputs does
        sg = True if not record else dtype not in dispatch._FLOAT_DTYPES
        t = _new_tensor(ref, stop_gradient=sg)
        if record and not t.stop_gradient:
            t._grad_node = node
            t._out_index = i
        op.outs.append((ref, t))
        outs.append(t)
    seg.ops.append(op)
    seg.sig_parts.append((token, kw_items, bindings, record, diff_t))
    dispatch._counters["lazy_ops_deferred"] += 1

    if len(seg.ops) >= int(flags.flag("eager_segment_max_ops")):
        _flush(seg, "segment_limit")

    return outs if is_seq else outs[0]


def _new_tensor(value, stop_gradient):
    from .tensor import Tensor

    t = Tensor.__new__(Tensor)
    t._value = value
    t.stop_gradient = stop_gradient
    t.grad = None
    t._grad_node = None
    t._out_index = 0
    t._backward_hooks = []
    t._inplace_version = 0
    t.name = ""
    t.persistable = False
    t.is_parameter = False
    return t


# ---------------------------------------------------------------------------
# Whole-step capture-and-replay (FLAGS_eager_step_capture).
#
# The LazyTensor / CUDA-Graphs idiom on top of lazy dispatch: the controller
# observes the per-step event sequence — one fused forward segment flush, one
# compiled-tape backward, one fused optimizer update — and once the same
# (segment signature, tape fingerprint, optimizer fingerprint) triple has
# recurred for FLAGS_eager_capture_warmup consecutive steps it re-traces the
# WHOLE step (forward + backward + optimizer update) as one jaxpr, compiled
# with donate_argnums over parameters and optimizer state so updates reuse
# their HBM buffers in place. The mechanics:
#
#   - run_backward, seeing an armed controller and a matching pending
#     segment + tape, DEFERS the backward: the segment stays unflushed, each
#     tape leaf gets a placeholder grad (a LazyRef on a stub segment), and
#     execution continues;
#   - optimizer.step() is the step boundary: with a deferred backward
#     pending it replays (or first compiles) the captured executable — ONE
#     device program for the whole step — and writes back op outputs, leaf
#     grads, new params, and new optimizer state;
#   - ANY materialization in between (host read of a pending tensor or a
#     placeholder grad, device.synchronize, a second backward, a signature
#     mismatch at either end) aborts transparently: the segment flushes, the
#     real tape backward runs, and the step completes on the 3-program path.
#     Fallback is a counted perf event, never a numerics change — the
#     captured program reproduces the tape's gradient contract structurally
#     (stop_gradient on every non-differentiable input position), so its
#     results match the per-op path exactly.
# ---------------------------------------------------------------------------
_capture_cache: "OrderedDict[Tuple, Any]" = OrderedDict()

# events a capturable step consists of, in order; kept small — anything else
# (per-op fallbacks, extra flushes, per-node backward sweeps) marks the step
# dirty / pattern-mismatched and the controller simply keeps observing. A
# k-step gradient-accumulation cycle observes [seg, bwd] * k before its one
# optimizer.step(), so the cap bounds the capturable accumulation period
# (k <= 32) rather than sitting at the plain 2-event step.
_MAX_OBSERVED_EVENTS = 64


class _Observer:
    """Per-thread step-signature observer / arming state.

    `cycle_len` is the armed accumulation period k (1 = plain step): the
    boundary pattern [seg, bwd] repeated k times before one optimizer.step()
    is *periodic* — once armed, microsteps 0..k-2 replay as one captured
    accumulate-only program each and microstep k-1 defers into the full
    captured update. `pos` tracks the position inside the current cycle."""

    __slots__ = ("events", "dirty", "prev", "stable", "armed", "cycle_len",
                 "pos")

    def __init__(self):
        self.events: List[Tuple] = []
        self.dirty = False
        self.prev: Optional[Tuple] = None
        self.stable = 0
        self.armed: Optional[Tuple] = None  # (seg_sig, tape_key, opt_fp)
        self.cycle_len = 1
        self.pos = 0


def _disarm(obs: "_Observer"):
    obs.armed, obs.prev, obs.stable = None, None, 0
    obs.cycle_len, obs.pos = 1, 0


class _DeferredStep:
    """One backward deferred between loss.backward() and optimizer.step().

    `grad_prev_vals` is None for a plain step; for the final microstep of an
    accumulation cycle it holds each leaf's k-1-step partial grad sum — a
    program input of the captured update, and the value the abort path
    restores before re-running the real sweep."""

    __slots__ = (
        "segment", "stub_seg", "root", "seg_sig", "tape_key",
        "leaves", "leaf_slots", "leaf_grads", "expected_opt_fp",
        "grad_prev_vals",
    )


class _CaptureEntry:
    """One compiled whole-step executable plus its slot bookkeeping.

    Everything here is structural (slot indices, plan closures, optimizer
    hyper floats) — no tensors or arrays are pinned, so a cached entry
    outlives any particular model instance with the same step signature."""

    __slots__ = ("exe", "param_idx", "extra_idx", "param_slots",
                 "extra_slots", "rest_slots", "warmed", "rescue",
                 # fused numerics telemetry (FLAGS_telemetry): the traced
                 # program carries one extra stacked vector output
                 "telemetry",
                 # async host pipeline: the in-flight background AOT
                 # compile (FLAGS_eager_async_compile); steps arriving
                 # before it finishes resolve on the 3-program path
                 "pending",
                 # static-analysis surface: the raw (unjitted) step fn, the
                 # arg ShapeDtypeStructs of the first replay, and whether
                 # params/state were donated — captured_step_program()
                 # retraces these for the memory planner without compiling
                 "step_fn", "arg_specs", "donated",
                 # proof-carrying parity (analysis.equivalence): the
                 # independently-built 3-program reference composition and
                 # the EquivalenceCertificate the FLAGS_check_programs=2
                 # gate produced before the first donated replay
                 "ref_fn", "certificate",
                 # planner-guided remat (analysis.plan): the RematPlan this
                 # build applied (or proved empty), None when FLAGS_memory_plan
                 # did not ask for one
                 "mem_plan",
                 # mesh-aware capture (FLAGS_eager_capture_sharded): the jax
                 # Mesh the executable was jitted against (structural —
                 # devices, not user buffers), the flat per-invar
                 # PartitionSpecs fed to the per-shard analyzer, and the
                 # per-position donation_safety verdicts recorded at build;
                 # all None for a single-chip capture
                 "mesh", "in_specs", "verdicts", "__weakref__")


class _CaptureIneligible(Exception):
    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def _capture_mesh(rec) -> Optional[Any]:
    """Mesh of a deferred step's leaves when mesh-aware capture applies:
    the first leaf whose committed value carries a multi-device
    NamedSharding names it (shard_params / fleet.distributed_train_step
    placement), else None — single-chip capture, the pre-mesh contract.
    FLAGS_eager_capture_sharded=0 pins the single-chip path."""
    if not flags.flag("eager_capture_sharded"):
        return None
    from jax.sharding import NamedSharding

    for t in rec.leaves:
        sh = getattr(t._value, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh.devices.size > 1:
            return sh.mesh
    return None


def _mesh_axes(mesh) -> Dict[str, int]:
    return dict(zip((str(a) for a in mesh.axis_names),
                    (int(s) for s in mesh.devices.shape)))


def _mesh_tag(mesh) -> Optional[str]:
    """Compact mesh label for attribution keys / capture state / emits:
    'dp2mp2' on a dp2×mp2 mesh (size-1 axes elided)."""
    if mesh is None:
        return None
    return "".join(
        f"{a}{s}" for a, s in _mesh_axes(mesh).items() if s > 1) or None


def _mesh_fingerprint(mesh, rec) -> Optional[Tuple]:
    """The capture cache key's mesh/spec element: mesh axes/shape plus each
    leaf's committed PartitionSpec. A respec (shard_params, an elastic
    rescale, a topology change) re-captures under a fresh key instead of
    replaying a stale layout; None single-chip keeps pre-mesh keys
    unchanged."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding

    specs = []
    for t in rec.leaves:
        sh = getattr(t._value, "sharding", None)
        specs.append(sh.spec if isinstance(sh, NamedSharding) else None)
    return (tuple(_mesh_axes(mesh).items()), tuple(specs))


def _mesh_ladder_tag() -> Optional[Tuple]:
    """Mesh component of the degradation-ladder key: captured → lazy →
    per-op demotion is tracked per (step signature, mesh), so a fault
    history earned on one topology never gates another — a post-rescale
    world re-earns (or re-loses) capture on its own record."""
    try:
        from ..parallel.topology import get_mesh

        mesh = get_mesh()
    except Exception:
        return None
    if mesh is None or mesh.devices.size <= 1:
        return None
    return (tuple(str(a) for a in mesh.axis_names),
            tuple(int(s) for s in mesh.devices.shape))


def _ladder_key(sig):
    try:
        return hash((sig, _mesh_ladder_tag()))
    except TypeError:
        return hash(sig)


def _capture_on() -> bool:
    # FLAGS_check_nan_inf needs the per-flush finite scan, which the
    # captured 1-program replay bypasses — checking runs lazy at 3 programs
    return (
        bool(flags.flag("eager_lazy_dispatch"))
        and bool(flags.flag("eager_step_capture"))
        and not flags.flag("check_nan_inf")
    )


def _mem_plan_on() -> bool:
    # planner-guided remat for the captured step: FLAGS_memory_plan=auto
    # plans against FLAGS_memory_budget_mb (a budget of 0 keeps the linter
    # semantics — nothing to optimize against)
    return (
        str(flags.flag("memory_plan")) == "auto"
        and float(flags.flag("memory_budget_mb")) > 0
    )


def _telemetry_on() -> bool:
    # fused numerics telemetry (paddle.profiler.attribution): changes the
    # traced step/update program (one extra stacked output), so it keys
    # the capture cache exactly like the rescue sentinel
    return bool(flags.flag("telemetry"))


def _observer() -> _Observer:
    obs = getattr(_tls, "observer", None)
    if obs is None:
        obs = _Observer()
        _tls.observer = obs
    return obs


def _observe_event(ev: Tuple):
    if not _capture_on():
        return
    obs = _observer()
    if len(obs.events) < _MAX_OBSERVED_EVENTS:
        obs.events.append(ev)
    else:
        obs.dirty = True


def _observe_op_program():
    # called from dispatch._count_program on every per-op launch; a step
    # containing per-op programs is not capturable as one executable
    obs = getattr(_tls, "observer", None)
    if obs is not None:
        obs.dirty = True


def _capture_fallback(reason: str):
    from . import dispatch

    dispatch._counters["capture_fallbacks"] += 1
    rs = dispatch._counters["capture_fallback_reasons"]
    rs[reason] = rs.get(reason, 0) + 1
    dispatch._emit("capture", site="captured", phase="fallback",
                   reason=reason)


def _opt_fingerprint(opt) -> Optional[Tuple]:
    """Hashable identity of the optimizer part of a step signature: rule
    type + global AND per-param hypers + weight decay + the grad-clip
    fingerprint + the ids of the params that will be updated. Per-param
    overrides (e.g. AdamW's apply_decay_param_fun exclusions) are baked
    into the compiled executable, so they MUST key it — same convention as
    _apply_fused's _jit_update_cache key. lr VALUE is excluded (schedulers
    may vary it per step; it is a traced input of the captured program).

    The clip fingerprint is (type tag, hypers) for the three built-in clip
    configs and ("none",) for no clip — those fold into the captured trace
    as pure functions of the tape grads (nn/clip.py). A CUSTOM clip
    (anything overriding _clip) has semantics the capture cannot reproduce:
    clip_fingerprint returns None and so does this fingerprint, which keeps
    the step on the eager 3-program path.

    Deliberately NOT memoized: per-param overrides can only be validated by
    recomputing them (a memo keyed on anything cheaper replays stale
    hypers), and the per-step cost equals what _apply_fused already pays to
    rebuild per_hypers — work a captured step skips entirely."""
    from ..nn.clip import clip_fingerprint

    clip_fp = clip_fingerprint(getattr(opt, "_grad_clip", None))
    if clip_fp is None:
        return None
    upd = [
        p for p in opt._param_list()
        if not p.stop_gradient and p.grad is not None
    ]
    return (
        type(opt),
        tuple(sorted(opt._hyper().items())),
        tuple(tuple(sorted(opt._per_param_hyper(p).items())) for p in upd),
        opt._weight_decay,
        clip_fp,
        # the Pallas fused-update enablement changes the traced program
        (bool(flags.flag("pallas_fused_update")),
         bool(flags.flag("pallas_update_interpret"))),
        tuple(id(p) for p in upd),
    )


def _step_boundary(opt):
    """Fold this step's observed events into the stability counter; arm the
    controller after FLAGS_eager_capture_warmup consecutive identical
    steady-state steps."""
    obs = _observer()
    events, dirty = obs.events, obs.dirty
    obs.events, obs.dirty = [], False
    opt_fp = None
    k = len(events) // 2
    # a capturable step is PERIODIC: [seg, bwd] repeated k times before this
    # one optimizer.step(). k == 1 is the plain train step; k > 1 is k-step
    # gradient accumulation — all k forward segments share one signature and
    # all k backwards share one tape. Once armed, microsteps 0..k-2 replay
    # as one captured accumulate-only program each and microstep k-1 defers
    # into the full captured update program.
    periodic = (
        not dirty
        and k >= 1
        and len(events) == 2 * k
        and all(
            events[2 * i][0] == "seg" and events[2 * i][1] == events[0][1]
            for i in range(k)
        )
        and all(
            events[2 * i + 1][0] == "bwd" and events[2 * i + 1][1] == events[1][1]
            for i in range(k)
        )
    )
    if periodic:
        try:
            # returns None for custom grad-clip classes — the built-in
            # clips fold into the captured trace as pure functions of the
            # tape grads (nn/clip.py); custom ones keep the eager path
            opt_fp = _opt_fingerprint(opt)
        except Exception:
            opt_fp = None
    if opt_fp is None:
        _disarm(obs)
        return
    sig = (events[0][1], events[1][1], opt_fp, k)
    if sig == obs.prev:
        obs.stable += 1
    else:
        obs.prev, obs.stable = sig, 1
    armed = (
        sig if obs.stable >= int(flags.flag("eager_capture_warmup")) else None
    )
    if armed is not None:
        from . import dispatch

        if not dispatch._resilience_module().runtime.captured_tier_ok(
            _ladder_key(events[0][1])
        ):
            armed = None  # ladder demoted this signature — don't arm
    if armed is not None and obs.armed != armed:
        obs.cycle_len, obs.pos = k, 0
    obs.armed = armed


def step_capture_backward(root) -> bool:
    """run_backward's capture hook. With the controller armed and the
    pending segment + tape matching the armed signature, this backward is
    taken over by the capture machinery; returns True when the caller must
    return without sweeping.

    Plain step (cycle_len == 1) and the LAST microstep of an accumulation
    cycle: the backward is DEFERRED — the whole step resolves at
    optimizer.step() as one donated program. Accumulate-only microsteps
    (pos < cycle_len - 1): forward + backward + grad-accumulate replay HERE
    as one captured program and the grads become concrete immediately."""
    if not _capture_on():
        return False
    obs = getattr(_tls, "observer", None)
    if obs is None or obs.armed is None:
        return False
    if getattr(_tls, "capture_deferred", None) is not None:
        return False  # a second backward this step — flush path aborts it
    from . import dispatch

    seg = getattr(_tls, "segment", None)
    if seg is None or seg.flushed or not seg.ops:
        return False
    rv = root._value
    if type(rv) is not LazyRef or rv._segment is not seg or rv._concrete is not None:
        return False
    if rv.size != 1:
        return False
    seg_sig = _seg_signature(seg)
    if not dispatch._resilience_module().runtime.captured_tier_ok(
        _ladder_key(seg_sig)
    ):
        # degradation ladder demoted this step signature: stay on the
        # 3-program path until the cooldown re-promotes it
        return False
    armed_seg, armed_tape, armed_opt, cycle_len = obs.armed
    if seg_sig != armed_seg:
        _capture_fallback("signature_mismatch")
        _disarm(obs)
        return False
    seg_nodes = {id(op.node) for op in seg.ops if op.record}
    struct = dispatch._tape_structure(
        root, node_check=lambda n: n.vjp_fn is None and id(n) in seg_nodes
    )
    if struct is None:
        _capture_fallback("tape_ineligible")
        _disarm(obs)
        return False
    tape_key, order_nodes, leaves = struct
    if tape_key != armed_tape:
        _capture_fallback("tape_mismatch")
        _disarm(obs)
        return False
    if len(order_nodes) != len(seg_nodes):
        # the segment recorded differentiable ops that are NOT ancestors of
        # the loss (auxiliary outputs): a normal flush would give them vjp
        # closures for a later backward of their own, which the captured
        # replay cannot — keep such steps on the 3-program path
        _capture_fallback("non_tape_recorded_ops")
        _disarm(obs)
        return False
    # every tape leaf must be a distinct concrete external input of the
    # segment. Grad state must match the cycle position: the FIRST backward
    # of a cycle starts from grad=None (run_backward creates fresh grads),
    # later microsteps accumulate into an existing concrete grad — any other
    # mix (stale grads at cycle start, a cleared grad mid-cycle) is a
    # pattern the capture cannot reproduce and falls back.
    pos = obs.pos if cycle_len > 1 else 0
    slots: List[int] = []
    ineligible = None
    for t in leaves:
        v = t._value
        slot = None if type(v) is LazyRef else seg.ext_ids.get(id(v))
        if slot is None:
            ineligible = "leaf_ineligible"
            break
        g = t.grad
        if pos == 0:
            if g is not None:
                ineligible = "leaf_ineligible"
                break
        elif g is None or type(g._value) is LazyRef:
            ineligible = "accum_grad_ineligible"
            break
        slots.append(slot)
    if ineligible is None and len(set(slots)) != len(slots):
        ineligible = "aliased_leaves"
    if ineligible is not None:
        _capture_fallback(ineligible)
        _disarm(obs)
        return False

    if cycle_len > 1 and pos < cycle_len - 1:
        # accumulate-only microstep: replay forward + backward (+ grad
        # accumulate) as ONE captured program right now. Nothing defers; a
        # failure simply returns False and the normal flush + sweep runs.
        return _run_accum_microstep(seg, root, seg_sig, tape_key, leaves,
                                    slots, pos, obs)

    # defer: detach the pending segment (later ops open a fresh one) and
    # hand every leaf a placeholder grad whose read resolves — or aborts —
    # the captured step
    _tls.segment = None
    stub_seg = _Segment()
    rec = _DeferredStep()
    rec.segment = seg
    rec.stub_seg = stub_seg
    rec.root = root
    rec.seg_sig = seg_sig
    rec.tape_key = tape_key
    rec.leaves = leaves
    rec.leaf_slots = slots
    rec.leaf_grads = []
    rec.expected_opt_fp = armed_opt
    rec.grad_prev_vals = None
    if pos > 0:
        # final microstep of an accumulation cycle: the captured update
        # consumes the k-1 partial sums. Keep each leaf's EXISTING grad
        # tensor (eager semantics mutate it in place) but swap its value
        # for a placeholder ref so any read before optimizer.step() aborts;
        # the previous partial sums ride along for the program inputs and
        # for the abort path's restore.
        rec.grad_prev_vals = [t.grad._value for t in leaves]
        for i, t in enumerate(leaves):
            v = t._value
            ref = LazyRef(stub_seg, i, 0, tuple(v.shape), v.dtype)
            gt = t.grad
            gt._value = ref
            rec.leaf_grads.append((t, gt, ref))
    else:
        for i, t in enumerate(leaves):
            v = t._value
            ref = LazyRef(stub_seg, i, 0, tuple(v.shape), v.dtype)
            gt = _new_tensor(ref, stop_gradient=True)
            t.grad = gt
            rec.leaf_grads.append((t, gt, ref))
    _tls.capture_deferred = rec
    return True


def _accum_step_fn(plan, n_ext, leaf_slots, root_op, root_out,
                   seed_shape, seed_dtype, with_grad_in):
    """Raw accumulate-only microstep program: forward replay + whole-program
    vjp over every tape leaf (+ add into the incoming partial grad sums).
    Same gradient contract as the full captured step (_plan_capture_forward
    stop-gradients every non-diff input position), and the accumulate order
    matches the eager sweep exactly: prev + new."""
    fwd = _plan_capture_forward(plan)
    leaf_slot_set = set(leaf_slots)
    rest_slots = [s for s in range(n_ext) if s not in leaf_slot_set]

    def accum_fn(leaf_vals, grad_in, rest_vals):
        ext = [None] * n_ext
        for s, v in zip(rest_slots, rest_vals):
            ext[s] = v

        def loss_of(lv):
            e = list(ext)
            for s, v in zip(leaf_slots, lv):
                e[s] = v
            results = fwd(e)
            return results[root_op][root_out], results

        _loss, vjp, results = jax.vjp(loss_of, tuple(leaf_vals), has_aux=True)
        (g,) = vjp(jnp.ones(seed_shape, seed_dtype))
        if with_grad_in:
            g = tuple(a + b for a, b in zip(grad_in, g))
        return results, tuple(g)

    return accum_fn, rest_slots


def _run_accum_microstep(seg, root, seg_sig, tape_key, leaves, slots, pos,
                         obs) -> bool:
    """Build/replay the captured accumulate-only program for one
    armed microstep; True when it resolved the backward (grads concrete).

    The incoming partial-sum grad buffers are NOT donated: the graceful
    fallback contract (a real fault resolves the microstep on the normal
    flush + sweep path) must still be able to read them — only the k-th
    microstep's update program donates params and optimizer state."""
    from . import dispatch

    with_grad_in = pos > 0
    key = (seg_sig, tape_key, "accum", with_grad_in)
    try:
        entry = dispatch._lru_get(_capture_cache, key)
    except TypeError:
        return False
    rv = root._value
    lkey = _ladder_key(seg_sig)
    akey = f"accum:{_sig_id(seg_sig)}"
    try:
        built_fn = None
        if entry is None:
            accum_fn, rest_slots = _accum_step_fn(
                _seg_plan(seg), len(seg.ext_vals), tuple(slots),
                rv._op_index, rv._out_index, rv._shape, rv._dtype,
                with_grad_in,
            )
            entry = (jax.jit(accum_fn), rest_slots)
            built_fn = accum_fn
            dispatch._counters["capture_accum_builds"] += 1
            dispatch._lru_put(
                _capture_cache, key, entry,
                evict_counter="capture_evictions",
                cap=int(flags.flag("eager_capture_cache_size")),
            )
            fresh = True
        else:
            fresh = False
        jfn, rest_slots = entry
        ext = seg.ext_vals
        args = (
            tuple(ext[s] for s in slots),
            tuple(leaves[i].grad._value for i in range(len(leaves)))
            if with_grad_in else (),
            tuple(ext[s] for s in rest_slots),
        )
        if built_fn is not None:
            # attribution cost registry: the accumulate-only microstep
            # program registers at build time (spec-only thunk; the plan
            # closure pins no user data)
            specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype), args
            )
            _register_program(
                akey, "accum",
                jaxpr_thunk=(lambda _fn=built_fn, _s=specs:
                             jax.make_jaxpr(_fn)(*_s)),
            )
        t0 = time.perf_counter()
        out = dispatch._rexec(
            "captured", lambda: jfn(*args), fresh=fresh, ladder_key=lkey,
        )
        dt = _add_time("compile_time_ms" if fresh else "replay_time_ms", t0)
        if not fresh:
            _note_program(akey, "accum", dt)
    except BaseException as e:
        if not isinstance(e, Exception):
            raise
        # any build/compile/runtime error: counted, then the normal flush +
        # tape-backward path resolves this microstep with identical numerics
        _capture_fallback("accum_error")
        _disarm(obs)
        return False
    results, g_out = out
    dispatch._count_program("captured")
    dispatch._counters["capture_accum_replays"] += 1
    dispatch._emit("capture", site="captured", phase="accum_replay",
                   pos=pos)

    # the captured program subsumes the segment flush (same write-back as
    # _run_captured, minus vjp closures — a second backward raises)
    seg.flushed = True
    if getattr(_tls, "segment", None) is seg:
        _tls.segment = None
    for op, outs in zip(seg.ops, results):
        for (ref, t), val in zip(op.outs, outs):
            ref._concrete = val
            if t._value is ref:
                t._value = val
        if op.record:
            op.node.out_avals = [(tuple(v.shape), v.dtype) for v in outs]
    seg.ops = []
    from .tensor import Tensor

    for t, g in zip(leaves, g_out):
        if with_grad_in:
            # eager parity: the sweep mutates the existing grad tensor in
            # place (t.grad._value = prev + new) — same object identity
            t.grad._value = g
        else:
            t.grad = Tensor(g, stop_gradient=True)
    obs.pos = pos + 1
    return True


def _abort_capture(reason: str, fallback: bool = True):
    """Resolve a deferred captured-step backward on the normal 3-program
    path: flush the segment (which populates the tape's vjp closures), run
    the real backward, and fill the placeholder grads. Numerics match the
    never-captured path exactly; the event is counted as a capture
    fallback and the controller re-observes from scratch.

    `fallback=False` is the async-compile pending resolution: the step
    resolves the same safe way, but it is NOT a capture fallback — the
    controller stays armed so the next occurrence joins the background
    build (counted separately as capture_build_pending_steps)."""
    from . import dispatch

    rec = getattr(_tls, "capture_deferred", None)
    if rec is None:
        return
    _tls.capture_deferred = None
    rec.stub_seg.flushed = True
    obs = getattr(_tls, "observer", None)
    if fallback:
        _capture_fallback(reason)
        if obs is not None:
            _disarm(obs)
            obs.events, obs.dirty = [], False
    elif obs is not None:
        obs.events, obs.dirty = [], False
        obs.pos = 0  # the cycle completed on the 3-program path
    # Reproduce the eager ordering exactly: the backward writes grads FIRST
    # (a fresh tensor for a plain step; in-place accumulation into the
    # restored k-1 partial sum for an accumulation cycle), any later user
    # write/clear of t.grad then replaced it. So: run the sweep over the
    # restored grad state, give the placeholder its computed value (whoever
    # saved p.grad at backward() time sees the real gradient), and put back
    # the user's replacement if there was one.
    saved = [(t, gt, ref, t.grad) for t, gt, ref in rec.leaf_grads]
    if rec.grad_prev_vals is None:
        for t, _gt, _ref, _cur in saved:
            t.grad = None
    else:
        # final accumulation microstep: restore the partial sums so the
        # sweep accumulates into them (t.grad._value = prev + new), exactly
        # what the eager path would have produced
        for (t, gt, _ref, _cur), prev in zip(saved, rec.grad_prev_vals):
            gt._value = prev
            t.grad = gt
    if not rec.segment.flushed:
        _flush(rec.segment, "capture_abort")
    root = rec.root
    seed = jnp.ones_like(materialize(root._value))
    if not dispatch._try_compiled_tape_backward(root, seed):
        dispatch.run_backward([root])
    for t, gt, ref, cur in saved:
        real = t.grad
        val = (
            real._value if real is not None
            else jnp.zeros(ref._shape, ref._dtype)
        )
        ref._concrete = val
        gt._value = val
        # keep the object identity handed out at backward() time, unless
        # the user replaced/cleared t.grad after the deferral
        t.grad = gt if cur is gt else cur


def _plan_capture_forward(plan, stop_gradients=True):
    """Pure replay of a segment plan for whole-step capture.

    The tape's gradient contract is reproduced structurally: gradient flows
    ONLY through recorded ops' differentiable input positions (exactly the
    positions the per-op path takes jax.vjp over); every other array input
    is wrapped in lax.stop_gradient, so jax.vjp over this whole replay
    equals the composition of the per-op vjps the tape would have applied.

    ``stop_gradients=False`` replays the same plan WITHOUT the gradient
    shaping — value-level identical (stop_gradient is an identity on
    values), used as program 1 of the 3-program reference composition the
    equivalence prover certifies the capture against."""

    def fwd(ext):
        results = []
        for fn, kw, bindings, diff_idx, record in plan:
            vals = []
            for j, (kind, a, b) in enumerate(bindings):
                if kind == _EXT:
                    v = ext[a]
                elif kind == _RES:
                    v = results[a][b]
                else:
                    vals.append(a)  # python literal — no gradient path
                    continue
                if stop_gradients and (not record or j not in diff_idx):
                    v = jax.lax.stop_gradient(v)
                vals.append(v)
            out = fn(*vals, **kw)
            results.append(list(out) if isinstance(out, (tuple, list)) else [out])
        return results

    return fwd


def _build_captured_step(rec: _DeferredStep, opt) -> _CaptureEntry:
    """Trace + jit the whole step — forward plan, loss vjp, grad clip,
    optimizer update — as ONE program with params and optimizer state
    donated."""
    from ..nn.clip import capture_clip_fn

    seg = rec.segment
    leaves = rec.leaves
    clip = getattr(opt, "_grad_clip", None)
    clip_fn = capture_clip_fn(clip)
    if clip is not None and clip_fn is None:
        # custom clip subclass: semantics the pure fold cannot cover
        raise _CaptureIneligible("grad_clip_custom")
    leaf_pos = {id(t): i for i, t in enumerate(leaves)}
    params = [
        p for p in opt._param_list()
        if not p.stop_gradient and p.grad is not None
    ]
    for p in params:
        if id(p) not in leaf_pos:
            # a param carries a grad the deferred tape did not produce
            # (stale grad from an earlier step): updating it from inside
            # the capture would diverge from the eager path
            raise _CaptureIneligible("stale_or_external_grad")
    param_idx = [leaf_pos[id(p)] for p in params]
    pset = set(param_idx)
    extra_idx = [i for i in range(len(leaves)) if i not in pset]
    param_slots = [rec.leaf_slots[i] for i in param_idx]
    extra_slots = [rec.leaf_slots[i] for i in extra_idx]
    n_ext = len(seg.ext_vals)
    leaf_slot_set = set(param_slots) | set(extra_slots)
    rest_slots = [s for s in range(n_ext) if s not in leaf_slot_set]

    plan = _seg_plan(seg)
    fwd = _plan_capture_forward(plan)
    rv = rec.root._value
    root_op, root_out = rv._op_index, rv._out_index
    seed_shape, seed_dtype = rv._shape, rv._dtype

    # the ONE shared definition of the traced optimizer math — identical to
    # what Optimizer._apply_fused jits, so captured and 3-program steps
    # cannot drift apart (it pins no optimizer instance)
    from ..optimizer.optimizer import make_fused_update
    from ..resilience import rescue as _rescue

    rescue_on = _rescue.active()
    tele_on = _telemetry_on()
    apply_update = make_fused_update(opt, params, sentinel=rescue_on,
                                     telemetry=tele_on)
    has_grad_in = rec.grad_prev_vals is not None

    def make_step_fn(planned_loss=None):
        def step_fn(p_vals, sts, lr, extra_vals, rest_vals, gp_in, gx_in):
            ext = [None] * n_ext
            for s, v in zip(rest_slots, rest_vals):
                ext[s] = v

            if planned_loss is not None:
                # planner-guided remat: the loss path replays as the sliced
                # jax.checkpoint stages the RematPlan chose (same eqns, same
                # order — bitwise-equal values, recomputed in the backward)
                def loss_of(dp, dx):
                    return planned_loss(dp, dx, tuple(rest_vals))
            else:
                def loss_of(dp, dx):
                    e = list(ext)
                    for s, v in zip(param_slots, dp):
                        e[s] = v
                    for s, v in zip(extra_slots, dx):
                        e[s] = v
                    results = fwd(e)
                    return results[root_op][root_out], results

            loss_val, vjp, results = jax.vjp(
                loss_of, tuple(p_vals), tuple(extra_vals), has_aux=True
            )
            del loss_val  # the loss is results[root_op][root_out]
            gp, gx = vjp(jnp.ones(seed_shape, seed_dtype))
            if has_grad_in:
                # accumulation: fold this microstep's grads into the k-1-step
                # partial sums, prev + new — the eager sweep's accumulate order
                gp = tuple(a + b for a, b in zip(gp_in, gp))
                gx = tuple(a + b for a, b in zip(gx_in, gx))
            # grad clipping (built-in configs only): the SAME pure function the
            # eager Optimizer.step() applies between backward and the fused
            # update (nn/clip.py _pure), over the param grads in param-list
            # order — global-norm reduction order and all. The update (and the
            # non-finite sentinel, when on) sees the CLIPPED grads; the grads
            # written back to p.grad stay unclipped, exactly like the eager
            # path, which never writes the clipped values back.
            upd_g = tuple(clip_fn(list(gp))) if clip_fn is not None else gp
            # numeric-rescue sentinel and fused telemetry (paddle.resilience /
            # paddle.profiler.attribution): extra OUTPUTS of the SAME program —
            # the sentinel scalar where-gates the update in-program, the
            # telemetry vector stacks per-param grad/param/update norms — so
            # both add zero program launches and never perturb the update math
            upd = apply_update(p_vals, upd_g, lr, sts)
            new_p, new_s = upd[0], upd[1]
            return (results, gp, gx, tuple(new_p), tuple(new_s)) + tuple(upd[2:])

        return step_fn

    # the 3-program reference composition (FLAGS_check_programs=2): what the
    # lazy tier would have executed, assembled from INDEPENDENT builds of the
    # same three programs — (1) the segment flush's forward (the plan replay
    # with no gradient shaping), (2) the tape backward (jax.vjp over the
    # stop_gradient-shaped replay — the per-op-vjp composition contract
    # documented on _plan_capture_forward), (3) the same grad-clip fold and
    # fused optimizer update Optimizer.step() jits. The equivalence prover
    # certifies the captured 1-program step against this BEFORE the first
    # donated replay; never compiled, only traced.
    ref_fwd_plain = _plan_capture_forward(plan, stop_gradients=False)
    ref_clip_fn = capture_clip_fn(clip)
    ref_apply = make_fused_update(opt, params, sentinel=rescue_on,
                                  telemetry=tele_on)

    def ref_step_fn(p_vals, sts, lr, extra_vals, rest_vals, gp_in, gx_in):
        ext = [None] * n_ext
        for s, v in zip(rest_slots, rest_vals):
            ext[s] = v
        e1 = list(ext)
        for s, v in zip(param_slots, p_vals):
            e1[s] = v
        for s, v in zip(extra_slots, extra_vals):
            e1[s] = v
        results = ref_fwd_plain(e1)  # program 1: the flush's forward

        def loss_of(dp, dx):
            e = list(ext)
            for s, v in zip(param_slots, dp):
                e[s] = v
            for s, v in zip(extra_slots, dx):
                e[s] = v
            return fwd(e)[root_op][root_out]

        _loss, vjp = jax.vjp(loss_of, tuple(p_vals), tuple(extra_vals))
        gp, gx = vjp(jnp.ones(seed_shape, seed_dtype))  # program 2: backward
        if has_grad_in:
            gp = tuple(a + b for a, b in zip(gp_in, gp))
            gx = tuple(a + b for a, b in zip(gx_in, gx))
        upd_g = tuple(ref_clip_fn(list(gp))) if ref_clip_fn is not None else gp
        upd = ref_apply(p_vals, upd_g, lr, sts)  # program 3: fused update
        return (results, gp, gx, tuple(upd[0]), tuple(upd[1])) + tuple(upd[2:])

    entry = _CaptureEntry()
    entry.ref_fn = ref_step_fn
    entry.certificate = None
    entry.rescue = rescue_on
    entry.telemetry = tele_on
    # donate params + optimizer state: XLA reuses their HBM buffers for the
    # updated values (the compile_train_step discipline, earned by plain
    # eager code). Batch data / extra leaves are NOT donated — they are
    # caller-owned and reused across steps. FLAGS_eager_capture_donate=0
    # opts out (keeps the 1-program step, drops in-place reuse) for code
    # that holds aliases of param/state buffers across steps.
    donate = (0, 1) if flags.flag("eager_capture_donate") else ()
    entry.arg_specs = None  # recorded at first replay (sharded: at build)
    entry.donated = bool(donate)
    entry.param_idx = param_idx
    entry.extra_idx = extra_idx
    entry.param_slots = param_slots
    entry.extra_slots = extra_slots
    entry.rest_slots = rest_slots
    entry.warmed = False
    entry.pending = None
    entry.mem_plan = None
    entry.mesh = None
    entry.in_specs = None
    entry.verdicts = None

    # mesh-aware capture (FLAGS_eager_capture_sharded): params carrying
    # multi-device NamedShardings get the whole step jitted as the same one
    # SPMD program ShardedTrainStep compiles — declared in/out shardings
    # from parallel.sharding param/state specs, donation gated on the
    # per-shard proof below
    mesh = _capture_mesh(rec)
    in_shardings = out_shardings = None
    if mesh is not None:
        if _mesh_axes(mesh).get("pp", 1) > 1:
            # the pipeline schedule is a shard_map region with its own step
            # builder (PipelinedTrainStep): refuse structurally
            raise _CaptureIneligible("pipelined_mesh")
        entry.mesh = mesh
        cap_p, cap_s, cargs = _capture_args(rec, opt, entry)
        entry.arg_specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype), cargs)
        from ..parallel.sharding import capture_step_shardings

        p_sh, st_sh = capture_step_shardings(cap_p, cap_s, mesh)
        # lr / batch / rest / grad-in positions stay unconstrained (None):
        # a committed dp-sharded batch keeps its layout, an uncommitted one
        # stays free — the same caller-placed-batch contract as
        # ShardedTrainStep, so matched specs give bitwise-equal reductions
        in_shardings = (tuple(p_sh), tuple(st_sh)) + (None,) * 5
        # updated params/state pinned to the INPUT layout: donation aliases
        # per-shard and the next replay's spec fingerprint is stable. The
        # param grads gp are pinned to the param shardings too — jit's
        # donation aliasing greedily pairs donated inputs with ANY
        # same-logical-shape output, and an unpinned gp whose propagated
        # layout differs from the param's fails the XLA per-shard aliasing
        # size check at runtime
        out_shardings = (
            (None, tuple(p_sh), None, tuple(p_sh), tuple(st_sh))
            + (None,) * (int(rescue_on) + int(tele_on)))
        flat_sh = jax.tree_util.tree_leaves((tuple(p_sh), tuple(st_sh)))
        n_flat = len(jax.tree_util.tree_leaves(entry.arg_specs))
        entry.in_specs = ([s.spec for s in flat_sh]
                          + [None] * (n_flat - len(flat_sh)))

    planned_loss = None
    if _mem_plan_on():
        # planner-guided remat (FLAGS_memory_plan=auto): slice this step's
        # loss replay into jax.checkpoint stages chosen against
        # FLAGS_memory_budget_mb. Every op output of the capture escapes to
        # the host write-back (the _flush contract), so the planner usually
        # proves there is nothing profitable to cut and returns an identity
        # plan — honesty over wishful savings. An unreachable budget is an
        # identity plan, not an exception: a planner that RAISES is an
        # internal error and surfaces as one (counted, then re-raised),
        # never as a quiet "ineligible".
        try:
            entry.mem_plan, planned_loss = _build_capture_plan(
                rec, opt, entry, make_step_fn, fwd,
                n_ext, param_slots, extra_slots, rest_slots,
                root_op, root_out)
        except Exception as e:
            from ..analysis import plan as _plan_mod

            _plan_mod.record_failure("capture", e)
            raise
    step_fn = make_step_fn(planned_loss)
    entry.step_fn = step_fn
    if mesh is not None and donate:
        # per-shard donation gate: donation stays on ONLY when the
        # analysis.sharding donation_safety pass proves EVERY donated flat
        # position at per-shard shapes; anything unproven demotes this
        # build to non-donated replay — a counted reason
        # (capture_donation_fallbacks), not a capture fallback: the step
        # still replays as 1 program, only in-place reuse is given up
        donate = _prove_sharded_donation(entry, mesh, donate)
        entry.donated = bool(donate)
    if mesh is not None:
        if donate:
            # jax 0.4.x donation sharp edge: the donation matcher compares a
            # donated input's PER-SHARD shape against an unpinned output's
            # GLOBAL shape, so e.g. a [16,4] weight sharded to [8,4] aliases
            # a [8,4] logits output and XLA's runtime per-shard size check
            # then faults the replay. Pin EVERY output before donating:
            # probe-compile non-donated (propagation chooses the unpinned
            # outputs' layouts), then rebuild with the inferred shardings —
            # the second compile propagates identically, aliasing now pairs
            # per-shard against per-shard
            probe = jax.jit(
                step_fn, in_shardings=in_shardings,
                out_shardings=out_shardings,
            ).lower(*entry.arg_specs).compile()
            out_shardings = probe.output_shardings
        entry.exe = jax.jit(step_fn, in_shardings=in_shardings,
                            out_shardings=out_shardings,
                            donate_argnums=donate)
    else:
        entry.exe = jax.jit(step_fn, donate_argnums=donate)
    return entry


def _prove_sharded_donation(entry: _CaptureEntry, mesh, donate):
    """Build-time per-shard donation proof of a mesh-aware capture: trace
    the candidate step (no compile), run the analysis.sharding
    donation_safety pass over the _ShardInliner-derived context, and keep
    ``donate`` only when every donated position's verdict is proven. The
    verdicts land on the entry for graph_lint / statusz. "Unproven" is a
    VERDICT the pass returns — donation is a proof-carrying optimization
    here, never a default; an exception from the trace or the analysis is
    an internal error and propagates."""
    from . import dispatch
    from ..analysis import memory as _amem
    from ..analysis import sharding as _ashard

    roles, donated_idx = _capture_arg_roles(entry)
    closed = jax.make_jaxpr(entry.step_fn)(*entry.arg_specs)
    ctx = _ashard.shard_context(
        closed, roles, mesh=mesh, in_specs=entry.in_specs,
        donated=donated_idx, source="captured-sharded")
    entry.verdicts = _amem.donation_verdicts(ctx)
    proven = bool(entry.verdicts) and all(
        v["proven"] for v in entry.verdicts)
    if proven:
        return donate
    dispatch._counters["capture_donation_fallbacks"] += 1
    dispatch._emit("capture", site="captured", phase="donation_fallback",
                   mesh=_mesh_tag(mesh))
    return ()


def _build_capture_plan(rec, opt, entry, make_step_fn, fwd, n_ext,
                        param_slots, extra_slots, rest_slots,
                        root_op, root_out):
    """Build (and maybe bind) a RematPlan for one capture build. Returns
    ``(plan, planned_loss)`` where planned_loss is None when the plan has no
    cuts. The measure oracle re-traces the FULL candidate step (forward,
    vjp, clip, fused update, donation) and reads the planner's peak — the
    recorded before/after figures are exact est_peak_hbm_mb values, not a
    side model."""
    from .. import analysis
    from ..analysis import memory as _memory
    from ..analysis import plan as _plan_mod

    _p, _s, cargs = _capture_args(rec, opt, entry)
    specs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype), cargs)
    entry.arg_specs = specs
    p_specs, _s_specs, _lr, extra_specs, rest_specs, _gp, _gx = specs
    res_tree = [None]

    def loss_pure(dp, dx, rest_vals):
        # the capture's loss path with every array input explicit, flat
        # outputs (loss first, then every op output — they all escape)
        ext = [None] * n_ext
        for s, v in zip(rest_slots, rest_vals):
            ext[s] = v
        for s, v in zip(param_slots, dp):
            ext[s] = v
        for s, v in zip(extra_slots, dx):
            ext[s] = v
        results = fwd(ext)
        flat, tree = jax.tree_util.tree_flatten(results)
        res_tree[0] = tree
        return (results[root_op][root_out], *flat)

    loss_closed = jax.make_jaxpr(loss_pure)(
        tuple(p_specs), tuple(extra_specs), tuple(rest_specs))

    def bind_loss(flat_fn):
        def planned_loss(dp, dx, rest_vals):
            flat, _ = jax.tree_util.tree_flatten(
                (tuple(dp), tuple(dx), tuple(rest_vals)))
            outs = flat_fn(*flat)
            results = jax.tree_util.tree_unflatten(res_tree[0], outs[1:])
            return outs[0], results
        return planned_loss

    roles, donated = _capture_arg_roles(entry)

    def measure(flat_fn):
        pl = bind_loss(flat_fn) if flat_fn is not None else None
        closed = jax.make_jaxpr(make_step_fn(pl))(*specs)
        if entry.mesh is not None:
            # mesh-aware capture: the plan is chosen against PER-DEVICE
            # peak — the _ShardInliner-derived context sizes every buffer
            # at its shard shape, so FLAGS_memory_budget_mb means one
            # chip's HBM on a mesh, not the global footprint
            from ..analysis.sharding import shard_context

            ctx = shard_context(closed, roles, mesh=entry.mesh,
                                in_specs=entry.in_specs, donated=donated,
                                source="captured-step")
        else:
            ctx = analysis.Context(closed, roles, "captured-step",
                                   donated=donated)
        return _memory.plan_memory(ctx).peak_bytes

    budget = int(float(flags.flag("memory_budget_mb")) * (1 << 20))
    plan = _plan_mod.build_remat_plan(
        loss_closed, budget_bytes=budget, measure=measure, source="capture")
    if plan.has_cuts:
        return plan, bind_loss(plan.bind())
    return plan, None


def _aot_compile(exe, specs):
    """Background-thread half of an async capture build: trace + XLA-compile
    the jitted step over abstract avals (jax AOT). Returns the Compiled
    executable; donation is part of the lowering, so the later replay on the
    main thread consumes its buffers exactly like a plain jit call."""
    import warnings

    with warnings.catch_warnings():
        # backends without real donation (CPU) warn at compile time
        warnings.filterwarnings("ignore", message=".*onated buffer.*")
        return exe.lower(*specs).compile()


def _capture_args(rec: _DeferredStep, opt, entry: _CaptureEntry):
    """The concrete argument tuple of one captured-step replay (also used at
    async-build submission time to derive the AOT lowering avals)."""
    seg = rec.segment
    leaves = rec.leaves
    params = [leaves[i] for i in entry.param_idx]
    ext = seg.ext_vals
    sched = getattr(opt, "_offload_sched", None)
    if sched is not None:
        # host-offload: parked accumulator groups must be device arrays
        # before they feed the captured executable (the wait is booked as
        # the scheduler's blocked time)
        sched.ensure_resident(opt, params)
    states = []
    for p in params:
        st = opt._accumulators.get(id(p))
        if st is None:
            st = opt._create_state(p)
        states.append(st)
    lr = jnp.asarray(opt.get_lr(), dtype=jnp.float32)
    if rec.grad_prev_vals is None:
        gp_in, gx_in = (), ()
    else:
        gp_in = tuple(rec.grad_prev_vals[i] for i in entry.param_idx)
        gx_in = tuple(rec.grad_prev_vals[i] for i in entry.extra_idx)
    return params, states, (
        tuple(ext[s] for s in entry.param_slots),
        tuple(states),
        lr,
        tuple(ext[s] for s in entry.extra_slots),
        tuple(ext[s] for s in entry.rest_slots),
        gp_in,
        gx_in,
    )


def _capture_arg_roles(entry: _CaptureEntry):
    """(invar roles, donated flat invar indices) of the captured step
    program traced from entry.arg_specs — donate_argnums=(0, 1) donates the
    leaves of the param and optimizer-state pytrees, which flatten first."""
    leaves = jax.tree_util.tree_leaves
    p_specs, s_specs, _lr, extra, rest, gp_in, gx_in = entry.arg_specs
    n_p, n_s = len(leaves(p_specs)), len(leaves(s_specs))
    roles = (
        [("param", f"param{i}") for i in range(n_p)]
        + [("buffer", f"opt_state{i}") for i in range(n_s)]
        + [("arg", "lr")]
        + [("feed", f"batch{i}") for i in range(len(leaves(extra)))]
        + [("arg", f"ext{i}") for i in range(len(leaves(rest)))]
        + [("arg", f"grad_in{i}")
           for i in range(len(leaves(gp_in)) + len(leaves(gx_in)))]
    )
    donated = tuple(range(n_p + n_s)) if entry.donated else ()
    return roles, donated


def captured_step_program():
    """(closed jaxpr, donated invar indices, invar roles) of the most
    recently replayed captured whole-step executable on this thread, or
    None when no capture has replayed yet (or its cache entry has been
    evicted and collected). Trace-only (no compile) — feeds the
    paddle_tpu.analysis.memory planner and
    paddle.profiler.measure_programs."""
    ref = getattr(_tls, "last_capture_entry", None)
    entry = ref() if ref is not None else None
    if entry is None or entry.arg_specs is None:
        return None
    closed = jax.make_jaxpr(entry.step_fn)(*entry.arg_specs)
    roles, donated = _capture_arg_roles(entry)
    return closed, donated, roles


def captured_step_shard_info():
    """``(mesh, flat per-invar PartitionSpecs, mesh axes dict)`` of the most
    recently replayed SHARDED captured step on this thread, or None (no
    sharded replay yet, or the cache entry was evicted and collected).
    Pairs with :func:`captured_step_program` —
    ``analysis.sharding.captured_step_context`` rebuilds the per-shard
    analyzer context from the two."""
    ref = getattr(_tls, "last_capture_entry", None)
    entry = ref() if ref is not None else None
    if entry is None or entry.mesh is None or entry.arg_specs is None:
        return None
    return entry.mesh, list(entry.in_specs or []), _mesh_axes(entry.mesh)


def captured_step_donation_verdicts():
    """Per-position donation_safety verdicts recorded at the last replayed
    capture's build (``analysis.memory.donation_verdicts`` records —
    position / role / proven / diagnostics), or None when the last replay
    was single-chip or nothing has replayed. ``graph_lint --mesh`` prints
    these per position in its JSON record."""
    ref = getattr(_tls, "last_capture_entry", None)
    entry = ref() if ref is not None else None
    return None if entry is None else entry.verdicts


class _CapturedStepHandle:
    """Routable stand-in for this thread's last replayed captured step:
    ``graph_lint --mesh`` and ``analysis.sharding.check_sharded_step``
    dispatch on ``_captured_step`` and rebuild the per-shard context from
    the capture registry — the handle itself pins nothing."""

    _captured_step = True


def captured_step_handle() -> _CapturedStepHandle:
    return _CapturedStepHandle()


def _check_captured_donation(entry: _CaptureEntry, params, states):
    # the static traced-program pass runs once per capture build (warmed is
    # set only after a successful replay, so a raising verdict re-proves)
    from ..analysis import memory as _memory

    roles, donated = _capture_arg_roles(entry)
    _memory.donation_gate(
        params, states,
        lambda: jax.make_jaxpr(entry.step_fn)(*entry.arg_specs),
        roles, donated, "captured-step",
        static_diags=[] if entry.warmed else None,
    )


def _certify_capture_equivalence(entry: _CaptureEntry):
    """FLAGS_check_programs=2 parity proof: structurally certify the
    captured 1-program step ≡ the 3-program composition (and, sharded, the
    donated executable's program against its non-donated probe trace — the
    same step_fn, so the one certificate covers both) BEFORE the first
    donated replay. Outcomes:

      certified  — counted; the certificate lands on the entry (statusz)
      divergent  — ProgramVerificationError with the structured
                   first-divergence diagnostic; the caller resolves the
                   step on the safe 3-program path, then surfaces it
      unprovable — a tracing/canonicalization failure is NOT a proof of
                   divergence: fall through the counted ladder
                   (_CaptureIneligible) instead of crashing the step
    """
    from . import dispatch
    from ..analysis import ProgramVerificationError
    from ..analysis import equivalence as _eq

    dispatch._counters["capture_equivalence_checks"] += 1
    try:
        cap = jax.make_jaxpr(entry.step_fn)(*entry.arg_specs)
        ref = jax.make_jaxpr(entry.ref_fn)(*entry.arg_specs)
        cert = _eq.prove_equivalent(
            cap, ref, label_a="captured-step",
            label_b="3-program-composition", source="captured-step")
    except Exception as e:
        dispatch._counters["capture_equivalence_unprovable"] += 1
        dispatch._emit("capture", site="captured", phase="equivalence",
                       result="unprovable", error=type(e).__name__)
        raise _CaptureIneligible("equivalence_unprovable")
    entry.certificate = cert
    if not cert.equivalent:
        dispatch._counters["capture_equivalence_divergences"] += 1
        dispatch._emit("capture", site="captured", phase="equivalence",
                       result="divergent", mesh=_mesh_tag(entry.mesh))
        raise ProgramVerificationError(
            "captured step is not provably equivalent to the 3-program "
            f"composition: {cert.summary()}",
            [d for d in [cert.divergence] if d is not None])
    dispatch._counters["capture_equivalence_certified"] += 1
    dispatch._emit("capture", site="captured", phase="equivalence",
                   result="certified", mesh=_mesh_tag(entry.mesh),
                   ops=cert.n_ops[0], outputs=cert.outputs_compared)


def captured_step_certificate():
    """The EquivalenceCertificate of the calling thread's last captured
    step, or None (no capture, or FLAGS_check_programs<2 at build)."""
    ref = getattr(_tls, "last_capture_entry", None)
    entry = ref() if ref is not None else None
    return entry.certificate if entry is not None else None


def _run_captured(rec: _DeferredStep, opt, entry: _CaptureEntry) -> bool:
    from . import dispatch

    seg = rec.segment
    leaves = rec.leaves
    ext = seg.ext_vals
    for i, s in zip(entry.param_idx, entry.param_slots):
        if leaves[i]._value is not ext[s]:
            raise _CaptureIneligible("param_rebound")
    for t, gt, ref in rec.leaf_grads:
        if t.grad is not gt or gt._value is not ref:
            # the user wrote/cleared a .grad between backward() and step():
            # the eager path would feed THAT value to the update — abort so
            # the normal path does exactly that
            raise _CaptureIneligible("grad_replaced")
    params, states, args = _capture_args(rec, opt, entry)
    if entry.arg_specs is None:
        entry.arg_specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype), args
        )
    if entry.donated and int(flags.flag("check_programs")):
        # donation-safety gate (analysis.memory): statically verify the
        # captured program's donated positions and gc-scan the to-be-donated
        # buffers for live external Tensor aliases (state_dict()/detach()
        # held across steps) BEFORE XLA invalidates them. Raises
        # ProgramVerificationError at FLAGS_check_programs>=2 — the caller
        # resolves the deferred step on the safe 3-program path first.
        _check_captured_donation(entry, params, states)
    if not entry.warmed and int(flags.flag("check_programs")) >= 2 \
            and entry.ref_fn is not None:
        # proof-carrying parity: certify captured ≡ 3-program composition
        # before anything is donated or replayed
        _certify_capture_equivalence(entry)
    lkey = _ladder_key(rec.seg_sig)
    # with donation on, a REAL fault from inside exe may fire after XLA
    # consumed the param/state buffers — replaying the same args would feed
    # deleted buffers, so such faults skip in-place retry and resolve via
    # the 3-program fallback (injected faults raise pre-launch and retry)
    unsafe = entry.donated
    tag = _mesh_tag(entry.mesh)
    ckey = f"captured:{_sig_id(rec.seg_sig)}" + (f"@{tag}" if tag else "")
    t0 = time.perf_counter()
    if entry.warmed:
        out = dispatch._rexec(
            "captured", lambda: entry.exe(*args), ladder_key=lkey,
            retry_unsafe=unsafe,
        )
        _note_program(ckey, "captured", _add_time("replay_time_ms", t0))
    else:
        import warnings

        def _first_run():
            with warnings.catch_warnings():
                # first call compiles (unless the async pipeline already
                # AOT-compiled it off-thread); backends without real buffer
                # donation (CPU) warn that donated buffers were unused —
                # benign here
                warnings.filterwarnings("ignore", message=".*onated buffer.*")
                return entry.exe(*args)

        out = dispatch._rexec("captured", _first_run, fresh=True,
                              ladder_key=lkey, retry_unsafe=unsafe)
        _add_time("compile_time_ms", t0)
        entry.warmed = True
        # attribution cost registry: the captured step registers its
        # static profile at build time. Weak thunks (the registry must
        # never outlive the capture cache — same discipline as
        # captured_step_program): the jaxpr trace and the XLA
        # cost_analysis both run lazily at the first program_costs read.
        import weakref as _weakref

        eref = _weakref.ref(entry)

        def _cap_jaxpr(_r=eref):
            e = _r()
            if e is None or e.arg_specs is None:
                return None
            return jax.make_jaxpr(e.step_fn)(*e.arg_specs)

        def _cap_cost(_r=eref):
            e = _r()
            if e is None or e.arg_specs is None:
                return None
            ca = getattr(e.exe, "cost_analysis", None)
            if ca is not None:
                try:
                    return ca()
                except Exception:
                    pass
            try:
                return e.exe.lower(*e.arg_specs).cost_analysis()
            except Exception:
                return None

        _roles, _donated = _capture_arg_roles(entry)
        _register_program(ckey, "captured", jaxpr_thunk=_cap_jaxpr,
                          cost_thunk=_cap_cost, donated=len(_donated))
    results, gp, gx, new_p, new_s = out[:5]
    _extra = list(out[5:])
    bad = _extra.pop(0) if entry.rescue else None
    tele = _extra.pop(0) if entry.telemetry else None

    _tls.capture_deferred = None
    rec.stub_seg.flushed = True
    # captured_step_program() surface: a WEAK ref, so the introspection
    # hook never outlives the capture cache (the step fn closes over the
    # plan and optimizer math — pinning it would keep a dropped model's
    # buffers reachable for the thread's lifetime)
    import weakref

    _tls.last_capture_entry = weakref.ref(entry)
    dispatch._count_program("captured")
    dispatch._counters["capture_replays"] += 1
    if entry.mesh is not None:
        dispatch._counters["capture_sharded_replays"] += 1
    # per-host capture tier for /statusz + fleet obs: what the LAST replay
    # on this thread actually ran as
    _tls.capture_tier = {
        "tier": "captured-sharded" if entry.mesh is not None else "captured",
        "mesh": tag,
        "donated": bool(entry.donated),
    }
    dispatch._emit("capture", site="captured", phase="replay",
                   donated=entry.donated, mesh=tag)

    # the captured program subsumes the segment flush: write every op
    # output back exactly like _flush does (minus the vjp closures, which
    # the capture consumed — a second backward raises, same as always)
    seg.flushed = True
    for op, outs in zip(seg.ops, results):
        for (ref, t), val in zip(op.outs, outs):
            ref._concrete = val
            if t._value is ref:
                t._value = val
        if op.record:
            op.node.out_avals = [(tuple(v.shape), v.dtype) for v in outs]
    seg.ops = []
    # donated param buffers are dead: drop the segment's references
    seg.ext_vals = []
    seg.ext_ids = {}

    for i, g in zip(list(entry.param_idx) + list(entry.extra_idx),
                    list(gp) + list(gx)):
        t, gt, ref = rec.leaf_grads[i]
        ref._concrete = g
        gt._value = g
    for p, v, ns in zip(params, new_p, new_s):
        p._value = v
        opt._accumulators[id(p)] = ns
    obs = getattr(_tls, "observer", None)
    if obs is not None:
        obs.events, obs.dirty = [], False  # stays armed for the next step
        obs.pos = 0  # an accumulation cycle completed; next one starts fresh
    if tele is not None:
        # fused telemetry host-read BEFORE the rescue policy runs, so a
        # rescue postmortem's tail already carries the spike event
        try:
            from ..profiler import attribution as _attribution

            _attribution.record_telemetry(
                _attribution.group_names(params), tele)
        except Exception:
            pass
    if bad is not None:
        from ..resilience import rescue as _rescue

        # host-reads the fused sentinel and applies the configured policy
        # (skip already happened in-program; lr_backoff/abort act here)
        _rescue.handle_sentinel(opt, bad)
    return True


def step_capture_step(optimizer) -> bool:
    """Optimizer.step() entry hook — the capture controller's step boundary.

    With no deferred backward pending this is the ordinary lazy-dispatch
    materialization point (flush, reason 'optimizer_step') plus signature
    observation. With a deferred backward pending, the whole step replays
    (or first compiles) as ONE donated XLA program and True is returned so
    Optimizer.step() skips the per-part path; any mismatch aborts to the
    normal path and returns False."""
    rec = getattr(_tls, "capture_deferred", None)
    if rec is None:
        flush_if_pending("optimizer_step")
        if _capture_on():
            _step_boundary(optimizer)
        return False

    def fallback(reason: str) -> bool:
        _abort_capture(reason)
        flush_if_pending("optimizer_step")
        return False

    if not _capture_on():
        # the flag was turned off between backward() and step(): honor it —
        # the deferred step resolves on the normal path, nothing is donated
        return fallback("capture_disabled")
    from ..resilience import faults as _faults

    plan = _faults.active_plan()
    if plan is not None and plan.would_fire(
        "nan", "grads", _faults.current_step()
    ):
        # nan:grads poisons a MATERIALIZED gradient, which the captured
        # 1-program replay never produces — resolve this step on the
        # 3-program path so the injection (and its in-program rescue)
        # actually fire instead of passing vacuously
        return fallback("nan_injected")
    from . import dispatch

    try:
        opt_fp = _opt_fingerprint(optimizer)
    except Exception:
        opt_fp = None
    if opt_fp is None or opt_fp != rec.expected_opt_fp:
        return fallback("optimizer_mismatch")
    from ..resilience import rescue as _rescue

    key = (rec.seg_sig, rec.tape_key, opt_fp,
           bool(flags.flag("eager_capture_donate")),
           rec.grad_prev_vals is not None,  # accumulation: grad-in program
           _rescue.active(),  # the sentinel changes the traced program
           _telemetry_on(),  # ... and so does the fused telemetry vector
           # planner-guided remat: the plan derives deterministically from
           # (signature, budget), so mode + budget fingerprint the plan
           # into the step key — a budget change recompiles, not replays
           (str(flags.flag("memory_plan")), float(flags.flag("memory_budget_mb")))
           if _mem_plan_on() else None,
           # mesh/spec fingerprint (mesh-aware capture): a respec or
           # topology change compiles a fresh executable; None single-chip
           _mesh_fingerprint(_capture_mesh(rec), rec))
    try:
        entry = dispatch._lru_get(_capture_cache, key)
    except TypeError:
        # unhashable step key (exotic custom-optimizer hypers) — the step
        # is not cacheable as a capture; run it on the normal path
        return fallback("unhashable_key")
    try:
        if entry is None:
            def _build_and_submit():
                # trace-free build (jax.jit is lazy); with the async
                # pipeline on, the expensive trace + XLA compile moves to
                # the background thread as an AOT lower().compile() over
                # the arg avals — real buffers never cross the thread
                # boundary, so donation stays a replay-time-only effect
                e = _build_captured_step(rec, optimizer)
                if not _async.enabled():
                    return e, None
                _p, _s, cargs = _capture_args(rec, optimizer, e)
                e.arg_specs = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype),
                    cargs,
                )
                exe, specs = e.exe, e.arg_specs
                fut = _async.submit(lambda: _aot_compile(exe, specs))
                e.pending = fut  # None when the queue is saturated
                return e, fut

            entry, fut = dispatch._rexec(
                "captured", _build_and_submit,
                fresh=True, ladder_key=_ladder_key(rec.seg_sig),
            )
            dispatch._counters["capture_builds"] += 1
            if entry.mesh is not None:
                dispatch._counters["capture_sharded_builds"] += 1
            dispatch._emit("capture", site="captured", phase="build",
                           background=fut is not None,
                           mesh=_mesh_tag(entry.mesh))
            dispatch._lru_put(
                _capture_cache, key, entry,
                evict_counter="capture_evictions",
                cap=int(flags.flag("eager_capture_cache_size")),
            )
            if fut is not None:
                # resolve THIS step on the 3-program path while the
                # executable compiles off-thread — not a capture fallback:
                # the controller stays armed and the next occurrence of
                # this signature joins the finished compile
                dispatch._counters["capture_async_builds"] += 1
                dispatch._counters["capture_build_pending_steps"] += 1
                dispatch._emit("capture", site="captured",
                               phase="build_pending")
                _abort_capture("build_pending", fallback=False)
                flush_if_pending("optimizer_step")
                return False
        elif entry.pending is not None:
            fut = entry.pending
            if not fut.done():
                dispatch._counters["capture_build_pending_steps"] += 1
                dispatch._emit("capture", site="captured",
                               phase="build_pending")
                _abort_capture("build_pending", fallback=False)
                flush_if_pending("optimizer_step")
                return False
            entry.pending = None
            try:
                entry.exe = fut.result()  # the AOT-compiled executable
            except Exception:
                # compile-thread failure: drop the entry so a later cycle
                # rebuilds from scratch, then surface the error with its
                # original traceback through the capture_error contract
                _capture_cache.pop(key, None)
                raise
            dispatch._counters["async_compile_joins"] += 1
            dispatch._emit("async_join", site="captured")
        return _run_captured(rec, optimizer, entry)
    except _CaptureIneligible as e:
        return fallback(e.reason)
    except FloatingPointError:
        # numeric_rescue=abort fired AFTER the captured step resolved (the
        # rescued update was already suppressed in-program) — propagate the
        # verdict, don't re-run the step on the fallback path
        raise
    except Exception as e:
        from ..analysis import ProgramVerificationError

        if isinstance(e, ProgramVerificationError):
            # verification failed at FLAGS_check_programs>=2: resolve the
            # deferred step on the safe 3-program path (numerics and
            # placeholder grads stay correct), then surface the verdict —
            # this is the static trip wire that fires BEFORE XLA's runtime
            # use-after-donate error (or CPU's silent non-donation). Label
            # the fallback by what actually failed, so the fallback-reason
            # histogram doesn't blame donation for a budget overrun.
            from ..analysis import Severity

            donation = any(
                d.pass_name == "donation_safety"
                and d.severity >= Severity.ERROR
                for d in e.diagnostics
            )
            fallback("donation_unsafe" if donation else "verification_failed")
            raise
        # any trace/compile/runtime error from the captured executable must
        # honor the fallback contract — the step completes on the normal
        # 3-program path instead of crashing optimizer.step() (and the
        # deferred placeholder grads must not outlive the failure)
        return fallback("capture_error")


# ---------------------------------------------------------------------------
# Decode-mode capture (paddle.serving)
#
# The whole-step controller above captures TRAINING steps by observing the
# eager event stream. Inference has no backward/optimizer to observe — a
# serving engine knows its step boundaries exactly — so decode-mode capture
# is the direct half of the same contract (the CUDA-Graphs capture/replay
# idiom from PAPERS.md): a pure step function, keyed by its bucket
# signature, jitted ONCE with the paged KV block pool donated, replayed from
# an LRU cache. Per-op dispatch inside the traced function already falls
# back to the per-op path on tracer args (lazy_apply's tracer bail-out), so
# the SAME paddle-ops function serves all three execution tiers:
#
#   captured  jit(fn, donate_argnums=pools)  — 1 donated program per step
#   lazy      jit(fn)                        — 1 program, inputs retained
#                                              (the retry-safe middle rung)
#   per-op    fn(*args) eagerly              — the ladder floor
#
# Build/replay/fallback/eviction counts land in
# paddle.profiler.dispatch_counters() under the serve_capture_* keys.
# ---------------------------------------------------------------------------
_serve_cache: "OrderedDict[Tuple, _ServeProgram]" = OrderedDict()


class _ServeProgram:
    """One captured serving program (a prefill or decode bucket signature)."""

    __slots__ = ("key", "fn", "donate_argnums", "_exe_donate", "_exe_plain",
                 "_built_donate", "_built_plain", "certificate", "__weakref__")

    def __init__(self, key, fn, donate_argnums):
        self.key = key
        self.fn = fn
        self.donate_argnums = tuple(donate_argnums)
        self._exe_donate = None
        self._exe_plain = None
        self._built_donate = False
        self._built_plain = False
        # EquivalenceCertificate binding the donated rung to the plain
        # retry rung (FLAGS_check_programs=2), or None
        self.certificate = None

    def _certify_rungs(self, args):
        """Proof-carrying parity for the serve ladder: before the donated
        rung consumes its first pool, certify its trace structurally
        equivalent to the non-donated retry rung's. Both rungs jit the
        same ``fn`` today, so this locks the ladder invariant (a fault on
        the donated tier replays on a PROVABLY identical program) against
        the rungs ever being forked. Divergence raises
        ProgramVerificationError while the pools are still intact;
        an unprovable trace is recorded and skipped."""
        from . import dispatch
        from ..analysis import ProgramVerificationError
        from ..analysis.equivalence import prove_equivalent

        dispatch._counters["serve_equivalence_checks"] += 1
        try:
            specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype),
                tuple(args),
            )
            cert = prove_equivalent(
                jax.make_jaxpr(self.fn)(*specs),
                jax.make_jaxpr(self.fn)(*specs),
                label_a="serve-donated", label_b="serve-plain",
                source=f"serve:{self.key}",
            )
        except ProgramVerificationError:
            raise
        except Exception as e:
            dispatch._emit("serve_capture", site="captured",
                           phase="equivalence", key=str(self.key),
                           result="unprovable", why=type(e).__name__)
            return
        if not cert.equivalent:
            dispatch._counters["serve_equivalence_divergences"] += 1
            dispatch._emit("serve_capture", site="captured",
                           phase="equivalence", key=str(self.key),
                           result="divergent")
            raise ProgramVerificationError(
                "donated serve rung is not provably equivalent to the "
                "plain retry rung: " + cert.summary(),
                [cert.divergence] if cert.divergence is not None else [])
        self.certificate = cert
        dispatch._counters["serve_equivalence_certified"] += 1
        dispatch._emit("serve_capture", site="captured", phase="equivalence",
                       key=str(self.key), result="certified",
                       ops=cert.n_ops[0], outputs=cert.outputs_compared)

    def built(self, donate: bool = True) -> bool:
        return self._built_donate if donate else self._built_plain

    def run(self, args, donate: bool = True):
        """Replay the captured program (building it on first use).

        ``donate=True`` consumes the buffers at ``donate_argnums`` in place
        (the captured tier); ``donate=False`` is the retry-safe middle rung
        — same single program, inputs retained."""
        import warnings as _warnings

        from . import dispatch

        if donate and self.donate_argnums:
            if self._exe_donate is None:
                self._exe_donate = jax.jit(
                    self.fn, donate_argnums=self.donate_argnums
                )
            exe, fresh = self._exe_donate, not self._built_donate
        else:
            if self._exe_plain is None:
                self._exe_plain = jax.jit(self.fn)
            exe, fresh = self._exe_plain, not self._built_plain
        akey = "serve:" + ":".join(str(x) for x in self.key)
        if fresh and donate and self.donate_argnums \
                and int(flags.flag("check_programs")) >= 2:
            self._certify_rungs(args)
        t0 = time.perf_counter()
        if fresh:
            # first call = trace + XLA compile; backends without real
            # donation (CPU) warn at compile time — same suppression as the
            # training capture's _aot_compile
            with _warnings.catch_warnings():
                _warnings.filterwarnings("ignore", message=".*onated buffer.*")
                out = exe(*args)
            if donate and self.donate_argnums:
                self._built_donate = True
            else:
                self._built_plain = True
            dispatch._counters["serve_capture_builds"] += 1
            dispatch._emit("serve_capture", site="captured", phase="build",
                           key=str(self.key), donated=bool(
                               donate and self.donate_argnums))
            _add_time("compile_time_ms", t0)
            # attribution cost registry: one entry per serving bucket
            # signature. Weak thunk — the step fn closes over the model,
            # and the registry must never outlive the serve cache.
            import weakref as _weakref

            pref = _weakref.ref(self)
            try:
                specs = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype),
                    tuple(args),
                )

                def _serve_jaxpr(_r=pref, _s=specs):
                    p = _r()
                    if p is None:
                        return None
                    return jax.make_jaxpr(p.fn)(*_s)

                _register_program(
                    akey, "serve", jaxpr_thunk=_serve_jaxpr,
                    donated=len(self.donate_argnums)
                    if (donate and self.donate_argnums) else 0,
                )
            except Exception:
                pass
        else:
            out = exe(*args)
            dispatch._counters["serve_capture_replays"] += 1
            _note_program(akey, "serve", _add_time("replay_time_ms", t0))
        return out


def serve_program(key: Tuple, fn: Callable, donate_argnums=()) -> _ServeProgram:
    """The decode-mode capture cache: one ``_ServeProgram`` per bucket
    signature, LRU-bounded by FLAGS_serving_capture_cache_size. A re-used
    key returns the cached handle (its compiled executables intact), so a
    steady-state decode loop replays without recompiling — verified by the
    serve_capture_builds counter staying flat."""
    from . import dispatch

    prog = _serve_cache.get(key)
    if prog is not None:
        _serve_cache.move_to_end(key)
        return prog
    prog = _ServeProgram(key, fn, donate_argnums)
    _serve_cache[key] = prog
    cap = int(flags.flag("serving_capture_cache_size"))
    while cap > 0 and len(_serve_cache) > cap:
        _serve_cache.popitem(last=False)
        dispatch._counters["serve_capture_evictions"] += 1
    return prog


def reset_serve_programs(owner=None):
    """Drop captured serving programs: all of them (test isolation), or —
    with ``owner`` set — only the ones whose key belongs to that engine uid
    (Engine.close(): a dead engine's step-function closures hold the model
    and would otherwise sit in the cache until LRU pressure evicts them)."""
    if owner is None:
        _serve_cache.clear()
        return
    for key in [k for k in _serve_cache
                if len(k) > 1 and k[1] == owner]:
        del _serve_cache[key]


def serve_capture_state() -> Dict[str, Any]:
    """Snapshot of the decode-mode capture cache (`Engine.stats()` and the
    diag server's /statusz read this)."""
    return {
        "cached_programs": len(_serve_cache),
        "built_programs": sum(
            1 for p in _serve_cache.values()
            if p._built_donate or p._built_plain
        ),
    }


def step_signature_id() -> Optional[int]:
    """Small stable id of the ARMED whole-step capture signature on this
    thread, or None when no signature is armed. The perf-regression
    sentinel keys its train-step baseline on this, so a workload change
    that re-arms capture starts a fresh baseline instead of tripping
    against the old step's timing."""
    obs = getattr(_tls, "observer", None)
    if obs is None or obs.armed is None:
        return None
    try:
        return hash(obs.armed) & 0xFFFF
    except TypeError:
        return None


def step_capture_state() -> Dict[str, Any]:
    """Snapshot of this thread's whole-step capture controller (for
    paddle.profiler.measure_programs's `_capture_state` entry)."""
    obs = getattr(_tls, "observer", None)
    tier_info = getattr(_tls, "capture_tier", None) or {}
    return {
        "enabled": _capture_on(),
        "armed": bool(obs is not None and obs.armed is not None),
        "stable_steps": 0 if obs is None else obs.stable,
        "deferred": getattr(_tls, "capture_deferred", None) is not None,
        "cached_steps": len(_capture_cache),
        # accumulation-cycle state: period k (1 = plain step) and the
        # position inside the current cycle
        "cycle_len": 1 if obs is None else obs.cycle_len,
        "cycle_pos": 0 if obs is None else obs.pos,
        # async host pipeline: background compiles still in flight
        "pending_compiles": _async.pending_jobs(),
        # mesh-aware capture: the tier the LAST replay on this thread ran
        # as ('captured-sharded' on a multi-device mesh), its mesh tag,
        # and whether that replay was donated — /statusz and the fleet obs
        # snapshot render these per host
        "tier": tier_info.get("tier"),
        "mesh": tier_info.get("mesh"),
        "donated": bool(tier_info.get("donated", False)),
    }
