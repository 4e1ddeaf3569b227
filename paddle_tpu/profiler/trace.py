"""paddle.profiler.trace — the flight recorder.

A bounded in-memory ring of structured runtime events
``{ts, kind, site, step, attrs}`` emitted at the execution choke points
(the always-cheap structured event layer the paper's HostTracer/
ChromeTracingLogger stack argues for, SURVEY.md §5):

  program          every device-program launch, by category
                   (op/segment/backward/optimizer/captured/compiled; the
                   last is one ``compile_train_step`` call)
  span             one closed host span (``span`` / ``RecordEvent``): name,
                   start on the profiler's clock, duration, id, parent span;
                   ``compile_train_step`` and its children each step,
                   ``create_parameter`` round each leaf a layer makes
  compile          one program built, from ``jax.monitoring``, sited at the
                   span open around it: its outermost trace, lowering and
                   compile-or-fetch seconds, whether the persistent cache
                   held it, the fetch's seconds, jax's name for it and when
                   its trace began (jax reports one trace event per nested
                   jit as well: they are folded into the program's record,
                   never kept one by one)
  flash_tiles      the flash attention kernels were built for a shape: how
                   many sub-tiles of a head's score square the causal walk
                   runs, masks and skips (trace time, once per compile)
  gdn_chunks       the gated delta rule's kernels were built for a shape:
                   chunk, chunks a grid step, value heads a key head and a
                   grid step, where the chunks are prepared (trace time,
                   once per trace)
  mixer_pass       the short conv or the gated norm round the rule was
                   traced for a shape: its Pallas kernels ("vmem") or the
                   jax.numpy expression ("xla", with why), rows, lanes and
                   the rows of a grid step (trace time, once per trace)
  ssd_chunks       the state-space scan was traced for a shape: seq, chunk,
                   the heads held and published, groups, its Pallas kernels
                   ("vmem") or the jax.numpy chunks ("xla", with why; counted
                   as ssd_scan_fallbacks) (trace time, once per trace)
  mixer_share      a mixer that holds a share of its heads was traced: the
                   kind (site), the heads held and published
  moe_route        the dropless expert layer was traced for a shape: the
                   experts held of the router's, experts a token, the row
                   buffer's rows, tokens, the bytes of the first pass's
                   kept product, the activation ("swiglu" / "relu2"), the
                   router ("softmax" / "sigmoid") and the routed scale
                   (trace time, once per trace)
  flush            lazy-segment flush: reason, cache hit/miss/join,
                   fused vs bridged vs per-op fallback
  async_compile /  background-compile submissions and the joins that
  async_join       install their executables
  capture          whole-step capture build/replay/fallback WITH REASON
  serve_capture    decode-mode capture builds (serving bucket programs)
  fault / retry    every resilience event: classification, attempt,
                   backoff, disruptive verdict
  ladder           degradation-ladder demotions and re-promotions
  serve            serving request lanes: admit/reject/prefill/decode/
                   complete/error/requeue, with request ids
  ckpt             checkpoint pipeline phases: snapshot/persist/commit/
                   stall, with per-phase ms
  stall            the step-stall watchdog fired
  preempt          a preemption signal reached the step boundary

The ring (``FLAGS_trace_ring_size``, default on) is a ``deque(maxlen=N)``
— append is O(1) and effectively free next to a device launch; with the
flag at 0 the emit fast path is a single dict read. ``Profiler.export``
merges these events (and per-request serving lanes) into the chrome trace;
crash postmortems dump the event tail plus the unified metrics snapshot to
``FLAGS_postmortem_dir`` as JSON.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
import traceback as _tb
from collections import deque
from typing import Any, Dict, List, Optional

import jax

from ..core import flags as _flags

__all__ = [
    "TraceEvent",
    "span",
    "add_stall_listener",
    "clear",
    "dump_postmortem",
    "emit",
    "enabled",
    "events",
    "heartbeat_age_ms",
    "last_postmortem_path",
    "remove_stall_listener",
    "step_heartbeat",
    "watchdog_disarm",
]

# direct reference to the flag registry entry: the emit fast path reads one
# dict key instead of going through flags.flag()'s name normalization
_ring_entry = _flags._registry["trace_ring_size"]

# wall-clock anchor for the perf_counter timestamps events carry: postmortem
# and chrome-trace consumers need absolute time, emit must not pay a second
# clock read
_ANCHOR_WALL = time.time()
_ANCHOR_NS = time.perf_counter_ns()

_ring: Optional[deque] = None
_ring_lock = threading.Lock()  # guards ring (re)creation only, not append
_faults = None  # lazily bound resilience.faults (step auto-fill)


class TraceEvent:
    """One flight-recorder event. ``ts`` is ``time.perf_counter_ns()`` at
    emit (monotonic; for a ``span`` event that is the span's END);
    ``wall_time`` derives the absolute time from the module anchor. A
    ``span`` event also carries ``start_ns`` on the profiler's own clock."""

    __slots__ = ("ts", "kind", "site", "step", "attrs")

    def __init__(self, ts: int, kind: str, site: str, step: int,
                 attrs: Optional[Dict[str, Any]]):
        self.ts = ts
        self.kind = kind
        self.site = site
        self.step = step
        self.attrs = attrs

    @property
    def wall_time(self) -> float:
        return _ANCHOR_WALL + (self.ts - _ANCHOR_NS) / 1e9

    def as_dict(self) -> Dict[str, Any]:
        return {
            "ts": round(self.wall_time, 6),
            "kind": self.kind,
            "site": self.site,
            "step": self.step,
            "attrs": dict(self.attrs) if self.attrs else {},
        }

    def __repr__(self):
        a = f" {self.attrs}" if self.attrs else ""
        return f"<TraceEvent {self.kind}/{self.site} step={self.step}{a}>"


def enabled() -> bool:
    return int(_ring_entry["value"]) > 0


def _current_step() -> int:
    global _faults
    if _faults is None:
        from ..resilience import faults as _f

        _faults = _f
    return _faults.current_step()


def emit(kind: str, site: str = "", step: Optional[int] = None, **attrs):
    """Record one event. Near-zero overhead by construction: off mode is a
    dict read + falsy test; on mode is one clock read and a bounded-deque
    append (no locks — deque.append is atomic under the GIL)."""
    size = _ring_entry["value"]
    if not size:
        return None
    size = int(size)
    if size <= 0:
        return None  # a negative flag value means off, not a hot-path raise
    global _ring
    ring = _ring
    if ring is None or ring.maxlen != size:
        # (re)configure: flag changed since the last emit. Old events are
        # carried over so a resize doesn't silently drop history. Creation
        # is locked so two threads racing the first emit (or a resize)
        # can't each install a ring and lose the other's events; the hot
        # append path below stays lock-free. Copying the old ring iterates
        # it while unlocked emitters may still append — retry the rare
        # 'mutated during iteration' race, and as a last resort start
        # empty: diagnostics must never add a second failure.
        with _ring_lock:
            ring = _ring
            if ring is None or ring.maxlen != size:
                for _ in range(4):
                    try:
                        ring = deque(_ring or (), maxlen=size)
                        break
                    except RuntimeError:
                        continue
                else:
                    ring = deque(maxlen=size)
                _ring = ring
    if step is None:
        step = _current_step()
    ev = TraceEvent(time.perf_counter_ns(), kind, site, step, attrs or None)
    ring.append(ev)
    return ev


def events(last: Optional[int] = None, kind: Optional[str] = None,
           site: Optional[str] = None) -> List[TraceEvent]:
    """Snapshot of the ring, oldest first (optionally only the trailing
    ``last`` events). ``kind=`` / ``site=`` filter during the copy, so a
    ``/flight?kind=ladder`` query or a postmortem builder materializes only
    the matching events instead of the whole ring; ``last`` applies AFTER
    the filters (the trailing N *matching* events). Safe against concurrent
    emits: the copy retries the rare 'deque mutated during iteration' race
    instead of locking the emit path."""
    ring = _ring
    if ring is None:
        return []
    if kind is None and site is None:
        keep = None
    else:
        def keep(e):
            return ((kind is None or e.kind == kind)
                    and (site is None or e.site == site))
    for _ in range(8):
        try:
            out = list(ring) if keep is None else [e for e in ring if keep(e)]
            break
        except RuntimeError:
            continue
    else:  # sustained concurrent churn: drain via indexed access
        out = [ring[i] for i in range(len(ring))]
        if keep is not None:
            out = [e for e in out if keep(e)]
    if last is not None and last >= 0:
        out = out[-last:] if last else []
    return out


def clear():
    """Drop every recorded event (test isolation / fresh measurement)."""
    ring = _ring
    if ring is not None:
        ring.clear()


# ---------------------------------------------------------------------------
# Host spans. One primitive for the program's own spans and for the Paddle
# API's RecordEvent: a jax.profiler.TraceAnnotation (so the span sits on the
# /host:CPU plane of any running trace, whose clock the device planes share)
# plus, on exit, one ``span`` event in the ring above, so that an untraced
# run keeps the same spans in memory.
# ---------------------------------------------------------------------------
_span_ids = itertools.count(1)
_tls = threading.local()


def _open_spans() -> list:
    try:
        return _tls.open
    except AttributeError:
        _tls.open = []
        return _tls.open


class span:
    """``with span("compile_train_step/launch"): ...`` or ``begin()`` /
    ``end()``. ``ids`` become the annotation's arguments and the ring event's
    attributes; ``step_num`` among them makes the annotation a
    ``StepTraceAnnotation`` (the device planes then get a per-step line) and
    is the event's ``step``.

    The ring event (kind ``span``, site = name) carries ``start_ns`` =
    ``time.time_ns()`` at entry, which is the clock ``.xplane.pb`` events are
    on (``profile_start_time`` + an event's ``start_ns``), ``dur_ns`` from
    the monotonic clock, ``id``, and ``parent`` = the id of the span open
    around it on this thread (None for a root). With
    ``FLAGS_trace_ring_size=0`` the ring half is one dict read; with no trace
    running the annotation half is JAX's inactive TraceMe."""

    __slots__ = ("name", "ids", "id", "parent", "_annot", "_t0", "_start")

    def __init__(self, name: str, event_type=None, **ids):
        self.name = name
        self.ids = ids
        self.id = self.parent = self._annot = self._t0 = None

    def begin(self):
        kind = (jax.profiler.StepTraceAnnotation if "step_num" in self.ids
                else jax.profiler.TraceAnnotation)
        self._annot = kind(self.name, **self.ids)
        self._annot.__enter__()
        if _ring_entry["value"]:
            stack = _open_spans()
            self.parent = stack[-1].id if stack else None
            self.id = next(_span_ids)
            stack.append(self)
            self._start = time.time_ns()
            self._t0 = time.perf_counter_ns()

    def end(self):
        if self._t0 is not None:
            dur = time.perf_counter_ns() - self._t0
            self._t0 = None
            stack = _open_spans()
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:  # begin()/end() pairs closed out of order
                stack.remove(self)
            emit("span", site=self.name, step=self.ids.get("step_num"),
                 start_ns=self._start, dur_ns=dur, id=self.id,
                 parent=self.parent, **self.ids)
        if self._annot is not None:
            self._annot.__exit__(None, None, None)
            self._annot = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


# ---------------------------------------------------------------------------
# Which step compiled, and what building it cost: jax.jit traces, lowers and
# compiles (or fetches from the persistent cache) inside the first launch, so
# without these a first step is only a long span. jax reports each phase as
# its own event, and a trace event for EVERY jit nested in the one traced
# (1,850 of them for the 10 programs a tiny GPT's first step builds), so the
# phases wait per thread (the lazy tier compiles on a background thread)
# until the backend compile's event closes the program: one `compile` event
# a program. Nested traces finish inside the outer one, so the program's
# trace is the LONGEST since the last program built, never their sum.
# ---------------------------------------------------------------------------
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT = "/jax/compilation_cache/cache_hits"
_BUILT = "/jax/core/compile/backend_compile_duration"
_pending = threading.local()  # this thread's phases since its last program


def _on_compile_event(name, *args, **kw):
    if name == _TRACE:
        if args[0] > getattr(_pending, "trace_s", 0.0):
            _pending.trace_s = args[0]
        return
    if name == _LOWER:
        _pending.lower_s = args[0]
        return
    if name == _FETCH:
        _pending.fetch_s = args[0]
        return
    if name != _HIT and name != _BUILT:
        return
    from ..core import dispatch

    if name == _HIT:
        _pending.cache_hit = True
        dispatch._counter_add("compile_cache_hits", 1)
        return
    seconds = args[0]
    phases = _pending.__dict__
    trace_s = phases.pop("trace_s", 0.0)
    lower_s = phases.pop("lower_s", 0.0)
    attrs = {"seconds": seconds, "trace_s": trace_s, "lower_s": lower_s,
             "fetch_s": phases.pop("fetch_s", 0.0),
             "cache_hit": phases.pop("cache_hit", False),
             "fun": kw.get("fun_name", ""),
             "start_ns": time.time_ns() - round(
                 (trace_s + lower_s + seconds) * 1e9)}
    # a background compile thread may be the caller
    dispatch._counter_add("backend_compiles", 1)
    dispatch._counter_add("backend_compile_s", seconds)
    stack = _open_spans()
    if stack:
        emit("compile", site=stack[-1].name, span=stack[-1].id, **attrs)
    else:
        emit("compile", **attrs)


jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
jax.monitoring.register_event_listener(_on_compile_event)


# ---------------------------------------------------------------------------
# Crash postmortems: dump the event tail + unified metrics + memory snapshot
# + resilience state as one JSON file in FLAGS_postmortem_dir.
# ---------------------------------------------------------------------------
_pm_lock = threading.Lock()
_pm_last_path: Optional[str] = None
_pm_seq = 0
_pm_active = False  # re-entrance guard: a postmortem must never postmortem


def last_postmortem_path() -> Optional[str]:
    return _pm_last_path


def dump_postmortem(reason: str, exc: Optional[BaseException] = None,
                    **attrs) -> Optional[str]:
    """Write one postmortem JSON; returns its path, or None when
    ``FLAGS_postmortem_dir`` is unset (the default) or the dump itself
    fails — a diagnostics path must never add a second crash."""
    global _pm_last_path, _pm_seq, _pm_active
    directory = str(_flags.flag("postmortem_dir"))
    if not directory:
        return None
    with _pm_lock:
        if _pm_active:
            return None
        _pm_active = True
        try:
            _pm_seq += 1
            seq = _pm_seq
            doc = _build_postmortem(reason, exc, attrs)
            os.makedirs(directory, exist_ok=True)
            name = f"postmortem_{reason}_{os.getpid()}_{seq:04d}.json"
            path = os.path.join(directory, name)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, default=str)
            os.replace(tmp, path)
            _pm_last_path = path
            _prune_postmortems(directory, keep_path=path)
            emit("postmortem", site=reason, path=path)
            return path
        except Exception:
            return None
        finally:
            _pm_active = False


def _prune_postmortems(directory: str, keep_path: Optional[str] = None):
    """Bound the postmortem directory to FLAGS_postmortem_keep files,
    oldest-first (a flapping sentinel or a rescue storm must not grow it
    without limit). The just-written dump is never pruned; pruned files
    are counted (postmortems_pruned) and reported by /postmortems."""
    keep = int(_flags.flag("postmortem_keep"))
    if keep <= 0:
        return  # 0 = unbounded (the pre-ISSUE-15 behavior)
    try:
        entries = []
        for name in os.listdir(directory):
            if not (name.startswith("postmortem_") and name.endswith(".json")):
                continue
            p = os.path.join(directory, name)
            try:
                entries.append((os.stat(p).st_mtime, name, p))
            except OSError:
                continue
        if len(entries) <= keep:
            return
        entries.sort()  # oldest first
        pruned = 0
        for _mtime, _name, p in entries[: len(entries) - keep]:
            if keep_path is not None and os.path.abspath(p) == os.path.abspath(
                    keep_path):
                continue
            try:
                os.remove(p)
                pruned += 1
            except OSError:
                continue
        if pruned:
            from ..core import dispatch

            # _counter_add: the watchdog daemon and persist threads dump
            # postmortems too, so the count must be race-free off-thread
            dispatch._counter_add("postmortems_pruned", pruned)
    except Exception:
        pass  # pruning must never fail the dump that triggered it


def _build_postmortem(reason, exc, attrs) -> Dict[str, Any]:
    doc: Dict[str, Any] = {
        "reason": reason,
        "time": time.time(),
        "pid": os.getpid(),
        "attrs": {k: v for k, v in (attrs or {}).items()},
    }
    try:
        doc["step"] = _current_step()
    except Exception:
        doc["step"] = None
    if exc is not None:
        doc["exception"] = {
            "type": type(exc).__name__,
            "message": str(exc),
            "traceback": _tb.format_exception(type(exc), exc,
                                              exc.__traceback__),
        }
    tail = int(_flags.flag("postmortem_events"))
    doc["events"] = [e.as_dict() for e in events(last=max(0, tail))]
    # unified metrics: registry-native + the adopted dispatch counters
    try:
        from . import metrics as _metrics

        doc["metrics"] = _metrics.snapshot(include_dispatch=True)
    except Exception:
        doc["metrics"] = None
    try:
        import jax

        live = jax.live_arrays()
        doc["memory"] = {
            "live_buffer_bytes": int(
                sum(int(getattr(a, "nbytes", 0) or 0) for a in live)
            ),
            "live_buffer_count": len(live),
        }
    except Exception:
        doc["memory"] = None
    try:
        from ..resilience import runtime as _rt

        doc["resilience"] = _rt.state()
    except Exception:
        doc["resilience"] = None
    # spike auto-triage (paddle.profiler.attribution): which program key's
    # measured EMA moved (cost-registry diff + the sentinel-tripped keys),
    # which parameter group's grad-norm broke trend (last N fused-telemetry
    # records), and the offending batch's sample ids recovered from the
    # registered GlobalStepSampler
    try:
        from . import attribution as _attribution

        doc["attribution"] = _attribution.triage_section()
    except Exception:
        doc["attribution"] = None
    return doc


def read_postmortem(path: str) -> Dict[str, Any]:
    """Load one postmortem JSON (tools/tests convenience)."""
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Step-stall watchdog (FLAGS_trace_stall_ms): a daemon thread that watches
# the step heartbeat (resilience.runtime.on_step_end) and dumps a 'stall'
# postmortem when no boundary lands inside the threshold. One trip per
# episode; the next heartbeat re-arms.
# ---------------------------------------------------------------------------
_wd_lock = threading.Lock()
_wd_thread: Optional[threading.Thread] = None
# heartbeats are PER SOURCE ('train' from optimizer.step, 'serve' from the
# engine tick): a combined train+serve process must not lose the training
# loop's liveness signal because an idle engine stood ITS heartbeat down
_wd_hb: Dict[str, int] = {}
_wd_fired: Dict[str, bool] = {}
_wd_stalls = 0
# consumers of stall trips beyond the postmortem dump — the serving
# Supervisor registers here so a wedged engine tick (no heartbeat inside
# FLAGS_trace_stall_ms) is observed and the engine restarted once the
# tick returns control
_stall_listeners: List = []


def add_stall_listener(fn):
    """Register ``fn(stalled_ms)`` to be called (from the watchdog daemon
    thread) every time the step-stall watchdog trips. Listener exceptions
    are swallowed — observability must never add a second failure."""
    with _wd_lock:
        if fn not in _stall_listeners:
            _stall_listeners.append(fn)


def remove_stall_listener(fn):
    with _wd_lock:
        if fn in _stall_listeners:
            _stall_listeners.remove(fn)


def step_heartbeat(source: str = "train"):
    """Step-boundary tick (called from resilience.runtime.on_step_end).
    Re-arms the watchdog for ``source`` and starts it on first use when
    FLAGS_trace_stall_ms > 0."""
    _wd_hb[source] = time.perf_counter_ns()
    _wd_fired[source] = False
    if float(_flags.flag("trace_stall_ms")) > 0 and _wd_thread is None:
        _start_watchdog()


def watchdog_disarm(source: Optional[str] = None):
    """Stand down the stall watchdog for ``source`` (every source when
    None) until the next heartbeat. A loop that ENDS looks exactly like a
    stalled one — no more step boundaries — so clean completion must
    disarm (train_step_range / train_epoch_range / Engine.run_until_idle
    do this in their finally) or every finished run would dump a spurious
    stall postmortem. Sources disarm independently: an idle serving
    engine standing down must not erase the training loop's liveness
    signal in a combined train+serve process."""
    if source is None:
        _wd_hb.clear()
        _wd_fired.clear()
    else:
        _wd_hb.pop(source, None)
        _wd_fired.pop(source, None)


def stall_count() -> int:
    return _wd_stalls


def heartbeat_age_ms(source: Optional[str] = None) -> Optional[float]:
    """Milliseconds since the last step heartbeat of ``source`` — or, when
    None, of the STALEST armed source — or None when no loop is running
    (never beat, or every finished loop disarmed its source). The
    diagnostics server's /healthz liveness check reads this — a heartbeat
    older than FLAGS_trace_stall_ms means that step loop is wedged."""
    if source is not None:
        hb = _wd_hb.get(source)
        return None if hb is None else (time.perf_counter_ns() - hb) / 1e6
    beats = list(_wd_hb.values())
    if not beats:
        return None
    return (time.perf_counter_ns() - min(beats)) / 1e6


def _start_watchdog():
    global _wd_thread
    with _wd_lock:
        if _wd_thread is not None:
            return
        t = threading.Thread(target=_watchdog_loop, daemon=True,
                             name="paddle-stall-watchdog")
        _wd_thread = t
        t.start()


def _watchdog_loop():
    global _wd_stalls
    while True:
        ms = float(_flags.flag("trace_stall_ms"))
        if ms <= 0:
            time.sleep(0.25)
            continue
        time.sleep(min(max(ms / 2000.0, 0.005), 0.5))
        now = time.perf_counter_ns()
        for source, hb in list(_wd_hb.items()):
            if _wd_fired.get(source):
                continue
            stalled_ms = (now - hb) / 1e6
            if stalled_ms < ms:
                continue
            _wd_fired[source] = True
            _wd_stalls += 1
            emit("stall", site="watchdog", source=source,
                 stalled_ms=round(stalled_ms, 1), threshold_ms=ms)
            dump_postmortem("stall", source=source,
                            stalled_ms=round(stalled_ms, 1),
                            threshold_ms=ms)
            with _wd_lock:
                listeners = list(_stall_listeners)
            for fn in listeners:
                try:
                    fn(stalled_ms)
                except Exception:
                    pass  # a listener must never take the watchdog down


# ---------------------------------------------------------------------------
# Chrome-trace conversion: flight events become instants on a dedicated
# lane; serving events become per-request async lanes (ph b/n/e keyed by
# request id), so a continuous-batching interleave or a ladder demotion is
# visible on one timeline next to the host spans (``span`` events, ph X).
# ---------------------------------------------------------------------------
_FLIGHT_TID = 1
_SPAN_TID = 2
_SERVE_END_PHASES = frozenset(("complete", "error", "reject", "shed",
                               "expire"))


def chrome_trace_events(evts: Optional[List[TraceEvent]] = None):
    pid = os.getpid()
    src = events() if evts is None else evts
    # a request's lane begins at its admit event; any serve event for a
    # request WITHOUT a begin in the window — rejected at submit, or its
    # admit already evicted from the ring — renders as a plain thread
    # instant (ph "i"), since async events without an enclosing b/e pair
    # (lone "e" OR lone "n") are dropped as malformed by trace viewers
    admitted = {
        (ev.attrs or {}).get("rid")
        for ev in src
        if ev.kind == "serve" and (ev.attrs or {}).get("phase") == "admit"
    }
    out = []
    for ev in src:
        ts_us = ev.ts / 1000.0
        attrs = dict(ev.attrs) if ev.attrs else {}
        if ev.kind == "serve":
            phase = attrs.pop("phase", "")
            rids = attrs.pop("rids", None)
            if rids is None:
                rid = attrs.pop("rid", None)
                rids = [] if rid is None else [rid]
            if not rids:
                # engine-scoped events (health/restart/block_leak) have no
                # request lane — render as plain flight instants
                out.append({
                    "name": f"serve:{phase}", "cat": "serving",
                    "ph": "i", "s": "t", "ts": ts_us, "pid": pid,
                    "tid": _FLIGHT_TID, "args": dict(attrs, step=ev.step),
                })
                continue
            for rid in rids:
                args = dict(attrs, phase=phase, step=ev.step)
                if rid not in admitted:
                    out.append({
                        "name": f"serve:{phase}", "cat": "serving",
                        "ph": "i", "s": "t", "ts": ts_us, "pid": pid,
                        "tid": _FLIGHT_TID, "args": dict(args, rid=rid),
                    })
                    continue
                if phase == "admit":
                    ph = "b"
                elif phase in _SERVE_END_PHASES:
                    ph = "e"
                else:
                    ph = "n"
                out.append({
                    "name": "request", "cat": "serving", "ph": ph,
                    "id": str(rid), "ts": ts_us, "pid": pid,
                    "tid": _FLIGHT_TID,
                    "args": args,
                })
            continue
        if ev.kind == "span":  # a closed host span: ev.ts is its end
            dur = attrs.pop("dur_ns")
            out.append({
                "name": ev.site, "cat": "host", "ph": "X",
                "ts": (ev.ts - dur) / 1000.0, "dur": dur / 1000.0,
                "pid": pid, "tid": _SPAN_TID,
                "args": dict(attrs, step=ev.step),
            })
            continue
        name = ev.kind if not ev.site else f"{ev.kind}:{ev.site}"
        out.append({
            "name": name, "cat": "flight", "ph": "i", "s": "t",
            "ts": ts_us, "pid": pid, "tid": _FLIGHT_TID,
            "args": dict(attrs, step=ev.step),
        })
    return out
