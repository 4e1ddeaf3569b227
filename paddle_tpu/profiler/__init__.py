"""paddle.profiler — profiling with the TPU/XLA backend.

Reference analogue: python/paddle/profiler/ (profiler.py scheduler states,
RecordEvent host annotation api → HostTracer host_event_recorder.h, CUPTI
CudaTracer, ChromeTracingLogger chrome://tracing export; SURVEY.md §5).

TPU-native: device-side tracing is jax.profiler (XPlane → TensorBoard/
perfetto, replacing CUPTI); host annotations keep the RecordEvent API, which
is `span` (a jax.profiler.TraceAnnotation plus a flight-recorder event);
`Profiler.summary()` reads the ring for the host view and the `.xplane.pb`
it wrote for the device view; the chrome-trace export writes the ring.
"""
from __future__ import annotations

import glob
import json
import os
import tempfile
import time
from collections.abc import Mapping as _MappingABC
from enum import Enum
from typing import Callable, Iterable, Optional

import jax

# dispatch-level counters: device-program launches by category, lazy-segment
# flush reasons, compile-cache hit/miss/eviction counts (core/dispatch.py).
# The programs-per-step arithmetic reads these.
from ..core.dispatch import (  # noqa: F401
    dispatch_counters,
    reset_dispatch_counters,
)

# runtime observability (OBSERVABILITY.md): the flight recorder (bounded
# ring of structured runtime events + crash postmortems + stall watchdog)
# and the unified typed metrics registry (counters/gauges/histograms with
# Prometheus exposition; the dispatch counters are adopted at snapshot time)
from . import metrics  # noqa: F401
from . import trace  # noqa: F401

# attribution layer (OBSERVABILITY.md "Attribution & triage"): the program
# cost registry, fused numerics telemetry, and postmortem triage
from . import attribution  # noqa: F401

__all__ = [
    "attribution",
    "diag",
    "program_costs",
    "sentinel",
    "Profiler",
    "ProfilerState",
    "ProfilerTarget",
    "RecordEvent",
    "span",
    "make_scheduler",
    "export_chrome_tracing",
    "load_profiler_result",
    "SummaryView",
    "SortedKeys",
    "dispatch_counters",
    "reset_dispatch_counters",
    "measure_programs",
    "metrics",
    "trace",
    "StepTimer",
]


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class SortedKeys(Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    GPUTotal = 3


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6


# One host-span primitive (profiler/trace.py): a TraceAnnotation on the
# running trace's /host:CPU plane plus a ``span`` event in the flight
# recorder's ring. RecordEvent (reference: profiler/utils.py RecordEvent over
# platform/profiler/event_tracing.h:47) is the same object under its Paddle
# name; its second positional argument (event_type) is accepted and unused.
span = trace.span
RecordEvent = span


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """reference: profiler.py make_scheduler — step-phase state machine."""

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        cycle = closed + ready + record
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """reference: profiler.py export_chrome_tracing callback."""

    def handle(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_time_{int(time.time())}.paddle_trace.json")
        prof.export(path, "json")
        return path

    return handle


class Profiler:
    """reference: profiler.py:43 Profiler — composes host + device tracers.

    Device side: jax.profiler.start_trace/stop_trace writes an
    ``.xplane.pb`` (TensorBoard-loadable) that ``summary()`` reads back for
    the device view. Host side: ``span`` / ``RecordEvent`` events, read from
    the flight recorder's ring (bounded: a long profile keeps its tail).
    """

    def __init__(self, *, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready=None, record_shapes=False, profile_memory=False,
                 timer_only=False, with_flops=False):
        self._scheduler = scheduler or (lambda step: ProfilerState.RECORD)
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self._scheduler = make_scheduler(
                closed=max(0, lo), ready=0, record=hi - lo, repeat=1
            )
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._device_dir = None
        self._tracing = False
        self._started_ns = None  # summary() reads the spans from here on
        self._view = None  # (trace directory, its device view), read once

    def start(self):
        self._started_ns = time.time_ns()
        self._state = self._scheduler(self._step)
        if self._state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._start_device()

    def _start_device(self):
        """A trace that cannot start raises: a profiler that silently
        records nothing would read as an idle device."""
        if not self._tracing and not self._timer_only:
            base = os.environ.get("PADDLE_PROFILER_DIR") or os.path.join(
                tempfile.gettempdir(), "paddle_tpu_prof")
            os.makedirs(base, exist_ok=True)
            self._device_dir = tempfile.mkdtemp(
                prefix=f"{int(time.time())}_", dir=base)
            jax.profiler.start_trace(self._device_dir)
            self._tracing = True

    def _stop_device(self):
        if self._tracing:
            self._tracing = False
            jax.profiler.stop_trace()

    def step(self, num_samples: Optional[int] = None):
        self._step += 1
        new_state = self._scheduler(self._step)
        if new_state != self._state:
            if new_state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
                self._start_device()
            elif self._state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
                self._stop_device()
                if self._on_trace_ready:
                    self._on_trace_ready(self)
            self._state = new_state

    def stop(self):
        if self._state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._stop_device()
            if self._on_trace_ready:
                self._on_trace_ready(self)
        self._state = ProfilerState.CLOSED

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def export(self, path: str, format: str = "json"):
        """Write the merged chrome trace from the flight recorder's ring:
        host spans (ph X), instants on a dedicated lane for
        flushes/captures/faults/ladder transitions, and per-request async
        lanes (ph b/n/e keyed by request id) for serving, so a
        continuous-batching interleave or a ladder demotion is visible on
        one timeline. Device XPlane dir noted in metadata."""
        flight = trace.events()
        events = trace.chrome_trace_events(flight)
        # per-program counter lanes (attribution): every measured program
        # run is a "C" sample, so each program key's wall time plots as
        # its own lane next to the flight instants and request lanes
        counter_events = attribution.chrome_counter_events()
        events = events + counter_events
        doc = {
            "traceEvents": events,
            "metadata": {
                "device_trace_dir": self._device_dir,
                "framework": "paddle_tpu",
                "flight_recorder_events": len(flight),
                "program_counter_samples": len(counter_events),
            },
        }
        with open(path, "w") as f:
            json.dump(doc, f, default=str)
        return path

    def device_view(self, layer_depth=2):
        """Where the device time of the traced stretch went, read from the
        ``.xplane.pb`` this profiler wrote (statistic.device_view): seconds
        by section, by layer path (``layer_depth`` names below the root
        layer: 2 gives ``layers.*/attn``, 3 ``layers.*/mixer/short_conv``)
        and by named kernel. None when nothing was traced (``timer_only``,
        or no RECORD state reached yet)."""
        from .statistic import device_view

        if self._device_dir is None or self._tracing:
            return None
        key = (self._device_dir, layer_depth)
        if self._view is None or self._view[0] != key:
            paths = glob.glob(os.path.join(
                self._device_dir, "plugins", "profile", "*", "*.xplane.pb"))
            self._view = (key, device_view(paths, layer_depth)
                          if paths else None)
        return self._view[1]

    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail=True,
                thread_sep=False, time_unit="ms", views=None, layer_depth=2):
        """reference: profiler_statistic.py — Overview + Operator report of
        the host spans in the ring (those begun since ``start()``; all of
        them for a profiler never started), then the device view (ModelView by
        section and layer, KernelView by named kernel) of the traced
        stretch. Prints the report and returns it."""
        from .statistic import build_device_report, build_summary_report

        events = [{"name": e.site, "dur": e.attrs["dur_ns"] / 1000.0}
                  for e in trace.events(kind="span")
                  if e.attrs["start_ns"] >= (self._started_ns or 0)]
        key = {
            SortedKeys.CPUTotal: "total",
            SortedKeys.CPUAvg: "avg",
            SortedKeys.CPUMax: "max",
        }.get(sorted_by, "total")
        table = build_summary_report(events, sorted_by=key, time_unit=time_unit)
        view = self.device_view(layer_depth)
        if view is not None:
            table += "\n\n" + build_device_report(view)
        print(table)
        return table


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)


class _Timer:
    """Throughput timer (reference: python/paddle/profiler/timer.py)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._start = None
        self._n = 0
        self._elapsed = 0.0

    def step(self, num_samples=1):
        now = time.perf_counter()
        if self._start is not None:
            self._elapsed += now - self._start
            self._n += num_samples
        self._start = now

    def ips(self):
        return self._n / self._elapsed if self._elapsed else 0.0


benchmark_timer = _Timer()


def benchmark():
    return benchmark_timer


class StepTimer:
    """Steady-state step-time tracker: an EMA over per-step wall time with
    drift detection against the value at the last `mark()`.

    The per-step companion of `measure_programs`' one-shot counters: callers
    either bracket each step with `lap()` or feed measured durations to
    `observe(dt_s)`. The checkpoint cadence tuner
    (paddle.distributed.checkpoint.CadenceTuner) reads `ema_ms` for the
    CheckFreq overhead arithmetic and `drift_pct()` to decide when a shifted
    steady state (e.g. after a degradation-ladder demotion) warrants
    re-tuning."""

    def __init__(self, alpha: float = 0.25):
        self.alpha = float(alpha)
        self.ema_ms: Optional[float] = None
        self.total_ms = 0.0
        self.count = 0
        self._marked_ms: Optional[float] = None
        self._lap_t0: Optional[float] = None

    def observe(self, dt_s: float):
        ms = float(dt_s) * 1000.0
        self.total_ms += ms
        self.count += 1
        if self.ema_ms is None:
            self.ema_ms = ms
        else:
            self.ema_ms += self.alpha * (ms - self.ema_ms)
        return self.ema_ms

    def lap(self):
        """Call once per step boundary; the first call only starts the
        clock, each later call records the elapsed step."""
        now = time.perf_counter()
        if self._lap_t0 is not None:
            self.observe(now - self._lap_t0)
        self._lap_t0 = now

    def mark(self):
        """Remember the current EMA as the drift baseline."""
        self._marked_ms = self.ema_ms

    def drift_pct(self) -> float:
        """Percent drift of the EMA from the value at the last mark()."""
        if not self._marked_ms or self.ema_ms is None:
            return 0.0
        return abs(self.ema_ms - self._marked_ms) / self._marked_ms * 100.0


def program_costs(top_k: int = 5, static: bool = True):
    """Per-program cost profiles (paddle.profiler.attribution): the static
    flop/byte/top-ops estimate of every registered executable paired with
    its measured wall-time EMA — see attribution.program_costs."""
    return attribution.program_costs(top_k=top_k, static=static)


def measure_programs(step_fn, *args, warmup: int = 2, **kwargs):
    """Dispatch-counter snapshot of ONE steady-state `step_fn` call.

    Runs `warmup` calls first (compiles segments / tape / optimizer
    programs; with FLAGS_eager_step_capture on, also the steps that arm the
    whole-step capture controller), flushes any pending lazy segment, zeroes
    the counters, runs one measured call, flushes again so trailing lazy ops
    are charged to the step, and returns the counter dict — including the
    capture hit/fallback/eviction counters and a `_capture_state` snapshot.
    This is the measurement the programs-per-step
    arithmetic — and the analysis launch-budget pass — is defined over."""
    from ..core import lazy

    for _ in range(max(0, warmup)):
        step_fn(*args, **kwargs)
    lazy.flush_if_pending("measure_programs")
    # join any in-flight background compiles (FLAGS_eager_async_compile):
    # the measured step must replay finished programs, not race the
    # background thread into another bridged/pending resolution
    lazy.drain_async()
    reset_dispatch_counters()
    out = step_fn(*args, **kwargs)
    lazy.flush_if_pending("measure_programs")
    # dispatch_counters() is an immutable snapshot — annotate a DEEP copy
    # (nested reason/site maps included), so callers can mutate or
    # json.dumps the measurement without tripping over a mappingproxy
    counters = {
        k: dict(v) if isinstance(v, _MappingABC) else v
        for k, v in dispatch_counters().items()
    }
    counters["_step_result"] = out
    counters["_capture_state"] = lazy.step_capture_state()
    counters["_memory"] = _memory_snapshot(counters)
    try:
        from ..resilience import runtime as _resilience_rt

        counters["_resilience"] = _resilience_rt.state()
    except Exception:  # measurement must never break the profiled step
        counters["_resilience"] = None
    return counters


def _memory_snapshot(counters):
    """Measured live-buffer stats at the step boundary plus, when a
    whole-step capture replayed the step, the static analysis.memory peak
    estimate of the captured program — the estimated-vs-measured pair the
    MEMORY_PLAN.md methodology is defined over. Absolute live bytes cover
    the whole process; compare deltas or the planner's boundary estimate,
    not raw totals."""
    snap = {}
    try:
        live = jax.live_arrays()
        snap["live_buffer_bytes"] = int(
            sum(int(getattr(a, "nbytes", 0) or 0) for a in live)
        )
        snap["live_buffer_count"] = len(live)
    except Exception:
        snap["live_buffer_bytes"] = None
        snap["live_buffer_count"] = None
    if int(counters.get("capture_replays", 0) or 0) > 0:
        try:
            from ..analysis import memory as _mem

            plans = _mem.captured_step_plans()
            if plans is not None:
                plan, _no_donation = plans
                snap["estimated_captured_peak_bytes"] = int(plan.peak_bytes)
                snap["estimated_captured_boundary_bytes"] = int(
                    plan.boundary_bytes
                )
                snap["estimated_donation_credit_bytes"] = int(
                    plan.donation_credit_bytes
                )
        except Exception:
            pass  # measurement must never break the profiled step
    return snap


# ops plane (ISSUE 13): the per-process diagnostics HTTP server and the
# perf-regression sentinel. Imported LAST — both reach back into this
# package (StepTimer, metrics, trace), so they must see it initialized.
from . import sentinel  # noqa: E402,F401
from . import diag  # noqa: E402,F401


def export_protobuf(dir_name: str, worker_name=None):
    """on_trace_ready factory writing the raw trace as a protobuf-style
    binary blob (reference: profiler/profiler.py export_protobuf). The
    modern artifact here is the chrome-trace JSON; this wraps it in a
    length-prefixed binary container for API parity."""
    import json
    import os
    import struct
    import time as _time

    def _handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_{int(_time.time())}.pb")
        tmp = path + ".json"
        prof.export(tmp, "json")
        with open(tmp) as f:
            payload = f.read().encode()
        os.remove(tmp)
        with open(path, "wb") as f:
            f.write(b"PDTRACE1" + struct.pack("<Q", len(payload)) + payload)
        return path

    return _handler
