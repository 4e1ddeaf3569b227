"""What every driver needs around its window: the device, compile counts,
memory, the profiler, spans. Imports JAX, so ``run.py`` loads it only after
the cell's files have been read."""
import contextlib
import glob
import os
import shutil
import time

import jax

from . import xplane


class NoAccelerator(RuntimeError):
    pass


def device_record(chips, rehearse):
    """``device`` of the result line; raises unless ``chips`` TPUs are there
    (a rehearsal takes whatever backend there is and says so)."""
    devs = jax.devices()
    d0 = devs[0]
    if not rehearse and (d0.platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"cell needs {chips} TPU chip(s); found {len(devs)} x "
            f"{d0.platform}: no result without the accelerator")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_stats():
    return [d.memory_stats() or {} for d in jax.local_devices()]


def peak_bytes_in_use():
    return max(int(s.get("peak_bytes_in_use", 0)) for s in memory_stats())


def bytes_in_use():
    return max(int(s.get("bytes_in_use", 0)) for s in memory_stats())


class CompileCounter:
    """Backend compiles and persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _evt(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


span = jax.profiler.TraceAnnotation


class Setup:
    """The split of ``setup_s``: ``with setup.phase('trace')`` adds up."""

    def __init__(self, t_process=None):
        self.parts = {}
        if t_process is not None:  # interpreter, run.py, importing JAX
            self.parts["before_driver"] = time.perf_counter() - t_process

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.parts[name] = self.parts.get(name, 0.0) \
                + time.perf_counter() - t0

    def line(self):
        return "setup split: " + ", ".join(
            f"{k} {v:.2f}s" for k, v in self.parts.items())


class Profile:
    """Trace a stretch of the window into a directory of the checkout's
    temporary space, reduce it, and delete it."""

    def __init__(self, root):
        self.dir = os.path.join(
            os.environ.get("TMPDIR") or os.path.join(root, ".bench_tmp"),
            "bench_trace")
        self.stopped = False

    def start(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)

    def stop(self):
        self.stopped = True
        jax.profiler.stop_trace()

    def reduce(self, spans):
        (path,) = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        try:
            return xplane.reduce_file(path, spans)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Window:
    """The measured stretch: ``--seconds`` on the clock. In a traced run its
    first ``trace_seconds`` are profiled, and the time the profiler takes to
    stop is taken out of the clock."""

    def __init__(self, cell, trace_seconds):
        self.seconds = cell.seconds
        self.trace_seconds = min(trace_seconds, cell.seconds)
        self.profile = Profile(cell.root) if cell.trace else None
        self.paused = 0.0
        self.t0 = None

    def open(self):
        if self.profile:
            self.profile.start()
        self.t0 = time.perf_counter()

    def elapsed(self):
        return time.perf_counter() - self.t0 - self.paused

    def trace_due(self):
        return (self.profile is not None and not self.profile.stopped
                and self.elapsed() >= self.trace_seconds)

    def stop_trace(self):
        t = time.perf_counter()
        self.profile.stop()
        self.paused += time.perf_counter() - t

    def over(self):
        return self.elapsed() >= self.seconds

    def close(self):
        """Seconds measured; ends a trace that is still running."""
        seconds = self.elapsed()
        if self.profile and not self.profile.stopped:
            self.profile.stop()
        return seconds

    def reduce(self, spans):
        return self.profile.reduce(spans) if self.profile else None
