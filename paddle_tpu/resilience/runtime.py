"""The choke-point executor: fault injection + retry/backoff + ladder
accounting around every device-program launch.

`execute(site, thunk)` is the one wrapper the execution runtime routes
program launches through — per-op dispatch ("op"), lazy-segment flush
("segment"), compiled-tape backward ("backward"), fused optimizer update
("optimizer"), captured-step build/replay ("captured"), and checkpoint IO
("checkpoint"). It consults the fault-injection plan (synthetic faults are
raised BEFORE the thunk runs, so a retry re-executes from scratch), retries
transient failures with capped exponential backoff + jitter, and reports
every fault to the degradation ladder so repeatedly-faulting tiers demote.

Every event lands in paddle.profiler.dispatch_counters():
fault_events / injected_faults / transient_faults / fatal_faults /
retry_attempts / retry_exhausted / retry_backoff_ms / fault_sites.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Hashable, Optional

from ..core import flags
from . import faults
from . import ladder as _ladder
from . import rescue as _rescue
from . import retry as _retry

__all__ = ["execute", "lazy_tier_ok", "captured_tier_ok", "on_step_end",
           "reset", "state"]

# site → ladder tier that owns faults there. Per-op/backward/optimizer
# programs run at the ladder floor (retried, never demoted); checkpoint IO
# is not an execution tier. The serving engine's prefill/decode launches
# run at the captured tier keyed by their bucket signature — a disruptive
# fault demotes that ONE bucket's program captured→lazy→per-op while other
# buckets keep replaying their captured executables.
_SITE_TIER = {
    "segment": "lazy",
    "captured": "captured",
    "prefill": "captured",
    "decode": "captured",
}

# exception type names that must pass through untouched: control-flow and
# verdict exceptions, not faults (counted elsewhere or not at all)
_PASSTHROUGH = frozenset((
    "_CaptureIneligible",
    "ProgramVerificationError",
    "Preempted",
    "FloatingPointError",
))

_dispatch = None


def _disp():
    global _dispatch
    if _dispatch is None:
        from ..core import dispatch as d

        _dispatch = d
    return _dispatch


def execute(site: str, thunk: Callable[[], Any], *, fresh: bool = False,
            ladder_key: Hashable = None, retry_unsafe: bool = False) -> Any:
    """Run `thunk()` under the resilience policy for `site`.

    `fresh=True` marks a fresh-compile point (the thunk's first run will
    compile), enabling `compile:` fault clauses there. `ladder_key` scopes
    ladder demotion (the captured tier passes its step-signature hash).
    `retry_unsafe=True` marks a thunk whose input buffers are DONATED: a
    real transient fault from inside it may fire after XLA consumed the
    inputs, so it is never re-invoked in place — the fault is recorded as
    disruptive (the ladder demotes) and propagates to the caller's fallback
    path. Injected faults raise BEFORE the thunk runs, so they still retry."""
    plan = faults.active_plan()
    if plan is None:
        # hot path (no fault injection): one call, no flag reads; a real
        # failure re-enters below with full classify/retry/ladder handling
        try:
            return thunk()
        except BaseException as e:
            if type(e).__name__ in _PASSTHROUGH or not isinstance(e, Exception):
                raise
            pending = e
    else:
        pending = None
    max_retries = int(flags.flag("retry_max"))
    attempt = 0
    while True:
        try:
            if pending is not None:
                e, pending = pending, None
                raise e
            if plan is not None:
                step = faults.current_step()
                if fresh:
                    plan.check("compile", site, step)
                plan.check("execute", site, step)
                plan.check("hang", site, step)
            return thunk()
        except BaseException as e:
            if type(e).__name__ in _PASSTHROUGH or not isinstance(e, Exception):
                raise
            transient = _retry.is_transient(e)
            replayable = transient and not (
                retry_unsafe and not isinstance(e, faults.InjectedFault)
            )
            disruptive = not replayable or attempt >= max_retries
            _record_fault(site, e, transient, ladder_key, disruptive)
            if not replayable:
                _postmortem_escape(site, e, attempt)
                raise
            if attempt >= max_retries:
                _disp()._counters["retry_exhausted"] += 1
                _postmortem_escape(site, e, attempt)
                raise
            attempt += 1
            d = _disp()
            d._counters["retry_attempts"] += 1
            delay = _retry.default_policy().delay_ms(attempt)
            d._emit("retry", site=site, attempt=attempt,
                    delay_ms=round(delay, 2), error=type(e).__name__)
            if delay > 0:
                time.sleep(delay / 1000.0)
            d._counters["retry_backoff_ms"] += delay


def _postmortem_escape(site: str, e: BaseException, attempt: int):
    """An unrecovered fault is escaping execute() — fatal, donated-input
    unsafe, or retries exhausted. Dump a crash postmortem (no-op unless
    FLAGS_postmortem_dir is set): even when a HIGHER tier's fallback later
    completes the step, the dump records why this launch failed — site,
    retries, classification, and the flight recorder's event tail."""
    try:
        _disp()._trace_module().dump_postmortem(
            "unrecovered_fault", exc=e, site=site, retries=attempt,
            transient=_retry.is_transient(e),
            injected=isinstance(e, faults.InjectedFault),
        )
    except Exception:
        pass  # diagnostics must never add a second failure


def _record_fault(site: str, e: BaseException, transient: bool,
                  ladder_key: Hashable, disruptive: bool):
    d = _disp()
    c = d._counters
    c["fault_events"] += 1
    sites = c["fault_sites"]
    sites[site] = sites.get(site, 0) + 1
    injected = isinstance(e, faults.InjectedFault)
    if injected:
        c["injected_faults"] += 1
    c["transient_faults" if transient else "fatal_faults"] += 1
    d._emit("fault", site=site, error=type(e).__name__, transient=transient,
            injected=injected, disruptive=disruptive)
    # only DISRUPTIVE faults (fatal, or transient with retries exhausted)
    # count toward ladder demotion: a retried-and-recovered fault re-ran the
    # exact same program, so it never perturbs numerics — demoting on it
    # would switch tiers mid-run for no reliability gain
    if disruptive:
        tier = _SITE_TIER.get(site)
        if tier is not None:
            _ladder.degradation_ladder().record_fault(tier, key=ladder_key)


def lazy_tier_ok() -> bool:
    """Fast gate read by the per-op dispatcher: False while the ladder has
    the lazy tier demoted (ops then take the per-op path)."""
    return not _ladder.degradation_ladder()._lazy_demoted


def captured_tier_ok(key: Hashable = None) -> bool:
    return _ladder.degradation_ladder().allows("captured", key)


def on_step_end(source: str = "train"):
    """Optimizer.step boundary tick: advances the fault-injection step
    counter, the ladder's cooldown clocks, the stall watchdog's heartbeat
    (paddle.profiler.trace / FLAGS_trace_stall_ms), and — when
    FLAGS_sentinel_pct > 0 — the perf-regression sentinel's step-time
    baseline for `source` ('train' from optimizer.step, 'serve[<uid>]'
    from each serving engine's tick; training steps running under an
    armed whole-step capture key by its signature so a re-capture
    re-baselines)."""
    faults.advance_step()
    _ladder.degradation_ladder().step_end()
    try:
        _disp()._trace_module().step_heartbeat(source)
    except Exception:
        pass  # observability must never break the step boundary
    try:
        key = source
        if source == "train":
            from ..core import lazy as _lazy

            sig = _lazy.step_signature_id()
            if sig is not None:
                key = f"train[{sig}]"
        # attribution cost registry: the step-boundary lap feeds the
        # host-inclusive `step`-category EMA (a slowdown BETWEEN program
        # launches still attributes to its train/serve key). Inner try:
        # an attribution failure must not cost the sentinel its lap.
        try:
            from ..profiler import attribution as _attribution

            _attribution.step_lap(key)
        except Exception:
            pass
        from ..profiler import sentinel as _sentinel

        if _sentinel.PerfSentinel.enabled():
            _sentinel.default_sentinel().lap(key)
    except Exception:
        pass  # the sentinel must never break the step boundary


def state() -> dict:
    """Snapshot of the resilience runtime (profiler.measure_programs's
    `_resilience` entry reads this)."""
    return {
        "step": faults.current_step(),
        "fault_inject": str(flags.flag("fault_inject")),
        "retry_max": int(flags.flag("retry_max")),
        "numeric_rescue": _rescue.mode(),
        "ladder": _ladder.degradation_ladder().state(),
    }


def reset():
    """Reset harness + ladder state (test isolation; counters are reset
    separately via paddle.profiler.reset_dispatch_counters)."""
    faults.reset()
    _ladder.degradation_ladder().reset()
