"""Planner-guided rematerialization: `memory_budget` as an optimizer.

The PR 4 liveness planner (analysis.memory) only *reports*: it estimates a
program's peak HBM and errors past ``FLAGS_memory_budget_mb``. This module
closes the loop — it USES the per-buffer live ranges, byte sizes, and
recompute costs (the attribution registry's flop model) to pick
rematerialization points that bring the estimated peak under the budget,
and emits a structured :class:`RematPlan` that the execution layers apply.

Mechanism (validated against the planner itself): wrapping the WHOLE
forward in one ``jax.checkpoint``/policy does not move the peak — every
rematerialized value is recomputed up front and coexists through the
backward sweep, so the working set is unchanged. What does move it is
*segmented* remat: slice the traced loss jaxpr into contiguous stages at
planner-chosen cut points and wrap only the stages peak-liveness demands
in their own ``jax.checkpoint``. Each marked stage then keeps only its
boundary values live; its interior is recomputed immediately before that
stage's backward and freed after. Unmarked stages keep their residuals
saved and pay zero recompute — which is how a plan beats the uniform
per-block checkpoint configuration's flat 4/3 recompute tax: it only
recomputes the slices that actually hold the peak up.

The planner works at the granularity of the loss jaxpr's top-level
equations (one per framework op — each is a pjit-wrapped fused region),
scores candidate segmentations by predicted recompute flops, and verifies
each candidate *exactly* by retracing the caller's full step with the
sliced forward and re-running the liveness planner over it — the reported
``peak_after`` is the same estimate ``memory_plan()`` would print for the
planned program, not a model of it.

Consumers: ``jit.compile_train_step(memory_plan=...)`` (the perf path),
the whole-step capture controller in ``core/lazy.py``
(``FLAGS_memory_plan=auto``), ``tools/graph_lint.py --plan``, and the
``optimizer.offload`` scheduler (``cold_state_indices`` marks accumulator
groups live only inside the update program).
"""
from __future__ import annotations

import hashlib
import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ClosedJaxpr, Jaxpr, Literal, Var, eval_jaxpr

__all__ = [
    "RematPlan",
    "build_remat_plan",
    "sliced_callable",
    "plan_program",
    "cold_state_indices",
    "state",
]

_MB = float(1 << 20)


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape or (1,))) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def _eqn_out_bytes(eqn) -> int:
    return sum(
        _aval_bytes(v.aval) for v in eqn.outvars
        if type(v) is Var
    )


def _slice_jaxpr(parent, invars, outvars, eqns):
    """Some of ``parent``'s equations as a jaxpr of their own, with the
    parent's debug info re-sized to the slice (jax wants one on every
    Jaxpr)."""
    dbg = parent.debug_info._replace(
        arg_names=tuple(f"in{i}" for i in range(len(invars))),
        result_paths=tuple(f"out{i}" for i in range(len(outvars))))
    return Jaxpr((), invars, outvars, eqns, debug_info=dbg)


def _eqn_flops(eqn, parent) -> int:
    """Recompute cost of one top-level equation, via the attribution
    registry's flop model over its inlined flat ops (sees through the
    pjit wrapper — same estimates program_costs caches)."""
    from ..profiler.attribution import _op_flops
    from . import _inline_ops

    invars, seen = [], set()
    for a in eqn.invars:
        if isinstance(a, Var) and id(a) not in seen:
            seen.add(id(a))
            invars.append(a)
    outvars = [v for v in eqn.outvars if type(v) is Var]
    mini = _slice_jaxpr(parent, invars, outvars, [eqn])
    try:
        ops, _producers, _outs = _inline_ops(ClosedJaxpr(mini, []))
        return sum(_op_flops(op) for op in ops)
    except Exception:
        return sum(_aval_bytes(v.aval) for v in outvars)


# ---------------------------------------------------------------------------
# Jaxpr slicing: a callable that evaluates the traced loss as a sequence of
# stages, each optionally under its own jax.checkpoint
# ---------------------------------------------------------------------------
def sliced_callable(closed, stages: Sequence[Tuple[int, int, bool]]):
    """Rebuild ``closed`` (a traced ClosedJaxpr) as a callable over its flat
    invars that evaluates the equations in contiguous ``(start, end,
    remat)`` stages. A ``remat=True`` stage is wrapped in ``jax.checkpoint``
    so only its boundary values survive the forward — its interior is
    recomputed during the backward. ``stages=[(0, n, False)]`` is the
    identity (bitwise-equal to evaluating ``closed`` directly, as is any
    other segmentation: the same equations run in the same order)."""
    jx = closed.jaxpr
    consts = list(closed.consts)
    outvar_set = {v for v in jx.outvars if isinstance(v, Var)}
    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(jx.eqns):
        for a in eqn.invars:
            if isinstance(a, Var):
                last_use[a] = i

    prepared = []
    for (start, end, remat) in stages:
        eqns = jx.eqns[start:end]
        produced = set()
        for eqn in eqns:
            produced.update(eqn.outvars)
        ins, seen = [], set()
        for eqn in eqns:
            for a in eqn.invars:
                if (isinstance(a, Var) and a not in produced
                        and a not in seen):
                    seen.add(a)
                    ins.append(a)
        outs = []
        for eqn in eqns:
            for v in eqn.outvars:
                if type(v) is Var and (
                        last_use.get(v, -1) >= end or v in outvar_set):
                    outs.append(v)
        sub = _slice_jaxpr(jx, ins, outs, eqns)

        def run_stage(vals, _sub=sub):
            return eval_jaxpr(_sub, (), *vals)

        if remat:
            run_stage = jax.checkpoint(run_stage)
        prepared.append((ins, outs, run_stage))

    def run(*flat):
        env: Dict[Any, Any] = {}
        for v, c in zip(jx.constvars, consts):
            env[v] = c
        for v, a in zip(jx.invars, flat):
            env[v] = a
        for ins, outs, fn in prepared:
            vals = fn([env[a] for a in ins])
            for v, val in zip(outs, vals):
                env[v] = val
        return [
            a.val if isinstance(a, Literal) else env[a]
            for a in jx.outvars
        ]

    return run


# ---------------------------------------------------------------------------
# The plan object
# ---------------------------------------------------------------------------
class RematPlan:
    """A chosen segmentation of one traced loss program, plus the planner's
    before/after peak estimates. Apply with :meth:`bind`; persist/display
    with :meth:`to_dict` / :meth:`summary`. ``closed`` (the traced loss
    jaxpr the stages index into) rides along for application but is not
    part of the fingerprint."""

    def __init__(self, *, stages, n_eqns, budget_bytes, peak_before_bytes,
                 peak_after_bytes, recompute_flops, full_remat_flops,
                 source="", note="", evals=0, closed=None):
        self.stages = tuple((int(s), int(t), bool(r)) for s, t, r in stages)
        self.n_eqns = int(n_eqns)
        self.budget_bytes = int(budget_bytes)
        self.peak_before_bytes = int(peak_before_bytes)
        self.peak_after_bytes = int(peak_after_bytes)
        self.recompute_flops = int(recompute_flops)
        self.full_remat_flops = int(full_remat_flops)
        self.source = source
        self.note = note
        self.evals = int(evals)
        self.closed = closed

    @property
    def has_cuts(self) -> bool:
        return any(r for _s, _t, r in self.stages)

    @property
    def feasible(self) -> bool:
        return self.budget_bytes <= 0 or (
            self.peak_after_bytes <= self.budget_bytes)

    @property
    def cut_points(self) -> Tuple[int, ...]:
        """Stage-boundary equation indices (where saved activations cut the
        rematerialized region)."""
        return tuple(s for s, _t, _r in self.stages[1:])

    @property
    def recompute_pct(self) -> float:
        """Predicted recompute flops as % of one full forward — the uniform
        per-block checkpoint plan sits at 100 (the measured 4/3 step tax)."""
        if not self.full_remat_flops:
            return 0.0
        return 100.0 * self.recompute_flops / self.full_remat_flops

    def fingerprint(self) -> str:
        payload = repr((self.stages, self.n_eqns, self.budget_bytes))
        return hashlib.sha1(payload.encode()).hexdigest()[:16]

    def bind(self, closed=None) -> Callable:
        """The planned executable: ``closed``'s flat invars in, flat outvars
        out, remat stages under their own ``jax.checkpoint``."""
        target = closed if closed is not None else self.closed
        if target is None:
            raise ValueError("RematPlan.bind() needs the traced loss jaxpr")
        return sliced_callable(target, self.stages)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "source": self.source,
            "n_eqns": self.n_eqns,
            "stages": [
                {"start": s, "end": t, "remat": r} for s, t, r in self.stages
            ],
            "cut_points": list(self.cut_points),
            "budget_mb": round(self.budget_bytes / _MB, 2),
            "peak_before_mb": round(self.peak_before_bytes / _MB, 2),
            "peak_after_mb": round(self.peak_after_bytes / _MB, 2),
            "recompute_flops": self.recompute_flops,
            "full_remat_flops": self.full_remat_flops,
            "recompute_pct": round(self.recompute_pct, 1),
            "feasible": self.feasible,
            "fingerprint": self.fingerprint(),
            "evals": self.evals,
            "note": self.note,
        }

    def summary(self) -> str:
        d = self.to_dict()
        lines = [
            f"memory plan [{self.source}] "
            f"{'FEASIBLE' if self.feasible else 'INFEASIBLE'} "
            f"fingerprint={d['fingerprint']}",
            f"  peak: {d['peak_before_mb']} MB -> {d['peak_after_mb']} MB "
            f"(budget {d['budget_mb']} MB)",
            f"  recompute: {d['recompute_pct']}% of one forward "
            f"(uniform per-block checkpoint = 100%)",
        ]
        if self.has_cuts:
            marked = [f"[{s}:{t})" + ("*" if r else "")
                      for s, t, r in self.stages]
            lines.append(
                f"  stages over {self.n_eqns} top-level eqns "
                f"(* = rematerialized): " + " ".join(marked))
            lines.append(f"  cut points (saved boundaries): "
                         f"{list(self.cut_points)}")
        else:
            why = self.note or "peak already under budget"
            lines.append(f"  no cuts chosen ({why})")
        return "\n".join(lines)

    def __repr__(self):
        return (f"RematPlan(source={self.source!r}, "
                f"peak={self.peak_before_bytes / _MB:.1f}->"
                f"{self.peak_after_bytes / _MB:.1f}MB, "
                f"budget={self.budget_bytes / _MB:.1f}MB, "
                f"cuts={list(self.cut_points)}, "
                f"recompute={self.recompute_pct:.0f}%, "
                f"feasible={self.feasible})")


# ---------------------------------------------------------------------------
# The planner
# ---------------------------------------------------------------------------
def _byte_balanced_bounds(weights: List[int], k: int) -> List[int]:
    """Split ``range(len(weights))`` into ``k`` contiguous chunks of roughly
    equal total weight (per-eqn output bytes) — balanced interiors keep the
    largest co-resident recompute working set small."""
    n = len(weights)
    total = max(1, sum(weights))
    bounds = [0]
    acc = 0
    target = total / k
    for i, w in enumerate(weights):
        acc += w
        while len(bounds) < k and acc >= target * len(bounds):
            nxt = i + 1
            if nxt > bounds[-1] and nxt < n:
                bounds.append(nxt)
            else:
                break
    while len(bounds) < k:
        nxt = min(n - 1, bounds[-1] + 1)
        if nxt <= bounds[-1]:
            break
        bounds.append(nxt)
    bounds.append(n)
    return bounds


def build_remat_plan(loss_closed, *, budget_bytes: int, measure: Callable,
                     source: str = "loss", max_evals: int = 8,
                     min_gain: float = 0.01) -> RematPlan:
    """Pick a segmentation of ``loss_closed`` whose *measured* whole-step
    peak fits ``budget_bytes``, spending as little recompute as possible.

    ``measure(stage_callable_or_None) -> peak_bytes`` is the caller's
    oracle: it must retrace its full step (forward + backward + update)
    with the given planned loss callable substituted in (``None`` = the
    unplanned step) and return the liveness planner's peak estimate — so
    every number in the plan is the exact figure ``memory_plan()`` reports
    for that program, not an approximation.

    Candidates are staged segmentations at increasing cut counts; within
    each, the *earliest* stages are marked for remat first (their residuals
    span the whole backward, so they are what holds the peak up) and the
    tail stage is kept saved — recompute stays strictly below the uniform
    per-block plan whenever such a candidate fits. Evaluation stops at the
    first (cheapest) feasible candidate, or falls back to the best peak
    seen (``min_gain`` improvement required) when the budget is
    unreachable — e.g. a captured-step program whose op outputs all escape
    to the host, which no remat can shrink."""
    jx = loss_closed.jaxpr
    n = len(jx.eqns)
    t0 = time.perf_counter()
    peak_before = int(measure(None))
    evals = 1

    flops = [_eqn_flops(e, jx) for e in jx.eqns]
    out_bytes = [_eqn_out_bytes(e) for e in jx.eqns]
    full_flops = sum(flops)

    def finish(stages, peak_after, note):
        plan = RematPlan(
            stages=stages, n_eqns=n, budget_bytes=budget_bytes,
            peak_before_bytes=peak_before, peak_after_bytes=peak_after,
            recompute_flops=sum(
                sum(flops[s:t]) for s, t, r in stages if r),
            full_remat_flops=full_flops, source=source, note=note,
            evals=evals, closed=loss_closed,
        )
        _record(source, plan, (time.perf_counter() - t0) * 1000.0)
        return plan

    identity = [(0, n, False)]
    if budget_bytes <= 0 or peak_before <= budget_bytes:
        return finish(identity, peak_before, "peak already under budget")
    if n < 2:
        return finish(identity, peak_before, "program too small to slice")

    # candidate family: K byte-balanced stages, earliest m marked remat —
    # ordered globally by predicted recompute flops so the first feasible
    # candidate is also the cheapest one tried
    candidates = []
    for k in (2, 3, 4, 6, 8, 12, 16, 24, 32):
        if k > n:
            break
        bounds = _byte_balanced_bounds(out_bytes, k)
        k = len(bounds) - 1  # fewer chunks when the tail eqns hold the bytes
        for m in sorted({max(1, k // 2), max(1, k - 1), k}):
            stages = [
                (bounds[i], bounds[i + 1], i < m) for i in range(k)
            ]
            cost = sum(sum(flops[s:t]) for s, t, r in stages if r)
            candidates.append((cost, k, stages))
    candidates.sort(key=lambda c: (c[0], c[1]))

    seen, ordered = set(), []
    for cost, k, stages in candidates:
        sig = tuple(stages)
        if sig not in seen:
            seen.add(sig)
            ordered.append((cost, stages))

    # bisect the cost-ordered candidate list for the cheapest feasible
    # segmentation: more remat monotonically (in this family) trades flops
    # for peak, so log2(len) exact measurements find the frontier instead
    # of burning the eval budget on cheap plans that cannot fit
    best_stages, best_peak = identity, peak_before
    measured: Dict[int, int] = {}

    def peak_of(idx: int) -> int:
        nonlocal evals, best_stages, best_peak
        if idx not in measured:
            stages = ordered[idx][1]
            measured[idx] = int(measure(sliced_callable(loss_closed, stages)))
            evals += 1
            if measured[idx] < best_peak:
                best_stages, best_peak = stages, measured[idx]
        return measured[idx]

    lo, hi = 0, len(ordered) - 1
    found = None
    while lo <= hi and evals < max_evals:
        mid = (lo + hi) // 2
        if peak_of(mid) <= budget_bytes:
            found = mid
            hi = mid - 1
        else:
            lo = mid + 1
    # peak is only approximately monotone in recompute cost (an all-remat
    # high-K plan saves MORE boundaries than a lower-K one) — spend any
    # remaining evals walking left from the frontier toward cheaper
    # candidates the bisection's monotonicity assumption skipped
    if found is not None:
        i = found - 1
        while i >= 0 and evals < max_evals:
            if i not in measured and peak_of(i) <= budget_bytes:
                found = i
            i -= 1
    if found is not None:
        return finish(ordered[found][1], measured[found], "")

    if best_peak < peak_before * (1.0 - min_gain):
        return finish(best_stages, best_peak,
                      "budget unreachable; best reduction kept")
    return finish(identity, peak_before,
                  "remat cannot reduce this program's peak")


# ---------------------------------------------------------------------------
# Cold optimizer state (feeds paddle_tpu.optimizer.offload)
# ---------------------------------------------------------------------------
def cold_state_indices(closed, roles) -> List[Tuple[int, str]]:
    """Flat invar indices (+ role names) of optimizer-state inputs that are
    *cold*: first read only inside the trailing update program — after the
    last forward read of every feed input and past the midpoint of the
    step. Their buffers are dead through the forward + backward, which is
    exactly the window the offload scheduler parks them on the host."""
    jx = closed.jaxpr
    first_read: Dict[Any, int] = {}
    last_read: Dict[Any, int] = {}
    for i, eqn in enumerate(jx.eqns):
        for a in eqn.invars:
            if isinstance(a, Var):
                first_read.setdefault(a, i)
                last_read[a] = i
    n = max(1, len(jx.eqns))
    feed_horizon = -1
    for v, (kind, _name) in zip(jx.invars, roles):
        if kind == "feed" and v in first_read:
            feed_horizon = max(feed_horizon, first_read[v])
    cold = []
    for i, (v, (kind, name)) in enumerate(zip(jx.invars, roles)):
        if kind != "buffer" or not str(name).startswith("opt_state"):
            continue
        fr = first_read.get(v)
        if fr is None:
            continue  # unread state passes through — trivially cold, but
            # offloading it saves nothing the donation didn't already
        if fr > feed_horizon and fr >= n // 2:
            cold.append((i, str(name)))
    return cold


# ---------------------------------------------------------------------------
# Whole-program planning for external callables (graph_lint --plan)
# ---------------------------------------------------------------------------
def plan_program(target, feed_specs=None, *, memory_budget_mb=None,
                 source=None, max_evals: int = 8) -> RematPlan:
    """Plan remat for a model/program the way ``graph_lint --plan`` sees it:
    trace the forward, wrap it in a synthetic training step (sum-of-outputs
    loss, vjp over the parameter inputs), and search segmentations of the
    forward until the step's planner peak fits the budget."""
    from . import Context, _context_of
    from ..core import flags as _flags
    from . import memory as _memory

    closed, roles, src = _context_of(target, feed_specs)
    source = source or f"plan:{src}"
    budget_mb = (float(_flags.flag("memory_budget_mb"))
                 if memory_budget_mb is None else float(memory_budget_mb))
    budget_bytes = int(budget_mb * _MB)

    jx = closed.jaxpr
    invars = list(jx.invars)
    roles = list(roles) + [("arg", f"in{i}")
                           for i in range(len(invars) - len(roles))]
    # differentiate w.r.t. the parameter inputs (all float inputs when the
    # target carries no roles — a bare callable's args are its "params")
    has_params = any(kind == "param" for kind, _ in roles)
    diff_idx = [
        i for i, (v, (kind, _n)) in enumerate(zip(invars, roles))
        if np.issubdtype(np.dtype(v.aval.dtype), np.inexact)
        and (kind == "param" or not has_params)
    ]
    if not diff_idx:
        raise ValueError(
            f"{source}: no differentiable (float) inputs to plan a "
            "training step over")
    specs = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype) for v in invars]

    def measure(stage_fn) -> int:
        run = stage_fn if stage_fn is not None else sliced_callable(
            closed, [(0, len(jx.eqns), False)])

        def step(*args):
            def lf(dvals):
                full = list(args)
                for i, v in zip(diff_idx, dvals):
                    full[i] = v
                outs = run(*full)
                tot = jnp.zeros((), jnp.float32)
                for o in outs:
                    if np.issubdtype(np.dtype(o.dtype), np.inexact):
                        tot = tot + jnp.sum(o.astype(jnp.float32))
                return tot
            lval, vjp = jax.vjp(lf, tuple(args[i] for i in diff_idx))
            (grads,) = vjp(jnp.ones((), jnp.float32))
            return lval, grads

        step_closed = jax.make_jaxpr(step)(*specs)
        ctx = Context(step_closed, roles, source)
        return _memory.plan_memory(ctx).peak_bytes

    return build_remat_plan(closed, budget_bytes=budget_bytes,
                            measure=measure, source=source,
                            max_evals=max_evals)


# ---------------------------------------------------------------------------
# Module state: last plan per source (for /statusz, metrics, events)
# ---------------------------------------------------------------------------
_lock = threading.Lock()
_state: Dict[str, Dict[str, Any]] = {}


def _record(source: str, plan: RematPlan, build_ms: float) -> None:
    doc = plan.to_dict()
    doc["build_ms"] = round(build_ms, 2)
    with _lock:
        _state[source] = doc
    try:
        from ..core import dispatch

        dispatch._counter_add("memory_plan_builds", 1)
        dispatch._emit(
            "memory_plan", site=source, phase="built",
            fingerprint=doc["fingerprint"], feasible=doc["feasible"],
            peak_before_mb=doc["peak_before_mb"],
            peak_after_mb=doc["peak_after_mb"],
            recompute_pct=doc["recompute_pct"],
        )
    except Exception:
        pass
    try:
        from ..profiler import metrics as _metrics

        reg = _metrics.default_registry()
        labels = {"source": source}
        reg.gauge("memory_plan_peak_before_mb",
                  doc="planner peak estimate before remat, MB",
                  labels=labels).set(doc["peak_before_mb"])
        reg.gauge("memory_plan_peak_after_mb",
                  doc="planner peak estimate with the chosen plan, MB",
                  labels=labels).set(doc["peak_after_mb"])
        reg.gauge("memory_plan_recompute_pct",
                  doc="predicted recompute as % of one forward "
                      "(uniform per-block checkpoint = 100)",
                  labels=labels).set(doc["recompute_pct"])
    except Exception:
        pass


def record_failure(source: str, err: BaseException) -> None:
    """Book a plan-build failure (the execution layers call this before
    falling back to the unplanned step)."""
    with _lock:
        _state[source] = {
            "source": source, "failed": True,
            "error": f"{type(err).__name__}: {err}",
        }
    try:
        from ..core import dispatch

        dispatch._counter_add("memory_plan_failures", 1)
        dispatch._emit("memory_plan", site=source, phase="failed",
                       error=type(err).__name__)
    except Exception:
        pass


def state() -> Dict[str, Any]:
    """Snapshot of the last plan (or failure) per source — the /statusz
    'memory plan & offload' section reads this."""
    with _lock:
        return {k: dict(v) for k, v in _state.items()}


def _reset_state() -> None:  # tests
    with _lock:
        _state.clear()
