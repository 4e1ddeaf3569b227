"""paddle_tpu.analysis — graph verifier & lint-pass framework.

Reference analogue: the IR pass/verifier infrastructure over ProgramDesc
(paddle/fluid/framework/ir — Pass::Apply, graph_pattern_detector.h — and the
operators' InferShape/InferDtype checks). The reference verifies a proto op
graph; here every execution mode already funnels through one IR — the traced
jaxpr — so the verifier runs over flattened jaxprs obtained from any of:

  - a ``static.Program``           (``analysis.check(program)``),
  - a ``paddle.jit.to_static`` fn  (``analysis.check(static_fn, specs)``),
  - a dygraph ``nn.Layer``         (``analysis.check(layer, specs)``),
  - a plain traceable callable     (``analysis.check(fn, specs)``),
  - the pending lazy-dispatch segment (``analysis.check_pending_segment()``).

Passes are registered by name (``register_pass``) and produce structured
``Diagnostic`` records (severity, op path, shapes/dtypes involved, fix
hint). ``FLAGS_check_programs`` wires the suite into ``Executor.run``
compile time and lazy-segment flush: 1 = report every diagnostic as a
Python warning, 2 = additionally raise ``ProgramVerificationError`` on
error-severity findings.

The pattern passes need to see *through* the per-op jit wrappers (every
framework op arrives as a one-primitive ``pjit`` call), so the analysis IR
is an **inlined flat op list**: call-like equations are inlined with full
variable substitution, making cross-op producer chains (transpose∘transpose,
log∘softmax) visible, while control-flow bodies (``scan``/``while``/``cond``)
are recursed into as separate scopes.
"""
from __future__ import annotations

import dataclasses
import enum
import warnings
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

# The IR's one point of contact with JAX's jaxpr types: the other analysis
# modules import these names from here, never from jax directly.
from jax.core import DropVar, ShapedArray, eval_jaxpr
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, Var

from ..core import flags as _flags

get_aval = jax.typeof

__all__ = [
    "Severity",
    "Diagnostic",
    "ProgramVerificationError",
    "check",
    "check_pending_segment",
    "check_launch_budget",
    "enforce",
    "register_pass",
    "pass_names",
    "run_passes",
]


class Severity(enum.IntEnum):
    """Diagnostic severity; ordered so ``>= Severity.ERROR`` comparisons work."""

    INFO = 0
    WARNING = 1
    ERROR = 2

    def __str__(self):  # "error", not "Severity.ERROR", in reports
        return self.name.lower()


@dataclasses.dataclass
class Diagnostic:
    """One structured finding from an analysis pass.

    The reference's pass framework logs free text; diagnostics here carry
    the op path plus the shapes/dtypes involved so tools (and tests) can
    key on them, and a fix hint aimed at the model author."""

    severity: Severity
    pass_name: str
    op: str  # op path, e.g. "eqn[12] transpose" or "feed:x"
    message: str
    hint: str = ""
    shapes: Tuple = ()
    dtypes: Tuple = ()
    source: str = ""
    # structured payload for tools (graph_lint --json, bench): the memory
    # passes put their peak/credit arithmetic here so consumers need not
    # parse the message text
    data: Dict = dataclasses.field(default_factory=dict)

    def __str__(self):
        loc = f" [{self.source}]" if self.source else ""
        hint = f" (hint: {self.hint})" if self.hint else ""
        return (
            f"{self.severity}[{self.pass_name}]{loc} {self.op}: "
            f"{self.message}{hint}"
        )


class ProgramVerificationError(RuntimeError):
    """Raised by enforce() when FLAGS_check_programs>=2 and an error-severity
    diagnostic is present. Carries the full diagnostic list."""

    def __init__(self, message, diagnostics):
        super().__init__(message)
        self.diagnostics = list(diagnostics)


# ---------------------------------------------------------------------------
# Analysis IR: inlined flat op list over a (closed) jaxpr
# ---------------------------------------------------------------------------
class ConstAtom:
    """A closed-over constant (weights/keys baked into the trace)."""

    __slots__ = ("val", "aval")

    def __init__(self, val):
        self.val = val
        try:
            self.aval = get_aval(val)
        except Exception:  # non-array const (rare) — shapeless placeholder
            self.aval = None

    def __repr__(self):
        return f"ConstAtom({getattr(self.aval, 'str_short', lambda: '?')()})"


class CanonVar:
    """Fresh canonical SSA value for one inlined op instance's output.

    The per-op jit cache means two applications of the same op share ONE
    inner jaxpr object — its Vars are not unique across call sites — so the
    inliner mints a fresh canonical var per instance to keep the producer
    map sound."""

    __slots__ = ("aval",)

    def __init__(self, aval):
        self.aval = aval

    def __repr__(self):
        return f"CanonVar({self.aval})"


class FlatOp:
    """One primitive application in the inlined op list.

    ``invars`` are *canonical atoms*: top-level jaxpr Vars, per-instance
    CanonVars resolved across inlined call boundaries, Literals, or
    ConstAtoms — so ``producers[op.invars[0]]`` chases a producer chain even
    when each op sat in its own pjit wrapper."""

    __slots__ = ("name", "invars", "outvars", "params", "scope", "index")

    def __init__(self, name, invars, outvars, params, scope, index):
        self.name = name
        self.invars = invars
        self.outvars = outvars
        self.params = params
        self.scope = scope
        self.index = index

    @property
    def path(self) -> str:
        pre = f"{self.scope}/" if self.scope else ""
        return f"{pre}eqn[{self.index}] {self.name}"

    def __repr__(self):
        return f"<FlatOp {self.path}>"


# control-flow primitives: recursed into as separate scopes (their bodies see
# sliced/carried values, so invars cannot be substituted 1:1). shard_map is
# scoped for the same reason in the default (global-shape) analysis: its body
# vars carry PER-SHARD avals, so substituting the global-shaped outer atoms
# through the boundary would mix global and per-shard buffer sizes in one
# producer chain. The mesh-scoped analyzer (analysis.sharding) inlines
# through it instead, after rewriting every outer aval to its per-shard
# shape.
_SCOPE_PRIMS = {"scan", "while", "cond", "switch", "shard_map"}


def _as_open(j):
    """(open jaxpr, consts) from a ClosedJaxpr or a bare Jaxpr."""
    if hasattr(j, "jaxpr"):
        return j.jaxpr, list(j.consts)
    return j, []


def _sub_jaxprs(eqn):
    """('call', [sub]) for inline-with-substitution equations, ('scope', subs)
    for control-flow bodies, (None, []) for plain primitives."""
    name = eqn.primitive.name
    if name == "scan":
        return "scope", [eqn.params["jaxpr"]]
    if name == "shard_map":
        # per-shard body avals — a scope, NOT a call: the params carry a
        # "jaxpr" key, but call-inlining would substitute global-shaped
        # outer atoms for per-shard body invars (unsound sizes/chains)
        return "scope", [eqn.params["jaxpr"]]
    if name == "while":
        return "scope", [eqn.params["cond_jaxpr"], eqn.params["body_jaxpr"]]
    if name in ("cond", "switch"):
        return "scope", list(eqn.params["branches"])
    # "fun_jaxpr" is custom_vjp_call_jaxpr's primal body (custom_jvp_call
    # carries plain "call_jaxpr"): the custom-gradient API contract is that
    # the primal function and the fwd rule return the same primal outputs,
    # so determinism/equivalence analysis sees through the body as an
    # ordinary 1:1 call (arity mismatches still fall back to a scope below)
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is not None:
            return "call", [sub]
    return None, []


def _resolve(atom, env):
    if isinstance(atom, Literal):
        return atom
    return env.get(atom, atom)


def _inline(open_jaxpr, consts, env, out, producers, scope):
    for cv, cval in zip(open_jaxpr.constvars, consts):
        env[cv] = ConstAtom(cval)
    for eqn in open_jaxpr.eqns:
        kind, subs = _sub_jaxprs(eqn)
        if kind == "call":
            sub_open, sub_consts = _as_open(subs[0])
            if len(sub_open.invars) == len(eqn.invars):
                ienv = {}
                for iv, outer in zip(sub_open.invars, eqn.invars):
                    ienv[iv] = _resolve(outer, env)
                _inline(sub_open, sub_consts, ienv, out, producers, scope)
                for ov, iov in zip(eqn.outvars, sub_open.outvars):
                    env[ov] = _resolve(iov, ienv)
                continue
            kind = "scope"  # arity mismatch — keep the call opaque, recurse
        if kind == "scope":
            for si, sub in enumerate(subs):
                sub_open, sub_consts = _as_open(sub)
                ienv = {iv: iv for iv in sub_open.invars}
                tag = eqn.primitive.name + (str(si) if len(subs) > 1 else "")
                _inline(sub_open, sub_consts, ienv, out, producers,
                        f"{scope}/{tag}" if scope else tag)
        canon = [CanonVar(ov.aval) for ov in eqn.outvars]
        op = FlatOp(
            eqn.primitive.name,
            [_resolve(v, env) for v in eqn.invars],
            canon,
            eqn.params,
            scope,
            len(out),
        )
        for ov, cv in zip(eqn.outvars, canon):
            env[ov] = cv
            producers[cv] = op
        out.append(op)


def _inline_ops(closed):
    """(flat ops, producer map, resolved output atoms) for a closed jaxpr."""
    open_jaxpr, consts = _as_open(closed)
    env: Dict[Any, Any] = {v: v for v in open_jaxpr.invars}
    out: List[FlatOp] = []
    producers: Dict[Any, FlatOp] = {}
    _inline(open_jaxpr, consts, env, out, producers, "")
    out_atoms = [_resolve(v, env) for v in open_jaxpr.outvars]
    return out, producers, out_atoms


# -- atom helpers (shared with passes.py) -----------------------------------
def atom_aval(a):
    return getattr(a, "aval", None)


def atom_shape(a):
    return tuple(getattr(atom_aval(a), "shape", ()))


def atom_dtype(a):
    dt = getattr(atom_aval(a), "dtype", None)
    try:
        return np.dtype(dt) if dt is not None else None
    except TypeError:
        return None  # extended dtypes (PRNG keys)


def atom_is_weak(a):
    return bool(getattr(atom_aval(a), "weak_type", False))


_PASSTHROUGH_SCALAR = {
    "convert_element_type", "broadcast_in_dim", "reshape", "stop_gradient",
    "squeeze", "expand_dims", "copy",
}

# tiny constant folder: framework lowerings build scalar configs as
# expressions (jnp.var's N - ddof, uniform's hi - lo); fold them so the
# passes see the value, as XLA's constant folding will
_FOLD_OPS = {
    "add": (2, lambda a, b: a + b),
    "sub": (2, lambda a, b: a - b),
    "mul": (2, lambda a, b: a * b),
    "div": (2, lambda a, b: a / b if b else None),
    "max": (2, lambda a, b: max(a, b)),
    "min": (2, lambda a, b: min(a, b)),
    "neg": (1, lambda a: -a),
}


def scalar_const(atom, producers, depth=6):
    """Python scalar behind `atom`, chasing converts/broadcasts and folding
    simple constant arithmetic; None if it is not a compile-time scalar."""
    if depth <= 0:
        return None
    if isinstance(atom, (Literal, ConstAtom)):
        try:
            arr = np.asarray(atom.val)
        except Exception:
            return None
        if arr.size != 1:
            return None
        return arr.reshape(()).item()
    op = producers.get(atom)
    if op is None:
        return None
    if op.name in _PASSTHROUGH_SCALAR:
        return scalar_const(op.invars[0], producers, depth - 1)
    arity_fn = _FOLD_OPS.get(op.name)
    if arity_fn is not None and len(op.invars) == arity_fn[0]:
        vals = [scalar_const(a, producers, depth - 1) for a in op.invars]
        if all(v is not None for v in vals):
            try:
                return arity_fn[1](*vals)
            except Exception:
                return None
    return None


# ---------------------------------------------------------------------------
# Pass registry
# ---------------------------------------------------------------------------
_passes: "OrderedDict[str, Callable]" = OrderedDict()


def register_pass(name: str):
    """Decorator: register ``fn(ctx) -> List[Diagnostic]`` under ``name``."""

    def deco(fn):
        _passes[name] = fn
        return fn

    return deco


def pass_names() -> List[str]:
    return list(_passes)


class Context:
    """Everything a pass sees for one checked program."""

    def __init__(self, closed, roles, source, counters=None, budget=None,
                 donated=(), alias_groups=None, alias_refs=None,
                 memory_budget_mb=None):
        # closed=None builds a jaxpr-less context (counter-only passes like
        # launch_budget) — every field still gets its default, so passes
        # never need getattr guards against a partially-built Context
        self.closed = closed
        self.jaxpr = _as_open(closed)[0] if closed is not None else None
        # (kind, name) per jaxpr invar; kind in {"param","buffer","feed","arg"}
        self.roles: List[Tuple[str, str]] = list(roles)
        self.source = source
        self.counters = counters
        self.budget = budget
        # memory/donation info (analysis.memory): flat invar indices donated
        # to the program, groups of indices bound to one runtime buffer, and
        # {index: [description of live external alias]} from a runtime scan
        self.donated: Tuple[int, ...] = tuple(donated or ())
        self.alias_groups = list(alias_groups or [])
        self.alias_refs: Dict[int, List] = dict(alias_refs or {})
        self.memory_budget_mb = memory_budget_mb
        # mesh-scoped subclasses (analysis.sharding.ShardContext) set these
        # before delegating here; every pass can getattr-free test
        # ``ctx.mesh_axes`` to know whether avals are per-shard
        if not hasattr(self, "mesh_axes"):
            self.mesh_axes = None
        if not hasattr(self, "in_specs"):
            self.in_specs = None
        # canonical per-invar atoms: the top-level jaxpr Vars by default; a
        # mesh-scoped context replaces them with per-shard CanonVars so
        # invar_roles()/plan_memory operate on what one chip actually holds
        self.invar_atoms: List = []
        self.ops, self.producers, self.out_atoms = self._build_ir()
        if not self.invar_atoms and self.jaxpr is not None:
            self.invar_atoms = list(self.jaxpr.invars)

    def _build_ir(self):
        """(ops, producers, out_atoms) — overridden by ShardContext with the
        per-shard inliner."""
        return _inline_ops(self.closed) if self.closed is not None else ([], {}, [])

    def invar_roles(self):
        invars = list(self.invar_atoms)
        roles = self.roles
        if len(roles) < len(invars):
            roles = roles + [("arg", str(i)) for i in range(len(roles), len(invars))]
        return list(zip(invars, roles))

    def used_atoms(self):
        used = set()
        for op in self.ops:
            for a in op.invars:
                if isinstance(a, (Var, CanonVar)):
                    used.add(a)
        for a in self.out_atoms:
            if isinstance(a, (Var, CanonVar)):
                used.add(a)
        return used


def run_passes(ctx: Context, passes: Optional[Sequence[str]] = None) -> List[Diagnostic]:
    names = list(passes) if passes is not None else pass_names()
    diags: List[Diagnostic] = []
    for name in names:
        fn = _passes.get(name)
        if fn is None:
            raise ValueError(
                f"unknown analysis pass {name!r}; registered: {pass_names()}"
            )
        for d in fn(ctx):
            if not d.source:
                d.source = ctx.source
            diags.append(d)
    diags.sort(key=lambda d: (-int(d.severity), d.pass_name, d.op))
    return diags


# ---------------------------------------------------------------------------
# Feed-spec normalization + tracing front-ends
# ---------------------------------------------------------------------------
def _norm_one_spec(spec, name=None):
    from ..core.dtype import to_np_dtype

    shape = getattr(spec, "shape", None)
    if shape is not None:
        dtype = getattr(spec, "dtype", "float32")
    else:
        shape, dtype = spec  # (shape, dtype) tuple
    shape = tuple(1 if d in (None, -1) else int(d) for d in shape)
    return (name or getattr(spec, "name", None), shape, to_np_dtype(dtype))


def _norm_specs(feed_specs) -> List[Tuple[Optional[str], Tuple, np.dtype]]:
    if feed_specs is None:
        return []
    if isinstance(feed_specs, dict):
        return [_norm_one_spec(s, name=n) for n, s in sorted(feed_specs.items())]
    if not isinstance(feed_specs, (list, tuple)):
        feed_specs = [feed_specs]
    return [_norm_one_spec(s) for s in feed_specs]


def _sds(specs):
    return tuple(jax.ShapeDtypeStruct(s, d) for _, s, d in specs)


def _trace_callable(fn, specs, layer=None, source="fn"):
    """Trace `fn(*tensors)` (optionally with `layer`'s params/buffers swapped
    in as jaxpr inputs) into a closed jaxpr + invar roles.

    Params/buffers become leading invars so the dead-code pass can report
    unused parameters; buffer values after the call are appended to the
    outputs so in-place running-stat updates (BatchNorm) are not reported
    as dead code — and so no tracer ever leaks into live layer state."""
    from ..core.dispatch import no_grad
    from ..core.tensor import Tensor
    from ..jit import _bind_values, _unwrap

    params = list(layer.named_parameters()) if layer is not None else []
    buffers = list(layer.named_buffers()) if layer is not None else []
    p_ts = [p for _, p in params]
    b_ts = [b for _, b in buffers]

    def traced(p_vals, b_vals, feed_vals):
        ins = [Tensor(v, stop_gradient=True) for v in feed_vals]
        with _bind_values(p_ts + b_ts, list(p_vals) + list(b_vals)), no_grad():
            out = fn(*ins)
            new_b = [b._value for b in b_ts]
        out = _unwrap(out)
        outs = list(out) if isinstance(out, (list, tuple)) else [out]
        return outs + new_b

    p_specs = tuple(
        jax.ShapeDtypeStruct(tuple(p._value.shape), p._value.dtype) for p in p_ts
    )
    b_specs = tuple(
        jax.ShapeDtypeStruct(tuple(b._value.shape), b._value.dtype) for b in b_ts
    )
    closed = jax.make_jaxpr(traced)(p_specs, b_specs, _sds(specs))
    roles = (
        [("param", n) for n, _ in params]
        + [("buffer", n) for n, _ in buffers]
        + [("feed", n or f"arg{i}") for i, (n, _, _) in enumerate(specs)]
    )
    return closed, roles, source


def _trace_program(program, feed_specs=None):
    from ..core.dispatch import no_grad
    from ..core.dtype import to_np_dtype
    from ..core.tensor import Tensor
    from ..jit import _bind_values
    from ..static import program_guard

    import jax.numpy as jnp

    if program.builder is None:
        raise RuntimeError(
            "program has no builder; run layers under this program "
            "(or set_builder) before checking it"
        )
    if feed_specs is not None:
        specs = _norm_specs(feed_specs)
    else:
        items = sorted(program.feed_vars.items())
        specs = [
            (n, tuple(1 if d in (None, -1) else max(int(d), 1) for d in v.shape),
             to_np_dtype(v.dtype))
            for n, v in items
        ]
    names = [n for n, _, _ in specs]

    # warm eagerly first, exactly like Executor.run / Program._traced_jaxpr:
    # static.nn parameters must materialize outside any trace. Mark _warmed
    # only AFTER the run succeeds — a failed check() must not disable the
    # eager-warm path for later legitimate Executor.run calls
    if not getattr(program, "_warmed", False):
        with program_guard(program), no_grad():
            program.builder({
                n: Tensor(jnp.zeros(s, d), stop_gradient=True)
                for n, s, d in specs
            })
        program._warmed = True

    params = program.all_parameters()
    buffers = []
    for layer in program._iter_layers():
        if hasattr(layer, "named_buffers"):
            buffers.extend(layer.named_buffers())
    p_ts = list(params)
    b_ts = [b for _, b in buffers]

    def traced(p_vals, b_vals, feed_vals):
        feed = {n: Tensor(v, stop_gradient=True) for n, v in zip(names, feed_vals)}
        with _bind_values(p_ts + b_ts, list(p_vals) + list(b_vals)), \
                program_guard(program), no_grad():
            out = program.builder(feed)
            new_b = [b._value for b in b_ts]
        outs = out if isinstance(out, (list, tuple)) else [out]
        outs = [o._value if hasattr(o, "_value") else o for o in outs]
        return list(outs) + new_b

    p_specs = tuple(
        jax.ShapeDtypeStruct(tuple(p._value.shape), p._value.dtype) for p in p_ts
    )
    b_specs = tuple(
        jax.ShapeDtypeStruct(tuple(b._value.shape), b._value.dtype) for b in b_ts
    )
    closed = jax.make_jaxpr(traced)(
        p_specs, b_specs, tuple(jax.ShapeDtypeStruct(s, d) for _, s, d in specs)
    )
    roles = (
        [("param", getattr(p, "name", None) or f"param{i}")
         for i, p in enumerate(p_ts)]
        + [("buffer", n) for n, _ in buffers]
        + [("feed", n) for n in names]
    )
    return closed, roles, "Program"


def _context_of(target, feed_specs):
    from ..static import Program
    from ..jit import StaticFunction
    from ..nn.layer_base import Layer

    # raw jaxprs pass straight through (hook points hand these in)
    if hasattr(target, "jaxpr") and hasattr(target, "consts"):
        return target, [], "jaxpr"
    if hasattr(target, "eqns") and hasattr(target, "invars"):
        if getattr(target, "constvars", None):
            raise ValueError(
                "open jaxpr with constvars — pass the ClosedJaxpr instead"
            )
        return ClosedJaxpr(target, []), [], "jaxpr"

    if isinstance(target, Program):
        return _trace_program(target, feed_specs)

    # paddle.jit.to_static products
    if isinstance(target, StaticFunction):
        specs = _norm_specs(feed_specs if feed_specs is not None else target._input_spec)
        if not specs:
            raise ValueError(
                "checking a to_static function requires feed_specs (or an "
                "input_spec on the function)"
            )
        name = getattr(target._dygraph_function, "__name__", "to_static")
        return _trace_callable(
            target._converted_function, specs, layer=target._layer,
            source=f"to_static:{name}",
        )
    inner = getattr(target, "_static_fn", None)
    if isinstance(inner, StaticFunction):
        return _context_of(inner, feed_specs)

    if isinstance(target, Layer):
        specs = _norm_specs(feed_specs)
        if not specs:
            raise ValueError("checking a Layer requires feed_specs")
        fn = target.forward
        if isinstance(fn, StaticFunction):
            fn = fn._converted_function
        return _trace_callable(
            fn, specs, layer=target, source=type(target).__name__
        )

    if callable(target):
        specs = _norm_specs(feed_specs)
        if not specs:
            raise ValueError("checking a callable requires feed_specs")
        return _trace_callable(
            target, specs, layer=None,
            source=getattr(target, "__name__", "fn"),
        )
    raise TypeError(
        f"cannot analyze object of type {type(target).__name__}: expected a "
        "Program, Layer, to_static function, callable, or (closed) jaxpr"
    )


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------
def check(
    program_or_fn,
    feed_specs=None,
    *,
    passes: Optional[Sequence[str]] = None,
    counters: Optional[Dict[str, Any]] = None,
    budget: Optional[int] = None,
    source: Optional[str] = None,
    donated: Sequence[int] = (),
    alias_groups=None,
    alias_refs=None,
    memory_budget_mb: Optional[float] = None,
) -> List[Diagnostic]:
    """Run the analysis pass suite over a traced program.

    ``program_or_fn``: a ``static.Program``, ``nn.Layer``, ``to_static``
    function, plain traceable callable, or an already-traced (closed) jaxpr.
    ``feed_specs``: input shapes/dtypes — ``InputSpec`` list, ``(shape,
    dtype)`` tuples, or a ``{name: spec}`` dict. Required unless the target
    is a Program (which knows its feed vars) or carries an input_spec.
    ``donated``/``alias_groups``/``alias_refs`` feed the memory passes:
    donated flat invar indices, indices sharing one runtime buffer, and
    live-external-alias descriptions per index (see ``analysis.memory``).
    ``memory_budget_mb`` overrides ``FLAGS_memory_budget_mb`` for this run.
    Returns diagnostics sorted most-severe first."""
    closed, roles, src = _context_of(program_or_fn, feed_specs)
    ctx = Context(
        closed, roles, source or src, counters=counters, budget=budget,
        donated=donated, alias_groups=alias_groups, alias_refs=alias_refs,
        memory_budget_mb=memory_budget_mb,
    )
    return run_passes(ctx, passes)


def check_pending_segment(passes=None) -> List[Diagnostic]:
    """Analyze this thread's pending lazy-dispatch segment WITHOUT flushing
    it. Returns [] when nothing is pending."""
    from ..core import lazy

    closed = lazy.pending_segment_jaxpr()
    if closed is None:
        return []
    ctx = Context(closed, [], "lazy-segment")
    return run_passes(ctx, passes)


def check_launch_budget(step_fn=None, *args, budget=None, counters=None,
                        warmup=2, **kwargs) -> List[Diagnostic]:
    """Audit steady-state device-program launches per step against a budget.

    Reuses the dispatch counters (PR 1): runs ``step_fn`` ``warmup`` times,
    then measures one step. Alternatively pass a ``counters`` dict captured
    around a step. ``budget=None`` picks the budget from the counters: 1
    when whole-step capture replayed the step as one donated program
    (``FLAGS_eager_step_capture``), else 3 — the lazy-dispatch steady state
    (fused forward + compiled-tape backward + fused optimizer)."""
    if counters is None:
        if step_fn is None:
            raise ValueError("check_launch_budget needs a step_fn or counters")
        from ..profiler import measure_programs

        counters = measure_programs(step_fn, *args, warmup=warmup, **kwargs)
    ctx = Context(None, [], "launch-budget", counters=dict(counters),
                  budget=budget)
    return run_passes(ctx, ["launch_budget"])


def enforce(diags: List[Diagnostic], where: str, level: Optional[int] = None):
    """Apply the FLAGS_check_programs policy to a diagnostic list.

    level 0 (or empty diags): no-op. level>=1: each diagnostic becomes a
    Python warning. level>=2: error-severity findings raise
    ``ProgramVerificationError`` (after warning the rest)."""
    if level is None:
        level = int(_flags.flag("check_programs"))
    if level <= 0 or not diags:
        return diags
    errors = [d for d in diags if d.severity >= Severity.ERROR]
    for d in diags:
        warnings.warn(f"[{where}] {d}", stacklevel=3)
    if level >= 2 and errors:
        err = ProgramVerificationError(
            f"{where}: program verification failed with "
            f"{len(errors)} error-severity diagnostic(s):\n"
            + "\n".join(f"  {d}" for d in errors),
            diags,
        )
        try:
            from ..profiler import trace as _trace

            _trace.dump_postmortem(
                "verification_failed", exc=err, where=where,
                diagnostics=[str(d) for d in errors],
            )
        except Exception:
            pass  # the verdict must surface even if the dump fails
        raise err
    return diags


from . import passes as _builtin_passes  # noqa: E402,F401  (registers the suite)
from . import memory  # noqa: E402  (registers memory_budget / donation_safety)
from . import plan  # noqa: E402  (remat planner over the liveness estimates)
from . import sharding  # noqa: E402  (registers collective_cost / resharding_lint)
from . import equivalence  # noqa: E402  (registers the equivalence pass)

__all__ += ["memory", "plan", "sharding", "equivalence"]
