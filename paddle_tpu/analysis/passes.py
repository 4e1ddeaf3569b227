"""Built-in analysis passes over the inlined flat-op IR.

Reference analogue: fluid/framework/ir pass suite (identity_scale_op_clean,
delete_dropout_op, transpose folding in transfer_layout_elim_pass, the
is_test/AMP audits) plus the operators' InferDtype checks — reimplemented as
jaxpr-level lints. Each pass is ``fn(ctx) -> List[Diagnostic]`` registered by
name; severity policy:

  ERROR   — will produce wrong numbers or fail on TPU (f64 upcast,
            unguarded log),
  WARNING — probably a bug or a real perf hazard (dead op, redundant pair,
            fp16 long-axis sum, possible div-by-zero),
  INFO    — worth knowing, often benign (fusable transpose pair, bf16
            accumulation note).
"""
from __future__ import annotations

from typing import List

import jax
import numpy as np

from . import (
    Context,
    DropVar,
    Literal,
    Var,
    Diagnostic,
    Severity,
    atom_dtype,
    atom_is_weak,
    atom_shape,
    register_pass,
    scalar_const,
    _as_open,
    _sub_jaxprs,
)

_F64 = np.dtype(np.float64)
_F32 = np.dtype(np.float32)
_F16 = np.dtype(np.float16)
_BF16 = np.dtype("bfloat16") if hasattr(np, "dtype") else None
try:
    _BF16 = np.dtype(jax.numpy.bfloat16)
except Exception:  # pragma: no cover
    _BF16 = None

_LOW_PRECISION = {d for d in (_F16, _BF16) if d is not None}


def _is_float(dt):
    # jnp.issubdtype, not np: bfloat16/float8 are ml_dtypes extensions that
    # numpy's floating hierarchy does not know about
    try:
        return dt is not None and jax.numpy.issubdtype(dt, jax.numpy.floating)
    except TypeError:
        return False


def _is_real(v):
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _float_dtypes(op):
    out = []
    for v in op.outvars:
        dt = atom_dtype(v)
        if _is_float(dt):
            out.append(dt)
    return out


# ---------------------------------------------------------------------------
# 1. shape/dtype verifier
# ---------------------------------------------------------------------------
_NARROW_FLOATS = {np.dtype(np.float32), _F16} | ({_BF16} if _BF16 else set())


@register_pass("dtype_check")
def dtype_check(ctx: Context) -> List[Diagnostic]:
    diags = []
    # -- silent float64 upcast: TPUs have no native f64. Flag the upcast
    # POINT — an op where a narrower float input becomes a non-weak f64
    # output (usually a numpy float64 scalar/array promotion). f64 derived
    # purely from integer bits (the RNG uniform's bitcast trick) or from
    # values that were already f64 is framework lowering, not an upcast.
    for op in ctx.ops:
        if not any(atom_dtype(a) in _NARROW_FLOATS for a in op.invars):
            continue
        for v in op.outvars:
            if atom_dtype(v) == _F64 and not atom_is_weak(v):
                diags.append(Diagnostic(
                    Severity.ERROR, "dtype_check", op.path,
                    "silent float64 upcast: "
                    f"{atom_dtype(op.invars[0])} input becomes float64",
                    hint="cast to float32/bfloat16 — check numpy float64 "
                         "scalars/arrays entering the graph and "
                         "jax_enable_x64",
                    shapes=(atom_shape(v),), dtypes=("float64",),
                ))
                break  # one diagnostic per op is enough

    # -- AMP bf16/f32 mixing audit: matmul/conv compute split across float
    # widths in one program means the autocast policy is not being applied
    # consistently (some casts will dominate step time, some accuracy)
    heavy = [op for op in ctx.ops
             if op.name in ("dot_general", "conv_general_dilated")]
    widths = {}
    for op in heavy:
        for dt in _float_dtypes(op):
            widths.setdefault(dt, op)
    low = [d for d in widths if d in _LOW_PRECISION]
    if low and _F32 in widths:
        lo = low[0]
        diags.append(Diagnostic(
            Severity.WARNING, "dtype_check", widths[_F32].path,
            f"mixed-precision compute: both {lo} and float32 "
            "matmul/conv ops in one program",
            hint="run the model under paddle.amp.auto_cast (O1/O2) or cast "
                 "weights/inputs consistently; stray f32 matmuls forfeit "
                 "most of the AMP speedup",
            dtypes=(str(lo), "float32"),
        ))

    # -- feed dtype mismatch: a float feed whose every use first converts it
    # to another float width was declared with the wrong dtype
    for invar, (kind, name) in ctx.invar_roles():
        if kind != "feed":
            continue
        dt = atom_dtype(invar)
        if not _is_float(dt):
            continue
        consumers = [op for op in ctx.ops if invar in op.invars]
        if not consumers:
            continue
        casts = {
            np.dtype(op.params["new_dtype"])
            for op in consumers
            if op.name == "convert_element_type"
        }
        if len(casts) == 1 and len(consumers) == len(
            [op for op in consumers if op.name == "convert_element_type"]
        ):
            (target,) = casts
            if target != dt and _is_float(target):
                diags.append(Diagnostic(
                    Severity.WARNING, "dtype_check", f"feed:{name}",
                    f"feed '{name}' declared {dt} but every use first casts "
                    f"it to {target}",
                    hint=f"declare the feed as {target} (or drop the casts) "
                         "to avoid a per-step convert",
                    dtypes=(str(dt), str(target)),
                ))
    return diags


# ---------------------------------------------------------------------------
# 2. dead code / unused feeds / unused parameters
# ---------------------------------------------------------------------------
def _eqn_label(eqn):
    """Human-readable primitive name(s) for a (possibly call-like) eqn."""
    kind, subs = _sub_jaxprs(eqn)
    if kind == "call":
        sub_open, _ = _as_open(subs[0])
        names = [e.primitive.name for e in sub_open.eqns
                 if e.primitive.name != "convert_element_type"]
        if not names:
            names = [e.primitive.name for e in sub_open.eqns]
        if len(names) == 1:
            inner_eqn = [e for e in sub_open.eqns
                         if e.primitive.name == names[0]][0]
            return _eqn_label(inner_eqn)
        if names:
            return "+".join(names[:3]) + ("…" if len(names) > 3 else "")
    return eqn.primitive.name


def _dead_eqns(open_jaxpr, path, acc, index_base=0):
    live = {v for v in open_jaxpr.outvars if isinstance(v, Var)}
    status = []
    for eqn in reversed(open_jaxpr.eqns):
        is_live = bool(getattr(eqn, "effects", None)) or any(
            not isinstance(ov, DropVar) and ov in live
            for ov in eqn.outvars
        )
        status.append((eqn, is_live))
        if is_live:
            live.update(v for v in eqn.invars if isinstance(v, Var))
    for i, (eqn, is_live) in enumerate(reversed(status)):
        here = f"{path}eqn[{i}]"
        if not is_live:
            # zero-output equations are framework no-ops (XLA erases them),
            # not user defects — only value-producing dead ops are findings
            # (a fully-unused output shows up as a DropVar, which still
            # counts: the computation itself is the waste)
            if len(eqn.outvars) > 0:
                acc.append((eqn, here))
        else:
            kind, subs = _sub_jaxprs(eqn)
            for si, sub in enumerate(subs):
                sub_open, _ = _as_open(sub)
                tag = eqn.primitive.name + (str(si) if len(subs) > 1 else "")
                _dead_eqns(sub_open, f"{here}/{tag}/", acc)


@register_pass("dead_code")
def dead_code(ctx: Context) -> List[Diagnostic]:
    diags = []
    dead = []
    _dead_eqns(ctx.jaxpr, "", dead)
    for eqn, path in dead:
        shapes = tuple(tuple(getattr(v.aval, "shape", ())) for v in eqn.outvars)
        diags.append(Diagnostic(
            Severity.WARNING, "dead_code", f"{path} {_eqn_label(eqn)}",
            "dead op: results are never used",
            hint="remove the computation (it still costs compile time, and "
                 "under eager dispatch it runs)",
            shapes=shapes,
        ))
    used = ctx.used_atoms()
    for invar, (kind, name) in ctx.invar_roles():
        if invar in used:
            continue
        if kind == "feed":
            diags.append(Diagnostic(
                Severity.WARNING, "dead_code", f"feed:{name}",
                f"unused feed '{name}': declared but never consumed",
                hint="drop the static.data declaration or wire it into the "
                     "program",
                shapes=(atom_shape(invar),),
            ))
        elif kind == "param":
            diags.append(Diagnostic(
                Severity.WARNING, "dead_code", f"param:{name}",
                f"unused parameter '{name}': it will train as dead weight",
                hint="delete the parameter or stop passing it to the "
                     "optimizer",
                shapes=(atom_shape(invar),),
            ))
        elif kind == "buffer":
            diags.append(Diagnostic(
                Severity.INFO, "dead_code", f"buffer:{name}",
                f"unused buffer '{name}'",
                shapes=(atom_shape(invar),),
            ))
    return diags


# ---------------------------------------------------------------------------
# 3. redundant-op patterns
# ---------------------------------------------------------------------------
def _perm_compose(p1, p2):
    # result of transpose(transpose(x, p1), p2)
    return tuple(p1[i] for i in p2)


def _from_rng(atom, producers, depth=12):
    """True when `atom` derives from raw random bits / bitcasts — arithmetic
    there is framework RNG lowering (uniform = bits*(hi-lo)+lo), not user
    code, and not worth a lint."""
    stack = [atom]
    seen = 0
    while stack and seen < depth:
        a = stack.pop()
        seen += 1
        op = producers.get(a)
        if op is None:
            continue
        if op.name.startswith("random_") or op.name in (
            "bitcast_convert_type", "threefry2x32",
        ):
            return True
        stack.extend(a for a in op.invars if not isinstance(a, Literal))
    return False


@register_pass("redundant_ops")
def redundant_ops(ctx: Context) -> List[Diagnostic]:
    diags = []
    prod = ctx.producers
    for op in ctx.ops:
        if op.name == "transpose":
            p = prod.get(op.invars[0])
            if p is not None and p.name == "transpose":
                perm = _perm_compose(
                    tuple(p.params["permutation"]),
                    tuple(op.params["permutation"]),
                )
                if perm == tuple(range(len(perm))):
                    diags.append(Diagnostic(
                        Severity.WARNING, "redundant_ops", op.path,
                        "transpose∘transpose cancels out to identity",
                        hint="remove both transposes",
                        shapes=(atom_shape(op.invars[0]),),
                    ))
                else:
                    diags.append(Diagnostic(
                        Severity.INFO, "redundant_ops", op.path,
                        "back-to-back transposes",
                        hint=f"fuse into one transpose with perm={list(perm)}",
                    ))
        elif op.name in ("mul", "add", "sub", "div"):
            checks = {
                "mul": ((0, 1.0), (1, 1.0)),
                "add": ((0, 0.0), (1, 0.0)),
                "sub": ((1, 0.0),),
                "div": ((1, 1.0),),
            }[op.name]
            for idx, ident in checks:
                if idx >= len(op.invars):
                    continue
                v = scalar_const(op.invars[idx], prod)
                if _is_real(v) and float(v) == ident:
                    other = op.invars[1 - idx]
                    # const∘const is a compile-time expression XLA folds for
                    # free, and arithmetic on raw RNG bits is the uniform
                    # lowering — neither is a user-level finding
                    if scalar_const(other, prod) is not None:
                        break
                    if _from_rng(other, prod):
                        break
                    expr = {"mul": "x*1", "add": "x+0", "sub": "x-0",
                            "div": "x/1"}[op.name]
                    diags.append(Diagnostic(
                        Severity.WARNING, "redundant_ops", op.path,
                        f"identity arithmetic: {expr} is a no-op",
                        hint="drop the op (likely a stale scale/bias or a "
                             "disabled branch left in the graph)",
                        shapes=(atom_shape(op.outvars[0]),),
                    ))
                    break
        elif op.name in ("reduce_sum", "reduce_max", "reduce_min",
                         "reduce_prod"):
            p = prod.get(op.invars[0])
            if p is not None and p.name == "broadcast_in_dim":
                in_shape = atom_shape(p.invars[0])
                out_shape = tuple(p.params["shape"])
                bdims = tuple(p.params["broadcast_dimensions"])
                expanded = {
                    d for d in range(len(out_shape))
                    if d not in bdims
                    or in_shape[bdims.index(d)] != out_shape[d]
                }
                hit = expanded & set(op.params.get("axes", ()))
                if hit:
                    factor = int(np.prod([out_shape[d] for d in hit]))
                    diags.append(Diagnostic(
                        Severity.WARNING, "redundant_ops", op.path,
                        "broadcast-then-reduce: materializes and reduces "
                        f"{factor}× redundant data",
                        hint="reduce before broadcasting, or express the "
                             "contraction as matmul/einsum",
                        shapes=(in_shape, out_shape),
                    ))
        elif op.name == "log":
            p = prod.get(op.invars[0])
            if p is not None and p.name == "div":
                pn = prod.get(p.invars[0])
                if pn is not None and pn.name == "exp":
                    diags.append(Diagnostic(
                        Severity.WARNING, "redundant_ops", op.path,
                        "log(softmax(x)) computed as two ops",
                        hint="use F.log_softmax: one fused op, and it cannot "
                             "underflow to log(0) = -inf",
                    ))
        elif op.name in ("psum", "psum_invariant") and \
                getattr(ctx, "mesh_axes", None) is None:
            # collective idioms on plain contexts; a mesh-scoped context
            # defers to resharding_lint (analysis.sharding) so the full
            # suite never reports one defect twice
            p = prod.get(op.invars[0]) if op.invars else None
            if p is not None and p.name in ("psum", "psum_invariant"):
                a0 = set(_coll_axis_names(op.params))
                a1 = set(_coll_axis_names(p.params))
                # psum(psum(x, 'a'), 'b') is the legitimate staged two-axis
                # reduction — only the SAME axis set is redundant
                if a0 and a0 == a1:
                    diags.append(Diagnostic(
                        Severity.WARNING, "redundant_ops", op.path,
                        f"psum∘psum over the same axis {sorted(a0)}: the "
                        "second all-reduce multiplies by the group size and "
                        "doubles the wire traffic",
                        hint="reduce once (or psum(x, ('a','b')) for one "
                             "fused all-reduce over both axes)",
                        shapes=(atom_shape(op.invars[0]),),
                    ))
        elif op.name in ("slice", "dynamic_slice", "squeeze") and \
                getattr(ctx, "mesh_axes", None) is None:
            p = prod.get(op.invars[0]) if op.invars else None
            if p is not None and p.name == "all_gather" and \
                    atom_shape(op.outvars[0]) == atom_shape(p.invars[0]):
                diags.append(Diagnostic(
                    Severity.WARNING, "redundant_ops", op.path,
                    "all_gather immediately sliced back to the local shard: "
                    "a full-axis round trip that ends where it started",
                    hint="drop the gather (the shard is already local) or "
                         "keep the gathered value if other shards are read",
                    shapes=(atom_shape(p.invars[0]),),
                ))
    return diags


def _coll_axis_names(params):
    ax = params.get("axes", params.get("axis_name"))
    if ax is None:
        return ()
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


# ---------------------------------------------------------------------------
# 4. numerical-hazard lint
# ---------------------------------------------------------------------------
# ops that preserve the sign/positivity property we are chasing
_CHAIN_PASSTHROUGH = {
    "broadcast_in_dim", "reshape", "convert_element_type", "stop_gradient",
    "transpose", "squeeze", "expand_dims", "copy", "slice", "dynamic_slice",
    "concatenate", "reduce_sum", "min", "reduce_window_sum",
    # sqrt preserves positivity, so x/sqrt(var+eps) chases through to +eps
    "sqrt", "rsqrt",
}
# ops whose output is strictly positive (guard log/div) given any input
_POSITIVE = {"exp", "logistic"}
# ops whose output is non-negative (guard sqrt)
_NONNEG = {"abs", "square"} | _POSITIVE


def _guarded(atom, ctx, nonneg_ok=False, depth=8):
    """Best-effort proof that `atom` is positive (or ≥0 when nonneg_ok):
    chases the producer chain through shape/convert ops looking for a
    guarding op (clip/max with a positive floor, +eps, exp/sigmoid, |x|,
    x², even powers)."""
    prod = ctx.producers
    seen = 0
    stack = [atom]
    while stack and seen < depth:
        a = stack.pop()
        seen += 1
        v = scalar_const(a, prod)
        if v is not None:
            if _is_real(v) and (v > 0 or (nonneg_ok and v >= 0)):
                return True
            continue
        op = prod.get(a)
        if op is None:
            continue
        if op.name in _POSITIVE:
            return True
        if nonneg_ok and op.name in _NONNEG:
            return True
        if op.name == "max":  # clip floor: max(c, x) with c > 0 (≥ 0)
            for o in op.invars:
                c = scalar_const(o, prod)
                if _is_real(c) and (c > 0 or (nonneg_ok and c >= 0)):
                    return True
            stack.extend(op.invars)  # max of guarded values is guarded
        elif op.name == "add":  # x + eps heuristic (eps a positive scalar)
            for o in op.invars:
                c = scalar_const(o, prod)
                if _is_real(c) and c > 0:
                    return True
        elif op.name == "integer_pow":
            if int(op.params.get("y", 1)) % 2 == 0 and nonneg_ok:
                return True
        elif op.name == "mul":  # x*x is ≥ 0
            if nonneg_ok and len(op.invars) == 2 and \
                    op.invars[0] is op.invars[1]:
                return True
        elif op.name == "select_n" and len(op.invars) == 3:
            # the explicit zero-replacement guard — where(x == 0, c, x)
            # with c > 0 (the safe-softmax / flash-attention idiom):
            # the zero case is replaced by a positive constant and every
            # other case is x itself, so the result never hits zero
            pred, c0, c1 = op.invars
            try:
                pop = prod.get(pred)
            except TypeError:  # Literal predicate
                pop = None
            if pop is not None and pop.name == "eq":
                for const_case, x_case in ((c0, c1), (c1, c0)):
                    c = scalar_const(const_case, prod)
                    if not (_is_real(c) and c > 0):
                        continue
                    cmp = [scalar_const(o, prod) for o in pop.invars]
                    if any(v == 0 for v in cmp if _is_real(v)) and \
                            any(o is x_case for o in pop.invars):
                        return True
        elif op.name in _CHAIN_PASSTHROUGH:
            stack.append(op.invars[0])
    return False


@register_pass("numeric_hazards")
def numeric_hazards(ctx: Context) -> List[Diagnostic]:
    diags = []
    roles = dict(ctx.invar_roles())
    for op in ctx.ops:
        if op.name == "log":
            if not _guarded(op.invars[0], ctx):
                diags.append(Diagnostic(
                    Severity.ERROR, "numeric_hazards", op.path,
                    "unguarded log: operand can reach 0 or go negative "
                    "(NaN/-inf)",
                    hint="clip first (paddle.log(paddle.clip(x, min=eps))), "
                         "or use paddle.log1p / F.log_softmax",
                    shapes=(atom_shape(op.invars[0]),),
                    dtypes=(str(atom_dtype(op.invars[0])),),
                ))
        elif op.name == "div":
            if len(op.invars) > 1:
                den = op.invars[1]
                c = scalar_const(den, ctx.producers)
                if c is not None and c != 0:
                    continue
                if not _guarded(den, ctx):
                    diags.append(Diagnostic(
                        Severity.WARNING, "numeric_hazards", op.path,
                        "possible division by zero: denominator has no "
                        "positivity guard",
                        hint="add an epsilon (x / (d + eps)) or clip the "
                             "denominator",
                        shapes=(atom_shape(den),),
                    ))
        elif op.name in ("sqrt", "rsqrt"):
            if not _guarded(op.invars[0], ctx, nonneg_ok=(op.name == "sqrt")):
                diags.append(Diagnostic(
                    Severity.WARNING, "numeric_hazards", op.path,
                    f"unguarded {op.name}: negative input gives NaN"
                    + ("" if op.name == "sqrt" else ", zero gives inf"),
                    hint="add an epsilon under the root "
                         f"({op.name}(x + eps)) or clip to ≥ 0",
                    shapes=(atom_shape(op.invars[0]),),
                ))
        elif op.name == "exp":
            a = op.invars[0]
            if a in roles and roles[a][0] in ("feed", "arg"):
                diags.append(Diagnostic(
                    Severity.WARNING, "numeric_hazards", op.path,
                    "exp applied directly to a raw input: overflows to inf "
                    "beyond ~88 (f32) / ~11 (f16)",
                    hint="normalize first (subtract the max, as softmax "
                         "does) or clip the input range",
                    shapes=(atom_shape(a),),
                ))
        elif op.name in ("reduce_sum", "reduce_prod", "cumsum"):
            dt = atom_dtype(op.invars[0])
            if dt not in _LOW_PRECISION:
                continue
            shape = atom_shape(op.invars[0])
            axes = op.params.get("axes", ())
            if op.name == "cumsum":
                axes = (op.params.get("axis", 0),)
            n = int(np.prod([shape[a] for a in axes])) if axes else 1
            if n > 2048:
                sev = Severity.WARNING if dt == _F16 else Severity.INFO
                why = ("float16 saturates at 65504"
                       if dt == _F16 else
                       "bfloat16 has an 8-bit mantissa")
                diags.append(Diagnostic(
                    sev, "numeric_hazards", op.path,
                    f"{dt} reduction over {n} elements: {why}, long-axis "
                    "accumulation loses precision",
                    hint="accumulate in float32: x.astype('float32')"
                         ".sum(...).astype(x.dtype)",
                    shapes=(shape,), dtypes=(str(dt),),
                ))
    return diags


# ---------------------------------------------------------------------------
# 5. program/launch budget (reuses the PR 1 dispatch counters)
# ---------------------------------------------------------------------------
@register_pass("launch_budget")
def launch_budget(ctx: Context) -> List[Diagnostic]:
    if not ctx.counters:
        return []  # only meaningful when a counter snapshot is provided
    c = ctx.counters
    # whole-step capture (FLAGS_eager_step_capture) tightens the budget: a
    # captured steady-state step is ONE donated XLA program, not three, and
    # each accumulate-only microstep of a captured k-step gradient-
    # accumulation cycle replays as one captured program (counted in
    # capture_accum_replays). The auto budget is therefore one program per
    # replay in the measured window — a k-cycle window legitimately
    # launches k captured programs, and an accumulation loop under
    # FLAGS_check_programs must not warn spuriously.
    replays = int(c.get("capture_replays", 0))
    accum_replays = int(c.get("capture_accum_replays", 0))
    captured = replays > 0 or accum_replays > 0
    if ctx.budget is not None:
        budget = ctx.budget
    else:
        budget = (replays + accum_replays) if captured else 3
    diags = []
    programs = int(c.get("programs", 0))
    if programs > budget:
        parts = ", ".join(
            f"{k.removesuffix('_programs')}={c[k]}"
            for k in ("op_programs", "segment_programs", "backward_programs",
                      "optimizer_programs", "captured_programs")
            if c.get(k)
        )
        what = (
            "one captured program per update step / accumulate microstep"
            if captured
            else "fused forward + compiled-tape backward + fused optimizer"
        )
        diags.append(Diagnostic(
            Severity.WARNING, "launch_budget", "step",
            f"step launched {programs} device programs "
            f"(budget {budget}: {what}); breakdown: {parts}",
            hint="enable FLAGS_eager_lazy_dispatch, keep data-dependent "
                 "(jit=False) ops out of the hot loop, and check "
                 "flush_reasons in paddle.profiler.dispatch_counters()",
        ))
    if captured and programs <= budget:
        what_ran = (
            "each microstep of the accumulation cycle replayed as one "
            "captured XLA program (update step donated)"
            if accum_replays
            else "the step replayed as 1 XLA program with parameters and "
                 "optimizer state donated in place"
        )
        diags.append(Diagnostic(
            Severity.INFO, "launch_budget", "step",
            f"whole-step capture active: {what_ran} "
            f"(capture_replays={replays}"
            + (f", capture_accum_replays={accum_replays}" if accum_replays
               else "")
            + ")",
        ))
    fallbacks = int(c.get("capture_fallbacks", 0))
    if fallbacks > 0:
        reasons = c.get("capture_fallback_reasons") or {}
        parts = ", ".join(f"{k}={v}" for k, v in sorted(reasons.items()))
        diags.append(Diagnostic(
            Severity.WARNING, "launch_budget", "step",
            f"step fell back out of whole-step capture {fallbacks} time(s)"
            + (f" ({parts})" if parts else ""),
            # built-in grad clipping and k-step gradient accumulation are
            # CAPTURABLE patterns now — they no longer belong on this
            # permanent-bailout list (only custom clip subclasses do)
            hint="a steady-state step keeps capture only when its signature "
                 "is stable: avoid per-step shape/scalar changes, tensor "
                 "hooks, retain_graph/create_graph, custom grad-clip "
                 "subclasses (the built-in ClipGradBy* configs capture "
                 "fine), irregular accumulation cycles, and reads of .grad "
                 "or pending tensors between backward() and "
                 "optimizer.step()",
        ))
    if int(c.get("segment_cache_misses", 0)) > 0:
        diags.append(Diagnostic(
            Severity.INFO, "launch_budget", "step",
            f"steady-state step still compiled "
            f"{c['segment_cache_misses']} new segment(s)",
            hint="unstable segment signatures (varying shapes/scalars) "
                 "defeat the segment cache — check flush_reasons",
        ))
    return diags


# ---------------------------------------------------------------------------
# 6. determinism lint — the static twin of the bitwise guarantees the
# elastic resharding contract (distributed.fleet.elastic) depends on:
# per-replica runs must be bitwise reproducible, and cross-replica
# reductions must be world-size invariant when world sizes stay powers of
# two (deterministic_tree_sum's documented invariant)
# ---------------------------------------------------------------------------
# scatters that COMBINE duplicate-index updates (min/max are associative and
# commutative, so their accumulation order cannot change the result)
_ACCUM_SCATTERS = {"scatter-add", "scatter-mul"}
# primitives whose results leave the deterministic traced world: host
# callbacks observe wall-clock / host iteration order, so a replay is not
# bitwise bound to the original run
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "callback",
                   "outside_call", "host_callback_call"}
# the sampler core: ops that consume a PRNG key atom and emit bits — two
# samplers fed the SAME key atom draw identical streams
_RNG_CONSUMERS = {"random_bits", "threefry2x32"}
# 1:1 key plumbing, chased through when resolving a sampler's key root.
# Key-DERIVING ops (random_fold_in, random_split, random_seed) deliberately
# stop the chase: their outputs are NEW keys, and conflating them would
# flag every split subkey pair as a reuse
_KEY_PLUMBING = {"random_wrap", "random_unwrap"}


def _key_root(atom, producers, depth=8):
    while depth > 0:
        if isinstance(atom, Literal):
            return atom
        op = producers.get(atom)
        if op is None or op.name not in _KEY_PLUMBING or not op.invars:
            return atom
        atom = op.invars[0]
        depth -= 1
    return atom


def _index_root(atom, producers, depth=12):
    """Chase an index tensor through shape/convert plumbing to the value
    that actually carries the indices."""
    while depth > 0:
        if isinstance(atom, Literal):
            return atom
        op = producers.get(atom)
        if op is None or op.name not in _CHAIN_PASSTHROUGH or not op.invars:
            return atom
        atom = op.invars[0]
        depth -= 1
    return atom


def _indices_provably_unique(root, producers):
    """True when the index values cannot contain duplicates: an iota (or a
    compile-time constant whose values are distinct)."""
    op = producers.get(root) if not isinstance(
        root, Literal) else None
    if op is not None and op.name == "iota":
        return True
    val = getattr(root, "val", None)  # Literal / ConstAtom
    if val is not None:
        try:
            arr = np.asarray(val)
            return arr.size == np.unique(arr).size
        except Exception:
            return False
    return False


@register_pass("determinism")
def determinism(ctx: Context) -> List[Diagnostic]:
    from .sharding import _axis_sizes_from_ops

    diags = []
    prod = ctx.producers
    # mesh-scoped contexts carry axis sizes; a plain Context analyzing a
    # shard_map-bearing program reads them off the shard_map mesh params
    axis_sizes = getattr(ctx, "mesh_axes", None) \
        or _axis_sizes_from_ops(ctx.ops)

    # index roots of every gather in the program: a float scatter-add over
    # the SAME index root is autodiff's gather transpose (embedding /
    # take_along_axis gradients) — XLA combines its duplicate updates in a
    # fixed order per compilation, and the whole-step parity certificates
    # (analysis.equivalence) already bind it bitwise to the eager path, so
    # it is not a user-facing hazard
    gather_roots = set()
    for op in ctx.ops:
        if op.name == "gather" and len(op.invars) >= 2:
            r = _index_root(op.invars[1], prod)
            if not isinstance(r, Literal):
                gather_roots.add(id(r))

    key_users = {}
    for op in ctx.ops:
        if op.name in _ACCUM_SCATTERS and len(op.invars) >= 3:
            dt = atom_dtype(op.outvars[0])
            if not _is_float(dt):
                continue  # integer accumulation is exact in any order
            if op.params.get("unique_indices"):
                continue  # caller promised no duplicates
            root = _index_root(op.invars[1], prod)
            if _indices_provably_unique(root, prod):
                continue
            if not isinstance(root, Literal) \
                    and id(root) in gather_roots:
                continue  # autodiff gather transpose (see above)
            diags.append(Diagnostic(
                Severity.WARNING, "determinism", op.path,
                f"float {op.name} with potentially-duplicate indices: the "
                "order duplicate updates combine in is "
                "implementation-defined, so results need not be bitwise "
                "reproducible across backends/compilations",
                hint="pass unique_indices=True if the indices are provably "
                     "unique, accumulate in int/f64 and cast, or sort "
                     "indices first (segment_sum over sorted ids)",
                shapes=(atom_shape(op.outvars[0]),),
                dtypes=(str(dt),),
            ))
        elif op.name in ("psum", "psum_invariant"):
            dt = atom_dtype(op.outvars[0]) if op.outvars else None
            if not _is_float(dt):
                continue
            names = _coll_axis_names(op.params)
            n = 1
            for a in names:
                n *= int(axis_sizes.get(a, 1))
            if n > 1 and (n & (n - 1)) != 0:
                diags.append(Diagnostic(
                    Severity.WARNING, "determinism", op.path,
                    f"cross-replica float reduction over a group of {n} "
                    f"(axes {list(names)}): a non-power-of-two group has no "
                    "balanced reduction tree, so the result is not bitwise "
                    "invariant across world sizes",
                    hint="keep reduction group sizes powers of two, or "
                         "route host-side re-reductions through "
                         "deterministic_tree_sum "
                         "(distributed.fleet.elastic), whose pairwise tree "
                         "is world-size invariant for power-of-two counts",
                    shapes=(atom_shape(op.outvars[0]),),
                    dtypes=(str(dt),),
                ))
        elif op.name in _RNG_CONSUMERS and op.invars:
            k = _key_root(op.invars[0], prod)
            if not isinstance(k, Literal):
                key_users.setdefault(id(k), []).append(op)
        elif op.name in _CALLBACK_PRIMS:
            diags.append(Diagnostic(
                Severity.WARNING, "determinism", op.path,
                "host callback escapes the traced program: its result can "
                "depend on wall-clock time or host iteration order, so a "
                "replay is not bitwise bound to the original run",
                hint="move the computation into the traced program, or "
                     "accept that this step is unreproducible and exclude "
                     "it from parity checks",
            ))

    for ops in key_users.values():
        if len(ops) < 2:
            continue
        first = ops[0]
        for op in ops[1:]:
            diags.append(Diagnostic(
                Severity.WARNING, "determinism", op.path,
                f"PRNG key reused: the same key feeds {first.path} and "
                f"{op.path}, which therefore draw IDENTICAL random streams",
                hint="split or fold_in the key per consumer "
                     "(jax.random.split / paddle.seed threading); reused "
                     "keys silently correlate dropout masks and init draws",
            ))
    return diags
