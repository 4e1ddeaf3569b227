"""SPMD sharding analyzer: per-shard analysis IR, collective cost model,
resharding lints.

Reference analogue: the reference's hybrid-parallel stack validates its
collective programs at runtime (reducer bucket checks, pipeline schedule
asserts); GSPMD-style systems instead derive a *static* cost model from the
partitioned program and feed it back into planning (the Alpa/GSPMD
discipline in PAPERS.md). This module does that over the PR 2 analysis IR:

  - ``ShardContext`` — a mesh-scoped :class:`~paddle_tpu.analysis.Context`
    whose inliner rewrites every buffer's aval to its **per-shard** shape.
    Each jaxpr invar becomes a fresh per-shard ``ShardVar`` (same soundness
    rule as the pjit inlining: fresh canonical SSA per instance), specs are
    propagated through elementwise/transpose/reshape/broadcast/reduce/
    dot_general/slice ops, ``sharding_constraint`` equations re-anchor them,
    and ``shard_map`` regions are inlined *through* (their body avals are
    already per-shard). Every downstream pass — ``memory_budget``,
    ``donation_safety``, ``plan_memory`` — then operates on what one chip
    actually holds.
  - an **implied-collective** model for GSPMD programs, which carry no
    explicit collectives in the jaxpr (XLA inserts them at partitioning):
    a ``dot_general`` whose contracted dimension is sharded on axis *a*
    implies a psum of the output over *a* (this is exactly the dp gradient
    all-reduce and the row-parallel TP activation reduce); a reduction over
    a sharded dimension implies the same; a ``sharding_constraint`` that
    un-shards a dimension implies an all-gather, and one that moves a
    dimension between axes implies an all-to-all.
  - ``collective_cost`` (registered pass): classifies every explicit and
    implied collective with per-device bytes-on-wire under a ring-ICI cost
    model (all-reduce moves ``2·(n-1)/n·B``, all-gather ``(n-1)·B_shard``,
    reduce-scatter / all-to-all ``(n-1)/n·B``, ppermute ``B``) and reports
    the per-program comm/compute ratio. The same numbers feed
    ``profiler.attribution`` static profiles (``comm_bytes`` /
    ``collective_count`` — visible in ``/programz`` and ``fleet_top
    --programs``).
  - ``resharding_lint`` (registered pass): implicit-reshard hazards —
    psum∘psum over the same axis, all_gather immediately sliced back to the
    shard, a replicated output where the declared out-spec says sharded,
    and loop-invariant collectives inside scan bodies that could hoist.

Both passes stay silent on programs with no mesh, no ``shard_map`` region,
and no collectives, so the single-device ``FLAGS_check_programs`` suites
add no noise.

Public as ``paddle.static.analysis.sharding``. Entry points:
``check_sharded_step`` (lint a ``ShardedTrainStep`` without compiling it),
``shard_context`` (build a per-shard Context for any traced jaxpr),
``parse_mesh`` (``"dp=2,mp=2"`` → axis dict, the ``graph_lint --mesh``
syntax), and ``plan_memory(ctx, mesh=...)`` in ``analysis.memory`` for the
per-device peak-HBM estimate.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..core import flags as _flags
from . import (
    CanonVar,
    Literal,
    ShapedArray,
    Var,
    ConstAtom,
    Context,
    Diagnostic,
    FlatOp,
    Severity,
    _as_open,
    _resolve,
    _sub_jaxprs,
    register_pass,
)

__all__ = [
    "CollectiveOp",
    "ShardContext",
    "ShardVar",
    "captured_step_context",
    "check_sharded_step",
    "collective_records",
    "collective_stats",
    "parse_mesh",
    "pipelined_step_context",
    "ring_wire_bytes",
    "schedule_of",
    "shard_context",
    "sharded_step_context",
]

# primitives that move bytes between devices; the *_invariant forms appear
# under shard_map's check_vma typing, the rest are the explicit lax collectives
_COLLECTIVE_PRIMS = {
    "psum", "psum_invariant", "pmax", "pmin", "all_gather",
    "all_gather_invariant", "reduce_scatter", "all_to_all", "ppermute",
    "pbroadcast",
}
# cost-model kind per primitive (pmax/pmin are all-reduces on the wire)
_COLL_KIND = {
    "psum": "psum", "psum_invariant": "psum", "pmax": "psum", "pmin": "psum",
    "all_gather": "all_gather", "all_gather_invariant": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "all_to_all": "all_to_all", "ppermute": "ppermute",
    "pbroadcast": "pbroadcast",
}


def parse_mesh(text) -> Dict[str, int]:
    """``"dp=2,mp=2"`` → ``{"dp": 2, "mp": 2}`` (the graph_lint --mesh and
    test syntax). Also accepts a jax ``Mesh`` or an axis dict unchanged."""
    if isinstance(text, dict):
        return {str(k): int(v) for k, v in text.items()}
    shape = getattr(text, "shape", None)
    if shape is not None and hasattr(shape, "items"):  # jax Mesh
        return {str(k): int(v) for k, v in shape.items()}
    axes: Dict[str, int] = {}
    for part in str(text).split(","):
        part = part.strip()
        if not part:
            continue
        name, _, val = part.partition("=")
        if not val:
            raise ValueError(
                f"bad mesh spec {text!r}: expected axis=size pairs like "
                "'dp=2,mp=2'"
            )
        axes[name.strip()] = int(val)
    return axes


def ring_wire_bytes(kind: str, payload_bytes: int, group_size: int) -> int:
    """Per-device bytes on wire for one collective under the ring-ICI model
    (bidirectional ring over the mesh axis, the TPU ICI topology): an
    all-reduce is reduce-scatter + all-gather (``2·(n-1)/n·B``), an
    all-gather receives every other shard (``(n-1)·B_shard``),
    reduce-scatter and all-to-all each move ``(n-1)/n`` of the local
    payload, a ppermute forwards the full payload once, and pbroadcast is a
    replication marker with no wire traffic. Pure integer arithmetic —
    golden-testable, no timing."""
    n = int(group_size)
    b = int(payload_bytes)
    if n <= 1 or b <= 0:
        return 0
    if kind == "psum":
        return 2 * b * (n - 1) // n
    if kind == "all_gather":
        return b * (n - 1)
    if kind in ("reduce_scatter", "all_to_all"):
        return b * (n - 1) // n
    if kind == "ppermute":
        return b
    return 0  # pbroadcast / unknown


@dataclasses.dataclass
class CollectiveOp:
    """One classified collective (explicit or implied by the spec model)."""

    kind: str  # psum | all_gather | reduce_scatter | all_to_all | ppermute | pbroadcast
    path: str  # flat-op path it is attached to
    axes: Tuple[str, ...]  # mesh axes it reduces/moves over
    group_size: int  # product of the axis sizes
    payload_bytes: int  # per-device payload entering the collective
    wire_bytes: int  # per-device bytes on wire (ring-ICI), one execution
    count: int = 1  # trip multiplicity (scan bodies)
    implied: bool = False  # True: inserted by GSPMD, not in the jaxpr
    shape: Tuple = ()
    dtype: str = ""

    @property
    def total_wire_bytes(self) -> int:
        return int(self.wire_bytes) * int(self.count)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "kind": self.kind, "path": self.path, "axes": list(self.axes),
            "group_size": int(self.group_size),
            "payload_bytes": int(self.payload_bytes),
            "wire_bytes": int(self.wire_bytes), "count": int(self.count),
            "implied": bool(self.implied),
            "shape": [int(d) for d in self.shape], "dtype": self.dtype,
        }


# ---------------------------------------------------------------------------
# Spec arithmetic: a spec is a per-dim tuple of mesh-axis-name tuples
# ---------------------------------------------------------------------------
def _norm_spec(pspec, ndim: int) -> Tuple[Tuple[str, ...], ...]:
    """PartitionSpec / tuple / None → canonical ``((axes...),) * ndim``."""
    entries = list(pspec) if pspec is not None else []
    out: List[Tuple[str, ...]] = []
    for e in entries[:ndim]:
        if e is None:
            out.append(())
        elif isinstance(e, (tuple, list)):
            out.append(tuple(a for a in e if a is not None))
        else:
            out.append((e,))
    while len(out) < ndim:
        out.append(())
    return tuple(out)


def _dedupe_spec(spec) -> Tuple[Tuple[str, ...], ...]:
    """A mesh axis may shard at most one dim — keep the first occurrence."""
    seen = set()
    out = []
    for names in spec:
        kept = tuple(a for a in names if a not in seen)
        seen.update(kept)
        out.append(kept)
    return tuple(out)


def _merge_dim(a: Tuple[str, ...], b: Tuple[str, ...]) -> Tuple[str, ...]:
    if a == b or not b:
        return a
    if not a:
        return b
    return a  # conflict: keep the first (conservative)


def _merge_specs(specs: Sequence, ndim: int) -> Tuple[Tuple[str, ...], ...]:
    out = [()] * ndim
    for s in specs:
        s = tuple(s)
        off = ndim - len(s)  # right-align broadcasting inputs
        for d, names in enumerate(s):
            out[off + d] = _merge_dim(out[off + d], names)
    return _dedupe_spec(tuple(out))


def _shard_factor(names: Tuple[str, ...], axes: Dict[str, int]) -> int:
    f = 1
    for a in names:
        f *= int(axes.get(a, 1))
    return f


def _shard_aval(aval, spec, axes):
    """Per-shard aval: each sharded dim divided by its axis-size product.
    A dim the axes do not divide stays global (XLA pads; the estimate must
    stay an upper bound)."""
    shape = tuple(getattr(aval, "shape", ()))
    if not shape or aval is None:
        return aval
    new = list(shape)
    changed = False
    for d, names in enumerate(spec[:len(new)]):
        f = _shard_factor(names, axes)
        if f > 1 and new[d] % f == 0:
            new[d] //= f
            changed = True
    if not changed:
        return aval
    try:
        return aval.update(shape=tuple(new))
    except Exception:
        return ShapedArray(tuple(new), aval.dtype)


def _aval_nbytes(aval) -> int:
    if aval is None:
        return 0
    shape = tuple(getattr(aval, "shape", ()))
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    try:
        item = int(np.dtype(dt).itemsize)
    except TypeError:
        item = int(getattr(dt, "itemsize", 8))
    return n * item


class ShardVar(CanonVar):
    """Per-shard canonical SSA value: a CanonVar whose aval is the
    per-device shape, annotated with the propagated partition spec.
    ``explicit`` marks specs pinned by the program itself (an invar
    sharding, a sharding_constraint, a collective) rather than derived by
    propagation — the resharding lint only trusts explicit specs."""

    __slots__ = ("spec", "explicit")

    def __init__(self, aval, spec=(), explicit=False):
        super().__init__(aval)
        self.spec = tuple(spec)
        self.explicit = bool(explicit)

    def __repr__(self):
        return f"ShardVar({self.aval}, spec={self.spec})"


def _spec_of(atom, ndim: int) -> Tuple[Tuple[str, ...], ...]:
    if isinstance(atom, ShardVar):
        return _norm_spec(atom.spec, ndim)
    return ((),) * ndim


# ---------------------------------------------------------------------------
# The mesh-scoped inliner
# ---------------------------------------------------------------------------
def _coll_axes(params) -> Tuple[str, ...]:
    ax = params.get("axes", params.get("axis_name"))
    if ax is None:
        return ()
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


class _ShardInliner:
    """Rewrites a (global-shaped) closed jaxpr into the per-shard flat-op
    IR, recording every explicit and implied collective on the way."""

    def __init__(self, axes: Dict[str, int], collectives: List[CollectiveOp]):
        self.axes = dict(axes)
        self.collectives = collectives
        self.ops: List[FlatOp] = []
        self.producers: Dict[Any, FlatOp] = {}

    # -- collective recording ------------------------------------------------
    def _record(self, kind, path, names, payload, *, count=1, implied=False,
                shape=(), dtype=""):
        names = tuple(a for a in names if a in self.axes or a not in ())
        n = _shard_factor(tuple(names), self.axes)
        self.collectives.append(CollectiveOp(
            kind=kind, path=path, axes=tuple(names), group_size=n,
            payload_bytes=int(payload),
            wire_bytes=ring_wire_bytes(kind, payload, n),
            count=int(count), implied=implied,
            shape=tuple(int(d) for d in shape), dtype=str(dtype),
        ))

    # -- op emission ---------------------------------------------------------
    def _emit(self, name, invars, out_avals, out_specs, params, scope,
              explicit=False):
        outs = [ShardVar(av, sp, explicit=explicit)
                for av, sp in zip(out_avals, out_specs)]
        op = FlatOp(name, invars, outs, params, scope, len(self.ops))
        for ov in outs:
            self.producers[ov] = op
        self.ops.append(op)
        return op, outs

    # -- entry ---------------------------------------------------------------
    def run(self, closed, in_specs):
        open_jaxpr, consts = _as_open(closed)
        invar_atoms = []
        env: Dict[Any, Any] = {}
        specs = list(in_specs or [])
        for i, v in enumerate(open_jaxpr.invars):
            ndim = len(tuple(getattr(v.aval, "shape", ())))
            spec = _norm_spec(specs[i] if i < len(specs) else None, ndim)
            sv = ShardVar(_shard_aval(v.aval, spec, self.axes), spec,
                          explicit=True)
            env[v] = sv
            invar_atoms.append(sv)
        self._walk(open_jaxpr, consts, env, "", 1, manual=False)
        out_atoms = [_resolve(v, env) for v in open_jaxpr.outvars]
        return self.ops, self.producers, out_atoms, invar_atoms

    # -- the walk ------------------------------------------------------------
    def _walk(self, open_jaxpr, consts, env, scope, mult, manual):
        for cv, cval in zip(open_jaxpr.constvars, consts):
            env[cv] = ConstAtom(cval)
        for eqn in open_jaxpr.eqns:
            name = eqn.primitive.name
            ins = [_resolve(v, env) for v in eqn.invars]
            if name == "shard_map":
                self._shard_map(eqn, ins, env, scope, mult)
                continue
            if name in ("scan", "while", "cond", "switch"):
                self._scoped(eqn, ins, env, scope, mult, manual)
                continue
            kind, subs = _sub_jaxprs(eqn)
            if kind == "call":
                sub_open, sub_consts = _as_open(subs[0])
                if len(sub_open.invars) == len(eqn.invars):
                    ienv = dict(zip(sub_open.invars, ins))
                    self._walk(sub_open, sub_consts, ienv, scope, mult,
                               manual)
                    for ov, iov in zip(eqn.outvars, sub_open.outvars):
                        env[ov] = _resolve(iov, ienv)
                    continue
            self._primitive(eqn, ins, env, scope, mult, manual)

    # -- shard_map: mesh-scoped inline-through -------------------------------
    def _shard_map(self, eqn, ins, env, scope, mult):
        body, body_consts = _as_open(eqn.params["jaxpr"])
        mesh = eqn.params.get("mesh")
        shape = getattr(mesh, "shape", None)
        if shape is not None and hasattr(shape, "items"):
            for k, v in shape.items():
                self.axes.setdefault(str(k), int(v))
        in_names = eqn.params.get("in_names") or ()
        out_names = eqn.params.get("out_names") or ()

        def names_spec(names, ndim):
            spec = [()] * ndim
            for d, ax in (names or {}).items():
                if int(d) < ndim:
                    spec[int(d)] = tuple(ax)
            return tuple(spec)

        ienv = {}
        for i, (iv, outer) in enumerate(zip(body.invars, ins)):
            iv_aval = getattr(iv, "aval", None)
            outer_aval = getattr(outer, "aval", None)
            if (outer_aval is not None and iv_aval is not None
                    and tuple(getattr(outer_aval, "shape", ())) ==
                    tuple(getattr(iv_aval, "shape", ()))
                    and not isinstance(outer, Literal)):
                # per-shard shapes agree: the body reads the caller's buffer
                # in place — substitute (sound: fresh ShardVars upstream)
                ienv[iv] = outer
            else:
                # layouts differ (outer spec ≠ in_names): XLA reshards at
                # the boundary; a "reshard" view op keeps liveness honest
                ndim = len(tuple(getattr(iv_aval, "shape", ())))
                spec = names_spec(in_names[i] if i < len(in_names) else {},
                                  ndim)
                _, outs = self._emit(
                    "reshard", [outer], [iv_aval], [spec], {}, scope)
                ienv[iv] = outs[0]
        self._walk(body, body_consts, ienv, scope, mult, manual=True)
        for i, (ov, iov) in enumerate(zip(eqn.outvars, body.outvars)):
            inner = _resolve(iov, ienv)
            ndim = len(tuple(getattr(ov.aval, "shape", ())))
            spec = names_spec(out_names[i] if i < len(out_names) else {},
                              ndim)
            per_shard = _shard_aval(ov.aval, spec, self.axes)
            inner_aval = getattr(inner, "aval", None)
            if (inner_aval is not None and tuple(
                    getattr(inner_aval, "shape", ())) ==
                    tuple(getattr(per_shard, "shape", ()))
                    and not isinstance(inner, Literal)):
                if isinstance(inner, ShardVar):
                    inner.spec = spec
                    inner.explicit = True
                env[ov] = inner
            else:
                _, outs = self._emit(
                    "reshard", [inner], [per_shard], [spec], {}, scope,
                    explicit=True)
                env[ov] = outs[0]

    # -- scan/while/cond: scope-style with spec-mapped body invars -----------
    def _scoped(self, eqn, ins, env, scope, mult, manual):
        name = eqn.primitive.name
        _, subs = _sub_jaxprs(eqn)
        body_mult = mult
        n_consts = n_carry = 0
        if name == "scan":
            n_consts = int(eqn.params.get("num_consts", 0))
            n_carry = int(eqn.params.get("num_carry", 0))
            body_mult = mult * max(1, int(eqn.params.get("length", 1)))
        for si, sub in enumerate(subs):
            sub_open, sub_consts = _as_open(sub)
            tag = name + (str(si) if len(subs) > 1 else "")
            ienv = {}
            for i, iv in enumerate(sub_open.invars):
                outer = ins[i] if i < len(ins) else None
                ndim = len(tuple(getattr(iv.aval, "shape", ())))
                if name == "scan" and outer is not None:
                    o_ndim = len(tuple(getattr(
                        getattr(outer, "aval", None), "shape", ())) or ())
                    o_spec = _spec_of(outer, o_ndim)
                    spec = (tuple(o_spec[:ndim]) if i < n_consts + n_carry
                            else tuple(o_spec[1:1 + ndim]))  # xs: drop scan dim
                    spec = _norm_spec(spec, ndim)
                else:
                    spec = ((),) * ndim
                ienv[iv] = ShardVar(
                    _shard_aval(iv.aval, spec, self.axes), spec)
            self._walk(sub_open, sub_consts, ienv, env_scope(scope, tag),
                       body_mult, manual)
        # the outer control-flow op itself: carry outputs inherit the carry
        # inputs' specs; stacked ys are conservatively replicated
        out_avals, out_specs = [], []
        for oi, ov in enumerate(eqn.outvars):
            ndim = len(tuple(getattr(ov.aval, "shape", ())))
            if name == "scan" and oi < n_carry:
                carry_in = ins[n_consts + oi] if n_consts + oi < len(ins) \
                    else None
                spec = _spec_of(carry_in, ndim) if carry_in is not None \
                    else ((),) * ndim
            else:
                spec = ((),) * ndim
            out_avals.append(_shard_aval(ov.aval, spec, self.axes))
            out_specs.append(spec)
        _, outs = self._emit(name, ins, out_avals, out_specs, eqn.params,
                             scope)
        for ov, sv in zip(eqn.outvars, outs):
            env[ov] = sv

    # -- plain primitives: spec propagation + implied collectives ------------
    def _primitive(self, eqn, ins, env, scope, mult, manual):
        name = eqn.primitive.name
        path = f"{scope}/eqn[{len(self.ops)}] {name}" if scope \
            else f"eqn[{len(self.ops)}] {name}"
        out_specs = self._propagate(eqn, ins, path, mult, manual)
        out_avals = []
        for ov, spec in zip(eqn.outvars, out_specs):
            if manual:
                out_avals.append(ov.aval)  # body avals are already per-shard
            else:
                out_avals.append(_shard_aval(ov.aval, spec, self.axes))
        explicit = name in ("sharding_constraint",) or name in _COLLECTIVE_PRIMS
        op, outs = self._emit(name, ins, out_avals, out_specs, eqn.params,
                              scope, explicit=explicit)
        if name in _COLLECTIVE_PRIMS:
            payload = sum(_aval_nbytes(getattr(a, "aval", None))
                          for a in ins
                          if not isinstance(a, Literal))
            self._record(_COLL_KIND[name], op.path, _coll_axes(eqn.params),
                         payload, count=mult,
                         shape=tuple(getattr(
                             getattr(ins[0], "aval", None), "shape", ())),
                         dtype=str(getattr(
                             getattr(ins[0], "aval", None), "dtype", "")))
        for ov, sv in zip(eqn.outvars, outs):
            env[ov] = sv

    def _propagate(self, eqn, ins, path, mult, manual):
        """Out spec per outvar; records implied collectives for GSPMD
        (non-manual) regions."""
        name = eqn.primitive.name
        n_out = len(eqn.outvars)
        out_shapes = [tuple(getattr(ov.aval, "shape", ()))
                      for ov in eqn.outvars]

        def repl():
            return [((),) * len(s) for s in out_shapes]

        if manual and name not in _COLLECTIVE_PRIMS \
                and name != "sharding_constraint":
            return repl()  # manual regions: explicit collectives only

        if name == "sharding_constraint":
            sh = eqn.params.get("sharding")
            pspec = getattr(sh, "spec", None)
            ndim = len(out_shapes[0])
            new = _norm_spec(pspec, ndim) if pspec is not None \
                else ((),) * ndim
            old = _spec_of(ins[0], ndim)
            if not manual:
                self._constraint_reshard(old, new, eqn.outvars[0].aval,
                                         path, mult)
            return [new]

        if name == "dot_general":
            return [self._dot_general(eqn, ins, path, mult)]

        if name in ("reduce_sum", "reduce_prod", "reduce_max", "reduce_min",
                    "reduce_and", "reduce_or", "reduce_xor",
                    "argmax", "argmin"):
            in_spec = _spec_of(ins[0], len(tuple(getattr(
                getattr(ins[0], "aval", None), "shape", ()))))
            axes_red = tuple(eqn.params.get("axes", ()))
            red_names = tuple(a for d in axes_red
                              for a in (in_spec[d] if d < len(in_spec)
                                        else ()))
            out_spec = tuple(s for d, s in enumerate(in_spec)
                             if d not in axes_red)
            out_spec = _norm_spec(out_spec, len(out_shapes[0]))
            if red_names and name.startswith("reduce_"):
                payload = _aval_nbytes(_shard_aval(
                    eqn.outvars[0].aval, out_spec, self.axes))
                self._record("psum", path, red_names, payload, count=mult,
                             implied=True, shape=out_shapes[0],
                             dtype=str(eqn.outvars[0].aval.dtype))
            return [out_spec] + [((),) * len(s) for s in out_shapes[1:]]

        if name == "transpose":
            perm = tuple(eqn.params.get("permutation", ()))
            in_spec = _spec_of(ins[0], len(perm))
            return [tuple(in_spec[p] for p in perm)]

        if name == "broadcast_in_dim":
            in_shape = tuple(getattr(
                getattr(ins[0], "aval", None), "shape", ()))
            bdims = tuple(eqn.params.get("broadcast_dimensions", ()))
            out = [()] * len(out_shapes[0])
            in_spec = _spec_of(ins[0], len(in_shape))
            for i, d in enumerate(bdims):
                if i < len(in_shape) and in_shape[i] == out_shapes[0][d]:
                    out[d] = in_spec[i]
            return [_dedupe_spec(tuple(out))]

        if name == "reshape":
            in_shape = tuple(getattr(
                getattr(ins[0], "aval", None), "shape", ()))
            in_spec = _spec_of(ins[0], len(in_shape))
            # the walker sees GLOBAL shapes in GSPMD mode, but the op's
            # recorded avals are per-shard — use the eqn's own (global)
            # shapes for the factor matching
            g_in = tuple(getattr(eqn.invars[0].aval, "shape", ()))
            return [_reshape_spec(g_in, out_shapes[0], in_spec, self.axes)]

        if name == "squeeze":
            dims = set(eqn.params.get("dimensions", ()))
            in_spec = _spec_of(ins[0], len(tuple(getattr(
                getattr(ins[0], "aval", None), "shape", ()))))
            return [tuple(s for d, s in enumerate(in_spec) if d not in dims)]

        if name == "slice":
            g_in = tuple(getattr(eqn.invars[0].aval, "shape", ()))
            starts = tuple(eqn.params.get("start_indices", ()))
            limits = tuple(eqn.params.get("limit_indices", ()))
            strides = eqn.params.get("strides") or (1,) * len(g_in)
            in_spec = _spec_of(ins[0], len(g_in))
            out = tuple(
                in_spec[d] if (starts[d] == 0 and limits[d] == g_in[d]
                               and strides[d] == 1) else ()
                for d in range(len(g_in))
            )
            return [out]

        if name == "split":
            # jnp.split's own primitive: every piece spans the other dims
            # whole, so only the split axis loses its sharding (slice rule)
            g_in = tuple(getattr(eqn.invars[0].aval, "shape", ()))
            axis = int(eqn.params.get("axis", 0))
            in_spec = _spec_of(ins[0], len(g_in))
            piece = tuple(() if d == axis else in_spec[d]
                          for d in range(len(g_in)))
            return [piece] * n_out

        if name == "dynamic_slice":
            g_in = tuple(getattr(eqn.invars[0].aval, "shape", ()))
            sizes = tuple(eqn.params.get("slice_sizes", ()))
            in_spec = _spec_of(ins[0], len(g_in))
            return [tuple(in_spec[d] if sizes[d] == g_in[d] else ()
                          for d in range(len(g_in)))]

        if name in ("dynamic_update_slice", "scatter", "scatter_add",
                    "scatter-add", "scatter_mul", "scatter_min",
                    "scatter_max"):
            nd = len(out_shapes[0])
            return [_spec_of(ins[0], nd)] + [((),) * len(s)
                                             for s in out_shapes[1:]]

        if name == "concatenate":
            dim = int(eqn.params.get("dimension", 0))
            nd = len(out_shapes[0])
            merged = list(_merge_specs(
                [_spec_of(a, nd) for a in ins], nd))
            if dim < len(merged):
                merged[dim] = ()
            return [tuple(merged)]

        if name == "pad":
            cfg = tuple(eqn.params.get("padding_config", ()))
            nd = len(out_shapes[0])
            in_spec = _spec_of(ins[0], nd)
            return [tuple(in_spec[d] if d < len(cfg) and cfg[d] == (0, 0, 0)
                          else () for d in range(nd))]

        if name in ("rev", "copy", "convert_element_type", "stop_gradient",
                    "reduce_precision", "real", "imag"):
            nd = len(out_shapes[0])
            return [_spec_of(ins[0], nd)] + [((),) * len(s)
                                             for s in out_shapes[1:]]

        # generic elementwise: every input is scalar or output-shaped
        if n_out == 1:
            nd = len(out_shapes[0])
            shaped = []
            ok = True
            for a in ins:
                sh = tuple(getattr(getattr(a, "aval", None), "shape", ()))
                if sh == ():
                    continue
                # compare GLOBAL shapes (per-shard avals divide uniformly)
                shaped.append(a)
            g_out = out_shapes[0]
            for a, gv in zip(ins, eqn.invars):
                g_sh = tuple(getattr(getattr(gv, "aval", None), "shape", ()))
                if g_sh not in ((), g_out):
                    ok = False
                    break
            if ok and shaped:
                return [_merge_specs(
                    [_spec_of(a, len(tuple(getattr(
                        getattr(a, "aval", None), "shape", ()))))
                     for a in shaped], nd)]
        return repl()

    def _dot_general(self, eqn, ins, path, mult):
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs_g = tuple(getattr(eqn.invars[0].aval, "shape", ()))
        rhs_g = tuple(getattr(eqn.invars[1].aval, "shape", ()))
        lspec = _spec_of(ins[0], len(lhs_g))
        rspec = _spec_of(ins[1], len(rhs_g))
        # out dims: batch, then lhs free, then rhs free
        out_spec: List[Tuple[str, ...]] = []
        for bl, br in zip(lb, rb):
            out_spec.append(_merge_dim(lspec[bl], rspec[br]))
        for d in range(len(lhs_g)):
            if d not in lc and d not in lb:
                out_spec.append(lspec[d])
        for d in range(len(rhs_g)):
            if d not in rc and d not in rb:
                out_spec.append(rspec[d])
        out_spec = _dedupe_spec(tuple(out_spec))
        # contracted dim sharded on axis a (either operand) → partial sums
        # per shard, GSPMD all-reduces the output over a — THE implied psum
        # (dp grad all-reduce, row-parallel TP activation reduce)
        contracted = tuple(dict.fromkeys(
            [a for d in lc for a in lspec[d]]
            + [a for d in rc for a in rspec[d]]
        ))
        if contracted:
            payload = _aval_nbytes(_shard_aval(
                eqn.outvars[0].aval, out_spec, self.axes))
            self._record("psum", path, contracted, payload, count=mult,
                         implied=True,
                         shape=tuple(getattr(eqn.outvars[0].aval, "shape",
                                             ())),
                         dtype=str(eqn.outvars[0].aval.dtype))
        return out_spec

    def _constraint_reshard(self, old, new, out_aval, path, mult):
        """A sharding_constraint that changes the layout: un-sharding a dim
        is an all-gather, moving it between axes is an all-to-all;
        sharding a replicated dim is a local slice (no wire traffic)."""
        for d, (o, n_) in enumerate(zip(old, new)):
            if o == n_:
                continue
            gathered = tuple(a for a in o if a not in n_)
            if not gathered:
                continue
            payload = _aval_nbytes(_shard_aval(out_aval, old, self.axes))
            kind = "all_to_all" if n_ else "all_gather"
            self._record(kind, path, gathered, payload, count=mult,
                         implied=True,
                         shape=tuple(getattr(out_aval, "shape", ())),
                         dtype=str(getattr(out_aval, "dtype", "")))


def env_scope(scope: str, tag: str) -> str:
    return f"{scope}/{tag}" if scope else tag


def _reshape_spec(in_shape, out_shape, in_spec, axes):
    """Propagate a spec through reshape by greedy composite-group matching:
    within a group (a run of in-dims whose size product equals a run of
    out-dims'), a sharded in-dim carries to the last out-dim its shard
    factor divides (the common batch-split ``[B,..] → [k, B/k, ..]``
    pattern shards the inner dim). Unmatched sharding is dropped
    (replicated — the conservative upper bound)."""
    out = [()] * len(out_shape)
    i = j = 0
    while i < len(in_shape) and j < len(out_shape):
        gi, gj = i + 1, j + 1
        pi, pj = in_shape[i], out_shape[j]
        while pi != pj:
            if pi < pj and gi < len(in_shape):
                pi *= in_shape[gi]
                gi += 1
            elif pj < pi and gj < len(out_shape):
                pj *= out_shape[gj]
                gj += 1
            else:
                return tuple(out)  # ragged (shouldn't happen) — bail
        ins_g = list(range(i, gi))
        outs_g = list(range(j, gj))
        if len(ins_g) == len(outs_g) and all(
                in_shape[a] == out_shape[b]
                for a, b in zip(ins_g, outs_g)):
            for a, b in zip(ins_g, outs_g):
                out[b] = in_spec[a] if a < len(in_spec) else ()
        else:
            names = tuple(a for d in ins_g
                          for a in (in_spec[d] if d < len(in_spec) else ()))
            f = _shard_factor(names, axes)
            if f > 1:
                for b in reversed(outs_g):
                    if out_shape[b] % f == 0:
                        out[b] = names
                        break
        i, j = gi, gj
    return _dedupe_spec(tuple(out))


# ---------------------------------------------------------------------------
# ShardContext: the mesh-scoped Context
# ---------------------------------------------------------------------------
class ShardContext(Context):
    """A :class:`Context` whose IR is per-shard: invars become fresh
    ``ShardVar`` atoms sized to one device's shard, specs are propagated,
    and ``ctx.collectives`` lists every classified collective. All PR 2/4
    passes run on it unchanged — ``plan_memory`` then reports per-device
    peak HBM, and ``donation_safety`` proofs run against per-shard live
    ranges."""

    def __init__(self, closed, roles, source="sharded", *, mesh_axes,
                 in_specs=None, out_specs=None, donated=(),
                 alias_groups=None, alias_refs=None, memory_budget_mb=None,
                 counters=None, budget=None):
        self.mesh_axes = {str(k): int(v)
                          for k, v in parse_mesh(mesh_axes).items()}
        self.in_specs = list(in_specs) if in_specs is not None else None
        self.out_specs = list(out_specs) if out_specs is not None else None
        self.collectives: List[CollectiveOp] = []
        super().__init__(closed, roles, source, counters=counters,
                         budget=budget, donated=donated,
                         alias_groups=alias_groups, alias_refs=alias_refs,
                         memory_budget_mb=memory_budget_mb)

    def _build_ir(self):
        if self.closed is None:
            return [], {}, []
        inliner = _ShardInliner(self.mesh_axes, self.collectives)
        ops, producers, out_atoms, invar_atoms = inliner.run(
            self.closed, self.in_specs)
        self.mesh_axes.update(inliner.axes)  # axes learned from shard_maps
        self.invar_atoms = invar_atoms
        return ops, producers, out_atoms


def shard_context(closed, roles=(), *, mesh, in_specs=None, out_specs=None,
                  donated=(), source="sharded", memory_budget_mb=None,
                  alias_groups=None, alias_refs=None) -> ShardContext:
    """Build a per-shard analysis context for an already-traced (closed)
    jaxpr. ``mesh`` is a jax Mesh, an axis dict, or a ``"dp=2,mp=2"``
    string; ``in_specs`` is one PartitionSpec (or tuple) per flat invar."""
    return ShardContext(
        closed, list(roles), source, mesh_axes=parse_mesh(mesh),
        in_specs=in_specs, out_specs=out_specs, donated=donated,
        memory_budget_mb=memory_budget_mb, alias_groups=alias_groups,
        alias_refs=alias_refs,
    )


# ---------------------------------------------------------------------------
# ShardedTrainStep front-end
# ---------------------------------------------------------------------------
def _norm_batch_specs(batch_specs):
    out = []
    for s in batch_specs or []:
        shape = getattr(s, "shape", None)
        if shape is not None:
            dt = getattr(s, "dtype", "float32")
        else:
            shape, dt = s
        shape = tuple(1 if d in (None, -1) else int(d) for d in shape)
        try:
            dt = np.dtype(dt)
        except TypeError:
            pass
        out.append(jax.ShapeDtypeStruct(shape, dt))
    return out


def sharded_step_context(step, batch_specs, *, memory_budget_mb=None,
                         source=None) -> ShardContext:
    """Trace a ``ShardedTrainStep`` (no XLA compile) and build its
    per-shard context: flat roles/in-specs in jaxpr invar order, every
    param and optimizer-state position marked donated (the step's
    ``donate_argnums=(0, 1)``), and the declared out-specs attached for
    the resharding lint."""
    import jax.numpy as jnp

    mesh = step.mesh
    if mesh is None:
        raise ValueError("sharded_step_context needs a step with a mesh")
    states = step._opt_state
    if states is None:
        from ..jit.step import init_opt_state

        states = init_opt_state(step.optimizer, step._params)
    p_sh, st_sh, b_sh, batch_sh = step._shardings(states)
    batch_sds = _norm_batch_specs(batch_specs)
    step_fn, in_sh, out_sh = step._step_parts(len(batch_sds), states)

    def _sds(v):
        v = getattr(v, "_value", v)
        return jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)

    p_sds = tuple(_sds(p) for p in step._params)
    st_sds = tuple({k: _sds(v) for k, v in st.items()} for st in states)
    b_sds = tuple(_sds(b) for b in step._buffers)
    key = jax.random.PRNGKey(0)
    key_sds = jax.ShapeDtypeStruct(tuple(key.shape), key.dtype)
    lr_sds = jax.ShapeDtypeStruct((), jnp.float32)
    closed = jax.make_jaxpr(step_fn)(p_sds, st_sds, b_sds, key_sds, lr_sds,
                                     *batch_sds)

    roles: List[Tuple[str, str]] = []
    specs: List[Any] = []
    for i, p in enumerate(step._params):
        roles.append(("param", getattr(p, "name", None) or f"param{i}"))
        specs.append(p_sh[i].spec)
    n_state = 0
    for i, (st, sh) in enumerate(zip(states, st_sh)):
        for k in sorted(st):
            roles.append(("arg", f"opt_state:{i}.{k}"))
            specs.append(sh[k].spec)
            n_state += 1
    for i, b in enumerate(step._buffers):
        roles.append(("buffer", getattr(b, "name", None) or f"buffer{i}"))
        specs.append(b_sh[i].spec)
    roles.append(("arg", "rng_key"))
    specs.append(None)
    roles.append(("arg", "lr"))
    specs.append(None)
    for i, s in enumerate(batch_sds):
        roles.append(("feed", f"batch{i}"))
        specs.append(batch_sh.spec)
    if len(roles) != len(closed.jaxpr.invars):
        raise RuntimeError(
            f"sharded step trace misaligned: {len(roles)} roles vs "
            f"{len(closed.jaxpr.invars)} jaxpr invars"
        )
    donated = tuple(range(len(step._params) + n_state))
    out_specs = [getattr(s, "spec", None)
                 for s in jax.tree_util.tree_leaves(out_sh)]
    mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ShardContext(
        closed, roles, source or "sharded-step", mesh_axes=mesh_axes,
        in_specs=specs, out_specs=out_specs, donated=donated,
        memory_budget_mb=memory_budget_mb,
    )


def pipelined_step_context(step, batch_specs, *, memory_budget_mb=None,
                           source=None) -> ShardContext:
    """Per-shard context for a ``PipelinedTrainStep`` (the shard_map-manual
    GPipe schedule): stacked block params pp-sharded on dim 0, the
    ppermute/psum collectives of the schedule classified from the body's
    per-shard avals, every param/state position donated
    (``donate_argnums=(0, 1, 2, 3)``).

    If the full step cannot be traced the context falls back to the forward
    GPipe loss program: the identical shard_map schedule with the identical
    ppermute/psum collectives, minus the optimizer tail (and hence with
    nothing donated)."""
    import jax.numpy as jnp

    mesh = step.mesh
    saved = (step._stacked, step._stacked_state, step._repl_state)
    if step._stacked is None:
        step._stacked = step._init_stacked()
    if step._stacked_state is None:
        step._stacked_state = step._init_stacked_state()
    if step._repl_state is None:
        step._repl_state = step._init_repl_state()
    try:
        step_fn, in_sh, out_sh = step._step_parts()

        def _sds(v):
            v = getattr(v, "_value", v)
            return jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)

        repl_sds = tuple(_sds(p) for p in step._repl_params)
        stacked_sds = tuple(_sds(v) for v in step._stacked)
        rs_sds = tuple({k: _sds(v) for k, v in st.items()}
                       for st in step._repl_state)
        ss_sds = tuple({k: _sds(v) for k, v in st.items()}
                       for st in step._stacked_state)
        b_sds = tuple(_sds(b) for b in step._buffers)
        key = jax.random.PRNGKey(0)
        key_sds = jax.ShapeDtypeStruct(tuple(key.shape), key.dtype)
        lr_sds = jax.ShapeDtypeStruct((), jnp.float32)
        batch_sds = _norm_batch_specs(batch_specs)
        full_step = True
        try:
            closed = jax.make_jaxpr(step_fn)(
                repl_sds, stacked_sds, rs_sds, ss_sds, b_sds, key_sds,
                lr_sds, *batch_sds)
        except Exception:
            # trace the forward loss program instead (same collectives, no
            # optimizer tail)
            full_step = False
            closed = jax.make_jaxpr(step._loss_program)(
                repl_sds, stacked_sds, b_sds, key_sds, *batch_sds)
    finally:
        step._stacked, step._stacked_state, step._repl_state = saved

    roles: List[Tuple[str, str]] = []
    for i, p in enumerate(step._repl_params):
        roles.append(("param", getattr(p, "name", None) or f"param{i}"))
    for j in range(len(stacked_sds)):
        roles.append(("param", f"stacked{j}"))
    if full_step:
        for i, st in enumerate(rs_sds):
            for k in sorted(st):
                roles.append(("arg", f"repl_state:{i}.{k}"))
        for j, st in enumerate(ss_sds):
            for k in sorted(st):
                roles.append(("arg", f"stacked_state:{j}.{k}"))
    for i, b in enumerate(step._buffers):
        roles.append(("buffer", getattr(b, "name", None) or f"buffer{i}"))
    roles.append(("arg", "rng_key"))
    if full_step:
        roles.append(("arg", "lr"))
    for i in range(len(batch_sds)):
        roles.append(("feed", f"batch{i}"))
    repl_sh, stacked_sh, rs_sh, ss_sh, buf_sh, key_sh, lr_sh, *batch_sh = \
        in_sh
    if full_step:
        n_donated = len(jax.tree_util.tree_leaves(
            (repl_sds, stacked_sds, rs_sds, ss_sds)))
        flat_in_sh = jax.tree_util.tree_leaves(in_sh)
        flat_out_sh = jax.tree_util.tree_leaves(out_sh)
    else:
        n_donated = 0  # forward-only program: nothing to donate
        flat_in_sh = jax.tree_util.tree_leaves(
            (repl_sh, stacked_sh, buf_sh, key_sh, tuple(batch_sh)))
        flat_out_sh = [jax.tree_util.tree_leaves(out_sh)[0]]  # scalar loss
    specs = [getattr(s, "spec", None) for s in flat_in_sh]
    out_specs = [getattr(s, "spec", None) for s in flat_out_sh]
    if len(roles) != len(closed.jaxpr.invars) or \
            len(specs) != len(closed.jaxpr.invars):
        raise RuntimeError(
            f"pipelined step trace misaligned: {len(roles)} roles / "
            f"{len(specs)} specs vs {len(closed.jaxpr.invars)} jaxpr invars"
        )
    mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ShardContext(
        closed, roles, source or "pipelined-step", mesh_axes=mesh_axes,
        in_specs=specs, out_specs=out_specs,
        donated=tuple(range(n_donated)),
        memory_budget_mb=memory_budget_mb,
    )


def captured_step_context(*, memory_budget_mb=None,
                          source=None) -> ShardContext:
    """Per-shard analysis context for the thread's last replayed SHARDED
    captured whole-step program (``core.lazy`` whole-step capture on a
    mesh). Rebuilds the closed jaxpr and per-invar PartitionSpecs from the
    capture registry — trace-only, no XLA compile. Raises RuntimeError
    when no sharded capture has replayed on this thread yet."""
    from ..core import lazy as _lazy

    prog = _lazy.captured_step_program()
    info = _lazy.captured_step_shard_info()
    if prog is None or info is None:
        raise RuntimeError(
            "no sharded captured step has replayed on this thread; run a "
            "captured training step on a mesh first (FLAGS_eager_step_capture "
            "with NamedSharding params)")
    closed, donated, roles = prog
    mesh, in_specs, axes = info
    return ShardContext(
        closed, list(roles), source or "captured-sharded", mesh_axes=axes,
        in_specs=in_specs, donated=donated,
        memory_budget_mb=memory_budget_mb,
    )


def check_sharded_step(step, batch_specs, *, passes=None,
                       memory_budget_mb=None, source=None
                       ) -> List[Diagnostic]:
    """Run the full analysis suite over a sharded/pipelined train step's
    traced program at per-shard shapes — the multi-chip twin of
    ``analysis.check``. Trace-only: no XLA compile, runs in milliseconds,
    safe as a build-time gate under ``FLAGS_check_programs``. Accepts a
    ``ShardedTrainStep``, a ``PipelinedTrainStep``, or a
    ``lazy.captured_step_handle()`` (batch_specs ignored for the latter —
    the captured program embeds its own batch shapes)."""
    from . import run_passes

    if getattr(step, "_captured_step", False):  # lazy captured-step handle
        ctx = captured_step_context(memory_budget_mb=memory_budget_mb,
                                    source=source)
        return run_passes(ctx, passes)
    if hasattr(step, "_stacked"):  # PipelinedTrainStep (pp schedule)
        ctx = pipelined_step_context(step, batch_specs,
                                     memory_budget_mb=memory_budget_mb,
                                     source=source)
    else:
        ctx = sharded_step_context(step, batch_specs,
                                   memory_budget_mb=memory_budget_mb,
                                   source=source)
    return run_passes(ctx, passes)


# ---------------------------------------------------------------------------
# Collective extraction for plain (non-mesh) contexts + attribution
# ---------------------------------------------------------------------------
def _axis_sizes_from_ops(ops) -> Dict[str, int]:
    axes: Dict[str, int] = {}
    for op in ops:
        if op.name == "shard_map":
            shape = getattr(op.params.get("mesh"), "shape", None)
            if shape is not None and hasattr(shape, "items"):
                for k, v in shape.items():
                    axes.setdefault(str(k), int(v))
    return axes


def collective_records(ctx) -> List[CollectiveOp]:
    """Classified collectives of a context. ShardContext carries them from
    the per-shard inline; for a plain Context the explicit collectives
    inside ``shard_map`` scopes are classified here (their avals are
    already per-shard), with axis sizes read off the shard_map mesh
    params."""
    recs = getattr(ctx, "collectives", None)
    if recs is not None:
        return list(recs)
    ops = getattr(ctx, "ops", None) or []
    axes = _axis_sizes_from_ops(ops)
    out: List[CollectiveOp] = []
    for op in ops:
        if op.name not in _COLLECTIVE_PRIMS:
            continue
        names = _coll_axes(op.params)
        n = _shard_factor(names, axes)
        payload = sum(_aval_nbytes(getattr(a, "aval", None))
                      for a in op.invars
                      if not isinstance(a, Literal))
        kind = _COLL_KIND[op.name]
        first = getattr(op.invars[0], "aval", None) if op.invars else None
        out.append(CollectiveOp(
            kind=kind, path=op.path, axes=names, group_size=n,
            payload_bytes=payload,
            wire_bytes=ring_wire_bytes(kind, payload, n),
            shape=tuple(getattr(first, "shape", ())),
            dtype=str(getattr(first, "dtype", "")),
        ))
    return out


def collective_stats(closed) -> Dict[str, int]:
    """``{"comm_bytes", "collective_count"}`` for one closed jaxpr — the
    attribution hook (``profiler.attribution`` static profiles). Explicit
    collectives only (no spec info at this call site); zero-collective
    programs return zeros so single-chip profiles are unchanged."""
    from . import _inline_ops

    ops, _producers, _outs = _inline_ops(closed)
    recs = collective_records(type("C", (), {
        "collectives": None, "ops": ops})())
    return {
        "comm_bytes": int(sum(r.total_wire_bytes for r in recs)),
        "collective_count": int(sum(r.count for r in recs)),
    }


def _flops_of_ops(ops) -> int:
    from ..profiler.attribution import _op_flops

    return int(sum(_op_flops(op) for op in ops))


# ---------------------------------------------------------------------------
# Pass: collective_cost
# ---------------------------------------------------------------------------
def _fmt_bytes(n: int) -> str:
    if n >= 1 << 20:
        return f"{n / float(1 << 20):.1f}MB"
    if n >= 1 << 10:
        return f"{n / 1024.0:.1f}KB"
    return f"{n}B"


@register_pass("collective_cost")
def collective_cost(ctx: Context) -> List[Diagnostic]:
    recs = collective_records(ctx)
    if not recs and getattr(ctx, "mesh_axes", None) is None:
        return []  # single-device program — stay silent
    comm_bytes = sum(r.total_wire_bytes for r in recs)
    count = sum(r.count for r in recs)
    flops = _flops_of_ops(ctx.ops)
    ratio = comm_bytes / float(flops) if flops else 0.0
    by_kind: Dict[str, List[int]] = {}
    for r in recs:
        row = by_kind.setdefault(r.kind, [0, 0])
        row[0] += r.total_wire_bytes
        row[1] += r.count
    kinds = ", ".join(
        f"{k} ×{n} {_fmt_bytes(b)}"
        for k, (b, n) in sorted(by_kind.items(), key=lambda kv: -kv[1][0])
    ) or "none"
    diags = [Diagnostic(
        Severity.INFO, "collective_cost", "program",
        f"{count} collective(s), {_fmt_bytes(comm_bytes)} on wire per "
        f"device per step (ring-ICI); comm/compute "
        f"{ratio:.2e} bytes/flop; by kind: {kinds}",
        data={
            "comm_bytes": int(comm_bytes),
            "collective_count": int(count),
            "flops_est": int(flops),
            "comm_compute_ratio": float(ratio),
            "collectives": [r.to_dict() for r in recs],
        },
    )]
    warn_at = float(_flags.flag("comm_ratio_warn"))
    if warn_at > 0 and ratio > warn_at:
        heavy = max(recs, key=lambda r: r.total_wire_bytes)
        diags.append(Diagnostic(
            Severity.WARNING, "collective_cost", heavy.path,
            f"comm/compute ratio {ratio:.2e} bytes/flop exceeds "
            f"FLAGS_comm_ratio_warn={warn_at:g}: this program is "
            "interconnect-bound under the ring-ICI model "
            f"(heaviest: {heavy.kind} over {list(heavy.axes)}, "
            f"{_fmt_bytes(heavy.total_wire_bytes)})",
            hint="re-balance the mesh (more model-parallel, less data-"
                 "parallel traffic), raise the per-device batch, or check "
                 "resharding_lint for removable round trips",
        ))
    return diags


# ---------------------------------------------------------------------------
# Pass: resharding_lint
# ---------------------------------------------------------------------------
def _scan_hoist_findings(open_jaxpr, path, acc):
    """Loop-invariant collectives: a collective inside a scan body whose
    transitive inputs are all scan CONSTS (or literals) recomputes the same
    cross-device traffic every iteration — hoist it above the loop."""
    for i, eqn in enumerate(open_jaxpr.eqns):
        name = eqn.primitive.name
        here = f"{path}eqn[{i}]"
        if name == "scan":
            body, _ = _as_open(eqn.params["jaxpr"])
            nc = int(eqn.params.get("num_consts", 0))
            pure = set(body.invars[:nc])
            for bi, be in enumerate(body.eqns):
                ins = [v for v in be.invars if isinstance(v, Var)]
                if ins and all(v in pure for v in ins):
                    if be.primitive.name in _COLLECTIVE_PRIMS:
                        acc.append((
                            f"{here}/scan/eqn[{bi}] {be.primitive.name}",
                            be,
                            int(eqn.params.get("length", 0)),
                        ))
                    pure.update(be.outvars)
            _scan_hoist_findings(body, f"{here}/scan/", acc)
        else:
            _k, subs = _sub_jaxprs(eqn)
            for si, sub in enumerate(subs):
                sub_open, _c = _as_open(sub)
                tag = name + (str(si) if len(subs) > 1 else "")
                _scan_hoist_findings(sub_open, f"{here}/{tag}/", acc)


@register_pass("resharding_lint")
def resharding_lint(ctx: Context) -> List[Diagnostic]:
    mesh_scoped = getattr(ctx, "mesh_axes", None) is not None
    has_region = any(op.name == "shard_map" for op in ctx.ops) or any(
        op.name in _COLLECTIVE_PRIMS for op in ctx.ops)
    if not mesh_scoped and not has_region:
        return []  # single-device program — stay silent
    diags: List[Diagnostic] = []
    prod = ctx.producers

    if mesh_scoped:
        # psum∘psum / gather-then-slice are redundant_ops findings on plain
        # contexts; the mesh-scoped suite reports them here instead (the
        # redundant_ops pass defers when ctx.mesh_axes is set) so the full
        # suite never double-reports one defect
        for op in ctx.ops:
            if op.name in ("psum", "psum_invariant"):
                p = prod.get(op.invars[0]) if op.invars else None
                if p is not None and p.name in ("psum", "psum_invariant") and \
                        set(_coll_axes(op.params)) == \
                        set(_coll_axes(p.params)):
                    diags.append(Diagnostic(
                        Severity.WARNING, "resharding_lint", op.path,
                        "psum∘psum over the same axis "
                        f"{sorted(_coll_axes(op.params))}: the second "
                        "all-reduce multiplies by the group size and "
                        "doubles the wire traffic",
                        hint="reduce once (or use the two-axis form "
                             "psum(x, ('a','b')) for a single fused "
                             "all-reduce)",
                        shapes=(tuple(getattr(getattr(
                            op.invars[0], "aval", None), "shape", ())),),
                    ))
            elif op.name in ("slice", "dynamic_slice", "squeeze"):
                p = prod.get(op.invars[0]) if op.invars else None
                if p is not None and p.name == "all_gather" and \
                        tuple(getattr(getattr(op.outvars[0], "aval", None),
                                      "shape", ())) == \
                        tuple(getattr(getattr(p.invars[0], "aval", None),
                                      "shape", ())):
                    diags.append(Diagnostic(
                        Severity.WARNING, "resharding_lint", op.path,
                        "all_gather immediately sliced back to the local "
                        "shard: a full-axis round trip that ends where it "
                        "started",
                        hint="drop the gather (the shard is already local) "
                             "or keep the gathered value if other shards "
                             "are actually read",
                        shapes=(tuple(getattr(getattr(
                            p.invars[0], "aval", None), "shape", ())),),
                    ))

    # replicated output where the declared out-spec says sharded — only
    # when the propagated spec is EXPLICIT (constraint/collective-pinned);
    # propagation fallbacks must not false-positive
    out_specs = getattr(ctx, "out_specs", None)
    if mesh_scoped and out_specs:
        for pos, (atom, decl) in enumerate(zip(ctx.out_atoms, out_specs)):
            if not isinstance(atom, ShardVar) or not atom.explicit:
                continue
            ndim = len(tuple(getattr(atom.aval, "shape", ())))
            want = _norm_spec(decl, ndim)
            have = _norm_spec(atom.spec, ndim)
            missing = [d for d in range(ndim) if want[d] and not have[d]]
            if missing and not any(have):
                diags.append(Diagnostic(
                    Severity.WARNING, "resharding_lint", f"output[{pos}]",
                    f"output {pos} is replicated inside the program but its "
                    f"declared out-spec shards dim(s) {missing}: XLA will "
                    "slice at the boundary and every device computed the "
                    "full value first",
                    hint="keep the value sharded through the program (check "
                         "lost sharding constraints) or declare the output "
                         "replicated",
                    shapes=(tuple(getattr(atom.aval, "shape", ())),),
                ))

    # loop-invariant collectives inside scan bodies
    if ctx.jaxpr is not None:
        acc: List = []
        _scan_hoist_findings(ctx.jaxpr, "", acc)
        for path, eqn, length in acc:
            diags.append(Diagnostic(
                Severity.WARNING, "resharding_lint", path,
                f"loop-invariant {eqn.primitive.name} inside a scan body: "
                "its inputs are scan constants, so the same collective "
                f"runs every iteration"
                + (f" (×{length})" if length else ""),
                hint="hoist the collective above the lax.scan / fori loop",
                shapes=(tuple(getattr(eqn.invars[0].aval, "shape", ()))
                        if eqn.invars else (),),
            ))
    return diags


# ---------------------------------------------------------------------------
# Pass: collective_schedule — SPMD divergence
# ---------------------------------------------------------------------------
# In SPMD every rank runs the SAME program, so every rank must reach every
# collective in the SAME order: a collective reachable only under control
# flow predicated on a rank-varying value (the device coordinate) is the
# classic SPMD deadlock — some ranks enter the collective, their peers
# never arrive, and the step hangs instead of erroring.

def schedule_of(ops) -> List[Dict[str, Any]]:
    """Ordered collective schedule of a flat-op list: one record per
    collective, in program order, ``{kind, op, path, axes, group_size,
    payload_bytes, scope}``. This is the artifact two programs must agree
    on to be SPMD-interchangeable; ``graph_lint --diff`` and
    ``equivalence.program_diff`` print schedule deltas from it."""
    axes = _axis_sizes_from_ops(ops)
    out: List[Dict[str, Any]] = []
    for op in ops:
        if op.name not in _COLLECTIVE_PRIMS:
            continue
        names = _coll_axes(op.params)
        payload = sum(_aval_nbytes(getattr(a, "aval", None))
                      for a in op.invars
                      if not isinstance(a, Literal))
        out.append({
            "kind": _COLL_KIND[op.name],
            "op": op.name,
            "path": op.path,
            "axes": tuple(names),
            "group_size": _shard_factor(names, axes),
            "payload_bytes": int(payload),
            "scope": op.scope,
        })
    return out


def _jaxpr_has_collective(j, depth=6) -> bool:
    """True when the (closed or open) jaxpr contains a collective anywhere,
    including nested control-flow/call bodies."""
    if depth <= 0:
        return False
    open_j, _consts = _as_open(j)
    for eqn in open_j.eqns:
        if eqn.primitive.name in _COLLECTIVE_PRIMS:
            return True
        for v in eqn.params.values():
            subs = v if isinstance(v, (tuple, list)) else (v,)
            for s in subs:
                if hasattr(s, "eqns") or hasattr(s, "jaxpr"):
                    if _jaxpr_has_collective(s, depth - 1):
                        return True
    return False


def _rank_varying(atom, producers, depth=64) -> bool:
    """True when ``atom`` derives from the device coordinate
    (``axis_index``): a branch predicated on it takes different arms on
    different ranks."""
    stack = [atom]
    steps = 0
    while stack and steps < depth:
        a = stack.pop()
        steps += 1
        if isinstance(a, Literal):
            continue
        try:
            op = producers.get(a)
        except TypeError:
            continue
        if op is None:
            continue
        if op.name == "axis_index":
            return True
        stack.extend(op.invars)
    return False


@register_pass("collective_schedule")
def collective_schedule(ctx) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    prod = ctx.producers
    for op in ctx.ops:
        if op.name in ("cond", "switch"):
            branches = op.params.get("branches") or ()
            if not any(_jaxpr_has_collective(b) for b in branches):
                continue
            pred = op.invars[0] if op.invars else None
            if pred is not None and _rank_varying(pred, prod):
                diags.append(Diagnostic(
                    Severity.ERROR, "collective_schedule", op.path,
                    f"collective inside a {op.name} branch whose predicate "
                    "derives from axis_index: ranks taking different arms "
                    "reach different collective schedules — the classic "
                    "SPMD deadlock (some ranks enter the collective, peers "
                    "never arrive)",
                    hint="hoist the collective out of the branch, or make "
                         "the predicate rank-invariant (e.g. reduce it with "
                         "psum/pmax first)",
                ))
        elif op.name == "while":
            bodies = [op.params.get("cond_jaxpr"),
                      op.params.get("body_jaxpr")]
            if not any(b is not None and _jaxpr_has_collective(b)
                       for b in bodies):
                continue
            if any(_rank_varying(a, prod) for a in op.invars
                   if not isinstance(a, Literal)):
                diags.append(Diagnostic(
                    Severity.ERROR, "collective_schedule", op.path,
                    "collective inside a while loop whose carry derives "
                    "from axis_index: ranks can run different trip counts, "
                    "so they disagree on how many collectives execute — "
                    "SPMD deadlock",
                    hint="make the trip count rank-invariant (pmax the "
                         "continue predicate) or move the collective out "
                         "of the loop",
                ))
    return diags
