"""paddle_tpu.analysis.equivalence — structural equivalence prover.

Every execution tier the framework grows — per-op → lazy(3-program) →
captured(1-program) → sharded-captured, telemetry on/off, donated vs plain,
planned vs unplanned — carries a *bitwise parity* contract. This module
turns that contract from a test-suite hope into a compile-time artifact: a
**structural proof** that two traced programs compute the same function,
checked before the first donated replay ever runs (the CUDA-Graphs
capture/replay discipline: a replayed program must provably be the path it
replaces).

The proof is canonical value numbering over the inlined flat-op IR
(``analysis._inline_ops``): every atom gets a content key derived from its
producer's primitive name, canonicalized params, and input keys —
alpha-renaming is free (keys never mention variable names), and a declared
allowlist of *bitwise-safe* rewrites is folded into the keys:

  - **commutative operand ordering** — ``add``/``mul``/``max``/… operand
    keys are sorted (IEEE float addition is commutative; only association
    changes results, and association is visible as tree shape);
  - **identity elision** — ``stop_gradient`` / ``copy`` are value-level
    no-ops (the capture inserts ``stop_gradient`` at non-differentiable
    positions; the 3-program flush does not);
  - **literal folding** — compile-time scalar chains fold to their value
    (``scalar_const``), so a literal ``2.0`` matches a ``1.0 + 1.0`` const
    chain and a broadcast-of-scalar;
  - **remat / recompute deduplication** — duplicated subcomputations (a
    ``jax.checkpoint`` replay under ``prevent_cse``, or the 3-program
    composition recomputing the forward inside its backward) hash to the
    SAME keys as the originals, so a planned program proves equal to its
    unplanned twin;
  - **declared extra outputs** — the rescue sentinel and the telemetry
    triple are extra *outputs* of the same program; callers declare how
    many trailing outputs each side may carry beyond the common contract.

Two programs are **certified equivalent** when their (declared-common)
output key sequences match. When they do not, a synchronized backward walk
from the first mismatched output pair produces a structured
*first-divergence* diagnostic: the two op paths, shapes and dtypes where
the programs first disagree.

Consumers:

  - ``core.lazy`` (FLAGS_check_programs=2): certifies the captured
    1-program step against the 3-program composition — and the sharded
    capture against its non-donated probe trace — before the first donated
    replay; an unprovable certificate falls back through the counted
    ``_CaptureIneligible`` ladder.
  - ``jit.CompiledTrainStep``: certifies the remat-planned step against
    its unplanned twin when a memory plan is applied.
  - ``core.lazy._ServeProgram``: certifies the donated and plain serve
    rungs trace the same program.
  - ``tools/graph_lint.py --diff A B``: schedule/structure diff between
    any two lintable targets.
"""
from __future__ import annotations

import dataclasses
import hashlib
import re
from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from . import (
    CanonVar,
    Literal,
    ConstAtom,
    Context,
    Diagnostic,
    Severity,
    _as_open,
    _inline_ops,
    atom_dtype,
    atom_shape,
    register_pass,
    scalar_const,
)

__all__ = [
    "CanonicalProgram",
    "EquivalenceCertificate",
    "canonicalize",
    "prove_equivalent",
    "certify_callables",
    "program_diff",
]


# bitwise-commutative binary primitives (operand ORDER never changes the
# result; association — which is tree shape, not operand order — does and
# is NOT rewritten)
_COMMUTATIVE = {"add", "add_any", "mul", "max", "min", "and", "or", "xor",
                "eq", "ne"}

# value-level identity ops: elided from producer chains
_IDENTITY = {"stop_gradient", "copy"}

_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


def _val_digest(val) -> str:
    """Content digest of a closed-over constant (shape, dtype, bytes)."""
    try:
        arr = np.asarray(val)
        h = hashlib.sha1(arr.tobytes()).hexdigest()[:16]
        return f"const:{arr.shape}:{arr.dtype}:{h}"
    except Exception:
        return f"const:{_ADDR_RE.sub('0x', repr(val))}"


def _scalar_key(atom, producers) -> Optional[str]:
    """Literal-folding: the canonical key of a compile-time scalar, chasing
    converts/broadcasts and folding constant arithmetic — None when `atom`
    is not a scalar constant."""
    if atom_shape(atom) != ():
        return None
    v = scalar_const(atom, producers)
    if v is None:
        return None
    return f"sc:{atom_dtype(atom)}:{v!r}"


class CanonicalProgram:
    """One side of an equivalence proof: the flat-op IR plus the canonical
    value-number key of every reachable atom."""

    __slots__ = ("closed", "ops", "producers", "out_atoms", "out_keys",
                 "rewrites", "_memo", "_jmemo")

    def __init__(self, closed, _jmemo=None):
        self.closed = closed
        self.ops, self.producers, self.out_atoms = _inline_ops(closed)
        self.rewrites: Counter = Counter()
        self._memo: Dict[int, str] = {}
        self._jmemo: Dict[int, Tuple[Any, str]] = (
            {} if _jmemo is None else _jmemo)
        open_jaxpr, _ = _as_open(closed)
        for i, v in enumerate(open_jaxpr.invars):
            self._memo[id(v)] = f"in:{i}"
        # ops arrive topologically ordered (scoped bodies before their scope
        # op); computing keys in list order keeps this iterative — no
        # recursion depth limit on deep GPT chains
        for op in self.ops:
            if op.scope:
                # scoped bodies (scan/while/cond/shard_map) never appear in
                # top-level producer chains; their content reaches the proof
                # through the scope op's param digest
                continue
            self._op_keys(op)
        self.out_keys = [self.key_of(a) for a in self.out_atoms]

    # -- atom keys ---------------------------------------------------------
    def key_of(self, atom) -> str:
        k = self._memo.get(id(atom))
        if k is not None:
            return k
        sk = _scalar_key(atom, self.producers)
        if sk is not None:
            self.rewrites["literal_folds"] += 1
            self._memo[id(atom)] = sk
            return sk
        if isinstance(atom, Literal):
            k = f"lit:{atom_dtype(atom)}:{_val_digest(atom.val)}"
        elif isinstance(atom, ConstAtom):
            k = _val_digest(atom.val)
        else:
            op = self.producers.get(atom)
            if op is None:
                # an unproduced free var (scoped-body invar leaking — should
                # not happen at top level); key by aval only
                k = f"free:{atom_shape(atom)}:{atom_dtype(atom)}"
            else:
                self._op_keys(op)
                k = self._memo[id(atom)]
        self._memo[id(atom)] = k
        return k

    def _op_keys(self, op) -> None:
        """Assign canonical keys to every outvar of `op` (memoized)."""
        if op.outvars and id(op.outvars[0]) in self._memo:
            return
        if op.name in _IDENTITY and len(op.invars) == 1 \
                and len(op.outvars) == 1:
            self.rewrites["identity_elisions"] += 1
            self._memo[id(op.outvars[0])] = self.key_of(op.invars[0])
            return
        ins = [self.key_of(a) for a in op.invars]
        if op.name in _COMMUTATIVE and len(ins) == 2:
            ins = sorted(ins)
        pdig = _params_digest(op.params, self._jmemo)
        base = hashlib.sha1(
            f"{op.name}|{pdig}|{','.join(ins)}".encode()
        ).hexdigest()[:20]
        for k, ov in enumerate(op.outvars):
            sk = _scalar_key(ov, self.producers)
            if sk is not None:
                self.rewrites["literal_folds"] += 1
                self._memo[id(ov)] = sk
            else:
                self._memo[id(ov)] = f"{op.name}:{base}:{k}"

    # -- divergence helpers ------------------------------------------------
    def producer(self, atom):
        """producers.get with unhashable-atom (Literal) guard."""
        if isinstance(atom, (Literal, ConstAtom)):
            return None
        try:
            return self.producers.get(atom)
        except TypeError:
            return None

    def chase(self, atom):
        """Skip identity producers (stop_gradient/copy chains)."""
        seen = 0
        while seen < 64:
            op = self.producer(atom)
            if op is None or op.name not in _IDENTITY \
                    or len(op.invars) != 1:
                return atom
            atom = op.invars[0]
            seen += 1
        return atom

    def describe(self, atom) -> str:
        k = self._memo.get(id(atom), "")
        if k.startswith("in:"):
            return f"invar[{k[3:]}]"
        if isinstance(atom, Literal):
            return f"literal {atom.val!r}"
        if isinstance(atom, ConstAtom):
            return f"const{list(atom_shape(atom))}:{atom_dtype(atom)}"
        op = self.producer(atom)
        if op is None:
            return "free var"
        return op.path


def _params_digest(params, jmemo) -> str:
    """Canonical digest of an eqn's params: jaxpr-valued params recurse into
    a full canonical sub-digest (so scope ops — scan/while/cond/shard_map —
    prove body equivalence structurally); trace-time thunks and callables
    are skipped (their identity is not semantic across traces); everything
    else is repr'd with memory addresses scrubbed."""
    items = []
    for k in sorted(params):
        v = params[k]
        d = _value_digest(v, jmemo)
        if d is not None:
            items.append(f"{k}={d}")
    return ";".join(items)


def _value_digest(v, jmemo) -> Optional[str]:
    if callable(v) and not hasattr(v, "jaxpr") \
            and not isinstance(v, (type,)):
        return None  # trace-time thunk / closure — not semantic
    if hasattr(v, "jaxpr") or type(v).__name__ == "Jaxpr":
        return _jaxpr_digest(v, jmemo)
    if isinstance(v, (tuple, list)):
        parts = [_value_digest(x, jmemo) for x in v]
        return "(" + ",".join(p for p in parts if p is not None) + ")"
    if isinstance(v, np.ndarray) or isinstance(v, jax.Array):
        return _val_digest(v)
    return _ADDR_RE.sub("0x", repr(v))


def _jaxpr_digest(j, jmemo) -> str:
    """Canonical digest of a sub-jaxpr: the value-number keys of its outputs
    under its own positional invars (alpha-rename-free, same allowlist)."""
    cached = jmemo.get(id(j))
    if cached is not None:
        return cached[1]
    try:
        sub = CanonicalProgram(j, _jmemo=jmemo)
        dig = "jaxpr:" + hashlib.sha1(
            "|".join(sub.out_keys).encode()).hexdigest()[:20]
    except Exception:
        open_j, _ = _as_open(j)
        dig = f"jaxpr:opaque:{len(open_j.eqns)}eqns"
    jmemo[id(j)] = (j, dig)
    return dig


@dataclasses.dataclass
class EquivalenceCertificate:
    """Outcome of one structural equivalence proof."""

    equivalent: bool
    reason: str
    label_a: str = "A"
    label_b: str = "B"
    n_ops: Tuple[int, int] = (0, 0)
    outputs_compared: int = 0
    rewrites: Dict[str, int] = dataclasses.field(default_factory=dict)
    divergence: Optional[Diagnostic] = None

    def summary(self) -> str:
        state = "EQUIVALENT" if self.equivalent else "DIVERGENT"
        rw = ", ".join(f"{k}={v}" for k, v in sorted(self.rewrites.items()))
        return (f"equivalence[{self.label_a} ≡ {self.label_b}]: {state} — "
                f"{self.reason} ({self.n_ops[0]}/{self.n_ops[1]} ops, "
                f"{self.outputs_compared} outputs"
                + (f"; rewrites: {rw}" if rw else "") + ")")

    def to_dict(self) -> Dict[str, Any]:
        return {
            "equivalent": self.equivalent,
            "reason": self.reason,
            "labels": [self.label_a, self.label_b],
            "n_ops": list(self.n_ops),
            "outputs_compared": self.outputs_compared,
            "rewrites": dict(self.rewrites),
            "divergence": (None if self.divergence is None
                           else str(self.divergence)),
        }


def canonicalize(closed) -> CanonicalProgram:
    """Canonical value numbering of a (closed) jaxpr — one side of a proof."""
    return CanonicalProgram(closed)


def _first_divergence(A: CanonicalProgram, B: CanonicalProgram,
                      out_idx: int, source: str) -> Diagnostic:
    """Synchronized backward walk from the first mismatched output pair to
    the first structurally diverging op (path, shapes, dtypes)."""
    stack = [(A.out_atoms[out_idx], B.out_atoms[out_idx])]
    seen = set()
    guard = 0
    while stack and guard < 20000:
        guard += 1
        a, b = stack.pop()
        a, b = A.chase(a), B.chase(b)
        if (id(a), id(b)) in seen:
            continue
        seen.add((id(a), id(b)))
        if A.key_of(a) == B.key_of(b):
            continue
        opa, opb = A.producer(a), B.producer(b)
        shapes = (atom_shape(a), atom_shape(b))
        dtypes = (str(atom_dtype(a)), str(atom_dtype(b)))
        if opa is None or opb is None:
            return Diagnostic(
                Severity.ERROR, "equivalence",
                f"{A.describe(a)} vs {B.describe(b)}",
                f"programs diverge at output {out_idx}: "
                f"{A.describe(a)} ≠ {B.describe(b)}",
                hint="the two tiers do not compute the same value here",
                shapes=shapes, dtypes=dtypes, source=source,
                data={"output_index": out_idx,
                      "a": A.describe(a), "b": B.describe(b)},
            )
        if opa.name != opb.name or _params_digest(opa.params, A._jmemo) \
                != _params_digest(opb.params, B._jmemo):
            why = ("op kinds differ" if opa.name != opb.name
                   else "op params differ")
            return Diagnostic(
                Severity.ERROR, "equivalence",
                f"{opa.path} vs {opb.path}",
                f"first divergence (output {out_idx}): {why} — "
                f"{opa.name} vs {opb.name}",
                hint="inspect the two op paths; this is the first point "
                     "where the programs stop being isomorphic",
                shapes=shapes, dtypes=dtypes, source=source,
                data={"output_index": out_idx, "a_path": opa.path,
                      "b_path": opb.path, "a_op": opa.name,
                      "b_op": opb.name},
            )
        # same op, same params: descend into the first differing input pair
        # (aligned by sorted key for commutative ops, positionally otherwise)
        ia = [(A.key_of(x), x) for x in opa.invars]
        ib = [(B.key_of(x), x) for x in opb.invars]
        if opa.name in _COMMUTATIVE and len(ia) == 2:
            ia.sort(key=lambda p: p[0])
            ib.sort(key=lambda p: p[0])
        if len(ia) != len(ib):
            return Diagnostic(
                Severity.ERROR, "equivalence",
                f"{opa.path} vs {opb.path}",
                f"first divergence (output {out_idx}): same op "
                f"{opa.name} applied with {len(ia)} vs {len(ib)} inputs",
                shapes=shapes, dtypes=dtypes, source=source,
                data={"output_index": out_idx, "a_path": opa.path,
                      "b_path": opb.path},
            )
        for (ka, xa), (kb, xb) in zip(ia, ib):
            if ka != kb:
                stack.append((xa, xb))
                break
        else:
            # inputs all match but output keys differ: output-index skew
            return Diagnostic(
                Severity.ERROR, "equivalence",
                f"{opa.path} vs {opb.path}",
                f"first divergence (output {out_idx}): same op, same "
                f"inputs, different output position",
                shapes=shapes, dtypes=dtypes, source=source,
                data={"output_index": out_idx, "a_path": opa.path,
                      "b_path": opb.path},
            )
    return Diagnostic(
        Severity.ERROR, "equivalence", f"output[{out_idx}]",
        f"programs diverge at output {out_idx} (divergence deeper than the "
        f"walk budget)",
        source=source, data={"output_index": out_idx},
    )


def prove_equivalent(a, b, *, extra_outputs_a: int = 0,
                     extra_outputs_b: int = 0, label_a: str = "A",
                     label_b: str = "B",
                     source: str = "equivalence") -> EquivalenceCertificate:
    """Certify two (closed) jaxprs structurally equivalent.

    ``extra_outputs_a``/``extra_outputs_b`` declare how many TRAILING
    outputs each side carries beyond the common contract (the telemetry
    triple, the rescue sentinel) — they are excluded from the proof.
    Returns an :class:`EquivalenceCertificate`; ``certificate.divergence``
    carries the structured first-divergence diagnostic when the proof
    fails. Raises on untraceable inputs (callers treat that as an
    *unprovable* certificate, distinct from a *divergent* one)."""
    A = a if isinstance(a, CanonicalProgram) else canonicalize(a)
    B = b if isinstance(b, CanonicalProgram) else canonicalize(b)
    n_ops = (len(A.ops), len(B.ops))
    rewrites = dict(Counter(A.rewrites) + Counter(B.rewrites))
    ka = A.out_keys[:len(A.out_keys) - int(extra_outputs_a)]
    kb = B.out_keys[:len(B.out_keys) - int(extra_outputs_b)]
    if len(ka) != len(kb):
        d = Diagnostic(
            Severity.ERROR, "equivalence", "outputs",
            f"output arity mismatch: {label_a} has {len(ka)} outputs, "
            f"{label_b} has {len(kb)} (beyond the declared extras)",
            hint="declare extra outputs (telemetry/sentinel) explicitly",
            source=source,
            data={"n_outputs": [len(ka), len(kb)],
                  "declared_extras": [extra_outputs_a, extra_outputs_b]},
        )
        return EquivalenceCertificate(
            False, "output arity mismatch", label_a, label_b, n_ops,
            min(len(ka), len(kb)), rewrites, d)
    for i, (x, y) in enumerate(zip(ka, kb)):
        if x != y:
            d = _first_divergence(A, B, i, source)
            return EquivalenceCertificate(
                False, f"outputs diverge starting at index {i}",
                label_a, label_b, n_ops, len(ka), rewrites, d)
    return EquivalenceCertificate(
        True, "all outputs canonically identical", label_a, label_b,
        n_ops, len(ka), rewrites)


def certify_callables(fn_a, fn_b, arg_specs, **kw) -> EquivalenceCertificate:
    """Trace two callables over the same ShapeDtypeStruct tree and prove
    them equivalent (the capture controller / serve-rung entry point)."""
    ca = jax.make_jaxpr(fn_a)(*arg_specs)
    cb = jax.make_jaxpr(fn_b)(*arg_specs)
    return prove_equivalent(ca, cb, **kw)


# ---------------------------------------------------------------------------
# structure diff (graph_lint --diff)
# ---------------------------------------------------------------------------
def program_diff(a, b, label_a: str = "A", label_b: str = "B",
                 extra_outputs_a: int = 0,
                 extra_outputs_b: int = 0) -> Tuple[
                     EquivalenceCertificate, List[str]]:
    """(certificate, printable diff lines) between two closed jaxprs:
    op-histogram delta, collective-schedule diff (kinds/axes/payloads in
    program order), and the first-divergence diagnostic when the structural
    proof fails."""
    from .sharding import schedule_of

    A, B = canonicalize(a), canonicalize(b)
    cert = prove_equivalent(
        A, B, label_a=label_a, label_b=label_b,
        extra_outputs_a=extra_outputs_a, extra_outputs_b=extra_outputs_b,
        source="graph_lint --diff")
    lines = [cert.summary()]
    ha = Counter(op.name for op in A.ops)
    hb = Counter(op.name for op in B.ops)
    delta = {n: (ha.get(n, 0), hb.get(n, 0))
             for n in sorted(set(ha) | set(hb))
             if ha.get(n, 0) != hb.get(n, 0)}
    if delta:
        lines.append(f"op histogram deltas ({label_a} vs {label_b}):")
        for n, (x, y) in delta.items():
            lines.append(f"  {n}: {x} vs {y}")
    else:
        lines.append("op histograms identical")
    sa, sb = schedule_of(A.ops), schedule_of(B.ops)
    if sa or sb:
        lines.append(f"collective schedule: {len(sa)} vs {len(sb)} "
                     "collectives")
        for i in range(max(len(sa), len(sb))):
            ra = _sched_str(sa[i]) if i < len(sa) else "—"
            rb = _sched_str(sb[i]) if i < len(sb) else "—"
            mark = " " if ra == rb else "!"
            lines.append(f" {mark} [{i}] {ra} | {rb}")
    else:
        lines.append("no collectives on either side")
    if cert.divergence is not None:
        lines.append(str(cert.divergence))
    return cert, lines


def _sched_str(rec: Dict[str, Any]) -> str:
    return (f"{rec['kind']}@{','.join(map(str, rec['axes']))} "
            f"{rec.get('payload_bytes', 0)}B")


# ---------------------------------------------------------------------------
# registry pass: runs only when a reference program is attached to the
# context (ctx.reference) — silent everywhere else, so existing self-lint
# gates see zero new diagnostics
# ---------------------------------------------------------------------------
@register_pass("equivalence")
def _equivalence_pass(ctx: Context) -> List[Diagnostic]:
    ref = getattr(ctx, "reference", None)
    if ref is None or ctx.closed is None:
        return []
    try:
        cert = prove_equivalent(
            ctx.closed, ref, label_a=ctx.source or "program",
            label_b="reference", source=ctx.source)
    except Exception as e:  # unprovable ≠ divergent: report, don't crash
        return [Diagnostic(
            Severity.WARNING, "equivalence", "program",
            f"equivalence unprovable: {type(e).__name__}: {e}",
            source=ctx.source)]
    if cert.equivalent:
        return []
    return [cert.divergence]
