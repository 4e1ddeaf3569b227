"""Static memory planner: liveness, peak-HBM estimation & donation safety.

Reference analogue: the reference treats memory as a first-class subsystem
(AllocatorFacade, best-fit / auto-growth strategies, stream-safe allocation
— paddle/fluid/memory/allocation/). On TPU, XLA's buffer assignment owns
HBM, so the planner's job moves *earlier*: compute, statically on the same
inlined flat-op IR every execution mode funnels through (PR 2), what XLA's
allocator will be asked to hold — per-buffer live ranges, a linear-scan
peak estimate, and whether buffer donation (PR 3's `donate_argnums`) is
actually safe. The liveness arithmetic follows XLA's buffer-liveness
analysis and memory planners like Checkmate (Jain et al., MLSys 2020):

  - every non-literal atom (jaxpr input, closed-over constant, op output)
    is one buffer sized from its aval (shape x dtype itemsize);
  - an op output is born at its op and dies at its last read (or escapes
    with the program outputs); constants live for the program's lifetime;
  - a NON-donated input is caller-owned: its buffer is unavailable for
    reuse for the whole execution. A DONATED input dies entering its last
    read — XLA aliases the buffer onto that op's output (the in-place
    ``p -= lr*g`` update reuse ``donate_argnums`` exists for), so old and
    new values never coexist. This is exactly the HBM saving whole-step
    capture claims, and ``donation_credit_bytes`` quantifies it (peak
    without donation minus peak with donation);
  - peak HBM = max over time of the live-buffer sum. The estimate is an
    *unfused upper bound*: XLA's fusion never materializes more than this,
    and for segment/captured programs (whose op outputs all escape to the
    host framework) it is tight — see MEMORY_PLAN.md for the
    estimated-vs-measured methodology.

Two passes are registered in the PR 2 registry:

  - ``memory_budget``: reports estimated peak HBM (with the top-k largest
    live buffers) and errors when it exceeds ``FLAGS_memory_budget_mb`` or
    the detected device HBM;
  - ``donation_safety``: statically proves/refutes that each donated
    argument position is never aliased by a live external reference —
    returned-unchanged outputs, double-bound donated positions, and (via
    the gc-based ``donated_buffer_alias_diags`` scan wired into the
    whole-step capture replay and ``compile_train_step``) use-after-donate
    patterns like ``state_dict()``/``detach()`` aliases held across steps,
    flagged *before* XLA invalidates the buffer at runtime.

Both stay silent unless configured (a budget set, donation info present,
or device HBM exceeded), so the default ``FLAGS_check_programs`` suites
add no noise.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..core import flags as _flags
from . import (
    Context,
    Literal,
    Diagnostic,
    Severity,
    register_pass,
    _SCOPE_PRIMS,
    ConstAtom,
)

__all__ = [
    "BlockPoolPlan",
    "Buffer",
    "MemoryPlan",
    "plan_block_pool",
    "plan_memory",
    "captured_step_plans",
    "device_hbm_bytes",
    "tensor_aliases",
    "donated_buffer_alias_diags",
    "donated_buffer_diags",
    "donation_gate",
    "traced_program_diags",
]

_MB = float(1 << 20)


def _dtype_itemsize(dt) -> int:
    try:
        return int(np.dtype(dt).itemsize)
    except TypeError:
        # jax extended dtypes (PRNG keys wrap uint32[2], float8 wrappers)
        return int(getattr(dt, "itemsize", 8))


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
    return n * _dtype_itemsize(dt)


def _fmt_bytes(n: int) -> str:
    if n >= 1 << 20:
        return f"{n / _MB:.1f}MB"
    if n >= 1 << 10:
        return f"{n / 1024:.1f}KB"
    return f"{n}B"


@dataclasses.dataclass
class Buffer:
    """One planned buffer: a jaxpr input, closed-over constant, or op
    output, with its (donation-credited) live range over the op timeline
    (born=-1: exists at program entry; dies=n_ops: escapes/held to exit)."""

    kind: str  # "param" | "buffer" | "feed" | "arg" | "const" | "op" | "body"
    name: str  # role name, op path, or const tag
    shape: Tuple
    dtype: str
    nbytes: int
    born: int
    dies: int
    donated: bool = False

    def label(self) -> str:
        return f"{self.kind}:{self.name}" if self.kind != "op" else self.name


class MemoryPlan:
    """Result of the liveness simulation over one program."""

    def __init__(self, buffers, n_ops, peak_bytes, peak_index, peak_op_path,
                 peak_no_donation_bytes):
        self.buffers: List[Buffer] = buffers
        self.n_ops = n_ops
        self.peak_bytes = peak_bytes
        self.peak_index = peak_index  # op timeline position of the peak
        self.peak_op_path = peak_op_path
        self.peak_no_donation_bytes = peak_no_donation_bytes

    @property
    def donation_credit_bytes(self) -> int:
        """HBM the donated inputs free below the no-donation peak — the
        saving buffer donation is worth on this program."""
        return self.peak_no_donation_bytes - self.peak_bytes

    @property
    def input_bytes(self) -> int:
        return sum(b.nbytes for b in self.buffers
                   if b.born < 0 and b.kind != "const")

    @property
    def const_bytes(self) -> int:
        return sum(b.nbytes for b in self.buffers if b.kind == "const")

    @property
    def output_bytes(self) -> int:
        return sum(b.nbytes for b in self.buffers
                   if b.born >= 0 and b.dies >= self.n_ops)

    @property
    def boundary_bytes(self) -> int:
        """Live bytes at program exit: non-donated inputs + constants +
        escaping outputs — what stays resident between launches."""
        return sum(b.nbytes for b in self.live_at(self.n_ops))

    def live_at(self, t: int) -> List[Buffer]:
        return [b for b in self.buffers if b.born <= t <= b.dies]

    def top_live(self, k: int = 5) -> List[Buffer]:
        live = sorted(self.live_at(self.peak_index),
                      key=lambda b: -b.nbytes)
        return live[:k]

    def to_dict(self) -> Dict:
        top = self.top_live(5)
        return {
            "peak_bytes": int(self.peak_bytes),
            "peak_mb": round(self.peak_bytes / _MB, 3),
            "peak_index": int(self.peak_index),
            "peak_op": self.peak_op_path,
            "peak_no_donation_bytes": int(self.peak_no_donation_bytes),
            "donation_credit_bytes": int(self.donation_credit_bytes),
            "input_bytes": int(self.input_bytes),
            "const_bytes": int(self.const_bytes),
            "output_bytes": int(self.output_bytes),
            "boundary_bytes": int(self.boundary_bytes),
            "n_ops": int(self.n_ops),
            "n_buffers": len(self.buffers),
            "top_live": [
                {"name": b.label(), "shape": list(map(int, b.shape)),
                 "dtype": b.dtype, "nbytes": int(b.nbytes),
                 "donated": b.donated}
                for b in top
            ],
        }

    def __repr__(self):
        return (f"<MemoryPlan peak={_fmt_bytes(self.peak_bytes)} "
                f"@{self.peak_op_path or self.peak_index} "
                f"credit={_fmt_bytes(self.donation_credit_bytes)} "
                f"ops={self.n_ops} buffers={len(self.buffers)}>")


def _peak_of(intervals: Sequence[Tuple[int, int, int]], n_ops: int):
    """(peak bytes, peak time) over timeline t in [-1, n_ops] for
    (born, dies, nbytes) intervals (inclusive on both ends)."""
    delta = [0] * (n_ops + 3)
    for born, dies, nb in intervals:
        if dies < born or nb <= 0:
            continue
        delta[born + 1] += nb
        delta[dies + 2] -= nb
    cur, peak, at = 0, 0, -1
    for t in range(-1, n_ops + 1):
        cur += delta[t + 1]
        if cur > peak:
            peak, at = cur, t
    return peak, at


def _scope_extra(op, scope_prefix, scope_peaks) -> int:
    """Transient charge for a control-flow op: the max internal peak among
    body scopes this op could own (body scopes of same-primitive siblings
    share one tag, so the charge is the conservative max)."""
    if op.name not in _SCOPE_PRIMS:
        return 0
    best = 0
    for tag, pk in scope_peaks.items():
        local = tag[len(scope_prefix):] if scope_prefix else tag
        if "/" not in local and local.startswith(op.name):
            best = max(best, pk)
    return best


def _scope_peak(ops, scope, scope_peaks) -> int:
    """Internal peak of one control-flow body scope (approximate: body
    invars live throughout, outputs die at their last in-scope read)."""
    n = len(ops)
    last_use: Dict[int, int] = {}
    avals: Dict[int, int] = {}
    produced = set()
    for op in ops:
        for ov in op.outvars:
            produced.add(id(ov))
    intervals = []
    for i, op in enumerate(ops):
        for a in op.invars:
            if isinstance(a, Literal):
                continue
            last_use[id(a)] = i
            avals[id(a)] = _aval_nbytes(getattr(a, "aval", None))
    for aid, die in last_use.items():
        if aid not in produced:  # body input / carried value
            intervals.append((-1, n, avals.get(aid, 0)))
    for i, op in enumerate(ops):
        extra = _scope_extra(op, scope + "/", scope_peaks)
        if extra:
            intervals.append((i, i, extra))
        for ov in op.outvars:
            nb = _aval_nbytes(getattr(ov, "aval", None))
            intervals.append((i, last_use.get(id(ov), i), nb))
    peak, _ = _peak_of(intervals, n)
    return peak


def plan_memory(ctx: Context, donated: Optional[Sequence[int]] = None,
                *, mesh=None, in_specs=None) -> MemoryPlan:
    """Liveness simulation of ``ctx``'s program; ``donated`` overrides the
    context's donated invar-index set (e.g. to compare with/without).

    ``mesh``/``in_specs`` rebuild the context per-shard first (via
    ``analysis.sharding.shard_context``) so every buffer is sized to one
    device's shard and the returned peak is **per device** — the multi-chip
    budget ROADMAP item 1 needs. A context that is already mesh-scoped
    (``ctx.mesh_axes`` set) is planned as-is."""
    if mesh is not None and getattr(ctx, "mesh_axes", None) is None:
        from .sharding import shard_context

        ctx = shard_context(
            ctx.closed, ctx.roles, mesh=mesh, in_specs=in_specs,
            donated=getattr(ctx, "donated", ()),
            source=ctx.source,
            memory_budget_mb=getattr(ctx, "memory_budget_mb", None),
            alias_groups=getattr(ctx, "alias_groups", None),
            alias_refs=getattr(ctx, "alias_refs", None),
        )
    donated_set = set(
        donated if donated is not None else getattr(ctx, "donated", ()) or ()
    )
    by_scope: Dict[str, List] = {}
    for op in ctx.ops:
        by_scope.setdefault(op.scope, []).append(op)
    scope_peaks: Dict[str, int] = {}
    for scope in sorted((s for s in by_scope if s),
                        key=lambda s: -s.count("/")):
        scope_peaks[scope] = _scope_peak(by_scope[scope], scope, scope_peaks)

    top = by_scope.get("", [])
    n = len(top)
    last_use: Dict = {}
    for i, op in enumerate(top):
        for a in op.invars:
            if not isinstance(a, Literal):
                last_use[a] = i
    out_set = set()
    for a in getattr(ctx, "out_atoms", ()):
        if not isinstance(a, Literal):
            try:
                out_set.add(a)
            except TypeError:
                pass

    buffers: List[Buffer] = []

    def _mk(kind, name, aval, born, dies, donated=False):
        buffers.append(Buffer(
            kind, name, tuple(getattr(aval, "shape", ())),
            str(getattr(aval, "dtype", "?")), _aval_nbytes(aval),
            born, dies, donated,
        ))

    # jaxpr inputs: caller-owned for the whole program unless donated. A
    # donated buffer dies ENTERING its last read: XLA aliases it onto that
    # op's output (the in-place p -= lr*g update reuse donate_argnums
    # exists for), so old and new values never coexist. Never-read donated
    # buffers are freed at program entry — full credit.
    for idx, (invar, (kind, name)) in enumerate(ctx.invar_roles()):
        don = idx in donated_set
        if invar in out_set:
            dies = n
        elif don:
            dies = last_use.get(invar, 0) - 1
        else:
            dies = n
        _mk(kind, name, getattr(invar, "aval", None), -1, dies, don)

    # closed-over constants: baked into the executable, resident
    # throughout. Dedupe by the underlying VALUE — the inliner mints a
    # fresh ConstAtom per inline instance, but a shared inner jaxpr's
    # constant is one buffer no matter how many call sites reference it
    seen_consts = set()
    for op in top:
        for a in op.invars:
            if isinstance(a, ConstAtom) and id(a.val) not in seen_consts:
                seen_consts.add(id(a.val))
                _mk("const", f"const@{op.path}", a.aval, -1, n)

    # op outputs: born at their op, die at the last read / escape with the
    # program outputs. Control-flow ops charge their body's internal peak
    # as a transient during the op itself.
    produced = set()
    for i, op in enumerate(top):
        extra = _scope_extra(op, "", scope_peaks)
        if extra:
            buffers.append(Buffer("body", f"{op.path} body", (), "-",
                                  extra, i, i))
        for oi, ov in enumerate(op.outvars):
            produced.add(ov)
            dies = n if ov in out_set else last_use.get(ov, i)
            suffix = f"#{oi}" if len(op.outvars) > 1 else ""
            _mk("op", op.path + suffix, getattr(ov, "aval", None), i, dies)

    # output positions that are not a fresh op output — input passthroughs,
    # constants, and repeated atoms — each materialize their OWN buffer at
    # exit: an un-donated XLA program copies aliased outputs instead of
    # forwarding the input buffer (measured: jit output arrays are distinct
    # allocations per position, see MEMORY_PLAN.md)
    seen_outs = set()
    for pos, a in enumerate(getattr(ctx, "out_atoms", ())):
        if isinstance(a, Literal):
            _mk("out-copy", f"output[{pos}]", getattr(a, "aval", None), n, n)
            continue
        fresh = a in produced and a not in seen_outs
        seen_outs.add(a)
        if not fresh:
            _mk("out-copy", f"output[{pos}]", getattr(a, "aval", None), n, n)

    peak, at = _peak_of([(b.born, b.dies, b.nbytes) for b in buffers], n)
    nodon_peak, _ = _peak_of(
        [(b.born, n if b.donated else b.dies, b.nbytes) for b in buffers], n
    )
    peak_op = top[at].path if 0 <= at < n else ("exit" if at >= n else "entry")
    return MemoryPlan(buffers, n, peak, at, peak_op, nodon_peak)


# ---------------------------------------------------------------------------
# Device HBM detection (budget fallback when no explicit flag is set)
# ---------------------------------------------------------------------------
_hbm_cache: List = [False, None]


def device_hbm_bytes() -> Optional[int]:
    """Accelerator memory capacity of device 0, or None when the backend
    does not report one (CPU runs return None so tests stay quiet).

    Never FORCES backend initialization: a trace-only lint must not grab
    the accelerator (or block on a held libtpu) just to ask its size —
    when no backend is up yet, report None without caching so a later
    call after initialization still probes."""
    if _hbm_cache[0]:
        return _hbm_cache[1]
    try:
        from jax._src import xla_bridge

        if not getattr(xla_bridge, "_backends", None):
            return None  # uninitialized — don't init, don't cache
    except Exception:
        pass  # cannot tell — fall through and probe as before
    d = jax.devices()[0]
    # a TPU that reports no limit is an error, not a quiet "no budget"
    val = int(d.memory_stats()["bytes_limit"]) if d.platform == "tpu" else None
    _hbm_cache[:] = [True, val]
    return val


# ---------------------------------------------------------------------------
# Pass 6: memory_budget
# ---------------------------------------------------------------------------
@register_pass("memory_budget")
def memory_budget(ctx: Context) -> List[Diagnostic]:
    if not getattr(ctx, "ops", None):
        return []
    budget_mb = getattr(ctx, "memory_budget_mb", None)
    if budget_mb is None:
        flagged = float(_flags.flag("memory_budget_mb"))
        budget_mb = flagged if flagged > 0 else None
    donated = tuple(getattr(ctx, "donated", ()) or ())
    hbm = device_hbm_bytes()
    if budget_mb is None and not donated and hbm is None:
        return []  # not configured — stay silent in the default suites

    plan = plan_memory(ctx)
    diags = []
    if budget_mb is not None or donated:
        # the peak report is emitted only when the user configured a budget
        # or donation info is present — a detected device HBM alone gates
        # the OOM error below but must not turn every checked program into
        # a warning under FLAGS_check_programs (stay-silent contract)
        top = plan.top_live(5)
        top_str = ", ".join(
            f"{b.label()} {b.dtype}{list(b.shape)} {_fmt_bytes(b.nbytes)}"
            for b in top
        )
        credit = (
            f"; donation credit {_fmt_bytes(plan.donation_credit_bytes)} "
            f"({len([b for b in plan.buffers if b.donated])} donated buffers)"
            if donated else ""
        )
        # mesh-scoped contexts carry per-shard avals, so the whole plan —
        # peak, inputs, donation credit — is what ONE device holds
        per_dev = (" per device" if getattr(ctx, "mesh_axes", None) else "")
        diags.append(Diagnostic(
            Severity.INFO, "memory_budget",
            plan.peak_op_path
            if 0 <= plan.peak_index < plan.n_ops else "program",
            f"estimated peak HBM{per_dev} {_fmt_bytes(plan.peak_bytes)} "
            f"(inputs {_fmt_bytes(plan.input_bytes)}, consts "
            f"{_fmt_bytes(plan.const_bytes)}, outputs "
            f"{_fmt_bytes(plan.output_bytes)}{credit}); "
            f"largest live: {top_str}",
            shapes=tuple(b.shape for b in top),
            dtypes=tuple(b.dtype for b in top),
            data=plan.to_dict(),
        ))
    per_dev = (" per device" if getattr(ctx, "mesh_axes", None) else "")
    budget_bytes = int(budget_mb * _MB) if budget_mb else None
    if budget_bytes is not None and plan.peak_bytes > budget_bytes:
        diags.append(Diagnostic(
            Severity.ERROR, "memory_budget", "program",
            f"estimated peak HBM{per_dev} {_fmt_bytes(plan.peak_bytes)} exceeds the "
            f"declared budget of {budget_mb:g} MB "
            f"(FLAGS_memory_budget_mb)",
            hint="shrink batch/activation sizes, enable whole-step capture "
                 "donation (FLAGS_eager_capture_donate), or raise the "
                 "budget; the largest live buffers are listed in the "
                 "memory report diagnostic",
            data={"peak_bytes": int(plan.peak_bytes),
                  "budget_mb": float(budget_mb)},
        ))
    if hbm is not None and plan.peak_bytes > hbm:
        diags.append(Diagnostic(
            Severity.ERROR, "memory_budget", "program",
            f"estimated peak HBM {_fmt_bytes(plan.peak_bytes)} exceeds "
            f"device memory ({_fmt_bytes(hbm)}): this program will OOM at "
            "buffer assignment",
            hint="shard the model, shrink the batch, or enable recompute",
            data={"peak_bytes": int(plan.peak_bytes), "hbm_bytes": int(hbm)},
        ))
    return diags


def _use_after_donate_diag(label, holders, source="") -> Diagnostic:
    """The one use-after-donate ERROR, shared by the static pass (caller-
    provided alias_refs) and the runtime gc scan."""
    held = "; ".join(str(h) for h in holders[:3])
    more = f" (+{len(holders) - 3} more)" if len(holders) > 3 else ""
    return Diagnostic(
        Severity.ERROR, "donation_safety", label,
        f"use-after-donate: {len(holders)} live external reference(s) "
        f"alias this donated buffer [{held}{more}]; on TPU/GPU the alias "
        "dies with the donation (state_dict()/detach() held across a "
        "donated step is the classic shape of this bug)",
        hint="copy before holding (alias.clone()), drop the alias before "
             "the step, or set FLAGS_eager_capture_donate=0 to keep "
             "1-program capture without donation",
        source=source,
    )


# ---------------------------------------------------------------------------
# Pass 7: donation_safety
# ---------------------------------------------------------------------------
@register_pass("donation_safety")
def donation_safety(ctx: Context) -> List[Diagnostic]:
    donated = set(getattr(ctx, "donated", ()) or ())
    if not donated:
        return []  # nothing donated — vacuously safe, stay silent
    roles = ctx.invar_roles()
    alias_refs = getattr(ctx, "alias_refs", None) or {}
    alias_groups = getattr(ctx, "alias_groups", None) or []
    out_ids = {id(a) for a in getattr(ctx, "out_atoms", ())}
    last_use = set()
    for op in ctx.ops:
        for a in op.invars:
            last_use.add(id(a))

    diags: List[Diagnostic] = []

    def _name(idx):
        if idx < len(roles):
            kind, name = roles[idx][1]
            return f"{kind}:{name}"
        return f"arg:{idx}"

    for idx in sorted(donated):
        if idx >= len(roles):
            continue
        invar = roles[idx][0]
        if id(invar) in out_ids:
            diags.append(Diagnostic(
                Severity.ERROR, "donation_safety", _name(idx),
                "donated input is returned unchanged: the fetched output "
                "aliases a buffer XLA has already reused",
                hint="drop the passthrough output or remove this position "
                     "from donate_argnums",
                shapes=(tuple(getattr(invar.aval, "shape", ())),),
            ))
        elif id(invar) not in last_use:
            diags.append(Diagnostic(
                Severity.INFO, "donation_safety", _name(idx),
                "donated input is never read: its buffer is freed at "
                "program entry (full donation credit)",
            ))

    for group in alias_groups:
        g = set(group)
        dg = g & donated
        if dg and len(g) > 1:
            names = ", ".join(_name(i) for i in sorted(g))
            diags.append(Diagnostic(
                Severity.ERROR, "donation_safety", _name(min(dg)),
                f"one runtime buffer is bound to {len(g)} argument "
                f"positions ({names}) and at least one of them is donated: "
                "XLA will reuse the buffer while another position still "
                "reads it",
                hint="pass distinct arrays, or exclude the position from "
                     "donation",
            ))

    for idx, holders in sorted(alias_refs.items()):
        if idx not in donated or not holders:
            continue
        diags.append(_use_after_donate_diag(_name(idx), list(holders)))

    if not any(d.severity >= Severity.ERROR for d in diags):
        diags.append(Diagnostic(
            Severity.INFO, "donation_safety", "program",
            f"all {len(donated)} donated argument positions verified: no "
            "escaping outputs, no double-bound buffers, no live external "
            "aliases",
        ))
    return diags


def donation_verdicts(ctx: Context) -> List[Dict[str, object]]:
    """Per-position donation_safety verdicts over ``ctx``'s donated invars.

    One record per donated flat argument position:
    ``{"position", "role", "proven", "diagnostics"}`` — ``proven`` is True
    iff no ERROR-severity donation_safety diagnostic names the position
    (by its ``kind:name`` role label, directly as the diagnostic's op or
    inside a group-alias message). This is the gate the mesh-aware capture
    controller keys donation on — EVERY position must prove, or the
    captured program replays non-donated (capture_donation_fallbacks) —
    and the per-position table ``graph_lint --mesh --json`` prints."""
    from . import run_passes

    donated = sorted(set(getattr(ctx, "donated", ()) or ()))
    diags = [d for d in run_passes(ctx, ["donation_safety"])
             if d.pass_name == "donation_safety"]
    roles = ctx.invar_roles()

    def _name(idx):
        if idx < len(roles):
            kind, name = roles[idx][1]
            return f"{kind}:{name}"
        return f"arg:{idx}"

    out = []
    for idx in donated:
        label = _name(idx)
        errs = [d for d in diags
                if d.severity >= Severity.ERROR
                and (d.op == label or label in (d.message or ""))]
        out.append({
            "position": int(idx),
            "role": label,
            "proven": not errs,
            "diagnostics": [d.message for d in errs],
        })
    return out


# ---------------------------------------------------------------------------
# Runtime alias scan (the compile-time cross-check of the capture path's
# aliased_leaves fallback): enumerate live Tensor objects wrapping an array
# ---------------------------------------------------------------------------
def _scan_tensor_holders(target_ids, exclude=()) -> Dict[int, List[str]]:
    """ONE ``gc.get_objects()`` heap pass: {id(array): [description of live
    Tensor wrapping it]} for every id in ``target_ids`` (a per-buffer
    ``gc.get_referrers`` walk would traverse the heap once per parameter —
    prohibitive for large models under FLAGS_check_programs)."""
    import gc

    from ..core.tensor import Tensor

    ex = {id(t) for t in exclude}
    found: Dict[int, List[str]] = {}
    for obj in gc.get_objects():
        if isinstance(obj, Tensor) and id(obj) not in ex:
            v = getattr(obj, "_value", None)
            if id(v) in target_ids:
                name = getattr(obj, "name", "") or "<unnamed>"
                found.setdefault(id(v), []).append(
                    f"Tensor {name} shape={tuple(getattr(v, 'shape', ()))}"
                )
    return found


def tensor_aliases(arr, exclude=()) -> List[str]:
    """Descriptions of live ``Tensor`` objects (outside ``exclude``) whose
    ``_value`` IS ``arr``. These are exactly the references a buffer
    donation invalidates: ``p.detach()`` results, ``state_dict()`` wrappers,
    saved activations — held across a donated step, they die with it."""
    return _scan_tensor_holders({id(arr)}, exclude).get(id(arr), [])


def donated_buffer_alias_diags(named_arrays, exclude=(),
                               source="captured-step") -> List[Diagnostic]:
    """donation_safety diagnostics for to-be-donated runtime buffers.

    ``named_arrays``: [(label, jax array)] about to be donated;
    ``exclude``: Tensor objects that legitimately own them (the parameters
    themselves). One ERROR per aliased buffer, [] when all are clean.

    One ``gc.get_objects()`` heap pass covers ALL buffers."""
    found = _scan_tensor_holders(
        {id(arr) for _label, arr in named_arrays}, exclude
    )
    diags = []
    for label, arr in named_arrays:
        holders = found.get(id(arr), [])
        if holders:
            diags.append(_use_after_donate_diag(label, holders, source))
    return diags


def donated_buffer_diags(named_arrays, exclude=(),
                         source="captured-step") -> List[Diagnostic]:
    """The full runtime donation-safety scan shared by the whole-step
    capture replay and ``compile_train_step``: duplicate-bound buffers
    (tied weights — one array at two donated positions, which XLA cannot
    donate twice) plus the live-external-alias scan. Error-severity
    findings bump the ``donation_alias_flags`` dispatch counter."""
    by_id: Dict[int, List[str]] = {}
    for label, arr in named_arrays:
        by_id.setdefault(id(arr), []).append(label)
    diags: List[Diagnostic] = []
    for labels in by_id.values():
        if len(labels) > 1:
            diags.append(Diagnostic(
                Severity.ERROR, "donation_safety", labels[0],
                f"one runtime buffer is bound to {len(labels)} donated "
                f"positions ({', '.join(labels)}): XLA cannot donate the "
                "same buffer twice — the second donation reads an "
                "already-reused buffer",
                hint="untie the arrays (clone one), or exclude the shared "
                     "buffer from donation",
                source=source,
            ))
    diags += donated_buffer_alias_diags(named_arrays, exclude=exclude,
                                        source=source)
    if diags:
        from ..core.dispatch import _counters

        _counters["donation_alias_flags"] += len(diags)
    return diags


def donation_gate(params, states, trace_thunk, roles, donated, source,
                  static_diags=None) -> List[Diagnostic]:
    """The one donation-safety gate shared by the whole-step capture replay
    and ``compile_train_step``: runtime scan of the to-be-donated param and
    optimizer-state buffers (duplicates + live external aliases) plus the
    static traced-program passes, then ``enforce`` per
    ``FLAGS_check_programs``. Pass the previous return value as
    ``static_diags`` to reuse the (expensive) static result — it is only
    returned after enforce() succeeds, so a raising verdict is re-proven on
    the next call instead of being disarmed."""
    from . import enforce

    named = [
        (f"param:{getattr(p, 'name', '') or i}", p._value)
        for i, p in enumerate(params)
    ]
    for i, st in enumerate(states):
        for k in sorted(st):
            named.append((f"opt_state:{i}.{k}", st[k]))
    diags = donated_buffer_diags(named, exclude=params, source=source)
    if static_diags is None:
        static_diags = traced_program_diags(trace_thunk, roles, donated,
                                            source)
    enforce(diags + static_diags, where=f"{source} donation")
    return static_diags


def traced_program_diags(trace_thunk, roles, donated,
                         source) -> List[Diagnostic]:
    """Once-per-build static check of a donated program: trace it (no
    compile) and run the memory passes. Tracing failures yield [] — the
    static check must never break the step it audits."""
    from . import run_passes

    try:
        closed = trace_thunk()
        ctx = Context(closed, roles, source, donated=donated)
        return run_passes(ctx, ["memory_budget", "donation_safety"])
    except Exception:
        return []


@dataclasses.dataclass
class BlockPoolPlan:
    """Planner verdict sizing a paged KV block pool (paddle.serving).

    ``num_blocks`` is None when no budget is configured anywhere (flag,
    argument, or detected device HBM) — the caller applies its own default.
    ``overhead_bytes`` is the decode program's estimated peak *excluding*
    the pool itself: weights, activations, the gathered block views, block
    tables. The pool gets whatever the budget leaves."""

    num_blocks: Optional[int]
    block_bytes: int
    budget_bytes: Optional[int]
    overhead_bytes: int
    trace_peak_bytes: int

    @property
    def est_peak_hbm_mb(self) -> float:
        """Estimated peak of the traced decode program (MB)."""
        return self.trace_peak_bytes / _MB

    def pool_bytes(self, num_blocks: Optional[int] = None) -> int:
        n = self.num_blocks if num_blocks is None else num_blocks
        return int(n or 0) * self.block_bytes


def plan_block_pool(trace_thunk, *, block_bytes: int,
                    pool_bytes_in_trace: int = 0,
                    budget_mb: Optional[float] = None,
                    roles: Sequence = (), donated: Sequence[int] = (),
                    source: str = "serving-decode") -> BlockPoolPlan:
    """Size a paged KV block pool against the memory budget — the serving
    half of the ``memory_budget`` pass: trace the decode program once (no
    compile) over a MINIMAL pool, estimate its peak with the liveness
    planner, subtract the minimal pool's own bytes to get the non-pool
    overhead, and floor-divide the remaining budget by the per-block cost.
    The engine then refuses admission past the resulting pool instead of
    letting XLA OOM mid-decode.

    Budget precedence: explicit ``budget_mb`` > FLAGS_memory_budget_mb > the
    detected device HBM times FLAGS_fraction_of_gpu_memory_to_use; with none
    of the three, ``num_blocks`` is None.
    A failure to trace or plan the program propagates: a pool sized without
    its program's overhead would claim memory the weights already hold."""
    if budget_mb is None:
        flagged = float(_flags.flag("memory_budget_mb"))
        budget_mb = flagged if flagged > 0 else None
    budget_bytes = int(budget_mb * _MB) if budget_mb is not None else None
    if budget_bytes is None:
        # the device's reported limit, less the share the framework leaves
        # free (FLAGS_fraction_of_gpu_memory_to_use): the trace below covers
        # ONE program, and the prefill programs, XLA's own scratch and the
        # caller's other arrays live on the same device
        hbm = device_hbm_bytes()
        budget_bytes = int(
            hbm * float(_flags.flag("fraction_of_gpu_memory_to_use"))
        ) if hbm else None

    closed = trace_thunk()
    ctx = Context(closed, list(roles), source, donated=tuple(donated))
    peak = plan_memory(ctx, donated=tuple(donated)).peak_bytes
    overhead = max(0, int(peak) - int(pool_bytes_in_trace))

    num_blocks: Optional[int] = None
    if budget_bytes is not None:
        num_blocks = max(0, (budget_bytes - overhead) // int(block_bytes))
    return BlockPoolPlan(
        num_blocks=num_blocks,
        block_bytes=int(block_bytes),
        budget_bytes=budget_bytes,
        overhead_bytes=overhead,
        trace_peak_bytes=int(peak),
    )


def captured_step_plans():
    """(donation-credited plan, no-donation plan) of the most recently
    replayed captured whole-step program on this thread, or None — the
    recipe behind paddle.profiler.measure_programs's `_memory` entry."""
    from ..core import lazy

    prog = lazy.captured_step_program()
    if prog is None:
        return None
    closed, donated, roles = prog
    ctx = Context(closed, roles, "captured-step")
    return plan_memory(ctx, donated=donated), plan_memory(ctx, donated=())
