"""paddle.jit — dygraph-to-compiled-program (to_static) and the compiled
training-step engine.

Reference analogue:
  - Dy2Static AST pipeline + ProgramTranslator + PartialProgramLayer
    (python/paddle/fluid/dygraph/dygraph_to_static/, jit.py to_static) — the
    reference rewrites Python AST into a proto Program and runs it via
    run_program_op inside dygraph;
  - StandaloneExecutor/InterpreterCore (framework/new_executor/
    interpretercore.h:39) — the async instruction interpreter.

TPU-native design: no AST rewriting and no instruction interpreter. Python
*is* the tracer — `to_static` runs the user's forward under jax.jit with
parameters/buffers bound to tracers, producing ONE fused XLA program (the
InterpreterCore's job — scheduling, stream sync, GC — is all inside XLA).
The compiled call is then recorded on the eager tape as a single op, so
`loss.backward()` still works and differentiates *through* the compiled
forward. Data-dependent Python control flow must use static shapes /
lax.cond-style ops, mirroring the reference's ProgramTranslator constraints.

`compile_train_step` goes further: forward + backward + optimizer update in
one donated-buffer XLA program — the performance path used by hapi, the
benchmark's cells, and the distributed engine. The step program itself (loss,
gradient, clip, update) is `jit/step.py`, shared with the mesh builders.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dispatch as _dispatch
from ..core import random as _random
from ..core.dispatch import apply, no_grad
from ..core.tensor import Tensor
from ..nn.layer_base import Layer
from ..profiler import trace as _trace
from . import step as _step_core
from .step import _bind_values

__all__ = [
    "to_static",
    "not_to_static",
    "functional_call",
    "compile_train_step",
    "TranslatedLayer",
    "save",
    "load",
    "InputSpec",
]


class InputSpec:
    """reference: python/paddle/static/input.py InputSpec."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"


# ---------------------------------------------------------------------------
# functional bridge: run a stateful Layer with swapped-in (traced) values
# ---------------------------------------------------------------------------
def functional_call(layer: Layer, params: Dict[str, Any], *args, rngs=None, **kwargs):
    """Run `layer` with parameter/buffer values from `params` (a dict from
    state_dict-style names to arrays/tracers). Tape recording is disabled —
    gradients come from jax.grad over this function."""
    named = dict(layer.named_parameters())
    named.update(dict(layer.named_buffers()))
    tensors, values = [], []
    for k, v in params.items():
        if k in named:
            tensors.append(named[k])
            values.append(v._value if isinstance(v, Tensor) else v)
    wrapped = [Tensor(a, stop_gradient=True) if not isinstance(a, Tensor) else a for a in args]
    ctx = _random.rng_scope(rngs) if rngs is not None else contextlib.nullcontext()
    with _bind_values(tensors, values), no_grad(), ctx:
        return layer(*wrapped, **kwargs)


def _unwrap(o):
    if isinstance(o, Tensor):
        return o._value
    if isinstance(o, (list, tuple)):
        return type(o)(_unwrap(x) for x in o)
    if isinstance(o, dict):
        return {k: _unwrap(v) for k, v in o.items()}
    return o


# ---------------------------------------------------------------------------
# to_static
# ---------------------------------------------------------------------------
class StaticFunction:
    """The compiled wrapper produced by @to_static.

    Calls lower to one cached-jit XLA program whose inputs are
    (params..., buffers..., rng_key, *tensor_args); the call is recorded on
    the tape as a single op so backward works (grads flow to params AND
    tensor args). Mirrors PartialProgramLayer's run_program_op trick
    (dygraph_to_static/partial_program.py) without the proto Program."""

    def __init__(self, function: Callable, input_spec=None, layer: Optional[Layer] = None):
        self._dygraph_function = function
        # AST-convert data-dependent control flow (if/while/for-range over
        # tensors → lax.cond/while_loop) — the Dy2Static pipeline's job
        # (reference: loop_transformer.py:486, ifelse_transformer.py). Falls
        # back to the original function when no source is available.
        from .dy2static import convert_to_static

        self._converted_function = convert_to_static(function)
        self._input_spec = input_spec
        self._layer = layer
        self._compiled: Dict[Tuple, Callable] = {}

    @property
    def dygraph_function(self):
        return self._dygraph_function

    def _params_buffers(self):
        if self._layer is None:
            return [], []
        params = [p for _, p in self._layer.named_parameters()]
        buffers = [b for _, b in self._layer.named_buffers()]
        return params, buffers

    @staticmethod
    def _classify_arg(a):
        """Traced (array-like) vs static (hashable config) argument."""
        if isinstance(a, (Tensor, jax.Array, np.ndarray)):
            return None  # traced slot
        if a is None or isinstance(a, (bool, int, float, str)):
            return a
        if isinstance(a, (tuple, list)) and all(
            x is None or isinstance(x, (bool, int, float, str)) for x in a
        ):
            return tuple(a)
        raise TypeError(
            f"to_static argument of type {type(a).__name__} is neither a "
            "tensor/array (traced) nor simple static config; wrap it in a "
            "Tensor or pass it via closure"
        )

    def __call__(self, *args, **kwargs):
        params, buffers = self._params_buffers()
        n_p, n_b = len(params), len(buffers)

        tensor_args = []
        arg_template: List[Any] = []
        for a in args:
            slot = self._classify_arg(a)
            arg_template.append(slot if not (isinstance(a, (Tensor, jax.Array, np.ndarray))) else None)
            if isinstance(a, (Tensor, jax.Array, np.ndarray)):
                tensor_args.append(a if isinstance(a, Tensor) else Tensor(jnp.asarray(a)))
        kw_static = tuple(sorted(kwargs.items()))

        fn = self._converted_function
        layer = self._layer
        training = layer.training if layer is not None else True
        template = tuple(
            "T" if t is None else ("S", t) for t in arg_template
        )
        cfg = (template, kw_static, training, n_p, n_b)

        # one pure closure per static configuration — a stable function
        # identity is what keys the dispatcher's jit compile cache
        pure = self._compiled.get(cfg)
        if pure is None:
            frozen_template = tuple(arg_template)

            def pure(*flat):
                p_vals = flat[:n_p]
                b_vals = flat[n_p : n_p + n_b]
                key = flat[n_p + n_b]
                in_vals = list(flat[n_p + n_b + 1 :])
                rebuilt = []
                it = iter(in_vals)
                for t in frozen_template:
                    rebuilt.append(
                        Tensor(next(it), stop_gradient=True) if t is None else t
                    )
                with _bind_values(params + buffers, list(p_vals) + list(b_vals)), \
                        no_grad(), _random.rng_scope(key):
                    out = fn(*rebuilt, **dict(kw_static))
                    # read buffer values INSIDE the bind scope: forward may
                    # have updated them (BatchNorm running stats) and the
                    # bind context restores originals on exit
                    new_b = [b._value for b in buffers]
                out = _unwrap(out)
                flat_out = list(out) if isinstance(out, (tuple, list)) else [out]
                pure._meta = {
                    "n_out": len(flat_out),
                    "is_seq": isinstance(out, (tuple, list)),
                }
                return tuple(flat_out) + tuple(new_b)

            pure._meta = None
            pure.__name__ = f"to_static:{getattr(fn, '__name__', 'fn')}"
            self._compiled[cfg] = pure
            if _verbosity > 0:
                print(
                    f"[to_static] new static configuration for "
                    f"{pure.__name__}: template={template} "
                    f"kwargs={kw_static} training={training}"
                )
            if _code_level is not None and _code_level > 0:
                # the traced program IS the transformed code here: print its
                # jaxpr (reference set_code_level prints transformed source)
                try:
                    flat_spec = (
                        [p._value for p in params]
                        + [b._value for b in buffers]
                        + [_random.next_key()]
                        + [t._value for t in tensor_args]
                    )
                    print(jax.make_jaxpr(pure)(*flat_spec))
                except Exception as e:  # debugging aid must never break a run
                    print(f"[to_static] jaxpr dump failed: {e}")

        key_arr = _random.next_key()
        # `pure` is a closure (uncacheable by code identity) but its OBJECT
        # identity is stable per static config (held in self._compiled), so
        # it serves as its own cache token — this is what makes to_static
        # actually compile once and replay the XLA program on later calls
        outs = apply(
            pure, *params, *buffers, key_arr, *tensor_args,
            op_name=pure.__name__, cache_token=pure,
        )
        meta = pure._meta
        model_outs = outs[: meta["n_out"]]
        buf_outs = outs[meta["n_out"] :]
        if buf_outs:
            with no_grad():
                for b, nb in zip(buffers, buf_outs):
                    b._value = nb._value
        if meta["is_seq"]:
            return list(model_outs)
        return model_outs[0]

    def check(self, input_spec=None, **kwargs):
        """Run the paddle_tpu.analysis verifier over this compiled function
        (traced with `input_spec`, falling back to the decorator's spec).
        Returns the Diagnostic list — see paddle.static.analysis.check."""
        from .. import analysis

        return analysis.check(self, input_spec, **kwargs)

    # compatibility surface
    def concrete_program(self):
        raise NotImplementedError

    def rollback(self):
        return self._dygraph_function


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """paddle.jit.to_static decorator (reference: fluid/dygraph/jit.py)."""

    def decorate(fn):
        if isinstance(fn, Layer):
            layer = fn
            sf = StaticFunction(layer.forward, input_spec, layer)
            layer.forward = sf
            return layer
        if hasattr(fn, "__self__") and isinstance(fn.__self__, Layer):
            return StaticFunction(fn, input_spec, fn.__self__)

        @functools.wraps(fn)
        def maybe_layer_method(*args, **kw):
            if args and isinstance(args[0], Layer):
                # unbound Layer.forward decorated at class level
                inst = args[0]
                cache_name = "_static_forward_cache"
                sf = getattr(inst, cache_name, None)
                if sf is None:
                    # bind THEN wrap: a MethodType converts through the
                    # dy2static AST pipeline, a functools.partial would not
                    import types as _types

                    sf = StaticFunction(
                        _types.MethodType(fn, inst), input_spec, inst
                    )
                    setattr(inst, cache_name, sf)
                return sf(*args[1:], **kw)
            sf = maybe_layer_method._static_fn
            return sf(*args, **kw)

        maybe_layer_method._static_fn = StaticFunction(fn, input_spec, None)
        return maybe_layer_method

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


class ProgramTranslator:
    """reference: dygraph_to_static/program_translator.py — global toggle."""

    _instance = None
    enable_to_static = True

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static):
        ProgramTranslator.enable_to_static = enable_to_static


def enable_to_static(flag=True):
    ProgramTranslator.get_instance().enable(flag)


# ---------------------------------------------------------------------------
# Whole-step compilation (forward+backward+optimizer in one XLA program)
# ---------------------------------------------------------------------------
class CompiledTrainStep:
    """One donated-buffer XLA program per (shapes, training-phase).

    This is the TPU replacement for the reference's executor hot loop: where
    InterpreterCore schedules ~hundreds of kernels per step with stream sync
    and GC (new_executor/interpretercore.cc:527), here XLA fuses the whole
    step; parameters and optimizer state are donated so updates happen
    in-place in HBM.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer, mesh=None,
                 in_shardings=None, grad_input_idx=(), memory_plan=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # multi-chip: params/optimizer state follow parallel.sharding's
        # capture_step_shardings specs; in_shardings gives one Sharding (or
        # PartitionSpec, resolved on `mesh`) per batch argument. None entries
        # stay uncommitted and XLA places them.
        self.mesh = mesh if (mesh is not None and
                             getattr(mesh, "devices", None) is not None and
                             mesh.devices.size > 1) else None
        self._in_shardings = in_shardings
        self._placed = False  # params/state device_put once, on first call
        self._step = None
        self._step_fn_raw = None  # unjitted step fn, kept for the planner
        self._arg_specs = None  # ShapeDtypeStructs of the last call's args
        self._batch_sig = None
        self._static_donation_diags = None  # cached after a clean enforce
        self._opt_state = None
        self._params = [p for p in model.parameters() if not p.stop_gradient]
        self._buffers = [b for _, b in model.named_buffers()]
        # batch positions to ALSO differentiate: their grads come back to
        # the caller instead of an optimizer (the PS sparse path — pulled
        # embedding rows are step inputs, their grads push to the host
        # table; reference: distributed_push_sparse after the backward)
        self._grad_input_idx = tuple(int(i) for i in grad_input_idx)
        # planner-guided remat (analysis.plan): None = follow
        # FLAGS_memory_plan; "auto" = plan against FLAGS_memory_budget_mb;
        # an explicit RematPlan is rebound to this step's traced loss
        self._memory_plan_req = memory_plan
        self._mem_plan = None  # the active RematPlan (None = unplanned)
        # EquivalenceCertificate binding the planned (remat-sliced) step to
        # the unplanned step trace (FLAGS_check_programs=2), or None
        self._plan_certificate = None

    def _init_opt_state(self):
        sched = getattr(self.optimizer, "_offload_sched", None)
        if sched is not None:
            # compile_train_step pins its optimizer state as donated device
            # arrays — anything the offload scheduler parked must come home
            # before the program takes ownership
            sched.ensure_resident(self.optimizer, self._params)
        return _step_core.init_opt_state(self.optimizer, self._params)

    def _make_loss_core(self):
        return _step_core.make_loss_core(
            self.model, self.loss_fn, self._params, self._buffers,
            grad_input_idx=self._grad_input_idx)

    def _wrap_flat_loss(self, flat_fn):
        """Adapt a planned flat callable (the sliced loss jaxpr's invars in
        flat order) back to the loss_core signature."""
        n_b = len(self._buffers)

        def planned_loss(p_vals, diff_vals, b_vals, key, batch_vals):
            flat, _tree = jax.tree_util.tree_flatten(
                (tuple(p_vals), tuple(diff_vals), tuple(b_vals), key,
                 tuple(batch_vals)))
            outs = flat_fn(*flat)
            return outs[0], tuple(outs[1:1 + n_b])

        return planned_loss

    def _make_step_fn(self, planned_loss=None):
        return _step_core.make_step_fn(
            planned_loss if planned_loss is not None
            else self._make_loss_core(),
            self.optimizer, self._params,
            grad_input_idx=self._grad_input_idx)

    def _batch_shardings(self, n_batch):
        """One jax Sharding (or None = uncommitted) per batch argument,
        resolved from the user's ``in_shardings`` — PartitionSpecs bind to
        ``self.mesh``, Shardings pass through, missing tail entries stay
        None."""
        from jax.sharding import NamedSharding, Sharding

        given = list(self._in_shardings or [])[:n_batch]
        given += [None] * (n_batch - len(given))
        out = []
        for s in given:
            if s is None or isinstance(s, Sharding):
                out.append(s)
            else:  # a PartitionSpec (or axis tuple coercible to one)
                out.append(NamedSharding(self.mesh, s))
        return out

    def _certify_planned_step(self, planned_step):
        """Proof-carrying parity for planner-guided remat
        (FLAGS_check_programs=2): certify the plan-sliced step trace
        structurally equivalent to the unplanned step — remat duplicates
        under ``prevent_cse`` are an allowlisted rewrite the prover
        canonicalizes away. Divergence means the planner changed the
        function and raises; an unprovable trace drops the plan (counted
        via the planner failure registry) and trains unplanned."""
        from ..analysis import ProgramVerificationError
        from ..analysis import plan as _plan
        from ..analysis.equivalence import prove_equivalent
        from ..core import dispatch

        try:
            cert = prove_equivalent(
                jax.make_jaxpr(planned_step)(*self._arg_specs),
                jax.make_jaxpr(self._make_step_fn(None))(*self._arg_specs),
                label_a="planned-step", label_b="unplanned-step",
                source="compile_train_step",
            )
        except Exception as e:
            _plan.record_failure("compile_train_step", e)
            dispatch._emit("capture", site="jit", phase="equivalence",
                           result="unprovable", why=type(e).__name__)
            self._mem_plan = None
            return self._make_step_fn(None)
        if not cert.equivalent:
            dispatch._emit("capture", site="jit", phase="equivalence",
                           result="divergent")
            raise ProgramVerificationError(
                "planner-guided remat step is not provably equivalent to "
                "the unplanned step: " + cert.summary(),
                [cert.divergence] if cert.divergence is not None else [])
        self._plan_certificate = cert
        dispatch._emit("capture", site="jit", phase="equivalence",
                       result="certified", ops=cert.n_ops[0],
                       outputs=cert.outputs_compared)
        return planned_step

    def _build(self):
        from ..core import flags as _flags

        plan = self._mem_plan
        planned = None
        if plan is not None and plan.has_cuts:
            planned = self._wrap_flat_loss(plan.bind())
        step_fn = self._make_step_fn(planned)
        if planned is not None and int(_flags.flag("check_programs")) >= 2:
            step_fn = self._certify_planned_step(step_fn)
        # donate params and optimizer state: XLA reuses their HBM buffers
        self._step_fn_raw = step_fn
        if self.mesh is not None:
            # mesh-aware build: pin param/state layouts to the same specs
            # the capture tier and ShardedTrainStep derive, so the donated
            # buffers round-trip without resharding between steps
            from ..parallel.sharding import capture_step_shardings

            p_sh, st_sh = capture_step_shardings(
                self._params, list(self._opt_state), self.mesh)
            batch_sh = self._batch_shardings(len(self._arg_specs) - 5)
            in_sh = (tuple(p_sh), tuple(st_sh), None, None, None, *batch_sh)
            out_sh = (None, None, tuple(p_sh), tuple(st_sh), None)
            return jax.jit(step_fn, in_shardings=in_sh,
                           out_shardings=out_sh, donate_argnums=(0, 1))
        return jax.jit(step_fn, donate_argnums=(0, 1))

    def _place(self, batch_vals):
        """device_put params/optimizer state onto their mesh shardings once
        (first call), and the batch per ``in_shardings`` every call — the
        mirror of ShardedTrainStep.__call__'s placement."""
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel.sharding import capture_step_shardings

        if not self._placed:
            p_sh, st_sh = capture_step_shardings(
                self._params, list(self._opt_state), self.mesh)
            for p, sh in zip(self._params, p_sh):
                p._value = jax.device_put(p._value, sh)
            for st, shd in zip(self._opt_state, st_sh):
                for k, sh in shd.items():
                    st[k] = jax.device_put(st[k], sh)
            rep = NamedSharding(self.mesh, PartitionSpec())
            for b in self._buffers:
                b._value = jax.device_put(b._value, rep)
            for p, st in zip(self._params, self._opt_state):
                self.optimizer._accumulators[id(p)] = st
            self._placed = True
        batch_sh = self._batch_shardings(len(batch_vals))
        return [v if sh is None else jax.device_put(v, sh)
                for v, sh in zip(batch_vals, batch_sh)]

    def _loss_specs(self):
        p, st, b, key, _lr, *batch = self._arg_specs
        diff = tuple(batch[i] for i in self._grad_input_idx)
        return (tuple(p), diff, tuple(b), key, tuple(batch))

    def plan_remat(self, budget_mb=None, max_evals=8):
        """Build a :class:`analysis.plan.RematPlan` for this step's current
        shapes (needs one executed step, like ``memory_plan()``): trace the
        loss path, search planner-chosen ``jax.checkpoint`` segmentations,
        and verify each candidate's peak by re-planning the FULL step
        (forward + backward + donated update) with the sliced loss
        substituted in. ``budget_mb=None`` reads FLAGS_memory_budget_mb.
        The returned plan feeds ``memory_plan=`` on a new step (or is
        applied automatically under ``memory_plan='auto'``)."""
        if self._arg_specs is None:
            raise RuntimeError(
                "plan_remat() needs one executed step first (the argument "
                "shapes are taken from the last call)"
            )
        from .. import analysis
        from ..analysis import memory as _memory
        from ..analysis import plan as _plan
        from ..core import flags as _flags

        budget_mb = (float(_flags.flag("memory_budget_mb"))
                     if budget_mb is None else float(budget_mb))
        loss_closed = jax.make_jaxpr(self._make_loss_core())(
            *self._loss_specs())
        roles, don = self._roles_and_donated()

        def measure(flat_fn) -> int:
            planned = (self._wrap_flat_loss(flat_fn)
                       if flat_fn is not None else None)
            closed = jax.make_jaxpr(self._make_step_fn(planned))(
                *self._arg_specs)
            ctx = analysis.Context(closed, roles, "compile_train_step",
                                   donated=don)
            return _memory.plan_memory(ctx).peak_bytes

        return _plan.build_remat_plan(
            loss_closed, budget_bytes=int(budget_mb * (1 << 20)),
            measure=measure, source="compile_train_step",
            max_evals=max_evals)

    def _resolve_plan(self):
        """The RematPlan to apply for the current shapes, or None. Explicit
        plans are rebound to a fresh loss trace; 'auto' (parameter or
        FLAGS_memory_plan) plans against FLAGS_memory_budget_mb. A failed
        build is counted (memory_plan_failures) and falls back unplanned."""
        from ..analysis import plan as _plan
        from ..core import flags as _flags

        req = self._memory_plan_req
        mode = req if req is not None else str(_flags.flag("memory_plan"))
        if not mode:
            return None
        try:
            if isinstance(mode, _plan.RematPlan):
                fresh = jax.make_jaxpr(self._make_loss_core())(
                    *self._loss_specs())
                if mode.n_eqns != len(fresh.jaxpr.eqns):
                    raise ValueError(
                        f"explicit RematPlan indexes {mode.n_eqns} top-level "
                        f"eqns but this step's loss traces to "
                        f"{len(fresh.jaxpr.eqns)} — replan for these shapes")
                mode.closed = fresh
                return mode if mode.has_cuts else None
            if mode != "auto":
                raise ValueError(
                    f"memory_plan={mode!r}: expected 'auto' or a RematPlan")
            if float(_flags.flag("memory_budget_mb")) <= 0:
                return None
            plan = self.plan_remat()
            return plan if plan.has_cuts else None
        except Exception as e:
            _plan.record_failure("compile_train_step", e)
            return None

    def _roles_and_donated(self):
        """(invar roles, donated flat invar indices) for the traced step:
        donate_argnums=(0, 1) donates the param and optimizer-state leaves,
        which flatten first in the jaxpr's invar order."""
        leaves = jax.tree_util.tree_leaves
        p, st, b, _key, _lr, *batch = self._arg_specs
        n_p, n_s, n_b = len(leaves(p)), len(leaves(st)), len(leaves(b))
        n_batch = len(leaves(list(batch)))
        roles = (
            [("param", getattr(t, "name", "") or f"param{i}")
             for i, t in enumerate(self._params)][:n_p]
            + [("buffer", f"opt_state{i}") for i in range(n_s)]
            + [("buffer", f"buffer{i}") for i in range(n_b)]
            + [("arg", "rng_key"), ("arg", "lr")]
            + [("feed", f"batch{i}") for i in range(n_batch)]
        )
        return roles, tuple(range(n_p + n_s))

    def memory_plan(self, donated=None):
        """Static liveness plan of the whole-step program (see
        paddle_tpu.analysis.memory): traces the step function — no compile
        — and returns a ``MemoryPlan`` with the donation-credited peak-HBM
        estimate. Needs one executed step first (arg shapes come from the
        last call). ``donated=()`` plans the same program without donation
        credit, quantifying what ``donate_argnums`` saves."""
        if self._arg_specs is None:
            raise RuntimeError(
                "memory_plan() needs one executed step first (the argument "
                "shapes are taken from the last call)"
            )
        from .. import analysis
        from ..analysis import memory as _memory

        closed = jax.make_jaxpr(self._step_fn_raw)(*self._arg_specs)
        roles, don = self._roles_and_donated()
        ctx = analysis.Context(closed, roles, "compile_train_step",
                               donated=don if donated is None else donated)
        return _memory.plan_memory(ctx)

    def _check_donation(self, states):
        """FLAGS_check_programs hook: gc-scan the to-be-donated buffers for
        live external Tensor aliases and double-bound (tied) buffers, plus
        (once per program shape) the static jaxpr-level donation-safety and
        memory-budget passes over the traced step. The static result is
        cached only after a clean enforce, so a raising verdict re-proves
        on retry instead of being disarmed."""
        from ..analysis import memory as _memory

        roles, don = self._roles_and_donated()
        self._static_donation_diags = _memory.donation_gate(
            self._params, states,
            lambda: jax.make_jaxpr(self._step_fn_raw)(*self._arg_specs),
            roles, don, "compile_train_step",
            static_diags=self._static_donation_diags,
        )

    @no_grad()
    def __call__(self, *batch) -> Tensor:
        """One training step. Spans (paddle.profiler.span: on a running
        trace's /host:CPU plane, and in the flight recorder's ring whether or
        not one runs): the root `compile_train_step` is a step annotation
        numbered by the optimizer's step count; `/args` gathers the leaves,
        lr and key and builds the step when the shapes are new; `/launch` is
        the jitted call alone (jax.jit compiles inside the first one: the
        ring's `compile` event says which); `/writeback` rebinds the
        results. The launch counts as one `compiled` program."""
        with _trace.span("compile_train_step",
                         step_num=self.optimizer._step_count):
            with _trace.span("compile_train_step/args"):
                args = self._gather_args(batch)
            with _trace.span("compile_train_step/launch"):
                loss, in_grads, new_p, new_s, new_b = self._step(*args)
            _dispatch._count_program("compiled")
            with _trace.span("compile_train_step/writeback"):
                # the old (donated) leaves die here, inside the span: left to
                # the frame's teardown, freeing ~5 arrays a parameter would be
                # host time of the step that no span covers
                del args
                self._opt_state = _step_core.write_back(
                    self.optimizer, self._params, self._buffers,
                    new_p, new_s, new_b)
                return _step_core.step_result(loss, in_grads)

    def _gather_args(self, batch):
        """The jitted step's arguments for this call; (re)builds the step
        when the batch's shapes are new."""
        if self._opt_state is None:
            self._opt_state = self._init_opt_state()
        batch_vals = [b._value if isinstance(b, Tensor) else jnp.asarray(b) for b in batch]
        if self.mesh is not None:
            batch_vals = self._place(batch_vals)
        p_vals = tuple(p._value for p in self._params)
        b_vals = tuple(b._value for b in self._buffers)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = _random.next_key()
        args = (p_vals, tuple(self._opt_state), b_vals, key, lr, *batch_vals)
        # only the batch can change shape between calls (params/state/key are
        # fixed); refresh the traced-spec snapshot when it does so
        # memory_plan() and the donation gate always see the LAST program
        batch_sig = tuple((tuple(b.shape), str(b.dtype)) for b in batch_vals)
        if self._arg_specs is None or batch_sig != self._batch_sig:
            self._batch_sig = batch_sig
            self._arg_specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype), args
            )
            self._static_donation_diags = None  # re-verify the new program
            if (self._memory_plan_req is not None
                    or self._mem_plan is not None or self._step is None):
                # (re)plan remat for the new shapes — the plan indexes the
                # loss trace's equations, so it is shape-specific. With no
                # plan requested this is a no-op and the jitted step is
                # reused across batch shapes exactly as before.
                self._step = None
        if self._step is None:
            self._mem_plan = self._resolve_plan()
            self._step = self._build()
        from ..core import flags as _flags

        if int(_flags.flag("check_programs")):
            # donation-safety gate (analysis.memory): flag live aliases of
            # the donated param/state buffers before XLA reuses them
            self._check_donation(self._opt_state)
        return args


def compile_train_step(model, loss_fn, optimizer, mesh=None, in_shardings=None,
                       grad_input_idx=(), memory_plan=None):
    return CompiledTrainStep(model, loss_fn, optimizer, mesh, in_shardings,
                             grad_input_idx, memory_plan)


# ---------------------------------------------------------------------------
# jit.save / jit.load — deployment artifacts
# ---------------------------------------------------------------------------
class TranslatedLayer(Layer):
    """Inference layer rebuilt from a serialized compiled program
    (reference: fluid/dygraph/io.py TranslatedLayer from __model__+params)."""

    def __init__(self, exported, state):
        super().__init__()
        self._exported = exported
        self._state = state

    def forward(self, *args):
        vals = [a._value if isinstance(a, Tensor) else jnp.asarray(a) for a in args]
        out = self._exported.call(*self._state, *vals)
        if isinstance(out, (list, tuple)):
            outs = [Tensor(o, stop_gradient=True) for o in out]
            return outs if len(outs) > 1 else outs[0]
        return Tensor(out, stop_gradient=True)


def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save — serialize weights + a StableHLO program.

    reference: fluid/dygraph/jit.py save (program + persistables); here the
    artifact is the portable StableHLO export plus a .pdparams state file."""
    from ..framework.io_utils import save as _save_state

    if isinstance(layer, Layer):
        fn = layer.forward
        if isinstance(fn, StaticFunction):
            # export the CONVERTED function: control flow a StaticFunction
            # runs through lax.cond/while must export the same way
            fn = fn._converted_function
        else:
            from .dy2static import convert_to_static

            fn = convert_to_static(fn)
        params = [p for _, p in layer.named_parameters()]
        buffers = [b for _, b in layer.named_buffers()]
        state = [t._value for t in params + buffers]
        if input_spec is None:
            raise ValueError("paddle.jit.save requires input_spec")

        def pure(*flat):
            n = len(params) + len(buffers)
            svals, ivals = flat[:n], flat[n:]
            ins = [Tensor(v, stop_gradient=True) for v in ivals]
            with _bind_values(params + buffers, list(svals)), no_grad():
                out = fn(*ins)
            return _unwrap(out)

        from ..framework.artifact import export_artifact

        # shape-polymorphic export: None dims stay symbolic so the predictor
        # can run any batch size from one artifact; the exported program
        # binds params + ALL buffers (including non-persistable ones that
        # state_dict omits) — artifact metadata keeps the ordered state list
        export_artifact(
            pure,
            path,
            input_names=[
                getattr(s, "name", None) or f"input_{i}"
                for i, s in enumerate(input_spec)
            ],
            input_shapes=[list(s.shape) for s in input_spec],
            input_dtypes=[getattr(s, "dtype", "float32") for s in input_spec],
            state=state,
        )
        _save_state(layer.state_dict(), path + ".pdparams")
    else:
        raise TypeError("paddle.jit.save expects a Layer")


def load(path, **configs):
    """paddle.jit.load — rebuild a TranslatedLayer."""
    from ..framework.artifact import load_artifact

    exp, state, _meta = load_artifact(path)
    return TranslatedLayer(exp, state)


# ---------------------------------------------------------------------------
# dy2static debugging knobs + legacy TracedLayer
# ---------------------------------------------------------------------------

_verbosity = 0
_code_level = None


def set_verbosity(level=0, also_to_stdout=False):
    """reference: jit/dy2static logging_utils.set_verbosity — controls how
    chatty the trace pipeline is (this build traces directly, so the knob
    gates the dispatcher's op-level logging)."""
    global _verbosity
    _verbosity = int(level)


def set_code_level(level=100, also_to_stdout=False):
    """reference: logging_utils.set_code_level — print transformed code. The
    trace-based pipeline has no AST stages; at any level >0 StaticFunction
    prints the jaxpr of the traced program when first compiled."""
    global _code_level
    _code_level = int(level)


class TracedLayer:
    """reference: fluid/dygraph/jit.py TracedLayer — trace a dygraph layer
    once, then run/save the traced program."""

    def __init__(self, layer, static_fn, example_inputs):
        self._layer = layer
        self._fn = static_fn
        self._example_inputs = example_inputs

    @staticmethod
    def trace(layer, inputs):
        """Returns (eager_outputs, traced_layer)."""
        inputs = list(inputs) if isinstance(inputs, (list, tuple)) else [inputs]
        out = layer(*inputs)
        fn = to_static(layer.forward)
        return out, TracedLayer(layer, fn, inputs)

    def __call__(self, inputs):
        inputs = list(inputs) if isinstance(inputs, (list, tuple)) else [inputs]
        out = self._fn(*inputs)
        return out if isinstance(out, (list, tuple)) else [out]

    def save_inference_model(self, path, feed=None, fetch=None, **kwargs):
        save(self._layer, path, input_spec=self._example_inputs)
        return path
