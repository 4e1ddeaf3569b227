"""Sharding specs + the compiled sharded train step (GSPMD path).

This replaces, in one mechanism, four reference subsystems (SURVEY.md §2.D):
  - DP grad allreduce (imperative/reducer.cc bucketed NCCL allreduce) —
    XLA inserts the gradient all-reduce when the batch is sharded on `dp`;
  - ZeRO stages 1-3 (meta_parallel/sharding/group_sharded_stage{2,3}.py,
    meta_optimizers/sharding_optimizer.py:45) — optimizer state (stage 1/2)
    and parameters (stage 3) carry a `sharding`-axis spec; XLA materializes
    reduce-scatter + all-gather exactly where the hand-written stages put
    them;
  - TP (meta_parallel/parallel_layers/mp_layers.py) — weight specs partition
    on `mp`, activations get sharding constraints;
  - the 143 collective ops (operators/collective/) — GSPMD emits the HLO
    collectives with replica_groups derived from the mesh.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import random as _random
from ..core.dispatch import no_grad
from ..core.tensor import Tensor
from ..jit import step as _step_core
from .topology import get_mesh

ShardingSpec = P


def param_spec(p: Tensor, zero_stage: int = 0, mesh: Optional[Mesh] = None) -> P:
    """Sharding spec for one parameter: explicit layer-assigned spec first
    (TP layers set `dist_spec`), else ZeRO-3 shards the first divisible dim
    over `sharding`, else replicated."""
    mesh = mesh or get_mesh()
    if getattr(p, "fuse_replicated", False):
        # pinned by the fuse_all_reduce pass: too small to be worth
        # sharding — ride the fused replicated all-reduce
        return P(*([None] * p.ndim))
    spec = getattr(p, "dist_spec", None)
    if spec is not None:
        spec = P(*spec) if not isinstance(spec, P) else spec
    else:
        spec = P(*([None] * p.ndim))
    if zero_stage >= 3 and mesh is not None:
        n_shard = dict(zip(mesh.axis_names, mesh.devices.shape)).get("sharding", 1)
        if n_shard > 1:
            entries = list(spec) + [None] * (p.ndim - len(list(spec)))
            for d in range(p.ndim):
                if entries[d] is None and p.shape[d] % n_shard == 0:
                    entries[d] = "sharding"
                    break
            spec = P(*entries)
    return spec


def _state_spec(pspec: P, shape, zero_stage: int, mesh: Mesh) -> P:
    """Optimizer-state spec: mirrors the param spec; ZeRO-1/2 additionally
    shards moments over `sharding` (the optimizer-state partitioning of
    group_sharded_optimizer_stage2.py:41)."""
    entries = list(pspec) + [None] * (len(shape) - len(list(pspec)))
    if zero_stage >= 1 and mesh is not None and len(shape) > 0:
        n_shard = dict(zip(mesh.axis_names, mesh.devices.shape)).get("sharding", 1)
        if n_shard > 1 and "sharding" not in entries:
            for d in range(len(shape)):
                if entries[d] is None and shape[d] % n_shard == 0:
                    entries[d] = "sharding"
                    break
    return P(*entries)


def shard_params(model, mesh: Optional[Mesh] = None, zero_stage: int = 0):
    """Device_put every parameter/buffer with its NamedSharding — after this
    the weights physically live distributed across the mesh."""
    mesh = mesh or get_mesh()
    if mesh is None:
        return model
    with no_grad():
        for p in model.parameters():
            s = NamedSharding(mesh, param_spec(p, zero_stage, mesh))
            p._value = jax.device_put(p._value, s)
        for b in model.buffers():
            b._value = jax.device_put(b._value, NamedSharding(mesh, P()))
    return model


def capture_step_shardings(params, states, mesh: Optional[Mesh] = None):
    """NamedShardings of the donated leaves of a mesh-aware captured step.

    The whole-step capture controller (core.lazy) jits its captured program
    with declared in/out shardings so the replay is the same one SPMD
    program `ShardedTrainStep` compiles, buffer placement included. Per
    parameter: the committed NamedSharding when the buffer already lives
    distributed (shard_params / an earlier donated replay), else the
    derived `param_spec`. Per optimizer-state leaf: the committed sharding,
    else replicated for scalars (step counts) and the param spec mirrored
    through `_state_spec` otherwise — exactly the layout
    `ShardedTrainStep._shardings` declares, so a capture at matched specs
    is bitwise-comparable. Returns ``(param_shardings, state_shardings)``
    aligned with ``params`` / ``states`` (each state entry a dict keyed
    like the optimizer accumulator dict)."""
    mesh = mesh or get_mesh()
    if mesh is None:
        raise ValueError("capture_step_shardings requires a mesh")

    def _committed(val):
        sh = getattr(val, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh.devices.size > 1:
            return sh
        return None

    p_sh: List[NamedSharding] = []
    st_sh: List[Dict[str, NamedSharding]] = []
    for p, st in zip(params, states):
        v = p._value if isinstance(p, Tensor) else p
        psh = _committed(v) or NamedSharding(mesh, param_spec(p, 0, mesh))
        p_sh.append(psh)
        d = {}
        for k in sorted(st):
            sv = st[k]
            csh = _committed(sv)
            if csh is not None:
                d[k] = csh
            elif getattr(sv, "ndim", 0) == 0:
                d[k] = NamedSharding(mesh, P())
            else:
                d[k] = NamedSharding(
                    mesh, _state_spec(psh.spec, sv.shape, 1, mesh))
        st_sh.append(d)
    return tuple(p_sh), tuple(st_sh)


import threading as _threading

_constraint_tls = _threading.local()


class suppress_sharding_constraints:
    """Scope that turns with_sharding_constraint into a no-op. Used by the
    pipeline schedule: inside the shard_map-manual-over-pp region, GSPMD
    constraints naming auto axes can crash XLA's partitioner (group-count
    check in spmd_partitioner_util.cc); weight shardings alone propagate the
    TP layout there."""

    def __enter__(self):
        self._prev = getattr(_constraint_tls, "off", False)
        _constraint_tls.off = True
        return self

    def __exit__(self, *exc):
        _constraint_tls.off = self._prev
        return False


def _sharding_constraint_op(x, *, mesh, spec):
    """Module-level op fn (stable per-op jit cache token): the GSPMD
    sharding annotation as a regular dispatched op, so an eager constraint
    joins the pending lazy segment instead of forcing a flush."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def with_sharding_constraint(x, *spec):
    """Annotation helper usable inside layer forwards (no-op without a mesh).
    The TPU analogue of inserting a c_split/c_concat/c_identity op."""
    mesh = get_mesh()
    val = x._value if isinstance(x, Tensor) else x
    if mesh is None or isinstance(val, np.ndarray):
        return x
    if getattr(_constraint_tls, "off", False):
        return x
    from ..core.flags import flag as _flag

    if isinstance(x, Tensor) and not isinstance(val, jax.core.Tracer) \
            and bool(_flag("eager_lazy_dispatch")):
        # lazy-eager path: dispatch as a regular lazy op. The constraint
        # stays inside the pending segment (one fused program, whole-step
        # capture keeps its 3-program shape) and GSPMD resolves it at
        # flush — the old jitted-identity eager lowering instead flushed
        # HERE, and refused single-device committed inputs (a pallas
        # kernel's eager flush output) against a mesh-spanning
        # out_sharding. Per-op eager mode (lazy dispatch off) keeps the
        # skip-on-conflict lowering below: its tensors are committed to
        # one device, and force-resharding just this value would feed
        # mixed placements to the next multi-arg op.
        from ..core import dispatch

        try:
            return dispatch.apply(
                _sharding_constraint_op, x, mesh=mesh, spec=tuple(spec),
                op_name="sharding_constraint",
            )
        except Exception:
            # repair committed-placement mismatches instead of skipping:
            # device_put reshards a concrete value from ANY placement
            try:
                from ..core.lazy import materialize as _mat

                out = jax.device_put(
                    _mat(val), NamedSharding(mesh, P(*spec)))
            except (ValueError, TypeError):
                return x
            t = Tensor(out, stop_gradient=x.stop_gradient)
            t._grad_node = x._grad_node
            t._out_index = x._out_index
            return t
    try:
        out = jax.lax.with_sharding_constraint(val, NamedSharding(mesh, P(*spec)))
    except (ValueError, TypeError):
        return x
    if isinstance(x, Tensor):
        t = Tensor(out, stop_gradient=x.stop_gradient)
        t._grad_node = x._grad_node
        t._out_index = x._out_index
        return t
    return out


class ShardedTrainStep:
    """Compiled hybrid-parallel train step over the global mesh.

    The single entry point that turns (model, loss, optimizer, strategy)
    into one SPMD XLA program: batch sharded over (dp, sharding), params per
    their specs (TP/ZeRO-3), optimizer state ZeRO-sharded, buffers
    replicated. Donation keeps params/opt-state in place in HBM.
    Reference counterpart: the whole
    fleet.distributed_model + HybridParallelOptimizer + reducer pipeline
    (fleet/meta_parallel/*).
    """

    def __init__(self, model, loss_fn, optimizer, mesh=None, zero_stage=0,
                 batch_axes=("dp", "sharding"), forward_ctx=None,
                 accumulate_steps=1, loss_scale=1.0, grad_input_idx=()):
        # grad_input_idx: batch positions to ALSO differentiate — their
        # grads return to the caller (the PS sparse path: pulled rows in, row
        # grads out, pushed to the host table; reference:
        # distributed_push_sparse). accumulate_steps > 1 = compiled gradient
        # merge: the leading batch dim must divide into that many
        # microbatches (strategy.gradient_merge)
        self.grad_input_idx, self.accumulate_steps = _step_core.check_merge(
            grad_input_idx, accumulate_steps)
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # zero-arg context-manager factory wrapped around the traced forward
        # (fleet wires strategy.amp through here as an auto_cast factory)
        self.forward_ctx = forward_ctx
        # static loss scaling for pure-fp16 compute (1.0 = off); grads are
        # unscaled before clipping/update inside the compiled step
        self.loss_scale = float(loss_scale)
        self.mesh = mesh or get_mesh()
        self.zero_stage = zero_stage
        self.batch_axes = tuple(
            a for a in batch_axes if a in (self.mesh.axis_names if self.mesh else ())
        )
        self._params = [p for p in model.parameters() if not p.stop_gradient]
        self._buffers = [b for _, b in model.named_buffers()]
        self._step = None
        self._opt_state = None

    def _shardings(self, opt_state=None):
        mesh = self.mesh
        states = opt_state if opt_state is not None else self._opt_state
        p_specs = [param_spec(p, self.zero_stage, mesh) for p in self._params]
        p_sh = tuple(NamedSharding(mesh, s) for s in p_specs)
        st_sh = []
        for p, spec, st in zip(self._params, p_specs, states):
            st_sh.append(
                {
                    k: NamedSharding(
                        mesh,
                        _state_spec(spec, v.shape, max(self.zero_stage, 1), mesh)
                        if v.ndim > 0
                        else P(),
                    )
                    for k, v in st.items()
                }
            )
        b_sh = tuple(NamedSharding(mesh, P()) for _ in self._buffers)
        batch_spec = P(self.batch_axes if self.batch_axes else None)
        return p_sh, tuple(st_sh), b_sh, NamedSharding(mesh, batch_spec)

    def _step_parts(self, n_batch_args, opt_state=None):
        """(step_fn, in_shardings, out_shardings) — the traced function and
        its declared shardings, pre-jit. The sharding analyzer
        (analysis.sharding.check_sharded_step) traces step_fn at per-shard
        shapes without paying the XLA compile; _build wraps the same triple
        in jax.jit."""
        params = self._params
        # hybrid dp×sharding + ZeRO: GSPMD cannot partition the weight-grad
        # dots when the grad's zero-spec (sharded over 'sharding', replicated
        # over 'dp') propagates into batch-sharded activations that span
        # BOTH axes — it falls back to 'Involuntary full rematerialization'
        # (replicate-then-repartition) of every such activation. Pinning the
        # grads to their TP spec (no zero dim) right after the backward
        # keeps the grad dot local (partial sums + one all-reduce over the
        # batch group); the reshard onto the zero spec then happens at the
        # optimizer update, where it is a local slice.
        axes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape)) \
            if self.mesh else {}
        # stage 3's sharded PARAMS hit the same trap from the other side:
        # the zero spec propagates backwards through the weight-grad dot
        # onto forward activations (r5: the ernie-ctr dryrun showed the
        # remat on a gelu output under dp2×sharding4 stage3), so all three
        # stages pin when both axes are real
        hybrid_zero = (self.zero_stage in (1, 2, 3) and axes.get("dp", 1) > 1
                       and axes.get("sharding", 1) > 1)
        pin_grads = None
        if hybrid_zero:
            grad_pin = [
                NamedSharding(self.mesh, param_spec(p, 0, self.mesh))
                for p in params
            ]

            def pin_grads(grads):
                return tuple(
                    jax.lax.with_sharding_constraint(g, s)
                    for g, s in zip(grads, grad_pin)
                )

        gidx = self.grad_input_idx
        step_fn = _step_core.make_step_fn(
            _step_core.make_loss_core(
                self.model, self.loss_fn, params, self._buffers,
                grad_input_idx=gidx, forward_ctx=self.forward_ctx),
            self.optimizer, params, grad_input_idx=gidx,
            accumulate_steps=self.accumulate_steps,
            loss_scale=self.loss_scale, pin_grads=pin_grads)

        p_sh, st_sh, b_sh, batch_sh = self._shardings(opt_state)
        repl = NamedSharding(self.mesh, P())
        in_sh = (p_sh, st_sh, b_sh, repl, repl) + (batch_sh,) * n_batch_args
        out_sh = (repl, (batch_sh,) * len(gidx), p_sh, st_sh, b_sh)
        return step_fn, in_sh, out_sh

    def _build(self, n_batch_args):
        step_fn, in_sh, out_sh = self._step_parts(n_batch_args)
        return jax.jit(
            step_fn, in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=(0, 1),
        )

    def _check_programs(self, batch):
        """FLAGS_check_programs gate: run the per-shard analysis suite over
        the traced step before the first compile. Same enforcement point as
        Executor.run (1 = warn, 2 = raise on errors); the trace itself must
        never block training, so its failures are swallowed."""
        from ..core.flags import flag as _flag

        if not int(_flag("check_programs")):
            return
        try:
            from ..analysis import enforce
            from ..analysis.sharding import check_sharded_step

            specs = [
                jax.ShapeDtypeStruct(
                    tuple((b._value if isinstance(b, Tensor)
                           else np.asarray(b)).shape),
                    (b._value if isinstance(b, Tensor)
                     else np.asarray(b)).dtype,
                )
                for b in batch
            ]
            diags = check_sharded_step(self, specs, source="sharded-step")
        except Exception:
            return
        enforce(diags, "sharded_train_step")

    @no_grad()
    def __call__(self, *batch) -> Tensor:
        if self.accumulate_steps > 1:
            for b in batch:
                n0 = (b._value if isinstance(b, Tensor) else np.asarray(b)).shape[0]
                if n0 % self.accumulate_steps:
                    raise ValueError(
                        f"global batch {n0} is not divisible by gradient-"
                        f"merge accumulate_steps={self.accumulate_steps}"
                    )
        if self._opt_state is None:
            # (re)initialize + physically place optimizer state per its
            # (ZeRO) spec — jit donation requires argument shardings to
            # match declarations. Separate from the compile so a tuner can
            # reset state on an already-compiled winner (trial steps
            # mutate it) without paying the XLA compile twice.
            self._opt_state = _step_core.init_opt_state(
                self.optimizer, self._params)
            _, st_sh, _, _ = self._shardings()
            self._opt_state = [
                {k: jax.device_put(v, sh[k]) for k, v in st.items()}
                for st, sh in zip(self._opt_state, st_sh)
            ]
        if self._step is None:
            self._check_programs(batch)
            self._step = self._build(len(batch))
        _, _, _, batch_sh = self._shardings()
        batch_vals = [
            jax.device_put(
                b._value if isinstance(b, Tensor) else jnp.asarray(b), batch_sh
            )
            for b in batch
        ]
        p_vals = tuple(p._value for p in self._params)
        b_vals = tuple(b._value for b in self._buffers)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = _random.next_key()
        loss, in_grads, new_p, new_s, new_b = self._step(
            p_vals, tuple(self._opt_state), b_vals, key, lr, *batch_vals
        )
        self._opt_state = _step_core.write_back(
            self.optimizer, self._params, self._buffers, new_p, new_s, new_b)
        return _step_core.step_result(loss, in_grads)


def sharded_train_step(model, loss_fn, optimizer, mesh=None, zero_stage=0,
                       batch_axes=("dp", "sharding"), forward_ctx=None,
                       accumulate_steps=1, loss_scale=1.0, grad_input_idx=()):
    return ShardedTrainStep(model, loss_fn, optimizer, mesh, zero_stage,
                            batch_axes, forward_ctx, accumulate_steps,
                            loss_scale, grad_input_idx)
