"""Pipeline parallelism: the GPipe/1F1B schedule over the `pp` mesh axis.

Reference analogue:
  - fleet/meta_parallel/pipeline_parallel.py:80 `forward_backward_pipeline`
    (1F1B over batched NCCL p2p: warmup recv/forward/send, steady 1F1B,
    cooldown) and pp_layers.py:132 `PipelineLayer` segmentation;
  - fleet_executor/carrier.h:49 actor runtime for cross-host pipelines.

TPU-native design (NOT a port): there is no NCCL p2p on TPU — stage-to-stage
transfer is an XLA CollectivePermute riding ICI, and the whole schedule lives
*inside one compiled SPMD program*:

  - stage weights are STACKED: every per-block parameter of the homogeneous
    middle run is stacked to a leading [num_layers, ...] dim and sharded
    P("pp", ...) so each pp rank physically holds only its stage's slice
    (the memory property that makes PP worth it);
  - the program is `shard_map`-manual over `pp` only; dp/sharding/mp/sep stay
    in GSPMD "auto" mode, so TP layers/ZeRO specs compose unchanged inside a
    stage;
  - a `lax.scan` over ticks implements the schedule: at tick t, stage s
    processes microbatch t-s; outputs rotate one stage forward via
    `ppermute` [(i, i+1)] (parity with p2p_communication.py's
    send_forward/recv_forward, but compiler-scheduled);
  - backward is jax.grad through the scan: XLA reverses the schedule into
    the backward pipeline automatically, with per-tick rematerialization
    (jax.checkpoint) bounding activation memory the way the reference pairs
    PP with recompute.

The embedding + head (pre/post stages) are small and run replicated on every
pp rank; only the selected rank's contribution carries gradient (where-mask +
psum), so the math matches the reference's first/last-stage placement while
keeping the program SPMD.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .._jax_compat import shard_map

from ..core import random as _random
from ..core.dispatch import no_grad
from ..core.tensor import Tensor
from ..jit.step import _bind_values, clip_grads
from ..optimizer.optimizer import make_fused_update
from .topology import axis_size as _mesh_axis_size, get_mesh

__all__ = ["gpipe_loss", "PipelinedTrainStep", "pipelined_train_step"]


def _axis_size(mesh: Mesh, name: str) -> int:
    return _mesh_axis_size(name, mesh)


def gpipe_loss(
    stage_fn: Callable,
    inject_fn: Callable,
    head_loss_fn: Callable,
    stacked_local,
    x_mb,
    y_mb,
    *,
    num_stages: int,
    num_micro: int,
    axis: str = "pp",
    remat: bool = True,
):
    """GPipe forward inside a shard_map-manual-over-`axis` region → mean loss.

    stage_fn(stacked_local, h) -> h          one stage's block stack
    inject_fn(x_microbatch) -> h0            embedding (stage-0 injection)
    head_loss_fn(h, y_microbatch) -> scalar  final-ln + head + criterion
    x_mb/y_mb: [num_micro, mb, ...] microbatched inputs, replicated over pp.

    Returns the scalar loss, identical on every pp rank (psum of the
    last-stage contribution). Differentiable; grads of replicated params are
    psum'd by the shard_map transpose.
    """
    S, M = num_stages, num_micro
    s_idx = jax.lax.axis_index(axis)
    apply_stage = jax.checkpoint(stage_fn) if remat else stage_fn

    # activation shape probe (no FLOPs at runtime: dead-code eliminated
    # unless needed): stage I/O shape == embedding output shape
    h0_shape = jax.eval_shape(inject_fn, jax.eval_shape(lambda: x_mb[0]))
    zeros_h = jnp.zeros(h0_shape.shape, h0_shape.dtype)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 injects microbatch t (clamped in cooldown; results unused)
        xt = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
        )
        h_in = jnp.where(s_idx == 0, inject_fn(xt), state)
        y = apply_stage(stacked_local, h_in)
        # last stage's tick t output is microbatch t-(S-1); warmup garbage
        # lands on slot 0 and is overwritten at t = S-1
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        outputs = jax.lax.dynamic_update_index_in_dim(outputs, y, out_idx, axis=0)
        # rotate activations one stage forward (reference: p2p send_forward /
        # recv_forward pairs); edge ranks receive zeros
        state = jax.lax.ppermute(y, axis, [(i, i + 1) for i in range(S - 1)])
        return (state, outputs), None

    outputs0 = jnp.zeros((M,) + h0_shape.shape, h0_shape.dtype)
    (_, outputs), _ = jax.lax.scan(
        tick, (zeros_h, outputs0), jnp.arange(M + S - 1)
    )

    # head + loss per microbatch, scanned to keep one microbatch of logits
    # live at a time; only the last pp rank's value is real
    def head_tick(acc, my):
        h, y = my
        return acc + head_loss_fn(h, y).astype(acc.dtype), None

    loss_sum, _ = jax.lax.scan(head_tick, jnp.zeros((), jnp.float32), (outputs, y_mb))
    loss_local = loss_sum / M
    return jax.lax.psum(jnp.where(s_idx == S - 1, loss_local, 0.0), axis)


def _collect_blocks(model):
    """Resolve the pipeline partition protocol on `model`:
    (pre_fn, blocks, post_fn). Models expose pp_embed/pp_blocks/pp_head
    (GPTForPretraining); PipelineLayer gets the homogeneous-middle adapter."""
    if hasattr(model, "pp_blocks"):
        blocks = list(model.pp_blocks)
        return model.pp_embed, blocks, model.pp_head
    raise TypeError(
        f"{type(model).__name__} is not pipeline-partitionable: expose "
        "pp_embed(x)/pp_blocks/pp_head(h) or use fleet.PipelineLayer"
    )


def _named_params(layer) -> List[Tensor]:
    return [p for _, p in sorted(layer.named_parameters(), key=lambda kv: kv[0])]


class PipelinedTrainStep:
    """Compiled pipeline-parallel train step (composes with dp/mp/sharding).

    One XLA program: stacked block params (pp-sharded dim 0), replicated
    embed/head params (mp/ZeRO specs honored in GSPMD auto mode), GPipe scan,
    loss, grads, optimizer update — with buffer donation.
    Reference counterpart: PipelineParallel.train_batch →
    forward_backward_pipeline (pipeline_parallel.py:80) + optimizer step.
    """

    def __init__(self, model, loss_fn, optimizer, mesh: Optional[Mesh] = None,
                 num_micro: int = 4, zero_stage: int = 0, remat: bool = True,
                 forward_ctx=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # zero-arg context-manager factory around every traced forward
        # region (fleet wires strategy.amp through here)
        import contextlib

        self.forward_ctx = forward_ctx or contextlib.nullcontext
        self.mesh = mesh or get_mesh()
        if self.mesh is None:
            raise RuntimeError("pipeline parallelism requires an initialized mesh")
        self.S = _axis_size(self.mesh, "pp")
        self.M = num_micro
        self.zero_stage = zero_stage
        self.remat = remat

        pre_fn, blocks, post_fn = _collect_blocks(model)
        if len(blocks) % max(self.S, 1) != 0:
            raise ValueError(
                f"num blocks {len(blocks)} not divisible by pp={self.S}"
            )
        self.pre_fn, self.blocks, self.post_fn = pre_fn, blocks, post_fn
        self.template = blocks[0]
        self.block_param_objs = [_named_params(b) for b in blocks]
        t_shapes = [tuple(p.shape) for p in self.block_param_objs[0]]
        for ps in self.block_param_objs[1:]:
            if [tuple(p.shape) for p in ps] != t_shapes:
                raise ValueError("pipeline middle blocks are not homogeneous")
        # params outside the blocks (embedding, final ln, head) stay unstacked
        block_ids = {id(p) for ps in self.block_param_objs for p in ps}
        self._repl_params = [
            p for p in model.parameters()
            if id(p) not in block_ids and not p.stop_gradient
        ]
        self._buffers = [b for _, b in model.named_buffers()]
        if self._buffers:
            # buffer mutation (BatchNorm running stats) inside the scanned
            # schedule cannot escape the scan trace; ShardedTrainStep threads
            # buffers out, this step cannot yet
            names = [n for n, _ in model.named_buffers()]
            raise ValueError(
                "pipelined training does not support layers with buffers "
                f"(running statistics) yet: {names[:5]} — use LayerNorm/"
                "GroupNorm in the pipelined middle or pp_degree=1"
            )
        self._step = None
        self._loss_program = None  # forward GPipe loss (for the analyzer)
        self._stacked = None      # list of [L, ...] arrays, one per block param
        self._stacked_state = None
        self._repl_state = None

    # ---- sharding specs ---------------------------------------------------
    def _stacked_spec(self, p: Tensor) -> P:
        """P('pp', <dist_spec of the block param>); ZeRO additionally shards
        a free dim over 'sharding' (stage-local ZeRO, like the reference's
        pp+sharding hybrid)."""
        base = list(getattr(p, "dist_spec", None) or [None] * p.ndim)
        base += [None] * (p.ndim - len(base))
        if self.zero_stage >= 3:
            n_shard = _axis_size(self.mesh, "sharding")
            if n_shard > 1:
                for d in range(p.ndim):
                    if base[d] is None and p.shape[d] % n_shard == 0:
                        base[d] = "sharding"
                        break
        return P("pp", *base)

    def _repl_spec(self, p: Tensor) -> P:
        from .sharding import param_spec

        return param_spec(p, self.zero_stage, self.mesh)

    def _state_specs(self, spec: P, shape) -> P:
        # optimizer state mirrors its param's spec (incl. the pp dim)
        entries = list(spec) + [None] * (len(shape) - len(list(spec)))
        return P(*entries) if len(shape) > 0 else P()

    # ---- state ------------------------------------------------------------
    def _init_stacked(self):
        vals = []
        for j in range(len(self.block_param_objs[0])):
            vals.append(
                jnp.stack([ps[j]._value for ps in self.block_param_objs])
            )
        return vals

    def _make_state(self, val) -> dict:
        t = Tensor(val, stop_gradient=True)
        return self.optimizer._create_state(t)

    def _init_stacked_state(self):
        """Stacked optimizer moments; honors state restored by
        set_state_dict (checkpoint resume) when every block has it."""
        acc = self.optimizer._accumulators
        out = []
        for j, stacked in enumerate(self._stacked):
            per_layer = [acc.get(id(ps[j])) for ps in self.block_param_objs]
            if all(st is not None for st in per_layer):
                # scalar states (beta-pow step counters) are shared across
                # layers, tensor states stack along the layer dim
                out.append(
                    {
                        k: (
                            per_layer[0][k]
                            if jnp.ndim(per_layer[0][k]) == 0
                            else jnp.stack([st[k] for st in per_layer])
                        )
                        for k in per_layer[0].keys()
                    }
                )
            else:
                out.append(self._make_state(stacked))
        return out

    def _init_repl_state(self):
        acc = self.optimizer._accumulators
        out = []
        for p in self._repl_params:
            st = acc.get(id(p))
            out.append(dict(st) if st is not None else self._make_state(p._value))
        return out

    # ---- lazy write-back (state_dict / checkpoint paths) -------------------
    def sync_params(self):
        """Materialize the authoritative stacked weights back into the live
        per-layer param Tensors (invoked lazily from Layer.state_dict)."""
        if self._stacked is None:
            return
        with no_grad():
            for li, ps in enumerate(self.block_param_objs):
                for j, p in enumerate(ps):
                    p._value = self._stacked[j][li]

    def sync_opt_state(self):
        """Write stacked/replicated moments back into optimizer._accumulators
        (invoked lazily from Optimizer.state_dict)."""
        if self._stacked_state is None:
            return
        acc = self.optimizer._accumulators
        for j, st in enumerate(self._stacked_state):
            for li, ps in enumerate(self.block_param_objs):
                cur = acc.setdefault(id(ps[j]), {})
                for k, v in st.items():
                    cur[k] = v if jnp.ndim(v) == 0 else v[li]
        for p, st in zip(self._repl_params, self._repl_state):
            acc[id(p)] = dict(st)

    # ---- build ------------------------------------------------------------
    def _step_parts(self):
        """(step_fn, in_shardings, out_shardings) pre-jit — the sharding
        analyzer traces step_fn at per-shard shapes without compiling;
        _build wraps the same triple in jax.jit."""
        mesh, S, M = self.mesh, self.S, self.M
        loss_fn, opt = self.loss_fn, self.optimizer
        template_params = self.block_param_objs[0]
        t_objs = _named_params(self.template)
        repl_params, buffers = self._repl_params, self._buffers
        pre_fn, post_fn = self.pre_fn, self.post_fn
        L_per = len(self.blocks) // S
        # the stacked [L, ...] arrays update under the template block's
        # per-parameter hypers: the blocks are homogeneous
        update_repl = make_fused_update(opt, repl_params)
        update_stacked = make_fused_update(opt, template_params)
        grad_clip = opt._grad_clip
        remat = self.remat

        stacked_specs = [self._stacked_spec(p) for p in template_params]
        repl_specs = [self._repl_spec(p) for p in repl_params]

        from .sharding import suppress_sharding_constraints

        def body(repl_vals, stacked_locals, b_vals, key, x_mb, y_mb):
            """Runs per-(pp, dp, sharding)-rank; mp stays GSPMD-auto so TP
            weight shardings propagate inside the stage. Making the batch
            axes MANUAL pins every activation's dp sharding — GSPMD-auto dp
            used to replicate-then-repartition activations between the scan
            carries and the in-stage program ('Involuntary full
            rematerialization' churn)."""
            with _random.rng_scope(key), suppress_sharding_constraints():
                fwd_ctx = self.forward_ctx

                def stage_fn(locals_, h):
                    for i in range(L_per):
                        slice_vals = [v[i] for v in locals_]
                        with _bind_values(t_objs, slice_vals), no_grad(), \
                                fwd_ctx():
                            h = self.template(
                                Tensor(h, stop_gradient=True)
                            )._value
                    return h

                def inject_fn(xt):
                    with _bind_values(repl_params + buffers,
                                      list(repl_vals) + list(b_vals)), \
                            no_grad(), fwd_ctx():
                        return pre_fn(Tensor(xt, stop_gradient=True))._value

                def head_loss_fn(h, y):
                    with _bind_values(repl_params + buffers,
                                      list(repl_vals) + list(b_vals)), \
                            no_grad(), fwd_ctx():
                        out = post_fn(Tensor(h, stop_gradient=True))
                        loss = (
                            loss_fn(out, Tensor(y, stop_gradient=True))
                            if loss_fn is not None else out
                        )
                    lv = loss._value if isinstance(loss, Tensor) else loss
                    if lv.ndim > 0:  # parity with the pp==1 path's loss.mean()
                        lv = lv.mean()
                    return lv.astype(jnp.float32)

                loss = gpipe_loss(
                    stage_fn, inject_fn, head_loss_fn, stacked_locals,
                    x_mb, y_mb, num_stages=S, num_micro=M, remat=remat,
                )
                # local-batch mean → global-batch mean (dp ranks hold
                # disjoint microbatch slices under the manual batch axis;
                # the 'sharding' slice of the batch stays GSPMD-auto because
                # ZeRO-3 shards stage weights over it in-stage)
                return jax.lax.pmean(loss, "dp")

        smapped = shard_map(
            body, mesh=mesh,
            in_specs=(P(), P("pp"), P(), P(),
                      P(None, "dp"), P(None, "dp")),
            out_specs=P(),
            axis_names={"pp", "dp"}, check_vma=False,
        )

        def loss_program(repl_vals, stacked_vals, b_vals, key, x, y):
            # forward GPipe loss only — the static analyzer traces this when
            # jax<0.5 cannot differentiate through shard_map (same schedule,
            # same ppermute/psum collectives, no optimizer tail)
            x_mb = x.reshape((M, x.shape[0] // M) + x.shape[1:])
            y_mb = y.reshape((M, y.shape[0] // M) + y.shape[1:])
            return smapped(tuple(repl_vals), tuple(stacked_vals),
                           tuple(b_vals), key, x_mb, y_mb)

        self._loss_program = loss_program

        def step_fn(repl_vals, stacked_vals, repl_states, stacked_states,
                    b_vals, key, lr, x, y):
            # microbatch: [B, ...] -> [M, B//M, ...]
            x_mb = x.reshape((M, x.shape[0] // M) + x.shape[1:])
            y_mb = y.reshape((M, y.shape[0] // M) + y.shape[1:])
            loss, (g_repl, g_stacked) = jax.value_and_grad(
                smapped, argnums=(0, 1)
            )(tuple(repl_vals), tuple(stacked_vals), tuple(b_vals), key, x_mb, y_mb)

            # one global clip over replicated + stacked grads (the
            # stacked arrays already hold all layers, so the global norm
            # matches the unstacked model's)
            n_r = len(repl_vals)
            clipped = clip_grads(
                grad_clip, list(repl_vals) + list(stacked_vals),
                list(g_repl) + list(g_stacked))
            g_repl, g_stacked = clipped[:n_r], clipped[n_r:]
            with jax.named_scope("optimizer"):
                new_repl, new_rs = update_repl(
                    repl_vals, g_repl, lr, repl_states)
                new_stacked, new_ss = update_stacked(
                    stacked_vals, g_stacked, lr, stacked_states)
            return loss, tuple(new_repl), tuple(new_stacked), tuple(new_rs), tuple(new_ss)

        repl_sh = tuple(NamedSharding(mesh, s) for s in repl_specs)
        stacked_sh = tuple(NamedSharding(mesh, s) for s in stacked_specs)
        rs_sh = tuple(
            {k: NamedSharding(mesh, self._state_specs(spec, v.shape))
             for k, v in st.items()}
            for spec, st in zip(repl_specs, self._repl_state)
        )
        ss_sh = tuple(
            {k: NamedSharding(mesh, self._state_specs(spec, v.shape))
             for k, v in st.items()}
            for spec, st in zip(stacked_specs, self._stacked_state)
        )
        repl = NamedSharding(mesh, P())
        batch_sh = NamedSharding(mesh, P(("dp", "sharding")))
        in_sh = (repl_sh, stacked_sh, rs_sh, ss_sh,
                 tuple(repl for _ in self._buffers), repl, repl,
                 batch_sh, batch_sh)
        out_sh = (repl, repl_sh, stacked_sh, rs_sh, ss_sh)
        return step_fn, in_sh, out_sh

    def _build(self):
        step_fn, in_sh, out_sh = self._step_parts()
        return jax.jit(
            step_fn, in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=(0, 1, 2, 3),
        )

    def _check_programs(self, batch):
        """FLAGS_check_programs gate before the first compile — the same
        per-shard analysis suite ShardedTrainStep runs (1 = warn,
        2 = raise on errors); trace failures never block training."""
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
            diags = check_sharded_step(self, specs, source="pipelined-step")
        except Exception:
            return
        enforce(diags, "pipelined_train_step")

    # ---- call -------------------------------------------------------------
    @no_grad()
    def __call__(self, x, y) -> Tensor:
        if self._step is None:
            self._stacked = self._init_stacked()
            self._stacked_state = self._init_stacked_state()
            self._repl_state = self._init_repl_state()
            self._check_programs((x, y))
            self._step = self._build()
            # lazy write-back hooks: state_dict() on the model/optimizer
            # pulls the authoritative stacked values without paying the
            # per-step gather cost
            self.model._lazy_param_sync = self.sync_params
            self.optimizer._lazy_state_sync = self.sync_opt_state
            # physically place stacked params/state so donation matches
            for j, v in enumerate(self._stacked):
                sh = NamedSharding(self.mesh, self._stacked_spec(
                    self.block_param_objs[0][j]))
                self._stacked[j] = jax.device_put(v, sh)
                self._stacked_state[j] = {
                    k: jax.device_put(sv, NamedSharding(
                        self.mesh, self._state_specs(
                            self._stacked_spec(self.block_param_objs[0][j]),
                            sv.shape)))
                    for k, sv in self._stacked_state[j].items()
                }
        batch_sh = NamedSharding(self.mesh, P(("dp", "sharding")))
        xv = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        yv = y._value if isinstance(y, Tensor) else jnp.asarray(y)
        if xv.shape[0] % self.M != 0:
            raise ValueError(
                f"batch size {xv.shape[0]} not divisible by "
                f"accumulate_steps/num_micro={self.M}"
            )
        xv = jax.device_put(xv, batch_sh)
        yv = jax.device_put(yv, batch_sh)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = _random.next_key()
        repl_vals = tuple(p._value for p in self._repl_params)
        b_vals = tuple(b._value for b in self._buffers)
        loss, new_repl, new_stacked, new_rs, new_ss = self._step(
            repl_vals, tuple(self._stacked), tuple(self._repl_state),
            tuple(self._stacked_state), b_vals, key, lr, xv, yv,
        )
        for p, v in zip(self._repl_params, new_repl):
            p._value = v
        self._stacked = list(new_stacked)
        self._repl_state = list(new_rs)
        self._stacked_state = list(new_ss)
        # live block params are synced lazily (sync_params via state_dict);
        # repl params were rebound above and accumulators for them flow
        # through sync_opt_state
        self.optimizer._step_count += 1
        return Tensor(loss, stop_gradient=True)


def pipelined_train_step(model, loss_fn, optimizer, mesh=None, num_micro=4,
                         zero_stage=0, remat=True, forward_ctx=None):
    return PipelinedTrainStep(
        model, loss_fn, optimizer, mesh, num_micro, zero_stage, remat,
        forward_ctx
    )
