"""The train-step program, written once.

Bind values, forward, loss, ``value_and_grad`` (or the gradient-merge scan),
unscale, clip, update, mask: every compiled builder traces this closure and
adds only what is its own. ``jit.CompiledTrainStep`` adds the remat plan, the
donation gate and the spans; ``parallel.ShardedTrainStep`` the shardings and
the decision whether to pin gradients; ``parallel.PipelinedTrainStep`` has a
staged loss of its own and takes the clip and the update applier from here.
The scopes the profiler's device view splits a step by (``forward``,
``loss``, ``grad_clip``, ``optimizer``) are made here and nowhere else.

Imports nothing of ``parallel``, ``analysis`` or Pallas: ``import paddle_tpu``
loads this file.
"""
from __future__ import annotations

import contextlib
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from ..core import random as _random
from ..core.dispatch import no_grad
from ..core.tensor import Tensor
from ..optimizer.optimizer import make_fused_update


@contextlib.contextmanager
def _bind_values(tensors: Sequence[Tensor], values: Sequence[Any]):
    saved = [t._value for t in tensors]
    for t, v in zip(tensors, values):
        t._value = v
    try:
        yield
    finally:
        for t, s in zip(tensors, saved):
            t._value = s


def check_merge(grad_input_idx, accumulate_steps):
    """Normalised ``(grad_input_idx, accumulate_steps)``; refuses what the
    gradient-merge scan cannot carry."""
    gidx = tuple(int(i) for i in grad_input_idx)
    accum_k = int(accumulate_steps)
    if accum_k < 1:
        raise ValueError("accumulate_steps must be >= 1")
    if gidx and accum_k > 1:
        raise ValueError(
            "grad_input_idx is not supported with compiled gradient "
            "merge (the per-microbatch input grads would need their "
            "own accumulation contract)"
        )
    return gidx, accum_k


def make_loss_core(model, loss_fn, params, buffers, *, grad_input_idx=(),
                   forward_ctx=None):
    """The pure loss path `(p_vals, diff_vals, b_vals, key, batch_vals)
    -> (loss, new_buffers)` — every array input explicit (no tracer
    closure), so the remat planner can trace it standalone, slice it
    into jax.checkpoint stages, and substitute the planned callable
    into the step with identical semantics. `diff_vals` replace the batch
    positions `grad_input_idx`; `forward_ctx` is a zero-arg context-manager
    factory round the traced forward (fleet wires strategy.amp through it)."""
    gidx = tuple(grad_input_idx)
    fwd_ctx = forward_ctx or contextlib.nullcontext

    def loss_core(p_vals, diff_vals, b_vals, key, batch_vals):
        full = list(batch_vals)
        for i, v in zip(gidx, diff_vals):
            full[i] = v
        ins = [Tensor(v, stop_gradient=True) for v in full]
        with _bind_values(params + buffers, list(p_vals) + list(b_vals)), \
                no_grad(), _random.rng_scope(key), fwd_ctx():
            # the sections the profiler's device view splits by; the
            # backward of each reads transpose(jvp(forward))/... by itself
            with jax.named_scope("forward"):
                out = model(*ins[:-1]) if len(ins) > 1 else model(ins[0])
            with jax.named_scope("loss"):
                loss = (loss_fn(out, ins[-1]) if loss_fn is not None
                        else out)
            # buffer values after forward (BN running stats updates)
            new_b = tuple(b._value for b in buffers)
        lv = loss._value if isinstance(loss, Tensor) else loss
        return lv, new_b

    return loss_core


def clip_grads(grad_clip, p_vals, grads):
    """`grads` through the optimizer's clip object (None = unclipped). The
    clip objects are pure jnp math on Tensor wrappers — tracer-safe, so the
    eager clip semantics apply unchanged."""
    if grad_clip is None:
        return grads
    with jax.named_scope("grad_clip"):
        pairs = grad_clip(
            [
                (Tensor(pv, stop_gradient=True), Tensor(gv, stop_gradient=True))
                for pv, gv in zip(p_vals, grads)
            ]
        )
    return [g._value for _, g in pairs]


def make_step_fn(loss_core, optimizer, params, *, grad_input_idx=(),
                 accumulate_steps=1, loss_scale=1.0, pin_grads=None):
    """`step_fn(p_vals, opt_states, b_vals, key, lr, *batch_vals) -> (loss,
    in_grads, new_p, new_s, new_b)` over `loss_core` (a planned loss is
    simply another `loss_core`).

    `grad_input_idx`: batch positions to ALSO differentiate; their grads
    come back as `in_grads` instead of reaching the optimizer (unscaled, not
    clipped). `accumulate_steps > 1`: compiled gradient merge. `loss_scale`:
    static loss scaling for pure-fp16 compute (1.0 = off); loss and grads
    are unscaled before the clip. `pin_grads(grads)`: the caller's sharding
    pin on the fresh gradients (the hybrid dp x sharding ZeRO pin)."""
    gidx, accum_k = check_merge(grad_input_idx, accumulate_steps)
    loss_scale = float(loss_scale)
    grad_clip = optimizer._grad_clip
    # ASP masks (incubate/asp.py): pruned params must stay n:m sparse
    # through the compiled update too — fold the mask into the new
    # param value (mask is a traced constant; prune BEFORE building)
    from ..incubate import asp as _asp

    asp_masks = [_asp._mask_for(p) for p in params]
    apply_update = make_fused_update(optimizer, params)

    def value_and_grads(p_vals, b_vals, key, batch_vals):
        def loss_of(p_vals, diff_vals):
            lv, new_b = loss_core(p_vals, diff_vals, b_vals, key,
                                  tuple(batch_vals))
            if loss_scale != 1.0:
                lv = lv * loss_scale
            return lv, new_b

        return jax.value_and_grad(loss_of, argnums=(0, 1), has_aux=True)(
            tuple(p_vals), tuple(batch_vals[i] for i in gidx))

    def step_fn(p_vals, opt_states, b_vals, key, lr, *batch_vals):
        if accum_k > 1:
            # compiled gradient merge (reference: GradientMergeOptimizer
            # program rewrite): split the global batch into k chunks and
            # lax.scan value_and_grad over them, accumulating fp32 grads
            # — peak activation memory is one microbatch's, the update
            # applies ONCE on the averaged gradient
            chunks = tuple(
                v.reshape((accum_k, v.shape[0] // accum_k) + v.shape[1:])
                for v in batch_vals
            )
            keys = jax.random.split(key, accum_k)

            def scan_body(carry, xs):
                g_acc, b_cur = carry
                (lv, new_b), (gs, _) = value_and_grads(
                    p_vals, b_cur, xs[0], xs[1:])
                g_acc = tuple(
                    a + g.astype(jnp.float32) for a, g in zip(g_acc, gs)
                )
                return (g_acc, new_b), lv

            g0 = tuple(jnp.zeros(p.shape, jnp.float32) for p in p_vals)
            (g_acc, new_b), losses = jax.lax.scan(
                scan_body, (g0, tuple(b_vals)), (keys,) + chunks
            )
            grads = tuple(
                (g / accum_k).astype(p.dtype) for g, p in zip(g_acc, p_vals)
            )
            loss = jnp.mean(losses)
            in_grads = ()  # refused with gradient merge (check_merge)
        else:
            (loss, new_b), (grads, in_grads) = value_and_grads(
                p_vals, b_vals, key, batch_vals)
        if pin_grads is not None:
            grads = pin_grads(grads)
        if loss_scale != 1.0:
            def unscale(g):
                return (g.astype(jnp.float32) / loss_scale).astype(g.dtype)

            loss = loss / loss_scale
            grads = tuple(unscale(g) for g in grads)
            # input grads ship to the caller (PS push): they must be
            # unscaled exactly like the param grads
            in_grads = tuple(unscale(g) for g in in_grads)
        grads = clip_grads(grad_clip, p_vals, grads)
        with jax.named_scope("optimizer"):
            new_p, new_s = apply_update(p_vals, grads, lr, opt_states)
            new_p = [v if m is None else v * m.astype(v.dtype)
                     for v, m in zip(new_p, asp_masks)]
        return loss, in_grads, tuple(new_p), tuple(new_s), new_b

    return step_fn


def init_opt_state(optimizer, params):
    """The optimizer's state dict of each parameter, created where the
    optimizer holds none yet."""
    states = []
    for p in params:
        st = optimizer._accumulators.get(id(p))
        if st is None:
            st = optimizer._create_state(p)
            optimizer._accumulators[id(p)] = st
        states.append(st)
    return states


def write_back(optimizer, params, buffers, new_p, new_s, new_b):
    """Rebind a step's results: parameters, buffers, the optimizer's
    accumulators and step count. Returns the new state list."""
    for p, v in zip(params, new_p):
        p._value = v
    for b, v in zip(buffers, new_b):
        b._value = v
    states = list(new_s)
    for p, st in zip(params, states):
        optimizer._accumulators[id(p)] = st
    optimizer._step_count += 1
    return states


def step_result(loss, in_grads):
    """What a step hands its caller: the loss, and the input gradients where
    `grad_input_idx` asked for any."""
    loss_t = Tensor(loss, stop_gradient=True)
    if in_grads:
        return loss_t, [Tensor(g, stop_gradient=True) for g in in_grads]
    return loss_t
