"""Activation recomputation (gradient checkpointing).

Reference analogue: fleet/utils/recompute.py:199 (PyLayer-based: stash RNG
state, rerun forward in backward) and the static-graph variant
_append_backward_ops_with_checkpoints_ (fluid/backward.py:760).

TPU-native: `jax.checkpoint` IS this feature — inside any traced program it
drops residuals and rematerializes in the backward pass, with XLA deciding
the schedule. Under the eager tape we wrap the segment as one tape op whose
vjp closure holds only the inputs (jax.checkpoint semantics), so eager
training gets the same memory/recompute trade. RNG state is preserved by
construction: the segment key is an explicit input, so the rematerialized
forward replays identical dropout masks (the reference stashes CUDA RNG
state by hand for this).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax

from ..core.dispatch import apply, no_grad
from ..core.tensor import Tensor
from ..core import random as _random

__all__ = ["recompute", "recompute_sequential", "KEEP_NAME"]

# A value an op tags jax.ad_checkpoint.checkpoint_name(x, KEEP_NAME) is kept
# for the backward of a recomputed segment; everything else is made again.
# (ops/linear_attention.py tags the chunk inverse: 64 KiB a chunk in bf16 and
# a tenth of a linear-attention layer's forward to make.)
KEEP_NAME = "recompute_keep"


def recompute(function: Callable, *args, **kwargs):
    """paddle.distributed.fleet.utils.recompute — checkpoint one segment."""
    kwargs.pop("preserve_rng_state", True)
    kwargs.pop("use_reentrant", True)

    if not any(isinstance(a, Tensor) for a in args):
        return function(*args, **kwargs)

    # parameters the segment reads: the checkpointed pure fn must take them
    # as inputs so the tape differentiates w.r.t. them (the reference leans
    # on the global tape inside its PyLayer; our tape sees one fused node)
    seg_params = []
    fn_self = getattr(function, "__self__", None)
    if fn_self is not None and hasattr(fn_self, "parameters"):
        seg_params = [p for p in fn_self.parameters() if not p.stop_gradient]

    from ..jit import _bind_values

    tensor_args = [a for a in args if isinstance(a, Tensor)]
    n_params = len(seg_params)

    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(KEEP_NAME))
    def ckpt(key, p_vals, arg_vals):
        rebuilt = []
        it = iter(arg_vals)
        for a in args:
            rebuilt.append(
                Tensor(next(it), stop_gradient=True) if isinstance(a, Tensor) else a
            )
        with _bind_values(seg_params, list(p_vals)), no_grad(), _random.rng_scope(key):
            out = function(*rebuilt, **kwargs)
        if isinstance(out, Tensor):
            return out._value
        if isinstance(out, (tuple, list)):
            return tuple(o._value if isinstance(o, Tensor) else o for o in out)
        return out

    def segment(key, *flat):
        return ckpt(key, tuple(flat[:n_params]), tuple(flat[n_params:]))

    segment.__name__ = f"recompute:{getattr(function, '__name__', 'segment')}"
    key = _random.next_key()
    return apply(segment, key, *seg_params, *tensor_args, op_name=segment.__name__)


def recompute_sequential(ctx, functions, *args, **kwargs):
    """reference: paddle.incubate.distributed.fleet.recompute_sequential —
    checkpoint a Sequential in chunks."""
    segments = ctx.get("segments", 1) if isinstance(ctx, dict) else int(ctx or 1)
    layers = list(functions)
    per = (len(layers) + segments - 1) // segments
    out = args[0]

    class _Seg:
        def __init__(self, chunk):
            self.chunk = chunk

        def __call__(self, x):
            for l in self.chunk:
                x = l(x)
            return x

        @property
        def __self__(self):
            return self.chunk[0] if self.chunk else None

    for i in range(0, len(layers), per):
        chunk = layers[i : i + per]

        def seg_run(x, _chunk=chunk):
            for l in _chunk:
                x = l(x)
            return x

        # gather params of the whole chunk for differentiation
        seg_run.__self__ = _ChunkParams(chunk)
        out = recompute(seg_run, out, **kwargs)
    return out


class _ChunkParams:
    def __init__(self, layers):
        self._layers = layers

    def parameters(self):
        out = []
        for l in self._layers:
            if hasattr(l, "parameters"):
                out.extend(l.parameters())
        return out
