"""Fused multi-tensor optimizer update as a Pallas TPU kernel.

The optimizer update is a long elementwise tail after the matmuls (its share
of the step on the chip is not measured yet): for Adam, XLA
lowers each parameter's update to a chain of ~10 elementwise HLOs whose
fusion still walks the parameter, gradient, and both moment buffers several
times. This kernel (FLAGS_pallas_fused_update) runs each parameter's WHOLE
update chain as one VMEM-resident pass — one read and one write per buffer —
tiled (block_rows, 128) over the flattened buffer:

    SGD       p' = p - lr * (g + wd*p)
    Momentum  v' = mu*v + (g + wd*p);  p' = p - lr * (v' [+ mu*v' nesterov])
    Adam      m' = b1*m + (1-b1)*g;  v' = b2*v + (1-b2)*g^2
              p' = p - lr_t * m' / (sqrt(v') + eps)

The PR 5 numeric-rescue sentinel stays fused: the caller passes the step's
non-finite verdict as a scalar and the kernel where-gates its own writes, so
a rescued step leaves every buffer untouched at zero extra kernel passes,
and programs-per-step stays 1 under whole-step capture (the pallas_call is
just another op inside the one donated XLA program).

Scope is deliberately the three rules the flag documents (SGD / Momentum /
Adam — AdamW's decoupled decay and the norm-computing rules keep the lax
composition) and parameters whose flattened size is a multiple of 1024
(8 sublanes x 128 lanes, the f32 tile): everything else falls back to the
lax composition per parameter, bit-for-bit the unflagged path. Scalar state
(Adam's beta-pow accumulators) and the bias-corrected step size are scalar
math, computed in the surrounding trace and prefetched into SMEM.

Off-TPU the kernel runs only under FLAGS_pallas_update_interpret (the
Pallas interpreter; slow, parity tests only) — otherwise `supported()` is
False and callers use the lax rule unchanged.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import flags

__all__ = ["enabled", "rule_kind", "supported", "param_update"]

_LANES = 128
_MIN_ROWS = 8  # f32 sublane tile
_0 = np.int32(0)  # index-map literal; Python ints trace to i64 under x64


def enabled() -> bool:
    if not flags.flag("pallas_fused_update"):
        return False
    if flags.flag("pallas_update_interpret"):
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return bool(flags.flag("pallas_update_interpret")) or (
        jax.default_backend() != "tpu"
    )


def rule_kind(opt_type) -> Optional[str]:
    """'sgd' | 'momentum' | 'adam' when opt_type's _update is one of the
    three stock rules this kernel implements; None otherwise (subclasses
    overriding _update get the lax path — same convention as the capture
    controller's clip check)."""
    from ...optimizer.optimizer import SGD, Adam, Momentum

    upd = opt_type._update
    if upd is SGD._update:
        return "sgd"
    if upd is Momentum._update:
        return "momentum"
    if upd is Adam._update:
        return "adam"
    return None


def supported(kind: Optional[str], p, g, state: Dict) -> bool:
    """One parameter's eligibility: f32 buffers whose flattened size tiles
    to (8, 128), grad already cast to the param dtype, and the state layout
    of the stock rule."""
    if kind is None:
        return False
    if p.dtype != jnp.float32 or g.dtype != p.dtype:
        return False
    n = 1
    for d in p.shape:
        n *= int(d)
    if n == 0 or n % (_MIN_ROWS * _LANES) != 0:
        return False
    for v in state.values():
        if v.shape == p.shape and v.dtype != p.dtype:
            return False
    return True


def _block_rows(rows: int) -> int:
    for b in (512, 256, 128, 64, 32, 16, 8):
        if rows % b == 0:
            return b
    return _MIN_ROWS


# ---------------------------------------------------------------------------
# kernels — scalar operands (lr / lr_t and the sentinel verdict) ride in
# SMEM as (1, 1) refs; hypers are static python floats baked into the trace
# ---------------------------------------------------------------------------
def _sgd_kernel(lr_ref, bad_ref, p_ref, g_ref, out_p_ref, *, wd, gate):
    p = p_ref[:]
    g = g_ref[:]
    if wd:
        g = g + wd * p
    new_p = p - lr_ref[0, 0] * g
    if gate:
        new_p = jnp.where(bad_ref[0, 0] != 0, p, new_p)
    out_p_ref[:] = new_p


def _momentum_kernel(lr_ref, bad_ref, p_ref, g_ref, v_ref, out_p_ref,
                     out_v_ref, *, mu, nesterov, wd, gate):
    p = p_ref[:]
    g = g_ref[:]
    v = v_ref[:]
    if wd:
        g = g + wd * p
    new_v = mu * v + g
    step = g + mu * new_v if nesterov else new_v
    new_p = p - lr_ref[0, 0] * step
    if gate:
        bad = bad_ref[0, 0] != 0
        new_p = jnp.where(bad, p, new_p)
        new_v = jnp.where(bad, v, new_v)
    out_p_ref[:] = new_p
    out_v_ref[:] = new_v


def _adam_kernel(lr_ref, bad_ref, p_ref, g_ref, m_ref, v_ref, out_p_ref,
                 out_m_ref, out_v_ref, *, b1, b2, eps, wd, gate):
    p = p_ref[:]
    g = g_ref[:]
    m = m_ref[:]
    v = v_ref[:]
    if wd:
        g = g + wd * p
    new_m = b1 * m + (1 - b1) * g
    new_v = b2 * v + (1 - b2) * jnp.square(g)
    # lr_ref holds the bias-corrected step size lr_t (scalar math stays in
    # the surrounding trace, like the beta-pow state updates)
    new_p = p - lr_ref[0, 0] * new_m / (jnp.sqrt(new_v) + eps)
    if gate:
        bad = bad_ref[0, 0] != 0
        new_p = jnp.where(bad, p, new_p)
        new_m = jnp.where(bad, m, new_m)
        new_v = jnp.where(bad, v, new_v)
    out_p_ref[:] = new_p
    out_m_ref[:] = new_m
    out_v_ref[:] = new_v


def _call(kernel, scalars, bufs, n_out, interpret):
    """Tile the flattened buffers to (block_rows, 128) and invoke `kernel`:
    scalar operands in SMEM, every buffer one VMEM read or write."""
    shape = bufs[0].shape
    rows = bufs[0].size // _LANES
    br = _block_rows(rows)
    grid = (rows // br,)
    tiled = [b.reshape(rows, _LANES) for b in bufs]
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (_0, _0),
                               memory_space=pltpu.SMEM)
    buf_spec = pl.BlockSpec((br, _LANES), lambda i: (i, _0),
                            memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        name="fused_update",
        grid=grid,
        in_specs=[scalar_spec] * len(scalars) + [buf_spec] * len(tiled),
        out_specs=[buf_spec] * n_out,
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), tiled[0].dtype)
        ] * n_out,
        interpret=interpret,
    )(*scalars, *tiled)
    return [o.reshape(shape) for o in out]


def param_update(kind: str, p, g, lr, state: Dict, hyper: Dict, *, wd, bad):
    """One parameter's fused update pass. Mirrors the stock `_update` rules
    exactly (same formulas, same operand order); `bad` is the step's fused
    non-finite sentinel (or None) — gating happens in-kernel, so the caller
    must NOT re-gate these outputs. Returns (new_p, new_state)."""
    interpret = _interpret()
    gate = bad is not None
    sbad = (
        jnp.asarray(bad, jnp.int32).reshape(1, 1)
        if gate else jnp.zeros((1, 1), jnp.int32)
    )
    if kind == "sgd":
        lr_s = lr.astype(p.dtype).reshape(1, 1)
        (new_p,) = _call(
            functools.partial(_sgd_kernel, wd=wd, gate=gate),
            [lr_s, sbad], [p, g], 1, interpret,
        )
        return new_p, state
    if kind == "momentum":
        lr_s = lr.astype(p.dtype).reshape(1, 1)
        new_p, new_v = _call(
            functools.partial(
                _momentum_kernel, mu=hyper["mu"],
                nesterov=bool(hyper["nesterov"]), wd=wd, gate=gate,
            ),
            [lr_s, sbad], [p, g, state["velocity"]], 2, interpret,
        )
        return new_p, {"velocity": new_v}
    if kind == "adam":
        b1, b2, eps = hyper["b1"], hyper["b2"], hyper["eps"]
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        lr_t = (lr * jnp.sqrt(1 - b2p) / (1 - b1p)).astype(p.dtype)
        new_p, new_m, new_v = _call(
            functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
                              gate=gate),
            [lr_t.reshape(1, 1), sbad],
            [p, g, state["moment1"], state["moment2"]], 3, interpret,
        )
        if gate:
            # the scalar beta-pow accumulators gate with the buffers: a
            # rescued step must not advance the bias correction either
            badb = jnp.asarray(bad, jnp.bool_)
            b1p = jnp.where(badb, state["beta1_pow"], b1p)
            b2p = jnp.where(badb, state["beta2_pow"], b2p)
        return new_p, {
            "moment1": new_m, "moment2": new_v,
            "beta1_pow": b1p, "beta2_pow": b2p,
        }
    raise ValueError(f"unsupported fused-update kind {kind!r}")
