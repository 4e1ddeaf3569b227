"""True int8 execution path.

Reference analogue: the slim stack's quantized inference ops
(quantize/dequantize + int8 conv/mul kernels dispatched by the analysis
passes). TPU-native: `lax.dot_general` on int8 operands with an int32
accumulator — exactly the MXU's 8-bit mode (the chip's int8 throughput is
~2x its bf16 FLOPs) — then a float
dequant fused in by XLA.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..nn.layer_base import Layer

__all__ = ["quantize_weight_int8", "int8_matmul", "Int8Linear"]


def quantize_weight_int8(w: np.ndarray, axis: int = -1):
    """Per-channel symmetric int8 weights (reference
    channel_wise_abs_max): returns (int8 array, float32 per-channel
    scales broadcastable along `axis`)."""
    w = np.asarray(w, np.float32)
    red = tuple(i for i in range(w.ndim) if i != (axis % w.ndim))
    scale = np.maximum(np.max(np.abs(w), axis=red, keepdims=True), 1e-8)
    q = np.clip(np.round(w / scale * 127.0), -127, 127).astype(np.int8)
    return q, scale.astype(np.float32)


def _int8_dot(xq, wq):
    """int8 x int8 -> int32 dot (the MXU 8-bit path)."""
    return jax.lax.dot_general(
        xq, wq, (((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def int8_matmul(x, w_int8, w_scale, act_scale):
    """Quantize x to int8 with `act_scale`, run the int8 dot, dequantize.

    out = (xq @ wq) * (act_scale/127) * (w_scale/127) — all the float work
    is elementwise on the int32 accumulator, which XLA fuses.
    """
    def fn(xv, wq, wscale, ascale):
        xq = jnp.clip(jnp.round(xv / ascale * 127.0), -127, 127).astype(
            jnp.int8
        )
        acc = _int8_dot(xq, wq)
        return acc.astype(jnp.float32) * (ascale / 127.0) * (
            wscale.reshape(1, -1) / 127.0
        )

    return apply(fn, x, w_int8, w_scale, act_scale, op_name="int8_matmul",
                 differentiable=False)


class Int8Linear(Layer):
    """Inference-only Linear with int8-stored weights and the int8 MXU dot
    (what ConvertToInt8Pass lowers a calibrated QuantedLinear to). Weight
    memory is 4x smaller than f32; the matmul runs on the 8-bit path."""

    def __init__(self, w_int8: np.ndarray, w_scale: np.ndarray,
                 bias, act_scale: float):
        super().__init__()
        self.register_buffer("weight_int8",
                             Tensor(jnp.asarray(w_int8, jnp.int8)))
        self.register_buffer("weight_scale",
                             Tensor(jnp.asarray(w_scale, jnp.float32)))
        self.register_buffer(
            "act_scale", Tensor(jnp.asarray(float(act_scale), jnp.float32))
        )
        self.bias = bias

    @classmethod
    def from_quanted(cls, qlinear) -> "Int8Linear":
        w = np.asarray(qlinear._linear.weight._value)
        wq, ws = quantize_weight_int8(w, axis=-1)
        act_scale = float(np.asarray(qlinear.fq_act.scale._value))
        if act_scale <= 0:
            raise ValueError(
                "QuantedLinear has no calibrated activation scale — run "
                "calibration (PTQ) or training (QAT) first"
            )
        return cls(wq, ws.reshape(-1), qlinear._linear.bias, act_scale)

    def forward(self, x):
        shape = list(x.shape)
        x2 = x.reshape([-1, shape[-1]]) if x.ndim > 2 else x
        out = int8_matmul(x2, self.weight_int8, self.weight_scale,
                          self.act_scale)
        if self.bias is not None:
            out = out + self.bias
        if len(shape) > 2:
            out = out.reshape(shape[:-1] + [out.shape[-1]])
        return out
