"""Neural-network kernels (pure jax).

Reference analogue: phi conv/pool/norm/softmax/activation kernels
(paddle/phi/kernels/{conv_kernel.h,pool_kernel.h,batch_norm_kernel.h,...})
and the fused ops in paddle/fluid/operators/fused/. Convs and matmuls are the
MXU path; keep NCHW data arriving from the paddle-compatible API but lower via
lax.conv_general_dilated which XLA lays out for TPU.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np


def _pair(v, n=2):
    if isinstance(v, (tuple, list)):
        return tuple(v)
    return (v,) * n


def _conv_padding(padding, spatial, kernel, stride, dilation):
    """Normalize paddle padding spec to lax padding list."""
    if isinstance(padding, str):
        p = padding.upper()
        if p == "SAME":
            return "SAME"
        if p == "VALID":
            return "VALID"
        raise ValueError(padding)
    if isinstance(padding, int):
        return [(padding, padding)] * spatial
    padding = list(padding)
    if len(padding) == spatial:
        return [(p, p) for p in padding]
    if len(padding) == 2 * spatial:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(spatial)]
    raise ValueError(f"bad padding {padding}")


# ---------------------------------------------------------------------------
# Convolution — reference: phi/kernels/conv_kernel.h, conv_transpose_kernel.h
# ---------------------------------------------------------------------------
def conv2d(
    x,
    weight,
    bias=None,
    *,
    stride=1,
    padding=0,
    dilation=1,
    groups=1,
    data_format="NCHW",
):
    stride = _pair(stride)
    dilation = _pair(dilation)
    pad = _conv_padding(padding, 2, weight.shape[-2:], stride, dilation)
    dn = (data_format, "OIHW", data_format)
    out = jax.lax.conv_general_dilated(
        x,
        weight,
        window_strides=stride,
        padding=pad,
        rhs_dilation=dilation,
        dimension_numbers=dn,
        feature_group_count=groups,
    )
    if bias is not None:
        if data_format == "NCHW":
            out = out + bias.reshape(1, -1, 1, 1)
        else:
            out = out + bias.reshape(1, 1, 1, -1)
    return out


def conv1d(x, weight, bias=None, *, stride=1, padding=0, dilation=1, groups=1, data_format="NCL"):
    stride = _pair(stride, 1)
    dilation = _pair(dilation, 1)
    pad = _conv_padding(padding, 1, weight.shape[-1:], stride, dilation)
    fmt = "NCH" if data_format in ("NCL", "NCH") else "NHC"
    out = jax.lax.conv_general_dilated(
        x,
        weight,
        window_strides=stride,
        padding=pad,
        rhs_dilation=dilation,
        dimension_numbers=(fmt, "OIH", fmt),
        feature_group_count=groups,
    )
    if bias is not None:
        out = out + (bias.reshape(1, -1, 1) if fmt == "NCH" else bias.reshape(1, 1, -1))
    return out


def conv3d(x, weight, bias=None, *, stride=1, padding=0, dilation=1, groups=1, data_format="NCDHW"):
    stride = _pair(stride, 3)
    dilation = _pair(dilation, 3)
    pad = _conv_padding(padding, 3, weight.shape[-3:], stride, dilation)
    dn = (data_format, "OIDHW", data_format)
    out = jax.lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=groups,
    )
    if bias is not None:
        if data_format == "NCDHW":
            out = out + bias.reshape(1, -1, 1, 1, 1)
        else:
            out = out + bias.reshape(1, 1, 1, 1, -1)
    return out


def conv2d_transpose(
    x, weight, bias=None, *, stride=1, padding=0, output_padding=0,
    dilation=1, groups=1, data_format="NCHW",
):
    stride = _pair(stride)
    dilation = _pair(dilation)
    output_padding = _pair(output_padding)
    if isinstance(padding, str):
        raise NotImplementedError("string padding for conv_transpose")
    padding = _conv_padding(padding, 2, weight.shape[-2:], stride, dilation)
    kh, kw = weight.shape[-2:]
    # gradient-style transpose conv: lax conv with lhs dilation
    pad_t = [
        (
            dilation[i] * (k - 1) - padding[i][0],
            dilation[i] * (k - 1) - padding[i][1] + output_padding[i],
        )
        for i, k in enumerate((kh, kw))
    ]
    # weight is (in, out/groups, kh, kw) in paddle conv_transpose layout
    w = jnp.flip(weight, axis=(-2, -1))
    if groups > 1:
        ci = w.shape[0]
        w = w.reshape(groups, ci // groups, *w.shape[1:])
        w = jnp.swapaxes(w, 1, 2).reshape(-1, ci // groups, kh, kw)
    else:
        w = jnp.swapaxes(w, 0, 1)
    dn = (data_format, "OIHW", data_format)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=pad_t, lhs_dilation=stride,
        rhs_dilation=dilation, dimension_numbers=dn, feature_group_count=groups,
    )
    if bias is not None:
        if data_format == "NCHW":
            out = out + bias.reshape(1, -1, 1, 1)
        else:
            out = out + bias.reshape(1, 1, 1, -1)
    return out


def linear(x, weight, bias=None):
    """reference: phi matmul + elementwise_add; paddle weight layout [in, out]."""
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Pooling — reference: phi/kernels/pool_kernel.h
# ---------------------------------------------------------------------------
def _ceil_extra(dim, k, s, p_lo, p_hi):
    """High-side padding extension so ceil_mode emits the tail window.

    The reference PoolOutputSize (funcs/pooling.h:372) is a pure ceil; a
    window starting at/beyond input+pad would hold zero real elements
    (division by zero in the reference kernel), so such windows are
    dropped — every emitted window holds >=1 real element."""
    out_ceil = -(-(dim + p_lo + p_hi - k) // s) + 1
    if (out_ceil - 1) * s >= dim + p_lo:
        out_ceil -= 1
    out_floor = (dim + p_lo + p_hi - k) // s + 1
    return (out_ceil - out_floor) * s


def _apply_ceil_mode(pads, spatial, ks, st, data_format):
    """Extend the high side of the two spatial pad pairs for ceil_mode."""
    lo = 2 if data_format == "NCHW" else 1
    pads = list(pads)
    for i in range(2):
        p_lo, p_hi = pads[lo + i]
        pads[lo + i] = (
            p_lo, p_hi + _ceil_extra(spatial[i], ks[i], st[i], p_lo, p_hi)
        )
    return pads


def max_pool2d(x, *, kernel_size, stride=None, padding=0, ceil_mode=False, data_format="NCHW"):
    ks = _pair(kernel_size)
    st = _pair(stride if stride is not None else kernel_size)
    pad = _conv_padding(padding, 2, ks, st, (1, 1))
    if data_format == "NCHW":
        window = (1, 1) + ks
        strides = (1, 1) + st
        pads = [(0, 0), (0, 0)] + (pad if isinstance(pad, list) else [(0, 0)] * 2)
        spatial = (x.shape[2], x.shape[3])
    else:
        window = (1,) + ks + (1,)
        strides = (1,) + st + (1,)
        pads = [(0, 0)] + (pad if isinstance(pad, list) else [(0, 0)] * 2) + [(0, 0)]
        spatial = (x.shape[1], x.shape[2])
    if pad == "SAME" or pad == "VALID":
        pads = pad
    elif ceil_mode:
        pads = _apply_ceil_mode(pads, spatial, ks, st, data_format)
    return jax.lax.reduce_window(
        x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        jax.lax.max, window, strides, pads,
    )


def max_pool2d_with_index(x, *, kernel_size, stride=None, padding=0,
                          ceil_mode=False):
    """Max pool returning (out, mask) where mask holds each max's flat index
    in its input plane (reference: phi max_pool2d_with_index kernel, NCHW).

    Indices are found by comparing the pooled max against each of the k*k
    strided window offsets — a static unrolled loop XLA fuses; first match
    wins on ties (matching the CUDA kernel's scan order)."""
    if isinstance(padding, str):
        raise ValueError(
            "max_pool2d(return_mask=True) needs explicit integer padding "
            "(the index math has no SAME/VALID form); pass numbers"
        )
    ks = _pair(kernel_size)
    st = _pair(stride if stride is not None else kernel_size)
    ph, pw = _pair(padding)
    n, c, h, w = x.shape

    def _extra(dim, k, s, p):
        return _ceil_extra(dim, k, s, p, p) if ceil_mode else 0

    eh, ew = _extra(h, ks[0], st[0], ph), _extra(w, ks[1], st[1], pw)
    neg = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    out = jax.lax.reduce_window(
        x, neg, jax.lax.max, (1, 1) + ks, (1, 1) + st,
        [(0, 0), (0, 0), (ph, ph + eh), (pw, pw + ew)],
    )
    oh, ow = out.shape[2], out.shape[3]
    padded = jnp.pad(
        x, [(0, 0), (0, 0), (ph, ph + eh), (pw, pw + ew)], constant_values=neg
    )
    # window origin rows/cols in UNPADDED coordinates
    base_r = jnp.arange(oh) * st[0] - ph
    base_c = jnp.arange(ow) * st[1] - pw
    idx = jnp.zeros((n, c, oh, ow), jnp.int64)
    found = jnp.zeros((n, c, oh, ow), bool)
    for di in range(ks[0]):
        for dj in range(ks[1]):
            vals = jax.lax.slice(
                padded,
                (0, 0, di, dj),
                (n, c, di + (oh - 1) * st[0] + 1, dj + (ow - 1) * st[1] + 1),
                (1, 1, st[0], st[1]),
            )
            hit = (vals == out) & ~found
            gidx = (base_r[:, None] + di) * w + (base_c[None, :] + dj)
            idx = jnp.where(hit, gidx[None, None].astype(jnp.int64), idx)
            found = found | hit
    return out, idx


def max_unpool2d(x, indices, *, kernel_size, stride=None, padding=0,
                 output_size=None):
    """Scatter pooled values back to their argmax positions (reference:
    phi unpool_kernel, NCHW). `indices` are flat per-plane positions as
    produced by max_pool2d_with_index."""
    ks = _pair(kernel_size)
    st = _pair(stride if stride is not None else kernel_size)
    ph, pw = _pair(padding)
    n, c, oh, ow = x.shape
    if output_size is not None:
        h, w = int(output_size[-2]), int(output_size[-1])
    else:
        h = (oh - 1) * st[0] - 2 * ph + ks[0]
        w = (ow - 1) * st[1] - 2 * pw + ks[1]
    flat_x = x.reshape(n * c, oh * ow)
    flat_i = indices.reshape(n * c, oh * ow)
    out = jnp.zeros((n * c, h * w), x.dtype)
    rows = jnp.arange(n * c)[:, None]
    out = out.at[rows, flat_i].set(flat_x)
    return out.reshape(n, c, h, w)


def avg_pool2d(
    x, *, kernel_size, stride=None, padding=0, ceil_mode=False,
    exclusive=True, divisor_override=None, data_format="NCHW",
):
    ks = _pair(kernel_size)
    st = _pair(stride if stride is not None else kernel_size)
    pad = _conv_padding(padding, 2, ks, st, (1, 1))
    if data_format == "NCHW":
        window = (1, 1) + ks
        strides = (1, 1) + st
        pads = [(0, 0), (0, 0)] + (pad if isinstance(pad, list) else [])
        spatial = (x.shape[2], x.shape[3])
    else:
        window = (1,) + ks + (1,)
        strides = (1,) + st + (1,)
        pads = [(0, 0)] + (pad if isinstance(pad, list) else []) + [(0, 0)]
        spatial = (x.shape[1], x.shape[2])
    if pad in ("SAME", "VALID"):
        pads = pad
    else:
        base_pads = pads
        if ceil_mode:
            pads = _apply_ceil_mode(pads, spatial, ks, st, data_format)
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
    if divisor_override is not None:
        if divisor_override <= 0:
            raise ValueError(
                f"divisor_override must be > 0, got {divisor_override}"
            )
        return summed / divisor_override
    if pads in ("SAME", "VALID"):
        return summed / (ks[0] * ks[1])

    def _counts(extent, count_pads):
        # window counts depend only on the spatial dims: compute them on a
        # [1,1,H,W]-extent ones tensor (broadcasts over batch/channels) so
        # XLA constant-folds a tiny array, not the full activation shape
        if data_format == "NCHW":
            ones = jnp.ones((1, 1) + extent, x.dtype)
        else:
            ones = jnp.ones((1,) + extent + (1,), x.dtype)
        return jax.lax.reduce_window(
            ones, 0.0, jax.lax.add, window, strides, count_pads
        )

    if exclusive:
        # divisor = real (non-pad) elements per window
        if any(p != (0, 0) for p in pads):
            return summed / _counts(spatial, pads)
        return summed / (ks[0] * ks[1])
    # inclusive: padding counts, but the ceil-mode extension never does —
    # windows are clamped to the padded extent (reference pool kernel /
    # torch count_include_pad=True semantics)
    if ceil_mode and pads != base_pads:
        lo = 2 if data_format == "NCHW" else 1
        padded = tuple(spatial[i] + sum(base_pads[lo + i]) for i in range(2))
        ext = [(0, 0)] * lo + [
            (0, pads[lo + i][1] - base_pads[lo + i][1]) for i in range(2)
        ]
        if data_format != "NCHW":
            ext.append((0, 0))
        return summed / _counts(padded, ext)
    return summed / (ks[0] * ks[1])


def adaptive_avg_pool2d(x, *, output_size, data_format="NCHW"):
    os = _pair(output_size)
    if data_format == "NCHW":
        h, w = x.shape[2], x.shape[3]
    else:
        h, w = x.shape[1], x.shape[2]
    if h % os[0] == 0 and w % os[1] == 0:
        ks = (h // os[0], w // os[1])
        return avg_pool2d(
            x, kernel_size=ks, stride=ks, padding=0, exclusive=False,
            data_format=data_format,
        )
    # general case: mean over variable windows via interpolation-style gather
    axis_h = 2 if data_format == "NCHW" else 1
    out = x
    for ax, o, n in ((axis_h, os[0], h), (axis_h + 1, os[1], w)):
        starts = (jnp.arange(o) * n) // o
        ends = ((jnp.arange(o) + 1) * n + o - 1) // o
        # build averaging matrix [o, n]
        idx = jnp.arange(n)
        mask = (idx[None, :] >= starts[:, None]) & (idx[None, :] < ends[:, None])
        mat = mask.astype(x.dtype) / jnp.sum(mask, axis=1, keepdims=True).astype(x.dtype)
        out = jnp.tensordot(out, mat, axes=[[ax], [1]])
        out = jnp.moveaxis(out, -1, ax)
    return out


def max_pool1d(x, *, kernel_size, stride=None, padding=0, ceil_mode=False):
    xs = x[..., None]
    out = max_pool2d(
        xs, kernel_size=(kernel_size if isinstance(kernel_size, int) else kernel_size[0], 1),
        stride=(stride if isinstance(stride, int) else (stride[0] if stride else kernel_size), 1),
        padding=(padding if isinstance(padding, int) else padding[0], 0),
    )
    return out[..., 0]


def adaptive_avg_pool1d(x, *, output_size):
    xs = x[..., None]
    out = adaptive_avg_pool2d(xs, output_size=(output_size, 1))
    return out[..., 0]


# ---------------------------------------------------------------------------
# Normalization — reference: phi/kernels/batch_norm_kernel.h,
# layer_norm_kernel.h, group_norm; cuDNN replaced by XLA-fused elementwise.
# ---------------------------------------------------------------------------
def batch_norm_infer(x, mean, var, scale, bias, *, epsilon=1e-5, data_format="NCHW"):
    c_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    inv = jax.lax.rsqrt(var + epsilon)
    out = (x - mean.reshape(shape)) * (inv * scale).reshape(shape) + bias.reshape(shape)
    return out


def batch_norm_train(x, scale, bias, *, epsilon=1e-5, data_format="NCHW"):
    """Returns (out, batch_mean, batch_var)."""
    c_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    mean = jnp.mean(x, axis=axes)
    var = jnp.var(x, axis=axes)
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    inv = jax.lax.rsqrt(var + epsilon)
    out = (x - mean.reshape(shape)) * (inv * scale).reshape(shape) + bias.reshape(shape)
    return out, mean, var


def layer_norm(x, weight=None, bias=None, *, epsilon=1e-5, begin_norm_axis=-1):
    if begin_norm_axis < 0:
        begin_norm_axis = x.ndim + begin_norm_axis
    axes = tuple(range(begin_norm_axis, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


def rms_norm(x, weight, *, epsilon=1e-6, zero_centered=False):
    """x / rms(x) * gain over the last axis, the statistics in float32
    whatever ``x`` is held in. ``zero_centered``: the gain is ``1 + weight``
    (the parameter is stored round 0, as the present-day decoders do)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    gain = weight.astype(jnp.float32)
    if zero_centered:
        gain = 1.0 + gain
    return (xf * jax.lax.rsqrt(var + epsilon) * gain).astype(x.dtype)


def rope_inv_freq(theta, dim, yarn=None):
    """[dim / 2] float32: the turn a position gives each rotary pair,
    theta ** (-2 i / dim), or with ``yarn`` (a mapping with the published
    ``factor``, ``original_max_position_embeddings``, ``beta_fast`` and
    ``beta_slow``) the YaRN table of Peng et al., "YaRN: Efficient Context
    Window Extension of Large Language Models" (2023), as ``transformers``
    makes it: low = floor(dim ln(L / (beta_fast 2 pi)) / (2 ln theta)) and
    high = ceil(dim ln(L / (beta_slow 2 pi)) / (2 ln theta)) with L the
    original length, ramp(i) = clip((i - low) / (high - low), 0, 1), and
    the pair's turn (1 - ramp) theta ** (-2 i / dim) + ramp theta **
    (-2 i / dim) / factor. YaRN's attention factor scales the scores and is
    not in the table."""
    half = dim // 2
    inv_freq = 1.0 / (theta ** (np.arange(half, dtype=np.float32)
                                * (2.0 / dim)))
    if yarn is None:
        return inv_freq
    length = yarn["original_max_position_embeddings"]

    def pair(beta):  # the pair that turns beta times over the length
        return dim * math.log(length / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair(yarn["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (inv_freq / np.float32(yarn["factor"]) * ramp
            + inv_freq * (1.0 - ramp)).astype(np.float32)


def rotary_embedding(x, positions=None, *, rotary_dim, theta=10000.0,
                     inv_freq=None):
    """Rotary positions on the first ``rotary_dim`` dims of each head of
    ``x`` [batch, seq, heads, head_dim], the rest passed through: dim i is
    paired with dim i + rotary_dim / 2 (the half-split convention), position
    p turns pair i by p * theta ** (-2 i / rotary_dim), or by p *
    ``inv_freq[i]`` where a table is given (``rope_inv_freq``). Position s
    of the sequence is s (from 0), or ``positions[s]`` where they are given
    ([seq], the same for every row of the batch). Angles in float32."""
    s, half = x.shape[1], rotary_dim // 2
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (np.arange(half, dtype=np.float32)
                                    * (2.0 / rotary_dim)))
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.float32)
    else:
        positions = positions.astype(jnp.float32)
    ang = positions[:, None] * inv_freq
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rotary_dim].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., rotary_dim:]], -1)


def group_norm(x, weight=None, bias=None, *, num_groups, epsilon=1e-5, data_format="NCHW"):
    if data_format != "NCHW":
        raise NotImplementedError
    n, c = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    g = num_groups
    xg = x.reshape(n, g, c // g, *spatial)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    out = ((xg - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape)
    shape = (1, c) + (1,) * len(spatial)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


def instance_norm(x, weight=None, bias=None, *, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


# ---------------------------------------------------------------------------
# Activations — reference: phi/kernels/activation_kernel.h
# ---------------------------------------------------------------------------
def relu(x):
    return jax.nn.relu(x)


def relu6(x):
    return jnp.clip(x, 0.0, 6.0)


def leaky_relu(x, *, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope)


def prelu(x, weight):
    return jnp.where(x >= 0, x, x * weight)


def elu(x, *, alpha=1.0):
    return jax.nn.elu(x, alpha)


def selu(x, *, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


def celu(x, *, alpha=1.0):
    return jax.nn.celu(x, alpha)


def gelu(x, *, approximate=False):
    return jax.nn.gelu(x, approximate=approximate)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def silu(x):
    return jax.nn.silu(x)


def swish(x):
    return jax.nn.silu(x)


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def softplus(x, *, beta=1.0, threshold=20.0):
    scaled = beta * x
    return jnp.where(scaled > threshold, x, jnp.log1p(jnp.exp(scaled)) / beta)


def softsign(x):
    return jax.nn.soft_sign(x)


def softshrink(x, *, threshold=0.5):
    return jnp.where(
        x > threshold, x - threshold, jnp.where(x < -threshold, x + threshold, 0.0)
    )


def hardshrink(x, *, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


def hardtanh(x, *, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


def hardsigmoid(x, *, slope=1.0 / 6.0, offset=0.5):
    return jnp.clip(x * slope + offset, 0.0, 1.0)


def hardswish(x):
    return x * jnp.clip(x / 6.0 + 0.5, 0.0, 1.0)


def tanhshrink(x):
    return x - jnp.tanh(x)


def thresholded_relu(x, *, threshold=1.0):
    return jnp.where(x > threshold, x, 0.0)


def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


def maxout(x, *, groups, axis=1):
    c = x.shape[axis]
    new_shape = list(x.shape)
    new_shape[axis] = c // groups
    new_shape.insert(axis + 1, groups)
    return jnp.max(x.reshape(new_shape), axis=axis + 1)


def glu(x, *, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


def softmax(x, *, axis=-1):
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, *, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


def gumbel_softmax(x, key, *, temperature=1.0, hard=False, axis=-1):
    g = jax.random.gumbel(key, x.shape, dtype=x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        y_hard = jnp.zeros_like(y)
        y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis, inplace=False)
        y = y_hard + jax.lax.stop_gradient(-y) + y  # straight-through
    return y


# ---------------------------------------------------------------------------
# Losses — reference: phi cross_entropy / bce / mse kernels,
# operators/softmax_with_cross_entropy_op
# ---------------------------------------------------------------------------
def softmax_with_cross_entropy(
    logits, label, *, soft_label=False, ignore_index=-100, axis=-1,
    reduction="none",
):
    """reduction folds the mean/sum into this one op so an eager training
    step dispatches a single program for the whole loss (the reference's
    softmax_with_cross_entropy is likewise one fused kernel)."""
    logp = jax.nn.log_softmax(logits, axis=axis)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lab = label
        if lab.ndim == logits.ndim:
            lab = jnp.squeeze(lab, axis=axis)
        picked = jnp.take_along_axis(
            logp, jnp.expand_dims(jnp.clip(lab, 0, None).astype(jnp.int32), axis), axis=axis
        )
        loss = -picked
        valid = jnp.expand_dims(lab != ignore_index, axis)
        loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def mse_loss(input, label):
    return jnp.square(input - label)


def l1_loss(input, label):
    return jnp.abs(input - label)


def smooth_l1_loss(input, label, *, delta=1.0):
    d = jnp.abs(input - label)
    return jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)


def bce_loss(input, label):
    eps = 1e-12
    return -(label * jnp.log(input + eps) + (1 - label) * jnp.log(1 - input + eps))


def bce_with_logits(logit, label, pos_weight=None):
    log_p = jax.nn.log_sigmoid(logit)
    log_not_p = jax.nn.log_sigmoid(-logit)
    if pos_weight is not None:
        return -(pos_weight * label * log_p + (1 - label) * log_not_p)
    return -(label * log_p + (1 - label) * log_not_p)


def nll_loss(log_prob, label, weight=None, *, ignore_index=-100):
    picked = jnp.take_along_axis(
        log_prob, jnp.clip(label, 0, None)[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    loss = -picked
    if weight is not None:
        loss = loss * jnp.take(weight, jnp.clip(label, 0, None))
    return jnp.where(label != ignore_index, loss, 0.0)


def kl_div(input, label):
    # input is log-prob
    return label * (jnp.log(jnp.clip(label, 1e-12, None)) - input)


def cosine_similarity(x1, x2, *, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.sqrt(jnp.sum(x1 * x1, axis=axis))
    n2 = jnp.sqrt(jnp.sum(x2 * x2, axis=axis))
    return dot / jnp.clip(n1 * n2, eps, None)


def hinge_embedding_loss(input, label, *, margin=1.0):
    return jnp.where(label == 1.0, input, jnp.maximum(0.0, margin - input))


def margin_ranking_loss(input, other, label, *, margin=0.0):
    return jnp.maximum(0.0, -label * (input - other) + margin)


# ---------------------------------------------------------------------------
# Embedding — reference: phi/kernels/embedding_kernel.h,
# operators/collective/c_embedding_op (vocab-parallel variant in parallel/)
# ---------------------------------------------------------------------------
def embedding(x, weight, *, padding_idx=None):
    out = jnp.take(weight, x.astype(jnp.int32), axis=0)
    if padding_idx is not None:
        mask = (x != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# Dropout — key passed explicitly (see core/random.py for key plumbing)
# ---------------------------------------------------------------------------
def dropout(x, key, *, p=0.5, mode="upscale_in_train", mask_shape=None):
    """mask_shape: broadcastable mask dims (paddle's `axis` arg — the mask
    varies only along the listed axes and is broadcast along the rest)."""
    if p == 0.0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - p, mask_shape or x.shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    return jnp.where(keep, x, 0.0).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — reference: operators/fused/fused_attention_op.cu, fmha_ref.h.
# XLA fuses this well already; a Pallas flash kernel lives in
# paddle_tpu/ops/pallas/flash_attention.py for long sequences.
# ---------------------------------------------------------------------------
def block_diffusion_mask(half, block):
    """[2 half, 2 half] bool, True where row may attend column: the
    two-stream mask of block-diffusion training over a stream of ``half``
    clean positions and then ``half`` noised ones, in blocks of ``block``
    (``pallas.flash_attention``'s ``block_mask``, as a dense array)."""
    pos = jnp.arange(2 * half)
    noised, blk = pos >= half, (pos % half) // block
    (nq, nk), (bq, bk) = ((a[:, None], a[None, :]) for a in (noised, blk))
    return ((nq & nk & (bk == bq)) | (nq & ~nk & (bk < bq))
            | (~nq & ~nk & (bk <= bq)))


def scaled_dot_product_attention(
    q, k, v, mask=None, dropout_key=None, *, scale=None, is_causal=False,
    dropout_p=0.0, block_mask=None, window=None,
):
    """q,k,v: [batch, seq, heads, head_dim] (paddle fused_attention layout).
    Attention dropout applies to the probabilities when dropout_key is given
    (the functional wrapper threads a key only in training). ``block_mask``
    = (half, block): ``block_diffusion_mask`` in the place of ``is_causal``.
    ``window`` = W with ``is_causal``: the band of each query's last W keys,
    its own included.

    The flash hot path lives in flash_scaled_dot_product_attention below —
    selection happens in the functional wrapper (nn/functional) so the
    per-op jit cache never mixes the two lowerings.
    """
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d**0.5)
    qf = jnp.swapaxes(q, 1, 2)  # [b, h, s, d]
    kf = jnp.swapaxes(k, 1, 2)
    vf = jnp.swapaxes(v, 1, 2)
    if kf.shape[1] != qf.shape[1]:  # grouped-query heads: head i on i // group
        group = qf.shape[1] // kf.shape[1]
        kf = jnp.repeat(kf, group, axis=1)
        vf = jnp.repeat(vf, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * s
    if block_mask is not None:
        logits = jnp.where(block_diffusion_mask(*block_mask), logits,
                           jnp.finfo(logits.dtype).min)
    elif is_causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((ql, kl), dtype=bool), k=kl - ql)
        if window is not None:
            causal = causal & ~jnp.tril(jnp.ones((ql, kl), dtype=bool),
                                        k=kl - ql - window)
        logits = jnp.where(causal, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        logits = logits + mask
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(probs.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf)
    return jnp.swapaxes(out, 1, 2)


def cached_attention(q, k_cache, v_cache, k_new, v_new, cur_len, *, scale):
    """Fixed-shape KV-cache attention step (reference: fused attention's
    CacheKV path). Writes the new K/V at position cur_len into the
    PREALLOCATED [b, max_len, h, d] caches via dynamic_update_slice and
    attends with a prefix+causal mask — every decode step has identical
    shapes, so ONE compiled program serves the whole generation (no
    per-length retraces). cur_len is a traced int32 scalar.

    Returns (out [b, s_new, h, d], k_cache, v_cache).
    """
    zero = jnp.int32(0)
    cur = cur_len.astype(jnp.int32)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k_new.astype(k_cache.dtype),
                                           (zero, cur, zero, zero))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v_new.astype(v_cache.dtype),
                                           (zero, cur, zero, zero))
    s_new = q.shape[1]
    L = k_cache.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache) * np.float32(scale)
    # token i of the new chunk may attend cache positions j <= cur_len + i
    allowed = (
        jnp.arange(L)[None, :] <= (cur + jnp.arange(s_new))[:, None]
    )  # [s_new, L]
    logits = jnp.where(allowed[None, None], logits, np.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache)
    return out.astype(q.dtype), k_cache, v_cache


def paged_decode_attention(q, k_pool, v_pool, tables, lens, k_new, v_new, *,
                           scale, block_size, prefill=False):
    """Paged-KV variant of ``cached_attention`` (the vLLM PagedAttention
    idiom over the same math): each sequence's context lives as a chain of
    fixed-size blocks in one shared pool instead of a private
    ``[b, max_len, h, d]`` buffer, so serving memory is bounded by the pool
    — not by ``max_seq_len × admitted sequences``.

      q            [b, s, h, d]   query chunk (s == 1 for decode steps)
      k/v_pool     [n_blocks, block_size, h, d]  the shared block pool
      tables       [b, n_blk] int32  physical block id per logical block
      lens         [b] int32  tokens already cached per row (pre-append)
      k/v_new      [b, s, h, d]   this chunk's K/V, written at lens..lens+s-1

    Returns ``(out [b, s, h, d], k_pool, v_pool)`` with the new rows
    written. The attention math — einsum strings, prefix+causal mask with
    the same -1e30 fill, softmax — is kept LINE-IDENTICAL to
    ``cached_attention`` so a paged decode is bitwise-equal to the
    fixed-shape cache path over the same context length: the gathered
    block view holds the same values the fixed cache would, masked
    positions contribute exactly 0 after softmax, and 0·garbage == 0.

    ``prefill=True`` (static) asserts the chunk starts at position 0 with
    ``s`` a block multiple and writes whole blocks in one vectorized
    scatter; the general path (decode: s == 1) unrolls over s. Rows padded
    into a batch bucket must point their table at a PRIVATE scratch block
    (one per batch slot) so no two rows scatter into the same block.
    """
    b, s = q.shape[0], q.shape[1]
    if prefill:
        if s % block_size != 0:
            raise ValueError(
                f"paged prefill chunk length {s} is not a multiple of "
                f"block_size {block_size}"
            )
        nb = s // block_size
        k_vals = k_new.astype(k_pool.dtype).reshape(
            (b, nb, block_size) + tuple(k_new.shape[2:]))
        v_vals = v_new.astype(v_pool.dtype).reshape(
            (b, nb, block_size) + tuple(v_new.shape[2:]))
        k_pool = k_pool.at[tables[:, :nb]].set(k_vals)
        v_pool = v_pool.at[tables[:, :nb]].set(v_vals)
    else:
        for i in range(s):  # s is static (1 for decode) — unrolls
            pos = (lens + i).astype(jnp.int32)
            blk = jnp.take_along_axis(
                tables, (pos // block_size)[:, None], axis=1)[:, 0]
            off = pos % block_size
            k_pool = k_pool.at[blk, off].set(k_new[:, i].astype(k_pool.dtype))
            v_pool = v_pool.at[blk, off].set(v_new[:, i].astype(v_pool.dtype))
    n_blk = tables.shape[1]
    L = n_blk * block_size
    k_cache = k_pool[tables].reshape((b, L) + tuple(k_pool.shape[-2:]))
    v_cache = v_pool[tables].reshape((b, L) + tuple(v_pool.shape[-2:]))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache) * np.float32(scale)
    # token i of the new chunk may attend positions j <= lens + i — the
    # cached_attention mask with a per-row cur
    allowed = (
        jnp.arange(L)[None, None, :]
        <= (lens[:, None] + jnp.arange(s)[None, :])[:, :, None]
    )  # [b, s_new, L]
    logits = jnp.where(allowed[:, None], logits, np.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache)
    return out.astype(q.dtype), k_pool, v_pool


def flash_scaled_dot_product_attention(q, k, v, *, scale=None, is_causal=False,
                                       block_mask=None, window=None):
    """Pallas flash kernel path (ops/pallas/flash_attention.py — the
    fused_attention_op.cu replacement): O(S·D) memory instead of the O(S²)
    probability matrix, which is what makes long-seq training fit in HBM.
    No mask/dropout support — the functional wrapper falls back to the dense
    path for those."""
    from .pallas import flash_attention as _flash

    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d**0.5)
    if block_mask is not None:
        with jax.named_scope("block_diffusion_attention"):
            return _flash(q, k, v, scale=s, block_mask=block_mask)
    return _flash(q, k, v, scale=s, causal=is_causal, window=window)


def flash_attention_refusal(q_shape, k_shape, v_shape, block_mask=None,
                            window=None):
    """Why the flash kernel cannot take these [batch, seq, heads, head_dim]
    shapes, as a short reason, or None where it can. k and v may have fewer
    heads than q (grouped-query heads: a divisor of q's). ``block_mask``:
    the two-stream block mask (half, block) asked for; ``window``: a
    sliding window of that many keys."""
    from .pallas.flash_attention import supports as _supports
    from .pallas.flash_attention import supports_block_mask

    q_shape, k_shape, v_shape = map(tuple, (q_shape, k_shape, v_shape))
    if len(q_shape) != 4 or len(k_shape) != 4:
        return "rank"
    if k_shape != v_shape:
        return "k_v_shapes_differ"
    if (q_shape[0], q_shape[1], q_shape[3]) != (k_shape[0], k_shape[1],
                                                k_shape[3]):
        return "q_kv_lengths_differ"
    if k_shape[2] == 0 or q_shape[2] % k_shape[2]:
        return "kv_heads_do_not_divide_q_heads"
    if block_mask is not None:
        if not supports_block_mask(q_shape[1], q_shape[3], block_mask):
            return "block_mask_not_tiled"
    elif window is not None:
        if not _supports(q_shape[1], q_shape[3], window=window):
            return "window_not_tiled"
    elif not _supports(q_shape[1], q_shape[3]):
        return "seq_or_head_dim_not_tiled"
    return None


def flash_attention_eligible(q_shape, k_shape, v_shape) -> bool:
    return flash_attention_refusal(q_shape, k_shape, v_shape) is None


# ---------------------------------------------------------------------------
# Interpolate / vision ops — reference: phi interpolate kernels
# ---------------------------------------------------------------------------
def interpolate(
    x, *, size=None, scale_factor=None, mode="nearest", align_corners=False,
    data_format="NCHW",
):
    if data_format == "NCHW":
        n, c, h, w = x.shape
        spatial = (h, w)
    else:
        n, h, w, c = x.shape
        spatial = (h, w)
    if size is None:
        sf = scale_factor if isinstance(scale_factor, (tuple, list)) else (scale_factor,) * 2
        size = (int(h * sf[0]), int(w * sf[1]))
    size = tuple(int(s) for s in size)
    method = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]
    if data_format == "NCHW":
        shape = (n, c) + size
    else:
        shape = (n,) + size + (c,)
    if align_corners and method != "nearest":
        # jax.image.resize has no align_corners; emulate with explicit coords
        axes = (2, 3) if data_format == "NCHW" else (1, 2)
        out = x
        for ax, o in zip(axes, size):
            n_in = out.shape[ax]
            if o == 1:
                coords = jnp.zeros((1,))
            else:
                coords = jnp.linspace(0.0, n_in - 1.0, o)
            i0 = jnp.clip(jnp.floor(coords).astype(jnp.int32), 0, n_in - 1)
            i1 = jnp.clip(i0 + 1, 0, n_in - 1)
            t = (coords - i0).astype(x.dtype)
            a = jnp.take(out, i0, axis=ax)
            b = jnp.take(out, i1, axis=ax)
            tshape = [1] * out.ndim
            tshape[ax] = o
            out = a + (b - a) * t.reshape(tshape)
        return out
    return jax.image.resize(x, shape, method=method)


def pixel_shuffle(x, *, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    if data_format == "NCHW":
        n, c, h, w = x.shape
        x = x.reshape(n, c // (r * r), r, r, h, w)
        x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
        return x.reshape(n, c // (r * r), h * r, w * r)
    raise NotImplementedError


def grid_sample(x, grid, *, mode="bilinear", padding_mode="zeros", align_corners=True):
    n, c, h, w = x.shape
    gx = grid[..., 0]
    gy = grid[..., 1]
    if align_corners:
        fx = (gx + 1) * 0.5 * (w - 1)
        fy = (gy + 1) * 0.5 * (h - 1)
    else:
        fx = ((gx + 1) * w - 1) * 0.5
        fy = ((gy + 1) * h - 1) * 0.5
    x0 = jnp.floor(fx).astype(jnp.int32)
    y0 = jnp.floor(fy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx = fx - x0
    wy = fy - y0

    def sample(xi, yi):
        valid = (xi >= 0) & (xi < w) & (yi >= 0) & (yi < h)
        xi = jnp.clip(xi, 0, w - 1)
        yi = jnp.clip(yi, 0, h - 1)
        batch = jnp.arange(n).reshape(n, 1, 1)
        vals = x[batch, :, yi, xi]  # [n, gh, gw, c]
        return jnp.where(valid[..., None], vals, 0.0)

    v00 = sample(x0, y0)
    v01 = sample(x1, y0)
    v10 = sample(x0, y1)
    v11 = sample(x1, y1)
    wx_ = wx[..., None]
    wy_ = wy[..., None]
    out = (
        v00 * (1 - wx_) * (1 - wy_)
        + v01 * wx_ * (1 - wy_)
        + v10 * (1 - wx_) * wy_
        + v11 * wx_ * wy_
    )
    return jnp.transpose(out, (0, 3, 1, 2))


def label_smooth(label, *, epsilon=0.1):
    num = label.shape[-1]
    return (1.0 - epsilon) * label + epsilon / num


def npair_normalize(x, *, axis=1, epsilon=1e-12):
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    return x / jnp.maximum(norm, epsilon)


# ---------------------------------------------------------------------------
# N-d pooling generalization (1d rides on 2d; 3d implemented directly) —
# reference: phi/kernels/pool_kernel.h Pool3D / funcs/pooling.cc Pool3dFunctor
# ---------------------------------------------------------------------------
def _tuple3(v):
    if isinstance(v, (tuple, list)):
        return tuple(int(x) for x in (list(v) + [v[-1]] * 3)[:3])
    return (int(v),) * 3


def _pool3d_geometry(x, kernel_size, stride, padding, ceil_mode, data_format):
    ks = _tuple3(kernel_size)
    st = _tuple3(stride if stride is not None else kernel_size)
    pd = _tuple3(padding)
    lo = 2 if data_format == "NCDHW" else 1
    spatial = tuple(x.shape[lo + i] for i in range(3))
    pads = [(0, 0)] * x.ndim
    for i in range(3):
        extra = _ceil_extra(spatial[i], ks[i], st[i], pd[i], pd[i]) if ceil_mode else 0
        pads[lo + i] = (pd[i], pd[i] + extra)
    if data_format == "NCDHW":
        window = (1, 1) + ks
        strides = (1, 1) + st
    else:
        window = (1,) + ks + (1,)
        strides = (1,) + st + (1,)
    return ks, st, pads, window, strides, spatial, lo


def max_pool3d(x, *, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCDHW"):
    ks, st, pads, window, strides, _, _ = _pool3d_geometry(
        x, kernel_size, stride, padding, ceil_mode, data_format
    )
    neg = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    return jax.lax.reduce_window(x, neg, jax.lax.max, window, strides, pads)


def avg_pool3d(x, *, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW"):
    ks, st, pads, window, strides, spatial, lo = _pool3d_geometry(
        x, kernel_size, stride, padding, ceil_mode, data_format
    )
    pd = _tuple3(padding)
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
    if divisor_override is not None:
        if divisor_override <= 0:
            raise ValueError(f"divisor_override must be > 0, got {divisor_override}")
        return summed / divisor_override

    def _counts(extent, count_pads):
        shape = [1] * x.ndim
        for i in range(3):
            shape[lo + i] = extent[i]
        ones = jnp.ones(shape, x.dtype)
        return jax.lax.reduce_window(
            ones, 0.0, jax.lax.add, window, strides, count_pads
        )

    if exclusive:
        if any(p != (0, 0) for p in pads):
            return summed / _counts(spatial, pads)
        return summed / (ks[0] * ks[1] * ks[2])
    # inclusive: padding counts but the ceil-mode extension never does
    # (windows clamp to the padded extent — funcs/pooling.cc Pool3dFunctor)
    extras = [pads[lo + i][1] - pd[i] for i in range(3)]
    if ceil_mode and any(extras):
        padded = tuple(spatial[i] + 2 * pd[i] for i in range(3))
        ext = [(0, 0)] * x.ndim
        for i in range(3):
            ext[lo + i] = (0, extras[i])
        return summed / _counts(padded, ext)
    return summed / (ks[0] * ks[1] * ks[2])


def avg_pool1d(x, *, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True):
    k = kernel_size if isinstance(kernel_size, int) else kernel_size[0]
    s = (stride if isinstance(stride, int) else (stride[0] if stride else k)) or k
    p = padding if isinstance(padding, int) else padding[0]
    out = avg_pool2d(
        x[..., None], kernel_size=(k, 1), stride=(s, 1), padding=(p, 0),
        ceil_mode=ceil_mode, exclusive=exclusive,
    )
    return out[..., 0]


# adaptive pooling — reference: phi adaptive pool kernels (AdaptStartIndex/
# AdaptEndIndex window math, funcs/pooling.cc:68)
def _adaptive_axis_reduce(x, axis, out_size, reducer):
    """Reduce variable [start,end) windows along one axis."""
    n = x.shape[axis]
    starts = [(i * n) // out_size for i in range(out_size)]
    ends = [-(-((i + 1) * n) // out_size) for i in range(out_size)]
    slices = []
    for s, e in zip(starts, ends):
        seg = jax.lax.slice_in_dim(x, s, e, axis=axis)
        slices.append(reducer(seg, axis=axis, keepdims=True))
    return jnp.concatenate(slices, axis=axis)


def adaptive_pool_nd(x, *, output_size, nd, kind, data_format="channels_first"):
    lo = 2 if data_format == "channels_first" else 1
    os = output_size if isinstance(output_size, (tuple, list)) else (output_size,) * nd
    reducer = jnp.max if kind == "max" else jnp.mean
    out = x
    for i in range(nd):
        if os[i] is None:
            continue
        out = _adaptive_axis_reduce(out, lo + i, int(os[i]), reducer)
    return out


def adaptive_max_pool1d(x, *, output_size):
    return adaptive_pool_nd(x, output_size=output_size, nd=1, kind="max")


def adaptive_max_pool2d(x, *, output_size, data_format="NCHW"):
    return adaptive_pool_nd(
        x, output_size=output_size, nd=2, kind="max",
        data_format="channels_first" if data_format == "NCHW" else "channels_last",
    )


def adaptive_max_pool3d(x, *, output_size, data_format="NCDHW"):
    return adaptive_pool_nd(
        x, output_size=output_size, nd=3, kind="max",
        data_format="channels_first" if data_format == "NCDHW" else "channels_last",
    )


def adaptive_avg_pool3d(x, *, output_size, data_format="NCDHW"):
    return adaptive_pool_nd(
        x, output_size=output_size, nd=3, kind="avg",
        data_format="channels_first" if data_format == "NCDHW" else "channels_last",
    )


def max_unpool1d(x, indices, *, kernel_size, stride=None, padding=0,
                 output_size=None):
    k = kernel_size if isinstance(kernel_size, int) else kernel_size[0]
    s = k if stride is None else (stride if isinstance(stride, int) else stride[0])
    p = padding if isinstance(padding, int) else padding[0]
    os2 = None if output_size is None else tuple(output_size) + (1,)
    out = max_unpool2d(
        x[..., None], indices[..., None], kernel_size=(k, 1), stride=(s, 1),
        padding=(p, 0), output_size=os2,
    )
    return out[..., 0]


def max_unpool3d(x, indices, *, kernel_size, stride=None, padding=0,
                 output_size=None):
    """Scatter pooled values to their argmax positions in the DHW volume."""
    ks = _tuple3(kernel_size)
    st = _tuple3(stride if stride is not None else kernel_size)
    pd = _tuple3(padding)
    n, c, od, oh, ow = x.shape
    if output_size is not None:
        d, h, w = (int(v) for v in output_size[-3:])
    else:
        d = (od - 1) * st[0] - 2 * pd[0] + ks[0]
        h = (oh - 1) * st[1] - 2 * pd[1] + ks[1]
        w = (ow - 1) * st[2] - 2 * pd[2] + ks[2]
    flat_x = x.reshape(n * c, -1)
    flat_i = indices.reshape(n * c, -1)
    out = jnp.zeros((n * c, d * h * w), x.dtype)
    rows = jnp.arange(n * c)[:, None]
    out = out.at[rows, flat_i].set(flat_x)
    return out.reshape(n, c, d, h, w)


# ---------------------------------------------------------------------------
# transposed convolutions (1d rides on 2d; 3d direct) — reference:
# phi/kernels/conv_transpose_kernel.h
# ---------------------------------------------------------------------------
def conv1d_transpose(x, weight, bias=None, *, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, data_format="NCL"):
    if isinstance(padding, str):
        raise NotImplementedError("string padding for conv1d_transpose")

    def one(v):
        return v if isinstance(v, int) else v[0]

    out = conv2d_transpose(
        x[..., None], weight[..., None],
        None if bias is None else bias,
        stride=(one(stride), 1), padding=(one(padding), 0),
        output_padding=(one(output_padding), 0), dilation=(one(dilation), 1),
        groups=groups, data_format="NCHW",
    )
    return out[..., 0]


def conv3d_transpose(x, weight, bias=None, *, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCDHW"):
    stride = _tuple3(stride)
    dilation = _tuple3(dilation)
    output_padding = _tuple3(output_padding)
    if isinstance(padding, str):
        raise NotImplementedError("string padding for conv3d_transpose")
    padding = _conv_padding(padding, 3, weight.shape[-3:], stride, dilation)
    kd, kh, kw = weight.shape[-3:]
    pad_t = [
        (
            dilation[i] * (k - 1) - padding[i][0],
            dilation[i] * (k - 1) - padding[i][1] + output_padding[i],
        )
        for i, k in enumerate((kd, kh, kw))
    ]
    w = jnp.flip(weight, axis=(-3, -2, -1))
    if groups > 1:
        ci = w.shape[0]
        w = w.reshape(groups, ci // groups, *w.shape[1:])
        w = jnp.swapaxes(w, 1, 2).reshape(-1, ci // groups, kd, kh, kw)
    else:
        w = jnp.swapaxes(w, 0, 1)
    dn = (data_format, "OIDHW", data_format)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1, 1), padding=pad_t, lhs_dilation=stride,
        rhs_dilation=dilation, dimension_numbers=dn, feature_group_count=groups,
    )
    if bias is not None:
        shape = (1, -1, 1, 1, 1) if data_format == "NCDHW" else (1, 1, 1, 1, -1)
        out = out + bias.reshape(shape)
    return out


# ---------------------------------------------------------------------------
# fold (col2im) — reference: phi/kernels/fold_kernel.h (inverse of unfold)
# ---------------------------------------------------------------------------
def fold(x, *, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1):
    if isinstance(output_sizes, int):
        output_sizes = (output_sizes, output_sizes)
    if isinstance(kernel_sizes, int):
        kernel_sizes = (kernel_sizes, kernel_sizes)
    if isinstance(strides, int):
        strides = (strides, strides)
    if isinstance(paddings, int):
        paddings = (paddings, paddings, paddings, paddings)
    elif len(paddings) == 2:
        paddings = (paddings[0], paddings[1], paddings[0], paddings[1])
    if isinstance(dilations, int):
        dilations = (dilations, dilations)
    n, ckk, L = x.shape
    kh, kw = kernel_sizes
    c = ckk // (kh * kw)
    oh, ow = output_sizes
    ph = oh + paddings[0] + paddings[2]
    pw = ow + paddings[1] + paddings[3]
    nh = (ph - (dilations[0] * (kh - 1) + 1)) // strides[0] + 1
    nw = (pw - (dilations[1] * (kw - 1) + 1)) // strides[1] + 1
    if nh * nw != L:
        raise ValueError(
            f"fold: {L} columns inconsistent with output_sizes {output_sizes} "
            f"(expected {nh}*{nw})"
        )
    cols = x.reshape(n, c, kh, kw, nh, nw)
    out = jnp.zeros((n, c, ph, pw), x.dtype)
    # scatter-add each kernel offset's plane (static k*k unrolled loop)
    for i in range(kh):
        for j in range(kw):
            hi = i * dilations[0]
            wj = j * dilations[1]
            out = out.at[
                :, :,
                hi : hi + nh * strides[0] : strides[0],
                wj : wj + nw * strides[1] : strides[1],
            ].add(cols[:, :, i, j])
    return out[:, :, paddings[0] : ph - paddings[2], paddings[1] : pw - paddings[3]]


# ---------------------------------------------------------------------------
# misc tensor/nn ops (reference files inline)
# ---------------------------------------------------------------------------
def diag_embed(x, *, offset=0, dim1=-2, dim2=-1):
    """reference: nn/functional/extension.py diag_embed → phi diag_embed."""
    nd = x.ndim + 1
    d1 = dim1 % nd
    d2 = dim2 % nd
    if d1 == d2:
        raise ValueError("diag_embed dims must differ")
    m = x.shape[-1] + abs(offset)
    # build in canonical (..., d1, d2) order then move axes into place
    idx = jnp.arange(x.shape[-1])
    row = idx + max(-offset, 0)
    base = jnp.zeros(x.shape[:-1] + (m, m), x.dtype)
    col = idx + max(offset, 0)
    base = base.at[..., row, col].set(x)
    lo, hi = sorted((d1, d2))
    out = jnp.moveaxis(base, -2, lo)
    out = jnp.moveaxis(out, -1, hi)
    if d1 > d2:
        out = jnp.swapaxes(out, d1, d2)
    return out


def sequence_mask(lengths, *, maxlen=None, dtype="int64"):
    """reference: nn/functional/extension.py sequence_mask."""
    from ..core.dtype import to_np_dtype

    if maxlen is None:
        raise ValueError(
            "maxlen must be given under jit (dynamic maxlen would make the "
            "output shape data-dependent); pass int(lengths.max())"
        )
    mask = jnp.arange(maxlen)[None, :] < jnp.asarray(lengths).reshape(-1, 1)
    shape = tuple(jnp.asarray(lengths).shape) + (maxlen,)
    return mask.reshape(shape).astype(to_np_dtype(dtype))


def gather_tree(ids, parents):
    """Trace beam-search ancestry bottom-up (reference:
    operators/gather_tree_op.cc; ids/parents: [T, B, beam])."""
    def step(cur_parents, xs):
        t_ids, t_parents = xs
        sel = jnp.take_along_axis(t_ids, cur_parents, axis=-1)
        new_parents = jnp.take_along_axis(t_parents, cur_parents, axis=-1)
        return new_parents, sel

    init_parents = jnp.broadcast_to(
        jnp.arange(ids.shape[-1]), ids.shape[1:]
    )
    # walk from the last step backwards
    rev_ids = jnp.flip(ids, axis=0)
    rev_parents = jnp.flip(parents, axis=0)
    _, outs = jax.lax.scan(step, init_parents, (rev_ids, rev_parents))
    return jnp.flip(outs, axis=0)


def temporal_shift(x, *, seg_num, shift_ratio=0.25, data_format="NCHW"):
    """TSM shift (reference: operators/temporal_shift_op.h): fold the batch
    into [N/T, T, C, H, W], shift the first fold of channels backward in
    time, the second forward, rest unshifted."""
    if data_format != "NCHW":
        x = jnp.transpose(x, (0, 3, 1, 2))
    nt, c, h, w = x.shape
    n = nt // seg_num
    v = x.reshape(n, seg_num, c, h, w)
    c1 = int(c * shift_ratio)
    c2 = int(c * 2 * shift_ratio)
    pad = jnp.zeros((n, 1, c, h, w), x.dtype)
    prev = jnp.concatenate([v[:, 1:], pad], axis=1)[:, :, :c1]
    nxt = jnp.concatenate([pad, v[:, :-1]], axis=1)[:, :, c1:c2]
    keep = v[:, :, c2:]
    out = jnp.concatenate([prev, nxt, keep], axis=2).reshape(nt, c, h, w)
    if data_format != "NCHW":
        out = jnp.transpose(out, (0, 2, 3, 1))
    return out


def affine_grid(theta, *, out_shape, align_corners=True):
    """reference: operators/affine_grid_op.h — 2D batch affine sampling grid.
    theta [N, 2, 3] -> grid [N, H, W, 2] (normalized coords)."""
    n, h, w = out_shape[0], out_shape[-2], out_shape[-1]

    def axis_coords(size):
        if align_corners:
            return jnp.linspace(-1.0, 1.0, size, dtype=theta.dtype)
        step = 2.0 / size
        return jnp.linspace(-1.0 + step / 2, 1.0 - step / 2, size,
                            dtype=theta.dtype)

    ys = axis_coords(h)
    xs = axis_coords(w)
    gx, gy = jnp.meshgrid(xs, ys)  # [H, W]
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1)  # [H, W, 3]
    return jnp.einsum("hwk,nik->nhwi", base, theta)


def bilinear(x1, x2, weight, bias=None):
    """Bilinear tensor product (reference: operators/bilinear_tensor_product_op.h):
    out[n, o] = x1[n, :] @ W[o] @ x2[n, :] + b[o]."""
    out = jnp.einsum("ni,oij,nj->no", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out


def pixel_unshuffle(x, *, downscale_factor, data_format="NCHW"):
    """reference: phi pixel_unshuffle kernel."""
    r = downscale_factor
    if data_format == "NCHW":
        n, c, h, w = x.shape
        x = x.reshape(n, c, h // r, r, w // r, r)
        x = jnp.transpose(x, (0, 1, 3, 5, 2, 4))
        return x.reshape(n, c * r * r, h // r, w // r)
    n, h, w, c = x.shape
    x = x.reshape(n, h // r, r, w // r, r, c)
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return x.reshape(n, h // r, w // r, c * r * r)


# ---------------------------------------------------------------------------
# losses — reference: the corresponding phi loss kernels
# ---------------------------------------------------------------------------
def square_error_cost(input, label):
    """reference: operators/squared_l2_distance — per-element (x - y)^2."""
    d = input - label
    return d * d


def log_loss(input, label, *, epsilon=1e-4):
    """reference: operators/log_loss_op.h."""
    return -label * jnp.log(input + epsilon) - (1.0 - label) * jnp.log(
        1.0 - input + epsilon
    )


def dice_loss(input, label, *, epsilon=1e-5):
    """reference: nn/functional/loss.py dice_loss (prob input, int label)."""
    label_oh = jax.nn.one_hot(label.squeeze(-1), input.shape[-1], dtype=input.dtype)
    red = tuple(range(1, input.ndim))
    intersect = jnp.sum(input * label_oh, axis=red)
    denom = jnp.sum(input, axis=red) + jnp.sum(label_oh, axis=red)
    dice = (2.0 * intersect + epsilon) / (denom + epsilon)
    return jnp.mean(1.0 - dice)


def npair_loss(anchor, positive, labels, *, l2_reg=0.002):
    """reference: nn/functional/loss.py npair_loss."""
    reg = jnp.mean(jnp.sum(anchor * anchor, axis=1)) + jnp.mean(
        jnp.sum(positive * positive, axis=1)
    )
    reg = reg * 0.25 * l2_reg
    sim = anchor @ positive.T  # [B, B]
    labels = labels.reshape(-1)
    target = (labels[:, None] == labels[None, :]).astype(anchor.dtype)
    target = target / jnp.sum(target, axis=1, keepdims=True)
    logp = jax.nn.log_softmax(sim, axis=1)
    ce = -jnp.mean(jnp.sum(target * logp, axis=1))
    return ce + reg


def ctc_loss_per_sample(log_probs, labels, input_lengths, label_lengths,
                        *, blank=0):
    """CTC forward algorithm in log space over [T, B, C] log-probs
    (reference: operators/warpctc_op.h semantics; the reference applies
    softmax inside warpctc — callers pass raw logits through log_softmax
    first, which F.ctc_loss does).

    labels: [B, L] padded with anything (masked by label_lengths)."""
    T, B, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    neg_inf = jnp.asarray(-1e30, log_probs.dtype)

    # extended label sequence: blank, l1, blank, l2, ..., blank
    ext = jnp.full((B, S), blank, labels.dtype)
    ext = ext.at[:, 1::2].set(labels)
    # allowed skip (s-2 -> s): ext[s] != blank and ext[s] != ext[s-2]
    skip_ok = jnp.zeros((B, S), bool)
    skip_ok = skip_ok.at[:, 2:].set(
        (ext[:, 2:] != blank) & (ext[:, 2:] != ext[:, :-2])
    )
    sidx = jnp.arange(S)[None, :]
    valid_s = sidx < (2 * label_lengths[:, None] + 1)

    def emit(t_lp):  # [B, C] -> [B, S] log-prob of each ext symbol
        return jnp.take_along_axis(t_lp, ext, axis=1)

    alpha0 = jnp.full((B, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(log_probs[0, :, blank])
    first_lab = emit(log_probs[0])[:, 1]
    alpha0 = alpha0.at[:, 1].set(jnp.where(label_lengths > 0, first_lab, neg_inf))

    def step(alpha, t_lp):
        prev1 = jnp.concatenate(
            [jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1
        )
        prev2 = jnp.concatenate(
            [jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1
        )
        prev2 = jnp.where(skip_ok, prev2, neg_inf)
        stacked = jnp.stack([alpha, prev1, prev2], axis=0)
        merged = jax.scipy.special.logsumexp(stacked, axis=0)
        new = merged + emit(t_lp)
        return jnp.where(valid_s, new, neg_inf), None

    ts = jnp.arange(1, T)

    def masked_step(alpha, inputs):
        t, t_lp = inputs
        new, _ = step(alpha, t_lp)
        # past each sample's input length the alphas freeze
        active = (t < input_lengths)[:, None]
        return jnp.where(active, new, alpha), None

    alpha, _ = jax.lax.scan(masked_step, alpha0, (ts, log_probs[1:]))
    endA = jnp.take_along_axis(alpha, (2 * label_lengths - 1)[:, None], axis=1)[:, 0]
    endB = jnp.take_along_axis(alpha, (2 * label_lengths)[:, None], axis=1)[:, 0]
    ll = jax.scipy.special.logsumexp(jnp.stack([endA, endB]), axis=0)
    # empty label: loss = -sum of blank log-probs up to input_length
    t_idx = jnp.arange(T)[:, None]
    blank_sum = jnp.sum(
        jnp.where(t_idx < input_lengths[None, :], log_probs[:, :, blank], 0.0),
        axis=0,
    )
    ll = jnp.where(label_lengths == 0, blank_sum, ll)
    return -ll


def hsigmoid_loss_op(x, labels, weight, bias=None, path_table=None,
                     path_code=None, *, num_classes):
    """Hierarchical sigmoid loss (reference:
    operators/hierarchical_sigmoid_op.h + funcs/matrix_bit_code.h SimpleCode:
    c = label + num_classes; index(j) = (c >> (j+1)) - 1; bit(j) = (c >> j) & 1;
    length = bits(c >> 1)). Returns [N, 1]."""
    n = x.shape[0]
    if path_table is not None:
        # custom tree: indices [N, L] (pad -1), codes [N, L]
        idx = path_table
        bits = path_code.astype(x.dtype)
        valid = (idx >= 0)
        safe_idx = jnp.maximum(idx, 0)
    else:
        max_len = int(np.floor(np.log2(max(num_classes - 1, 1)))) + 1
        c = labels.reshape(-1).astype(jnp.int64) + num_classes
        j = jnp.arange(max_len)
        idx = (c[:, None] >> (j[None, :] + 1)) - 1
        bits = ((c[:, None] >> j[None, :]) & 1).astype(x.dtype)
        # length = number of bits in (c >> 1): j valid while (c>>1) >> j > 0
        valid = ((c[:, None] >> (j[None, :] + 1)) > 0)
        safe_idx = jnp.clip(idx, 0, weight.shape[0] - 1)
    w = weight[safe_idx]                       # [N, L, D]
    pre = jnp.einsum("nld,nd->nl", w, x)
    if bias is not None:
        pre = pre + bias.reshape(-1)[safe_idx]
    # sigmoid cross entropy with target bit: softplus(pre) - bit*pre
    loss = jnp.where(valid, jax.nn.softplus(pre) - bits * pre, 0.0)
    return jnp.sum(loss, axis=1, keepdims=True)


def margin_cross_entropy_op(logits, label, *, margin1=1.0, margin2=0.5,
                            margin3=0.0, scale=64.0):
    """ArcFace-family margin softmax (reference:
    operators/margin_cross_entropy_op.cu): target logit cos(theta) becomes
    cos(m1*theta + m2) - m3, all logits scaled by s. Returns (loss, softmax)."""
    oh = jax.nn.one_hot(label.reshape(-1), logits.shape[-1], dtype=logits.dtype)
    cos = jnp.clip(logits, -1.0, 1.0)
    if margin1 != 1.0 or margin2 != 0.0:
        theta = jnp.arccos(cos)
        target = jnp.cos(margin1 * theta + margin2)
    else:
        target = cos
    target = target - margin3
    adjusted = jnp.where(oh > 0, target, logits) * scale
    logp = jax.nn.log_softmax(adjusted, axis=-1)
    loss = -jnp.sum(oh * logp, axis=-1, keepdims=True)
    return loss, jnp.exp(logp)


def sparse_attention_op(q, k, v, offset, columns):
    """Block-sparse attention with a per-(batch, head) CSR pattern
    (reference: operators/sparse_attention_op.cu). TPU-native lowering:
    materialize the CSR pattern as a mask and let XLA fuse the masked
    softmax — on MXU the dense QK^T is the fast path for the seq lengths
    the reference op supports."""
    S = q.shape[-2]

    def one_head(qh, kh, vh, off, cols):
        nnz = cols.shape[0]
        j = jnp.arange(nnz)
        row_of_j = jnp.searchsorted(off, j, side="right") - 1
        mask = jnp.zeros((S, S), bool).at[row_of_j, cols].set(True)
        scores = (qh @ kh.T) / jnp.sqrt(jnp.asarray(qh.shape[-1], qh.dtype))
        scores = jnp.where(mask, scores, -jnp.inf)
        # rows with no allowed key produce 0 output, not NaN
        w = jax.nn.softmax(scores, axis=-1)
        w = jnp.where(mask.any(-1, keepdims=True), w, 0.0)
        return w @ vh

    return jax.vmap(jax.vmap(one_head))(q, k, v, offset, columns)
