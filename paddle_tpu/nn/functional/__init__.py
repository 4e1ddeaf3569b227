"""paddle.nn.functional — functional neural-net API.

Reference analogue: python/paddle/nn/functional/ (activation.py, common.py,
conv.py, loss.py, norm.py, pooling.py, input.py). Dispatches to
paddle_tpu.ops.nn_ops through the autograd-aware dispatcher.
"""
from __future__ import annotations

import numpy as np

from ...core import random as _random
from ...core.dispatch import apply, is_grad_enabled
from ...core.tensor import Tensor, to_tensor
from ...ops import nn_ops as _nn
from ...ops import manipulation as _mp

__all__ = []  # populated at bottom


def _t(v):
    return tuple(v) if isinstance(v, (list, tuple)) else v


# ----------------------------- activations ---------------------------------
def relu(x, name=None):
    return apply(_nn.relu, x, op_name="relu")


def relu_(x, name=None):
    out = relu(x)
    x._value = out._value
    if out._grad_node is not None:
        x._grad_node = out._grad_node
        x._out_index = out._out_index
        x.stop_gradient = out.stop_gradient
    x._bump_version()
    return x


def relu6(x, name=None):
    return apply(_nn.relu6, x)


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply(_nn.leaky_relu, x, negative_slope=negative_slope)


def prelu(x, weight, data_format="NCHW", name=None):
    w = weight
    if isinstance(w, Tensor) and w.size > 1 and x.ndim > 1:
        shape = [1] * x.ndim
        c_axis = 1 if data_format.startswith("NC") else x.ndim - 1
        shape[c_axis] = w.size
        w = w.reshape(shape)
    return apply(_nn.prelu, x, w)


def elu(x, alpha=1.0, name=None):
    return apply(_nn.elu, x, alpha=alpha)


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return apply(_nn.selu, x, scale=scale, alpha=alpha)


def celu(x, alpha=1.0, name=None):
    return apply(_nn.celu, x, alpha=alpha)


def gelu(x, approximate=False, name=None):
    return apply(_nn.gelu, x, approximate=approximate, op_name="gelu")


def sigmoid(x, name=None):
    return apply(_nn.sigmoid, x, op_name="sigmoid")


def silu(x, name=None):
    return apply(_nn.silu, x)


def swish(x, name=None):
    return apply(_nn.swish, x)


def mish(x, name=None):
    return apply(_nn.mish, x)


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return apply(_nn.softplus, x, beta=beta, threshold=threshold)


def softsign(x, name=None):
    return apply(_nn.softsign, x)


def softshrink(x, threshold=0.5, name=None):
    return apply(_nn.softshrink, x, threshold=threshold)


def hardshrink(x, threshold=0.5, name=None):
    return apply(_nn.hardshrink, x, threshold=threshold)


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return apply(_nn.hardtanh, x, min=min, max=max)


def hardsigmoid(x, slope=1.0 / 6.0, offset=0.5, name=None):
    return apply(_nn.hardsigmoid, x, slope=slope, offset=offset)


def hardswish(x, name=None):
    return apply(_nn.hardswish, x)


def tanhshrink(x, name=None):
    return apply(_nn.tanhshrink, x)


def thresholded_relu(x, threshold=1.0, name=None):
    return apply(_nn.thresholded_relu, x, threshold=threshold)


def log_sigmoid(x, name=None):
    return apply(_nn.log_sigmoid, x)


def maxout(x, groups, axis=1, name=None):
    return apply(_nn.maxout, x, groups=groups, axis=axis)


def glu(x, axis=-1, name=None):
    return apply(_nn.glu, x, axis=axis)


def tanh(x, name=None):
    import jax.numpy as jnp

    return apply(jnp.tanh, x, op_name="tanh")


def softmax(x, axis=-1, dtype=None, name=None):
    out = apply(_nn.softmax, x, axis=axis, op_name="softmax")
    return out.astype(dtype) if dtype is not None else out


def log_softmax(x, axis=-1, dtype=None, name=None):
    out = apply(_nn.log_softmax, x, axis=axis)
    return out.astype(dtype) if dtype is not None else out


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    return apply(
        _nn.gumbel_softmax, x, _random.next_key(), temperature=temperature,
        hard=hard, axis=axis,
    )


# ----------------------------- linear/conv ----------------------------------
def linear(x, weight, bias=None, name=None):
    if bias is None:
        return apply(_nn.linear, x, weight, op_name="linear")
    return apply(_nn.linear, x, weight, bias, op_name="linear")


def conv2d(
    x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
    data_format="NCHW", name=None,
):
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(
        _nn.conv2d, *args, stride=_t(stride), padding=_t(padding),
        dilation=_t(dilation), groups=groups, data_format=data_format,
        op_name="conv2d",
    )


def conv1d(
    x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
    data_format="NCL", name=None,
):
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(
        _nn.conv1d, *args, stride=_t(stride), padding=_t(padding),
        dilation=_t(dilation), groups=groups, data_format=data_format,
    )


def conv3d(
    x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
    data_format="NCDHW", name=None,
):
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(
        _nn.conv3d, *args, stride=_t(stride), padding=_t(padding),
        dilation=_t(dilation), groups=groups, data_format=data_format,
    )


def conv2d_transpose(
    x, weight, bias=None, stride=1, padding=0, output_padding=0, groups=1,
    dilation=1, data_format="NCHW", output_size=None, name=None,
):
    if output_size is not None:
        spatial = (
            tuple(x.shape[2:4]) if data_format == "NCHW" else tuple(x.shape[1:3])
        )
        output_padding = _transpose_out_padding(
            output_size, spatial, tuple(weight.shape[-2:]), stride, padding,
            dilation, output_padding, 2,
        )
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(
        _nn.conv2d_transpose, *args, stride=_t(stride), padding=_t(padding),
        output_padding=_t(output_padding), dilation=_t(dilation), groups=groups,
        data_format=data_format,
    )


# ----------------------------- pooling --------------------------------------
def max_pool2d(
    x, kernel_size, stride=None, padding=0, ceil_mode=False,
    return_mask=False, data_format="NCHW", name=None,
):
    if return_mask:
        if data_format != "NCHW":
            raise ValueError("return_mask requires NCHW (reference kernel layout)")
        return apply(
            _nn.max_pool2d_with_index, x, kernel_size=_t(kernel_size),
            stride=_t(stride), padding=_t(padding), ceil_mode=ceil_mode,
            op_name="max_pool2d_with_index",
        )
    return apply(
        _nn.max_pool2d, x, kernel_size=_t(kernel_size), stride=_t(stride),
        padding=_t(padding), ceil_mode=ceil_mode, data_format=data_format,
        op_name="max_pool2d",
    )


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None, name=None):
    if data_format != "NCHW":
        raise ValueError("max_unpool2d requires NCHW")
    out_sz = tuple(output_size) if output_size is not None else None
    return apply(
        _nn.max_unpool2d, x, indices, kernel_size=_t(kernel_size),
        stride=_t(stride), padding=_t(padding), output_size=out_sz,
        op_name="max_unpool2d",
    )


def avg_pool2d(
    x, kernel_size, stride=None, padding=0, ceil_mode=False, exclusive=True,
    divisor_override=None, data_format="NCHW", name=None,
):
    return apply(
        _nn.avg_pool2d, x, kernel_size=_t(kernel_size), stride=_t(stride),
        padding=_t(padding), ceil_mode=ceil_mode, exclusive=exclusive,
        divisor_override=divisor_override, data_format=data_format,
        op_name="avg_pool2d",
    )


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return apply(
        _nn.adaptive_avg_pool2d, x, output_size=_t(output_size),
        data_format=data_format,
    )


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False, name=None):
    return apply(
        _nn.max_pool1d, x, kernel_size=_t(kernel_size), stride=_t(stride),
        padding=_t(padding), ceil_mode=ceil_mode,
    )


def adaptive_avg_pool1d(x, output_size, name=None):
    return apply(_nn.adaptive_avg_pool1d, x, output_size=output_size)


# ----------------------------- norm ------------------------------------------
def batch_norm(
    x, running_mean, running_var, weight, bias, training=False,
    momentum=0.9, epsilon=1e-05, data_format="NCHW", use_global_stats=None, name=None,
):
    """reference: nn/functional/norm.py batch_norm; running stats updated
    in-place like the reference's BatchNorm kernels (momentum semantics:
    running = momentum*running + (1-momentum)*batch)."""
    if use_global_stats is None:
        use_global_stats = not training
    if use_global_stats:
        return apply(
            _nn.batch_norm_infer, x, running_mean, running_var, weight, bias,
            epsilon=epsilon, data_format=data_format, op_name="batch_norm_infer",
        )
    out, bm, bv = apply(
        _nn.batch_norm_train, x, weight, bias, epsilon=epsilon,
        data_format=data_format, op_name="batch_norm",
    )
    # update running stats (no tape). Works under a jit trace too: traced
    # buffer values are threaded out of the compiled program by
    # StaticFunction / CompiledTrainStep (paddle_tpu.jit).
    if isinstance(running_mean, Tensor):
        with __import__("paddle_tpu").no_grad():
            running_mean._value = (
                running_mean._value * momentum + bm._value * (1 - momentum)
            )
            # the reference accumulates the *biased* batch variance into
            # running_var (phi/kernels/cpu/batch_norm_kernel.cc:152) — no
            # Bessel correction, so eval-mode outputs match it exactly
            running_var._value = (
                running_var._value * momentum + bv._value * (1 - momentum)
            )
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05, name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    begin = x.ndim - len(normalized_shape)
    # None weight/bias pass straight through apply (empty pytree under jit)
    return apply(
        _nn.layer_norm, x, weight, bias, epsilon=epsilon, begin_norm_axis=begin,
        op_name="layer_norm",
    )


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None, data_format="NCHW", name=None):
    return apply(
        _nn.group_norm, x, weight, bias, num_groups=num_groups, epsilon=epsilon,
        data_format=data_format,
    )


def instance_norm(
    x, running_mean=None, running_var=None, weight=None, bias=None,
    use_input_stats=True, momentum=0.9, eps=1e-05, data_format="NCHW", name=None,
):
    args = [x]
    if weight is not None:
        args += [weight, bias]
    return apply(_nn.instance_norm, *args, epsilon=eps)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    import jax.numpy as jnp

    def _norm(v, p, axis, epsilon):
        n = jnp.sum(jnp.abs(v) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return v / jnp.maximum(n, epsilon)

    return apply(_norm, x, p=float(p), axis=axis, epsilon=epsilon)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    import jax.numpy as jnp

    def _lrn(v, size, alpha, beta, k):
        sq = jnp.square(v)
        half = size // 2
        pads = [(0, 0)] * v.ndim
        pads[1] = (half, size - half - 1)
        padded = jnp.pad(sq, pads)
        acc = sum(
            padded[:, i : i + v.shape[1]] for i in range(size)
        )
        return v / jnp.power(k + alpha * acc / size, beta)

    return apply(_lrn, x, size=size, alpha=alpha, beta=beta, k=k)


# ----------------------------- dropout ---------------------------------------
def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    if not training or p == 0.0:
        # downscale_in_infer (the fluid-era default) scales at INFERENCE:
        # out = x * (1-p) in eval, unscaled masking in train
        if mode == "downscale_in_infer" and p > 0.0:
            return x * (1.0 - p)
        return x if isinstance(x, Tensor) else to_tensor(x)
    mask_shape = None
    if axis is not None:
        ndim = len(x.shape)
        axes = {a % ndim for a in ([axis] if isinstance(axis, int) else axis)}
        mask_shape = tuple(
            int(d) if i in axes else 1 for i, d in enumerate(x.shape)
        )
    return apply(
        _nn.dropout, x, _random.next_key(), p=float(p), mode=mode,
        mask_shape=mask_shape, op_name="dropout",
    )


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    if not training or p == 0.0:
        return x
    import jax
    import jax.numpy as jnp

    def _d2(v, key, *, p, data_format):
        if data_format == "NCHW":
            shape = (v.shape[0], v.shape[1], 1, 1)
        else:  # NHWC: channel last
            shape = (v.shape[0], 1, 1, v.shape[3])
        keep = jax.random.bernoulli(key, 1.0 - p, shape)
        return jnp.where(keep, v / (1.0 - p), 0.0).astype(v.dtype)

    return apply(_d2, x, _random.next_key(), p=float(p), data_format=data_format)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x
    import jax
    import jax.numpy as jnp

    def _ad(v, key, p):
        alpha = 1.6732632423543772
        scale = 1.0507009873554805
        neg = -alpha * scale
        keep = jax.random.bernoulli(key, 1.0 - p, v.shape)
        a = (1.0 / (scale * ((1 - p) * (1 + p * alpha**2)) ** 0.5))
        b = -a * neg * p
        return a * jnp.where(keep, v, neg) + b

    return apply(_ad, x, _random.next_key(), p=float(p))


# ----------------------------- losses ----------------------------------------
def _reduce(loss, reduction):
    if reduction == "mean":
        return loss.mean()
    if reduction == "sum":
        return loss.sum()
    return loss


def cross_entropy(
    input, label, weight=None, ignore_index=-100, reduction="mean",
    soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0, name=None,
):
    """reference: nn/functional/loss.py cross_entropy →
    softmax_with_cross_entropy op (operators/softmax_with_cross_entropy_op)."""
    if label_smoothing > 0.0:
        num = input.shape[axis]
        if not soft_label:
            import paddle_tpu as paddle

            label = paddle.nn.functional.one_hot(label, num)
            soft_label = True
        label = label * (1.0 - label_smoothing) + label_smoothing / num
    # mean with a real ignore_index divides by the VALID count (handled in
    # the tail below); one predicate gates both that branch and the
    # fused-reduction exclusion so they cannot drift apart
    mean_needs_valid_count = (
        reduction == "mean" and ignore_index != -100 and not soft_label
    )
    if not use_softmax:
        lg = apply(
            lambda p: __import__("jax.numpy", fromlist=["log"]).log(
                __import__("jax.numpy", fromlist=["clip"]).clip(p, 1e-12, None)
            ),
            input,
        )
        loss = nll_from_logprob(lg, label, soft_label, ignore_index, axis)
    else:
        # fold mean/sum into the one fused op when no post-scaling applies —
        # the whole loss is then a single dispatched program (fwd and bwd)
        if (
            weight is None
            and reduction in ("mean", "sum")
            and not mean_needs_valid_count
        ):
            return apply(
                _nn.softmax_with_cross_entropy, input, label, soft_label=soft_label,
                ignore_index=ignore_index, axis=axis, reduction=reduction,
                op_name="softmax_with_cross_entropy",
            )
        loss = apply(
            _nn.softmax_with_cross_entropy, input, label, soft_label=soft_label,
            ignore_index=ignore_index, axis=axis, op_name="softmax_with_cross_entropy",
        )
    loss = loss.squeeze(axis) if loss.ndim > max(input.ndim - 1, 1) - 0 else loss
    if weight is not None and not soft_label:
        import jax.numpy as jnp

        def _w(wt, lb, *, ignore_index):
            w = jnp.take(wt, jnp.clip(lb, 0, None))
            # ignored positions contribute neither loss nor denominator
            return jnp.where(lb != ignore_index, w, 0.0)

        w = apply(_w, weight, label, ignore_index=ignore_index)
        loss = loss * w
        if reduction == "mean":
            return loss.sum() / w.sum().clip(min=1e-12)
    if mean_needs_valid_count:
        valid = (label != ignore_index).astype(loss.dtype)
        denom = valid.sum().clip(min=1.0)
        return loss.sum() / denom
    return _reduce(loss, reduction)


def nll_from_logprob(logp, label, soft_label, ignore_index, axis):
    import jax.numpy as jnp

    if soft_label:
        return apply(
            lambda lp, lb, axis: -jnp.sum(lb * lp, axis=axis), logp, label, axis=axis
        )
    return apply(
        lambda lp, lb, axis, ignore_index: jnp.where(
            lb != ignore_index,
            -jnp.take_along_axis(
                lp, jnp.expand_dims(jnp.clip(lb, 0, None).astype(jnp.int32), axis), axis=axis
            ).squeeze(axis),
            0.0,
        ),
        logp, label, axis=axis, ignore_index=ignore_index,
    )


def softmax_with_cross_entropy(
    logits, label, soft_label=False, ignore_index=-100, numeric_stable_mode=True,
    return_softmax=False, axis=-1,
):
    loss = apply(
        _nn.softmax_with_cross_entropy, logits, label, soft_label=soft_label,
        ignore_index=ignore_index, axis=axis,
    )
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def mse_loss(input, label, reduction="mean", name=None):
    return _reduce(apply(_nn.mse_loss, input, label), reduction)


def l1_loss(input, label, reduction="mean", name=None):
    return _reduce(apply(_nn.l1_loss, input, label), reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    return _reduce(apply(_nn.smooth_l1_loss, input, label, delta=delta), reduction)


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    loss = apply(_nn.bce_loss, input, label)
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def binary_cross_entropy_with_logits(
    logit, label, weight=None, reduction="mean", pos_weight=None, name=None
):
    if pos_weight is not None:
        loss = apply(_nn.bce_with_logits, logit, label, pos_weight)
    else:
        loss = apply(_nn.bce_with_logits, logit, label)
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    if weight is not None:
        loss = apply(_nn.nll_loss, input, label, weight, ignore_index=ignore_index)
    else:
        loss = apply(_nn.nll_loss, input, label, ignore_index=ignore_index)
    return _reduce(loss, reduction)


def kl_div(input, label, reduction="mean", name=None):
    loss = apply(_nn.kl_div, input, label)
    if reduction == "batchmean":
        return loss.sum() / input.shape[0]
    return _reduce(loss, reduction)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):
    return _reduce(
        apply(_nn.margin_ranking_loss, input, other, label, margin=margin), reduction
    )


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    return _reduce(
        apply(_nn.hinge_embedding_loss, input, label, margin=margin), reduction
    )


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    return apply(_nn.cosine_similarity, x1, x2, axis=axis, eps=eps)


def sigmoid_focal_loss(
    logit, label, normalizer=None, alpha=0.25, gamma=2.0, reduction="sum", name=None
):
    import jax
    import jax.numpy as jnp

    def _focal(lg, lb, alpha, gamma):
        p = jax.nn.sigmoid(lg)
        ce = _nn.bce_with_logits(lg, lb)
        p_t = p * lb + (1 - p) * (1 - lb)
        a_t = alpha * lb + (1 - alpha) * (1 - lb)
        return a_t * ((1 - p_t) ** gamma) * ce

    loss = apply(_focal, logit, label, alpha=alpha, gamma=gamma)
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce(loss, reduction)


# ----------------------------- embedding / inputs ----------------------------
def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    return apply(_nn.embedding, x, weight, padding_idx=padding_idx, op_name="embedding")


def one_hot(x, num_classes, name=None):
    from ...ops import creation as _c

    return apply(_c.one_hot, x, num_classes=num_classes, differentiable=False)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    return apply(_nn.label_smooth, label, epsilon=epsilon)


# ----------------------------- shape / vision --------------------------------
def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    return apply(
        _mp.pad, x, pad=tuple(int(p) for p in pad), mode=mode, value=value,
        data_format=data_format, op_name="pad",
    )


def interpolate(
    x, size=None, scale_factor=None, mode="nearest", align_corners=False,
    align_mode=0, data_format="NCHW", name=None,
):
    return apply(
        _nn.interpolate, x,
        size=None if size is None else tuple(int(s) for s in size),
        scale_factor=_t(scale_factor), mode=mode, align_corners=align_corners,
        data_format=data_format,
    )


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
             align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode, data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    return apply(_nn.pixel_shuffle, x, upscale_factor=upscale_factor, data_format=data_format)


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros", align_corners=True, name=None):
    return apply(
        _nn.grid_sample, x, grid, mode=mode, padding_mode=padding_mode,
        align_corners=align_corners,
    )


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    return apply(
        _mp.unfold, x, kernel_sizes=_t(kernel_sizes), strides=_t(strides),
        paddings=_t(paddings), dilations=_t(dilations),
    )


# ----------------------------- attention -------------------------------------
def rms_norm(x, weight, epsilon=1e-6, zero_centered=False, name=None):
    """RMSNorm over the last axis; ``zero_centered``: the gain is 1 + weight."""
    return apply(_nn.rms_norm, x, weight, epsilon=epsilon,
                 zero_centered=zero_centered, op_name="rms_norm")


def rotary_embedding(x, rotary_dim=None, theta=10000.0, positions=None,
                     name=None):
    """Rotary positions on the first ``rotary_dim`` dims of each head of
    ``x`` [batch, seq, heads, head_dim] (all of them by default);
    ``positions`` [seq] in the place of 0 .. seq - 1."""
    return apply(_nn.rotary_embedding, x, positions,
                 rotary_dim=int(rotary_dim or x.shape[-1]), theta=float(theta),
                 op_name="rotary_embedding")


def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False,
    training=True, name=None, scale=None, block_mask=None, window=None,
):
    # ``scale`` multiplies q k^T in place of head_dim ** -0.5 (a model with
    # an attention multiplier of its own); without it the ops are called as
    # they always were. ``block_mask`` = (half, block) is the two-stream mask
    # of block-diffusion training in the place of ``is_causal``
    # (ops/pallas/flash_attention.py); the kernels walk it, and where they
    # cannot the dense path builds it as an array. ``window`` = W, with
    # ``is_causal``: a sliding window, each query attending its last W keys
    # (its own included); the kernels walk the band, the dense path builds it
    options = {} if scale is None else {"scale": float(scale)}
    if block_mask is not None:
        options["block_mask"] = tuple(map(int, block_mask))
    if window is not None:
        if not is_causal or block_mask is not None or int(window) < 1:
            raise ValueError(
                f"scaled_dot_product_attention: window={window} is a causal "
                "band of one or more keys (is_causal=True, no block_mask)")
        options["window"] = int(window)
    dropout_key = (
        _random.next_key() if (dropout_p > 0.0 and training) else None
    )
    # pick the lowering HERE (not inside the op) so the per-op jit cache
    # keys on distinct function objects and FLAGS_use_flash_attention
    # toggles take effect immediately
    from ...core import flags as _flags
    from ...parallel import topology as _topo

    # a pallas_call has no GSPMD partitioning rule: under a >1-device mesh
    # XLA would replicate q/k/v (all-gathering sharded batch/seq/heads), so
    # sharded programs keep the dense einsum path, which GSPMD partitions.
    _mesh = _topo.get_mesh()
    _single_device = _mesh is None or _mesh.devices.size == 1
    if _flags.flag("use_flash_attention"):
        # why the dense O(S^2) path is taken, where it is: counted
        # (`flash_attention_fallbacks`, by reason) and left in the flight
        # recorder, so that a fallback at a long sequence cannot pass unseen
        refusal = (
            "multi_device_mesh" if not _single_device
            else "attn_mask" if attn_mask is not None
            else "attention_dropout" if dropout_key is not None
            else _nn.flash_attention_refusal(query.shape, key.shape,
                                             value.shape, block_mask,
                                             options.get("window"))
        )
        if refusal is None:
            return apply(
                _nn.flash_scaled_dot_product_attention, query, key, value,
                is_causal=is_causal, op_name="flash_sdpa", **options,
            )
        from ...core import dispatch as _dispatch

        _dispatch._count_flash_fallback(
            refusal, tuple(query.shape), tuple(key.shape))
    return apply(
        _nn.scaled_dot_product_attention, query, key, value, attn_mask,
        dropout_key, is_causal=is_causal, dropout_p=dropout_p, op_name="sdpa",
        **options,
    )


__all__ = [n for n in dir() if not n.startswith("_")]


# ---------------------------------------------------------------------------
# N-d pooling / conv-transpose / fold + misc surface completion
# (reference: nn/functional/{pooling,conv,common,loss,extension}.py)
# ---------------------------------------------------------------------------
def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    return apply(
        _nn.avg_pool1d, x, kernel_size=_t(kernel_size), stride=_t(stride),
        padding=_t(padding), ceil_mode=ceil_mode, exclusive=exclusive,
        op_name="avg_pool1d",
    )


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW",
               name=None):
    return apply(
        _nn.avg_pool3d, x, kernel_size=_t(kernel_size), stride=_t(stride),
        padding=_t(padding), ceil_mode=ceil_mode, exclusive=exclusive,
        divisor_override=divisor_override, data_format=data_format,
        op_name="avg_pool3d",
    )


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW", name=None):
    if return_mask:
        raise NotImplementedError(
            "max_pool3d(return_mask=True): 3-D argmax masks are not "
            "implemented; use max_pool2d(return_mask=True) per-slice"
        )
    return apply(
        _nn.max_pool3d, x, kernel_size=_t(kernel_size), stride=_t(stride),
        padding=_t(padding), ceil_mode=ceil_mode, data_format=data_format,
        op_name="max_pool3d",
    )


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    return apply(
        _nn.adaptive_avg_pool3d, x, output_size=_t(output_size),
        data_format=data_format, op_name="adaptive_avg_pool3d",
    )


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    if return_mask:
        raise NotImplementedError("adaptive_max_pool1d(return_mask=True)")
    return apply(
        _nn.adaptive_max_pool1d, x, output_size=_t(output_size),
        op_name="adaptive_max_pool1d",
    )


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    if return_mask:
        raise NotImplementedError("adaptive_max_pool2d(return_mask=True)")
    return apply(
        _nn.adaptive_max_pool2d, x, output_size=_t(output_size),
        op_name="adaptive_max_pool2d",
    )


def adaptive_max_pool3d(x, output_size, return_mask=False, name=None):
    if return_mask:
        raise NotImplementedError("adaptive_max_pool3d(return_mask=True)")
    return apply(
        _nn.adaptive_max_pool3d, x, output_size=_t(output_size),
        op_name="adaptive_max_pool3d",
    )


def max_unpool1d(x, indices, kernel_size, stride=None, padding=0,
                 output_size=None, data_format="NCL", name=None):
    if data_format != "NCL":
        raise ValueError(
            f"max_unpool1d supports NCL only (reference unpool kernel "
            f"layout), got {data_format}"
        )
    return apply(
        _nn.max_unpool1d, x, indices, kernel_size=_t(kernel_size),
        stride=_t(stride), padding=_t(padding),
        output_size=None if output_size is None else tuple(output_size),
        op_name="max_unpool1d",
    )


def max_unpool3d(x, indices, kernel_size, stride=None, padding=0,
                 output_size=None, data_format="NCDHW", name=None):
    if data_format != "NCDHW":
        raise ValueError(
            f"max_unpool3d supports NCDHW only (reference unpool kernel "
            f"layout), got {data_format}"
        )
    return apply(
        _nn.max_unpool3d, x, indices, kernel_size=_t(kernel_size),
        stride=_t(stride), padding=_t(padding),
        output_size=None if output_size is None else tuple(output_size),
        op_name="max_unpool3d",
    )


def _transpose_out_padding(output_size, in_spatial, k, stride, padding,
                           dilation, output_padding, nd):
    """Derive output_padding from a requested output_size (reference:
    conv_transpose output_size semantics: out = (in-1)*s - 2p + d*(k-1) + 1
    + output_padding, with 0 <= output_padding < stride)."""
    def tup(v):
        return tuple(v) if isinstance(v, (tuple, list)) else (v,) * nd

    if output_size is None:
        return _t(output_padding)
    if hasattr(output_size, "numpy"):
        output_size = [int(v) for v in output_size.numpy()]
    want = tuple(int(v) for v in output_size)[-nd:]
    s, p, d = tup(stride), tup(padding), tup(dilation)
    out_pad = []
    for i in range(nd):
        base = (in_spatial[i] - 1) * s[i] - 2 * p[i] + d[i] * (k[i] - 1) + 1
        extra = want[i] - base
        # valid range mirrors the reference: 0 <= output_padding < max(s, d)
        if not (0 <= extra < max(s[i], d[i], 1)):
            raise ValueError(
                f"output_size {want} unreachable from input spatial "
                f"{tuple(in_spatial)} (base {base}, stride {s[i]})"
            )
        out_pad.append(extra)
    return tuple(out_pad)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    if output_size is not None:
        output_padding = _transpose_out_padding(
            output_size, (x.shape[2] if data_format == "NCL" else x.shape[1],),
            (weight.shape[-1],), stride, padding, dilation, output_padding, 1,
        )
    return apply(
        _nn.conv1d_transpose, x, weight, bias, stride=_t(stride),
        padding=_t(padding), output_padding=_t(output_padding),
        dilation=_t(dilation), groups=groups, data_format=data_format,
        op_name="conv1d_transpose",
    )


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    if output_size is not None:
        spatial = (
            tuple(x.shape[2:5]) if data_format == "NCDHW" else tuple(x.shape[1:4])
        )
        output_padding = _transpose_out_padding(
            output_size, spatial, tuple(weight.shape[-3:]), stride, padding,
            dilation, output_padding, 3,
        )
    return apply(
        _nn.conv3d_transpose, x, weight, bias, stride=_t(stride),
        padding=_t(padding), output_padding=_t(output_padding),
        dilation=_t(dilation), groups=groups, data_format=data_format,
        op_name="conv3d_transpose",
    )


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    return apply(
        _nn.fold, x, output_sizes=_t(output_sizes),
        kernel_sizes=_t(kernel_sizes), strides=_t(strides),
        paddings=_t(paddings), dilations=_t(dilations), op_name="fold",
    )


def diag_embed(x, offset=0, dim1=-2, dim2=-1, name=None):
    return apply(
        _nn.diag_embed, x, offset=offset, dim1=dim1, dim2=dim2,
        op_name="diag_embed",
    )


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    if maxlen is None:
        maxlen = int(np.asarray(x.numpy()).max())
    return apply(
        _nn.sequence_mask, x, maxlen=int(maxlen), dtype=str(dtype),
        differentiable=False, op_name="sequence_mask",
    )


def gather_tree(ids, parents):
    return apply(_nn.gather_tree, ids, parents, differentiable=False,
                 op_name="gather_tree")


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None,
                   data_format="NCHW"):
    return apply(
        _nn.temporal_shift, x, seg_num=int(seg_num),
        shift_ratio=float(shift_ratio), data_format=data_format,
        op_name="temporal_shift",
    )


def affine_grid(theta, out_shape, align_corners=True, name=None):
    if hasattr(out_shape, "numpy"):
        out_shape = [int(v) for v in out_shape.numpy()]
    return apply(
        _nn.affine_grid, theta, out_shape=tuple(int(v) for v in out_shape),
        align_corners=align_corners, op_name="affine_grid",
    )


def bilinear(x1, x2, weight, bias=None, name=None):
    return apply(_nn.bilinear, x1, x2, weight, bias, op_name="bilinear")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    return apply(
        _nn.pixel_unshuffle, x, downscale_factor=int(downscale_factor),
        data_format=data_format, op_name="pixel_unshuffle",
    )


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    """Drop whole 3-D channel volumes (reference: nn/functional/common.py
    dropout3d)."""
    if not training or p == 0.0:
        return x
    import jax
    import jax.numpy as jnp

    def _d3(v, key, *, p, data_format):
        if data_format == "NCDHW":
            shape = (v.shape[0], v.shape[1], 1, 1, 1)
        else:
            shape = (v.shape[0], 1, 1, 1, v.shape[4])
        keep = jax.random.bernoulli(key, 1.0 - p, shape)
        return jnp.where(keep, v / (1.0 - p), 0.0).astype(v.dtype)

    return apply(
        _d3, x, _random.next_key(), p=float(p), data_format=data_format,
        op_name="dropout3d",
    )


def zeropad2d(x, padding, data_format="NCHW", name=None):
    return pad(x, padding, mode="constant", value=0.0,
               data_format=data_format)


# in-place activation variants (reference: *_ in nn/functional/activation.py)
def _make_inplace(fn):
    def inner(x, *args, **kwargs):
        out = fn(x, *args, **kwargs)
        x._value = out._value
        if out._grad_node is not None:
            x._grad_node = out._grad_node
            x._out_index = out._out_index
            x.stop_gradient = out.stop_gradient
        x._bump_version()
        return x

    return inner


elu_ = _make_inplace(elu)
tanh_ = _make_inplace(tanh)
softmax_ = _make_inplace(softmax)


# losses
def square_error_cost(input, label):
    return apply(_nn.square_error_cost, input, label,
                 op_name="square_error_cost")


def log_loss(input, label, epsilon=1e-4, name=None):
    return apply(_nn.log_loss, input, label, epsilon=float(epsilon),
                 op_name="log_loss")


def dice_loss(input, label, epsilon=1e-5, name=None):
    return apply(_nn.dice_loss, input, label, epsilon=float(epsilon),
                 op_name="dice_loss")


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    return apply(_nn.npair_loss, anchor, positive, labels,
                 l2_reg=float(l2_reg), op_name="npair_loss")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC loss over [T, B, C] logits (reference: nn/functional/loss.py
    ctc_loss → warpctc, which softmaxes internally — so raw logits in)."""
    lp = log_softmax(log_probs, axis=-1)
    loss = apply(
        _nn.ctc_loss_per_sample, lp, labels, input_lengths, label_lengths,
        blank=int(blank), op_name="ctc_loss",
    )
    if norm_by_times:
        loss = loss / input_lengths.astype(loss.dtype)
    if reduction == "mean":
        # reference divides each sample by its label length before averaging
        denom = label_lengths.astype(loss.dtype).clip(min=1.0)
        return (loss / denom).mean()
    if reduction == "sum":
        return loss.sum()
    return loss


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    return apply(
        _nn.hsigmoid_loss_op, input, label, weight, bias,
        path_table, path_code, num_classes=int(num_classes),
        op_name="hsigmoid_loss",
    )


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean"):
    loss, sm = apply(
        _nn.margin_cross_entropy_op, logits, label, margin1=float(margin1),
        margin2=float(margin2), margin3=float(margin3), scale=float(scale),
        op_name="margin_cross_entropy",
    )
    if reduction == "mean":
        loss = loss.mean()
    elif reduction == "sum":
        loss = loss.sum()
    return (loss, sm) if return_softmax else loss


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    if key_padding_mask is not None or attn_mask is not None:
        raise NotImplementedError(
            "sparse_attention masks beyond the CSR pattern"
        )
    return apply(
        _nn.sparse_attention_op, query, key, value, sparse_csr_offset,
        sparse_csr_columns, op_name="sparse_attention",
    )


def class_center_sample(label, num_classes, num_samples, group=None):
    """Sample negative class centers (reference:
    operators/class_center_sample_op.cu): keep all positive classes, fill
    with sampled negatives up to num_samples; remap labels into the sampled
    index space. Data-dependent sizes → host-side op."""
    lab = np.asarray(label.numpy()).reshape(-1)
    pos = np.unique(lab)
    rest = num_samples - len(pos)
    if rest > 0:
        import jax as _jax

        neg_pool = np.setdiff1d(np.arange(num_classes), pos)
        # draw through the framework generator so paddle.seed reproduces runs
        seed = int(_jax.random.randint(_random.next_key(), (), 0, 2**31 - 1))
        rng = np.random.default_rng(seed)
        sampled = np.concatenate([pos, rng.permutation(neg_pool)[:rest]])
    else:
        sampled = pos
    remap = np.full(num_classes, -1, np.int64)
    remap[sampled] = np.arange(len(sampled))
    return to_tensor(remap[lab]), to_tensor(sampled.astype(np.int64))


__all__ = [n for n in dir() if not n.startswith("_")]
