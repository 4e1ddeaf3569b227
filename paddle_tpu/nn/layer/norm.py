"""Normalization layers. Reference: python/paddle/nn/layer/norm.py."""
from __future__ import annotations

from ...core.tensor import Tensor
from .. import functional as F
from .. import initializer as I
from ..layer_base import Layer


class _BatchNormBase(Layer):
    def __init__(
        self, num_features, momentum=0.9, epsilon=1e-05, weight_attr=None,
        bias_attr=None, data_format="NCHW", use_global_stats=None, name=None,
    ):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        self.weight = (
            None if weight_attr is False
            else self.create_parameter(
                shape=[num_features], attr=weight_attr,
                default_initializer=I.Constant(1.0),
            )
        )
        self.bias = (
            None if bias_attr is False
            else self.create_parameter(shape=[num_features], attr=bias_attr, is_bias=True)
        )
        self.register_buffer("_mean", Tensor([0.0] * num_features, dtype="float32"))
        self.register_buffer("_variance", Tensor([1.0] * num_features, dtype="float32"))

    def forward(self, x):
        return F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self._momentum, epsilon=self._epsilon,
            data_format=self._data_format, use_global_stats=self._use_global_stats,
        )

    def extra_repr(self):
        return f"num_features={self._num_features}, momentum={self._momentum}"


class BatchNorm1D(_BatchNormBase):
    pass


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    pass


class BatchNorm(_BatchNormBase):
    """fluid-era BatchNorm (reference: fluid/dygraph/nn.py BatchNorm)."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-05, **kw):
        super().__init__(num_channels, momentum, epsilon)
        self._act = act

    def forward(self, x):
        out = super().forward(x)
        if self._act:
            out = getattr(F, self._act)(out)
        return out


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica BN. On TPU the mean/var allreduce happens automatically
    when the train step is compiled over a data-sharded mesh (XLA inserts the
    collective); eager single-process falls back to local stats.
    Reference: python/paddle/nn/layer/norm.py SyncBatchNorm."""

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        out = layer
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, cls):
            out = cls(layer._num_features, layer._momentum, layer._epsilon)
            if layer.weight is not None:
                out.weight.set_value(layer.weight)
                out.bias.set_value(layer.bias)
            out._mean.set_value(layer._mean)
            out._variance.set_value(layer._variance)
        for name, sub in list(layer._sub_layers.items()):
            out._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return out


class RMSNorm(Layer):
    """x / rms(x) * gain over the last axis (statistics in float32).
    ``zero_centered``: the parameter is stored round 0 and the gain is
    ``1 + weight``."""

    def __init__(self, hidden_size, epsilon=1e-6, zero_centered=False,
                 weight_attr=None, name=None):
        super().__init__()
        self._epsilon = epsilon
        self._zero_centered = zero_centered
        self.weight = self.create_parameter(
            shape=[hidden_size], attr=weight_attr,
            default_initializer=I.Constant(0.0 if zero_centered else 1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self._epsilon, self._zero_centered)


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        self.weight = (
            None if weight_attr is False
            else self.create_parameter(
                shape=self._normalized_shape, attr=weight_attr,
                default_initializer=I.Constant(1.0),
            )
        )
        self.bias = (
            None if bias_attr is False
            else self.create_parameter(
                shape=self._normalized_shape, attr=bias_attr, is_bias=True
            )
        )

    def forward(self, x):
        return F.layer_norm(
            x, self._normalized_shape, self.weight, self.bias, self._epsilon
        )

    def extra_repr(self):
        return f"normalized_shape={self._normalized_shape}, epsilon={self._epsilon}"


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._num_groups = num_groups
        self._num_channels = num_channels
        self._epsilon = epsilon
        self._data_format = data_format
        self.weight = (
            None if weight_attr is False
            else self.create_parameter(
                shape=[num_channels], attr=weight_attr,
                default_initializer=I.Constant(1.0),
            )
        )
        self.bias = (
            None if bias_attr is False
            else self.create_parameter(shape=[num_channels], attr=bias_attr, is_bias=True)
        )

    def forward(self, x):
        return F.group_norm(
            x, self._num_groups, self._epsilon, self.weight, self.bias,
            self._data_format,
        )


class InstanceNorm2D(Layer):
    def __init__(self, num_features, epsilon=1e-05, momentum=0.9, weight_attr=None,
                 bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._epsilon = epsilon
        self.scale = (
            None if weight_attr is False
            else self.create_parameter(
                shape=[num_features], attr=weight_attr,
                default_initializer=I.Constant(1.0),
            )
        )
        self.bias = (
            None if bias_attr is False
            else self.create_parameter(shape=[num_features], attr=bias_attr, is_bias=True)
        )

    def forward(self, x):
        return F.instance_norm(
            x, weight=self.scale, bias=self.bias, eps=self._epsilon
        )


InstanceNorm1D = InstanceNorm2D
InstanceNorm3D = InstanceNorm2D


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta, self.k)


class SpectralNorm(Layer):
    """reference: nn/layer/norm.py SpectralNorm (phi spectral_norm kernel) —
    normalize `weight` by its largest singular value, estimated with
    `power_iters` rounds of power iteration on persistent u/v vectors."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12, name=None):
        super().__init__()
        self.dim = dim
        self.power_iters = power_iters
        self.eps = eps
        self._shape = list(weight_shape)
        h = self._shape[dim]
        w = 1
        for i, s in enumerate(self._shape):
            if i != dim:
                w *= s
        import paddle_tpu as _paddle

        # persistent estimation state, refined every forward (reference keeps
        # u/v as non-trainable persistables updated in place) — seeded from
        # the global generator so paddle.seed governs it
        self.register_buffer("weight_u", _paddle.randn([h]))
        self.register_buffer("weight_v", _paddle.randn([w]))

    def forward(self, weight):
        import jax
        import jax.numpy as jnp

        from ...core.dispatch import apply, no_grad
        from ...core.tensor import Tensor

        def f(wt, u, v, dim, power_iters, eps):
            mat = jnp.moveaxis(wt, dim, 0).reshape(wt.shape[dim], -1)
            for _ in range(power_iters):
                v = mat.T @ u
                v = v / (jnp.linalg.norm(v) + eps)
                u = mat @ v
                u = u / (jnp.linalg.norm(u) + eps)
            sigma = u @ mat @ v
            return wt / sigma, jax.lax.stop_gradient(u), jax.lax.stop_gradient(v)

        out, u, v = apply(
            f, weight, self.weight_u, self.weight_v, dim=self.dim,
            power_iters=self.power_iters, eps=self.eps, op_name="spectral_norm",
        )
        # refine the persistent estimate so sigma converges across forwards
        with no_grad():
            self.weight_u._value = u._value
            self.weight_v._value = v._value
        return out
