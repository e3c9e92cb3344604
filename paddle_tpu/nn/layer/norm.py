"""Normalization layers — reference python/paddle/nn/layer/norm.py."""
import jax
import jax.numpy as jnp

from ...framework.core import Tensor
from .. import functional as F
from ..initializer import Constant
from ..layer_base import Layer
from ..layout import resolve_data_format as _resolve_df

__all__ = [
    "LayerNorm", "RMSNorm", "BatchNorm", "BatchNorm1D", "BatchNorm2D",
    "BatchNorm3D", "SyncBatchNorm", "GroupNorm", "InstanceNorm1D",
    "InstanceNorm2D", "InstanceNorm3D", "LocalResponseNorm", "SpectralNorm",
]


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        if weight_attr is not False:
            self.weight = self.create_parameter(
                self._normalized_shape, attr=weight_attr, default_initializer=Constant(1.0))
        else:
            self.weight = None
        if bias_attr is not False:
            self.bias = self.create_parameter(
                self._normalized_shape, attr=bias_attr, is_bias=True)
        else:
            self.bias = None

    def forward(self, input):
        with jax.named_scope("layer_norm"):
            return F.layer_norm(input, self._normalized_shape, self.weight,
                                self.bias, self._epsilon)

    def extra_repr(self):
        return f"normalized_shape={self._normalized_shape}, epsilon={self._epsilon}"


class RMSNorm(Layer):
    """TPU-native extra (matches incubate fused_rms_norm in newer paddle)."""

    def __init__(self, hidden_size, epsilon=1e-6, weight_attr=None, name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            [hidden_size], attr=weight_attr, default_initializer=Constant(1.0))

    def forward(self, input):
        return F.rms_norm(input, self.weight, self._epsilon)


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, data_format=None, use_global_stats=None, name=None):
        data_format = _resolve_df(data_format, 2)
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        self.weight = None if weight_attr is False else self.create_parameter(
            [num_features], attr=weight_attr, default_initializer=Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            [num_features], attr=bias_attr, is_bias=True)
        self.register_buffer("_mean", Tensor(jnp.zeros([num_features], jnp.float32)))
        self.register_buffer("_variance", Tensor(jnp.ones([num_features], jnp.float32)))

    def forward(self, input):
        return F.batch_norm(
            input, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self._momentum, epsilon=self._epsilon,
            data_format=self._data_format, use_global_stats=self._use_global_stats)

    def extra_repr(self):
        return f"num_features={self._num_features}, momentum={self._momentum}, epsilon={self._epsilon}"


class BatchNorm(_BatchNormBase):
    """Legacy fluid-style BatchNorm(num_channels) — acts like BatchNorm2D."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-05,
                 param_attr=None, bias_attr=None, data_layout="NCHW",
                 use_global_stats=None, **kwargs):
        super().__init__(num_channels, momentum, epsilon, param_attr, bias_attr,
                         data_layout, use_global_stats)
        self._act = act

    def forward(self, input):
        out = super().forward(input)
        if self._act:
            out = getattr(F, self._act)(out)
        return out


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, data_format=None, use_global_stats=None, name=None):
        data_format = _resolve_df(data_format, 1)
        super().__init__(num_features, momentum, epsilon, weight_attr, bias_attr,
                         "NCHW" if data_format == "NCL" else "NHWC", use_global_stats)


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, data_format=None, use_global_stats=None, name=None):
        data_format = _resolve_df(data_format, 3)
        super().__init__(num_features, momentum, epsilon, weight_attr, bias_attr,
                         "NCHW" if data_format == "NCDHW" else "NHWC", use_global_stats)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica batchnorm. Inside shard_map/pmap the mean/var reduce over
    the 'dp' mesh axis (XLA psum); single-device it equals BatchNorm."""

    def forward(self, input):
        from ...distributed import in_shard_map, get_data_parallel_axis
        axis = get_data_parallel_axis() if in_shard_map() else None
        if axis is None:
            return super().forward(input)
        import jax

        def _f(v, rm, rv, w, b):
            ax = 1 if self._data_format.startswith("NC") else v.ndim - 1
            reduce_axes = tuple(i for i in range(v.ndim) if i != ax)
            x32 = v.astype(jnp.float32)
            cnt = jax.lax.psum(jnp.asarray(
                float(jnp.prod(jnp.asarray([v.shape[i] for i in reduce_axes])))), axis)
            mean = jax.lax.psum(jnp.sum(x32, axis=reduce_axes), axis) / cnt
            var = jax.lax.psum(jnp.sum(jnp.square(x32), axis=reduce_axes), axis) / cnt \
                - jnp.square(mean)
            shape = [1] * v.ndim
            shape[ax] = v.shape[ax]
            out = (x32 - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + self._epsilon)
            out = out.astype(v.dtype)
            if w is not None:
                out = out * w.reshape(shape).astype(v.dtype)
            if b is not None:
                out = out + b.reshape(shape).astype(v.dtype)
            return out
        from ...framework.core import apply_op
        return apply_op(_f, input, self._mean, self._variance, self.weight, self.bias)

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, SyncBatchNorm):
            new = SyncBatchNorm(layer._num_features, layer._momentum, layer._epsilon,
                                data_format=layer._data_format)
            new.weight, new.bias = layer.weight, layer.bias
            new._buffers.update(layer._buffers)
            return new
        for name, sub in list(layer._sub_layers.items()):
            layer._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return layer


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, data_format=None, name=None):
        data_format = _resolve_df(data_format, 2)
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self._data_format = data_format
        self.weight = None if weight_attr is False else self.create_parameter(
            [num_channels], attr=weight_attr, default_initializer=Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            [num_channels], attr=bias_attr, is_bias=True)

    def forward(self, input):
        return F.group_norm(input, self._num_groups, self._epsilon, self.weight,
                            self.bias, self._data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-05, momentum=0.9, weight_attr=None,
                 bias_attr=None, data_format=None, name=None):
        data_format = _resolve_df(data_format, 2)
        super().__init__()
        self._epsilon = epsilon
        self._data_format = data_format
        if weight_attr is False:
            self.scale = None
        else:
            self.scale = self.create_parameter(
                [num_features], attr=weight_attr, default_initializer=Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            [num_features], attr=bias_attr, is_bias=True)

    def forward(self, input):
        return F.instance_norm(input, weight=self.scale, bias=self.bias,
                               eps=self._epsilon, data_format=self._data_format)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=0.0001, beta=0.75, k=1.0, data_format=None, name=None):
        data_format = _resolve_df(data_format, 2)
        super().__init__()
        self.args = (size, alpha, beta, k, data_format)

    def forward(self, input):
        return F.local_response_norm(input, *self.args)


class SpectralNorm(Layer):
    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12, dtype="float32"):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._eps = eps
        import numpy as np
        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        from ..initializer import Normal
        self.weight_u = self.create_parameter([h], default_initializer=Normal(0.0, 1.0))
        self.weight_v = self.create_parameter([w], default_initializer=Normal(0.0, 1.0))
        self.weight_u.stop_gradient = True
        self.weight_v.stop_gradient = True

    def forward(self, weight):
        from ...framework.core import apply_op
        import jax

        def _f(w, u, v):
            mat = jnp.moveaxis(w, self._dim, 0).reshape(w.shape[self._dim], -1)
            for _ in range(self._power_iters):
                v = mat.T @ u
                v = v / (jnp.linalg.norm(v) + self._eps)
                u = mat @ v
                u = u / (jnp.linalg.norm(u) + self._eps)
            sigma = u @ mat @ v
            return w / sigma
        return apply_op(_f, weight, self.weight_u, self.weight_v)
