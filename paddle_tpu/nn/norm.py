"""Norm layers.

Parity surface: paddle.nn.BatchNorm1D/2D/3D, LayerNorm, GroupNorm,
InstanceNorm, SyncBatchNorm, SpectralNorm, LocalResponseNorm
(reference: python/paddle/nn/layer/norm.py over operators/batch_norm_op.*).

BatchNorm running stats are ``Buffer``s; in eager training mode the layer
assigns the updated stats back into its buffers, and under
``functional_call(..., return_buffers=True)`` the updates are captured
functionally (no side effects leak into a jit trace).

SyncBatchNorm: cross-replica stats via a mesh-axis psum when called inside
shard_map/pjit with a data axis present — the TPU-native equivalent of the
reference's sync_batch_norm_op.cu (NCCL allreduce of partial sums).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import functional as F
from . import initializer as I
from .layer_base import Layer

__all__ = [
    "BatchNorm", "BatchNorm1D", "BatchNorm2D", "BatchNorm3D", "SyncBatchNorm",
    "LayerNorm", "RMSNorm", "GroupNorm", "InstanceNorm1D", "InstanceNorm2D",
    "InstanceNorm3D", "LocalResponseNorm", "SpectralNorm",
]


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self.num_features = num_features
        self.momentum = momentum
        self.epsilon = epsilon
        self.data_format = data_format
        self.use_global_stats = use_global_stats
        if weight_attr is not False:
            self.weight = self.create_parameter(
                (num_features,), attr=weight_attr, default_initializer=I.Constant(1.0))
        else:
            self.weight = None
        if bias_attr is not False:
            self.bias = self.create_parameter((num_features,), attr=bias_attr, is_bias=True)
        else:
            self.bias = None
        self.register_buffer("_mean", jnp.zeros((num_features,), jnp.float32))
        self.register_buffer("_variance", jnp.ones((num_features,), jnp.float32))

    def forward(self, x):
        out = F.batch_norm(
            x, self._mean.value, self._variance.value,
            self.weight.value if self.weight is not None else None,
            self.bias.value if self.bias is not None else None,
            training=self.training, momentum=self.momentum, epsilon=self.epsilon,
            data_format=self.data_format, use_global_stats=self.use_global_stats)
        if isinstance(out, tuple):
            out, new_mean, new_var = out
            self._mean.value = new_mean
            self._variance.value = new_var
        return out


class BatchNorm(_BatchNormBase):
    """Legacy fluid.dygraph.BatchNorm parity (accepts act=None)."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-5,
                 data_format="NCHW", **kwargs):
        super().__init__(num_channels, momentum=momentum, epsilon=epsilon,
                         data_format=data_format)
        self._act = act

    def forward(self, x):
        out = super().forward(x)
        if self._act == "relu":
            out = F.relu(out)
        elif self._act:
            out = getattr(F, self._act)(out)
        return out


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format="NCL", use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr, bias_attr,
                         data_format, use_global_stats, name)


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format="NCDHW", use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr, bias_attr,
                         data_format, use_global_stats, name)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica BN (ref: operators/sync_batch_norm_op.cu — NCCL partial
    sums across ranks).

    TPU-native semantics — two regimes:

    * **GSPMD (jit / the fleet path)**: the batch dim is *sharded*, not
      per-replica, so ``jnp.mean`` over it already IS the global-batch mean
      (XLA inserts the cross-chip reduction).  No collective is emitted
      here — the sync the reference needed NCCL for is the compiler's job.
    * **shard_map (manual code)**: each program instance sees its local
      shard, so the partial moments are ``lax.pmean``-ed over whichever
      data axes are bound (default: ``data``/``sharding``; override with
      ``axis_name=`` for custom meshes).
    """

    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format="NCHW", name=None, axis_name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr, bias_attr,
                         data_format, None, name)
        self.axis_name = axis_name

    def _sync_axes(self):
        """Mapped axes to reduce over: the bound subset of the defaults, or
        the user's explicit axis_name (which must be bound)."""
        if self.axis_name is None:
            candidates = ("data", "sharding")
            explicit = False
        else:
            candidates = ((self.axis_name,) if isinstance(self.axis_name, str)
                          else tuple(self.axis_name))
            explicit = True
        bound = []
        for a in candidates:
            try:
                jax.lax.axis_size(a)
                bound.append(a)
            except NameError:
                if explicit:
                    from ..framework.errors import InvalidArgumentError

                    raise InvalidArgumentError(
                        f"SyncBatchNorm(axis_name={self.axis_name!r}): axis "
                        f"{a!r} is not bound here — it only names shard_map "
                        f"axes; under plain jit the batch mean is already "
                        f"global (leave axis_name unset)")
        return tuple(bound)

    def forward(self, x):
        x = jnp.asarray(x)
        if not self.training:
            return super().forward(x)
        ch_axis = x.ndim - 1 if self.data_format in ("NHWC", "NLC", "NDHWC") else 1
        axes = tuple(i for i in range(x.ndim) if i != ch_axis)
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axes)
        meansq = jnp.mean(jnp.square(xf), axis=axes)
        sync = self._sync_axes()
        if sync:
            mean = jax.lax.pmean(mean, sync)
            meansq = jax.lax.pmean(meansq, sync)
        var = meansq - jnp.square(mean)
        new_mean = self.momentum * self._mean.value + (1 - self.momentum) * mean
        new_var = self.momentum * self._variance.value + (1 - self.momentum) * var
        self._mean.value = new_mean
        self._variance.value = new_var
        shape = [1] * x.ndim
        shape[ch_axis] = x.shape[ch_axis]
        inv = jax.lax.rsqrt(var + self.epsilon)
        out = (xf - mean.reshape(shape)) * inv.reshape(shape)
        if self.weight is not None:
            out = out * self.weight.value.reshape(shape)
        if self.bias is not None:
            out = out + self.bias.value.reshape(shape)
        return out.astype(x.dtype)

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        """Parity: paddle.nn.SyncBatchNorm.convert_sync_batchnorm."""
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, SyncBatchNorm):
            new = cls(layer.num_features, layer.momentum, layer.epsilon,
                      data_format=layer.data_format)
            new.set_state_dict(layer.state_dict())
            return new
        for name, sub in list(layer._sub_layers.items()):
            layer._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return layer


class LayerNorm(Layer):
    """Parity: paddle.nn.LayerNorm (ref: operators/layer_norm_op.cu)."""

    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.epsilon = epsilon
        if weight_attr is not False:
            self.weight = self.create_parameter(
                self.normalized_shape, attr=weight_attr, default_initializer=I.Constant(1.0))
        else:
            self.weight = None
        if bias_attr is not False:
            self.bias = self.create_parameter(self.normalized_shape, attr=bias_attr, is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        return F.layer_norm(x, self.normalized_shape,
                            self.weight.value if self.weight is not None else None,
                            self.bias.value if self.bias is not None else None,
                            self.epsilon)


class RMSNorm(Layer):
    """``x / sqrt(mean(x^2) + eps) * weight`` over the last axis, computed
    in float32 and returned in ``x``'s dtype (Zhang & Sennrich 2019): no
    mean subtraction, no bias.  ``zero_centered``: the stored weight is the
    gain's distance from one, ``* (1 + weight)``, initialised to 0."""

    def __init__(self, size, epsilon=1e-6, dtype=None, zero_centered=False):
        super().__init__()
        self.epsilon = epsilon
        self.zero_centered = bool(zero_centered)
        self.weight = self.create_parameter(
            (int(size),), dtype=dtype, default_initializer=I.Constant(
                0.0 if zero_centered else 1.0))

    def forward(self, x):
        x = jnp.asarray(x)
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.epsilon)
        w = self.weight.value.astype(jnp.float32)
        return (y * (1.0 + w if self.zero_centered else w)).astype(x.dtype)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self.num_groups = num_groups
        self.num_channels = num_channels
        self.epsilon = epsilon
        self.data_format = data_format
        if weight_attr is not False:
            self.weight = self.create_parameter(
                (num_channels,), attr=weight_attr, default_initializer=I.Constant(1.0))
        else:
            self.weight = None
        if bias_attr is not False:
            self.bias = self.create_parameter((num_channels,), attr=bias_attr, is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        return F.group_norm(x, self.num_groups, self.epsilon,
                            self.weight.value if self.weight is not None else None,
                            self.bias.value if self.bias is not None else None,
                            self.data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9, weight_attr=None,
                 bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self.num_features = num_features
        self.epsilon = epsilon
        self.data_format = data_format
        if weight_attr is not False:
            self.weight = self.create_parameter(
                (num_features,), attr=weight_attr, default_initializer=I.Constant(1.0))
        else:
            self.weight = None
        if bias_attr is not False:
            self.bias = self.create_parameter((num_features,), attr=bias_attr, is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight.value if self.weight is not None else None,
                               bias=self.bias.value if self.bias is not None else None,
                               eps=self.epsilon, data_format=self.data_format)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
        super().__init__()
        self.args = (size, alpha, beta, k, data_format)

    def forward(self, x):
        return F.local_response_norm(x, *self.args)


class SpectralNorm(Layer):
    """Parity: paddle.nn.SpectralNorm (ref: operators/spectral_norm_op.cc) —
    power-iteration estimate of the largest singular value."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12, name=None):
        super().__init__()
        self.dim = dim
        self.power_iters = power_iters
        self.eps = eps
        h = weight_shape[dim]
        w = 1
        for i, s in enumerate(weight_shape):
            if i != dim:
                w *= s
        self.register_buffer("weight_u", jax.random.normal(
            jax.random.PRNGKey(0), (h,), jnp.float32), persistable=False)
        self.register_buffer("weight_v", jax.random.normal(
            jax.random.PRNGKey(1), (w,), jnp.float32), persistable=False)

    def forward(self, weight):
        weight = jnp.asarray(weight)
        mat = jnp.moveaxis(weight, self.dim, 0).reshape(weight.shape[self.dim], -1)
        u, v = self.weight_u.value, self.weight_v.value
        for _ in range(self.power_iters):
            v = mat.T @ u
            v = v / (jnp.linalg.norm(v) + self.eps)
            u = mat @ v
            u = u / (jnp.linalg.norm(u) + self.eps)
        sigma = u @ mat @ v
        self.weight_u.value = jax.lax.stop_gradient(u)
        self.weight_v.value = jax.lax.stop_gradient(v)
        return weight / sigma
