"""Normalization layers (SURVEY §2.5: BatchNormalization,
SpatialBatchNormalization, SpatialCrossMapLRN, SpatialWithinChannelLRN,
SpatialContrastiveNormalization, SpatialDivisiveNormalization,
SpatialSubtractiveNormalization, Normalize) plus Dropout and L1Penalty
(grouped with the reference's "Regularization" rows).

BatchNorm running statistics are module *buffers*: the functional training
step carries them in the state pytree and they advance under jit
(``functional_call`` returns the updated state) — the JAX re-design of the
reference's in-place ``runningMean``/``runningVar`` updates.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.nn.module import Module, Parameter
from bigdl_tpu.utils.rng import next_rng_id, require_rng

__all__ = [
    "BatchNormalization", "SpatialBatchNormalization", "SpatialCrossMapLRN",
    "SpatialWithinChannelLRN", "SpatialContrastiveNormalization",
    "SpatialDivisiveNormalization", "SpatialSubtractiveNormalization",
    "Normalize", "Dropout", "L1Penalty", "RMSNorm",
]


def _bn_reduce_count(x, axes):
    n = 1
    for a in axes:
        n *= x.shape[a]
    return n


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bn_train_apply(axes, eps, x, weight, bias):
    """Fused training batch-norm with hand-written VJP.

    TPU profile finding (round 5, ResNet-50): the autodiff of the naive
    ``mean``/``var`` two-pass form lowered to a pile of per-channel
    reduce fusions with bf16 accumulators at ~25% of the train step.
    This version makes the minimum number of passes — ONE fused
    sum/sum-of-squares read forward (f32 accumulation), ONE fused
    dbeta/dgamma read backward, and the standard fused dx formula — and
    keeps every reduction in f32.  Semantics follow
    ``nn/BatchNormalization.scala:269`` (biased var for normalization).

    Returns ``(out, mean, var)``; mean/var are f32 for the caller's
    running-stat buffers (stop-gradient them — their cotangents are
    ignored by the VJP, which is correct only for buffer use)."""
    out, mean, var, _ = _bn_train_fwd_impl(axes, eps, x, weight, bias)
    return out, mean, var


def _bn_train_fwd_impl(axes, eps, x, weight, bias):
    n = _bn_reduce_count(x, axes)
    s1 = jnp.sum(x, axis=axes, dtype=jnp.float32)
    # the f32 convert fuses into the reduce read (no materialized copy);
    # squaring in bf16 would cost ~3 mantissa bits on the stats
    s2 = jnp.sum(lax.square(x.astype(jnp.float32)), axis=axes,
                 dtype=jnp.float32)
    mean = s1 / n
    var = jnp.maximum(s2 / n - lax.square(mean), 0.0)
    inv = lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    for a in range(x.ndim):
        if a not in axes:
            shape[a] = x.shape[a]
    scale = (inv * weight).reshape(shape).astype(x.dtype)
    shift = (bias - mean * inv * weight).reshape(shape).astype(x.dtype)
    out = x * scale + shift
    return out, mean, var, inv


def _bn_train_vjp_fwd(axes, eps, x, weight, bias):
    out, mean, var, inv = _bn_train_fwd_impl(axes, eps, x, weight, bias)
    return (out, mean, var), (x, weight, mean, inv)


def _bn_train_vjp_bwd(axes, eps, res, cotangents):
    gy, _gmean, _gvar = cotangents  # stat cotangents: buffer-only outputs
    x, weight, mean, inv = res
    n = _bn_reduce_count(x, axes)
    shape = [1] * x.ndim
    for a in range(x.ndim):
        if a not in axes:
            shape[a] = x.shape[a]
    gy32 = gy.astype(jnp.float32)
    xhat32 = (x.astype(jnp.float32) - mean.reshape(shape)) \
        * inv.reshape(shape)
    dbeta = jnp.sum(gy32, axis=axes, dtype=jnp.float32)
    dgamma = jnp.sum(gy32 * xhat32, axis=axes, dtype=jnp.float32)
    k = (weight * inv).reshape(shape)
    dx = (k * (gy32 - (dbeta / n).reshape(shape)
               - xhat32 * (dgamma / n).reshape(shape))).astype(x.dtype)
    return dx, dgamma.astype(weight.dtype), dbeta.astype(weight.dtype)


_bn_train_apply.defvjp(_bn_train_vjp_fwd, _bn_train_vjp_bwd)


class BatchNormalization(Module):
    """Batch norm over [batch, feature] (``nn/BatchNormalization.scala``)."""

    _feature_axis = 1

    def __init__(self, n_output: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True, init_weight=None, init_bias=None):
        super().__init__()
        self.n_output, self.eps, self.momentum, self.affine = n_output, eps, momentum, affine
        if affine:
            self.weight = Parameter(init_weight if init_weight is not None
                                    else jnp.ones((n_output,), jnp.float32))
            self.bias = Parameter(init_bias if init_bias is not None
                                  else jnp.zeros((n_output,), jnp.float32))
        self.register_buffer("running_mean", jnp.zeros((n_output,), jnp.float32))
        self.register_buffer("running_var", jnp.ones((n_output,), jnp.float32))

    def reset(self):
        if self.affine:
            self.weight = jnp.ones((self.n_output,), jnp.float32)
            self.bias = jnp.zeros((self.n_output,), jnp.float32)
        self.running_mean = jnp.zeros((self.n_output,), jnp.float32)
        self.running_var = jnp.ones((self.n_output,), jnp.float32)

    def _stat_axes(self, ndim):
        return tuple(a for a in range(ndim) if a != self._feature_axis)

    def update_output(self, input):
        axes = self._stat_axes(input.ndim)
        shape = [1] * input.ndim
        shape[self._feature_axis] = self.n_output
        if self.training:
            w = self.weight if self.affine \
                else jnp.ones((self.n_output,), jnp.float32)
            b = self.bias if self.affine \
                else jnp.zeros((self.n_output,), jnp.float32)
            out, mean, var = _bn_train_apply(axes, self.eps, input, w, b)
            mean = lax.stop_gradient(mean)
            var = lax.stop_gradient(var)
            n = input.size // self.n_output
            unbiased = var * n / max(n - 1, 1)
            self.running_mean = (1 - self.momentum) * self.running_mean + self.momentum * mean
            self.running_var = (1 - self.momentum) * self.running_var + self.momentum * unbiased
            return out
        mean, var = self.running_mean, self.running_var
        inv = lax.rsqrt(var + self.eps).reshape(shape)
        out = (input - mean.reshape(shape)) * inv
        if self.affine:
            out = out * self.weight.reshape(shape) + self.bias.reshape(shape)
        return out.astype(input.dtype)


class SpatialBatchNormalization(BatchNormalization):
    """Batch norm over [batch, C, H, W] / [batch, H, W, C]
    (``nn/SpatialBatchNormalization.scala``)."""

    def __init__(self, n_output: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True, init_weight=None, init_bias=None,
                 format: str = "NCHW"):
        super().__init__(n_output, eps, momentum, affine, init_weight, init_bias)
        self.format = format

    @property
    def _feature_axis(self):  # type: ignore[override]
        return 3 if self.format == "NHWC" else 1


class SpatialCrossMapLRN(Module):
    """AlexNet-style local response normalization across channels
    (``nn/SpatialCrossMapLRN.scala``)."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75,
                 k: float = 1.0, format: str = "NCHW"):
        super().__init__()
        # the reference only defines odd windows (SpatialCrossMapLRN.scala:59);
        # even sizes would also diverge from torch's window anchoring
        assert size % 2 == 1, f"LRN only supports odd size, got {size}"
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k
        self.format = format

    def update_output(self, input):
        # ops/lrn.py: the channel window as a banded C x C
        # product in the input's own layout (NCHW or NHWC), exact custom
        # VJP; one leg on every platform, no BIGDL_KERNELS choice
        from bigdl_tpu.ops.lrn import cross_map_lrn

        squeeze = input.ndim == 3
        x = input[None] if squeeze else input
        if x.ndim == 4:
            out = cross_map_lrn(x, self.size, self.alpha, self.beta,
                                self.k, self.format)
            return out[0] if squeeze else out
        # rank > 4: generic channel-window reference, autodiff's backward
        c_ax = x.ndim - 1 if self.format == "NHWC" else 1
        half = (self.size - 1) // 2
        dims, strides, pads = [1] * x.ndim, [1] * x.ndim, [(0, 0)] * x.ndim
        dims[c_ax] = self.size
        pads[c_ax] = (half, self.size - 1 - half)
        window_sum = lax.reduce_window(x * x, 0.0, lax.add, tuple(dims),
                                       tuple(strides), pads)
        scale = self.k + window_sum * (self.alpha / self.size)
        out = x * jnp.power(scale, -self.beta)
        return out[0] if squeeze else out


def _gaussian_kernel(size: int) -> np.ndarray:
    sigma = 0.25 * size
    xs = np.arange(size) - (size - 1) / 2.0
    k = np.exp(-(xs**2) / (2 * sigma * sigma))
    k2 = np.outer(k, k)
    return (k2 / k2.sum()).astype(np.float32)


class SpatialWithinChannelLRN(Module):
    """LRN within each channel over a spatial window
    (``nn/SpatialWithinChannelLRN.scala``)."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75):
        super().__init__()
        self.size, self.alpha, self.beta = size, alpha, beta

    def update_output(self, input):
        from bigdl_tpu.ops.lrn import within_channel_lrn

        if input.ndim == 3:
            return within_channel_lrn(input[None], self.size, self.alpha,
                                      self.beta)[0]
        if input.ndim == 4:
            return within_channel_lrn(input, self.size, self.alpha,
                                      self.beta)
        # rank > 4: reference path, autodiff's backward
        half = (self.size - 1) // 2
        dims, strides, pads = [1] * input.ndim, [1] * input.ndim, [(0, 0)] * input.ndim
        for ax in (input.ndim - 2, input.ndim - 1):
            dims[ax] = self.size
            pads[ax] = (half, self.size - 1 - half)
        window_mean = lax.reduce_window(input * input, 0.0, lax.add,
                                        tuple(dims), tuple(strides), pads) / (self.size * self.size)
        scale = 1.0 + window_mean * self.alpha
        return input * jnp.power(scale, -self.beta)


class SpatialSubtractiveNormalization(Module):
    """Subtract a kernel-weighted local mean (``nn/SpatialSubtractiveNormalization.scala``)."""

    def __init__(self, n_input_plane: int = 1, kernel: Optional[np.ndarray] = None):
        super().__init__()
        self.n_input_plane = n_input_plane
        k = np.asarray(kernel, np.float32) if kernel is not None else _gaussian_kernel(9)
        if k.ndim == 1:
            k = np.outer(k, k)
        self.register_buffer("kernel", k / k.sum())

    def update_output(self, input):
        from bigdl_tpu.ops.norm import subtractive_norm

        squeeze = input.ndim == 3
        x = input[None] if squeeze else input
        # the smoothing kernel is a buffer, never trained: stop_gradient
        # documents what the op's zero kernel-cotangent already enforces
        out = subtractive_norm(x, lax.stop_gradient(self.kernel))
        return out[0] if squeeze else out


class SpatialDivisiveNormalization(Module):
    """Divide by the local standard deviation (``nn/SpatialDivisiveNormalization.scala``)."""

    def __init__(self, n_input_plane: int = 1, kernel: Optional[np.ndarray] = None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.n_input_plane = n_input_plane
        k = np.asarray(kernel, np.float32) if kernel is not None else _gaussian_kernel(9)
        if k.ndim == 1:
            k = np.outer(k, k)
        self.register_buffer("kernel", k / k.sum())
        self.threshold, self.thresval = threshold, thresval

    def update_output(self, input):
        from bigdl_tpu.ops.norm import divisive_norm

        squeeze = input.ndim == 3
        x = input[None] if squeeze else input
        out = divisive_norm(x, lax.stop_gradient(self.kernel),
                            self.threshold, self.thresval)
        return out[0] if squeeze else out


class SpatialContrastiveNormalization(Module):
    """Subtractive then divisive normalization
    (``nn/SpatialContrastiveNormalization.scala``)."""

    def __init__(self, n_input_plane: int = 1, kernel: Optional[np.ndarray] = None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.div = SpatialDivisiveNormalization(n_input_plane, kernel, threshold, thresval)

    def update_output(self, input):
        return self.div.forward(self.sub.forward(input))


class Normalize(Module):
    """Lp-normalize along the feature dim (``nn/Normalize.scala``)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10):
        super().__init__()
        self.p, self.eps = p, eps

    def update_output(self, input):
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(input), axis=-1, keepdims=True)
        else:
            norm = jnp.sum(jnp.abs(input) ** self.p, axis=-1, keepdims=True) ** (1.0 / self.p)
        return input / (norm + self.eps)


class Dropout(Module):
    """Inverted dropout (``nn/Dropout.scala``: scales by 1/(1-p) in train
    when ``scale``)."""

    def __init__(self, init_p: float = 0.5, inplace: bool = False, scale: bool = True):
        super().__init__()
        self.p = init_p
        self.scale = scale
        self._rng_id = next_rng_id()

    def set_p(self, p: float):
        self.p = p
        return self

    def update_output(self, input):
        if not self.training or self.p <= 0.0:
            return input
        key = require_rng(self._rng_id)
        keep = jax.random.bernoulli(key, 1.0 - self.p, jnp.shape(input))
        out = jnp.where(keep, input, 0.0)
        if self.scale:
            out = out / (1.0 - self.p)
        return out.astype(input.dtype)


class L1Penalty(Module):
    """Identity forward that adds an L1 sparsity gradient in backward
    (``nn/L1Penalty.scala``) — expressed as a custom VJP."""

    def __init__(self, l1weight: float, size_average: bool = False,
                 provide_output: bool = True):
        super().__init__()
        self.l1weight = l1weight
        self.size_average = size_average

    def update_output(self, input):
        w = self.l1weight
        if self.size_average:
            w = w / input.size

        @jax.custom_vjp
        def penalty(x):
            return x

        def fwd(x):
            return x, jnp.sign(x)

        def bwd(sign, g):
            return (g + w * sign,)

        penalty.defvjp(fwd, bwd)
        return penalty(input)


class RMSNorm(Module):
    """Root-mean-square normalisation over the last dimension (Zhang &
    Sennrich 2019): ``w * x / sqrt(mean(x^2) + eps)``, no centring and
    no bias.  The statistics are taken in float32 whatever the
    activations' dtype; the result comes back in it.

    ``zero_centred``: the scale is ``1 + w`` with ``w`` starting at 0
    (the form some decoder families keep, so that weight decay pulls the
    scale to one and not to zero)."""

    def __init__(self, normalized_size: int, eps: float = 1e-6,
                 zero_centred: bool = False):
        super().__init__()
        self.normalized_size = normalized_size
        self.eps = eps
        self.zero_centred = zero_centred
        init = jnp.zeros if zero_centred else jnp.ones
        self.weight = Parameter(init((normalized_size,), jnp.float32))

    def update_output(self, input):
        x = input.astype(jnp.float32)
        y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps)
        scale = self.weight.astype(jnp.float32)
        y = y * (1.0 + scale if self.zero_centred else scale)
        return y.astype(input.dtype)

    def __repr__(self):
        return f"RMSNorm({self.normalized_size})"
