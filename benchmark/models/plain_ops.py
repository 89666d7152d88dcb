"""Plain ``jax.numpy`` building blocks of the references: float32, no
kernels, ``precision=HIGHEST`` on every contraction.  Imports nothing of
the program.

``quant`` is the control of "How ``correct`` is decided": ``None`` is
the reference proper; ``"int8"`` rounds both operands of every
convolution and matrix product, and the cotangent that arrives at its
output (the third operand of its backward), to 8-bit integers with one scale per tensor (the nearest
precision below the bfloat16 that the configurations state, and the
step that would tempt a later PR).  The arithmetic itself stays float32:
what is modelled is the information a lower precision throws away.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
_DN = ("NCHW", "OIHW", "NCHW")


def _round_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale).clip(-127, 127) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def lower(x, quant: Optional[str]):
    """An operand of a contraction: identity for ``quant=None``, else
    ``x`` as the lower precision keeps it.  The gradient passes straight
    through (the contraction's own backward already sees the rounded
    operands; its incoming cotangent is rounded by ``lower_out``)."""
    return x if quant is None else _round_int8(x)


def _lower_fwd(x, quant):
    return lower(x, quant), None


def _lower_bwd(quant, _, g):
    return (g,)


lower.defvjp(_lower_fwd, _lower_bwd)


def conv(x, w, b, stride: int = 1, pad: int = 0, quant=None):
    """NCHW convolution with OIHW weights, as the published layers
    define it (cross-correlation, symmetric zero padding)."""
    y = lax.conv_general_dilated(
        lower(x, quant), lower(w, quant), (stride, stride),
        [(pad, pad), (pad, pad)], dimension_numbers=_DN, precision=HIGHEST)
    y = lower_out(y, quant)
    return y if b is None else y + b[None, :, None, None]


def linear(x, w, b, quant=None):
    """``y = x W^T + b`` with ``W`` of shape (out, in)."""
    y = jnp.dot(lower(x, quant), lower(w, quant).T, precision=HIGHEST)
    return lower_out(y, quant) + b


def lower_out(y, quant):
    """Rounds only the cotangent that arrives at a contraction's output
    (the forward result of an int8 product is wide)."""
    return y if quant is None else _grad_only(y)


@jax.custom_vjp
def _grad_only(y):
    return y


_grad_only.defvjp(lambda y: (y, None), lambda _, g: (_round_int8(g),))


def relu(x):
    return jnp.maximum(x, 0.0)


def pool_out(n: int, k: int, s: int, p: int = 0, ceil: bool = False) -> int:
    span = n + 2 * p - k
    out = (-(-span // s) if ceil else span // s) + 1
    if ceil and (out - 1) * s >= n + p:
        out -= 1  # the last window may not start in the padding
    return out


def _pool_pads(n: int, k: int, s: int, p: int, ceil: bool
               ) -> Tuple[int, int]:
    out = pool_out(n, k, s, p, ceil)
    return p, max((out - 1) * s + k - n - p, 0)


def max_pool(x, k: int, s: int, p: int = 0, ceil: bool = False):
    ph = _pool_pads(x.shape[2], k, s, p, ceil)
    pw = _pool_pads(x.shape[3], k, s, p, ceil)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, k, k),
                             (1, 1, s, s), [(0, 0), (0, 0), ph, pw])


def global_avg_pool(x):
    """The 7x7/s1 average pool on a 7x7 map: the mean of each plane."""
    return jnp.mean(x, axis=(2, 3))


def cross_map_lrn(x, size: int, alpha: float, beta: float, k: float):
    """Krizhevsky et al. 2012, section 3.3, as Caffe and BigDL scale it:
    ``x / (k + alpha/size * sum over `size` neighbouring maps of x^2)
    ** beta``."""
    half = (size - 1) // 2
    sq = jnp.pad(x * x, ((0, 0), (half, size - 1 - half), (0, 0), (0, 0)))
    acc = sum(sq[:, i:i + x.shape[1]] for i in range(size))
    return x / (k + (alpha / size) * acc) ** beta


def batch_norm_train(x, gamma, beta, eps: float):
    """Ioffe & Szegedy 2015, training mode: statistics of this batch over
    (N, H, W), biased variance."""
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=(0, 2, 3), keepdims=True)
    xhat = (x - mean) * lax.rsqrt(var + eps)
    return xhat * gamma[None, :, None, None] + beta[None, :, None, None]


def log_softmax(z):
    z = z - jnp.max(z, axis=-1, keepdims=True)
    return z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))


def nll_sum(logp, labels):
    """Sum (not mean) of the negative log-likelihoods, so that blocks of
    rows add up."""
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


def conv_macs(cin: int, cout: int, k: int, out_hw: Sequence[int]) -> int:
    return cin * cout * k * k * out_hw[0] * out_hw[1]


def conv_out(n: int, k: int, s: int, p: int) -> int:
    return (n + 2 * p - k) // s + 1
