"""Fused subtractive / divisive / contrastive normalization with exact
VJPs.

These Torch-legacy ops are built around a kernel-weighted spatial
smoothing of a channel-reduced map, written as the reference writes it:
a 1-channel ``lax.conv`` (``smooth2d``; a poor shape for the MXU, but
no registry model builds one of these layers and no cell times them).
Channel reduction, division and thresholding are elementwise/small
reductions XLA fuses into their neighbours.

VJP derivations (g = upstream cotangent, C = channel count):

- subtractive (``nn/SpatialSubtractiveNormalization.scala``):
  ``y = x - sm(u)/coef`` with ``u = mean_c(x)``, ``coef = sm(1)`` the
  edge-coverage mass.  Exact:
  ``dx = g - (1/C) * sm^T(sum_c(g) / coef)``
  where ``sm^T`` is correlation with the FLIPPED kernel under swapped
  pads — the transpose of the forward smoothing.
- divisive (``nn/SpatialDivisiveNormalization.scala``):
  ``y = x / d``, ``d = thresh(max(sigma, mean_hw(sigma)))``,
  ``sigma = sqrt(clip(sm(mean_c(x^2))/coef, 0))``.  Exact backward
  chains the pieces: ``gd = -sum_c(g*x)/d^2``, gated through the
  threshold (``e >= t``), split across the ``max`` (position vs the
  spatial-mean branch, which re-broadcasts ``1/(H*W)``), through
  ``1/(2*sigma)`` (guarded at 0), ``/coef``, ``sm^T``, and finally
  ``dx = g/d + (2/C) * x * gusq``.  Ties and the clip/threshold corners
  are measure-zero for continuous activations.
- contrastive = divisive(subtractive(x)) — composing the two exact
  custom VJPs keeps the chain exact by construction.

The smoothing kernel is a module BUFFER, never trained — its cotangent
is defined as zero (``lax.stop_gradient`` semantics), matching the
framework's buffer contract.  One leg, announced to ``ops.dispatch`` as
``norm_smooth.fwd|.bwd backend=xla reason=only-leg``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.ops import dispatch as _dispatch

__all__ = ["smooth2d", "subtractive_norm", "divisive_norm",
           "contrastive_norm"]


def _fwd_pads(kh: int, kw: int):
    return (kh // 2, (kh - 1) // 2), (kw // 2, (kw - 1) // 2)


def _transpose_pads(kh: int, kw: int):
    (alo, ahi), (blo, bhi) = _fwd_pads(kh, kw)
    return (ahi, alo), (bhi, blo)


def smooth2d(stack, kernel, pads, flip: bool = False):
    """Kernel-weighted window sum over a [B, H, W] plane stack (the
    shared primitive under all three normalizations; ``flip=True`` with
    swapped pads is the exact transpose).  NOT differentiable on its
    own — always called inside a custom-vjp fwd/bwd rule."""
    _dispatch.note("norm_smooth.bwd" if flip else "norm_smooth.fwd", "xla",
                   "only-leg")
    k = kernel[::-1, ::-1] if flip else kernel
    v = stack[:, None]                  # [B, 1, H, W]
    w4 = k.astype(stack.dtype)[None, None]
    dn = lax.conv_dimension_numbers(v.shape, w4.shape,
                                    ("NCHW", "OIHW", "NCHW"))
    out = lax.conv_general_dilated(v, w4, (1, 1), pads,
                                   dimension_numbers=dn)
    return out[:, 0]


def _coef(kernel, h: int, w: int, dtype):
    """Edge-coverage mass: the kernel weight actually inside the image
    at each position (the reference divides the smoothed map by it)."""
    ones = jnp.ones((1, h, w), dtype)
    kh, kw = kernel.shape
    return smooth2d(ones, kernel, _fwd_pads(kh, kw))


# ---------------------------------------------------------------------------
# subtractive
# ---------------------------------------------------------------------------

@jax.custom_vjp
def subtractive_norm(x, kernel):
    """``x - local kernel-weighted mean`` over NCHW with exact custom
    VJP; the smoothing kernel is buffer-semantics (zero cotangent)."""
    y, _ = _sub_fwd(x, kernel)
    return y


def _sub_fwd(x, kernel):
    n, c, h, w = x.shape
    kh, kw = kernel.shape
    u = jnp.mean(x, axis=1)             # [N, H, W]
    coef = _coef(kernel, h, w, x.dtype)
    m = smooth2d(u, kernel, _fwd_pads(kh, kw)) / coef
    return x - m[:, None], coef


def _sub_vjp_fwd(x, kernel):
    y, coef = _sub_fwd(x, kernel)
    return y, (kernel, coef, x.shape[1])


def _sub_vjp_bwd(res, g):
    kernel, coef, c = res
    kh, kw = kernel.shape
    v = jnp.sum(g, axis=1) / coef
    corr_t = smooth2d(v, kernel, _transpose_pads(kh, kw), flip=True)
    return g - corr_t[:, None] / c, jnp.zeros_like(kernel)


subtractive_norm.defvjp(_sub_vjp_fwd, _sub_vjp_bwd)


# ---------------------------------------------------------------------------
# divisive
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def divisive_norm(x, kernel, threshold: float = 1e-4,
                  thresval: float = 1e-4):
    """``x / thresholded local std`` over NCHW with exact custom VJP."""
    y, _, _, _, _ = _div_fwd(x, kernel, threshold, thresval)
    return y


def _div_fwd(x, kernel, threshold, thresval):
    n, c, h, w = x.shape
    kh, kw = kernel.shape
    usq = jnp.mean(x * x, axis=1)       # [N, H, W]
    coef = _coef(kernel, h, w, x.dtype)
    s = smooth2d(usq, kernel, _fwd_pads(kh, kw)) / coef
    sigma = jnp.sqrt(jnp.clip(s, 0.0))
    mu = jnp.mean(sigma, axis=(1, 2), keepdims=True)
    e = jnp.maximum(sigma, mu)
    d = jnp.where(e < threshold, jnp.asarray(thresval, x.dtype), e)
    return x / d[:, None], sigma, mu, d, coef


def _div_vjp_fwd(x, kernel, threshold, thresval):
    y, sigma, mu, d, coef = _div_fwd(x, kernel, threshold, thresval)
    return y, (x, kernel, sigma, mu, d, coef)


def _div_vjp_bwd(threshold, thresval, res, g):
    x, kernel, sigma, mu, d, coef = res
    kh, kw = kernel.shape
    c = x.shape[1]
    hw = sigma.shape[1] * sigma.shape[2]
    gd = -jnp.sum(g * x, axis=1) / (d * d)
    e = jnp.maximum(sigma, mu)
    ge = jnp.where(e >= threshold, gd, 0.0)
    mask_sig = sigma >= mu              # ties -> position branch
    gmu = jnp.sum(jnp.where(mask_sig, 0.0, ge), axis=(1, 2),
                  keepdims=True)
    gsig = jnp.where(mask_sig, ge, 0.0) + gmu / hw
    gs = jnp.where(sigma > 0, gsig / (2.0 * sigma), 0.0)
    gusq = smooth2d(gs / coef, kernel, _transpose_pads(kh, kw),
                    flip=True)
    dx = g / d[:, None] + x * (2.0 / c) * gusq[:, None]
    return dx, jnp.zeros_like(kernel)


divisive_norm.defvjp(_div_vjp_fwd, _div_vjp_bwd)


def contrastive_norm(x, kernel, threshold: float = 1e-4,
                     thresval: float = 1e-4):
    """Subtractive then divisive normalization — composing the two
    exact custom VJPs keeps the whole chain exact."""
    return divisive_norm(subtractive_norm(x, kernel), kernel, threshold,
                         thresval)
