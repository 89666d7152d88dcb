"""The two LRN ops (cross-map + within-channel) with exact VJPs.

Both ops share the shape ``y = x * s^-beta`` and carry a hand-derived
exact cotangent under ``jax.custom_vjp`` in place of an autodiff chain
that re-materialized every intermediate:

- cross-map (``nn/SpatialCrossMapLRN.scala``):
  ``s_i = k + (a/n) * sum_{j in band(i)} x_j^2`` over a channel band of
  ``n = size`` channels;
  ``dx = g*s^-b - (2ab/n) * x * band^T(g*x*s^(-b-1))`` — for odd bands
  the transpose band IS the band.
- within-channel (``nn/SpatialWithinChannelLRN.scala``):
  ``s = 1 + (a/n^2) * win(x^2)`` over an ``n x n`` spatial window with
  Torch pads ``(lo, hi) = (half, n-1-half)``;
  ``dx = g*s^-b - (2ab/n^2) * x * win^T(g*x*s^(-b-1))`` where the
  transpose window uses the swapped pads ``(hi, lo)`` (exact also for
  even windows).

**Cross-map has ONE leg**: the channel window as a banded ``C x C`` 1x1
product on the MXU (``_band_apply``), in XLA's own layout for NCHW and
NHWC alike, on every platform, on and off a mesh, whatever
``BIGDL_KERNELS`` says; announced as ``kernel/dispatch
op=lrn_cross_map.fwd|.bwd backend=xla reason=only-leg`` once a
compilation.  Until PR 44 a Pallas kernel (square, unrolled
shift-accumulate band sum, powf epilogue, one pass a ``[C + halo, HW
tile]`` block) was the TPU's leg off a mesh.  It read 47% of its
roofline and still lost, through its interface: every operand padded to
``[N, C + 4, 3200]`` (one such copy forward, three backward), results
sliced and reshaped back, and XLA made to leave the
batch-and-channels-minor layout it keeps ``[256,192,56,56]`` in between
two convolutions.  Inception-v1 at batch 256 on one TPU v5e, the same
tree and machine, Pallas legs | ``BIGDL_KERNELS=xla`` (PERF.md section
6, PR 41): device step 69.898 | 47.072 ms, 3,338.4 | 4,766.3 records/s,
peak memory 7.94 | 5.75 GB; the head pool took 12.3 ms of that gap in
PR 41 and this op the rest (PERF.md section 6, PR 44, kernel | banded
product: 57.643 | 47.086 ms, 4,025 | 4,820 records/s; beside the four
calls' 6.35 ms a step the kernel's six pads cost 3.44 and eight layout
copies 4.96).  The four-chip cell, NHWC models and the CPU always ran
the banded product.

Within-channel has one leg too, ``lax.reduce_window`` sums under the
formulas above, announced the same way (``op=lrn_within_channel.fwd|
.bwd``): no cell runs it.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.ops import dispatch as _dispatch

__all__ = ["cross_map_lrn", "within_channel_lrn"]


def _pow(s, p: float):
    """``s ** p`` for s > 0 via exp/log — one transcendental pair the
    VPU lowers directly (jnp.power would route negative-base checks)."""
    if p == -0.5:
        return lax.rsqrt(s)
    return jnp.exp(p * jnp.log(s))


# ---------------------------------------------------------------------------
# cross-map LRN: the channel window as a banded C x C product, one leg
# ---------------------------------------------------------------------------

def _band_matrix(c: int, size: int, transpose: bool) -> np.ndarray:
    half = (size - 1) // 2
    hi = size - 1 - half
    d = np.arange(c)
    rel = d[None, :] - d[:, None]       # rel = j - i
    if transpose:
        band = (rel >= -hi) & (rel <= half)
    else:
        band = (rel >= -half) & (rel <= hi)
    return band.astype(np.float32)


def _band_apply(v, size: int, transpose: bool, layout: str):
    """Banded C x C matrix at every pixel as a 1x1 conv: the channel
    window on the MXU, NATIVELY in either layout (operands in ``v``'s
    dtype, float32 sums; see SpatialCrossMapLRN's original profile
    note: reduce_window over the non-minor channel dim was ~10x
    slower)."""
    c_ax = 3 if layout == "NHWC" else 1
    band = _band_matrix(v.shape[c_ax], size, transpose)
    if layout == "NHWC":
        w = jnp.asarray(band.T[None, None], v.dtype)  # HWIO
        dn = lax.conv_dimension_numbers(v.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    else:
        w = jnp.asarray(band[:, :, None, None], v.dtype)  # OIHW
        dn = lax.conv_dimension_numbers(v.shape, w.shape,
                                        ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(v, w, (1, 1), ((0, 0), (0, 0)),
                                    dimension_numbers=dn)


def _cml_note(leg: str, x, size: int, layout: str) -> None:
    """ONE leg on every platform, on and off a mesh, in every
    ``BIGDL_KERNELS`` mode: announced, not chosen (as ``ops/ssd.py``
    and the short convolution announce theirs)."""
    _dispatch.note(f"lrn_cross_map.{leg}", "xla", "only-leg",
                   channels=x.shape[3 if layout == "NHWC" else 1],
                   size=size, layout=layout)


def _cml_fwd(x, size, alpha, beta, k, layout):
    _cml_note("fwd", x, size, layout)
    den = k + _band_apply(x * x, size, False, layout) * (alpha / size)
    return x * _pow(den, -beta), den


def _cml_bwd(x, den, g, size, alpha, beta, layout):
    _cml_note("bwd", x, size, layout)
    t = g * x * _pow(den, -beta - 1.0)
    return g * _pow(den, -beta) \
        - (2.0 * alpha * beta / size) * x \
        * _band_apply(t, size, True, layout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def cross_map_lrn(x, size: int, alpha: float, beta: float, k: float,
                  layout: str = "NCHW"):
    """AlexNet-style cross-channel LRN over NCHW/NHWC with exact custom
    VJP: the banded product in the input's own layout (no relayout
    transposes), the one form on every platform."""
    y, _ = _cml_fwd(x, size, alpha, beta, k, layout)
    return y


def _cml_vjp_fwd(x, size, alpha, beta, k, layout):
    y, den = _cml_fwd(x, size, alpha, beta, k, layout)
    return y, (x, den)


def _cml_vjp_bwd(size, alpha, beta, k, layout, res, g):
    x, den = res
    return (_cml_bwd(x, den, g, size, alpha, beta, layout),)


cross_map_lrn.defvjp(_cml_vjp_fwd, _cml_vjp_bwd)


# ---------------------------------------------------------------------------
# within-channel LRN: spatial-window sums over NCHW
# ---------------------------------------------------------------------------

def _win_sum(v, size: int, pads: Tuple[int, int]):
    dims = (1, 1, size, size)
    p = ((0, 0), (0, 0), pads, pads)
    return lax.reduce_window(v, jnp.zeros((), v.dtype), lax.add, dims,
                             (1, 1, 1, 1), p)


def _wcl_fwd(x, size, alpha, beta):
    _dispatch.note("lrn_within_channel.fwd", "xla", "only-leg")
    lo, hi = (size - 1) // 2, size - 1 - (size - 1) // 2
    scale = 1.0 + _win_sum(x * x, size, (lo, hi)) * (alpha / (size * size))
    return x * _pow(scale, -beta), scale


def _wcl_bwd(x, scale, g, size, alpha, beta):
    _dispatch.note("lrn_within_channel.bwd", "xla", "only-leg")
    lo, hi = (size - 1) // 2, size - 1 - (size - 1) // 2
    t = g * x * _pow(scale, -beta - 1.0)
    ts = _win_sum(t, size, (hi, lo))
    return g * _pow(scale, -beta) \
        - (2.0 * alpha * beta / (size * size)) * x * ts


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def within_channel_lrn(x, size: int, alpha: float, beta: float):
    """Within-channel spatial LRN over NCHW with exact custom VJP."""
    y, _ = _wcl_fwd(x, size, alpha, beta)
    return y


def _wcl_vjp_fwd(x, size, alpha, beta):
    y, scale = _wcl_fwd(x, size, alpha, beta)
    return y, (x, scale)


def _wcl_vjp_bwd(size, alpha, beta, res, g):
    x, scale = res
    return (_wcl_bwd(x, scale, g, size, alpha, beta),)


within_channel_lrn.defvjp(_wcl_vjp_fwd, _wcl_vjp_bwd)
