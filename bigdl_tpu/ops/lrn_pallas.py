"""Fused LRN kernels (cross-map + within-channel) with exact VJPs.

Round-5 motivation (BASELINE.md, round 5): inception sat at 0.25 MFU
and its LRN layers lower to multi-op HLO chains — square, window-sum,
scale, power, multiply — that XLA leaves as separate HBM-bound fusions
(the channel window additionally fights TPU tiling: C is non-minor in
NCHW activations).  Each op here is ONE Pallas pass per block: read x,
square, unrolled shift-accumulate window sum, powf epilogue, write
(y, denom) — and the backward is the hand-derived exact cotangent in a
second fused pass, replacing an autodiff chain that re-materialized
every intermediate.

Math (both ops share the shape ``y = x * s^-beta``):

- cross-map (``nn/SpatialCrossMapLRN.scala``):
  ``s_i = k + (a/n) * sum_{j in band(i)} x_j^2`` over a channel band of
  ``n = size`` (odd) channels;
  ``dx = g*s^-b - (2ab/n) * x * band^T(g*x*s^(-b-1))`` — for odd bands
  the transpose band IS the band.
- within-channel (``nn/SpatialWithinChannelLRN.scala``):
  ``s = 1 + (a/n^2) * win(x^2)`` over an ``n x n`` spatial window with
  Torch pads ``(lo, hi) = (half, n-1-half)``;
  ``dx = g*s^-b - (2ab/n^2) * x * win^T(g*x*s^(-b-1))`` where the
  transpose window uses the swapped pads ``(hi, lo)`` (exact also for
  even windows).

Both are registered as ``jax.custom_vjp`` with the backend (Pallas vs
an XLA reference built from the same formulas) chosen per leg by
``ops.dispatch`` — the VJP is exact on either leg, so the numeric-grad
suite holds no matter how the knob is set.  Off-TPU the Pallas leg runs
``interpret=True`` (same code path, pure jax ops — this is what the
parity tests pin).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.ops import dispatch as _dispatch
from bigdl_tpu.ops.pallas_util import (TPU_DTYPES as _TPU_DTYPES,
                                       VMEM_BUDGET as _VMEM_BUDGET,
                                       plane_call as _plane_call)

__all__ = ["cross_map_lrn", "cross_map_lrn_supported",
           "within_channel_lrn", "within_channel_lrn_supported"]


def _pow(s, p: float):
    """``s ** p`` for s > 0 via exp/log — one transcendental pair the
    VPU lowers directly (jnp.power would route negative-base checks)."""
    if p == -0.5:
        return lax.rsqrt(s)
    return jnp.exp(p * jnp.log(s))


def _on_tpu_compiled() -> bool:
    return not _dispatch.use_interpret()


# ---------------------------------------------------------------------------
# cross-map LRN: banded channel-window sum, layout [N, Cpad, HW-tile]
# ---------------------------------------------------------------------------

def cross_map_lrn_supported(x, size: int, layout: str = "NCHW") -> bool:
    """Structural gate for the Pallas leg: 4-D NCHW, odd band.  NHWC
    stays on the XLA leg, which runs the banded conv NATIVELY in that
    layout — repacking for the kernel would cost the exact full-tensor
    relayout class this library exists to remove.  On real TPU
    additionally require a Mosaic dtype and the block to fit VMEM."""
    if x.ndim != 4 or size % 2 != 1 or size < 1 or layout != "NCHW":
        return False
    if _on_tpu_compiled():
        if x.dtype not in _TPU_DTYPES:
            return False
        n, c, h, w = x.shape
        f_pad = -(-(h * w) // 128) * 128
        t = _pick_tile(f_pad, c + size - 1, jnp.dtype(x.dtype).itemsize)
        if t is None:
            return False
    return True


def _pick_tile(f_pad: int, cp: int, esz: int):
    """Largest HW-tile whose fwd/bwd block stack fits the VMEM budget.
    Mosaic wants a block's last dimension to be a multiple of 128 (or
    the whole extent), so the candidates are the multiples of 128 that
    divide ``f_pad`` (itself a multiple of 128); None when even one
    128-lane tile does not fit."""
    lanes = f_pad // 128
    for k in range(lanes, 0, -1):
        # ~5 live [Cp, T] planes: x, sq, running band sum, den, y
        if lanes % k == 0 and 5 * cp * 128 * k * esz <= _VMEM_BUDGET:
            return 128 * k
    return None


def _cml_fwd_kernel(xp_ref, y_ref, den_ref, *, c: int, size: int,
                    half: int, alpha: float, beta: float, k: float):
    xp = xp_ref[0]                      # [Cp, T]
    sq = xp * xp
    s = sq[0:c]
    for d in range(1, size):
        s = s + sq[d:d + c]
    den = k + s * (alpha / size)
    den_ref[0] = den
    y_ref[0] = xp[half:half + c] * _pow(den, -beta)


def _cml_bwd_kernel(xp_ref, gp_ref, denp_ref, dx_ref, *, c: int, size: int,
                    half: int, alpha: float, beta: float):
    xp = xp_ref[0]
    gp = gp_ref[0]
    denp = denp_ref[0]                  # halo channels carry 1.0
    t = gp * xp * _pow(denp, -beta - 1.0)
    ts = t[0:c]
    for d in range(1, size):            # odd band: transpose == forward
        ts = ts + t[d:d + c]
    g = gp[half:half + c]
    x = xp[half:half + c]
    den = denp[half:half + c]
    dx_ref[0] = g * _pow(den, -beta) \
        - (2.0 * alpha * beta / size) * x * ts


def _cml_pack(a, pad_val: float, half: int, f_pad: int):
    """[N, C, H, W] -> [N, C + 2*half, f_pad] with channel halo."""
    n, c, h, w = a.shape
    flat = a.reshape(n, c, h * w)
    return jnp.pad(flat, ((0, 0), (half, half), (0, f_pad - h * w)),
                   constant_values=pad_val)


def _cml_call(kernel, packed_inputs, out_shapes, n, f_pad, t):
    from jax.experimental import pallas as pl

    grid = (n, f_pad // t)
    cp = packed_inputs[0].shape[1]
    in_specs = [pl.BlockSpec((1, cp, t), lambda b, i: (b, 0, i))
                for _ in packed_inputs]
    out_specs = [pl.BlockSpec((1, s[1], t), lambda b, i: (b, 0, i))
                 for s in out_shapes]
    outs = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        out_shape=[jax.ShapeDtypeStruct((n, s[1], f_pad), s[2])
                   for s in out_shapes] if len(out_shapes) > 1
        else jax.ShapeDtypeStruct(
            (n, out_shapes[0][1], f_pad), out_shapes[0][2]),
        interpret=_dispatch.use_interpret(),
    )(*packed_inputs)
    return outs


def _cml_fwd_pallas(x, size, alpha, beta, k):
    n, c, h, w = x.shape
    half = (size - 1) // 2
    f = h * w
    f_pad = -(-f // 128) * 128
    t = _pick_tile(f_pad, c + 2 * half, jnp.dtype(x.dtype).itemsize) \
        or f_pad
    xp = _cml_pack(x, 0.0, half, f_pad)
    kern = functools.partial(_cml_fwd_kernel, c=c, size=size, half=half,
                             alpha=alpha, beta=beta, k=k)
    y, den = _cml_call(kern, [xp],
                       [(n, c, x.dtype), (n, c, x.dtype)], n, f_pad, t)
    return (y[:, :, :f].reshape(n, c, h, w),
            den[:, :, :f].reshape(n, c, h, w))


def _cml_bwd_pallas(x, den, g, size, alpha, beta):
    n, c, h, w = x.shape
    half = (size - 1) // 2
    f = h * w
    f_pad = -(-f // 128) * 128
    t = _pick_tile(f_pad, c + 2 * half, jnp.dtype(x.dtype).itemsize) \
        or f_pad
    xp = _cml_pack(x, 0.0, half, f_pad)
    gp = _cml_pack(g, 0.0, half, f_pad)
    denp = _cml_pack(den, 1.0, half, f_pad)  # 1.0: powf stays finite
    kern = functools.partial(_cml_bwd_kernel, c=c, size=size, half=half,
                             alpha=alpha, beta=beta)
    dx = _cml_call(kern, [xp, gp, denp], [(n, c, x.dtype)], n, f_pad, t)
    return dx[:, :, :f].reshape(n, c, h, w)


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
    """Banded C x C matrix at every pixel as a 1x1 conv — it (and only
    it) runs the channel window on the MXU, NATIVELY in either layout;
    the XLA reference leg (see SpatialCrossMapLRN's original profile
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


def _cml_fwd_xla(x, size, alpha, beta, k, layout="NCHW"):
    den = k + _band_apply(x * x, size, False, layout) * (alpha / size)
    return x * _pow(den, -beta), den


def _cml_bwd_xla(x, den, g, size, alpha, beta, layout="NCHW"):
    t = g * x * _pow(den, -beta - 1.0)
    return g * _pow(den, -beta) \
        - (2.0 * alpha * beta / size) * x \
        * _band_apply(t, size, True, layout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def cross_map_lrn(x, size: int, alpha: float, beta: float, k: float,
                  layout: str = "NCHW"):
    """AlexNet-style cross-channel LRN over NCHW/NHWC with exact custom
    VJP; backend (fused Pallas kernel vs XLA banded-conv reference)
    chosen by ``ops.dispatch`` — the NHWC reference runs in its native
    layout (no relayout transposes)."""
    y, _ = _cml_fwd(x, size, alpha, beta, k, layout)
    return y


def _cml_fwd(x, size, alpha, beta, k, layout):
    if layout == "NHWC":  # elementwise VJP math is layout-agnostic
        return _cml_fwd_xla(x, size, alpha, beta, k, layout)
    return _dispatch.dispatch(
        "lrn_cross_map.fwd", _cml_fwd_pallas, _cml_fwd_xla,
        cross_map_lrn_supported(x, size, layout), x, size, alpha, beta,
        k)


def _cml_vjp_fwd(x, size, alpha, beta, k, layout):
    y, den = _cml_fwd(x, size, alpha, beta, k, layout)
    return y, (x, den)


def _cml_vjp_bwd(size, alpha, beta, k, layout, res, g):
    x, den = res
    if layout == "NHWC":
        return (_cml_bwd_xla(x, den, g, size, alpha, beta, layout),)
    dx = _dispatch.dispatch(
        "lrn_cross_map.bwd", _cml_bwd_pallas, _cml_bwd_xla,
        cross_map_lrn_supported(x, size, layout), x, den, g, size,
        alpha, beta)
    return (dx,)


cross_map_lrn.defvjp(_cml_vjp_fwd, _cml_vjp_bwd)


# ---------------------------------------------------------------------------
# within-channel LRN: spatial-window sum, layout [N*C, Hpad, Wpad]
# ---------------------------------------------------------------------------

def within_channel_lrn_supported(x, size: int) -> bool:
    if x.ndim != 4 or size < 1:
        return False
    if _on_tpu_compiled():
        if x.dtype not in _TPU_DTYPES:
            return False
        h, w = x.shape[2], x.shape[3]
        hp, wp = h + size - 1, w + size - 1
        # ~4 live [Hp, Wp] planes per block (x, sq, accumulator, out)
        if 4 * hp * wp * jnp.dtype(x.dtype).itemsize > _VMEM_BUDGET:
            return False
    return True


def _wcl_fwd_kernel(xp_ref, y_ref, sc_ref, *, h: int, w: int, size: int,
                    lo: int, alpha: float, beta: float):
    xp = xp_ref[...]                    # [P, Hp, Wp]: a block of planes
    sq = xp * xp
    ws = None
    for dh in range(size):
        for dw in range(size):
            tap = sq[:, dh:dh + h, dw:dw + w]
            ws = tap if ws is None else ws + tap
    scale = 1.0 + ws * (alpha / (size * size))
    sc_ref[...] = scale
    y_ref[...] = xp[:, lo:lo + h, lo:lo + w] * _pow(scale, -beta)


def _wcl_bwd_kernel(tp_ref, x_ref, g_ref, sc_ref, dx_ref, *, h: int,
                    w: int, size: int, alpha: float, beta: float):
    tp = tp_ref[...]                    # transpose-padded t, [P, Hp, Wp]
    ts = None
    for dh in range(size):
        for dw in range(size):
            tap = tp[:, dh:dh + h, dw:dw + w]
            ts = tap if ts is None else ts + tap
    g = g_ref[...]
    x = x_ref[...]
    scale = sc_ref[...]
    dx_ref[...] = g * _pow(scale, -beta) \
        - (2.0 * alpha * beta / (size * size)) * x * ts


def _wcl_fwd_pallas(x, size, alpha, beta):
    n, c, h, w = x.shape
    lo, hi = (size - 1) // 2, size - 1 - (size - 1) // 2
    planes = x.reshape(n * c, h, w)
    xp = jnp.pad(planes, ((0, 0), (lo, hi), (lo, hi)))
    kern = functools.partial(_wcl_fwd_kernel, h=h, w=w, size=size, lo=lo,
                             alpha=alpha, beta=beta)
    y, scale = _plane_call(kern, [xp],
                           [((h, w), x.dtype), ((h, w), x.dtype)], n * c,
                           _dispatch.use_interpret())
    return y.reshape(n, c, h, w), scale.reshape(n, c, h, w)


def _wcl_bwd_pallas(x, scale, g, size, alpha, beta):
    n, c, h, w = x.shape
    lo, hi = (size - 1) // 2, size - 1 - (size - 1) // 2
    t = (g * x * _pow(scale, -beta - 1.0)).reshape(n * c, h, w)
    # TRANSPOSE pads (hi, lo): position m gathers windows o with
    # m in [o-lo, o+hi]  <=>  o in [m-hi, m+lo]
    tp = jnp.pad(t, ((0, 0), (hi, lo), (hi, lo)))
    flat = lambda a: a.reshape(n * c, h, w)  # noqa: E731
    kern = functools.partial(_wcl_bwd_kernel, h=h, w=w, size=size,
                             alpha=alpha, beta=beta)
    dx = _plane_call(kern, [tp, flat(x), flat(g), flat(scale)],
                     [((h, w), x.dtype)], n * c,
                     _dispatch.use_interpret())
    return dx.reshape(n, c, h, w)


def _win_sum(v, size: int, pads: Tuple[int, int]):
    dims = (1, 1, size, size)
    p = ((0, 0), (0, 0), pads, pads)
    return lax.reduce_window(v, jnp.zeros((), v.dtype), lax.add, dims,
                             (1, 1, 1, 1), p)


def _wcl_fwd_xla(x, size, alpha, beta):
    lo, hi = (size - 1) // 2, size - 1 - (size - 1) // 2
    scale = 1.0 + _win_sum(x * x, size, (lo, hi)) * (alpha / (size * size))
    return x * _pow(scale, -beta), scale


def _wcl_bwd_xla(x, scale, g, size, alpha, beta):
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


def _wcl_fwd(x, size, alpha, beta):
    return _dispatch.dispatch(
        "lrn_within_channel.fwd", _wcl_fwd_pallas, _wcl_fwd_xla,
        within_channel_lrn_supported(x, size), x, size, alpha, beta)


def _wcl_vjp_fwd(x, size, alpha, beta):
    y, scale = _wcl_fwd(x, size, alpha, beta)
    return y, (x, scale)


def _wcl_vjp_bwd(size, alpha, beta, res, g):
    x, scale = res
    dx = _dispatch.dispatch(
        "lrn_within_channel.bwd", _wcl_bwd_pallas, _wcl_bwd_xla,
        within_channel_lrn_supported(x, size), x, scale, g, size, alpha,
        beta)
    return (dx,)


within_channel_lrn.defvjp(_wcl_vjp_fwd, _wcl_vjp_bwd)
