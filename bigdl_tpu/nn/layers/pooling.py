"""Pooling layers (SURVEY §2.5: SpatialMaxPooling, SpatialAveragePooling,
TemporalMaxPooling, VolumetricMaxPooling, RoiPooling).

The reference's hand-written pooling loops (``nn/NNPrimitive.scala:594-972``)
become ``lax.reduce_window`` — XLA lowers these to fused VPU reductions.
Ceil-mode semantics (Torch) are reproduced with explicit asymmetric padding;
average-pooling divisors follow the reference exactly: declared padding
counts when ``count_include_pad`` but ceil-overflow padding never does
(``SpatialAveragePooling.scala:133-135`` clips the pool size at the
declared pad).
"""

from __future__ import annotations

import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.module import Module

__all__ = [
    "SpatialMaxPooling", "SpatialAveragePooling", "TemporalMaxPooling",
    "VolumetricMaxPooling", "VolumetricAveragePooling", "RoiPooling",
]


def _max_init(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return -jnp.inf
    return jnp.iinfo(dtype).min


def _pool_out_size(size: int, k: int, stride: int, pad: int, ceil_mode: bool) -> int:
    if ceil_mode:
        out = int(math.ceil(float(size - k + 2 * pad) / stride)) + 1
    else:
        out = int(math.floor(float(size - k + 2 * pad) / stride)) + 1
    if pad > 0 and (out - 1) * stride >= size + pad:
        out -= 1  # Torch: last window must start inside the (left-)padded input
    return out


def _axis_padding(size: int, k: int, stride: int, pad: int, ceil_mode: bool
                  ) -> Tuple[int, int, int]:
    """(lo, hi, declared_hi): hi includes ceil-overflow; declared_hi is the
    part of hi within the user-declared padding (counts toward the
    count_include_pad divisor)."""
    if pad == -1:  # SAME
        out = -(-size // stride)
        total = max(0, (out - 1) * stride + k - size)
        lo, hi = total // 2, total - total // 2
        return lo, hi, hi
    out = _pool_out_size(size, k, stride, pad, ceil_mode)
    needed = (out - 1) * stride + k
    hi = max(0, needed - size - pad)
    return pad, hi, min(hi, pad)


class _PoolBase(Module):
    """Shared window plumbing over the trailing spatial axes."""

    ceil_mode = False
    #: which maximum of a window with tied maxima takes its cotangent:
    #: False, the first in (kh, kw) order takes all of it (the
    #: reference's loop, ``nn/NNPrimitive.scala:594-972``; what
    #: ``reduce_window``'s select-and-scatter backward does); True, the
    #: tied positions share it equally (``ops/pool.py
    #: maxpool_tie_split``: gradient mass is conserved either way, but
    #: only this one is symmetric in the ties).
    tie_split = False

    def torch_ties(self):
        """First-argmax tie gradient (the reference's semantics) via
        XLA's native select-and-scatter lowering — the default."""
        self.tie_split = False
        return self

    def split_ties(self):
        """Equal-split tie gradient via the residue-class gather VJP
        (conserves gradient mass across tied maxima)."""
        self.tie_split = True
        return self

    def _axes_spec(self, ndim) -> List[Tuple[int, int, int, int]]:
        """[(axis, k, stride, pad), ...] — subclasses define."""
        raise NotImplementedError

    def _window(self, x):
        dims = [1] * x.ndim
        strides = [1] * x.ndim
        pads = [(0, 0)] * x.ndim
        declared = [(0, 0)] * x.ndim
        for ax, k, d, p in self._axes_spec(x.ndim):
            dims[ax], strides[ax] = k, d
            lo, hi, dh = _axis_padding(x.shape[ax], k, d, p, self.ceil_mode)
            pads[ax] = (lo, hi)
            declared[ax] = (lo, dh)
        return tuple(dims), tuple(strides), pads, declared

    #: largest window (taps per element) the unrolled tie-split backward
    #: may handle — beyond this (e.g. global pooling over a 56x56 map)
    #: the per-tap unroll would blow up compile time, and XLA's
    #: select-and-scatter is used instead
    _TIE_SPLIT_MAX_TAPS = 64

    def _max(self, x):
        dims, strides, pads, _ = self._window(x)
        taps = 1
        for d in dims:
            taps *= d
        if self.tie_split and taps <= self._TIE_SPLIT_MAX_TAPS \
                and jnp.issubdtype(x.dtype, jnp.floating):
            # ops/pool.py: exact equal-tie-split custom VJP
            from bigdl_tpu.ops.pool import maxpool_tie_split
            return maxpool_tie_split(x, dims, strides, tuple(pads))
        return lax.reduce_window(x, _max_init(x.dtype), lax.max, dims, strides, pads)

    def _avg(self, x, count_include_pad: bool, divide: bool = True):
        dims, strides, pads, declared = self._window(x)
        # ops/pool.py: the Torch divisor map (declared padding counts,
        # ceil-overflow never does) is a trace-time numpy constant
        # there, and the backward the exact linear transpose
        from bigdl_tpu.ops.pool import avg_pool
        return avg_pool(x, dims, strides, tuple(pads), tuple(declared),
                        count_include_pad, divide)


class SpatialMaxPooling(_PoolBase):
    """(``nn/SpatialMaxPooling.scala``); pad == -1 means SAME (per axis)."""

    def __init__(self, kw: int, kh: int, dw: Optional[int] = None, dh: Optional[int] = None,
                 pad_w: int = 0, pad_h: int = 0, format: str = "NCHW",
                 global_pooling: bool = False):
        super().__init__()
        self.kw, self.kh = kw, kh
        self.dw, self.dh = dw or kw, dh or kh
        self.pad_w, self.pad_h = pad_w, pad_h
        self.format = format
        self.ceil_mode = False
        self.global_pooling = global_pooling

    def ceil(self):
        self.ceil_mode = True
        return self

    def floor(self):
        self.ceil_mode = False
        return self

    def _axes_spec(self, ndim):
        if self.format == "NHWC":
            h_ax, w_ax = ndim - 3, ndim - 2
        else:
            h_ax, w_ax = ndim - 2, ndim - 1
        return [(h_ax, self.kh, self.dh, self.pad_h),
                (w_ax, self.kw, self.dw, self.pad_w)]

    def _apply_global(self, input):
        if self.global_pooling:
            spec = self._axes_spec(input.ndim)
            (h_ax, *_), (w_ax, *_) = spec
            self.kh, self.kw = input.shape[h_ax], input.shape[w_ax]
            self.dh, self.dw = self.kh, self.kw

    def update_output(self, input):
        self._apply_global(input)
        return self._max(input)


class SpatialAveragePooling(SpatialMaxPooling):
    """(``nn/SpatialAveragePooling.scala``)."""

    def __init__(self, kw: int, kh: int, dw: Optional[int] = None, dh: Optional[int] = None,
                 pad_w: int = 0, pad_h: int = 0, global_pooling: bool = False,
                 ceil_mode: bool = False, count_include_pad: bool = True,
                 divide: bool = True, format: str = "NCHW"):
        super().__init__(kw, kh, dw, dh, pad_w, pad_h, format)
        self.ceil_mode = ceil_mode
        self.global_pooling = global_pooling
        self.count_include_pad = count_include_pad
        self.divide = divide

    def update_output(self, input):
        self._apply_global(input)
        return self._avg(input, self.count_include_pad, self.divide)


class TemporalMaxPooling(_PoolBase):
    """1-D max pooling over [batch, time, feature]
    (``nn/TemporalMaxPooling.scala``)."""

    def __init__(self, k_w: int, d_w: Optional[int] = None):
        super().__init__()
        self.k_w, self.d_w = k_w, d_w or k_w

    def _axes_spec(self, ndim):
        return [(ndim - 2, self.k_w, self.d_w, 0)]

    def update_output(self, input):
        return self._max(input)


class VolumetricMaxPooling(_PoolBase):
    """3-D max pooling over [batch, C, T, H, W]
    (``nn/VolumetricMaxPooling.scala``)."""

    def __init__(self, k_t: int, k_w: int, k_h: int,
                 d_t: Optional[int] = None, d_w: Optional[int] = None, d_h: Optional[int] = None,
                 pad_t: int = 0, pad_w: int = 0, pad_h: int = 0):
        super().__init__()
        self.k_t, self.k_w, self.k_h = k_t, k_w, k_h
        self.d_t, self.d_w, self.d_h = d_t or k_t, d_w or k_w, d_h or k_h
        self.pad_t, self.pad_w, self.pad_h = pad_t, pad_w, pad_h
        self.ceil_mode = False

    def ceil(self):
        self.ceil_mode = True
        return self

    def _axes_spec(self, ndim):
        return [(ndim - 3, self.k_t, self.d_t, self.pad_t),
                (ndim - 2, self.k_h, self.d_h, self.pad_h),
                (ndim - 1, self.k_w, self.d_w, self.pad_w)]

    def update_output(self, input):
        return self._max(input)


class VolumetricAveragePooling(VolumetricMaxPooling):
    """(``nn/VolumetricAveragePooling.scala``)."""

    def __init__(self, k_t: int, k_w: int, k_h: int,
                 d_t: Optional[int] = None, d_w: Optional[int] = None, d_h: Optional[int] = None,
                 pad_t: int = 0, pad_w: int = 0, pad_h: int = 0,
                 count_include_pad: bool = True):
        super().__init__(k_t, k_w, k_h, d_t, d_w, d_h, pad_t, pad_w, pad_h)
        self.count_include_pad = count_include_pad

    def update_output(self, input):
        return self._avg(input, self.count_include_pad)


class RoiPooling(Module):
    """Region-of-interest max pooling (``nn/RoiPooling.scala``).  Input is a
    table (features [N,C,H,W], rois [R,5] of (batch_idx, x1, y1, x2, y2)).
    Implemented with dense masks per output cell so shapes stay static under
    jit (no data-dependent slicing on TPU)."""

    def __init__(self, pooled_w: int, pooled_h: int, spatial_scale: float = 1.0):
        super().__init__()
        self.pooled_w, self.pooled_h = pooled_w, pooled_h
        self.spatial_scale = spatial_scale

    def update_output(self, input):
        data, rois = input
        n, c, h, w = data.shape

        def pool_one(roi):
            b = roi[0].astype(jnp.int32)
            x1 = jnp.round(roi[1] * self.spatial_scale).astype(jnp.int32)
            y1 = jnp.round(roi[2] * self.spatial_scale).astype(jnp.int32)
            x2 = jnp.round(roi[3] * self.spatial_scale).astype(jnp.int32)
            y2 = jnp.round(roi[4] * self.spatial_scale).astype(jnp.int32)
            roi_w = jnp.maximum(x2 - x1 + 1, 1)
            roi_h = jnp.maximum(y2 - y1 + 1, 1)
            bin_w = roi_w.astype(jnp.float32) / self.pooled_w
            bin_h = roi_h.astype(jnp.float32) / self.pooled_h
            feat = data[b]  # (C, H, W)

            ys = jnp.arange(h)
            xs = jnp.arange(w)

            def cell(py, px):
                hstart = jnp.floor(py * bin_h).astype(jnp.int32) + y1
                hend = jnp.ceil((py + 1) * bin_h).astype(jnp.int32) + y1
                wstart = jnp.floor(px * bin_w).astype(jnp.int32) + x1
                wend = jnp.ceil((px + 1) * bin_w).astype(jnp.int32) + x1
                hstart, hend = jnp.clip(hstart, 0, h), jnp.clip(hend, 0, h)
                wstart, wend = jnp.clip(wstart, 0, w), jnp.clip(wend, 0, w)
                mask = ((ys[:, None] >= hstart) & (ys[:, None] < hend)
                        & (xs[None, :] >= wstart) & (xs[None, :] < wend))
                empty = (hend <= hstart) | (wend <= wstart)
                masked = jnp.where(mask[None, :, :], feat, -jnp.inf)
                val = jnp.max(masked, axis=(1, 2))
                return jnp.where(empty, 0.0, val)

            py = jnp.arange(self.pooled_h)
            px = jnp.arange(self.pooled_w)
            return jax.vmap(lambda y: jax.vmap(lambda x: cell(y, x))(px))(py).transpose(2, 0, 1)

        return jax.vmap(pool_one)(rois.astype(jnp.float32))
