"""Tie-split max pooling + Torch-semantics average pooling as fused
kernels with exact custom VJPs.

Two ops the autodiff path got subtly wrong and XLA lowers expensively:

- ``maxpool_tie_split``: max pooling whose gradient is split EQUALLY
  among tied maxima (gradient mass conserved — the reference's
  ``split_ties()`` contract, vs select-and-scatter's first-argmax).
  The backward must compare every window tap against the window max
  and divide by the tie count; XLA expresses that as k*k interior-pad
  scatter kernels (the ~50%-of-Inception-step pathology the
  residue-class rewrite in PR-era ``nn/layers/pooling.py`` addressed).
  Here the whole backward — tie count, weight, residue-class gather,
  stride interleave — is ONE Pallas pass over the (n, c) planes.
- ``avg_pool``: Torch ceil-mode average pooling with the asymmetric
  declared-vs-overflow divisor (declared padding counts toward the
  divisor under ``count_include_pad``; ceil-overflow padding never
  does).  The divisor map is pure geometry, computed in numpy at trace
  time (a separable outer product) and baked into the kernel as a
  constant — forward is one windowed-sum pass, backward one
  residue-class scatter of ``gy / counts``.  A window that IS the
  padded plane (a head pool) is no kernel: a float32 sum times the one
  divisor and a broadcast back, plain ``jnp`` in every mode, which XLA
  fuses into its neighbours; as a plane kernel a 98-byte 7x7 plane took
  a 4 KB tile and two layout copies around the call (PERF.md, PR 41).

Residue-class geometry (shared with ``ops/pooling_pallas.py``'s argmax
kernel and the XLA reference leg): padded input positions split into
``stride`` residue classes per axis; within a class the windows
touching a position are a fixed ``ceil(k/s)`` set of plain shifts on
the output grid, so every slice in the kernel is static.  The output
grid is extended by ``jmax = ceil(k/s)-1`` leading rows so no shift
ever indexes negative — those rows are provably pad and are cut by the
final slice.

Both ops run their XLA reference legs for non-4D inputs (temporal /
volumetric pooling) and under ``BIGDL_KERNELS=xla``; the custom VJP is
identical math on either leg.  The avg-pool XLA backward is the true
linear transpose of ``reduce_window(add)`` (obtained via ``jax.vjp`` of
the window sum — exact, since the op is linear in x).
"""

from __future__ import annotations

import functools
import itertools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.ops import dispatch as _dispatch
from bigdl_tpu.ops.pallas_util import (TPU_DTYPES as _TPU_DTYPES,
                                       VMEM_BUDGET as _VMEM_BUDGET,
                                       plane_call as _shared_plane_call)

__all__ = ["maxpool_tie_split", "avg_pool", "pool_plane_supported"]

#: beyond this tap count the unrolled shift structure bloats compile
#: time (global-pool-sized windows) — XLA select-and-scatter territory
_MAX_TAPS = 64


def _axis_geom(n: int, k: int, s: int, lo: int, hi: int):
    """(P, out, L, jmax, M) per axis: padded extent, output size,
    residue-class length, max window shift, extended out-grid length."""
    p = lo + n + hi
    out = (p - k) // s + 1
    l = -(-p // s)
    jmax = -(-k // s) - 1
    return p, out, l, jmax, jmax + l


def pool_plane_supported(x, dims, strides) -> bool:
    """Pallas-leg gate: 4-D with the window on the trailing (H, W)
    axes, bounded taps; on real TPU also a Mosaic dtype, a stride-1
    window and a VMEM fit."""
    if x.ndim != 4 or dims[0] != 1 or dims[1] != 1:
        return False
    if strides[0] != 1 or strides[1] != 1:
        return False
    if dims[2] * dims[3] > _MAX_TAPS:
        return False
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return False
    if not _dispatch.use_interpret():
        if x.dtype not in _TPU_DTYPES:
            return False
        if strides[2] != 1 or strides[3] != 1:
            # Mosaic has no strided vector slice ("expected strides to
            # be confined to [1, 2)"), which _taps and the residue
            # gather need for a strided window: XLA leg on the chip
            return False
        esz = jnp.dtype(x.dtype).itemsize
        # ~10 live planes: padded input, padded y/gy, tie count, weight,
        # residue accumulators and the interleave stack
        if 10 * (x.shape[2] + dims[2]) * (x.shape[3] + dims[3]) \
                * max(1, esz) * 4 > _VMEM_BUDGET:
            return False
    return True


# ---------------------------------------------------------------------------
# Pallas kernels: a ref is a block of P whole planes, [P, rows, cols],
# and a body works on all P at once along the leading axis; the grid is
# ceil(N*C / P), P chosen by ``pallas_util.plane_call`` from the plane
# shape (256 for a 6x6 or 7x7 plane, 1 for a large one)
# ---------------------------------------------------------------------------

def _taps(xp, k2, s2, out2):
    """All window taps of a block of padded planes as strided
    [P, out_h, out_w] views — static slices only."""
    (kh, kw), (sh, sw), (oh, ow) = k2, s2, out2
    p = xp.shape[0]
    for dh in range(kh):
        for dw in range(kw):
            yield lax.slice(xp, (0, dh, dw),
                            (p, dh + (oh - 1) * sh + 1,
                             dw + (ow - 1) * sw + 1), (1, sh, sw))


def _interleave(parts, s2, l2):
    """[sh][sw] residue blocks of shape [P, Lh, Lw] -> [P, Lh*sh,
    Lw*sw]."""
    (sh, sw), (lh, lw) = s2, l2
    rows = []
    for rh in range(sh):
        cols = parts[rh]
        if sw == 1:
            rows.append(cols[0])
        else:
            rows.append(jnp.stack(cols, axis=3).reshape(-1, lh, lw * sw))
    if sh == 1:
        return rows[0]
    return jnp.stack(rows, axis=2).reshape(-1, lh * sh, rows[0].shape[2])


def _maxpool_fwd_kernel(xp_ref, y_ref, *, k2, s2, out2):
    xp = xp_ref[...]
    y = None
    for tap in _taps(xp, k2, s2, out2):
        y = tap if y is None else jnp.maximum(y, tap)
    y_ref[...] = y


def _tie_bwd_kernel(xp_ref, yp_ref, gp_ref, dx_ref, *, k2, s2, l2, m2,
                    j2, lo2, n2):
    """Each plane: tie count -> equal-split weight -> residue gather."""
    (kh, kw), (sh, sw) = k2, s2
    (lh, lw), (mh, mw) = l2, m2
    (jh_max, jw_max), (lo_h, lo_w), (h, w) = j2, lo2, n2
    xp = xp_ref[...]
    yp = yp_ref[...]
    gp = gp_ref[...]
    p = xp.shape[0]

    cnt = None
    for tap in _taps(xp, k2, s2, (mh, mw)):
        e = (tap == yp).astype(gp.dtype)
        cnt = e if cnt is None else cnt + e
    wgt = jnp.where(cnt > 0, gp / jnp.where(cnt > 0, cnt, 1), 0.0)

    parts = []
    for rh in range(sh):
        cols = []
        for rw in range(sw):
            xr = lax.slice(xp, (0, rh + jh_max * sh, rw + jw_max * sw),
                           (p, rh + jh_max * sh + (lh - 1) * sh + 1,
                            rw + jw_max * sw + (lw - 1) * sw + 1),
                           (1, sh, sw))
            acc = jnp.zeros((p, lh, lw), gp.dtype)
            for jh in range(-(-(kh - rh) // sh)):
                if rh + sh * jh >= kh:
                    continue
                for jw in range(-(-(kw - rw) // sw)):
                    if rw + sw * jw >= kw:
                        continue
                    yj = yp[:, jh_max - jh:jh_max - jh + lh,
                            jw_max - jw:jw_max - jw + lw]
                    wj = wgt[:, jh_max - jh:jh_max - jh + lh,
                             jw_max - jw:jw_max - jw + lw]
                    acc = acc + jnp.where(xr == yj, wj, 0.0)
            cols.append(acc)
        parts.append(cols)
    dxp = _interleave(parts, s2, l2)
    dx_ref[...] = dxp[:, lo_h:lo_h + h, lo_w:lo_w + w]


def _avg_fwd_kernel(xp_ref, inv_ref, y_ref, *, k2, s2, out2):
    s = None
    for tap in _taps(xp_ref[...], k2, s2, out2):
        s = tap if s is None else s + tap
    y_ref[...] = s * inv_ref[...]


def _avg_bwd_kernel(wp_ref, dx_ref, *, k2, s2, l2, j2, lo2, n2):
    (kh, kw), (sh, sw) = k2, s2
    (lh, lw) = l2
    (jh_max, jw_max), (lo_h, lo_w), (h, w) = j2, lo2, n2
    wp = wp_ref[...]
    p = wp.shape[0]
    parts = []
    for rh in range(sh):
        cols = []
        for rw in range(sw):
            acc = jnp.zeros((p, lh, lw), wp.dtype)
            for jh in range(-(-(kh - rh) // sh)):
                if rh + sh * jh >= kh:
                    continue
                for jw in range(-(-(kw - rw) // sw)):
                    if rw + sw * jw >= kw:
                        continue
                    acc = acc + wp[:, jh_max - jh:jh_max - jh + lh,
                                   jw_max - jw:jw_max - jw + lw]
            cols.append(acc)
        parts.append(cols)
    dxp = _interleave(parts, s2, l2)
    dx_ref[...] = dxp[:, lo_h:lo_h + h, lo_w:lo_w + w]


def _plane_call(kernel, inputs, out_hw, b, dtype, bcast=()):
    """Thin adapter onto the shared plane launcher
    (``ops/pallas_util.py``) — single [out_hw, dtype] output."""
    return _shared_plane_call(kernel, inputs, [(out_hw, dtype)], b,
                              _dispatch.use_interpret(), bcast=bcast)


def _hw_geom(x_shape, dims, strides, pads):
    h, w = x_shape[2], x_shape[3]
    kh, kw, sh, sw = dims[2], dims[3], strides[2], strides[3]
    gh = _axis_geom(h, kh, sh, *pads[2])
    gw = _axis_geom(w, kw, sw, *pads[3])
    return (kh, kw), (sh, sw), gh, gw


def _pad_out_grid(v, geom_h, geom_w, out_h, out_w, fill=0.0):
    """Pad an out-grid plane stack to the extended [M_h, M_w] grid:
    jmax leading rows/cols (shift room), residue tail trailing."""
    _, _, lh, jh, mh = geom_h
    _, _, lw, jw, mw = geom_w
    return jnp.pad(v, ((0, 0), (jh, mh - jh - out_h),
                       (jw, mw - jw - out_w)), constant_values=fill)


# ---------------------------------------------------------------------------
# tie-split max pooling
# ---------------------------------------------------------------------------

def _max_init(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return -jnp.inf
    return jnp.iinfo(dtype).min


def _tie_fwd_pallas(x, dims, strides, pads):
    n, c, h, w = x.shape
    k2, s2, gh, gw = _hw_geom(x.shape, dims, strides, pads)
    (ph, oh, *_), (pw, ow, *_) = gh, gw
    (lo_h, _), (lo_w, _) = pads[2], pads[3]
    planes = x.reshape(n * c, h, w)
    xp = jnp.pad(planes, ((0, 0), (lo_h, ph - lo_h - h),
                          (lo_w, pw - lo_w - w)),
                 constant_values=_max_init(x.dtype))
    kern = functools.partial(_maxpool_fwd_kernel, k2=k2, s2=s2,
                             out2=(oh, ow))
    y = _plane_call(kern, [xp], (oh, ow), n * c, x.dtype)
    return y.reshape(n, c, oh, ow)


def _tie_bwd_pallas(x, y, gy, dims, strides, pads):
    n, c, h, w = x.shape
    k2, s2, gh, gw = _hw_geom(x.shape, dims, strides, pads)
    (ph, oh, lh, jh_max, mh), (pw, ow, lw, jw_max, mw) = gh, gw
    (sh, sw) = s2
    (lo_h, _), (lo_w, _) = pads[2], pads[3]
    b = n * c
    # extended padded input: jmax*s extra leading -inf so the extended
    # out grid's windows all read in range; trailing out to the largest
    # static tap/residue slice
    xlen_h = max((mh - 1) * sh + k2[0], mh * sh)
    xlen_w = max((mw - 1) * sw + k2[1], mw * sw)
    top_h, top_w = lo_h + jh_max * sh, lo_w + jw_max * sw
    xp = jnp.pad(x.reshape(b, h, w),
                 ((0, 0), (top_h, xlen_h - top_h - h),
                  (top_w, xlen_w - top_w - w)),
                 constant_values=_max_init(x.dtype))
    yp = _pad_out_grid(y.reshape(b, oh, ow), gh, gw, oh, ow)
    gp = _pad_out_grid(gy.reshape(b, oh, ow), gh, gw, oh, ow)
    kern = functools.partial(
        _tie_bwd_kernel, k2=k2, s2=s2, l2=(lh, lw), m2=(mh, mw),
        j2=(jh_max, jw_max), lo2=(lo_h, lo_w), n2=(h, w))
    dx = _plane_call(kern, [xp, yp, gp], (h, w), b, gy.dtype)
    return dx.reshape(n, c, h, w).astype(x.dtype)


def _tie_bwd_xla(x, y, gy, dims, strides, pads):
    """Residue-class gather backward on the XLA leg (the PR-era rewrite
    of the k*k interior-pad transpose — one fused kernel per residue
    class instead of one strided-write kernel per tap)."""
    nd = x.ndim
    zero = jnp.zeros((), gy.dtype)
    P = [lo + n + hi for (lo, hi), n in zip(pads, x.shape)]
    L = [-(-p // s) for p, s in zip(P, strides)]
    xpad = [(lo, l * s - lo - n)
            for (lo, _), n, s, l in zip(pads, x.shape, strides, L)]
    xp = jnp.pad(x, xpad, constant_values=_max_init(x.dtype))

    cnt = None
    for off in itertools.product(*[range(d) for d in dims]):
        limits = [o + (n - 1) * s + 1
                  for o, n, s in zip(off, y.shape, strides)]
        e = (lax.slice(xp, off, limits, strides) == y).astype(gy.dtype)
        cnt = e if cnt is None else cnt + e
    wgt = gy / cnt

    parts = []
    for r in itertools.product(*[range(s) for s in strides]):
        xr = lax.slice(xp, r,
                       [ri + (l - 1) * s + 1
                        for ri, l, s in zip(r, L, strides)], strides)
        m = [max(0, -(-(k - ri) // s))
             for k, ri, s in zip(dims, r, strides)]
        acc = None
        for j in itertools.product(*[range(mi) for mi in m]):
            cfg = [(ji, li - oi - ji, 0)
                   for ji, li, oi in zip(j, L, y.shape)]
            yj = lax.pad(y, jnp.zeros((), y.dtype), cfg)
            wj = lax.pad(wgt, zero, cfg)
            t = jnp.where(xr == yj, wj, zero)
            acc = t if acc is None else acc + t
        parts.append(acc if acc is not None else jnp.zeros(L, gy.dtype))

    if len(parts) == 1:
        gxp = parts[0]
    else:
        d = jnp.stack(parts, axis=-1).reshape(tuple(L) + tuple(strides))
        perm = []
        for ax in range(nd):
            perm += [ax, nd + ax]
        gxp = d.transpose(perm).reshape(
            [l * s for l, s in zip(L, strides)])
    gx = lax.slice(gxp, [lo for lo, _ in pads],
                   [lo + n for (lo, _), n in zip(pads, x.shape)])
    return gx.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def maxpool_tie_split(x, dims, strides, pads):
    """Max pooling with the equal-tie-split exact gradient (mass
    conserved across tied maxima); any ndim on the XLA leg, fused
    per-plane Pallas kernels for 4-D trailing-(H, W) windows."""
    return _dispatch.dispatch(
        "pool_tie_split.fwd", _tie_fwd_pallas,
        lambda x, d, s, p: lax.reduce_window(
            x, _max_init(x.dtype), lax.max, d, s, p),
        pool_plane_supported(x, dims, strides), x, dims, strides, pads)


def _tie_vjp_fwd(x, dims, strides, pads):
    y = maxpool_tie_split(x, dims, strides, pads)
    return y, (x, y)


def _tie_vjp_bwd(dims, strides, pads, res, gy):
    x, y = res
    dx = _dispatch.dispatch(
        "pool_tie_split.bwd", _tie_bwd_pallas, _tie_bwd_xla,
        pool_plane_supported(x, dims, strides), x, y, gy, dims, strides,
        pads)
    return (dx,)


maxpool_tie_split.defvjp(_tie_vjp_fwd, _tie_vjp_bwd)


# ---------------------------------------------------------------------------
# average pooling (Torch divisor semantics)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _np_inv_counts(shape, dims, strides, pads, declared,
                   count_include_pad: bool):
    """Trace-constant reciprocal divisor map, broadcast-shaped: per
    windowed axis, the overlap of each window with the counted region —
    data plus declared padding under ``count_include_pad``
    (ceil-overflow padding never counts:
    ``SpatialAveragePooling.scala:133-135``), data only otherwise.
    Separable, so the map is an outer product over the windowed axes
    with extent 1 on the rest (broadcasts against the pooled output)."""
    axis_counts = []
    bshape = []
    for n, k, s, (lo, hi), (dlo, dhi) in zip(shape, dims, strides, pads,
                                             declared):
        p = lo + n + hi
        out = (p - k) // s + 1
        if k == 1 and s == 1 and lo == 0 and hi == 0:
            bshape.append(1)
            continue
        if count_include_pad:
            start, end = 0, dlo + n + dhi  # declared lo == lo always
        else:
            start, end = lo, lo + n
        o = np.arange(out)
        cnt = (np.minimum(o * s + k, end)
               - np.maximum(o * s, start)).clip(min=0)
        axis_counts.append(cnt.astype(np.float64))
        bshape.append(out)
    if not axis_counts:
        return np.ones(bshape)
    counts = functools.reduce(np.multiply.outer, axis_counts)
    return (1.0 / np.maximum(counts, 1.0)).reshape(bshape)


def _avg_fwd_pallas(x, dims, strides, pads, inv):
    n, c, h, w = x.shape
    k2, s2, gh, gw = _hw_geom(x.shape, dims, strides, pads)
    (ph, oh, *_), (pw, ow, *_) = gh, gw
    (lo_h, _), (lo_w, _) = pads[2], pads[3]
    planes = x.reshape(n * c, h, w)
    xp = jnp.pad(planes, ((0, 0), (lo_h, ph - lo_h - h),
                          (lo_w, pw - lo_w - w)))
    kern = functools.partial(_avg_fwd_kernel, k2=k2, s2=s2,
                             out2=(oh, ow))
    y = _plane_call(kern, [xp, jnp.asarray(inv[0, 0], x.dtype)[None]],
                    (oh, ow), n * c, x.dtype, bcast=(1,))
    return y.reshape(n, c, oh, ow)


def _avg_bwd_pallas(wgt, x_shape, dims, strides, pads, dtype):
    n, c, h, w = x_shape
    b = n * c
    k2, s2, gh, gw = _hw_geom(x_shape, dims, strides, pads)
    (_, oh, lh, jh_max, _), (_, ow, lw, jw_max, _) = gh, gw
    (lo_h, _), (lo_w, _) = pads[2], pads[3]
    wp = _pad_out_grid(wgt.reshape(b, oh, ow), gh, gw, oh, ow)
    kern = functools.partial(
        _avg_bwd_kernel, k2=k2, s2=s2, l2=(lh, lw),
        j2=(jh_max, jw_max), lo2=(lo_h, lo_w), n2=(h, w))
    dx = _plane_call(kern, [wp], (h, w), b, wgt.dtype)
    return dx.reshape(n, c, h, w).astype(dtype)


def _avg_bwd_xla(wgt, x_shape, dims, strides, pads, dtype):
    """Exact linear transpose of the strided window sum, closed form:
    interior-dilate the out-grid weights by the strides, edge-pad by
    k-1, window-sum with stride 1 — then every padded input position q
    reads exactly the windows containing it (``sum_{o: o*s <= q <
    o*s+k} wgt[o]``); slice off the declared padding."""
    cfg = [(k - 1, k - 1, s - 1) for k, s in zip(dims, strides)]
    dil = lax.pad(wgt, jnp.zeros((), wgt.dtype), cfg)
    full = lax.reduce_window(dil, jnp.zeros((), wgt.dtype), lax.add,
                             dims, (1,) * len(dims),
                             ((0, 0),) * len(dims))
    dx = lax.slice(full, [lo for lo, _ in pads],
                   [lo + n for (lo, _), n in zip(pads, x_shape)])
    return dx.astype(dtype)


def _whole_plane_axes(shape, dims, strides, pads):
    """The axes of a window that IS the padded extent of every axis it
    touches (one output a plane); None where it slides along one."""
    axes = []
    for a, (n, k, s, p) in enumerate(zip(shape, dims, strides, pads)):
        if k == n + sum(p):
            axes.append(a)
        elif (k, s) + tuple(p) != (1, 1, 0, 0):
            return None
    return tuple(axes) or None


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def avg_pool(x, dims, strides, pads, declared, count_include_pad: bool,
             divide: bool):
    """Torch-semantics average pooling (declared-vs-overflow divisors,
    ceil mode via the caller's asymmetric ``pads``) with exact custom
    VJP; ``divide=False`` returns the plain window sum.  A whole-plane
    window is one float32 reduction in every mode; any other: any ndim
    on the XLA leg, fused per-plane Pallas kernels for 4-D
    trailing-(H, W) windows."""
    # divide is a nondiff_argnum: a static Python bool at trace time,
    # not a tracer — the branch is resolved per compilation
    if not divide:  # noqa: lint/tracer-branch
        return lax.reduce_window(x, jnp.zeros((), x.dtype), lax.add,
                                 dims, strides, pads)
    inv = _np_inv_counts(x.shape, tuple(dims), tuple(strides),
                         tuple(pads), tuple(declared), count_include_pad)
    whole = _whole_plane_axes(x.shape, dims, strides, pads)
    if whole:  # noqa: lint/tracer-branch (shapes: static)
        # the padding adds zeros and there is one divisor
        _dispatch.note("pool_avg.fwd", "xla", "whole-plane")
        total = jnp.sum(x, axis=whole, keepdims=True,
                        dtype=jnp.promote_types(x.dtype, jnp.float32))
        return (total * inv.item()).astype(x.dtype)
    supported = pool_plane_supported(x, dims, strides) \
        and inv.shape[:2] == (1, 1)
    return _dispatch.dispatch(
        "pool_avg.fwd", _avg_fwd_pallas,
        lambda x, d, s, p, i: lax.reduce_window(
            x, jnp.zeros((), x.dtype), lax.add, d, s, p)
        * jnp.asarray(i, x.dtype),
        supported, x, dims, strides, pads, inv)


def _avg_vjp_fwd(x, dims, strides, pads, declared, count_include_pad,
                 divide):
    y = avg_pool(x, dims, strides, pads, declared, count_include_pad,
                 divide)
    # the backward needs only x's shape/dtype (the op is linear in x) —
    # a zero-length leading axis encodes both at zero residual memory
    return y, jnp.zeros((0,) + x.shape, x.dtype)


def _avg_vjp_bwd(dims, strides, pads, declared, count_include_pad,
                 divide, res, gy):
    x_shape, x_dtype = res.shape[1:], res.dtype
    inv = _np_inv_counts(x_shape, tuple(dims), tuple(strides),
                         tuple(pads), tuple(declared),
                         count_include_pad) if divide else np.ones(())
    if _whole_plane_axes(x_shape, dims, strides, pads):
        # every position is in the one window: a broadcast
        _dispatch.note("pool_avg.bwd", "xla", "whole-plane")
        wgt = gy.astype(jnp.promote_types(gy.dtype, jnp.float32))
        return (jnp.broadcast_to((wgt * inv.item()).astype(x_dtype),
                                 x_shape),)
    wgt = gy * jnp.asarray(inv, gy.dtype) if divide else gy
    return (_dispatch.dispatch(
        "pool_avg.bwd", _avg_bwd_pallas, _avg_bwd_xla,
        pool_plane_supported(jax.ShapeDtypeStruct(x_shape, x_dtype),
                             dims, strides),
        wgt, x_shape, dims, strides, pads, x_dtype),)


avg_pool.defvjp(_avg_vjp_fwd, _avg_vjp_bwd)
