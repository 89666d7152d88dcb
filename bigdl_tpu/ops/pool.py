"""Tie-split max pooling + Torch-semantics average pooling under exact
custom VJPs, in plain ``jnp``/``lax``: one form on every platform.

Two ops the autodiff path got subtly wrong:

- ``maxpool_tie_split``: max pooling whose gradient is split EQUALLY
  among tied maxima (gradient mass conserved — the reference's
  ``split_ties()`` contract, vs select-and-scatter's first-argmax).
  The backward compares every window tap against the window max and
  divides by the tie count; written as the transpose of the window
  gather XLA makes k*k interior-pad scatters of it, so it is a
  residue-class gather instead (``_tie_bwd``).
- ``avg_pool``: Torch ceil-mode average pooling with the asymmetric
  declared-vs-overflow divisor (declared padding counts toward the
  divisor under ``count_include_pad``; ceil-overflow padding never
  does).  The divisor map is pure geometry, computed in numpy at trace
  time (a separable outer product) and baked in as a constant —
  forward is one windowed sum times it, backward the closed-form
  transpose of the window sum applied to ``gy / counts``.  A window
  that IS the padded plane (a head pool) is a float32 sum times the one
  divisor and a broadcast back, which XLA fuses into its neighbours
  (PERF.md, PR 41).

Residue-class geometry of the tie-split backward: padded input
positions split into ``stride`` residue classes per axis; within a
class the windows touching a position are a fixed ``ceil(k/s)`` set of
plain shifts on the output grid, so every slice is static.

Both ops take any rank (temporal / volumetric pooling) and announce
themselves as ``kernel/dispatch backend=xla reason=only-leg`` (the
head pool as ``whole-plane``): ``BIGDL_KERNELS`` does not reach them.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.ops import dispatch as _dispatch

__all__ = ["maxpool_tie_split", "avg_pool"]

# ---------------------------------------------------------------------------
# tie-split max pooling
# ---------------------------------------------------------------------------

def _max_init(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return -jnp.inf
    return jnp.iinfo(dtype).min


def _tie_bwd(x, y, gy, dims, strides, pads):
    """Residue-class gather backward (in place of the k*k interior-pad
    transpose: one fused kernel per residue class, not one
    strided-write kernel per tap)."""
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
    conserved across tied maxima); any ndim."""
    _dispatch.note("pool_tie_split.fwd", "xla", "only-leg")
    return lax.reduce_window(x, _max_init(x.dtype), lax.max, dims, strides,
                             pads)


def _tie_vjp_fwd(x, dims, strides, pads):
    y = maxpool_tie_split(x, dims, strides, pads)
    return y, (x, y)


def _tie_vjp_bwd(dims, strides, pads, res, gy):
    x, y = res
    _dispatch.note("pool_tie_split.bwd", "xla", "only-leg")
    return (_tie_bwd(x, y, gy, dims, strides, pads),)


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


def _avg_bwd(wgt, x_shape, dims, strides, pads, dtype):
    """Exact linear transpose of the strided window sum, closed form:
    interior-dilate the out-grid weights by the strides, edge-pad by
    k-1, window-sum with stride 1 — then every padded input position q
    reads exactly the windows containing it (``sum_{o: o*s <= q <
    o*s+k} wgt[o]``); slice off the declared padding.  A floor-mode
    window grid can stop short of the padded extent: the tail no window
    reaches is padded on too, and reads zero."""
    cfg = [(k - 1, k - 1 + max(0, lo + n - (o - 1) * s - k), s - 1)
           for k, s, (lo, _), n, o
           in zip(dims, strides, pads, x_shape, wgt.shape)]
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
    window is one float32 reduction; any other, of any ndim, the window
    sum times the divisor map."""
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
    _dispatch.note("pool_avg.fwd", "xla", "only-leg")
    return lax.reduce_window(x, jnp.zeros((), x.dtype), lax.add, dims,
                             strides, pads) * jnp.asarray(inv, x.dtype)


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
    _dispatch.note("pool_avg.bwd", "xla", "only-leg")
    return (_avg_bwd(wgt, x_shape, dims, strides, pads, x_dtype),)


avg_pool.defvjp(_avg_vjp_fwd, _avg_vjp_bwd)
