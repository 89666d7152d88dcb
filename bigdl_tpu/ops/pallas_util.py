"""Shared plumbing for the Pallas kernel modules: the ONE home for the
VMEM budget, the Mosaic dtype set, and the plane-stack launcher — so the
support predicates in lrn_pallas/norm_pallas/pool_pallas can never
drift apart (a budget tuned in one module but not another would route
the same shape to different backends per op)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import dispatch as _dispatch

__all__ = ["VMEM_BUDGET", "TPU_DTYPES", "mosaic_dtype", "planes_per_block",
           "plane_call"]

#: per-block VMEM budget (bytes) — conservative vs the 16 MB/core arena
VMEM_BUDGET = 4 * 1024 * 1024

#: dtypes Mosaic compiles; anything else (f64 in the numeric-grad
#: suite) is interpret/XLA-only
TPU_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


def mosaic_dtype(dtype) -> bool:
    return dtype in TPU_DTYPES


def _tiled_bytes(shape, dtype) -> int:
    """VMEM bytes of one block of ``shape``: the two trailing axes are
    laid out in tiles of 8 x 128 32-bit words ((8, 128) float32,
    (16, 128) bfloat16), so a 7x7 or 13x13 bfloat16 plane holds 4 KB,
    not 98 or 338 bytes."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    *lead, rows, cols = (1, 1) + tuple(shape)
    return math.prod(lead) * -(-rows // sublanes) * sublanes \
        * -(-cols // 128) * 128 * itemsize


def planes_per_block(planes, b: int) -> int:
    """How many of ``b`` planes share one grid step: as many as fit
    ``VMEM_BUDGET`` when every per-plane input and output (``planes``:
    [(per-plane shape, dtype), ...]) is held tile-rounded and twice, for
    the pipeline's two buffers.  A large plane (a 512x512 image, a
    [C, HW] LRN slab) comes out at 1; a 7x7 plane at 256."""
    per_plane = 2 * sum(_tiled_bytes(s, d) for s, d in planes)
    return max(1, min(b, VMEM_BUDGET // per_plane))


def plane_call(kernel, inputs, out_shapes, b, interpret: bool,
               bcast=()):
    """Launcher over [B, *, *] plane stacks: a grid step takes a block
    of P whole (padded) planes, ``P = planes_per_block(...)`` read from
    the planes' shapes alone, and the grid is ``(ceil(B / P),)`` — P need
    not divide B: the last block is ragged, its rows past B are padding
    on the way in and dropped on the way out, and since no kernel mixes
    planes they touch nothing.  Whole planes, so spatial windows need no
    neighbor blocks; many of them, so a small plane does not pay a grid
    step's fixed cost (~0.35 us) for 4 KB of transfer.  Where P comes
    out 1 the program is the one-plane-a-step launch it always was.
    A plane stack is ``{2,1,0}`` with each plane tile-rounded, so a
    caller whose planes are far smaller than a tile pays the layout
    copies in and out as well: the average pool whose window is the
    whole plane does not come here (``pool_pallas.avg_pool``).

    ``kernel`` sees refs of shape ``(P,) + plane`` and works on every
    plane of the block at once (leading axis).  ``inputs``: arrays whose
    leading dim is B, except indices listed in ``bcast`` which are shared
    by every block verbatim (divisor planes, smoothing kernels).
    ``out_shapes``: [(per-plane shape, dtype), ...] — a single entry
    returns the bare array.  The launch reports itself to the dispatch
    decision being taken (``planes_per_block``, ``grid``)."""
    from jax.experimental import pallas as pl

    p = planes_per_block(
        [(a.shape[1:], a.dtype) for idx, a in enumerate(inputs)
         if idx not in bcast] + list(out_shapes), b)
    grid = (-(-b // p),)
    _dispatch.launched(planes_per_block=p, grid=grid)

    in_specs = []
    for idx, a in enumerate(inputs):
        if idx in bcast:
            in_specs.append(
                pl.BlockSpec(a.shape, lambda i, nd=a.ndim: (0,) * nd))
        else:
            in_specs.append(
                pl.BlockSpec((p,) + a.shape[1:],
                             lambda i, nd=a.ndim: (i,) + (0,) * (nd - 1)))
    multi = len(out_shapes) > 1
    out_specs = [pl.BlockSpec((p,) + s, lambda i, nd=len(s): (i,) + (0,) * nd)
                 for s, _ in out_shapes]
    out_shape = [jax.ShapeDtypeStruct((b,) + s, d) for s, d in out_shapes]
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=out_specs if multi else out_specs[0],
        out_shape=out_shape if multi else out_shape[0],
        interpret=interpret,
    )(*inputs)
