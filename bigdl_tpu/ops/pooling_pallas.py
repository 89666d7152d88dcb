"""Argmax-index max-pool: packed-u32 XLA forward + Pallas scatter backward.

Why this exists (round-5 TPU profile, Inception-v1 train step): XLA's
select-and-scatter backward — the best of the three maxpool gradients
measured so far (BASELINE.md round-3 table) — re-reads the full input
activation AND the pool output to locate each window's first argmax:
~21.5% of the step in select_and_scatter fusions plus ~7.1% in the
compare/select index path, all HBM-bound traffic over tensors like the
[256,64,112,112] first-pool activation.

Design (settled by hardware iteration — four Mosaic lowering classes and
one VMEM-economics dead end are documented in BASELINE.md):

- **Forward: one XLA ``reduce_window`` over packed u32.**  Each element
  packs ``monotonic(bf16 bits) << 16 | inverted low-8 (h, w) coords``;
  integer max then yields the window max AND its position in a single
  window pass: the monotonic map makes float order = unsigned order, the
  inverted coordinates break value ties toward the smallest (h, w) —
  the reference's first-argmax (``nn/NNPrimitive.scala:594-972``) — and
  a NaN's monotonic image is the largest u16, so NaN propagates exactly
  like ``lax.reduce_window(max)``.  The pack/unpack are elementwise and
  fuse into the reduce; no Pallas forward and no extra VPU argmax chain
  (a full Pallas forward measured ~2 ms of pure compare work on the
  first pool alone — more than the backward win it enabled).
- **Backward: a Pallas scatter kernel in channel-last layout.**
  ``(gy, idx) -> dx`` never touches x or y.  The layout is
  ``[rows, cols, N*C]``: rows land on the UNTILED leading dim (row
  phase-split/interleave are free reshapes), cols on the sublane dim
  (the one dim Mosaic reshape-splits natively), batch*channel on lanes
  (pure SIMD).  Every slice is static; halo rows come from a
  neighbor-block BlockSpec, not DMA code.

    select-and-scatter bwd traffic:  read x + read y + read gy + write dx
    argmax-index bwd traffic:        read gy + read idx(1/2 size) + write dx

Supported: 16-bit float dtypes (bf16/f16 — the bench path).  f32 would
need a u64 pack; it falls back to select-and-scatter.  Off-TPU the
backward runs in Pallas interpret mode so the CPU mesh exercises the
same code path.  ``BIGDL_POOL_KERNEL=off`` forces the fallback.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from bigdl_tpu.ops.attention import is_tpu_device

__all__ = ["maxpool_argmax", "pallas_pool_supported"]

_NEG = float("-inf")

#: windows larger than this are global-pool-sized; the unrolled shift
#: structure in the backward would bloat compile time
_MAX_TAPS = 64

#: per-block VMEM budget (bytes); conservative vs the 16 MB/core arena
_VMEM_BUDGET = 6 * 1024 * 1024

#: lane-chunk and row-tile defaults for the backward grid
_LANES = 512
_ROW_TILE = 8


def pallas_pool_supported(x, dims, strides, pads) -> bool:
    """True when (x, window) fits this path: 4-D NCHW input, window on
    the trailing two axes, 16-bit float dtype, window extents within the
    low-8-bit coordinate encoding, bounded tap count."""
    from bigdl_tpu.ops.dispatch import kernel_mode

    mode = os.environ.get("BIGDL_POOL_KERNEL", "auto")
    if mode == "off" or kernel_mode() == "xla":
        return False  # BIGDL_KERNELS=xla: process-wide Pallas kill switch
    if x.ndim != 4 or x.dtype not in (jnp.bfloat16, jnp.float16):
        return False  # f32 would need a u64 pack
    if dims[0] != 1 or dims[1] != 1 or strides[0] != 1 or strides[1] != 1:
        return False  # pooled axes must be the trailing (H, W) pair
    if pads[0] != (0, 0) or pads[1] != (0, 0):
        return False
    kh, kw = dims[2], dims[3]
    if kh * kw > _MAX_TAPS or kh < 1 or kw < 1:
        return False
    sh, sw = strides[2], strides[3]
    ho, wo, lh, lw = _geometry(x.shape[2], x.shape[3], kh, kw, sh, sw,
                               (pads[2], pads[3]))
    (lo_h, hi_h), (lo_w, hi_w) = pads[2], pads[3]
    if lo_h + x.shape[2] + hi_h > 256 or lo_w + x.shape[3] + hi_w > 256:
        # the low-8 coordinate code wraps at padded position 256, which
        # would invert first-argmax tie order across the wrap
        return False
    n = x.shape[0]
    if n * x.shape[1] % 8:
        return False  # lane chunking wants a multiple-of-8 batch extent
    # the backward block must fit the VMEM budget even at the minimum
    # (th=1, bl=8) tile — otherwise fall back instead of a Mosaic
    # VMEM-overflow compile error
    jw_max = -(-kw // sw) - 1
    cpad = -(-(jw_max + lw) // 8) * 8
    if _bwd_est(1, 8, cpad, kh * kw, jnp.dtype(x.dtype).itemsize) \
            > _VMEM_BUDGET:
        return False
    if mode == "auto":
        # OPT-IN until the scatter kernel A/Bs a win on hardware
        # (BASELINE.md round 5: 60% slower on the chip; ROADMAP D3)
        return False
    return True  # "interpret" / "on": run everywhere (tests)


def _bwd_est(th: int, bl: int, cpad: int, taps: int, esz: int) -> int:
    """Scoped-VMEM stack estimate for one backward block — shared by the
    support gate and the launcher's block chooser so they can't drift.
    Calibrated on hardware: the Mosaic stack does not reuse slots across
    the unrolled shift chain (~3 live planes per tap) plus the i32
    index upcast and block inputs."""
    plane = th * cpad * bl
    return (3 * taps + 6) * plane * esz + 3 * plane * 4


def _use_interpret() -> bool:
    if os.environ.get("BIGDL_POOL_KERNEL") == "interpret":
        return True
    return not is_tpu_device()


def _geometry(h: int, w: int, kh: int, kw: int, sh: int, sw: int,
              pads: Tuple[Tuple[int, int], Tuple[int, int]]):
    """Output sizes and residue-class lengths on the padded grid."""
    (lo_h, hi_h), (lo_w, hi_w) = pads
    ph, pw = lo_h + h + hi_h, lo_w + w + hi_w
    ho, wo = (ph - kh) // sh + 1, (pw - kw) // sw + 1
    lh, lw = -(-ph // sh), -(-pw // sw)  # ceil
    return ho, wo, lh, lw


# ---------------------------------------------------------------------------
# forward: packed-u32 reduce_window (pure XLA)
# ---------------------------------------------------------------------------

def _monotonic_u16(x):
    """Map 16-bit float bits to u16 such that float order == unsigned
    order (negatives flip all bits, positives flip the sign bit).  NaN
    maps above +inf, so integer max propagates it like float max.
    -0.0 collapses onto +0.0's key: the floats compare EQUAL, so the
    tie must resolve by position (select-and-scatter routes it to the
    first element), not by sign bit."""
    u = lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    sign = u >> 15
    mono = (u ^ (0x8000 + sign * 0x7FFF)) & 0xFFFF
    mono = jnp.where(u == 0x8000, jnp.uint32(0x8000), mono)
    # ALL NaNs (either sign bit) map to the top key: the sign-flip rule
    # alone would drop a negative NaN below -inf and silently hide a
    # diverged run
    return jnp.where(jnp.isnan(x), jnp.uint32(0xFFFF), mono)


def _unmonotonic(u16, dtype):
    sign = 1 - (u16 >> 15)  # monotonic image of a negative has top bit 0
    bits = (u16 ^ (0x8000 + sign * 0x7FFF)) & 0xFFFF
    return lax.bitcast_convert_type(bits.astype(jnp.uint16), dtype)


def _fwd_packed(x, dims, strides, pads):
    """(y, idx) from ONE u32 reduce_window.  idx = dh*kw + dw in int8,
    first-argmax tie order, computed per output window from the packed
    low-8 coordinates of the winning element."""
    n, c, h, w = x.shape
    kh, kw, sh, sw = dims[2], dims[3], strides[2], strides[3]
    (lo_h, _), (lo_w, _) = pads[2], pads[3]
    ho, wo, _, _ = _geometry(h, w, kh, kw, sh, sw, (pads[2], pads[3]))

    mono = _monotonic_u16(x)
    # inverted low-8 coordinates of the PADDED position: integer max
    # prefers the largest code, so inversion makes value ties resolve to
    # the smallest (h, w) — first argmax in the reference's scan order
    p_h = lax.broadcasted_iota(jnp.uint32, x.shape, 2) + lo_h
    p_w = lax.broadcasted_iota(jnp.uint32, x.shape, 3) + lo_w
    code = ((p_h & 0xFF) ^ 0xFF) << 8 | ((p_w & 0xFF) ^ 0xFF)
    packed = mono << 16 | code
    # init-0 invariant: mono >= 0x007F for every non-NaN input (the
    # minimum, at -inf, is 0x007F), so packed >= 0x7F0000 > 0 for every
    # real tap and the 0 init can never win a window that contains one.
    # A fully-padded window would decode init 0 to a NaN rather than
    # reduce_window's -inf, but pallas_pool_supported's pads-vs-window
    # geometry excludes that case.
    red = lax.reduce_window(packed, jnp.uint32(0), lax.max,
                            dims, strides, pads)

    y = _unmonotonic(red >> 16, x.dtype)
    win_h = (red >> 8) & 0xFF ^ 0xFF
    win_w = red & 0xFF ^ 0xFF
    o_h = lax.broadcasted_iota(jnp.uint32, red.shape, 2)
    o_w = lax.broadcasted_iota(jnp.uint32, red.shape, 3)
    dh = (win_h - sh * o_h) & 0xFF
    dw = (win_w - sw * o_w) & 0xFF
    idx = (dh * kw + dw).astype(jnp.int8)
    return y, idx


# ---------------------------------------------------------------------------
# backward: Pallas scatter kernel, channel-last layout
# ---------------------------------------------------------------------------

def _bwd_kernel(gy_ref, gy_next_ref, idx_ref, idx_next_ref, dx_ref, *,
                kh, kw, sh, sw, jh_max, jw_pad, th, w_out_cols, lo_w):
    """One (row-tile, lane-chunk) block.

    Row geometry: gy/idx arrive TOP-PADDED by jh_max rows (and tiled by
    th), so for output-grid row a in this tile and row shift jh the
    source row is ``a + jh_max - jh`` — always in [0, th + jh_max),
    covered by this block plus the first jh_max rows of the next block.
    Col geometry: gy/idx arrive LEFT-PADDED by jw_pad cols on the
    sublane dim, so col shifts are static slices too.  All shifts
    static, rows untiled (leading), cols sublane, lanes batch."""
    gy = jnp.concatenate([gy_ref[...], gy_next_ref[0:jh_max]], axis=0) \
        if jh_max else gy_ref[...]
    idx = jnp.concatenate([idx_ref[...], idx_next_ref[0:jh_max]], axis=0) \
        if jh_max else idx_ref[...]
    idx = idx.astype(jnp.int32)
    bl = gy.shape[2]

    # hoist the column shifts: a sublane-offset slice is a relayout
    # copy, so take each jw view ONCE (jw_max+1 of them) — the per-tap
    # row shifts below slice only the untiled leading dim (free views)
    n_jw = jw_pad + 1
    gy_w = [gy[:, jw_pad - jw:jw_pad - jw + w_out_cols] for jw in range(n_jw)]
    idx_w = [idx[:, jw_pad - jw:jw_pad - jw + w_out_cols]
             for jw in range(n_jw)]

    # residue-class accumulation: padded input row p = sh*a + rh
    # receives gy[a - jh] where the tap dh = rh + sh*jh won
    rows = []
    for rh in range(sh):
        cols = []
        for rw in range(sw):
            acc = jnp.zeros((th, w_out_cols, bl), gy.dtype)
            for jh in range(-(-(kh - rh) // sh)):
                dh = rh + sh * jh
                if dh >= kh:
                    continue
                for jw in range(-(-(kw - rw) // sw)):
                    dw = rw + sw * jw
                    if dw >= kw:
                        continue
                    t = dh * kw + dw
                    g = gy_w[jw][jh_max - jh:jh_max - jh + th]
                    m = idx_w[jw][jh_max - jh:jh_max - jh + th]
                    # mask-multiply, not where (Mosaic i1-select
                    # relayout); caveat: a non-finite gy element leaks
                    # NaN into sibling tap positions (0 * inf) — wider
                    # NaN spread on an already-diverged step, not hidden
                    acc = acc + (m == t).astype(g.dtype) * g
            cols.append(acc)
        # W-interleave on the SUBLANE dim: [th, L, bl] x sw ->
        # [th, L*sw, bl] with out[.., sw*b + rw, ..] = cols[rw][.., b, ..]
        if sw == 1:
            rows.append(cols[0])
        else:
            rows.append(jnp.stack(cols, axis=2).reshape(
                th, w_out_cols * sw, bl))
    # H-interleave on the UNTILED leading dim: free reshape
    if sh == 1:
        dxp = rows[0]
    else:
        dxp = jnp.stack(rows, axis=1).reshape(th * sh, rows[0].shape[1], bl)
    dx_ref[...] = dxp[:, lo_w:lo_w + dx_ref.shape[1], :]


def _bwd_impl(gy, idx, x_shape, x_dtype, dims, strides, pads):
    n, c, h, w = x_shape
    kh, kw, sh, sw = dims[2], dims[3], strides[2], strides[3]
    hw_pads = (pads[2], pads[3])
    (lo_h, _), (lo_w, _) = hw_pads
    ho, wo, lh, lw = _geometry(h, w, kh, kw, sh, sw, hw_pads)
    b = n * c
    jh_max = -(-kh // sh) - 1
    jw_max = -(-kw // sw) - 1

    # channel-last: [ho, wo, b] with b = (c, n), n MINOR.  XLA's TPU
    # layout for NCHW conv activations is {0,1,3,2} — memory order
    # H, W, C, N — so this exact transpose is a bitcast, not a data
    # movement; b built as (n, c) instead would force a real HBM
    # relayout at the pallas row-major operand boundary (measured:
    # the first A/B ran 2.2x SLOWER from exactly that).
    gyt = jnp.transpose(gy.astype(x_dtype).reshape(n, c, ho, wo),
                        (2, 3, 1, 0)).reshape(ho, wo, b)
    idxt = jnp.transpose(idx.reshape(n, c, ho, wo),
                         (2, 3, 1, 0)).reshape(ho, wo, b)

    # block chooser: the Mosaic scoped stack does not reuse slots
    # across the unrolled shift chain (measured 28.2 MB at th=8/bl=512
    # on the first pool), so budget ~3 live planes per tap plus the i32
    # index upcast and the block inputs, and shrink (th, bl) to fit
    taps = kh * kw
    cpad = -(-(jw_max + lw) // 8) * 8  # sublane-padded col extent
    esz = jnp.dtype(x_dtype).itemsize

    th, bl = _ROW_TILE, _LANES
    while b % bl:
        bl //= 2
    while _bwd_est(th, bl, cpad, taps, esz) > _VMEM_BUDGET and bl > 8 \
            and b % (bl // 2) == 0:
        bl //= 2
    while _bwd_est(th, bl, cpad, taps, esz) > _VMEM_BUDGET and th > 1:
        th //= 2

    # row tiling: pad top by jh_max (shift halo) + bottom so gyp holds
    # EXACTLY (n_tiles + 1) row blocks — the neighbor-block spec
    # (lambda i, l: (i + 1, ...)) reads block n_tiles for the last tile,
    # so it must exist in-array (round-5 advisor: sizing the bottom pad
    # off lh instead of gyt's true ho rows left the neighbor block out
    # of range when lh > ho + jh_max, silently relying on Mosaic's
    # block-index clamping); col padding: left jw_max, right to the
    # residue grid
    n_tiles = -(-lh // th)
    top, bot = jh_max, (n_tiles + 1) * th - jh_max - ho
    right = lw - wo
    gyp = jnp.pad(gyt, ((top, bot), (jw_max, right), (0, 0)))
    idxp = jnp.pad(idxt, ((top, bot), (jw_max, right), (0, 0)),
                   constant_values=-1)
    w_cols = lw  # output-grid cols available per row after left pad
    kern = functools.partial(
        _bwd_kernel, kh=kh, kw=kw, sh=sh, sw=sw, jh_max=jh_max,
        jw_pad=jw_max, th=th, w_out_cols=w_cols, lo_w=lo_w)
    cols_pad = gyp.shape[1]
    dxp = pl.pallas_call(
        kern,
        grid=(n_tiles, b // bl),
        in_specs=[
            pl.BlockSpec((th, cols_pad, bl), lambda i, l: (i, 0, l)),
            pl.BlockSpec((th, cols_pad, bl), lambda i, l: (i + 1, 0, l)),
            pl.BlockSpec((th, cols_pad, bl), lambda i, l: (i, 0, l)),
            pl.BlockSpec((th, cols_pad, bl), lambda i, l: (i + 1, 0, l)),
        ],
        out_specs=pl.BlockSpec((th * sh, lw * sw - lo_w, bl),
                               lambda i, l: (i, 0, l)),
        out_shape=jax.ShapeDtypeStruct(
            (n_tiles * th * sh, lw * sw - lo_w, b), x_dtype),
        interpret=_use_interpret(),
    )(gyp, gyp, idxp, idxp)
    # valid region: padded rows [lo_h, lo_h + h), cols already start at
    # lo_w in-kernel; back to NCHW — the row-major [h, w, c, n] result
    # transposed to NCHW is exactly the {0,1,3,2} physical layout the
    # conv-backward consumer wants, so this folds too
    dx = dxp[lo_h:lo_h + h, :w, :].reshape(h, w, c, n)
    return jnp.transpose(dx, (3, 2, 0, 1))


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------

def maxpool_argmax(x, dims, strides, pads):
    """Max pooling over the trailing (H, W) axes of an NCHW tensor with
    first-argmax gradient routing via a saved int8 tap index.  Value-
    and tie-parity with ``lax.reduce_window(max)`` + select-and-scatter
    under the support predicate ``pallas_pool_supported``."""
    return _pool(x, dims, strides, tuple(pads), x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _pool(x, dims, strides, pads, xshape):
    # undifferentiated primal (inference/eval): plain reduce_window —
    # identical values, fully XLA-fusable, no index computation
    return lax.reduce_window(x, _NEG, lax.max, dims, strides, pads)


def _vjp_fwd(x, dims, strides, pads, xshape):
    y, idx = _fwd_packed(x, dims, strides, pads)
    return y, idx


def _vjp_bwd(dims, strides, pads, xshape, idx, gy):
    dx = _bwd_impl(gy, idx, xshape, gy.dtype, dims, strides, pads)
    return (dx,)


_pool.defvjp(_vjp_fwd, _vjp_bwd)
