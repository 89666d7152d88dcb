"""The Mamba-2 state-space scan (Dao & Gu 2024, "Transformers are SSMs":
state-space duality): the recurrence of a selective state-space layer
whose state is a ``[head_dim, state]`` matrix a head, decayed by an
input-dependent scalar a head and position,

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t                               from S_0 = 0,

with ``dt_t > 0`` the step (after its softplus), ``A < 0`` one rate a
head, ``B_t``, ``C_t`` the input and output projections of the head's
GROUP (``groups`` of them, ``heads / groups`` heads each) and ``D`` one
skip weight a head.  Two forms stand here:

- :func:`ssd_recurrent` — the definition, one ``lax.scan`` step a token.
  It is what the tests compare with; at 8,192 positions it is as many
  dependent rank-one updates and its backward keeps a state a token.
- :func:`ssd` — the chunked form that trains.  Inside a chunk of ``Q``
  tokens, with ``L_t`` the running sum of ``dt A`` and ``S`` the state
  that enters the chunk,

      y_t  = sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s x_s
             + exp(L_t) S C_t + D x_t
      S'  = exp(L_Q) S + sum_s exp(L_Q - L_s) dt_s x_s B_s^T,

  so everything but ``S`` is batched products over all chunks at once
  (``C B^T`` once a group; the decay matrix ``exp(L_t - L_s)``, masked
  BEFORE the exponential, a head), and what is left to do in order is a
  walk over the chunks that carries ``S`` and does no product: ``S' =
  exp(L_Q) S + S_own``.  The states that enter the chunks are kept for
  the backward, and ``exp(L_t) S C_t`` is one more product.  Every
  exponent is of a number that is not positive.  Matrix products take
  their operands in ``x``'s dtype and accumulate in float32; the decays
  and the carried state are float32.

Shapes: ``x`` ``[batch, seq, heads, head_dim]``, ``dt`` ``[batch, seq,
heads]``, ``A``, ``D`` ``[heads]``, ``B``, ``C`` ``[batch, seq, groups,
state]``; the result is ``x``'s shape and dtype.  A length the chunk does
not divide is padded with tokens that change nothing (``dt = 0``).

TWO legs, and where the state lives between chunks is what tells them
apart.  ``ops/dispatch.choose_backend`` picks, from what the trace can
see:

- XLA's (:func:`_chunked`): batched products for all chunks at once
  through HBM (``C B^T``, the decayed ``Q x Q`` matrix times it, ``dt x``,
  the own and entering states), operands transposed to ``[batch, groups,
  heads a group, chunks, Q, *]`` and ``y`` transposed back, and between
  them :func:`_carry`, one ``lax.scan`` over the chunks (``S' = exp(L_Q) S
  + S_own``, no product; XLA's ``while``); autodiff's backward.  Off the
  TPU, inside ``dispatch.spmd_partitioned`` (a Mosaic kernel cannot be
  partitioned over a mesh), under ``BIGDL_KERNELS=xla``, and at a shape
  the kernels do not take.
- Pallas's (:func:`_scan_pallas`, one ``jax.custom_vjp``), on a TPU when
  ``chunk`` is :data:`CHUNK`, ``head_dim`` is whole sublane tiles (a
  multiple of 16), ``state`` whole lane tiles (a multiple of 128), x, B, C
  share a dtype Mosaic compiles and a record's states, ``heads * head_dim
  * state`` float32, fit :data:`STATE_BUDGET` of VMEM.  ONE Mosaic call a
  direction walks the chunks IN ORDER with the state in VMEM: a grid step
  is one chunk of :data:`HEAD_BLOCK` heads of one group, the grid
  ``(batch, chunks, blocks of heads)`` runs one step after another with
  the last axis fastest, and :func:`_advance` is the carry, the same
  float32 expression as :func:`_carry`'s step.  Operands come in the
  layout the mixer's activations have on the TPU (the sequence minor:
  ``x`` as ``[batch, heads * head_dim, seq]``; the transposes in front are
  bitcasts there).  The forward (:func:`_fwd_kernel`) keeps every block's
  state of a record in its last-state result, resident from the record's
  first chunk (where it is zeroed) to its last; a step writes the state
  that enters to the stack the backward needs, computes ``y`` from it,
  then the chunk's own state and the state that leaves.  The backward
  (:func:`_bwd_kernel`) takes the chunks from the last to the first
  through its index maps, with the cotangent of the state that leaves a
  chunk in a VMEM scratch started from the last state's cotangent; a step
  reads the chunk's entering state from the stack, gives ``dx``, ``d dt``,
  ``dA``, ``dB``, ``dC``, ``dD`` with the chunk's internals computed
  again, and steps the cotangent by the same :func:`_advance`.  ``C B^T``,
  the masked exponent, the ``Q x Q`` decay, its product with the scores,
  ``dt x``, a chunk's own state and ``exp(L_Q)`` live in VMEM only, and
  nothing ``Q x Q`` is kept.  Same mathematics, rounded where the XLA leg
  rounds; the running sums are products with a triangle of ones at
  ``Precision.HIGHEST``.  The one state stack that crosses HBM is the
  float32 entering states, ``[chunks, batch, groups, heads a group,
  head_dim, state]``: written once by the forward, read once by the
  backward.

The decision is announced on a ``kernel/dispatch`` instant (``op=ssd``,
``backend``, ``reason``, ``chunk``, ``chunks``, ``heads``, ``head_dim``,
``state``, ``groups`` and, for the Pallas leg, ``head_block``, ``grid``,
``carry="vmem"``, ``calls=2`` and ``state_bytes``), once a compilation,
and the scan's operations lie under the ``jax.named_scope`` :data:`SCOPE`:
an XLA dump and the profiler's op metadata carry it.  (The names of a
device trace's events do not, so the benchmark finds the scan's events by
the shapes only it has; both Mosaic calls hold the stack of entering
states, which those patterns name.  With no ``while`` left, the benchmark's
two scan rooflines, which count a call by it, read nothing on this leg.)
"""

from __future__ import annotations

from typing import Tuple

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd", "ssd_recurrent", "SCOPE", "CHUNK"]

#: the ``jax.named_scope`` around the chunked scan
SCOPE = "ssd"

#: tokens a chunk: the size the layers run the scan at, and the one place
#: it is set.  The chunk changes no answer, so neither a layer nor a plan
#: names one (a ``mamba_chunk_size`` a model publishes is how ITS kernels
#: tile the scan); ``chunk=`` of :func:`ssd` is for the scan's own tests
CHUNK = 128


def ssd_recurrent(x, dt, A, B, C, D, return_state: bool = False):
    """The recurrence token by token, in float32: the definition."""
    f32 = jnp.float32
    x, dt, A, B, C, D = (a.astype(f32) for a in (x, dt, A, B, C, D))
    b, _, h, p = x.shape
    r = h // B.shape[2]

    def token(state, inp):
        xt, dtt, bt, ct = inp
        bt, ct = (jnp.repeat(v, r, axis=1) for v in (bt, ct))  # [b, h, n]
        state = state * jnp.exp(dtt * A)[..., None, None] \
            + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return state, jnp.sum(state * ct[:, :, None, :], axis=-1)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C))
    state, y = lax.scan(token, jnp.zeros((b, h, p, B.shape[-1]), f32), xs)
    y = jnp.moveaxis(y, 0, 1) + D[:, None] * x
    return (y, state) if return_state else y


def _carry(decay, own) -> Tuple[jax.Array, jax.Array]:
    """The part that runs in order.  ``decay`` ``[chunks, ...]`` and
    ``own`` ``[chunks, ..., head_dim, state]``, float32 -> the state after
    the last chunk and the states that ENTER each chunk, stacked."""

    def step(state, inp):
        decay_i, own_i = inp
        return state * decay_i[..., None, None] + own_i, state

    return lax.scan(step, jnp.zeros(own.shape[1:], jnp.float32),
                    (decay, own))


def _whole_chunks(chunk: int, *arrays):
    """``[batch, seq, ...]`` arrays padded to whole chunks with tokens that
    change nothing (``dt = 0``), and how many chunks that is."""
    s = arrays[0].shape[1]
    c = -(-s // chunk)
    pad = c * chunk - s
    if pad:
        arrays = tuple(
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in arrays)
    return (c,) + tuple(arrays)


def _chunked(x, dt, A, B, C, D, chunk: int) -> Tuple[jax.Array, jax.Array]:
    f32 = jnp.float32
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    r, dtype = h // g, x.dtype
    c, x, dt, B, C = _whole_chunks(chunk, x, dt, B, C)
    dt = dt.astype(f32)
    # [batch, groups, heads a group, chunks, Q, *]; B and C have no head
    xc = x.reshape(b, c, chunk, g, r, p).transpose(0, 3, 4, 1, 2, 5)
    dtc = dt.reshape(b, c, chunk, g, r).transpose(0, 3, 4, 1, 2)
    bc, cc = (a.reshape(b, c, chunk, g, n).transpose(0, 3, 1, 2, 4)
              for a in (B, C))
    total = jnp.cumsum(dtc * A.astype(f32).reshape(g, r, 1, 1), axis=-1)
    rows = jnp.arange(chunk)
    upto = rows[:, None] >= rows[None, :]                    # s <= t
    # the exponent is masked BEFORE the exponential: above the diagonal it
    # is positive, and an overflow there would reach the gradient
    fade = jnp.where(upto, jnp.exp(jnp.where(
        upto, total[..., :, None] - total[..., None, :], 0.0)), 0.0)
    scores = jnp.einsum("bgctn,bgcsn->bgcts", cc, bc,
                        preferred_element_type=f32)          # C_t . B_s
    xdt = (xc.astype(f32) * dtc[..., None]).astype(dtype)    # dt_s x_s
    y = jnp.einsum("bgrcts,bgrcsp->bgrctp",
                   (fade * scores[:, :, None]).astype(dtype), xdt,
                   preferred_element_type=f32)
    last = total[..., -1:]
    own = jnp.einsum(
        "bgrcsp,bgcsn->bgrcpn",
        (xdt.astype(f32) * jnp.exp(last - total)[..., None]).astype(dtype),
        bc, preferred_element_type=f32)
    state, entering = _carry(jnp.moveaxis(jnp.exp(last[..., 0]), 3, 0),
                             jnp.moveaxis(own, 3, 0))
    y = y + jnp.exp(total)[..., None] * jnp.einsum(
        "bgctn,cbgrpn->bgrctp", cc, entering.astype(dtype),
        preferred_element_type=f32)
    y = y + D.astype(f32).reshape(g, r, 1, 1, 1) * xc.astype(f32)
    y = y.transpose(0, 3, 4, 1, 2, 5).reshape(b, c * chunk, h, p)
    return y[:, :s].astype(dtype), state.reshape(b, h, p, n)




# -- the Pallas leg: a chunk's products in VMEM -----------------------------------
#
# The kernels see the sequence on the LANES: ``x`` as ``[batch, heads *
# head_dim, seq]``, ``dt`` as ``[batch, heads, seq]``, ``B`` and ``C`` as
# ``[batch, groups * state, seq]``.  That is the layout XLA keeps the
# mixer's activations in on the TPU (``[1, 8192, 4096]`` with the sequence
# minor: the transposes in front of the calls are bitcasts there, and a
# copy where a program holds them the other way).  A grid step takes one
# chunk's ``Q`` columns of ``hb`` heads of one group: a head is ``head_dim``
# whole sublane rows, so what has no ``Q x Q`` in it (the states' products,
# ``dt x``, the skip) is done for the block's heads at once, and only the
# decayed product goes head by head.  ``dt`` comes as the chunk's ``[heads,
# Q]`` and a one-hot product picks the block's rows (and puts gradients
# back), exactly.

LANES = 128

#: heads a grid step of the Pallas leg takes (fewer when a group holds
#: fewer)
HEAD_BLOCK = 16

#: the VMEM a scan's carried state may take, ``heads * head_dim * state``
#: float32 (2 MB and 1 MB in the two cells that run the leg): the forward
#: holds a record's states twice (a result's two buffers), the backward
#: three times (the last state's cotangent and the scratch it starts),
#: inside the scoped VMEM a Mosaic call has by default (at 4 MiB, 128 heads
#: of 64 on a state of 128, both calls compile for a v5e); a larger state
#: takes the XLA leg
STATE_BUDGET = 4 << 20

#: what an exponent below the diagonal is set to BEFORE the exponential
_MASKED = -1e30

_NN = ((1,), (0,))      # a b
_NT = ((1,), (1,))      # a b^T
_TN = ((0,), (0,))      # a^T b


def _dot(a, b, form=_NN, exact=False):
    """A product on the MXU, float32 out; ``exact`` for float32 operands
    whose digits all count (running sums, one-hot selections)."""
    return lax.dot_general(
        a, b, (form, ((), ())), preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST if exact else None)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _head_block(r: int) -> int:
    """Heads of a group a grid step takes: the largest divisor of ``r`` up
    to :data:`HEAD_BLOCK`."""
    return max(d for d in range(1, HEAD_BLOCK + 1) if r % d == 0)


def _block_sums(dt_ref, a_ref, hb: int):
    """Of the step's ``hb`` heads: the one-hot ``[hb, heads]`` that picks
    them, ``L_t`` as rows ``[hb, Q]`` and as columns ``[Q, hb]`` (the SAME
    sums: ``L_t - L_t`` has to be 0) and ``dt`` as rows."""
    f32 = jnp.float32
    dt = dt_ref[...]                                         # [heads, Q]
    h, q = dt.shape
    pick = (_iota((hb, h), 1)
            == pl.program_id(2) * hb + _iota((hb, h), 0)).astype(f32)
    rows, cols = _iota((q, q), 0), _iota((q, q), 1)
    total = _dot(_dot(pick, dt * a_ref[...], exact=True),
                 (rows <= cols).astype(f32), exact=True)     # sum over s <= t
    return (pick, total,
            _dot((rows == cols).astype(f32), total, _NT, True),
            _dot(pick, dt, exact=True))


def _picked(col_ref, pick):
    """``[heads, 1]`` -> the block's ``[hb, 1]``."""
    col = jnp.broadcast_to(col_ref[...], (pick.shape[1], LANES))
    return _dot(pick, col, exact=True)[:, :1]


def _put_back(col, pick):
    """The block's ``[hb, 1]`` -> ``[heads, 1]``, zero elsewhere."""
    return _dot(pick, jnp.broadcast_to(col, (pick.shape[0], LANES)), _TN,
                True)[:, :1]


def _a_head(rows, p: int):
    """``[hb, Q or 1]`` a head -> ``[hb * p, Q or 1]``: a head's row over
    its ``p`` sublane rows."""
    hb, q = rows.shape
    return jnp.broadcast_to(rows[:, None], (hb, p, q)).reshape(hb * p, q)


def _states(ref, at=Ellipsis):
    """A ``[hb, head_dim, state]`` block (``ref[at]``) as ``[hb * head_dim,
    state]``."""
    hb, p, n = (block := ref[at]).shape
    return block.reshape(hb * p, n)


def _block_of(per_group: int, hb: int):
    """Where the grid step's block of heads lies in a record's ``[groups,
    heads a group, head_dim, state]`` states."""
    block = pl.program_id(2)
    return block // per_group, pl.ds(block % per_group * hb, hb)


def _fade(upto, total, total_c, j):
    """``exp(L_t - L_s)`` of head ``j``, ``[Q_s, Q_t]``, 0 where ``s > t``:
    the exponent is masked BEFORE the exponential."""
    return jnp.exp(jnp.where(upto, total[j:j + 1] - total_c[:, j:j + 1],
                             _MASKED))


def _advance(state, decay, own):
    """The carry where the state lives, in VMEM: ``S' = exp(L_Q) S +
    S_own`` in float32, the expression of :func:`_carry`'s step in its
    order.  The backward walks the chunks the other way with the same
    step: ``dS = exp(L_Q) dS' + (exp(L_t) dy_t)^T C_t``."""
    return state * decay + own


def _fwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref,
                y_ref, enter_ref, state_ref, *, hb, per_group):
    """A chunk of a block of heads, the chunks of a record in order:
    ``y_t = sum_{s<=t} exp(L_t - L_s)(C_t . B_s) dt_s x_s + exp(L_t) S C_t
    + D x_t`` from the state ``S`` that enters, which goes to the stack
    the backward reads, then ``S' = exp(L_Q) S + (exp(L_Q - L_s) dt_s
    x_s)^T B_s``.  ``state_ref`` holds every block's state of the record
    and stays in VMEM from the record's first chunk to its last."""
    f32, dtype = jnp.float32, x_ref.dtype
    mine = _block_of(per_group, hb)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[mine] = jnp.zeros(enter_ref.shape, f32)

    pick, total, total_c, dt = _block_sums(dt_ref, a_ref, hb)
    p, q = x_ref.shape[0] // hb, total.shape[1]
    upto = _iota((q, q), 0) <= _iota((q, q), 1)              # s <= t
    bm, cm = b_ref[...], c_ref[...]
    scores = _dot(bm, cm, _TN)                               # B_s . C_t
    entering = _states(state_ref, mine)
    enter_ref[...] = entering.reshape(enter_ref.shape)
    x = x_ref[...].astype(f32)
    xdt = (x * _a_head(dt, p)).astype(dtype)
    y = _a_head(_picked(d_ref, pick), p) * x + _a_head(jnp.exp(total), p) \
        * _dot(entering.astype(dtype), cm)
    for j in range(hb):
        head = slice(j * p, (j + 1) * p)
        weights = (_fade(upto, total, total_c, j) * scores).astype(dtype)
        y_ref[head] = (y[head] + _dot(xdt[head], weights)).astype(dtype)
    last = total[:, q - 1:q]
    kept = (xdt.astype(f32)
            * _a_head(jnp.exp(last - total), p)).astype(dtype)
    state_ref[mine] = _advance(
        entering, _a_head(jnp.exp(last), p),
        _dot(kept, bm, _NT)).reshape(enter_ref.shape)


def _bwd_kernel(x_ref, dy_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, s_ref,
                dlast_ref, dx_ref, ddt_ref, db_ref, dc_ref, dhead_ref,
                db_acc, dc_acc, ds_acc, *, hb, per_group):
    """The backward of a chunk of a block of heads, the chunks of a record
    from the LAST to the first (the index maps turn the grid's chunk
    axis), the chunk's internals computed again: ``dx``, ``d dt``, ``dB``,
    ``dC`` and, summed over the whole grid, ``dA`` and ``dD`` (columns 0
    and 1 of ``dhead``).  ``ds_acc`` holds, for every block of the record,
    the cotangent of the state that LEAVES the chunk: the last state's at
    the record's last chunk, and from there :func:`_advance`'s."""
    f32, dtype = jnp.float32, x_ref.dtype
    block = pl.program_id(2)
    mine = _block_of(per_group, hb)

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_acc[mine] = dlast_ref[mine]

    pick, total, total_c, dt = _block_sums(dt_ref, a_ref, hb)
    p, q = x_ref.shape[0] // hb, total.shape[1]
    rows, cols = _iota((q, q), 0), _iota((q, q), 1)
    upto = rows <= cols
    bm, cm = b_ref[...], c_ref[...]
    scores = _dot(bm, cm, _TN)
    last = total[:, q - 1:q]
    grow, shrink = jnp.exp(total), jnp.exp(last - total)
    skip = _picked(d_ref, pick)
    x, dy_low = x_ref[...].astype(f32), dy_ref[...]
    dy = dy_low.astype(f32)
    dt_p, grow_p, shrink_p = (_a_head(v, p) for v in (dt, grow, shrink))
    xdt = (x * dt_p).astype(dtype)
    kept = (xdt.astype(f32) * shrink_p).astype(dtype)
    entering, leaving = _states(s_ref), _states(ds_acc, mine)
    state, d_own = entering.astype(dtype), leaving.astype(dtype)
    # the carry, S' = exp(L_Q) S + S_own: d exp(L_Q) a head, and the
    # entering state's cotangent with y's part, (exp(L_t) dy_t)^T C_t
    d_decay = jnp.sum(jnp.sum((leaving * entering).reshape(hb, p, -1),
                              axis=1), axis=1, keepdims=True)
    grown = (dy * grow_p).astype(dtype)
    ds_acc[mine] = _advance(leaving, _a_head(jnp.exp(last), p),
                            _dot(grown, cm, _NT)).reshape(s_ref.shape)
    # y's part from the entering state, exp(L_t) (S C_t)
    d_c = _dot(state, grown, _TN)                            # [state, Q]
    d_grow = dy * _dot(state, cm) * grow_p
    # the chunk's own state, (exp(L_Q - L_s) dt_s x_s)^T B_s
    d_b = _dot(d_own, kept, _TN)
    d_kept = _dot(d_own, bm)
    d_xdt_own = d_kept * shrink_p
    d_shrink = d_kept * xdt.astype(f32) * shrink_p
    straight, skipped = dy * x, _a_head(skip, p) * dy
    sub, lane = _iota((hb, 1), 0), _iota((1, hb), 1)

    def a_row(v, j):                      # a head's [p, Q] -> its row of [hb, Q]
        return jnp.where(sub == j, jnp.sum(v, axis=0, keepdims=True), 0)

    d_scores = jnp.zeros((q, q), f32)
    d_total = jnp.zeros((hb, q), f32)     # d L_t a head, the rows' part
    d_total_c = jnp.zeros((q, hb), f32)   # the columns' part
    d_shrunk = jnp.zeros((hb, q), f32)    # d exp(L_Q - L_s) exp(L_Q - L_s)
    d_dt = jnp.zeros((hb, q), f32)        # through dt_s x_s
    d_skip = jnp.zeros((hb, q), f32)
    for j in range(hb):
        head = slice(j * p, (j + 1) * p)
        fade = _fade(upto, total, total_c, j)
        weights = fade * scores
        d_w = _dot(xdt[head], dy_low[head], _TN)             # [Q_s, Q_t]
        d_xdt = d_xdt_own[head] + _dot(dy_low[head], weights.astype(dtype),
                                       _NT)
        d_scores = d_scores + d_w * fade
        d_gap = d_w * weights                                # 0 where s > t
        d_total = d_total + a_row(d_gap, j) + a_row(d_grow[head], j)
        d_total_c = d_total_c - jnp.where(
            lane == j, jnp.sum(d_gap, axis=1, keepdims=True), 0)
        d_shrunk = d_shrunk + a_row(d_shrink[head], j)
        d_dt = d_dt + a_row(d_xdt * x[head], j)
        d_skip = d_skip + a_row(straight[head], j)
        dx_ref[head] = (d_xdt * dt_p[head] + skipped[head]).astype(dtype)
    low = d_scores.astype(dtype)
    d_b = d_b + _dot(cm, low, _NT)
    d_c = d_c + _dot(bm, low)
    # d L_t: rows, columns (the identity transposes them, exactly), and
    # L_Q's own on the chunk's last token; d (dt_s A): its sum over t >= s
    d_last = jnp.sum(d_shrunk, axis=1, keepdims=True) \
        + d_decay * jnp.exp(last)
    d_total = d_total - d_shrunk \
        + _dot(d_total_c, (rows == cols).astype(f32), _TN, True) \
        + jnp.where(_iota((1, q), 1) == q - 1, d_last, 0)
    d_rate = _dot(pick, _dot(d_total, (rows >= cols).astype(f32),
                             exact=True), _TN, True)         # [heads, Q]

    @pl.when(block == 0)
    def _():
        ddt_ref[...] = jnp.zeros_like(ddt_ref)

    ddt_ref[...] += d_rate * a_ref[...] + _dot(pick, d_dt, _TN, True)

    @pl.when((block == 0) & (pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dhead_ref[...] = jnp.zeros_like(dhead_ref)

    which = _iota((1, LANES), 1)
    dhead_ref[...] += jnp.where(
        which == 0, jnp.sum(d_rate * dt_ref[...], axis=1, keepdims=True), 0) \
        + jnp.where(which == 1, _put_back(
            jnp.sum(d_skip, axis=1, keepdims=True), pick), 0)

    # B and C are a group's: summed over its blocks of heads
    @pl.when(block % per_group == 0)
    def _():
        db_acc[...] = jnp.zeros_like(db_acc)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    db_acc[...] += d_b
    dc_acc[...] += d_c

    @pl.when(block % per_group == per_group - 1)
    def _():
        db_ref[...] = db_acc[...].astype(db_ref.dtype)
        dc_ref[...] = dc_acc[...].astype(dc_ref.dtype)


class _Launch:
    """Block specs and shapes of one scan's two calls: grid ``(batch,
    chunks, blocks of heads)``, a group's blocks next to each other, the
    last axis fastest and every axis ``"arbitrary"``: the steps run one
    after another in the grid's order, which is what carries the state (a
    ``"parallel"`` chunk axis would break the carry in silence).  An
    operand is a ``(block spec, shape, dtype)``; ``ascending`` holds the
    forward's, ``descending`` the backward's, whose grid step ``m`` is
    chunk ``chunks - 1 - m``."""

    def __init__(self, b, c, h, p, g, n, dtype, hb, interpret):
        r = h // g
        self.hb, self.per_group, self.grid = hb, r // hb, (b, c, h // hb)
        self.interpret, self.calls = interpret, {}
        shape = (b, c, h, p, g, n, dtype, hb)
        self.ascending = _Operands(lambda m: m, *shape)
        self.descending = _Operands(lambda m: c - 1 - m, *shape)
        self.acc = pltpu.VMEM((n, CHUNK), jnp.float32)
        # every block's state of a record
        self.carried = pltpu.VMEM((g, r, p, n), jnp.float32)

    def call(self, kernel, ins, outs, scratch=()):
        """The call as ONE jitted function a kernel: every layer of a model
        (and a forward computed again) traces and lowers a kernel's body
        once, not once a call (0.2 s each, 27 calls a step in a cell)."""
        if kernel not in self.calls:
            self.calls[kernel] = jax.jit(pl.pallas_call(
                functools.partial(kernel, hb=self.hb,
                                  per_group=self.per_group),
                grid=self.grid, in_specs=[o[0] for o in ins],
                out_specs=[o[0] for o in outs],
                out_shape=[jax.ShapeDtypeStruct(*o[1:]) for o in outs],
                scratch_shapes=list(scratch),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",) * 3),
                interpret=self.interpret))
        return self.calls[kernel]


class _Operands:
    """A direction's operands; ``at(m)`` is the chunk of grid step ``m``."""

    def __init__(self, at, b, c, h, p, g, n, dtype, hb):
        f32 = jnp.float32
        r, q = h // g, CHUNK
        per = r // hb

        def operand(block, index, shape, dtype):
            return pl.BlockSpec(block, index), shape, dtype

        # x, y and their cotangents, the sequence minor
        self.wide = operand((None, hb * p, q), lambda i, m, j: (i, j, at(m)),
                            (b, h * p, c * q), dtype)
        # dt and its cotangent
        self.steps = operand((None, h, q), lambda i, m, j: (i, 0, at(m)),
                             (b, h, c * q), f32)
        # A, D; dA and dD are columns 0 and 1 of [heads, 128]
        self.head = operand((h, 1), lambda i, m, j: (0, 0), (h, 1), f32)
        self.heads = operand((h, LANES), lambda i, m, j: (0, 0), (h, LANES),
                             f32)
        # B, C and their cotangents
        self.proj = operand((None, n, q),
                            lambda i, m, j: (i, j // per, at(m)),
                            (b, g * n, c * q), dtype)
        # the states that enter the chunks, float32, stacked as the XLA
        # leg's carry stacks them
        self.states = operand(
            (None, None, None, hb, p, n),
            lambda i, m, j: (at(m), i, j // per, j % per, 0, 0),
            (c, b, g, r, p, n), f32)
        # the state after a record's last token and its cotangent: the
        # record's, in VMEM from its first grid step to its last
        self.last = operand((None, g, r, p, n),
                            lambda i, m, j: (i, 0, 0, 0, 0), (b, g, r, p, n),
                            f32)


_launch = functools.lru_cache(maxsize=None)(_Launch)


def _minor(a):
    """``[batch, seq, ...]`` -> ``[batch, ..., seq]``."""
    return a.reshape(a.shape[:2] + (-1,)).transpose(0, 2, 1)


def _major(a, like):
    """:func:`_minor` undone, to ``like``'s shape."""
    return a.transpose(0, 2, 1).reshape(like.shape)


def _operands(x, dt, a, bm, cm, d):
    """The custom VJP's arguments as the kernels take them, and how the
    kernels are launched on them."""
    from bigdl_tpu.ops.dispatch import use_interpret

    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    return _launch(b, s // CHUNK, h, p, g, n, x.dtype, _head_block(h // g),
                   use_interpret()), (
        _minor(x), _minor(dt), a.reshape(h, 1), _minor(bm), _minor(cm),
        d.reshape(h, 1))


def _scan_pallas_fwd(x, dt, a, bm, cm, d):
    k, (xm, dtm, a_col, bmm, cmm, d_col) = _operands(x, dt, a, bm, cm, d)
    o = k.ascending
    y, entering, state = k.call(
        _fwd_kernel, [o.wide, o.steps, o.head, o.head, o.proj, o.proj],
        [o.wide, o.states, o.last])(xm, dtm, a_col, d_col, bmm, cmm)
    return (_major(y, x), state), (x, dt, a, bm, cm, d, entering)


@jax.custom_vjp
def _scan_pallas(x, dt, a, bm, cm, d):
    """The chunked scan on whole chunks of :data:`CHUNK`, one Mosaic call a
    direction with the state in VMEM: :func:`ssd`'s arguments, ``dt``,
    ``a`` and ``d`` float32 -> ``y`` as ``x`` and the last state ``[batch,
    groups, heads a group, head_dim, state]``."""
    return _scan_pallas_fwd(x, dt, a, bm, cm, d)[0]


def _scan_pallas_bwd(kept, cotangents):
    x, dt, a, bm, cm, d, entering = kept
    dy, d_state = cotangents
    k, (xm, dtm, a_col, bmm, cmm, d_col) = _operands(x, dt, a, bm, cm, d)
    o = k.descending
    dx, d_dt, d_b, d_c, d_head = k.call(
        _bwd_kernel,
        [o.wide, o.wide, o.steps, o.head, o.head, o.proj, o.proj, o.states,
         o.last],
        [o.wide, o.steps, o.proj, o.proj, o.heads],
        [k.acc, k.acc, k.carried])(
        xm, _minor(dy), dtm, a_col, d_col, bmm, cmm, entering, d_state)
    return (_major(dx, x), _major(d_dt, dt), d_head[:, 0], _major(d_b, bm),
            _major(d_c, cm), d_head[:, 1])


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)


def _chunked_pallas(x, dt, A, B, C, D) -> Tuple[jax.Array, jax.Array]:
    f32 = jnp.float32
    b, s, h, p = x.shape
    _, x, dt, B, C = _whole_chunks(CHUNK, x, dt, B, C)
    y, state = _scan_pallas(x, dt.astype(f32), A.astype(f32), B, C,
                            D.astype(f32))
    return y[:, :s], state.reshape(b, h, p, B.shape[-1])


def ssd(x, dt, A, B, C, D, chunk: int = CHUNK, return_state: bool = False):
    """The chunked form (module docstring); ``return_state`` also hands
    out the float32 state after the last token, ``[batch, heads,
    head_dim, state]``."""
    from bigdl_tpu.ops.dispatch import dispatch, launched
    from bigdl_tpu.ops.pallas_util import mosaic_dtype

    b, s, h, p = x.shape
    g, n = B.shape[2:]
    if h % g:
        raise ValueError(f"{h} heads over {g} groups")
    chunk = min(chunk, s)
    chunks = -(-s // chunk)
    hb = _head_block(h // g)
    said = dict(chunk=chunk, chunks=chunks, heads=h, head_dim=p, state=n,
                groups=g)
    state_bytes = h * p * n * 4
    # shapes and dtype only: a head is whole sublane tiles, a state whole
    # lane tiles, a record's states fit their share of VMEM
    supported = chunk == CHUNK and p % 16 == 0 and n % LANES == 0 \
        and x.dtype == B.dtype == C.dtype and mosaic_dtype(x.dtype) \
        and state_bytes <= STATE_BUDGET

    def kernels():
        launched(**said, head_block=hb, grid=(b, chunks, h // hb),
                 carry="vmem", calls=2, state_bytes=state_bytes)
        return _chunked_pallas(x, dt, A, B, C, D)

    def whole():
        launched(**said)
        return _chunked(x, dt, A, B, C, D, chunk)

    with jax.named_scope(SCOPE):
        y, state = dispatch("ssd", kernels, whole, bool(supported))
    return (y, state) if return_state else y
