"""The gated delta rule (Yang et al. 2024, "Gated Delta Networks"): the
recurrence of a linear-attention layer whose state is a ``[key_dim,
value_dim]`` matrix a head, decayed and corrected token by token,

    S_t = exp(g_t) S_{t-1} + k_t (beta_t (v_t - (exp(g_t) S_{t-1})^T k_t))^T
    o_t = S_t^T q_t                                     from S_0 = 0,

with ``g_t <= 0`` the log of the decay and ``beta_t`` in [0, 1] the
strength of the correction.  Two forms stand here:

- :func:`gated_delta_rule_recurrent` — the definition, one ``lax.scan``
  step a token.  It is what the tests compare with; at 16,384 positions
  it is as many dependent rank-one updates and its backward keeps a
  state a token.
- :func:`gated_delta_rule` — the chunked form that trains.  Inside a
  chunk of ``C`` tokens, with ``G_t`` the decays' running sum and ``S``
  the state that enters the chunk, the corrections ``u_t = beta_t (v_t -
  ...)`` solve ONE unit-lower-triangular system,

      (I + L) U = beta V - (beta exp(G) K) S,
      L[t, j] = beta_t exp(G_t - G_j) (k_t . k_j)   for j < t,

  so ``U = W - Kc S`` with ``W`` and ``Kc`` the solutions for the two
  right-hand sides, both free of ``S`` and computed for all chunks at
  once as batched products.  What is left to do in order is a
  ``lax.scan`` over the chunks that carries ``S``:

      U   = W - Kc S
      o   = (exp(G) Q) S + (Q K^T . exp(G_t - G_j), j <= t) U
      S' = exp(G_C) S + (exp(G_C - G) K)^T U.

  Every exponent is of a number that is not positive.  Matrix products
  take their operands in the inputs' dtype and accumulate in float32;
  the decays, the triangular solve and the carried state are float32.
  The backward keeps the state that enters each chunk and nothing a
  token, and computes the rest again rather than keep it (a chunk's
  ``U``; what is local to the chunks: masks, decays, the triangular
  systems); under ``nn.Remat`` that goes with the block.

Shapes: ``q``, ``k`` ``[batch, heads, seq, key_dim]``, ``v`` ``[batch,
heads, seq, value_dim]``, ``g``, ``beta`` ``[batch, heads, seq]``; the
result is ``v``'s shape and dtype.  A length the chunk does not divide
is padded with tokens that change nothing (``k = 0``, ``beta = 0``,
``g = 0``).

Two legs compute what is local to the chunks (``W``, ``Kc``, ``P =`` the
masked ``Q K^T``, ``exp(G) Q``, ``exp(G_C - G) K``, ``exp(G_C)``); the
scan over the chunks is one ``lax.scan`` for both, and its backward is
autodiff's.  ``ops/dispatch.choose_backend`` picks, from what the trace
can see:

- XLA's (:func:`_chunk_local`): batched products and
  ``lax.linalg.triangular_solve`` for all chunks at once through HBM,
  :data:`HEAD_BLOCK` heads at a time under ``lax.map`` and
  ``jax.checkpoint`` so that no float32 mask or system exists for all
  heads or outlives its pass; autodiff's backward.  Off the TPU, inside
  ``dispatch.spmd_partitioned`` (a Mosaic kernel cannot be partitioned
  over a mesh), under ``BIGDL_KERNELS=xla``, and at a shape the kernels
  do not take.
- Pallas's (:func:`_chunk_local_pallas`, a ``jax.custom_vjp`` whose
  residuals are its five inputs), on a TPU when ``chunk`` is
  :data:`CHUNK`, ``key_dim`` and ``value_dim`` are multiples of 128 and
  q, k, v share a dtype Mosaic compiles.  A grid step takes
  :data:`CHUNK_BLOCK` chunks of one head (the sequence is padded to whole
  grid steps) and works on all of them at once.  The forward kernel
  reads q, k, v, g, beta through its block index maps, builds the decays'
  running sums, the ``fade`` mask, ``k k^T``, ``q k^T`` and ``I + L``
  in VMEM, inverts ``I + L`` there in float32 (diagonal blocks of 2, 4,
  ... 64 merged by products: forward substitution, never a power of
  ``L``), and writes only what the scan reads, in the order the scan
  reads it (``[chunks, batch, heads, C, *]``: ``W`` float32, the others
  in the inputs' dtype).  The backward kernel takes the scan's
  cotangents of those five in that order, computes the chunk's internals
  again in VMEM and writes ``dq``, ``dk``, ``dv``, ``dg``, ``dbeta``
  (through the solve ``d rhs = M^-T d solved``, ``d L = -tril(d rhs
  solved^T, -1)``; ``dg`` by the reverse running sum inside the chunk).
  No mask, exponent table, system or inverse reaches HBM.  A chunk's
  decay ``exp(G_C)``, one number a chunk and head, is left to XLA.
  The decays' sums and every product the inverse enters are float32
  (``Precision.HIGHEST``); the inverse itself is a three-pass first
  guess and one Newton step whose residual is float32
  (:func:`_unit_lower_inverse`), as close to the exact inverse as
  ``lax.linalg.triangular_solve`` is.

The decision is announced on a ``kernel/dispatch`` instant
(``op=gated_delta_rule``, ``backend``, ``reason``, ``leg``, ``chunk``,
``chunks``, ``heads``, ``key_dim``, ``value_dim`` and, for the Pallas
leg, ``chunks_per_block`` and ``grid``), once a compilation, and the
rule's operations lie under the ``jax.named_scope`` :data:`SCOPE`: an
XLA dump and the profiler's op metadata carry it.  (The names of a
device trace's events do not, so the benchmark finds the rule's events
by the shapes only it has; the kernels keep to the chunked shapes it
names, and return no ``[.., heads, seq, dim]`` array.)
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gated_delta_rule", "gated_delta_rule_recurrent", "SCOPE",
           "CHUNK"]

#: the ``jax.named_scope`` around the chunked rule
SCOPE = "gated_delta_rule"

#: tokens a chunk: the size the layers run the rule at.  ``chunk=`` of
#: :func:`gated_delta_rule` is for the rule's own tests
CHUNK = 64

#: chunks a grid step of the Pallas leg takes: a grid step costs ~2.3 us
#: whatever its block, and 32 heads x 256 chunks of them would be 19 ms a
#: call (found by a sweep on the chip, PERF.md section 6, PR 33)
CHUNK_BLOCK = 8

#: heads whose chunk-local work (the triangular systems, in float32) is
#: done at once; the scan over the chunks takes all heads together
HEAD_BLOCK = 8


def gated_delta_rule_recurrent(q, k, v, g, beta, return_state: bool = False):
    """The recurrence token by token, in float32: the definition."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    hi = lax.Precision.HIGHEST

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None, None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, kt, precision=hi)
        u = bt[..., None] * (vt - seen)
        state = state + kt[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt, precision=hi)

    b, h, _, dk = k.shape
    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta))
    state, out = lax.scan(token, jnp.zeros((b, h, dk, v.shape[-1]), f32), xs)
    out = jnp.moveaxis(out, 0, 2)
    return (out, state) if return_state else out


def _chunk_local(q, k, v, g, beta):
    """Everything of a chunk that does not need the entering state, for
    all chunks at once.  Inputs ``[..., chunks, C, dim]`` (``g``, ``beta``
    ``[..., chunks, C]``); ``(W, Kc, P, Qg, Kg, decay)`` as the module
    docstring names them."""
    f32 = jnp.float32
    c = q.shape[-2]

    def product(a, b_):
        return jnp.einsum("...td,...jd->...tj", a, b_,
                          preferred_element_type=f32)

    total = jnp.cumsum(g, axis=-1)                       # G_t, float32
    rows = jnp.arange(c)
    below = rows[:, None] > rows[None, :]                # j < t
    upto = rows[:, None] >= rows[None, :]                # j <= t
    gap = total[..., :, None] - total[..., None, :]      # G_t - G_j
    # the exponent is masked BEFORE the exponential: above the diagonal
    # it is positive, and an overflow there would reach the gradient
    fade = jnp.exp(jnp.where(upto, gap, 0.0))
    lower = jnp.where(below, beta[..., :, None] * fade * product(k, k), 0.0)
    rhs = jnp.concatenate(
        [v.astype(f32) * beta[..., None],
         k.astype(f32) * (beta * jnp.exp(total))[..., None]], axis=-1)
    solved = lax.linalg.triangular_solve(
        lower + jnp.eye(c, dtype=f32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w, kc = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    p = jnp.where(upto, fade * product(q, k), 0.0)
    qg = q.astype(f32) * jnp.exp(total)[..., None]
    last = total[..., -1:]
    kg = k.astype(f32) * jnp.exp(last - total)[..., None]
    return w, kc, p, qg, kg, jnp.exp(last[..., 0])


def _in_chunks(q, k, v, g, beta, n: int, chunk: int):
    """``[batch, heads, n, chunk, *]``: the sequence padded to ``n``
    chunks with tokens that change nothing; g and beta in float32."""
    b, h, s, _ = q.shape
    pad = n * chunk - s
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, 0), (0, pad))) for a in (g, beta))
    q, k, v = (a.reshape(b, h, n, chunk, a.shape[-1]) for a in (q, k, v))
    return q, k, v, g.reshape(b, h, n, chunk), beta.reshape(b, h, n, chunk)


def _scan_chunks(xs, dtype) -> Tuple[jax.Array, jax.Array]:
    """The part that runs in order: ``(W, Kc, P, Qg, Kg, decay)`` stacked
    chunk-major (``[chunks, batch, heads, C, *]``, decay ``[chunks,
    batch, heads]``) -> the last state and the outputs, chunk-major."""
    f32 = jnp.float32
    w, kc = xs[:2]

    def step(state, x):
        w_i, kc_i, p_i, qg_i, kg_i, decay_i = x
        low = state.astype(dtype)
        u = w_i - jnp.einsum("bhtk,bhkv->bhtv", kc_i, low,
                             preferred_element_type=f32)
        u_low = u.astype(dtype)
        out = jnp.einsum("bhtk,bhkv->bhtv", qg_i, low,
                         preferred_element_type=f32) \
            + jnp.einsum("bhtj,bhjv->bhtv", p_i, u_low,
                         preferred_element_type=f32)
        state = state * decay_i[..., None, None] \
            + jnp.einsum("bhtk,bhtv->bhkv", kg_i, u_low,
                         preferred_element_type=f32)
        return state, out.astype(dtype)

    # the backward keeps the state that enters a chunk and computes the
    # chunk's ``U`` from it again
    return lax.scan(
        jax.checkpoint(step),
        jnp.zeros(w.shape[1:3] + (kc.shape[-1], w.shape[-1]), f32), xs)


def _chunked(q, k, v, g, beta, chunk: int) -> Tuple[jax.Array, jax.Array]:
    b, h, s, _ = q.shape
    dv, dtype = v.shape[-1], v.dtype
    n = -(-s // chunk)
    q, k, v, g, beta = _in_chunks(q, k, v, g, beta, n, chunk)

    def local(args):
        # the products of the scan take the inputs' dtype, as the others do
        w, kc, p, qg, kg, decay = _chunk_local(*args)
        return (w,) + tuple(a.astype(dtype) for a in (kc, p, qg, kg)) \
            + (decay,)

    # computed again in the backward pass, and a block of heads at a
    # time: what is kept of a chunk is what the scan reads, and the masks,
    # decays and systems behind it never exist for all heads at once
    block = max(d for d in range(1, HEAD_BLOCK + 1) if h % d == 0)
    by_block = tuple(jnp.moveaxis(a.reshape(
        (b, h // block, block) + a.shape[2:]), 1, 0)
        for a in (q, k, v, g, beta))
    w, kc, p, qg, kg, decay = (
        jnp.moveaxis(a, 0, 1).reshape((b, h) + a.shape[3:])
        for a in lax.map(jax.checkpoint(local), by_block))

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (w, kc, p, qg, kg, decay))
    state, out = _scan_chunks(xs, dtype)
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * chunk, dv)
    return out[:, :, :s], state


# -- the Pallas leg: a chunk's systems in VMEM -----------------------------------
#
# A grid step holds CHUNK_BLOCK chunks of one head and works on all of
# them at once, ``[chunks, C, *]`` arrays and batched products: the
# products of one chunk depend on each other (ten in a row for the
# inverse alone), and the compiler keeps the order it is given, so a loop
# over the chunks would wait out every product's latency.

_NN = ((2,), (1,))      # a b, a chunk
_NT = ((2,), (2,))      # a b^T
_TN = ((1,), (1,))      # a^T b


def _mm(a, b, form=_NN, passes=6):
    """A product on the MXU a chunk (or of two matrices), float32 out,
    operands of one dtype.  bfloat16 operands: one pass.  float32
    operands: as float32 (``HIGHEST``, six bfloat16 passes: the decays'
    sums, the solve) unless ``passes`` is 3: each operand split in a
    bfloat16 head and tail, the tails' own product (2^-16 of the result)
    left out, for a first guess that is then corrected."""
    batched = a.ndim == 3
    dims = (form, ((0,), (0,))) if batched else \
        (tuple((d[0] - 1,) for d in form), ((), ()))

    def dot(x, y, precision=None):
        return lax.dot_general(x, y, dims, precision=precision,
                               preferred_element_type=jnp.float32)

    if a.dtype != jnp.float32:
        return dot(a, b)
    if passes == 6:
        return dot(a, b, lax.Precision.HIGHEST)
    (a_head, a_tail), (b_head, b_tail) = _split(a), _split(b)
    return dot(a_head, b_head) + (dot(a_head, b_tail) + dot(a_tail, b_head))


def _split(x):
    head = x.astype(jnp.bfloat16)
    return head, (x - head.astype(jnp.float32)).astype(jnp.bfloat16)


def _grid_of(c):
    rows = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return rows, cols


def _unit_lower_inverse(low, rows, cols):
    """``(I + low)^-1`` for strictly lower-triangular ``[.., C, C]``,
    ``C`` a power of two, in float32: the inverses of the diagonal blocks
    of 2, then 4, ... merged by products,

        [[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]],

    all blocks of a size at once.  As forward substitution it never
    forms a power of ``low`` (the nilpotent product ``(I - L)(I + L^2)
    ...`` does, and loses every digit once keys repeat under ``beta``
    and decays near 1: its terms reach 1e18 where the inverse's entries
    are 1).  The ten products of the merging take three passes each and
    leave ``X`` some 1e-5 from the inverse; one Newton step ``X + X (I -
    (I + low) X)`` with the residual in full float32 squares that gap
    (the correction, 1e-5 of ``X``, needs one pass): float32's own
    rounding is what is left, at 37 passes for 60."""
    def below_left(s):
        # the lower left quarter of every diagonal block of size 2 s
        return ((rows & -(2 * s)) == (cols & -(2 * s))) \
            & ((rows & s) != 0) & ((cols & s) == 0)

    eye = jnp.where(rows == cols, 1.0, 0.0)
    inv = eye - jnp.where(below_left(1), low, 0.0)
    s = 2
    while s < low.shape[-1]:
        inv = inv - _mm(inv, _mm(jnp.where(below_left(s), low, 0.0), inv,
                                 passes=3), passes=3)
        s *= 2
    residual = eye - _mm(eye + low, inv)
    return inv + _mm(inv.astype(jnp.bfloat16), residual.astype(jnp.bfloat16))


def _columns_and_rows(g_ref, beta_ref, rows, cols):
    """The decays' running sums of every chunk of the block as columns
    ``[chunks, C, 1]`` and rows ``[chunks, 1, C]``, and ``beta`` as
    columns: the MXU transposes (the identity against ``x^T``, at
    ``HIGHEST``: exactly)."""
    g, beta = g_ref[...], beta_ref[...]                  # [chunks, C]
    eye = jnp.where(rows == cols, 1.0, 0.0)
    as_rows = _mm(g, jnp.where(rows >= cols, 1.0, 0.0), _NT)   # i <= j
    # the SAME sums as columns: G_t - G_t has to be 0
    as_cols, beta_cols = _mm(eye, as_rows, _NT), _mm(eye, beta, _NT)
    each = range(g.shape[0])
    return (jnp.stack([as_cols[:, i:i + 1] for i in each]),
            jnp.stack([as_rows[i:i + 1] for i in each]),
            jnp.stack([beta_cols[:, i:i + 1] for i in each]))


def _chunk_systems(q, k, v, g_ref, beta_ref):
    """What both kernels compute of the block's chunks, all of it in
    VMEM: ``q``, ``k`` ``[chunks, C, key_dim]``, ``v`` ``[chunks, C,
    value_dim]``."""
    f32 = jnp.float32
    rows, cols = _grid_of(q.shape[1])
    upto, below = rows >= cols, rows > cols
    total_c, total, beta_c = _columns_and_rows(g_ref, beta_ref, rows, cols)
    # masked BEFORE the exponential, as in _chunk_local
    fade = jnp.exp(jnp.where(upto, total_c - total, 0.0))
    kk, qk = _mm(k, k, _NT), _mm(q, k, _NT)
    low = jnp.where(below, beta_c * fade * kk, 0.0)
    inv = _unit_lower_inverse(low, rows, cols)
    grow = jnp.exp(total_c)                              # exp(G_t)
    w = _mm(inv, v.astype(f32) * beta_c)
    kc = _mm(inv, k.astype(f32) * (beta_c * grow))
    p = jnp.where(upto, fade * qk, 0.0)
    shrink = jnp.exp(total[:, :, -1:] - total_c)         # exp(G_C - G_t)
    return dict(eye=rows == cols, upto=upto, below=below,
                beta_c=beta_c, fade=fade, kk=kk, low=low, inv=inv,
                grow=grow, shrink=shrink, w=w, kc=kc, p=p)


def _local_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                      w_ref, kc_ref, p_ref, qg_ref, kg_ref):
    f32 = jnp.float32
    q, k = q_ref[...], k_ref[...]
    m = _chunk_systems(q, k, v_ref[...], g_ref, beta_ref)
    w_ref[...] = m["w"]
    kc_ref[...] = m["kc"].astype(kc_ref.dtype)
    p_ref[...] = m["p"].astype(p_ref.dtype)
    qg_ref[...] = (q.astype(f32) * m["grow"]).astype(qg_ref.dtype)
    kg_ref[...] = (k.astype(f32) * m["shrink"]).astype(kg_ref.dtype)


def _local_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                      dw_ref, dkc_ref, dp_ref, dqg_ref, dkg_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dtotal_ref):
    f32 = jnp.float32
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    low_t = q.dtype
    m = _chunk_systems(q, k, v, g_ref, beta_ref)
    eye, beta_c, fade, grow, shrink = (
        m[n] for n in ("eye", "beta_c", "fade", "grow", "shrink"))
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)

    def over_dim(x):                      # [chunks, C, dim] -> [chunks, C, 1]
        return jnp.sum(x, axis=2, keepdims=True)

    def as_row(col):                      # [chunks, C, 1] -> [chunks, 1, C]
        return jnp.sum(jnp.where(eye, col, 0.0), axis=1, keepdims=True)

    # through the solve: d rhs = M^-T d solved, d M = -d rhs solved^T
    d_rhs_v = _mm(m["inv"], dw_ref[...], _TN)
    d_rhs_k = _mm(m["inv"], dkc_ref[...].astype(f32), _TN)
    d_low = -jnp.where(m["below"], _mm(d_rhs_v, m["w"], _NT)
                       + _mm(d_rhs_k, m["kc"], _NT), 0.0)
    # low = beta_t fade k k^T, p = fade q k^T; gap = G_t - G_j
    d_p = dp_ref[...].astype(f32)
    d_gap = d_low * m["low"] + d_p * m["p"]
    d_kk = (beta_c * (d_low * fade)).astype(low_t)
    d_qk = jnp.where(m["upto"], d_p * fade, 0.0).astype(low_t)
    k_rhs = over_dim(d_rhs_k * kf)
    d_qg, d_kg = dqg_ref[...].astype(f32), dkg_ref[...].astype(f32)
    dq_ref[...] = (_mm(d_qk, k) + d_qg * grow).astype(dq_ref.dtype)
    dk_ref[...] = (_mm(d_qk, q, _TN) + _mm(d_kk, k) + _mm(d_kk, k, _TN)
                   + d_rhs_k * (beta_c * grow)
                   + d_kg * shrink).astype(dk_ref.dtype)
    dv_ref[...] = (d_rhs_v * beta_c).astype(dv_ref.dtype)
    d_beta = as_row(over_dim(d_low * fade * m["kk"]) + over_dim(d_rhs_v * vf)
                    + k_rhs * grow)
    # d G_t: rows of d gap less its columns, the two exponentials, and
    # exp(G_C - G_t)'s part in G_C on the chunk's last token
    d_shrink = over_dim(d_kg * kf) * shrink
    d_total = as_row(over_dim(d_gap) + (over_dim(d_qg * qf)
                                        + beta_c * k_rhs) * grow - d_shrink) \
        - jnp.sum(d_gap, axis=1, keepdims=True) \
        + jnp.where(lax.broadcasted_iota(jnp.int32, (1, 1, q.shape[1]), 2)
                    == q.shape[1] - 1,
                    jnp.sum(d_shrink, axis=1, keepdims=True), 0.0)
    for i in range(q.shape[0]):
        dbeta_ref[pl.ds(i, 1), :] = d_beta[i]
        dtotal_ref[pl.ds(i, 1), :] = d_total[i]
    # d g_i: the sum over t >= i of d G_t
    dg_ref[...] = _mm(dtotal_ref[...], jnp.where(m["upto"], 1.0, 0.0))


def _local_specs(b, h, n, dk, dv):
    """Block specs of a grid step's :data:`CHUNK_BLOCK` chunks: the
    inputs' order ``[batch, heads, chunks, C, *]`` and the scan's
    ``[chunks, batch, heads, C, *]``."""
    cb, c = CHUNK_BLOCK, CHUNK

    def by_head(*tail):
        return pl.BlockSpec((None, None, cb) + tail,
                            lambda i, j, m: (i, j, m) + (0,) * len(tail))

    def by_chunk(*tail):
        return pl.BlockSpec((cb, None, None) + tail,
                            lambda i, j, m: (m, i, j) + (0,) * len(tail))

    inputs = [by_head(c, dk), by_head(c, dk), by_head(c, dv), by_head(c),
              by_head(c)]
    scanned = [by_chunk(c, dv), by_chunk(c, dk), by_chunk(c, c),
               by_chunk(c, dk), by_chunk(c, dk)]
    return inputs, scanned, (b, h, n // cb)


def _scanned_shapes(b, h, n, dk, dv, dtype):
    c = CHUNK
    return [jax.ShapeDtypeStruct((n, b, h, c, dv), jnp.float32)] + [
        jax.ShapeDtypeStruct((n, b, h, c, d), dtype) for d in (dk, c, dk, dk)]


def _pallas_call(kernel, grid, in_specs, out_specs, out_shape, scratch):
    from bigdl_tpu.ops.dispatch import use_interpret

    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((CHUNK_BLOCK, CHUNK), jnp.float32)
                        for _ in range(scratch)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=use_interpret())


@jax.custom_vjp
def _chunk_local_pallas(q, k, v, g, beta):
    """:func:`_chunk_local` without its decay, as one kernel: ``[batch,
    heads, chunks, C, *]`` in, ``(W, Kc, P, Qg, Kg)`` out in the scan's
    order, ``W`` float32 and the others in ``v``'s dtype."""
    b, h, n, _, dk = q.shape
    dv = v.shape[-1]
    inputs, scanned, grid = _local_specs(b, h, n, dk, dv)
    return tuple(_pallas_call(
        _local_fwd_kernel, grid, inputs, scanned,
        _scanned_shapes(b, h, n, dk, dv, v.dtype), scratch=0)(
            q, k, v, g, beta))


def _chunk_local_pallas_fwd(q, k, v, g, beta):
    return _chunk_local_pallas(q, k, v, g, beta), (q, k, v, g, beta)


def _chunk_local_pallas_bwd(kept, cotangents):
    q, k, v, g, beta = kept
    b, h, n, _, dk = q.shape
    dv = v.shape[-1]
    inputs, scanned, grid = _local_specs(b, h, n, dk, dv)
    return tuple(_pallas_call(
        _local_bwd_kernel, grid, inputs + scanned, inputs,
        [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in kept], scratch=1)(
            *kept, *cotangents))


_chunk_local_pallas.defvjp(_chunk_local_pallas_fwd, _chunk_local_pallas_bwd)


def _pallas_chunks(s: int) -> int:
    """Chunks the Pallas leg runs a sequence of ``s`` tokens in: whole
    grid steps of :data:`CHUNK_BLOCK`."""
    return -(-s // (CHUNK * CHUNK_BLOCK)) * CHUNK_BLOCK


def _chunked_pallas(q, k, v, g, beta) -> Tuple[jax.Array, jax.Array]:
    b, h, s, _ = q.shape
    dv, n = v.shape[-1], _pallas_chunks(s)
    q, k, v, g, beta = _in_chunks(q, k, v, g, beta, n, CHUNK)
    # the kernel writes in the order the scan reads; a chunk's decay is
    # one number a head, left to XLA
    decay = jnp.moveaxis(jnp.exp(jnp.sum(g, axis=-1)), 2, 0)
    state, out = _scan_chunks(
        _chunk_local_pallas(q, k, v, g, beta) + (decay,), v.dtype)
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * CHUNK, dv)
    return out[:, :, :s], state


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK,
                     return_state: bool = False):
    """The chunked form (module docstring); ``return_state`` also hands
    out the float32 state after the last token, ``[batch, heads,
    key_dim, value_dim]``."""
    from bigdl_tpu.ops.dispatch import choose_backend, note
    from bigdl_tpu.ops.pallas_util import mosaic_dtype

    b, h, s, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    supported = chunk == CHUNK and dk % 128 == 0 and dv % 128 == 0 \
        and q.dtype == k.dtype == v.dtype and mosaic_dtype(v.dtype)
    backend, reason = choose_backend("gated_delta_rule", supported)
    said = dict(leg="chunked-scan", chunk=chunk, chunks=-(-s // chunk),
                heads=h, key_dim=dk, value_dim=dv)
    if backend == "pallas":
        n = _pallas_chunks(s)
        said.update(leg="chunk-kernels-scan", chunks=n,
                    chunks_per_block=CHUNK_BLOCK,
                    grid=(b, h, n // CHUNK_BLOCK))
    note("gated_delta_rule", backend, reason, **said)
    with jax.named_scope(SCOPE):
        if backend == "pallas":
            out, state = _chunked_pallas(q, k, v, g, beta)
        else:
            out, state = _chunked(q, k, v, g, beta, chunk)
    return (out, state) if return_state else out
