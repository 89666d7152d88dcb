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
  The backward is the scan's own: it keeps the state that enters each
  chunk and nothing a token, and computes the rest again rather than
  keep it (a chunk's ``U``; what is local to the chunks: masks, decays,
  the triangular systems, :data:`HEAD_BLOCK` heads at a time); under
  ``nn.Remat`` that goes with the block.

Shapes: ``q``, ``k`` ``[batch, heads, seq, key_dim]``, ``v`` ``[batch,
heads, seq, value_dim]``, ``g``, ``beta`` ``[batch, heads, seq]``; the
result is ``v``'s shape and dtype.  A length the chunk does not divide
is padded with tokens that change nothing (``k = 0``, ``beta = 0``,
``g = 0``).

There is one leg, XLA's (batched products, ``lax.scan``): the decision
is announced on a ``kernel/dispatch`` instant all the same (``leg``,
``chunk``, ``chunks``, ``heads``, ``key_dim``, ``value_dim``), and the
rule's operations lie under the ``jax.named_scope`` :data:`SCOPE`: an
XLA dump and the profiler's op metadata carry it.  (The names of a
device trace's events do not, so the benchmark finds the rule's events
by the shapes only it has.)
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["gated_delta_rule", "gated_delta_rule_recurrent", "SCOPE",
           "CHUNK"]

#: the ``jax.named_scope`` around the chunked rule
SCOPE = "gated_delta_rule"

#: tokens a chunk: the size the layers run the rule at.  ``chunk=`` of
#: :func:`gated_delta_rule` is for the rule's own tests
CHUNK = 64

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


def _chunked(q, k, v, g, beta, chunk: int) -> Tuple[jax.Array, jax.Array]:
    b, h, s, dk = q.shape
    dv, dtype, f32 = v.shape[-1], v.dtype, jnp.float32
    n = -(-s // chunk)
    pad = n * chunk - s
    g, beta = g.astype(f32), beta.astype(f32)
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, 0), (0, pad))) for a in (g, beta))
    q, k, v = (a.reshape(b, h, n, chunk, a.shape[-1]) for a in (q, k, v))
    g, beta = g.reshape(b, h, n, chunk), beta.reshape(b, h, n, chunk)

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

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (w, kc, p, qg, kg, decay))
    # the backward keeps the state that enters a chunk and computes the
    # chunk's ``U`` from it again
    state, out = lax.scan(jax.checkpoint(step),
                          jnp.zeros((b, h, dk, dv), f32), xs)
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * chunk, dv)
    return out[:, :, :s], state


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK,
                     return_state: bool = False):
    """The chunked form (module docstring); ``return_state`` also hands
    out the float32 state after the last token, ``[batch, heads,
    key_dim, value_dim]``."""
    from bigdl_tpu.ops.dispatch import note

    b, h, s, dk = q.shape
    chunk = min(chunk, s)
    note("gated_delta_rule", "xla", "one-leg", leg="chunked-scan",
         chunk=chunk, chunks=-(-s // chunk), heads=h, key_dim=dk,
         value_dim=v.shape[-1])
    with jax.named_scope(SCOPE):
        out, state = _chunked(q, k, v, g, beta, chunk)
    return (out, state) if return_state else out
