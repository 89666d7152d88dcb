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
  ``lax.scan`` over the chunks that carries ``S`` and does no product:
  ``S' = exp(L_Q) S + S_own``.  The states that enter the chunks come out
  of it stacked, and ``exp(L_t) S C_t`` is one more batched product.
  Every exponent is of a number that is not positive.  Matrix products
  take their operands in ``x``'s dtype and accumulate in float32; the
  decays and the carried state are float32.  The backward is autodiff's:
  the scan keeps the state that enters each chunk.

Shapes: ``x`` ``[batch, seq, heads, head_dim]``, ``dt`` ``[batch, seq,
heads]``, ``A``, ``D`` ``[heads]``, ``B``, ``C`` ``[batch, seq, groups,
state]``; the result is ``x``'s shape and dtype.  A length the chunk does
not divide is padded with tokens that change nothing (``dt = 0``).

ONE leg computes it, XLA's, on every platform: nothing is chosen, and
the PR that brings a Pallas leg for the chunk-local products brings
``ops/dispatch.choose_backend`` with it (the layer calls :func:`ssd` and
will not know).  The leg is announced on a ``kernel/dispatch`` instant
(``op=ssd``, ``backend="xla"``, ``reason="only-leg"``, ``chunk``,
``chunks``, ``heads``, ``head_dim``,
``state``, ``groups``), once a compilation, and the scan's operations lie
under the ``jax.named_scope`` :data:`SCOPE`: an XLA dump and the
profiler's op metadata carry it.  (The names of a device trace's events
do not, so the benchmark finds the scan's events by the shapes only it
has.)
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

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


def _chunked(x, dt, A, B, C, D, chunk: int) -> Tuple[jax.Array, jax.Array]:
    f32 = jnp.float32
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    r, dtype = h // g, x.dtype
    c = -(-s // chunk)
    pad = c * chunk - s
    if pad:
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, B, C))
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


def ssd(x, dt, A, B, C, D, chunk: int = CHUNK, return_state: bool = False):
    """The chunked form (module docstring); ``return_state`` also hands
    out the float32 state after the last token, ``[batch, heads,
    head_dim, state]``."""
    from bigdl_tpu.ops.dispatch import note

    b, s, h, p = x.shape
    g, n = B.shape[2:]
    if h % g:
        raise ValueError(f"{h} heads over {g} groups")
    chunk = min(chunk, s)
    # ONE leg on every platform, as the short convolution's: announced,
    # not chosen (a second leg brings ``choose_backend`` with it)
    note("ssd", "xla", "only-leg", chunk=chunk, chunks=-(-s // chunk),
         heads=h, head_dim=p, state=n, groups=g)
    with jax.named_scope(SCOPE):
        y, state = _chunked(x, dt, A, B, C, D, chunk)
    return (y, state) if return_state else y
