"""Manifold-constrained hyper-connections (arXiv:2512.24880, after
Hyper-Connections, arXiv:2409.19606): a residual path of ``n`` streams
in place of one.

A decoder that carries ``n`` residual streams reads a sub-layer's input
as a token-dependent weighted sum of them, writes the sub-layer's output
back to each with a token-dependent weight, and mixes the ``n`` streams
with a token-dependent ``n x n`` matrix that Sinkhorn-Knopp iterations
make doubly stochastic.  :class:`HyperConnection` is that path around ONE
sub-layer; :class:`StreamExpand` and :class:`StreamSum` start and end the
streams.  ``nn.DecoderBlock(streams=n)`` puts one around its mixer and
one around its feed-forward.

The streams travel as ONE array ``[batch, seq, n * embed]``, stream ``i``
in columns ``i * embed`` to ``(i + 1) * embed``: a token's ``vec(X)`` is a
row of it as it lies, a stream a lane-aligned slice.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import Module, Parameter

__all__ = ["HyperConnection", "StreamExpand", "StreamSum", "sinkhorn"]

#: the ``jax.named_scope`` around the path's own work (the compiled
#: step's ``op_name`` metadata carries it, a device trace's events do not)
SCOPE = "mhc"

#: ``H_post = POST_GAIN * sigmoid(...)``: a write weight starts at 1
POST_GAIN = 2.0


def sinkhorn(m, iters: int):
    """``m`` [n, n, ...] positive (row, column, then anything): ``iters``
    times divide every column by its sum, then every row by its sum.
    Rows of the result sum to 1 exactly, columns within the iteration's
    error.  Nothing is added to a sum: the entries are positive."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=0, keepdims=True)
        m = m / jnp.sum(m, axis=1, keepdims=True)
    return m


class HyperConnection(Module):
    """The ``n``-stream residual path around one sub-layer ``F``, a token
    at a time (``X`` in ``R^{n x C}``, the token's streams):

    - ``x^ = vec(X) / sqrt(mean(vec(X)^2) + eps)`` over all ``nC``
      entries (no weight);
    - ``[p, q, R] = x^ Phi^T``, ``Phi`` in ``R^{(n + n + n^2) x nC}``,
      rows in that order;
    - ``H_pre = sigmoid(a_pre p + b_pre)``, ``H_post = 2 sigmoid(a_post q
      + b_post)``, ``H_res = sinkhorn(exp(clip(a_res mat(R) + b_res,
      -clamp, clamp)))`` (``mat`` row-major: entry ``n i + j`` is row
      ``i``, column ``j``), :func:`sinkhorn` over ``sinkhorn_iters``
      iterations;
    - ``u = sum_i H_pre[i] X[i]``; ``f = F(u)``; ``X'[i] = sum_j H_res[i,
      j] X[j] + H_post[i] f``.

    :meth:`forward` takes the streams and returns ``(u, (H_post,
    H_res))``; :meth:`merge` takes the streams, ``f`` and that pair and
    returns ``X'``.  Dtypes: the streams, ``u`` and ``X'`` stay in the
    streams' own (the compute dtype); the statistics, the projection's
    accumulation, the three coefficients and Sinkhorn are float32, and so
    is each weighted sum before it is handed back.  The projection reads
    the streams as they are and the norm's factor multiplies its 24
    results, which is the same number.  Sinkhorn's backward is autodiff's.

    Parameters: ``phi`` ``[2n + n^2, nC]``, ``bias`` ``[2n + n^2]`` (``b``
    in ``Phi``'s order), ``alpha`` ``[3]`` (``a_pre, a_post, a_res``).
    They start where the path is one stream's (``phi`` 0, ``alpha`` 1,
    ``b_pre`` and ``b_res`` picking stream 0 and the identity); a model
    loads its own.

    ``mhc_stats`` (a buffer, riding the step's state as a state-space
    mixer's ``ssm_stats`` does): of the last forward, the largest
    distance of a column sum of ``H_res`` from 1 (the iteration's error),
    the mean off-diagonal entry of ``H_res``, the mean of ``H_pre`` and
    of ``H_post``."""

    def __init__(self, embed_dim: int, streams: int,
                 sinkhorn_iters: int = 20, clamp: float = 30.0,
                 eps: float = 1e-6):
        super().__init__()
        if streams < 2:
            raise ValueError(f"a hyper-connection over {streams} stream: "
                             f"one stream is the plain residual add")
        n = streams
        self.embed_dim, self.streams = embed_dim, n
        self.sinkhorn_iters, self.clamp, self.eps = sinkhorn_iters, clamp, eps
        self.phi = Parameter(jnp.zeros((2 * n + n * n, n * embed_dim),
                                       jnp.float32))
        start = np.full((2 * n + n * n,), -8.0, np.float32)
        start[0] = 8.0                       # H_pre reads stream 0
        start[n:2 * n] = 0.0                 # H_post writes 1 to each
        start[2 * n:][::n + 1] = 8.0         # H_res the identity
        self.bias = Parameter(jnp.asarray(start))
        self.alpha = Parameter(jnp.ones((3,), jnp.float32))
        self.register_buffer("mhc_stats", jnp.zeros((4,), jnp.float32))

    def _streams_of(self, x):
        c = self.embed_dim
        return [x[..., i * c:(i + 1) * c] for i in range(self.streams)]

    def coefficients(self, x) -> Tuple:
        """``x`` [B, S, nC] -> ``H_pre`` [n, B, S], ``H_post`` [n, B, S],
        ``H_res`` [n, n, B, S], float32, the token axes LAST: sixteen
        numbers a token lie as sixteen rows of tokens, which is how the
        chip's tiles hold them without padding."""
        n, f32 = self.streams, jnp.float32
        mean_sq = jnp.mean(jnp.square(x.astype(f32)), axis=-1)     # [B, S]
        raw = jnp.einsum("kc,bsc->kbs", self.phi.astype(x.dtype), x,
                         preferred_element_type=f32)
        proj = raw * jax.lax.rsqrt(mean_sq + self.eps)[None]
        bias, alpha = self.bias.astype(f32), self.alpha.astype(f32)
        pre = jax.nn.sigmoid(alpha[0] * proj[:n] + bias[:n, None, None])
        post = POST_GAIN * jax.nn.sigmoid(alpha[1] * proj[n:2 * n]
                                          + bias[n:2 * n, None, None])
        logits = alpha[2] * proj[2 * n:] + bias[2 * n:, None, None]
        m = jnp.exp(jnp.clip(logits, -self.clamp, self.clamp))
        res = sinkhorn(m.reshape((n, n) + m.shape[1:]), self.sinkhorn_iters)
        return pre, post, res

    def update_output(self, input):
        from bigdl_tpu import telemetry

        n, f32 = self.streams, jnp.float32
        telemetry.instant("residual/mhc", streams=n,
                          sinkhorn_iters=self.sinkhorn_iters,
                          clamp=self.clamp, eps=self.eps,
                          embed_dim=self.embed_dim, dtype=str(input.dtype))
        with jax.named_scope(SCOPE):
            pre, post, res = self.coefficients(input)
            off = 1.0 - jnp.eye(n, dtype=f32)[:, :, None, None]
            self.mhc_stats = jax.lax.stop_gradient(jnp.stack([
                jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0)),
                jnp.sum(res * off) / (res.size * (n - 1) / n),
                jnp.mean(pre), jnp.mean(post)]))
            u = sum(pre[i][..., None] * xi.astype(f32)
                    for i, xi in enumerate(self._streams_of(input)))
            return u.astype(input.dtype), (post, res)

    def merge(self, x, f, mix):
        """The streams after the sub-layer: ``X'[i] = sum_j H_res[i, j]
        X[j] + H_post[i] f``."""
        post, res = mix
        f32 = jnp.float32
        with jax.named_scope(SCOPE):
            xs = [xi.astype(f32) for xi in self._streams_of(x)]
            f = f.astype(f32)
            # each stream back to its dtype BEFORE they are joined: the
            # backward then converts a stream's cotangent where it reads
            # it, and never writes the joined one out in float32
            out = [(sum(res[i, j][..., None] * xj for j, xj in enumerate(xs))
                    + post[i][..., None] * f).astype(x.dtype)
                   for i in range(self.streams)]
            return jnp.concatenate(out, axis=-1)

    def step_counters(self, buffers, tele, layer: str):
        """``mhc/col_err_max``, ``mhc/res_offdiag_mean``, ``mhc/pre_mean``
        and ``mhc/post_mean`` of the last step, from this path's buffer as
        the step left it (the Optimizer calls this where it has the loss
        on the host)."""
        err, off, pre, post = (float(v) for v in np.asarray(
            buffers["mhc_stats"], np.float64))
        tele.counter("mhc/col_err_max", err, layer=layer)
        tele.counter("mhc/res_offdiag_mean", off, layer=layer)
        tele.counter("mhc/pre_mean", pre, layer=layer)
        tele.counter("mhc/post_mean", post, layer=layer)

    def __repr__(self):
        return (f"HyperConnection({self.embed_dim}, streams={self.streams}, "
                f"sinkhorn_iters={self.sinkhorn_iters})")


class StreamExpand(Module):
    """[B, S, C] -> [B, S, nC]: every stream starts as a copy of the
    embedding."""

    def __init__(self, streams: int):
        super().__init__()
        self.streams = streams

    def update_output(self, input):
        return jnp.tile(input, (1, 1, self.streams))


class StreamSum(Module):
    """[B, S, nC] -> [B, S, C]: the streams' sum (in float32, handed back
    in the streams' dtype), which the final norm reads."""

    def __init__(self, streams: int):
        super().__init__()
        self.streams = streams

    def update_output(self, input):
        b, s, nc = input.shape
        split = input.reshape(b, s, self.streams, nc // self.streams)
        return jnp.sum(split.astype(jnp.float32), axis=2).astype(input.dtype)
