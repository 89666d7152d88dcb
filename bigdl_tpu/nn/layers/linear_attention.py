"""Linear-attention layers: a mixer whose memory of the sequence is a
fixed-size state a head, not keys and values a token.

:class:`GatedDeltaNet` is the gated delta rule (Yang et al. 2024) as
current hybrid decoders build it, three such layers to one softmax
layer.  The recurrence itself is ``ops.delta_rule.gated_delta_rule``
(a chunked scan with its backward); this layer is everything around it.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import Module, Parameter

__all__ = ["GatedDeltaNet", "GatedRMSNorm"]


class GatedRMSNorm(Module):
    """``forward((x, z))``: ``w * x / sqrt(mean(x^2) + eps) * silu(z)``
    over the last dimension, norm before gate, ``w`` starting at 1; all
    of it in float32, the result in ``x``'s dtype.

    ``gate_first``: the other order, ``w * norm(x * silu(z))``.
    ``group_size``: the mean of squares is taken over each run of
    ``group_size`` channels of the last dimension on its own (it divides
    ``normalized_size``; ``w`` stays one scale a channel)."""

    def __init__(self, normalized_size: int, eps: float = 1e-6,
                 gate_first: bool = False,
                 group_size: Optional[int] = None):
        super().__init__()
        if group_size is not None and normalized_size % group_size:
            raise ValueError(f"groups of {group_size} channels over "
                             f"{normalized_size}")
        self.normalized_size, self.eps = normalized_size, eps
        self.gate_first, self.group_size = gate_first, group_size
        self.weight = Parameter(jnp.ones((normalized_size,), jnp.float32))

    def _norm(self, y):
        if self.group_size is None:
            return y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + self.eps)
        groups = y.reshape(y.shape[:-1] + (-1, self.group_size))
        groups = groups * jax.lax.rsqrt(
            jnp.mean(groups * groups, axis=-1, keepdims=True) + self.eps)
        return groups.reshape(y.shape)

    def update_output(self, input):
        x, z = input
        y = x.astype(jnp.float32)
        if self.gate_first:
            y = self._norm(y * jax.nn.silu(z.astype(jnp.float32))) \
                * self.weight.astype(jnp.float32)
            return y.astype(x.dtype)
        y = self._norm(y)
        y = y * self.weight.astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))
        return y.astype(x.dtype)

    def __repr__(self):
        return f"GatedRMSNorm({self.normalized_size})"


class GatedDeltaNet(Module):
    """Gated delta-rule linear attention over [batch, seq, embed], no
    bias anywhere.  With ``u`` the input, ``Hk`` key heads of ``key_dim``
    and ``Hv`` value heads of ``value_dim`` (``Hk`` divides ``Hv``; key
    head ``h // (Hv / Hk)`` serves value head ``h``):

    - ``[q, k, v, z] = u W_qkvz`` (``Hk key_dim``, ``Hk key_dim``, ``Hv
      value_dim``, ``Hv value_dim`` columns, in that order) and ``[b, a] =
      u W_ba`` (``Hv`` each);
    - ``[q, k, v] <- silu(conv([q, k, v]))``: a causal depthwise
      convolution of width ``conv_width`` along the sequence, every
      channel on its own (the last tap is the position itself);
    - q and k l2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), q then
      divided by ``sqrt(key_dim)``;
    - ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)`` a
      value head, in float32;
    - ``o = gated_delta_rule(q, k, v, g, beta)`` from a zero state, in
      chunks of ``ops.delta_rule.CHUNK`` tokens;
    - a :class:`GatedRMSNorm` of each head's ``o`` (one scale of
      ``value_dim``, shared by the heads) gated by ``silu(z)``; then the
      output projection.

    ``state_stats`` (a buffer, so it rides the step's state as a routed
    layer's ``held_load`` does and costs no sync): the mean decay
    ``exp(g)``, the mean ``beta`` and the largest Frobenius norm, over
    the heads, of the state after the last token, all of the last
    forward.  No recurrent-state cache: the layer trains and scores."""

    def __init__(self, embed_dim: int, key_heads: int, value_heads: int,
                 key_dim: int, value_dim: int, conv_width: int = 4,
                 eps: float = 1e-6):
        super().__init__()
        from bigdl_tpu.nn.init import RandomUniform
        from bigdl_tpu.nn.layers.linear import Linear

        if value_heads % key_heads:
            raise ValueError(f"{value_heads} value heads over {key_heads} "
                             f"key heads")
        self.embed_dim = embed_dim
        self.key_heads, self.value_heads = key_heads, value_heads
        self.key_dim, self.value_dim = key_dim, value_dim
        self.conv_width = conv_width
        keys, values = key_heads * key_dim, value_heads * value_dim
        self.in_proj_qkvz = Linear(embed_dim, 2 * keys + 2 * values,
                                   with_bias=False)
        self.in_proj_ba = Linear(embed_dim, 2 * value_heads, with_bias=False)
        self.conv_weight = Parameter(RandomUniform().init(
            (2 * keys + values, conv_width), fan_in=conv_width))
        self.A_log = Parameter(jnp.zeros((value_heads,), jnp.float32))
        self.dt_bias = Parameter(jnp.zeros((value_heads,), jnp.float32))
        self.norm = GatedRMSNorm(value_dim, eps)
        self.out_proj = Linear(values, embed_dim, with_bias=False)
        self.register_buffer("state_stats", jnp.zeros((3,), jnp.float32))

    def update_output(self, input):
        from bigdl_tpu.nn.layers.short_conv import causal_depthwise_conv
        from bigdl_tpu.ops.delta_rule import gated_delta_rule

        b, s, _ = input.shape
        hk, hv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        keys, values = hk * dk, hv * dv
        f32 = jnp.float32
        qkvz = self.in_proj_qkvz.forward(input)
        ba = self.in_proj_ba.forward(input).astype(f32)
        mixed, z = qkvz[..., :2 * keys + values], qkvz[..., 2 * keys + values:]
        # the convolution is the short-convolution mixer's too; the SiLU
        # after it is this layer's own
        mixed = jax.nn.silu(causal_depthwise_conv(
            mixed, self.conv_weight)).astype(mixed.dtype)
        q, k, v = jnp.split(mixed, [keys, 2 * keys], axis=-1)

        def unit(x):
            x = x.reshape(b, s, hk, dk).astype(f32)
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

        # [B, heads, S, dim], each key head repeated for its value heads
        q, k = (jnp.repeat(x.astype(input.dtype).transpose(0, 2, 1, 3),
                           hv // hk, axis=1)
                for x in (unit(q) / math.sqrt(dk), unit(k)))
        v = v.reshape(b, s, hv, dv).transpose(0, 2, 1, 3)
        beta = jax.nn.sigmoid(ba[..., :hv]).transpose(0, 2, 1)
        g = (-jnp.exp(self.A_log.astype(f32)) * jax.nn.softplus(
            ba[..., hv:] + self.dt_bias.astype(f32))).transpose(0, 2, 1)
        out, state = gated_delta_rule(q, k, v, g, beta, return_state=True)
        self.state_stats = jax.lax.stop_gradient(jnp.stack([
            jnp.mean(jnp.exp(g)), jnp.mean(beta),
            jnp.max(jnp.sqrt(jnp.sum(state * state, axis=(-2, -1))))]))
        out = self.norm.forward((out.transpose(0, 2, 1, 3),
                                 z.reshape(b, s, hv, dv)))
        return self.out_proj.forward(out.reshape(b, s, values))

    def step_counters(self, buffers, tele, layer: str):
        """``linear_attn/decay_mean``, ``linear_attn/beta_mean`` and
        ``linear_attn/state_norm_max`` of the last step, from this
        layer's buffer as the step left it (the Optimizer calls this
        where it has the loss on the host)."""
        decay, beta, norm = (float(v) for v in np.asarray(
            buffers["state_stats"], np.float64))
        tele.counter("linear_attn/decay_mean", decay, layer=layer)
        tele.counter("linear_attn/beta_mean", beta, layer=layer)
        tele.counter("linear_attn/state_norm_max", norm, layer=layer)

    def __repr__(self):
        return (f"GatedDeltaNet({self.embed_dim}, heads={self.key_heads}/"
                f"{self.value_heads}, dims={self.key_dim}/{self.value_dim})")
