"""State-space layers: a mixer whose memory of the sequence is a
fixed-size state a head, decayed by a scalar that depends on the input.

:class:`Mamba2Mixer` is the Mamba-2 block (Dao & Gu 2024) as current
hybrid decoders build it, several such layers to one softmax layer.  The
recurrence itself is ``ops.ssd.ssd`` (a chunked scan); this layer is
everything around it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import Module, Parameter

__all__ = ["Mamba2Mixer"]

#: the ``jax.named_scope`` around the mixer between its two projections:
#: an event of a device trace does not carry it, the compiled step's
#: ``op_name`` metadata does
SCOPE = "mamba2"


class Mamba2Mixer(Module):
    """Mamba-2 selective state-space mixer over [batch, seq, embed], no
    bias but the convolution's.  With ``u`` the input, ``H`` heads of
    ``head_dim`` (``H head_dim`` inner channels), ``groups`` groups of
    ``H / groups`` heads and a state of ``state`` numbers a channel:

    - ``[z, xBC, dt] = split(u W_in)`` (``H head_dim``, ``H head_dim + 2
      groups state`` and ``H`` columns, in that order);
    - ``xBC <- silu(conv(xBC) + b)``: a causal depthwise convolution of
      ``taps`` taps along the sequence, every channel on its own (the
      last tap is the position itself), a bias a channel;
    - ``[x, B, C] = split(xBC)`` (``H head_dim``, ``groups state``,
      ``groups state``): heads ``(H / groups) g .. (H / groups)(g + 1) -
      1`` read group ``g``'s ``B`` and ``C``;
    - ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head, in
      float32 (no clamp on ``dt``);
    - ``y = ssd(x, dt, A, B, C, D)`` from a zero state, in chunks of
      ``ops.ssd.CHUNK`` tokens: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
      B_t^T``, ``y_t = S_t C_t + D x_t``;
    - a :class:`GatedRMSNorm` with the gate FIRST, ``w * norm(y *
      silu(z))``, normed over each group's ``H head_dim / groups``
      channels on its own; then the output projection.

    A share of the heads (a tensor-parallel rank: ``H`` and ``groups``
    divided alike) is the same layer at those counts; its output is that
    rank's partial sum.

    ``ssm_stats`` (a buffer, so it rides the step's state as a routed
    layer's ``held_load`` does and costs no sync): the mean decay a token
    ``exp(dt A)``, the mean ``dt`` and the largest Frobenius norm, over
    the heads, of the state after the last token, all of the last
    forward.  No state cache: the layer trains and scores."""

    def __init__(self, embed_dim: int, heads: int, head_dim: int,
                 groups: int, state: int, taps: int = 4, eps: float = 1e-5):
        super().__init__()
        from bigdl_tpu.nn.init import RandomUniform
        from bigdl_tpu.nn.layers.linear import Linear
        from bigdl_tpu.nn.layers.linear_attention import GatedRMSNorm

        if heads % groups:
            raise ValueError(f"{heads} heads over {groups} groups")
        self.embed_dim, self.heads, self.head_dim = embed_dim, heads, head_dim
        self.groups, self.state, self.taps = groups, state, taps
        inner, conv = heads * head_dim, heads * head_dim + 2 * groups * state
        self.conv_weight = Parameter(RandomUniform().init(
            (conv, taps), fan_in=taps))
        self.conv_bias = Parameter(jnp.zeros((conv,), jnp.float32))
        self.A_log = Parameter(jnp.zeros((heads,), jnp.float32))
        self.D = Parameter(jnp.ones((heads,), jnp.float32))
        self.dt_bias = Parameter(jnp.zeros((heads,), jnp.float32))
        self.in_proj = Linear(embed_dim, inner + conv + heads,
                              with_bias=False)
        self.norm = GatedRMSNorm(inner, eps, gate_first=True,
                                 group_size=inner // groups)
        self.out_proj = Linear(inner, embed_dim, with_bias=False)
        self.register_buffer("ssm_stats", jnp.zeros((3,), jnp.float32))

    def update_output(self, input):
        from bigdl_tpu.nn.layers.short_conv import causal_depthwise_conv
        from bigdl_tpu.ops.ssd import ssd

        b, s, _ = input.shape
        h, p, g, n = self.heads, self.head_dim, self.groups, self.state
        inner, f32 = h * p, jnp.float32
        projected = self.in_proj.forward(input)
        with jax.named_scope(SCOPE):
            z, xbc, dt = jnp.split(
                projected, [inner, 2 * inner + 2 * g * n], axis=-1)
            # the convolution is the short-convolution mixer's too; its
            # bias and the SiLU after it are this layer's own
            xbc = jax.nn.silu(causal_depthwise_conv(xbc, self.conv_weight)
                              + self.conv_bias.astype(f32)).astype(xbc.dtype)
            x, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
            dt = jax.nn.softplus(dt.astype(f32) + self.dt_bias.astype(f32))
            a = -jnp.exp(self.A_log.astype(f32))
            y, last = ssd(x.reshape(b, s, h, p), dt, a,
                          bm.reshape(b, s, g, n), cm.reshape(b, s, g, n),
                          self.D, return_state=True)
            self.ssm_stats = jax.lax.stop_gradient(jnp.stack([
                jnp.mean(jnp.exp(dt * a)), jnp.mean(dt),
                jnp.max(jnp.sqrt(jnp.sum(last * last, axis=(-2, -1))))]))
            y = self.norm.forward((y.reshape(b, s, inner), z))
        return self.out_proj.forward(y)

    def step_counters(self, buffers, tele, layer: str):
        """``ssm/decay_mean``, ``ssm/dt_mean`` and ``ssm/state_norm_max``
        of the last step, from this layer's buffer as the step left it
        (the Optimizer calls this where it has the loss on the host)."""
        decay, dt, norm = (float(v) for v in np.asarray(
            buffers["ssm_stats"], np.float64))
        tele.counter("ssm/decay_mean", decay, layer=layer)
        tele.counter("ssm/dt_mean", dt, layer=layer)
        tele.counter("ssm/state_norm_max", norm, layer=layer)

    def __repr__(self):
        return (f"Mamba2Mixer({self.embed_dim}, heads={self.heads}x"
                f"{self.head_dim}, groups={self.groups}, state={self.state})")
