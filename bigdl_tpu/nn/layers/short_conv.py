"""Short-convolution layers: a mixer that sees the last few positions
through one small filter a channel and nothing further back.

:func:`causal_depthwise_conv` is the convolution itself, shared with
:class:`~bigdl_tpu.nn.layers.linear_attention.GatedDeltaNet` (which
applies a SiLU after it); :class:`GatedShortConv` is the double-gated
mixer current hybrid decoders put in three layers of four, one
grouped-query layer to every three of these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import Module, Parameter

__all__ = ["GatedShortConv", "causal_depthwise_conv"]

#: the ``jax.named_scope`` around the mixer's gates and convolution: an
#: event of a device trace does not carry it, the compiled step's
#: ``op_name`` metadata does
SCOPE = "gated_short_conv"


def causal_depthwise_conv(x, weight):
    """x [B, S, C], weight [C, taps]: ``y_t = sum_i weight[:, i] x_{t -
    (taps - 1) + i}``, every channel on its own, positions before the
    first read as zero (the last tap is the position itself:
    cross-correlation order, no bias, no activation).  Computed and
    returned in float32; a sequence never reads another row of the
    batch."""
    taps, s = weight.shape[1], x.shape[1]
    w = weight.astype(jnp.float32)
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s].astype(jnp.float32) * w[:, i]
               for i in range(taps))


class GatedShortConv(Module):
    """Double-gated short convolution over [batch, seq, embed], no bias
    and no activation anywhere:

    - ``[B, C, u] = split(x W_in, 3)`` (``embed`` columns each, in that
      order);
    - ``c = causal_depthwise_conv(B * u)``: ``taps`` taps a channel;
    - ``out = (C * c) W_out``.

    The input gate ``B`` and the output gate ``C`` are plain products.
    ``conv_stats`` (a buffer, so it rides the step's state as a routed
    layer's ``held_load`` does and costs no sync): the root mean square
    of ``B * u`` and of the layer's output, of the last forward.  No
    convolution-state cache: the layer trains and scores."""

    def __init__(self, embed_dim: int, taps: int = 3):
        super().__init__()
        from bigdl_tpu.nn.init import RandomUniform
        from bigdl_tpu.nn.layers.linear import Linear

        self.embed_dim, self.taps = embed_dim, taps
        self.in_proj = Linear(embed_dim, 3 * embed_dim, with_bias=False)
        self.conv_weight = Parameter(RandomUniform().init(
            (embed_dim, taps), fan_in=taps))
        self.out_proj = Linear(embed_dim, embed_dim, with_bias=False)
        self.register_buffer("conv_stats", jnp.zeros((2,), jnp.float32))

    def update_output(self, input):
        from bigdl_tpu.ops.dispatch import note

        b, s, d = input.shape
        note("gated_short_conv", "xla", "only-leg", taps=self.taps,
             channels=d, tokens=b * s)
        projected = self.in_proj.forward(input)
        with jax.named_scope(SCOPE):
            gate_in, gate_out, u = jnp.split(projected, 3, axis=-1)
            gated = gate_in * u
            mixed = gate_out * causal_depthwise_conv(
                gated, self.conv_weight).astype(input.dtype)
        out = self.out_proj.forward(mixed)

        def rms(x):
            return jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32))))

        self.conv_stats = jax.lax.stop_gradient(
            jnp.stack([rms(gated), rms(out)]))
        return out

    def step_counters(self, buffers, tele, layer: str):
        """``short_conv/gate_in_rms`` and ``short_conv/out_rms`` of the
        last step, from this layer's buffer as the step left it (the
        Optimizer calls this where it has the loss on the host)."""
        gate_in, out = (float(v) for v in np.asarray(
            buffers["conv_stats"], np.float64))
        tele.counter("short_conv/gate_in_rms", gate_in, layer=layer)
        tele.counter("short_conv/out_rms", out, layer=layer)

    def __repr__(self):
        return f"GatedShortConv({self.embed_dim}, taps={self.taps})"
