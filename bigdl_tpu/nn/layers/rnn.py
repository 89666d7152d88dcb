"""Recurrent layers (SURVEY §2.4-§2.5: Cell, Recurrent, RecurrentDecoder,
BiRecurrent, RnnCell, LSTM, LSTMPeephole, GRU, ConvLSTMPeephole,
ConvLSTMPeephole3D).

TPU-first redesign of the reference's time loop: ``Recurrent`` lowers to
``jax.lax.scan`` (one compiled step body, no per-timestep Python), and each
cell's input projection (the reference's ``preTopology`` hoisting,
``nn/Cell.scala:46`` / ``nn/Recurrent.scala:121+``) is applied to the whole
[batch*time] block as a single large MXU matmul before the scan.

Layout: [batch, time, ...] like the reference.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.init import RandomUniform
from bigdl_tpu.nn.layers.conv import SpatialConvolution, VolumetricConvolution
from bigdl_tpu.nn.layers.linear import Linear
from bigdl_tpu.nn.module import Container, Module, Parameter

__all__ = [
    "Cell", "RnnCell", "LSTM", "LSTMPeephole", "GRU",
    "ConvLSTMPeephole", "ConvLSTMPeephole3D",
    "Recurrent", "RecurrentDecoder", "BiRecurrent",
]


class Cell(Container):
    """RNN cell contract (``nn/Cell.scala:46``): ``initial_state`` sizes the
    carry, ``pre_topology`` is hoisted out of the time loop, ``step``
    advances one timestep."""

    hidden_size: int

    def initial_state(self, batch_size: int, dtype=jnp.float32):
        raise NotImplementedError

    def pre_topology(self) -> Optional[Module]:
        return None

    def step(self, x_t, state):
        """(pre-projected x_t, state) -> (output_t, new_state)."""
        raise NotImplementedError

    def update_output(self, input):
        """Single-step eager use: input = (x_t, state)."""
        x_t, state = input
        return self.step(x_t, state)


class RnnCell(Cell):
    """Elman RNN cell (``nn/RNN.scala``): h' = act(W_ih x + b_ih + W_hh h + b_hh)."""

    def __init__(self, input_size: int, hidden_size: int, activation: Optional[Module] = None,
                 isInputWithBias: bool = True, w_regularizer=None, u_regularizer=None,
                 b_regularizer=None):
        super().__init__()
        from bigdl_tpu.nn.layers.activation import Tanh

        self.input_size, self.hidden_size = input_size, hidden_size
        self.activation = activation if activation is not None else Tanh()
        self.i2h = Linear(input_size, hidden_size, with_bias=isInputWithBias,
                          w_regularizer=w_regularizer, b_regularizer=b_regularizer)
        self.h2h = Linear(hidden_size, hidden_size, w_regularizer=u_regularizer)

    def initial_state(self, batch_size, dtype=jnp.float32):
        return jnp.zeros((batch_size, self.hidden_size), dtype)

    def pre_topology(self):
        return self.i2h

    def step(self, x_t, state):
        h = self.activation.forward(x_t + self.h2h.forward(state))
        return h, h


def _make_dropouts(cell: Cell, p: float):
    """Gate-input dropout parity with the reference cells (``nn/LSTM.scala``
    applies Dropout(p) on the x and h projections).  The x-side mask is drawn
    per timestep (applied in the hoisted pre-projection over [B*T]); the
    h-side mask is drawn once per sequence inside the scan body — i.e.
    variational dropout, the deterministic-under-scan choice."""
    if p > 0:
        from bigdl_tpu.nn.layers.normalization import Dropout

        cell.dropout_x = Dropout(p)
        cell.dropout_h = Dropout(p)


def _pre_with_dropout(cell: Cell, proj: Module) -> Module:
    if cell.p > 0:
        from bigdl_tpu.nn.module import Sequential

        return Sequential(cell.dropout_x, proj)
    return proj


def _drop_h(cell: Cell, h):
    return cell.dropout_h.forward(h) if cell.p > 0 else h


class LSTM(Cell):
    """Standard LSTM (``nn/LSTM.scala``).  Gate order (i, f, g, o) packed in
    one 4*hidden projection so the scan body is two matmuls."""

    def __init__(self, input_size: int, hidden_size: int, p: float = 0.0,
                 w_regularizer=None, u_regularizer=None, b_regularizer=None):
        super().__init__()
        self.input_size, self.hidden_size = input_size, hidden_size
        self.p = p
        self.i2g = Linear(input_size, 4 * hidden_size,
                          w_regularizer=w_regularizer, b_regularizer=b_regularizer)
        self.h2g = Linear(hidden_size, 4 * hidden_size, with_bias=False,
                          w_regularizer=u_regularizer)
        _make_dropouts(self, p)

    def initial_state(self, batch_size, dtype=jnp.float32):
        z = jnp.zeros((batch_size, self.hidden_size), dtype)
        return (z, z)

    def pre_topology(self):
        return _pre_with_dropout(self, self.i2g)

    def step(self, x_t, state):
        h, c = state
        gates = x_t + self.h2g.forward(_drop_h(self, h))
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        return h_new, (h_new, c_new)


class LSTMPeephole(Cell):
    """LSTM with peephole connections from the cell state to the gates
    (``nn/LSTMPeephole.scala``)."""

    def __init__(self, input_size: int, hidden_size: int, p: float = 0.0,
                 w_regularizer=None, u_regularizer=None, b_regularizer=None):
        super().__init__()
        self.input_size, self.hidden_size = input_size, hidden_size
        self.p = p
        self.i2g = Linear(input_size, 4 * hidden_size,
                          w_regularizer=w_regularizer, b_regularizer=b_regularizer)
        self.h2g = Linear(hidden_size, 4 * hidden_size, with_bias=False,
                          w_regularizer=u_regularizer)
        self.peep_i = Parameter(jnp.zeros((hidden_size,), jnp.float32))
        self.peep_f = Parameter(jnp.zeros((hidden_size,), jnp.float32))
        self.peep_o = Parameter(jnp.zeros((hidden_size,), jnp.float32))
        _make_dropouts(self, p)

    def initial_state(self, batch_size, dtype=jnp.float32):
        z = jnp.zeros((batch_size, self.hidden_size), dtype)
        return (z, z)

    def pre_topology(self):
        return _pre_with_dropout(self, self.i2g)

    def step(self, x_t, state):
        h, c = state
        gates = x_t + self.h2g.forward(_drop_h(self, h))
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i = jax.nn.sigmoid(i + self.peep_i * c)
        f = jax.nn.sigmoid(f + self.peep_f * c)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        o = jax.nn.sigmoid(o + self.peep_o * c_new)
        h_new = o * jnp.tanh(c_new)
        return h_new, (h_new, c_new)


class GRU(Cell):
    """GRU (``nn/GRU.scala``): r/z from packed projections, candidate uses
    the reset-gated hidden state."""

    def __init__(self, input_size: int, hidden_size: int, p: float = 0.0,
                 w_regularizer=None, u_regularizer=None, b_regularizer=None):
        super().__init__()
        self.input_size, self.hidden_size = input_size, hidden_size
        self.p = p
        self.i2g = Linear(input_size, 3 * hidden_size,
                          w_regularizer=w_regularizer, b_regularizer=b_regularizer)
        self.h2rz = Linear(hidden_size, 2 * hidden_size, with_bias=False,
                           w_regularizer=u_regularizer)
        self.h2n = Linear(hidden_size, hidden_size, with_bias=False,
                          w_regularizer=u_regularizer)
        _make_dropouts(self, p)

    def initial_state(self, batch_size, dtype=jnp.float32):
        return jnp.zeros((batch_size, self.hidden_size), dtype)

    def pre_topology(self):
        return _pre_with_dropout(self, self.i2g)

    def step(self, x_t, state):
        x_r, x_z, x_n = jnp.split(x_t, 3, axis=-1)
        h_in = _drop_h(self, state)
        h_r, h_z = jnp.split(self.h2rz.forward(h_in), 2, axis=-1)
        r = jax.nn.sigmoid(x_r + h_r)
        z = jax.nn.sigmoid(x_z + h_z)
        n = jnp.tanh(x_n + r * self.h2n.forward(h_in))
        h_new = (1.0 - z) * n + z * state
        return h_new, h_new


class ConvLSTMPeephole(Cell):
    """Convolutional LSTM over [batch, time, C, H, W]
    (``nn/ConvLSTMPeephole.scala``); gates are SAME-padded convolutions."""

    def __init__(self, input_size: int, output_size: int, kernel_i: int, kernel_c: int,
                 stride: int = 1, with_peephole: bool = True,
                 w_regularizer=None, u_regularizer=None, b_regularizer=None):
        super().__init__()
        self.input_size, self.hidden_size = input_size, output_size
        self.output_size = output_size
        self.with_peephole = with_peephole
        self.i2g = SpatialConvolution(input_size, 4 * output_size, kernel_i, kernel_i,
                                      stride, stride, -1, -1,
                                      w_regularizer=w_regularizer, b_regularizer=b_regularizer)
        self.h2g = SpatialConvolution(output_size, 4 * output_size, kernel_c, kernel_c,
                                      1, 1, -1, -1, with_bias=False,
                                      w_regularizer=u_regularizer)
        if with_peephole:
            self.peep_i = Parameter(jnp.zeros((output_size, 1, 1), jnp.float32))
            self.peep_f = Parameter(jnp.zeros((output_size, 1, 1), jnp.float32))
            self.peep_o = Parameter(jnp.zeros((output_size, 1, 1), jnp.float32))
        self._spatial = None  # set lazily from input

    def initial_state(self, batch_size, dtype=jnp.float32, spatial=None):
        if spatial is None:
            spatial = self._spatial
        h, w = spatial
        z = jnp.zeros((batch_size, self.output_size, h, w), dtype)
        return (z, z)

    def pre_topology(self):
        return self.i2g

    def step(self, x_t, state):
        h, c = state
        gates = x_t + self.h2g.forward(h)
        i, f, g, o = jnp.split(gates, 4, axis=1)
        if self.with_peephole:
            i = jax.nn.sigmoid(i + self.peep_i * c)
            f = jax.nn.sigmoid(f + self.peep_f * c)
        else:
            i, f = jax.nn.sigmoid(i), jax.nn.sigmoid(f)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        if self.with_peephole:
            o = jax.nn.sigmoid(o + self.peep_o * c_new)
        else:
            o = jax.nn.sigmoid(o)
        h_new = o * jnp.tanh(c_new)
        return h_new, (h_new, c_new)


class ConvLSTMPeephole3D(ConvLSTMPeephole):
    """3-D ConvLSTM over [batch, time, C, T, H, W]
    (``nn/ConvLSTMPeephole3D.scala``)."""

    def __init__(self, input_size: int, output_size: int, kernel_i: int, kernel_c: int,
                 stride: int = 1, with_peephole: bool = True,
                 w_regularizer=None, u_regularizer=None, b_regularizer=None):
        Cell.__init__(self)
        self.input_size, self.hidden_size = input_size, output_size
        self.output_size = output_size
        self.with_peephole = with_peephole
        pad = (kernel_i - 1) // 2
        pad_c = (kernel_c - 1) // 2
        self.i2g = VolumetricConvolution(input_size, 4 * output_size,
                                         kernel_i, kernel_i, kernel_i, stride, stride, stride,
                                         pad, pad, pad,
                                         w_regularizer=w_regularizer, b_regularizer=b_regularizer)
        self.h2g = VolumetricConvolution(output_size, 4 * output_size,
                                         kernel_c, kernel_c, kernel_c, 1, 1, 1,
                                         pad_c, pad_c, pad_c, with_bias=False,
                                         w_regularizer=u_regularizer)
        if with_peephole:
            self.peep_i = Parameter(jnp.zeros((output_size, 1, 1, 1), jnp.float32))
            self.peep_f = Parameter(jnp.zeros((output_size, 1, 1, 1), jnp.float32))
            self.peep_o = Parameter(jnp.zeros((output_size, 1, 1, 1), jnp.float32))
        self._spatial = None

    def initial_state(self, batch_size, dtype=jnp.float32, spatial=None):
        if spatial is None:
            spatial = self._spatial
        t, h, w = spatial
        z = jnp.zeros((batch_size, self.output_size, t, h, w), dtype)
        return (z, z)


class Recurrent(Container):
    """Time-loop container over [batch, time, ...] (``nn/Recurrent.scala:36``):
    hoists the cell's pre-projection over all timesteps, then ``lax.scan``s
    the step body."""

    def __init__(self, cell: Optional[Cell] = None):
        super().__init__()
        if cell is not None:
            self.add(cell)
        self._last_state = None
        self._init_state_override = None
        self._remat_cell = False
        self._trace_attrs = ("_last_state",)

    def remat_cell(self):
        """Recompute the cell body in the backward pass instead of
        saving its intermediates.  The round-5 TPU profile of the large
        LSTM config put ~21% of the step in residual stacking (the
        [T, B, 4H] gate pre-activation buffer's init broadcast +
        dynamic-update-slice writes); rematerialization trades that HBM
        traffic for one extra fused-gate matmul per step in the
        backward.  Opt-in — measure per shape (BASELINE.md round 5
        has the verdict for the large LSTM config)."""
        self._remat_cell = True
        return self

    @property
    def cell(self) -> Cell:
        return self.layers[0]

    def get_hidden_state(self):
        return self._last_state

    def set_hidden_state(self, state):
        self._init_state_override = state
        return self

    def _pre_apply(self, input):
        pre = self.cell.pre_topology()
        if pre is None:
            return input
        b, t = input.shape[0], input.shape[1]
        flat = input.reshape((b * t,) + input.shape[2:])
        out = pre.forward(flat)
        return out.reshape((b, t) + out.shape[1:])

    def _initial_state(self, pre_x):
        """Size the carry from the PRE-PROJECTED input so strided ConvLSTM
        gate convolutions see matching spatial dims."""
        if self._init_state_override is not None:
            return self._init_state_override
        cell = self.cell
        if isinstance(cell, ConvLSTMPeephole):
            cell._spatial = pre_x.shape[3:]
        return cell.initial_state(pre_x.shape[0], pre_x.dtype)

    def update_output(self, input):
        cell = self.cell
        x = self._pre_apply(input)
        state0 = self._initial_state(x)
        xs = jnp.moveaxis(x, 1, 0)  # [T, B, ...]

        def body(state, x_t):
            out_t, new_state = cell.step(x_t, state)
            return new_state, out_t

        if self._remat_cell:
            body = jax.checkpoint(body)
        final_state, outs = lax.scan(body, state0, xs)
        self._last_state = final_state
        return jnp.moveaxis(outs, 0, 1)


class RecurrentDecoder(Recurrent):
    """Decoder loop feeding the output back as the next input for
    ``output_length`` steps (``nn/RecurrentDecoder.scala``).  Input is the
    first-step input [batch, ...]."""

    def __init__(self, output_length: int, cell: Optional[Cell] = None):
        super().__init__(cell)
        self.output_length = output_length

    def update_output(self, input):
        cell = self.cell
        if isinstance(cell, ConvLSTMPeephole):
            cell._spatial = input.shape[2:]
        state0 = self._init_state_override if self._init_state_override is not None \
            else cell.initial_state(input.shape[0], input.dtype)
        pre = cell.pre_topology()

        def body(carry, _):
            x, state = carry
            x_proj = pre.forward(x) if pre is not None else x
            out_t, new_state = cell.step(x_proj, state)
            return (out_t, new_state), out_t

        (_, final_state), outs = lax.scan(
            body, (input, state0), None, length=self.output_length)
        self._last_state = final_state
        return jnp.moveaxis(outs, 0, 1)


class BiRecurrent(Container):
    """Bidirectional wrapper (``nn/BiRecurrent.scala``): forward pass +
    time-reversed pass, merged (default JoinTable on the feature dim)."""

    def __init__(self, merge: Optional[Module] = None, cell: Optional[Cell] = None):
        super().__init__()
        if cell is not None:
            self.fwd = Recurrent(cell)
            self.bwd = Recurrent(cell.clone_module())
        self.merge = merge

    def with_cell(self, cell: Cell) -> "BiRecurrent":
        self.fwd = Recurrent(cell)
        self.bwd = Recurrent(cell.clone_module())
        return self

    def update_output(self, input):
        out_f = self.fwd.forward(input)
        out_b = jnp.flip(self.bwd.forward(jnp.flip(input, 1)), 1)
        if self.merge is not None:
            return self.merge.forward([out_f, out_b])
        return jnp.concatenate([out_f, out_b], axis=-1)
