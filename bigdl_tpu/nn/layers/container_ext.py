"""Composite containers beyond Sequential (SURVEY §2.4: Concat,
ConcatTable, ParallelTable, MapTable, TimeDistributed, and the
table-routing helpers).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from bigdl_tpu.nn.module import Container, Module

__all__ = [
    "Concat", "ConcatTable", "ParallelTable", "MapTable", "TimeDistributed",
    "Remat",
]


class Concat(Container):
    """Apply every member to the same input, concatenate outputs along
    ``dim`` (``nn/Concat.scala``; reference dim 1 of [batch, ...] — here an
    explicit 0-based axis, default 1)."""

    def __init__(self, dim: int = 1):
        super().__init__()
        self.dim = dim

    def update_output(self, input):
        return jnp.concatenate([m.forward(input) for m in self.layers], axis=self.dim)


class ConcatTable(Container):
    """Apply every member to the same input, output a table
    (``nn/ConcatTable.scala``)."""

    def update_output(self, input):
        return [m.forward(input) for m in self.layers]


class ParallelTable(Container):
    """Member i applied to input[i] (``nn/ParallelTable.scala``)."""

    def update_output(self, input):
        return [m.forward(x) for m, x in zip(self.layers, input)]


class MapTable(Container):
    """One module applied to every table element (``nn/MapTable.scala``).
    The reference clones the module per element with shared weights; under
    the functional core the SAME module instance is simply reused — weight
    sharing is the default."""

    def __init__(self, module: Optional[Module] = None):
        super().__init__()
        if module is not None:
            self.add(module)

    def update_output(self, input):
        m = self.layers[0]
        return [m.forward(x) for x in input]


class Remat(Container):
    """Gradient checkpointing / rematerialization boundary: activations
    inside the wrapped module are NOT saved for the backward pass —
    ``jax.checkpoint`` recomputes them during the gradient, trading
    recompute FLOPs for HBM (the standard TPU memory lever; no reference
    analogue — BigDL materializes every layer's output by design).

    Wrap repeated blocks of a deep model::

        nn.Sequential(*[nn.Remat(block()) for _ in range(depth)])

    Exact: forward values and gradients are bit-identical to the
    unwrapped module (dropout keys derive from the same fold_in chain on
    recompute), only the memory/compute schedule changes.
    """

    def __init__(self, module: Module, policy=None):
        super().__init__()
        self.add(module)
        self._policy = policy

    def update_output(self, input):
        import jax

        from bigdl_tpu.nn.module import load_state_dict, state_dict

        inner = self.layers[0]
        names = list(state_dict(inner, kind="buffer"))

        def run(v):
            # buffers a layer advances inside (running statistics, a
            # routed layer's load) leave the checkpoint as outputs: kept
            # on the module they would be tracers of the inner trace
            out = inner.forward(v)
            after = state_dict(inner, kind="buffer")
            return out, [after[n] for n in names]

        out, buffers = jax.checkpoint(run, policy=self._policy)(input)
        load_state_dict(inner, dict(zip(names, buffers)), strict=False)
        return out


class TimeDistributed(Container):
    """Apply the inner module to every timestep of [batch, time, ...]
    (``nn/TimeDistributed.scala``) by folding time into the batch — one big
    MXU-friendly batched op instead of a per-step loop."""

    def __init__(self, module: Module):
        super().__init__()
        self.add(module)

    def update_output(self, input):
        b, t = input.shape[0], input.shape[1]
        flat = input.reshape((b * t,) + input.shape[2:])
        out = self.layers[0].forward(flat)
        return out.reshape((b, t) + out.shape[1:])
