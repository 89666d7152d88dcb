"""Composite containers beyond Sequential (SURVEY §2.4: Concat,
ConcatTable, ParallelTable, MapTable, TimeDistributed, and the
table-routing helpers).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import telemetry
from bigdl_tpu.nn.module import Container, Module
from bigdl_tpu.ops.attention import FLASH_LSE, FLASH_OUT

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


_save_named = jax.checkpoint_policies.save_only_these_names(FLASH_OUT,
                                                             FLASH_LSE)


def _keep_named(prim, *avals, **params):
    """What :class:`Remat` keeps when it is given no policy: the values
    a kernel names, each said on a ``remat/keep`` instant as the
    backward pass's trace decides it."""
    keep = _save_named(prim, *avals, **params)
    if keep:
        (aval,) = avals
        telemetry.instant(
            "remat/keep", kept=params["name"], shape=list(aval.shape),
            dtype=str(aval.dtype),
            bytes=aval.size * aval.dtype.itemsize)
    return keep


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

    One class of value is kept all the same: what a kernel inside the
    module names for it, today the flash forward kernel's output and
    logsumexp (``ops.attention.FLASH_OUT``, ``FLASH_LSE``), whose
    recomputation is quadratic in the sequence and whose bytes are
    linear in it: ``batch x heads x seq x (head_dim x itemsize + 4)`` an
    attention layer.  A module that names nothing keeps nothing and
    lowers as it always did.  Each kept value is said on a trace-time
    ``remat/keep`` instant (``kept``, ``shape``, ``dtype``, ``bytes``).
    ``policy``: a policy of ``jax.checkpoint_policies`` in place of
    that rule (``nothing_saveable`` recomputes the kernel too).
    """

    def __init__(self, module: Module, policy=None):
        super().__init__()
        self.add(module)
        self._policy = policy

    def update_output(self, input):
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

        policy = _keep_named if self._policy is None else self._policy
        streams = getattr(inner, "streams", 1)
        if streams > 1:
            # the block's input is kept whatever the policy, and here it
            # is ``streams`` times a one-stream block's
            telemetry.instant(
                "remat/keep", kept="residual_streams", streams=streams,
                shape=list(input.shape), dtype=str(input.dtype),
                bytes=input.size * input.dtype.itemsize)
        out, buffers = jax.checkpoint(run, policy=policy)(input)
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
