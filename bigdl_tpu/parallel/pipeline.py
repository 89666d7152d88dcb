"""Pipeline parallelism over the ``pipe`` mesh axis.

The reference has no pipeline parallelism (synchronous data parallelism
only, SURVEY §2.7) — this is new TPU-first capability, like the ``seq``
machinery in ``parallel/sequence.py``.  The design is the SPMD/GPipe
collective-permute schedule (the standard TPU formulation — all chips run
the SAME program; no per-stage programs or send/recv graphs):

- the model is a stack of S structurally-identical blocks whose
  parameters carry a leading stage dimension sharded over ``pipe``;
- the global batch splits into M microbatches; the schedule runs
  ``M + S - 1`` ticks of ``lax.scan``.  Each tick every stage applies its
  block to its in-flight microbatch, then activations rotate one stage
  forward via ``lax.ppermute`` (ICI neighbor transfer, overlapped by XLA
  with the next tick's compute);
- stage 0 injects microbatch ``t`` at tick ``t``; the last stage emits
  microbatch ``t - (S-1)``; a bubble of ``S-1`` ticks is the usual GPipe
  cost, amortized by M;
- the whole schedule is differentiable (``ppermute``'s transpose is the
  reverse rotation), so ``jax.grad`` of the pipelined loss IS pipelined
  backprop — no hand-written backward schedule.

Heterogeneous stage stacks are out of scope by design: scan-over-stacked
blocks is the XLA-idiomatic form (one compiled block body), and a stack
of identical blocks is what pipeline parallelism is used for in practice
(transformer/MLP blocks).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.parallel.mesh import PIPE_AXIS

__all__ = ["pipeline_apply", "make_pipeline_fn", "PIPE_AXIS"]


def _stage_apply(block_fn, stage_params, h):
    """Apply the LOCAL stage's block (stage_params has a leading 1 dim
    inside shard_map)."""
    local = jax.tree.map(lambda a: a[0], stage_params)
    return block_fn(local, h)


def pipeline_apply(block_fn: Callable, stage_params, x_microbatches,
                   axis_name: str = PIPE_AXIS):
    """Run the pipelined stack INSIDE shard_map.

    ``block_fn(params, h) -> h``: one stage's computation.
    ``stage_params``: this stage's parameter shard, leading dim 1.
    ``x_microbatches``: [M, mb, ...] microbatches, replicated.
    Returns [M, mb, ...] outputs (valid on the LAST stage; other stages
    hold zeros — combine with ``lax.psum`` or mask outside if needed).
    """
    s = int(lax.psum(1, axis_name))
    stage = lax.axis_index(axis_name)
    m = x_microbatches.shape[0]
    ticks = m + s - 1

    h0 = jnp.zeros_like(x_microbatches[0])
    out0 = jnp.zeros((m,) + x_microbatches.shape[1:],
                     x_microbatches.dtype)

    def tick(carry, t):
        h, outs = carry
        # stage 0 swallows microbatch t (clamped; masked later)
        inject = x_microbatches[jnp.minimum(t, m - 1)]
        h = jnp.where(stage == 0, inject, h)
        h = _stage_apply(block_fn, stage_params, h)
        # the last stage emits microbatch t-(s-1) once the fill ends
        emit_idx = t - (s - 1)
        valid = (stage == s - 1) & (emit_idx >= 0)
        outs = lax.cond(
            valid,
            lambda o: lax.dynamic_update_index_in_dim(
                o, h, jnp.maximum(emit_idx, 0), 0),
            lambda o: o, outs)
        # rotate activations one stage forward (ring; stage0's incoming
        # value is overwritten by the next inject)
        h = lax.ppermute(h, axis_name,
                         [(i, (i + 1) % s) for i in range(s)])
        return (h, outs), None

    (_, outs), _ = lax.scan(tick, (h0, out0), jnp.arange(ticks))
    return outs


def make_pipeline_fn(block_fn: Callable, mesh, n_microbatches: int,
                     axis_name: str = PIPE_AXIS):
    """Build ``fn(stacked_params, x) -> y`` running the S-stage stack
    pipelined over ``mesh``'s ``axis_name``.

    ``stacked_params``: pytree with leading stage dim S (sharded over the
    pipe axis by the returned fn's shard_map specs).
    ``x``: the [B, ...] global batch; B must divide by n_microbatches.
    Returns the [B, ...] outputs, replicated (psum of the last stage's
    emissions).
    """
    from jax.sharding import PartitionSpec as P

    s = mesh.shape[axis_name]

    def fn(stacked_params, x):
        b = x.shape[0]
        if b % n_microbatches:
            raise ValueError(
                f"batch {b} must divide into {n_microbatches} microbatches")
        x_mb = x.reshape((n_microbatches, b // n_microbatches) + x.shape[1:])

        p_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)

        @partial(jax.shard_map, mesh=mesh, in_specs=(p_specs, P()),
                 out_specs=P(), check_vma=False)
        def run(params, xmb):
            outs = pipeline_apply(block_fn, params, xmb, axis_name)
            # only the last stage holds real outputs; psum replicates
            stage = lax.axis_index(axis_name)
            outs = jnp.where(stage == s - 1, outs, jnp.zeros_like(outs))
            return lax.psum(outs, axis_name)

        y_mb = run(stacked_params, x_mb)
        return y_mb.reshape((b,) + y_mb.shape[2:])

    return fn
