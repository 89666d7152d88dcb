"""Sequence / context parallelism: ring attention and Ulysses all-to-all.

The reference has NO long-context machinery — sequences are bounded by
single-node memory and iterated locally (SURVEY §5 "Long-context ...
Absent"); this subsystem is the TPU-first design the capability demands.
Two strategies, both SPMD over a ``seq`` mesh axis:

- **Ring attention** (`ring_attention`): q stays put; k/v chunks rotate
  around the ring via ``lax.ppermute`` (XLA lowers to ICI neighbor
  transfers that overlap with the blockwise compute), partial softmax
  states merged with the online-softmax algebra from
  ``bigdl_tpu.ops.attention``.  Memory per chip: O(S_local), supports
  sequences N_devices x longer than one chip holds.  Differentiable for
  free (ppermute's transpose is the reverse permute).

- **Ulysses** (`ulysses_attention`): ``lax.all_to_all`` re-shards
  [seq-sharded, all heads] -> [head-sharded, full seq], runs ordinary
  (flash) attention per local head group, and re-shards back.  Cheaper
  collectives for moderate S; requires heads % n_devices == 0.

Both are meant to be called INSIDE ``shard_map``/pjit with q,k,v already
sharded on the sequence axis; ``make_sequence_parallel_attention`` builds
the shard_map wrapper over a mesh for direct use on global arrays.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.ops.attention import (attention_partial, combine_partials,
                                     flash_attention, _NEG_INF)

__all__ = [
    "ring_attention",
    "ulysses_attention",
    "make_sequence_parallel_attention",
    "SEQ_AXIS",
]

SEQ_AXIS = "seq"


def ring_attention(q, k, v, axis_name: str = SEQ_AXIS,
                   causal: bool = False, scale: Optional[float] = None,
                   axis_size: Optional[int] = None):
    """Ring attention over local shards [B, H, S_local, D].

    Call inside shard_map with q/k/v sharded along seq.  Each of the
    ``n`` steps computes a blockwise partial against the currently-held
    k/v chunk, then rotates k/v to the next ring neighbor.
    """
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    n = axis_size if axis_size is not None else int(lax.psum(1, axis_name))
    idx = lax.axis_index(axis_name)
    s_local = q.shape[2]

    q_pos = idx * s_local + jnp.arange(s_local)
    b, h, sq, _ = q.shape
    state = (jnp.zeros((b, h, sq, d), jnp.float32),
             jnp.full((b, h, sq), _NEG_INF, jnp.float32),
             jnp.zeros((b, h, sq), jnp.float32))

    perm = [(i, (i + 1) % n) for i in range(n)]
    neutral = state
    for step in range(n):
        src = (idx - step) % n
        if causal:
            # branch by chunk position so fully-future chunks cost nothing
            # and fully-past chunks skip the mask: 0 = skip (src > idx),
            # 1 = diagonal triangle (src == idx), 2 = unmasked (src < idx)
            k_pos = src * s_local + jnp.arange(s_local)
            tri_mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
            case = jnp.where(src < idx, 2, jnp.where(src == idx, 1, 0))
            part = lax.switch(case, [
                lambda kv: neutral,
                lambda kv: attention_partial(q, kv[0], kv[1], scale,
                                             mask=tri_mask),
                lambda kv: attention_partial(q, kv[0], kv[1], scale),
            ], (k, v))
        else:
            part = attention_partial(q, k, v, scale)
        state = combine_partials(state, part)
        if step != n - 1:
            k, v = lax.ppermute((k, v), axis_name, perm)
    acc, _, l = state
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = SEQ_AXIS,
                      causal: bool = False, scale: Optional[float] = None,
                      use_flash: bool = False):
    """Ulysses sequence parallelism over local shards [B, H, S_local, D].

    all_to_all to [B, H/n, S_global, D], local full-sequence attention
    (optionally the Pallas flash kernel), all_to_all back.
    """
    # [B, H, S_local, D] -> [B, H/n, S_global, D]
    qg = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    if use_flash:
        out = flash_attention(qg, kg, vg, causal=causal, scale=scale)
    else:
        from bigdl_tpu.ops.attention import dot_product_attention

        out = dot_product_attention(qg, kg, vg, causal=causal, scale=scale)
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def make_sequence_parallel_attention(mesh, strategy: str = "ring",
                                     axis_name: str = SEQ_AXIS,
                                     causal: bool = False,
                                     scale: Optional[float] = None,
                                     use_flash: bool = False,
                                     batch_axis: Optional[str] = None):
    """shard_map-wrap ring/ulysses attention for global [B, H, S, D] arrays
    sharded on ``axis_name`` over ``mesh``.  Pass ``batch_axis`` to
    compose with data parallelism on a 2-D ``(data, seq)`` mesh: the
    batch dim shards over ``batch_axis`` while each data-row runs its own
    k/v ring over ``axis_name`` (ppermute is scoped per axis, so the
    rings never cross data rows)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis_name]
    spec = P(batch_axis, None, axis_name, None)

    if strategy == "ring":
        fn = partial(ring_attention, axis_name=axis_name, causal=causal,
                     scale=scale, axis_size=n)
    elif strategy == "ulysses":
        fn = partial(ulysses_attention, axis_name=axis_name, causal=causal,
                     scale=scale, use_flash=use_flash)
    else:
        raise ValueError(f"unknown strategy {strategy!r}")

    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)
