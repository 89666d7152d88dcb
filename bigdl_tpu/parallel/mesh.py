"""Device-mesh utilities (the TPU-native replacement for the reference's
Engine node/core topology, ``utils/Engine.scala:313-418``).

Axes convention.  What has run on chips is the ``data`` axis
(``DistriOptimizer`` on a four-chip mesh: the benchmark's
``inception_v1.train.b1024.c4``); the other axes are traced, compiled
and compared with one device on virtual CPU devices only
(``__graft_entry__.dryrun_multichip``, the tier-1 tests), never on a
chip:
- ``data``  — data parallelism (the reference's only axis;
  ``parallel/train_step.py`` batch sharding + ZeRO-1)
- ``model`` — tensor parallelism (``TrainStep.extra_sharding_rules``
  megatron-style weight shardings; see ``__graft_entry__.dryrun_multichip``)
- ``seq``   — sequence/context parallelism for long sequences
  (``parallel/sequence.py`` ring attention / Ulysses all-to-all)
- ``pipe``  — pipeline stages (``parallel/pipeline.py`` GPipe/ppermute
  schedule)
- ``expert``— expert parallelism for MoE layers
  (``nn/layers/moe.py`` ``MixtureOfExperts``, GShard-style dense
  dispatch; ``RoutedExperts``, the layer a chip runs, holds its share
  of the experts and has no exchange yet)
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

__all__ = ["make_mesh", "data_sharding", "replicated", "mesh_process_count",
           "shard_local_batch", "DATA_AXIS", "MODEL_AXIS", "SEQ_AXIS",
           "PIPE_AXIS", "EXPERT_AXIS"]

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"


def make_mesh(shape: Optional[Sequence[int]] = None,
              axis_names: Sequence[str] = (DATA_AXIS,),
              devices=None):
    """Build a ``jax.sharding.Mesh``.  ``shape=None`` puts all devices on
    the first axis."""
    import jax
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else jax.devices()
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axis_names) - 1)
    arr = np.array(devs).reshape(tuple(shape))
    return Mesh(arr, tuple(axis_names))


def data_sharding(mesh, ndim: int, batch_axes: Sequence[str] = (DATA_AXIS,)):
    """NamedSharding that splits the leading axis over the data axes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [None] * ndim
    spec[0] = batch_axes[0] if len(batch_axes) == 1 else tuple(batch_axes)
    return NamedSharding(mesh, P(*spec))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def mesh_process_count(mesh) -> int:
    """Number of host processes the mesh spans (1 = single-host)."""
    if mesh is None:
        return 1
    return len({d.process_index for d in mesh.devices.flat})


def _batch_scale(mesh, batch_axes: Sequence[str]) -> int:
    """global_rows // local_rows for THIS process: how many times larger
    the global batch dim is than the rows this process feeds.

    The batch dim is split K ways (K = prod of the batch axes' mesh
    sizes); this process addresses K_p distinct batch-shard positions, so
    it feeds K_p/K of the global rows.  On a mesh whose batch axes do NOT
    span processes (e.g. multi-host model/seq parallelism with data=1)
    K_p == K and every process feeds the full global batch."""
    import jax

    axes = [mesh.axis_names.index(a) for a in batch_axes]
    k = 1
    for a in batch_axes:
        k *= mesh.shape[a]
    pid = jax.process_index()
    coords = {tuple(idx[i] for i in axes)
              for idx in np.ndindex(mesh.devices.shape)
              if mesh.devices[idx].process_index == pid}
    if k % len(coords) != 0:
        raise ValueError(
            f"batch axes {batch_axes} split {k} ways but this process "
            f"addresses {len(coords)} positions — uneven process layout")
    return k // len(coords)


def _host_or_device(a):
    """``a`` as ``jax.device_put`` may split it: a ``jax.Array`` as it
    is, anything else as a HOST array, never committed whole to one
    device on its way to several."""
    import jax

    return a if isinstance(a, jax.Array) else np.asarray(a)


def shard_local_batch(mesh, local, batch_axes: Sequence[str] = (DATA_AXIS,)):
    """Place one process's shard of the global batch onto the mesh.

    Single-host: ``device_put`` of the (already global) HOST batch with
    its sharding, so each device's rows go from the host straight to
    that device over its own link.  (Committed to device 0 first, 616
    MB took 74 ms to arrive on four chips and held device 0's memory
    meanwhile; straight, 32 ms: PERF.md section 6, PR 29.)
    Multi-host: each process passes its LOCAL rows and the global array is
    assembled with ``jax.make_array_from_process_local_data`` — the
    TPU-native analogue of the reference's one-cached-partition-per-node
    feeding (``dataset/DataSet.scala:164-240``)."""
    import jax

    sharding = data_sharding(mesh, np.ndim(local), batch_axes)
    if mesh_process_count(mesh) == 1:
        return jax.device_put(_host_or_device(local), sharding)
    local = np.asarray(local)
    scale = _batch_scale(mesh, batch_axes)
    global_shape = (local.shape[0] * scale,) + local.shape[1:]
    return jax.make_array_from_process_local_data(sharding, local,
                                                  global_shape)
