#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile a cell's real
train step, at its real size, for a DESCRIBED ``v5e:2x2`` with the TPU
compiler installed here, and read ``memory_analysis()`` against one
chip's 16 GB.  No chip, no run, no time: a compile that passes is not a
chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_for_chip.py <cell> [...]

The program builds its mesh from ``jax.devices()`` and places its own
parameters, which a described device cannot hold; so this script takes
the program's pure step function (``TrainStep._step_fn``) and hands it
the described devices and shapes itself.  The process's backend is the
CPU, so the kernel dispatch takes the XLA legs: for the one-chip cells
this is the step WITHOUT its Pallas kernels (those are compiled for the
chip by ``tests/test_chip_compile.py``); for the four-chip cell, where
every op takes its XLA leg on the chip too, it is the real program.
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def compile_cell(name: str):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import models, optimizers, run
    from bigdl_tpu.parallel.train_step import TrainStep

    cell = run.load_cell(name)
    w, conf = cell["workload"], cell["config"]
    family, recipe = models.load(conf), optimizers.load(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:w["chips"]]), ("data",))
    step = TrainStep(family.build(conf), family.criterion(),
                     recipe.build(conf),
                     compute_dtype=jnp.dtype(conf["compute_dtype"]))
    if w["chips"] > 1:
        step.mesh = mesh  # read by _step_fn for its sharding constraint
        step.parameter_sync = w["parameter_sync"]
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def shaped(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    key = jax.eval_shape(lambda: jax.random.key(0))
    x, y = models.make_records(family, 0, 1, conf)  # one record's shapes

    def batch_of(records):
        return jax.ShapeDtypeStruct((w["batch"], *records.shape[1:]),
                                    records.dtype, sharding=rows)

    args = (shaped(step.params, rep), shaped(step.opt_state, rep),
            shaped(step.buffers, rep), batch_of(x), batch_of(y),
            jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep))
    compiled = jax.jit(step._step_fn(), donate_argnums=(0, 1, 2)).lower(
        *args).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{name}: compiled for {w['chips']} described v5e chip(s); "
          f"per chip: arguments {mem.argument_size_in_bytes / 2**30:.2f} "
          f"GiB, temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB, "
          f"total {per_chip / 2**30:.2f} GiB of 16; collectives: "
          + ", ".join(f"{c} x{text.count(c + '(') + text.count(c + '-start(')}"
                      for c in ("all-reduce", "all-gather",
                                "reduce-scatter")))
    if per_chip > 16e9:
        raise SystemExit(f"{name} does not fit one chip")


if __name__ == "__main__":
    import jax

    # a compile for a described chip is written to the cache and cannot
    # be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    for cell_name in sys.argv[1:]:
        compile_cell(cell_name)
