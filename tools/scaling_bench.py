#!/usr/bin/env python
"""Scale-out efficiency harness — the pod-scale half of the BASELINE
north star (images/sec at 8/32/128/256 chips; the reference's cluster
protocol is ``models/utils/DistriOptimizerPerf.scala:33-124`` run at
increasing executor counts).

Runs the SAME compiled train step (`parallel/train_step.py`) over data-
parallel meshes of increasing size with a FIXED per-chip batch (weak
scaling, the reference's per-node partition model) and reports images/sec
and efficiency vs linear extrapolation of the smallest mesh.

On real multi-chip hardware this measures ICI allreduce overlap; on this
single-chip dev box run it with the virtual CPU mesh to validate the
protocol end-to-end:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/scaling_bench.py --config lenet_mnist --sizes 1,2,4,8

Prints one JSON line per mesh size plus a summary line.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="inception_v1_imagenet")
    ap.add_argument("--sizes", default="",
                    help="comma list of mesh sizes (default: 1,2,4,..,n_devices)")
    ap.add_argument("--per-chip-batch", type=int, default=0,
                    help="per-chip batch (default: config batch / largest size)")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--zero1", action="store_true",
                    help="use the ZeRO-1 sharded-optimizer layout")
    ap.add_argument("--sync", default=None,
                    choices=["allreduce", "sharded", "fsdp"],
                    help="parameter_sync mode (overrides --zero1; fsdp "
                         "= ZeRO-3 parameter sharding)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    import bench
    import bigdl_tpu.optim as optim
    from bigdl_tpu.parallel.train_step import TrainStep
    from bigdl_tpu.utils.rng import RNG

    from bigdl_tpu.utils.engine import Engine

    Engine.check_singleton(raise_on_conflict=True)
    devices = jax.devices()  # a backend that does not come up raises
    n = len(devices)
    nproc = jax.process_count()
    if args.sizes:
        sizes = [int(s) for s in args.sizes.split(",")]
        too_big = [s for s in sizes if s > n]
        if too_big:
            ap.error(f"requested mesh sizes {too_big} exceed the "
                     f"{n} available devices")
        if any(s % nproc for s in sizes):
            ap.error(f"mesh sizes must be multiples of the "
                     f"{nproc} participating processes")
    else:
        sizes = [s for s in (1, 2, 4, 8, 16, 32, 64, 128, 256)
                 if s <= n and s % nproc == 0]
    build_model, build_batch, criterion, batch = bench._configs()[args.config]
    per_chip = args.per_chip_batch or max(1, batch // max(sizes))

    results = []
    for size in sizes:
        RNG.set_seed(0)
        from bigdl_tpu.nn.fuse import optimize_for_tpu

        model = optimize_for_tpu(build_model())
        mesh = Mesh(np.array(devices[:size]), ("data",))
        step = TrainStep(model, criterion,
                         optim.SGD(learning_rate=0.01, momentum=0.9),
                         mesh=mesh,
                         parameter_sync=args.sync or (
                             "sharded" if args.zero1 else "allreduce"),
                         compute_dtype=jnp.bfloat16)
        # each process builds its LOCAL rows of the global batch
        # (TrainStep._shard_batch's multi-host contract)
        x, y = build_batch(per_chip * size // nproc)
        step.aot_scan(x, y, jax.random.key(0), args.iters)
        losses = step.run_scan(x, y, jax.random.key(1), args.iters)
        if not bool(jnp.isfinite(losses).all()):
            raise FloatingPointError("non-finite loss during warmup")
        drain = bench.make_drain(step)
        drain()
        # h2d stays OUTSIDE the timed window: it scales with global batch
        # and would otherwise bias efficiency_vs_linear downward
        xs, ys = step._shard_batch(x, y)
        t0 = time.perf_counter()
        step.run_scan_sharded(xs, ys, jax.random.key(2))
        drain()
        wall = time.perf_counter() - t0
        rate = per_chip * size * args.iters / wall
        results.append({"chips": size, "global_batch": per_chip * size,
                        "images_per_sec": round(rate, 2),
                        "per_chip_images_per_sec": round(rate / size, 2)})
        print(json.dumps(results[-1]), flush=True)

    base = min(results, key=lambda r: r["chips"])
    summary = {
        "metric": f"{args.config}_scaling_efficiency",
        "config": args.config,
        "per_chip_batch": per_chip,
        "parameter_sync": args.sync or (
            "sharded" if args.zero1 else "allreduce"),
        "efficiency_vs_linear": {
            str(r["chips"]): round(
                r["images_per_sec"] /
                (base["images_per_sec"] * r["chips"] / base["chips"]), 4)
            for r in results},
        "device": devices[0].device_kind,
    }
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
