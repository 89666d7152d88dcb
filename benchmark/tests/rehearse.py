#!/usr/bin/env python3
"""Rehearsal of ``run.py`` without the chip: the rest of a run after the
look for an accelerator, on the CPU at a tiny size.  Prints counts and
the comparison only: a time taken here means nothing, and none is
printed under a device metric's name.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse.py tiny_inception.c1 [--trace]
    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse.py tiny_lm.c1 tiny_lm_adam.c1
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 benchmark/tests/rehearse.py tiny_inception.c4
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def tiny_cell(name: str):
    """A cell dict as ``run.load_cell`` makes it, from ``tests/data``,
    reporting every metric of ``BENCHMARK.json``."""
    from benchmark import run

    workload = run.read_json(HERE, "data", name + ".workload.json")
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    # a configuration of the benchmark at the workload's tiny batch, cut
    # as the workload says (the published layers with 10 classes); or one
    # that only the tests have, from a file beside the workload
    if "config_file" in workload:
        config = run.read_json(HERE, "data", workload["config_file"])
    else:
        config = run.read_json(run.HERE, "configs",
                               workload["config"] + ".json")
    config.update(workload.get("config_overrides", {}))
    return {"name": name, "chips": workload["chips"], "workload": workload,
            "config": config,
            "end_to_end": bench["end_to_end"],
            # the CPU is in no table of peaks, and rightly an error there
            "per_layer": [m for m in bench["per_layer"] if m["name"]
                          not in ("step.mfu", "kernel.pallas_roofline")]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 12345)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", action="store_true")
    args = ap.parse_args()
    import jax

    from benchmark import run

    ok = True
    for name in args.cells:
        cell = tiny_cell(name)
        out = run.run_cell(cell, args.seed, args.seconds, args.trace,
                           jax.devices()[:cell["chips"]])
        print(json.dumps({"cell": name, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "metric_names": sorted(out["metrics"]),
                          "device": out["device"]["platform"],
                          "compared": out["compared"]}))
        ok = ok and out["correct"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
