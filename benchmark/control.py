#!/usr/bin/env python3
"""The control of "How ``correct`` is decided", on the chip at a cell's
own size: the plain reference put in the program's place and computed in
the nearest precision below the one the configuration states (int8 for
bfloat16), against the reference proper, on the same weights and rows.
Prints, for every seed, the numbers ``correct`` compares; the smallest
of them over the seeds is the upper end each limit is set under.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13

The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import compare, models, optimizers, reference, run  # noqa: E402


def control_numbers(cell, seed: int, quant: str = "int8"):
    """The compared numbers of the lower-precision reference against the
    reference proper, at the cell's batch and check steps."""
    w, conf = cell["workload"], cell["config"]
    family, recipe = models.load(conf), optimizers.load(conf)
    n = w["check_steps"] * w["batch"]
    x, y = models.make_records(family, seed, n, conf)
    batches = [(x[i:i + w["batch"]], y[i:i + w["batch"]])
               for i in range(0, n, w["batch"])]
    weights = reference.host_weights(family.param_specs(conf), seed,
                                     conf["init_gain"])
    want = reference.follow(family, weights, batches, recipe, conf)
    got = reference.follow(family, weights, batches, recipe, conf,
                           quant=quant)
    return compare.numbers(got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.setup_compile_cache()
    run.find_devices(1)
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = control_numbers(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "int8", "numbers": nums,
                          "would_pass": compare.judge(
                              nums, cell["workload"]["limits"])}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
