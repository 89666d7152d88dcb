#!/usr/bin/env python
"""Time-to-accuracy harness — the third leg of the BASELINE protocol
(images/sec, scaling efficiency, **time-to-accuracy**; BASELINE.md
"report ... plus time-to-accuracy for the five configs").

Trains a model-zoo config through the real Optimizer loop (validation
every epoch, ``Trigger.max_score`` early stop) and reports wall-clock
seconds and epochs to the target validation Top-1.  Real dataset folders
are used when given; otherwise the loaders synthesize class-dependent
data so the protocol runs anywhere (synthetic targets are reached in a
couple of epochs — the point offline is the protocol, the point on
hardware is the number).

    python tools/tta_bench.py --model lenet --target 0.95 [-f mnist/]
    python tools/tta_bench.py --model vgg_cifar --target 0.9 -b 128

Prints ONE JSON line: {"metric": "<model>_time_to_acc", ...}.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="lenet")
    ap.add_argument("-f", "--folder", default=None)
    ap.add_argument("-b", "--batch-size", type=int, default=64)
    ap.add_argument("--target", type=float, default=0.95,
                    help="validation Top-1 accuracy to stop at")
    ap.add_argument("--max-epoch", type=int, default=20)
    ap.add_argument("--learning-rate", type=float, default=0.05)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--num-classes", type=int, default=0)
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.models.cli import _build_model, _load_data
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(args.seed)
    x, y = _load_data(args.model, args.folder, "train", args.num_classes)
    xt, yt = _load_data(args.model, args.folder, "test", args.num_classes)
    if args.folder is None:
        # synthetic loaders draw disjoint class patterns per split; hold
        # validation out of the train split so accuracy is meaningful
        cut = max(len(x) // 4, 1)
        xt, yt = x[:cut], y[:cut]
        x, y = x[cut:], y[cut:]
    model = _build_model(args.model, args.num_classes)

    samples = [Sample(x[i], y[i]) for i in range(len(x))]
    val_samples = [Sample(xt[i], yt[i]) for i in range(len(xt))]

    o = optim.LocalOptimizer(
        model, samples, nn.ClassNLLCriterion(), batch_size=args.batch_size,
        end_trigger=optim.Trigger.or_(
            optim.Trigger.max_epoch(args.max_epoch),
            optim.Trigger.max_score(args.target)))
    o.set_optim_method(optim.SGD(learning_rate=args.learning_rate,
                                 momentum=args.momentum))
    o.set_validation(optim.Trigger.every_epoch(), val_samples,
                     [optim.Top1Accuracy()], args.batch_size)
    t0 = time.perf_counter()
    o.optimize()
    wall = time.perf_counter() - t0

    score = float(o.state.get("score", 0.0))
    result = {
        "metric": f"{args.model}_time_to_acc",
        "value": round(wall, 2),
        "unit": f"seconds to Top-1 >= {args.target}",
        "reached": bool(score >= args.target),
        "final_top1": round(score, 4),
        "epochs": int(o.state.get("epoch", 0)),
        "iterations": int(o.state.get("neval", 0)),
        "records": len(samples),
        "synthetic_data": args.folder is None,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
