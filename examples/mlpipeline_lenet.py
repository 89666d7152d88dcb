#!/usr/bin/env python
"""ML-pipeline LeNet example — train LeNet-5 on MNIST through the
DLClassifier estimator/transformer contract (reference
``example/MLPipeline/DLClassifierLeNet.scala:40``: a DLClassifier fit
over a DataFrame of (feature, label) rows, then transform over the
validation split).

The sklearn-style analogue: ``DLClassifier.fit(X, y)`` over normalized
MNIST pixels, ``transform(X_val)`` for predictions.  Real IDX files are
used when ``--folder`` has them; otherwise the loader synthesizes data so
the example always runs.

Run: ``python examples/mlpipeline_lenet.py [--folder mnist/] [-b 64]``
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def prepare(split: str, folder=None, limit=2048):
    """IDX files -> normalized float rows, like the reference's
    BytesToGreyImg -> GreyImgNormalizer chain."""
    from bigdl_tpu.dataset.datasets import (TRAIN_MEAN, TRAIN_STD,
                                            load_mnist)

    x, y = load_mnist(folder, split=split, synthetic_size=limit)
    x = (x.reshape(len(x), -1).astype(np.float32) - TRAIN_MEAN) / TRAIN_STD
    return x[:limit], y[:limit]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-f", "--folder", default=None,
                   help="MNIST IDX folder (synthetic data when absent)")
    p.add_argument("-b", "--batchSize", type=int, default=64)
    p.add_argument("-e", "--maxEpoch", type=int, default=4)
    p.add_argument("--limit", type=int, default=2048,
                   help="cap on rows (keeps the example fast)")
    args = p.parse_args(argv)

    # the reference example's first two lines: log redirection on
    from bigdl_tpu.utils.logging import redirect_thirdparty_logs

    redirect_thirdparty_logs()

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.models.lenet import build_lenet5
    from bigdl_tpu.pipeline import DLClassifier
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(1)
    if args.folder:
        x_train, y_train = prepare("train", args.folder, args.limit)
        x_val, y_val = prepare("test", args.folder, args.limit)
    else:
        # synthetic fallback draws disjoint class patterns per split, so
        # hold validation out of the train split instead
        x, y = prepare("train", None, args.limit)
        cut = max(len(x) // 4, 1)
        x_train, y_train = x[cut:], y[cut:]
        x_val, y_val = x[:cut], y[:cut]

    estimator = DLClassifier(build_lenet5(10), nn.ClassNLLCriterion(),
                             feature_size=(28, 28)) \
        .set_batch_size(args.batchSize) \
        .set_max_epoch(args.maxEpoch) \
        .set_optim_method(optim.Adam(learning_rate=1e-3))
    transformer = estimator.fit(x_train, y_train)

    pred = transformer.transform(x_val)
    acc = float((pred == y_val).mean())
    for i in range(min(10, len(pred))):  # transformed.show() analogue
        print(f"label={y_val[i]} predict={pred[i]}")
    print(f"validation accuracy: {acc:.4f} over {len(y_val)} rows")
    return acc


if __name__ == "__main__":
    main()
