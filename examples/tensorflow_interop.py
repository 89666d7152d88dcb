#!/usr/bin/env python
"""TensorFlow interop example — both directions of the reference's
``example/tensorflow`` pair (``Load.scala``: run a TF-exported GraphDef
as a BigDL model; ``Save.scala``: export a BigDL model so TensorFlow
can read it).

Round trip shown here: build a small classifier, export it to a binary
GraphDef (``save_graphdef``), re-import it (``load_graphdef``), and
verify the imported graph computes identical outputs — then keep
training the IMPORTED graph (Consts were promoted to Variables).

Run: ``python examples/tensorflow_interop.py [--modelPath out.pb]``
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--modelPath", default=None,
                   help="where to write the GraphDef (tempfile default)")
    args = p.parse_args(argv)

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.utils.rng import RNG
    from bigdl_tpu.utils.tf_graph import load_graphdef, save_graphdef

    RNG.set_seed(9)
    model = nn.Sequential(
        nn.Linear(6, 16), nn.ReLU(), nn.Linear(16, 3), nn.LogSoftMax(),
    ).evaluate()
    path = args.modelPath or os.path.join(
        tempfile.mkdtemp(prefix="bigdl_tf_"), "model.pb")

    # Save.scala direction: BigDL module tree -> binary GraphDef
    outputs = save_graphdef(model, path, input_name="input")
    print(f"saved GraphDef to {path} (outputs: {outputs})")

    # Load.scala direction: GraphDef -> trainable Graph (train_consts
    # promotes the exported Const weights to Variables)
    imported = load_graphdef(path, ["input"], outputs,
                             train_consts=True).evaluate()
    x = np.random.RandomState(0).randn(8, 6).astype(np.float32)
    a, b = np.asarray(model.forward(x)), np.asarray(imported.forward(x))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    print("imported graph matches the original forward (max "
          f"|diff| = {np.abs(a - b).max():.2e})")

    # the imported graph is TRAINABLE (Const weights became Variables)
    rng = np.random.RandomState(1)
    xs = rng.randn(96, 6).astype(np.float32)
    ys = np.argmax(xs[:, :3], axis=1)
    samples = [Sample(xs[i], np.int64(ys[i])) for i in range(96)]
    o = optim.LocalOptimizer(imported.training_mode(), samples,
                             nn.ClassNLLCriterion(), batch_size=16,
                             end_trigger=optim.Trigger.max_epoch(25))
    o.set_optim_method(optim.SGD(learning_rate=0.5, momentum=0.9))
    o.optimize()
    pred = np.asarray(imported.evaluate().forward(xs)).argmax(1)
    acc = float((pred == ys).mean())
    print(f"fine-tuned imported graph accuracy: {acc:.3f}")
    return acc


if __name__ == "__main__":
    main()
