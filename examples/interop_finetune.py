#!/usr/bin/env python
"""Interop chain example — load a Caffe model, fine-tune the head on new
classes, fold BatchNorm for serving, and save both a BTPU checkpoint and
a Caffe round-trip (reference capability chain:
``example/loadmodel/LoadModel.scala`` + ``utils/caffe/CaffePersister``,
SURVEY §2.9/§2.13).

The script is self-contained: it first EMITS a small "pretrained" Caffe
model with the repo's own persister (standing in for a downloaded
caffemodel), then walks the chain a user migrating from the reference
would: import -> freeze trunk -> replace head -> train -> optimize for
serving -> export.

Run: ``python examples/interop_finetune.py``
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def make_pretrained_caffe(tmp):
    """A tiny conv trunk saved as prototxt+caffemodel (the 'pretrained
    zoo model'; classifier heads are dropped at fine-tune time anyway)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.utils.caffe_persister import save_caffe
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(0)
    trunk = nn.Sequential(
        nn.SpatialConvolution(1, 8, 3, 3, 1, 1, 1, 1).set_name("conv1"),
        nn.ReLU(True),
        nn.SpatialMaxPooling(2, 2).set_name("pool1"),
        nn.SpatialConvolution(8, 16, 3, 3, 1, 1, 1, 1).set_name("conv2"),
        nn.ReLU(True),
        nn.SpatialMaxPooling(2, 2).set_name("pool2"),
    )
    proto, caffemodel = os.path.join(tmp, "net.prototxt"), os.path.join(tmp, "net.caffemodel")
    save_caffe(trunk, proto, caffemodel, input_shapes=(1, 1, 16, 16))
    return proto, caffemodel


def main():
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn.fuse import fold_batchnorm
    from bigdl_tpu.utils import serializer
    from bigdl_tpu.utils.caffe import CaffeLoader

    tmp = tempfile.mkdtemp()
    proto, caffemodel = make_pretrained_caffe(tmp)

    # 1. import the pretrained trunk (utils/caffe.py wire-level loader)
    trunk, _ins, _outs = CaffeLoader(proto, caffemodel).load()
    n_params = len(list(trunk.named_parameters()))
    print(f"imported Caffe trunk: {n_params} param tensors")

    # 2. freeze the trunk, graft a fresh conv+BN head for 3 new classes
    trunk.freeze()
    finetune = nn.Sequential(
        trunk,
        nn.SpatialConvolution(16, 32, 1, 1, with_bias=False)
        .set_name("head_conv"),
        nn.SpatialBatchNormalization(32),
        nn.ReLU(True),
        nn.View(32 * 4 * 4),
        nn.Linear(512, 3).set_name("cls"),
        nn.LogSoftMax())

    # 3. fine-tune on a synthetic 3-class task: class-dependent intensity
    rng = np.random.RandomState(0)
    ys = rng.randint(0, 3, 240)
    xs = (rng.randn(240, 1, 16, 16) * 0.5
          + (ys - 1)[:, None, None, None]).astype(np.float32)
    samples = [Sample(x, np.array(y)) for x, y in zip(xs, ys)]
    opt = optim.Optimizer(finetune, samples, nn.ClassNLLCriterion(),
                          batch_size=48,
                          end_trigger=optim.Trigger.max_epoch(30))
    opt.set_optim_method(optim.SGD(learning_rate=0.1, momentum=0.9))
    trained = opt.optimize()
    out = np.asarray(trained.evaluate().forward(jnp.asarray(xs[:64])))
    acc = (out.argmax(1) == ys[:64]).mean()
    print(f"fine-tuned accuracy on train slice: {acc:.2f}")
    assert acc > 0.9, "fine-tune failed to learn the synthetic task"

    # 4. serving-time graph optimization: fold the BN into head_conv
    n_before = len(trained.layers)
    fold_batchnorm(trained)
    print(f"fold_batchnorm: {n_before} -> {len(trained.layers)} layers")
    assert len(trained.layers) == n_before - 1

    # 5. persist: BTPU checkpoint (the native no-code-exec format)
    ckpt = os.path.join(tmp, "finetuned.btpu")
    serializer.save_module(trained, ckpt, overwrite=True)
    reloaded = serializer.load_module(ckpt)
    np.testing.assert_allclose(
        np.asarray(reloaded.evaluate().forward(jnp.asarray(xs[:8]))),
        np.asarray(trained.evaluate().forward(jnp.asarray(xs[:8]))),
        rtol=1e-5, atol=1e-6)
    print(f"BTPU round-trip OK -> {ckpt}")

    # 6. export the folded serving model back to Caffe and reload it —
    # the full CaffePersister round-trip on a model we trained here
    from bigdl_tpu.utils.caffe_persister import save_caffe

    out_proto = os.path.join(tmp, "served.prototxt")
    out_cm = os.path.join(tmp, "served.caffemodel")
    # export the trained+folded head conv (the part Caffe can express)
    serving = nn.Sequential(trained.get(1), nn.ReLU(True))
    save_caffe(serving, out_proto, out_cm, input_shapes=(1, 16, 4, 4))
    back, _, _ = CaffeLoader(out_proto, out_cm).load()
    probe = jnp.asarray(rng.randn(4, 16, 4, 4).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(back.evaluate().forward(probe)),
        np.asarray(serving.evaluate().forward(probe)),
        rtol=1e-4, atol=1e-5)
    print(f"Caffe export round-trip OK -> {out_proto}")


if __name__ == "__main__":
    main()
