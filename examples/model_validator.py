#!/usr/bin/env python
"""ModelValidator — the multi-format interop acceptance harness
(reference ``example/loadmodel/ModelValidator.scala:44``): load a model
saved as BigDL-TPU (BTPU), Caffe, Torch7 ``.t7``, or TensorFlow GraphDef
and report Top-1 / Top-5 accuracy over a validation folder.

The reference drives ImageNet through per-model preprocessors; here the
validation set is either

- a ``.npz`` file with arrays ``x`` (N, ...) and ``y`` (N,), or
- a folder of class subdirectories holding ``.npy`` feature arrays or
  images (decoded via PIL when installed), with an optional ``--meanFile``
  ``.npy`` subtracted from each record.

Run::

    python examples/model_validator.py -t bigdl  --modelPath m.btpu -f val/
    python examples/model_validator.py -t caffe  --modelPath m.caffemodel \
        --caffeDefPath m.prototxt -f val/
    python examples/model_validator.py -t torch  --modelPath m.t7 -f val/
    python examples/model_validator.py -t tf     --modelPath m.pb \
        --tfInput input --tfOutput logsoftmax_5 -f val.npz
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def load_model(model_type: str, model_path: str, caffe_def_path=None,
               tf_input="input", tf_output=None):
    """Dispatch on the four supported serialization formats
    (``ModelValidator.scala:105-131`` TorchModel/CaffeModel/BigDlModel)."""
    t = model_type.lower()
    if t == "bigdl":
        from bigdl_tpu.utils.serializer import load_module

        return load_module(model_path)
    if t == "caffe":
        from bigdl_tpu.utils.caffe import load_caffe

        if not caffe_def_path:
            raise SystemExit("caffe models need --caffeDefPath")
        return load_caffe(caffe_def_path, model_path)
    if t == "torch":
        from bigdl_tpu.utils.torch_file import load_torch

        return load_torch(model_path)
    if t == "tf":
        from bigdl_tpu.utils.tf_graph import load_graphdef

        if not tf_output:
            raise SystemExit("tf models need --tfOutput")
        return load_graphdef(model_path, [tf_input], [tf_output])
    raise SystemExit(f"unknown model type {model_type!r}; "
                     "use bigdl, caffe, torch, or tf")


def load_validation_samples(folder: str, mean_file=None):
    """(x, label) Samples from an ``.npz`` file or a class-subdir tree."""
    from bigdl_tpu.dataset.image import BytesToImage
    from bigdl_tpu.dataset.sample import Sample

    mean = np.load(mean_file) if mean_file else None

    def feat(arr):
        arr = np.asarray(arr, np.float32)
        return arr - mean if mean is not None else arr

    if os.path.isfile(folder):
        data = np.load(folder)
        return [Sample(feat(x), np.int64(y))
                for x, y in zip(data["x"], data["y"])]

    classes = sorted(d for d in os.listdir(folder)
                     if os.path.isdir(os.path.join(folder, d)))
    if not classes:
        raise SystemExit(f"no class subdirectories under {folder}")
    samples = []
    decode = BytesToImage()
    for label, cls in enumerate(classes):
        cdir = os.path.join(folder, cls)
        for name in sorted(os.listdir(cdir)):
            path = os.path.join(cdir, name)
            if name.endswith(".npy"):
                arr = np.load(path)
            else:
                with open(path, "rb") as f:
                    img = next(decode.apply(iter([(f.read(), label)])))
                arr = img.data.transpose(2, 0, 1)  # HWC -> CHW
            samples.append(Sample(feat(arr), np.int64(label)))
    return samples


def validate(model, samples, batch_size: int = 32):
    """Evaluate Top-1/Top-5 like the reference's ``model.evaluate`` call
    (``ModelValidator.scala:133-139``)."""
    import bigdl_tpu.optim as optim

    methods = [optim.Top1Accuracy(), optim.Top5Accuracy()]
    results = optim.Evaluator(model, batch_size=batch_size).evaluate(
        samples, methods)
    return {m.name: r.result()[0] for r, m in results}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-f", "--folder", default="./",
                   help="validation folder (class subdirs) or .npz file")
    p.add_argument("-t", "--modelType", required=True,
                   choices=["bigdl", "caffe", "torch", "tf"])
    p.add_argument("--modelPath", required=True)
    p.add_argument("--caffeDefPath", default=None)
    p.add_argument("--tfInput", default="input")
    p.add_argument("--tfOutput", default=None)
    p.add_argument("-b", "--batchSize", type=int, default=32)
    p.add_argument("--meanFile", default=None)
    p.add_argument("--quantize", action="store_true",
                   help="evaluate the int8-quantized model (bigquant)")
    args = p.parse_args(argv)

    model = load_model(args.modelType, args.modelPath, args.caffeDefPath,
                       args.tfInput, args.tfOutput)
    if args.quantize:
        from bigdl_tpu.nn.quantized import quantize

        model = quantize(model)
    samples = load_validation_samples(args.folder, args.meanFile)
    scores = validate(model, samples, args.batchSize)
    for name, value in scores.items():
        print(f"{args.modelType} {args.modelPath} {name}: {value:.4f}")
    return scores


if __name__ == "__main__":
    main()
