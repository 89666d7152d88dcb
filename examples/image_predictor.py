#!/usr/bin/env python
"""Image-classification predictor — load a trained model and predict
classes for a folder of images (reference
``example/imageclassification/ImagePredictor.scala:38``: DLClassifierModel
transform over an image DataFrame, printing (imageName, predict) rows).

The image path mirrors the reference's transformer chain
``BytesToBGRImg -> BGRImgCropper -> BGRImgNormalizer`` with the repo's
``BytesToImage -> CenterCropper -> ImageNormalizer``; ``.npy`` feature
files are accepted too so the example runs without PIL.

Run::

    python examples/image_predictor.py -t bigdl --modelPath m.btpu \
        -f images/ --imageSize 224
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# model loading is shared with the ModelValidator example (the reference
# pair shares MlUtils.loadModel the same way)
from examples.model_validator import load_model

# ImageNet eval normalization (``MlUtils.scala`` testMean/testStd)
TEST_MEAN = (0.485 * 255, 0.456 * 255, 0.406 * 255)
TEST_STD = (0.229 * 255, 0.224 * 255, 0.225 * 255)


def load_image_features(folder: str, image_size: int):
    """[(name, CHW float array)] via the crop+normalize chain."""
    from bigdl_tpu.dataset.image import (BytesToImage, CenterCropper,
                                         ImageNormalizer, LabeledImage)

    crop = CenterCropper(image_size, image_size)
    norm = ImageNormalizer(TEST_MEAN, TEST_STD)
    decode = BytesToImage()
    rows = []
    for name in sorted(os.listdir(folder)):
        path = os.path.join(folder, name)
        if not os.path.isfile(path):
            continue
        if name.endswith(".npy"):
            rows.append((name, np.load(path).astype(np.float32)))
            continue
        with open(path, "rb") as f:
            img = next(decode.apply(iter([(f.read(), 0)])))
        img = next(norm.apply(crop.apply(iter([img]))))
        rows.append((name, img.data.transpose(2, 0, 1)))  # HWC -> CHW
    if not rows:
        raise SystemExit(f"no image files under {folder}")
    return rows


def predict(model, rows, image_size: int, batch_size: int = 32):
    """(imageName, predict) pairs through DLClassifierModel.transform."""
    from bigdl_tpu.pipeline import DLClassifierModel

    trans = DLClassifierModel(model, (3, image_size, image_size)) \
        .set_batch_size(batch_size)
    feats = np.stack([r[1] for r in rows])
    classes = trans.transform(feats)
    return [(name, int(c)) for (name, _), c in zip(rows, classes)]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-f", "--folder", required=True)
    p.add_argument("-t", "--modelType", default="bigdl",
                   choices=["bigdl", "caffe", "torch", "tf"])
    p.add_argument("--modelPath", required=True)
    p.add_argument("--caffeDefPath", default=None)
    p.add_argument("--tfInput", default="input")
    p.add_argument("--tfOutput", default=None)
    p.add_argument("-b", "--batchSize", type=int, default=32)
    p.add_argument("--imageSize", type=int, default=224)
    p.add_argument("--showNum", type=int, default=100)
    args = p.parse_args(argv)

    model = load_model(args.modelType, args.modelPath, args.caffeDefPath,
                       args.tfInput, args.tfOutput)
    rows = load_image_features(args.folder, args.imageSize)
    results = predict(model, rows, args.imageSize, args.batchSize)
    for name, cls in results[:args.showNum]:
        print(f"{name} predict={cls}")
    return results


if __name__ == "__main__":
    main()
