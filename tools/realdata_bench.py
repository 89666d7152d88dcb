#!/usr/bin/env python
"""Real-data input-pipeline benchmark — trains from TFRecord FILES through
the full host pipeline (record framing + Example proto decode + crop/
normalize batch assembly) with the Optimizer's async prefetch, and reports
whether input ever stalls the device (Metrics ``data time``).

This is the proof the framework's input path keeps a chip fed the way the
reference's SequenceFile + MTLabeledBGRImgToBatch pipeline feeds ImageNet
(``dataset/DataSet.scala:319`` SeqFileFolder,
``dataset/image/MTLabeledBGRImgToBatch.scala:31``); the benchmark's
cells feed ready float32 ``Sample``s, so this tool is their real-data
complement until ROADMAP R5 gives it a cell.

    # ImageNet shapes on the TPU (writes ~0.6 GB of records first):
    python tools/realdata_bench.py --config inception --iters 16

    # CPU smoke (tiny shapes):
    JAX_PLATFORMS=cpu python tools/realdata_bench.py --config tiny

Prints per-iteration throughput lines and ONE final JSON line with the
data-wait share.
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def write_dataset(path, n, h, w, classes, seed=0):
    """TFRecord files of raw uint8 HWC images + labels (the reference's
    SequenceFile-of-JPEG role, without a JPEG codec dependency)."""
    from bigdl_tpu.dataset.tfrecord import write_tfrecord
    from bigdl_tpu.utils.protowire import emit_bytes, emit_varint

    def feature_bytes(b):
        #  Feature{bytes_list: BytesList{value: b}}
        inner = emit_bytes(1, b)
        return emit_bytes(1, inner)

    def feature_int(v):
        inner = emit_varint(1, v)
        return emit_bytes(3, inner)

    def example(img, label):
        feats = b""
        for key, val in (("image", feature_bytes(img.tobytes())),
                         ("label", feature_int(int(label)))):
            entry = emit_bytes(1, key.encode()) + emit_bytes(2, val)
            feats += emit_bytes(1, entry)
        return emit_bytes(1, feats)

    rng = np.random.default_rng(seed)
    files = []
    per_file = max(n // 4, 1)
    base = rng.integers(0, 255, (classes, h, w, 3), np.uint8)
    idx = 0
    for f in range(4):
        recs = []
        for _ in range(per_file):
            label = idx % classes
            noise = rng.integers(-25, 25, (h, w, 3))
            img = np.clip(base[label].astype(np.int16) + noise,
                          0, 255).astype(np.uint8)
            recs.append(example(img, label))
            idx += 1
        fp = os.path.join(path, f"train-{f:05d}.tfrecord")
        write_tfrecord(fp, recs)
        files.append(fp)
    return files


def make_dataset(files, h, w, crop, batch, mean, std):
    """TFRecordIterator -> parse_example -> LabeledImage -> MTImageToBatch
    -> MiniBatch: the full host chain the Optimizer consumes."""
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.image import LabeledImage, MTImageToBatch
    from bigdl_tpu.dataset.minibatch import MiniBatch
    from bigdl_tpu.dataset.tfrecord import TFRecordIterator
    from bigdl_tpu.dataset.transformer import Transformer

    class DecodeExamples(Transformer):
        """Chunked batch decode through the native (C++ multithreaded)
        Example parser; Python wire walker as fallback.  Chunks of one
        minibatch keep the prefetcher's stream smooth instead of
        stalling a whole file's decode at file boundaries."""

        def apply(self, it):
            from bigdl_tpu import native

            def chunks():
                buf = []
                for path in it:
                    for rec in TFRecordIterator(path):
                        buf.append(rec)
                        if len(buf) == batch:
                            yield buf
                            buf = []
                if buf:
                    yield buf

            for recs in chunks():
                imgs, labels = native.parse_examples_fixed(
                    recs, [("image", "bytes", h * w * 3),
                           ("label", "int64", 1)])
                for i in range(len(recs)):
                    yield LabeledImage(imgs[i].reshape(h, w, 3),
                                       int(labels[i, 0]))

    class ToMiniBatch(Transformer):
        def apply(self, it):
            for feats, labels in it:
                yield MiniBatch(feats, labels)

    return DataSet.array(files) \
        .transform(DecodeExamples()) \
        .transform(MTImageToBatch(batch, crop, crop, mean, std)) \
        .transform(ToMiniBatch())


CONFIGS = {
    # name: (image hw, crop, batch, records, model builder)
    "inception": (256, 224, 64, 1024, "inception"),
    "tiny": (36, 32, 32, 256, "tiny"),
}


def build_model(kind, crop):
    import bigdl_tpu.nn as nn

    if kind == "inception":
        from bigdl_tpu import models
        from bigdl_tpu.nn.fuse import optimize_for_tpu

        return optimize_for_tpu(models.build_inception_v1(1000))
    return nn.Sequential(
        nn.SpatialConvolution(3, 16, 3, 3, 2, 2, 1, 1), nn.ReLU(True),
        nn.SpatialMaxPooling(2, 2, 2, 2),
        nn.Reshape((16 * (crop // 4) * (crop // 4),)),
        nn.Linear(16 * (crop // 4) * (crop // 4), 10), nn.LogSoftMax())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="tiny", choices=sorted(CONFIGS))
    ap.add_argument("--iters", type=int, default=12)
    ap.add_argument("--data-dir", default=None,
                    help="reuse/keep the TFRecord files here")
    args = ap.parse_args()

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.utils.rng import RNG

    hw, crop, batch, records, kind = CONFIGS[args.config]
    data_dir = args.data_dir or tempfile.mkdtemp(prefix="bigdl_realdata_")
    os.makedirs(data_dir, exist_ok=True)
    if not any(f.endswith(".tfrecord") for f in os.listdir(data_dir)):
        t0 = time.perf_counter()
        write_dataset(data_dir, records, hw, hw, classes=10)
        print(f"# wrote {records} records ({hw}x{hw}) to {data_dir} "
              f"in {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    files = sorted(os.path.join(data_dir, f) for f in os.listdir(data_dir)
                   if f.endswith(".tfrecord"))

    mean, std = (123.68, 116.78, 103.94), (58.4, 57.1, 57.4)
    ds = make_dataset(files, hw, hw, crop, batch, mean, std)
    RNG.set_seed(1)
    o = optim.LocalOptimizer(build_model(kind, crop), ds,
                             nn.ClassNLLCriterion(), batch_size=batch,
                             end_trigger=optim.Trigger.max_iteration(args.iters))
    o.set_optim_method(optim.SGD(learning_rate=0.01))
    t0 = time.perf_counter()
    o.optimize()
    wall = time.perf_counter() - t0

    m = o.metrics
    # exclude the compile iteration from the steady-state accounting
    steady_iters = max(m.count("computing time"), 1)
    data_wait = m.total("data time") - (m._scalars["data time"][0]
                                        if m.count("data time") else 0.0)
    compute = m.total("computing time")
    result = {
        "metric": f"realdata_{args.config}_img_s",
        "value": round(batch * steady_iters /
                       max(compute + max(data_wait, 0.0), 1e-9), 1),
        "unit": "img/s (steady-state)",
        "data_wait_mean_s": round(data_wait / steady_iters, 6),
        "data_wait_share": round(max(data_wait, 0.0) /
                                 max(compute + max(data_wait, 0.0), 1e-9), 4),
        "prefetch": int(os.environ.get("BIGDL_PREFETCH", "2") or 2),
        "iters": args.iters,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
