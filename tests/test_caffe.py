"""Caffe import: prototxt text-format parsing, binary caffemodel blob
decoding, DAG building, and a numeric oracle comparison against torch."""

import struct
import tempfile

import numpy as np
import pytest

from bigdl_tpu.utils.caffe import (CaffeLoader, load_caffe,
                                   load_caffemodel_blobs, parse_prototxt)

PROTOTXT = """
name: "testnet"  # a comment
input: "data"
input_dim: 1
input_dim: 3
input_dim: 8
input_dim: 8
layer {
  name: "conv1"
  type: "Convolution"
  bottom: "data"
  top: "conv1"
  convolution_param { num_output: 4 kernel_size: 3 pad: 1 stride: 1 }
}
layer {
  name: "relu1"
  type: "ReLU"
  bottom: "conv1"
  top: "conv1"
}
layer {
  name: "pool1"
  type: "Pooling"
  bottom: "conv1"
  top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 }
}
layer {
  name: "ip1"
  type: "InnerProduct"
  bottom: "pool1"
  top: "ip1"
  inner_product_param { num_output: 5 }
}
layer {
  name: "prob"
  type: "Softmax"
  bottom: "ip1"
  top: "prob"
}
"""


def _varint(n):
    out = b""
    while True:
        b7 = n & 0x7F
        n >>= 7
        out += bytes([b7 | (0x80 if n else 0)])
        if not n:
            return out


def _ld(field, payload):
    return _varint((field << 3) | 2) + _varint(len(payload)) + payload


def _blob(arr):
    arr = np.asarray(arr, np.float32)
    shape_msg = b"".join(_varint((1 << 3) | 0) + _varint(d)
                         for d in arr.shape)
    data = struct.pack(f"<{arr.size}f", *arr.reshape(-1))
    return _ld(7, shape_msg) + _ld(5, data)


def _layer_v2(name, blobs):
    body = _ld(1, name.encode())
    for b in blobs:
        body += _ld(7, _blob(b))
    return _ld(100, body)


def _make_caffemodel(path, weights):
    buf = b"".join(_layer_v2(n, bs) for n, bs in weights.items())
    with open(path, "wb") as f:
        f.write(buf)


@pytest.fixture
def caffe_files():
    rng = np.random.RandomState(0)
    w = {
        "conv1": [rng.randn(4, 3, 3, 3).astype(np.float32),
                  rng.randn(4).astype(np.float32)],
        "ip1": [rng.randn(5, 4 * 4 * 4).astype(np.float32),
                rng.randn(5).astype(np.float32)],
    }
    proto = tempfile.mktemp(suffix=".prototxt")
    model = tempfile.mktemp(suffix=".caffemodel")
    with open(proto, "w") as f:
        f.write(PROTOTXT)
    _make_caffemodel(model, w)
    return proto, model, w


def test_parse_prototxt():
    net = parse_prototxt(PROTOTXT)
    assert net["name"] == "testnet"
    assert net["input"] == "data"
    assert net["input_dim"] == [1, 3, 8, 8]
    layers = net["layer"]
    assert [l["type"] for l in layers] == \
        ["Convolution", "ReLU", "Pooling", "InnerProduct", "Softmax"]
    assert layers[0]["convolution_param"]["num_output"] == 4
    assert layers[2]["pooling_param"]["pool"] == "MAX"


def test_caffemodel_blob_roundtrip(caffe_files):
    _, model, w = caffe_files
    blobs = load_caffemodel_blobs(model)
    assert set(blobs) == {"conv1", "ip1"}
    np.testing.assert_allclose(blobs["conv1"][0], w["conv1"][0])
    np.testing.assert_allclose(blobs["ip1"][1], w["ip1"][1])


def test_load_caffe_oracle_vs_torch(caffe_files):
    torch = pytest.importorskip("torch")
    import torch.nn as tnn

    proto, model_path, w = caffe_files
    model = load_caffe(proto, model_path).evaluate()
    x = np.random.RandomState(1).randn(1, 3, 8, 8).astype(np.float32)
    got = np.asarray(model.forward(x))

    ref = tnn.Sequential(
        tnn.Conv2d(3, 4, 3, padding=1), tnn.ReLU(), tnn.MaxPool2d(2, 2),
        tnn.Flatten(), tnn.Linear(4 * 4 * 4, 5), tnn.Softmax(dim=-1))
    with torch.no_grad():
        ref[0].weight.copy_(torch.from_numpy(w["conv1"][0]))
        ref[0].bias.copy_(torch.from_numpy(w["conv1"][1]))
        ref[4].weight.copy_(torch.from_numpy(w["ip1"][0]))
        ref[4].bias.copy_(torch.from_numpy(w["ip1"][1]))
        expected = ref(torch.from_numpy(x)).numpy()
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


def test_load_caffe_branching_eltwise():
    proto_text = """
input: "data"
input_shape { dim: 1 dim: 2 dim: 4 dim: 4 }
layer { name: "c1" type: "Convolution" bottom: "data" top: "c1"
        convolution_param { num_output: 2 kernel_size: 1 } }
layer { name: "c2" type: "Convolution" bottom: "data" top: "c2"
        convolution_param { num_output: 2 kernel_size: 1 } }
layer { name: "sum" type: "Eltwise" bottom: "c1" bottom: "c2" top: "sum"
        eltwise_param { operation: SUM } }
layer { name: "cat" type: "Concat" bottom: "c1" bottom: "sum" top: "cat" }
"""
    proto = tempfile.mktemp(suffix=".prototxt")
    with open(proto, "w") as f:
        f.write(proto_text)
    model = load_caffe(proto).evaluate()
    x = np.random.RandomState(2).randn(1, 2, 4, 4).astype(np.float32)
    out = model.forward(x)
    assert out.shape == (1, 4, 4, 4)  # concat of 2+2 channels


def test_train_phase_layers_skipped():
    proto_text = """
input: "data"
input_shape { dim: 1 dim: 3 dim: 4 dim: 4 }
layer { name: "c" type: "Convolution" bottom: "data" top: "c"
        convolution_param { num_output: 2 kernel_size: 1 } }
layer { name: "trainaug" type: "Dropout" bottom: "c" top: "c"
        include { phase: TRAIN } }
"""
    proto = tempfile.mktemp(suffix=".prototxt")
    with open(proto, "w") as f:
        f.write(proto_text)
    loader = CaffeLoader(proto)
    model, ins, outs = loader.load()
    names = [m.get_name() for m in model.__dict__["_modules"].values()]
    assert "trainaug" not in names


def test_customized_converter_hook():
    import bigdl_tpu.nn as nn

    proto_text = """
input: "data"
input_shape { dim: 1 dim: 3 dim: 4 dim: 4 }
layer { name: "c" type: "Convolution" bottom: "data" top: "c"
        convolution_param { num_output: 2 kernel_size: 1 } }
layer { name: "dummy" type: "Dummy" bottom: "c" top: "d" }
"""
    proto = tempfile.mktemp(suffix=".prototxt")
    with open(proto, "w") as f:
        f.write(proto_text)
    loader = CaffeLoader(
        proto, customized_converters={
            "Dummy": lambda lay, in_ch, blobs: (nn.ReLU(), in_ch)})
    model, _, _ = loader.load()
    x = np.random.RandomState(3).randn(1, 3, 4, 4).astype(np.float32)
    assert model.forward(x).shape == (1, 2, 4, 4)


def test_global_pooling_and_eltwise_coeff_and_concat_axis():
    proto_text = """
# leading comment
input: "data"
input_shape { dim: 1 dim: 2 dim: 4 dim: 4 }
layer { name: "gmax" type: "Pooling" bottom: "data" top: "gmax"
        pooling_param { pool: MAX global_pooling: true } }
# trailing comment"""
    proto = tempfile.mktemp(suffix=".prototxt")
    with open(proto, "w") as f:
        f.write(proto_text)
    model = load_caffe(proto).evaluate()
    x = np.random.RandomState(4).randn(1, 2, 4, 4).astype(np.float32)
    out = np.asarray(model.forward(x))
    assert out.shape == (1, 2, 1, 1)
    np.testing.assert_allclose(out.reshape(2), x.max(axis=(2, 3)).reshape(2))

    proto_text2 = """
input: "data"
input_shape { dim: 1 dim: 2 dim: 4 dim: 4 }
layer { name: "c1" type: "Convolution" bottom: "data" top: "c1"
        convolution_param { num_output: 2 kernel_size: 1 } }
layer { name: "diff" type: "Eltwise" bottom: "data" bottom: "c1" top: "d"
        eltwise_param { operation: SUM coeff: 1 coeff: -1 } }
layer { name: "cat2" type: "Concat" bottom: "d" bottom: "c1" top: "cat"
        concat_param { axis: 2 } }
"""
    proto2 = tempfile.mktemp(suffix=".prototxt")
    with open(proto2, "w") as f:
        f.write(proto_text2)
    model2 = load_caffe(proto2).evaluate()
    out2 = model2.forward(x)
    assert out2.shape == (1, 2, 8, 4)  # concat along axis 2


# ----------------------------- export (CaffePersister) --------------------

def _roundtrip(model, input_shape, x):
    """save -> reload with our own loader -> compare forward outputs
    (the reference round-trip contract, ``CaffePersister.scala:47``)."""
    import jax.numpy as jnp

    from bigdl_tpu.parallel.train_step import EvalStep
    from bigdl_tpu.utils.caffe_persister import save_caffe

    proto = tempfile.mktemp(suffix=".prototxt")
    weights = tempfile.mktemp(suffix=".caffemodel")
    save_caffe(model, proto, weights, input_shapes=input_shape)
    reloaded, _, _ = CaffeLoader(proto, weights).load()
    reloaded.evaluate()
    model.evaluate()
    # the compiled inference forward: an eager forward of a zoo model
    # compiles every layer's op on its own
    a, b = (np.asarray(EvalStep(m).run(x)) for m in (model, reloaded))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    return proto, weights


def test_persister_unnamed_modules_get_fresh_unique_names():
    """``get_name()``'s fallback derives from ``id() % 1e5``, so two
    unnamed modules can collide and silently shadow each other's layer
    + blobs in the prototxt — the cause of the intermittent inception_v1
    roundtrip failure (wrong channel wiring / dangling nodes on reload,
    dependent on heap layout).  The persister must mint its own fresh
    names for unnamed modules and keep only user-set ones."""
    import re

    import bigdl_tpu.nn as nn
    from bigdl_tpu.utils.caffe_persister import CaffePersister

    model = nn.Sequential(
        nn.SpatialConvolution(3, 4, 1, 1).set_name("conv_explicit"),
        nn.ReLU(),
        nn.SpatialConvolution(4, 4, 1, 1),
        nn.SpatialConvolution(4, 2, 1, 1))
    p = CaffePersister(model, input_shapes=(1, 3, 8, 8))
    p.build()
    names = [lay["name"] for lay in p.layers]
    assert "conv_explicit" in names
    assert len(names) == len(set(names))
    for nm in names:
        if nm != "conv_explicit":
            # persister-scoped counter names, never id-derived ones
            assert not re.fullmatch(r"(SpatialConvolution|ReLU)\d+", nm), nm

    # minted names must also dodge user-set ones wherever they appear in
    # the model ("conv1" here would be the counter's first conv pick)
    clash = nn.Sequential(
        nn.SpatialConvolution(3, 4, 1, 1),
        nn.SpatialConvolution(4, 4, 1, 1).set_name("conv1"))
    p = CaffePersister(clash, input_shapes=(1, 3, 8, 8))
    p.build()
    names = [lay["name"] for lay in p.layers]
    assert len(names) == len(set(names)), names
    assert "conv1" in names


def test_persister_sequential_cnn_roundtrip():
    import bigdl_tpu.nn as nn

    model = nn.Sequential(
        nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1).set_name("conv1"),
        nn.ReLU(),
        nn.SpatialMaxPooling(2, 2, 2, 2),
        nn.SpatialCrossMapLRN(3, 0.001, 0.75),
        nn.SpatialConvolution(4, 6, 3, 3, 2, 2, 0, 0, n_group=2,
                              with_bias=False),
        nn.Sigmoid(),
        nn.SpatialAveragePooling(2, 2, 1, 1),
        nn.InferReshape([0, -1]),
        nn.Linear(6, 5).set_name("fc"),
        nn.SoftMax(),
    )
    x = np.random.RandomState(0).randn(2, 3, 12, 12).astype(np.float32)
    proto, _ = _roundtrip(model, (1, 3, 12, 12), x)
    # named layers keep their names in the prototxt
    text = open(proto).read()
    assert 'name: "conv1"' in text and 'name: "fc"' in text


def test_persister_batchnorm_eps_and_1d_roundtrip():
    """Non-default eps must survive the round-trip (it is part of the
    normalization math, 1.2e-3 divergence when dropped), for BOTH the
    spatial and the dense (N,C) BatchNormalization variants — realistic
    running stats, not fresh-init."""
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn

    rs = np.random.RandomState(11)
    bn = nn.BatchNormalization(6, eps=1e-3)
    bn.weight = jnp.asarray(rs.rand(6) + 0.5, jnp.float32)
    bn.bias = jnp.asarray(rs.randn(6), jnp.float32)
    bn.running_mean = jnp.asarray(rs.randn(6), jnp.float32)
    bn.running_var = jnp.asarray(rs.rand(6) * 1e-2, jnp.float32)  # eps matters
    model = nn.Sequential(nn.Linear(3, 6), bn, nn.ReLU())
    x = rs.randn(4, 3).astype(np.float32)
    _roundtrip(model, (1, 3), x)


def test_persister_batchnorm_scale_roundtrip():
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn

    bn = nn.SpatialBatchNormalization(4)
    bn.weight = jnp.asarray(np.random.RandomState(1).rand(4) + 0.5,
                            jnp.float32)
    bn.bias = jnp.asarray(np.random.RandomState(2).randn(4), jnp.float32)
    bn.running_mean = jnp.asarray(np.random.RandomState(3).randn(4),
                                  jnp.float32)
    bn.running_var = jnp.asarray(np.random.RandomState(4).rand(4) + 0.5,
                                 jnp.float32)
    model = nn.Sequential(nn.SpatialConvolution(2, 4, 1, 1), bn, nn.ReLU())
    x = np.random.RandomState(5).randn(2, 2, 5, 5).astype(np.float32)
    _roundtrip(model, (1, 2, 5, 5), x)


def test_persister_graph_dag_roundtrip():
    import bigdl_tpu.nn as nn
    from bigdl_tpu.nn.graph import node_from_module

    inp = nn.Input(name="data")
    c1 = node_from_module(nn.SpatialConvolution(3, 4, 1, 1).set_name("b1"),
                          [inp])
    c2 = node_from_module(nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1)
                          .set_name("b2"), [inp])
    add = node_from_module(nn.CAddTable().set_name("sum"), [c1, c2])
    cat = node_from_module(nn.JoinTable(1, 0).set_name("cat"), [add, c1])
    out = node_from_module(nn.ReLU().set_name("out"), [cat])
    model = nn.Graph([inp], [out])
    x = np.random.RandomState(6).randn(2, 3, 6, 6).astype(np.float32)
    _roundtrip(model, (1, 3, 6, 6), x)


def test_persister_concat_container_and_floor_pooling():
    import bigdl_tpu.nn as nn

    model = nn.Sequential(
        nn.Concat(1)
        .add(nn.Sequential(nn.SpatialConvolution(2, 3, 1, 1), nn.ReLU()))
        .add(nn.Sequential(nn.SpatialMaxPooling(3, 3, 1, 1, 1, 1),
                           nn.SpatialConvolution(2, 2, 1, 1))),
        nn.SpatialMaxPooling(3, 3, 2, 2),  # floor mode must round-trip
    )
    x = np.random.RandomState(7).randn(2, 2, 7, 7).astype(np.float32)
    _roundtrip(model, (1, 2, 7, 7), x)


def test_prototxt_writer_parses_back():
    from bigdl_tpu.utils.caffe_persister import to_prototxt

    net = {"name": "n", "layer": [
        {"name": "p", "type": "Pooling", "bottom": ["d"], "top": "p",
         "pooling_param": {"pool": "MAX", "kernel_h": 3, "kernel_w": 3,
                           "stride_h": 2, "stride_w": 2}},
        {"name": "e", "type": "Eltwise", "bottom": ["p", "d"], "top": "e",
         "eltwise_param": {"operation": "SUM", "coeff": [1.0, -1.0]}},
    ]}
    parsed = parse_prototxt(to_prototxt(net))
    assert parsed["name"] == "n"
    layers = parsed["layer"]
    assert layers[0]["bottom"] == "d"
    assert layers[1]["bottom"] == ["p", "d"]
    assert layers[1]["eltwise_param"]["coeff"] == [1.0, -1.0]
    assert layers[0]["pooling_param"]["pool"] == "MAX"


@pytest.mark.parametrize("name,build,shape", [
    ("lenet", lambda: _zoo().build_lenet5(10), (1, 28, 28)),
    ("vgg16_cifar", lambda: _zoo().build_vgg_for_cifar10(10), (3, 32, 32)),
    ("inception_v1", lambda: _zoo().build_inception_v1(100), (3, 224, 224)),
])
def test_persister_zoo_roundtrip(name, build, shape):
    """VERDICT r4 next-step #8: the models that matter round-trip
    through prototxt+caffemodel with numeric equivalence (reference
    contract ``CaffePersister.scala:47``).  Exercises the LogSoftMax ->
    Softmax+Log emission, the 1-D BatchNormalization emitter, and the
    left-aligned Scale reload."""
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(0)
    model = build()
    x = np.random.default_rng(0).normal(size=(2,) + shape).astype(np.float32)
    _roundtrip(model, (1,) + shape, x)


def _zoo():
    from bigdl_tpu import models
    return models
