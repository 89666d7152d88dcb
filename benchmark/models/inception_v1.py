"""GoogLeNet / Inception-v1 (Szegedy et al., arXiv:1409.4842, Table 1
and Figure 3), without the auxiliary classifiers: the program's builder,
and the plain float32 reference of the same mathematics.

Departures from the paper, both the program's and therefore the
reference's: the two LRN layers and every max pool use Caffe's ceil-mode
output size (``bvlc_googlenet``, which BigDL's ``Inception_v1.scala``
copies); no dropout before the classifier when the configuration says
``has_dropout: false``.
"""

from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp

from . import plain_ops as P

#: rows per block of the reference's gradient: no layer couples the rows
#: of a batch, so blocks add up and float32 activations stay small
BLOCK_ROWS = 64

#: name, in, 1x1, 3x3 reduce, 3x3, 5x5 reduce, 5x5, pool proj (Table 1)
_INCEPTION = [
    ("3a", 192, 64, 96, 128, 16, 32, 32),
    ("3b", 256, 128, 128, 192, 32, 96, 64),
    ("pool",),
    ("4a", 480, 192, 96, 208, 16, 48, 64),
    ("4b", 512, 160, 112, 224, 24, 64, 64),
    ("4c", 512, 128, 128, 256, 24, 64, 64),
    ("4d", 512, 112, 144, 288, 32, 64, 64),
    ("4e", 528, 256, 160, 320, 32, 128, 128),
    ("pool",),
    ("5a", 832, 256, 160, 320, 32, 128, 128),
    ("5b", 832, 384, 192, 384, 48, 128, 128),
]

#: stem convolutions: name, in, out, kernel, stride, pad
_STEM = [("conv1/7x7_s2", 3, 64, 7, 2, 3),
         ("conv2/3x3_reduce", 64, 64, 1, 1, 0),
         ("conv2/3x3", 64, 192, 3, 1, 1)]

_LRN = dict(size=5, alpha=1e-4, beta=0.75, k=1.0)


def build(config: Dict):
    """The system under test, through the program's own builder."""
    from bigdl_tpu import models

    return models.build_inception_v1(config["classes"],
                                     has_dropout=config["has_dropout"])


def criterion():
    import bigdl_tpu.nn as nn

    return nn.ClassNLLCriterion()


def _branch_convs(row):
    name, cin, c1, c3r, c3, c5r, c5, pp = row
    pre = f"inception_{name}/"
    return [(pre + "1x1", cin, c1, 1, 1, 0),
            (pre + "3x3_reduce", cin, c3r, 1, 1, 0),
            (pre + "3x3", c3r, c3, 3, 1, 1),
            (pre + "5x5_reduce", cin, c5r, 1, 1, 0),
            (pre + "5x5", c5r, c5, 5, 1, 2),
            (pre + "pool_proj", cin, pp, 1, 1, 0)]


def _convs():
    out = list(_STEM)
    for row in _INCEPTION:
        if row[0] != "pool":
            out += _branch_convs(row)
    return out


def param_specs(config: Dict) -> List[Dict]:
    """Every parameter in the order the layers are applied: weight then
    bias of each convolution, then the classifier."""
    specs = []
    for name, cin, cout, k, _, _ in _convs():
        specs.append(dict(name=name + ".weight", shape=(cout, cin, k, k),
                          kind="weight", fan_in=cin * k * k))
        specs.append(dict(name=name + ".bias", shape=(cout,), kind="bias"))
    n = config["classes"]
    specs.append(dict(name="loss3/classifier.weight", shape=(n, 1024),
                      kind="weight", fan_in=1024))
    specs.append(dict(name="loss3/classifier.bias", shape=(n,), kind="bias"))
    return specs


def loss_sum(params, x, y, quant=None):
    """Sum over the rows of ``x`` of the negative log-likelihood of
    ``y``; ``params`` in ``param_specs`` order."""
    it = iter(params)

    def conv(h, spec):
        _, _, _, _, s, p = spec
        w, b = next(it), next(it)
        return P.relu(P.conv(h, w, b, s, p, quant))

    h = conv(x, _STEM[0])
    h = P.max_pool(h, 3, 2, 0, ceil=True)
    h = P.cross_map_lrn(h, **_LRN)
    h = conv(h, _STEM[1])
    h = conv(h, _STEM[2])
    h = P.cross_map_lrn(h, **_LRN)
    h = P.max_pool(h, 3, 2, 0, ceil=True)
    for row in _INCEPTION:
        if row[0] == "pool":
            h = P.max_pool(h, 3, 2, 0, ceil=True)
            continue
        b1, b3r, b3, b5r, b5, bp = _branch_convs(row)
        h = jnp.concatenate([
            conv(h, b1),
            conv(conv(h, b3r), b3),
            conv(conv(h, b5r), b5),
            conv(P.max_pool(h, 3, 1, 1, ceil=True), bp)], axis=1)
    h = P.global_avg_pool(h)
    w, b = next(it), next(it)
    return P.nll_sum(P.log_softmax(P.linear(h, w, b, quant)), y)


def flops_per_record(config: Dict) -> Dict[str, int]:
    """Model FLOPs (2 per multiply-add) of the convolutions and the
    classifier for one 3x224x224 record.  Backward is twice forward
    (one product for the input's gradient, one for the weight's), less
    the input gradient of conv1, which nothing needs."""
    hw = config["image"][1]
    size = {}
    h = P.conv_out(hw, 7, 2, 3)
    size["conv1/7x7_s2"] = h
    h = P.pool_out(h, 3, 2, 0, True)
    size["conv2/3x3_reduce"] = size["conv2/3x3"] = h
    h = P.pool_out(h, 3, 2, 0, True)
    for row in _INCEPTION:
        if row[0] == "pool":
            h = P.pool_out(h, 3, 2, 0, True)
        else:
            for c in _branch_convs(row):
                size[c[0]] = h
    macs = {name: P.conv_macs(cin, cout, k, (size[name],) * 2)
            for name, cin, cout, k, _, _ in _convs()}
    macs["loss3/classifier"] = 1024 * config["classes"]
    fwd = 2 * sum(macs.values())
    bwd = 2 * fwd - 2 * macs["conv1/7x7_s2"]
    return {"forward": fwd, "backward": bwd, "total": fwd + bwd}
