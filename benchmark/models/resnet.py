"""ResNet-50 (He et al., arXiv:1512.03385, Table 1; bottleneck blocks,
projection shortcuts where the shape changes — option B): the program's
builder, and the plain float32 reference of the same mathematics.

Departures from the paper, the program's and therefore the reference's
(both follow ``fb.resnet.torch``, which BigDL's ``ResNet.scala`` copies):
the stride of a down-sampling block sits on its 3x3 convolution, and
every convolution carries a bias in front of its batch normalisation.
"""

from __future__ import annotations

from typing import Dict, List

import jax

from . import plain_ops as P

#: batch normalisation couples the rows of a batch, so the reference
#: takes the batch whole and recomputes each block in its backward pass
BLOCK_ROWS = None

_STAGES = {50: [(64, 3), (128, 4), (256, 6), (512, 3)]}
_BN_EPS = 1e-5


def build(config: Dict):
    from bigdl_tpu import models

    return models.build_resnet(config["depth"], config["classes"])


def criterion():
    import bigdl_tpu.nn as nn

    return nn.ClassNLLCriterion()


def _blocks(config):
    """(n_in, width, stride) of every bottleneck block."""
    out, n_in = [], 64
    for stage, (w, count) in enumerate(_STAGES[config["depth"]]):
        for i in range(count):
            out.append((n_in, w, 2 if stage > 0 and i == 0 else 1))
            n_in = 4 * w
    return out


def _block_convs(n_in, w, stride):
    """name suffix, in, out, kernel, stride, pad; the shortcut last."""
    convs = [("a", n_in, w, 1, 1, 0), ("b", w, w, 3, stride, 1),
             ("c", w, 4 * w, 1, 1, 0)]
    if n_in != 4 * w or stride != 1:
        convs.append(("shortcut", n_in, 4 * w, 1, stride, 0))
    return convs


def _conv_bn_specs(name, cin, cout, k):
    return [dict(name=name + ".conv.weight", shape=(cout, cin, k, k),
                 kind="weight", fan_in=cin * k * k),
            dict(name=name + ".conv.bias", shape=(cout,), kind="bias"),
            dict(name=name + ".bn.weight", shape=(cout,), kind="scale"),
            dict(name=name + ".bn.bias", shape=(cout,), kind="bias")]


def param_specs(config: Dict) -> List[Dict]:
    specs = _conv_bn_specs("conv1", 3, 64, 7)
    for i, blk in enumerate(_blocks(config)):
        for suffix, cin, cout, k, _, _ in _block_convs(*blk):
            specs += _conv_bn_specs(f"block{i}.{suffix}", cin, cout, k)
    n, feat = config["classes"], 4 * _STAGES[config["depth"]][-1][0]
    specs.append(dict(name="fc.weight", shape=(n, feat), kind="weight",
                      fan_in=feat))
    specs.append(dict(name="fc.bias", shape=(n,), kind="bias"))
    return specs


def _conv_bn(h, p, stride, pad, quant):
    w, b, gamma, beta = p
    return P.batch_norm_train(P.conv(h, w, b, stride, pad, quant),
                              gamma, beta, _BN_EPS)


def _bottleneck(h, params, blk, quant):
    convs = _block_convs(*blk)
    ps = [params[4 * i:4 * i + 4] for i in range(len(convs))]
    y = h
    for i in range(3):
        _, _, _, _, s, pad = convs[i]
        y = _conv_bn(y, ps[i], s, pad, quant)
        if i < 2:
            y = P.relu(y)
    if len(convs) == 4:
        h = _conv_bn(h, ps[3], convs[3][4], 0, quant)
    return P.relu(y + h)


def loss_sum(params, x, y, quant=None):
    params = list(params)
    h = P.relu(_conv_bn(x, params[:4], 2, 3, quant))
    h = P.max_pool(h, 3, 2, 1)
    at = 4
    for blk in _blocks({"depth": _depth_of(params)}):
        n = 4 * len(_block_convs(*blk))
        block = jax.checkpoint(
            lambda hh, pp, blk=blk: _bottleneck(hh, pp, blk, quant))
        h = block(h, params[at:at + n])
        at += n
    h = P.global_avg_pool(h)
    return P.nll_sum(P.log_softmax(
        P.linear(h, params[at], params[at + 1], quant)), y)


def _depth_of(params) -> int:
    for depth in _STAGES:
        if len(param_specs({"depth": depth, "classes": 1})) == len(params):
            return depth
    raise ValueError(f"{len(params)} parameters fit no known depth")


def flops_per_record(config: Dict) -> Dict[str, int]:
    """Model FLOPs (2 per multiply-add) of the convolutions and the
    classifier for one 3x224x224 record; backward is twice forward less
    the input gradient of conv1, which nothing needs."""
    h = P.conv_out(config["image"][1], 7, 2, 3)
    conv1 = P.conv_macs(3, 64, 7, (h, h))
    macs = conv1
    h = P.pool_out(h, 3, 2, 1)
    for blk in _blocks(config):
        h_out = P.conv_out(h, 3, blk[2], 1)
        for suffix, cin, cout, k, _, _ in _block_convs(*blk):
            out = h if suffix == "a" else h_out
            macs += P.conv_macs(cin, cout, k, (out, out))
        h = h_out
    macs += 4 * _STAGES[config["depth"]][-1][0] * config["classes"]
    fwd = 2 * macs
    bwd = 2 * fwd - 2 * conv1
    return {"forward": fwd, "backward": bwd, "total": fwd + bwd}
