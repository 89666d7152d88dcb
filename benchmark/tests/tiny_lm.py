"""A token-sequence family for the benchmark's own tests, at a toy
width: the registry's ``transformer_lm`` (``models.build_transformer_lm``:
token and learned position embeddings, pre-norm blocks of causal
multi-head attention and a GELU MLP, a final layer norm, a vocabulary
head) trained as ``cli train`` trains it, with
``TimeDistributedCriterion(ClassNLLCriterion(), size_average=True)``;
and the plain float32 reference of the same mathematics.

It proves that the harness can feed, follow and judge records that are
token ids under either update rule.  It is a fixture: no entry in
``BENCHMARK.json``, no file under ``configs/``, no number of it is a
metric.  A configuration names it by its module path
(``"family": "benchmark.tests.tiny_lm"``).
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax.numpy as jnp

from benchmark import reference
from benchmark.models import plain_ops as P

#: no layer couples the rows of a batch
BLOCK_ROWS = 4

#: heads of every block: the one size no parameter's shape shows
HEADS = 4

_LN_EPS = 1e-5


def build(config: Dict):
    from bigdl_tpu import models

    return models.build_transformer_lm(
        config["vocab"], config["layers"], config["width"], HEADS,
        max_len=config["positions"], mlp_ratio=config["mlp_ratio"],
        dropout=0.0, scan=False)


def criterion():
    import bigdl_tpu.nn as nn

    return nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)


def make_records(seed: int, n: int, config: Dict):
    return reference.make_token_records(
        seed, n, config["positions"], config["vocab"], config["zipf"])


def _linear_specs(name, n_in, n_out):
    return [dict(name=name + ".weight", shape=(n_out, n_in), kind="weight",
                 fan_in=n_in),
            dict(name=name + ".bias", shape=(n_out,), kind="bias")]


def _norm_specs(name, n):
    return [dict(name=name + ".weight", shape=(n,), kind="scale"),
            dict(name=name + ".bias", shape=(n,), kind="bias")]


def param_specs(config: Dict) -> List[Dict]:
    e, v = config["width"], config["vocab"]
    specs = [dict(name="tokens.weight", shape=(v, e), kind="weight",
                  fan_in=e),
             dict(name="positions.weight", shape=(config["positions"], e),
                  kind="weight", fan_in=e)]
    for i in range(config["layers"]):
        b = f"block{i}."
        specs += _norm_specs(b + "ln1", e)
        for proj in ("q", "k", "v", "out"):
            specs += _linear_specs(b + proj, e, e)
        specs += _norm_specs(b + "ln2", e)
        specs += _linear_specs(b + "fc1", e, e * config["mlp_ratio"])
        specs += _linear_specs(b + "fc2", e * config["mlp_ratio"], e)
    return specs + _norm_specs("ln_f", e) + _linear_specs("head", e, v)


def _layer_norm(x, gamma, beta):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + _LN_EPS) * gamma + beta


def _gelu(x):
    """The tanh form (Hendrycks & Gimpel 2016), ``jax.nn.gelu``'s default."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, p, quant):
    (wq, bq), (wk, bk), (wv, bv), (wo, bo) = p
    n, s, e = x.shape

    def split(t):
        return t.reshape(n, s, HEADS, e // HEADS).transpose(0, 2, 1, 3)

    q, k, v = (split(P.linear(x, w, b, quant))
               for w, b in ((wq, bq), (wk, bk), (wv, bv)))
    scores = P.lower_out(jnp.einsum(
        "bhqd,bhkd->bhqk", P.lower(q, quant), P.lower(k, quant),
        precision=P.HIGHEST), quant) / math.sqrt(e // HEADS)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    out = P.lower_out(jnp.einsum(
        "bhqk,bhkd->bhqd", P.lower(probs, quant), P.lower(v, quant),
        precision=P.HIGHEST), quant)
    return P.linear(out.transpose(0, 2, 1, 3).reshape(n, s, e), wo, bo,
                    quant)


def loss_sum(params, x, y, quant=None):
    """Sum over the records of each record's mean, over its positions, of
    the next token's negative log-likelihood: what the time-distributed
    criterion averages over a batch."""
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    tokens, positions = take(2)
    h = tokens[x] + positions[None, :x.shape[1]]
    for _ in range((len(params) - 6) // 16):
        ln1, attn = take(2), [take(2) for _ in range(4)]
        ln2, fc1, fc2 = take(2), take(2), take(2)
        h = h + _attention(_layer_norm(h, *ln1), attn, quant)
        m = _gelu(P.linear(_layer_norm(h, *ln2), *fc1, quant))
        h = h + P.linear(m, *fc2, quant)
    logp = P.log_softmax(P.linear(_layer_norm(h, *take(2)), *take(2), quant))
    return P.nll_sum(logp.reshape(-1, logp.shape[-1]),
                     y.reshape(-1)) / y.shape[1]
