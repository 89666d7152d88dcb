"""LFM2-24B-A2B (LiquidAI; ``model_type`` ``lfm2_moe``), one chip's share:
the program's builder and the plain float32 reference of the same
mathematics.

The layers, as both compute them (d hidden, u always the normed input,
no bias anywhere, no activation in a convolution layer; what the
published ``config.json`` leaves open is listed under ``assumed`` in the
configuration's file):

- ``Norm(x) = w x / sqrt(mean(x^2) + eps)``, ``w`` starting at 1.
- Block: ``h = x + Mixer(Norm_1(x))``, ``y = h + FFN(Norm_2(h))``; layer
  i's mixer is ``layer_types[i]``, its feed-forward dense for ``i <
  num_dense_layers`` and sparse after.  The cut runs the published layers
  ``first_layer .. first_layer + num_hidden_layers - 1``.  After the last
  block ``Norm_f``, ``logits = x E^T`` with ``E`` the EMBEDDING's matrix
  over the ids held (tied: one parameter, read twice), log-softmax, mean
  negative log-likelihood over the positions.  No positional term
  outside the attention layers.
- ``conv`` mixer: ``[B, C, g] = split(u W_in, 3)`` (d columns each, in
  that order); ``z = B * g``; ``c_t = sum_{j < taps} k[:, j] z_{t - (taps -
  1) + j}``, ``z`` before position 0 read as zero (causal, depthwise,
  cross-correlation order); ``out = (C * c) W_out``.
- ``full_attention`` mixer: ``q = u W_q`` over H heads of D, ``k, v`` over
  G kv heads; q and k normed a head (``Norm`` of D) BEFORE the rotation;
  rotate-half rotary on all D dimensions; ``s_ij = q_i . k_j / sqrt(D)``
  for ``j <= i``; softmax; no gate; ``W_o``.
- Dense feed-forward: ``(silu(u W_1) * (u W_3)) W_2``.
- Sparse feed-forward: ``s = sigmoid(u W_r)`` over all the published
  experts; the ``num_experts_per_tok`` with the largest ``s + b`` (``b``
  one number an expert; ties to the lower index); ``w_e = scale * s_e /
  (sum of the chosen s + 1e-6)``: the bias chooses and does not weigh;
  ``y = sum over chosen e that are HELD here of w_e Expert_e(u)``, every
  expert the gated SiLU form.  No shared expert.  What the experts that
  are not held would add is left out.

The reference is straightforward ``jax.numpy``: the convolution is a sum
of shifted copies, attention a masked softmax in blocks, routing a dense
mask over the held experts with no sort and no top-k.  It knows nothing
of capacities, grouped products or kernels, and imports nothing of the
program.

Memory, which decides the reference's shape here.  The harness's
``follow`` keeps four trees of 3.09 GB on the chip from the second step
on (parameters, velocity, the last step's gradient, the one a gradient
program writes) and a fifth while it adds blocks of rows up, so a step's
two records are ONE block (``BLOCK_ROWS`` None) and one gradient program.
Inside it the records go through a LAYER one after the other
(``_over_records``) and only then through the next layer: the backward
pass adds up one layer's parameter gradients over the records and is
done with them, 3.5 GB of temporaries compiled for the chip.  The two
shapes tried before did not fit beside the harness's trees (my chip
runs, PR 34): one program a record (1.2 GB of temporaries, but the fifth
tree: 16.6 GB of 16.9) and both records whole, one after the other
(every leaf's gradient twice: 6.5 GB of temporaries; unrolled: 8.0).
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import reference
from benchmark.kernels.attention import kept_elements
from benchmark.models import plain_ops as P
from benchmark.models.laguna import (_gated, _gated_specs, _mm, _rms_norm,
                                     _rotate, _scale, _sigmoid, _w,
                                     rotary_tables)
from benchmark.models.qwen3_next import _by_row_blocks, _causal_softmax

#: a step's records are one block of the reference's gradient (see the
#: module's docstring)
BLOCK_ROWS = None

#: positions a block of the reference's row-wise work
_ROWS = 1024

#: added to the sum of the chosen scores before it divides
NORM_EPS = 1e-6


def layers_of(conf: Dict) -> List[Dict]:
    """Per layer of the cut: ``mixer`` (``"conv"`` or ``"full"``) and
    ``ffn`` (``"dense"`` or ``"sparse"``), from the published per-layer
    list, the published count of leading dense layers and where the cut
    starts."""
    first, n = conf["first_layer"], conf["num_hidden_layers"]
    return [dict(mixer="conv" if kind == "conv" else "full",
                 ffn="dense" if i < conf["num_dense_layers"] else "sparse")
            for i, kind in enumerate(conf["layer_types"])][first:first + n]


def head_dim(conf: Dict) -> int:
    return conf["hidden_size"] // conf["num_attention_heads"]


def _rotary_conf(conf: Dict) -> Dict:
    return {"partial_rotary_factor": 1.0, **conf["rope_parameters"]}


# -- the program --------------------------------------------------------------

def build(conf: Dict):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import models

    if not hasattr(nn, "GatedShortConv"):
        raise SystemExit("this program has no short-convolution mixer "
                         "(nn.GatedShortConv): it cannot run the lfm2 "
                         "family")
    plan = models.DecoderPlan(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        head_dim=head_dim(conf), kv_heads=conf["num_key_value_heads"],
        layers=[models.LayerPlan(layer["mixer"], conf["num_attention_heads"],
                                 layer["ffn"]) for layer in layers_of(conf)],
        window=0, rotary_window=None,
        rotary_full=nn.Rotary(head_dim(conf),
                              theta=conf["rope_parameters"]["rope_theta"]),
        dense_width=conf["intermediate_size"],
        expert_width=conf["moe_intermediate_size"], shared_width=0,
        n_experts=conf["num_experts_published"],
        top_k=conf["num_experts_per_tok"],
        held=tuple(conf["held_experts"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        normalize=conf["norm_topk_prob"], gate=None, eps=conf["norm_eps"],
        qk_norm=True, conv_taps=conf["conv_L_cache"],
        router_score="sigmoid", router_bias=conf["use_expert_bias"],
        tie_embeddings=conf["tie_word_embeddings"])
    return models.build_decoder_lm(plan, remat=True)


def criterion():
    import bigdl_tpu.nn as nn

    return nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)


def make_records(seed: int, n: int, conf: Dict):
    return reference.make_token_records(
        seed, n, conf["sequence_length"], conf["vocab_size"], conf["zipf"])


# -- the parameters, in the program's order -----------------------------------

#: the configuration ``param_specs`` last described, for ``loss_sum``
_LAST_CONF = None

#: parameters of each kind of mixer and of feed-forward
_MIXER_LEAVES = {"conv": 3, "full": 6}
_FFN_LEAVES = {"dense": 3, "sparse": 5}


def param_specs(conf: Dict) -> List[Dict]:
    global _LAST_CONF
    _LAST_CONF = conf
    if not (conf["tie_word_embeddings"] and conf["use_expert_bias"]):
        raise SystemExit("the lfm2 family's reference ties embedding and "
                         "head and chooses by a biased score; the "
                         "configuration says otherwise")
    d, dh = conf["hidden_size"], head_dim(conf)
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    held, we = conf["held_experts"][1], conf["moe_intermediate_size"]
    taps = conf["conv_L_cache"]
    # the one [vocab, d] matrix is drawn as a projection from d (rows of
    # norm about one, the published initializer's 0.02 at d = 2048): the
    # first norm rescales what the embedding reads of it
    specs = [_w("embed", (conf["vocab_size"], d), d)]
    for i, layer in enumerate(layers_of(conf)):
        b = f"layer{i}."
        specs.append(_scale(b + "norm1", d))
        if layer["mixer"] == "conv":
            specs += [_w(b + "conv", (d, taps), taps),
                      _w(b + "in", (3 * d, d), d), _w(b + "out", (d, d), d)]
        else:
            specs += [_w(b + "q", (h * dh, d), d),
                      _w(b + "k", (g * dh, d), d), _w(b + "v", (g * dh, d), d),
                      _scale(b + "q_norm", dh), _scale(b + "k_norm", dh),
                      _w(b + "o", (d, h * dh), h * dh)]
        specs.append(_scale(b + "norm2", d))
        if layer["ffn"] == "dense":
            specs += _gated_specs(b + "mlp", d, conf["intermediate_size"])
        else:
            specs += [_w(b + "experts.gate", (held, d, we), d),
                      _w(b + "experts.up", (held, d, we), d),
                      _w(b + "experts.down", (held, we, d), we),
                      dict(name=b + "expert_bias",
                           shape=(conf["num_experts_published"],),
                           kind="bias"),
                      _w(b + "router", (conf["num_experts_published"], d), d)]
    return specs + [_scale("norm_f", d)]


# -- FLOPs ---------------------------------------------------------------------

def flops_per_record(conf: Dict) -> Dict[str, int]:
    """Forward + backward of one record, 2 FLOPs a multiply-add, backward
    twice the forward; recomputation, norms, rotary, softmax, the
    convolution layers' two gates and the update are not counted.  Matrix
    products by active parameters a token (a routed expert counts the
    assignments that land here in expectation, ``tokens * k * held /
    experts`` rows a layer; the tied head is a product, the embedding a
    lookup; the convolution's taps are parameters a token too);
    attention by the score elements the causal mask keeps, exactly."""
    s, d, dh = conf["sequence_length"], conf["hidden_size"], head_dim(conf)
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    share = conf["num_experts_per_tok"] * conf["held_experts"][1] \
        / conf["num_experts_published"]
    gated = lambda width: 3 * d * width  # noqa: E731
    params, scores, taps = 0.0, 0, 0
    for layer in layers_of(conf):
        if layer["mixer"] == "conv":
            params += 3 * d * d + d * d
            taps += d * conf["conv_L_cache"]
        else:
            params += d * (2 * h * dh + 2 * g * dh)
            scores += h * kept_elements(s)
        if layer["ffn"] == "dense":
            params += gated(conf["intermediate_size"])
        else:
            params += d * conf["num_experts_published"] \
                + share * gated(conf["moe_intermediate_size"])
    params += d * conf["vocab_size"]                 # the head
    products = int(round(3 * 2 * params * s))
    attention = 3 * 2 * 2 * dh * scores              # q.k and p.v
    conv = 3 * 2 * taps * s
    return {"matrix_products": products, "attention": attention,
            "convolution": conv, "total": products + attention + conv}


# -- the reference --------------------------------------------------------------

def short_conv(z, k, quant=None):
    """z [S, C], k [C, taps]: ``c_t = sum_j k[:, j] z_{t - (taps - 1) +
    j}``, positions before the first read as zero."""
    s, taps = z.shape[0], k.shape[1]
    padded = jnp.pad(P.lower(z, quant), ((taps - 1, 0), (0, 0)))
    k = P.lower(k, quant)
    return P.lower_out(
        sum(padded[j:j + s] * k[:, j] for j in range(taps)), quant)


def conv_mixer(u, p, quant=None):
    k, w_in, w_out = p
    d = u.shape[-1]
    bcg = _by_row_blocks(lambda ub: _mm(ub, w_in, quant), u, rows=_ROWS)
    gate_in, gate_out, g = bcg[:, :d], bcg[:, d:2 * d], bcg[:, 2 * d:]
    c = short_conv(gate_in * g, k, quant)
    return _by_row_blocks(lambda x: _mm(x, w_out, quant), gate_out * c,
                          rows=_ROWS)


def _full_attention(u, p, conf, quant):
    wq, wk, wv, q_norm, k_norm, wo = p
    s, dh = u.shape[0], head_dim(conf)
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    eps = conf["norm_eps"]
    tables = rotary_tables(_rotary_conf(conf), dh, s)
    q = _rotate(_rms_norm(_mm(u, wq, quant).reshape(s, h, dh), q_norm, eps),
                tables)
    k = _rotate(_rms_norm(_mm(u, wk, quant).reshape(s, g, dh), k_norm, eps),
                tables)
    v = _mm(u, wv, quant).reshape(s, g, dh)
    out = _causal_softmax(q, k, v, quant)
    return _mm(out.reshape(s, h * dh), wo, quant)


def route(u, w_r, bias, conf, quant=None):
    """[S, E] weights, zero for the experts a token did not choose: the
    choice is by ``s + bias``, the weight is ``s`` alone."""
    k = conf["num_experts_per_tok"]
    score = _sigmoid(_mm(u, w_r, quant))                 # [S, E]
    pick = score + bias
    idx = jnp.arange(score.shape[-1])
    # experts ranked above e: a larger s + b, or the same at a lower index
    above = (pick[:, None, :] > pick[:, :, None]) | (
        (pick[:, None, :] == pick[:, :, None])
        & (idx[None, None, :] < idx[None, :, None]))
    chosen = jnp.sum(above, axis=-1) < k                 # [S, E]
    weight = jnp.where(chosen, score, 0.0)
    if conf["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                           + NORM_EPS)
    return conf["routed_scaling_factor"] * weight


def sparse(u, p, conf, quant=None, held=None):
    """The routed layer's result from the experts ``held = (first,
    count)`` has parameters for (default: the configuration's)."""
    e_gate, e_up, e_down, bias, w_r = p
    first, count = held or conf["held_experts"]
    weight = route(u, w_r, bias, conf, quant)

    def add_expert(y, e):
        # [d, width] stacks hold W^T of the (out, in) form _mm takes
        gate, up, down, w_e = e
        return y + w_e[:, None] * _gated(u, (gate.T, up.T, down.T), quant), None

    # a scan and not a Python loop: sixteen unrolled experts a layer made
    # a program the chip's compiler took minutes over, in every run
    y, _ = lax.scan(jax.checkpoint(add_expert), jnp.zeros_like(u),
                    (e_gate, e_up, e_down, weight[:, first:first + count].T))
    return y


def _over_records(fn, *arrays):
    """``fn`` on one record at a time (the leading axis), each recomputed
    in the backward pass, which then adds up ONE call's parameter
    gradients at a time: see the module's docstring."""
    return lax.map(jax.checkpoint(lambda r: fn(*r)), arrays)


def _loss_of_each(params, conf, x, y, quant):
    """x, y [R, S]: each record's mean negative log-likelihood, [R].  The
    records go through a layer one after the other, and only then
    through the next."""
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    eps = conf["norm_eps"]
    embed = take(1)[0]
    h = embed[x]                                         # [R, S, d]
    for layer in layers_of(conf):
        norm1, mixer = take(1)[0], take(_MIXER_LEAVES[layer["mixer"]])
        norm2, ffn = take(1)[0], take(_FFN_LEAVES[layer["ffn"]])

        def block(h, norm1=norm1, mixer=mixer, norm2=norm2, ffn=ffn,
                  layer=layer):
            u = _rms_norm(h, norm1, eps)
            if layer["mixer"] == "conv":
                h = h + conv_mixer(u, mixer, quant)
            else:
                h = h + _full_attention(u, mixer, conf, quant)
            if layer["ffn"] == "dense":
                fn = lambda u: _gated(u, ffn, quant)  # noqa: E731
            else:
                fn = lambda u: sparse(u, ffn, conf, quant)  # noqa: E731
            return h + _by_row_blocks(fn, _rms_norm(h, norm2, eps),
                                      rows=_ROWS)

        h = _over_records(block, h)
    norm_f = take(1)[0]

    def nll(hb, yb):
        logp = P.log_softmax(_mm(_rms_norm(hb, norm_f, eps), embed, quant))
        return -jnp.take_along_axis(logp, yb[:, None], axis=1)[:, 0]

    return _over_records(
        lambda h, y: jnp.mean(_by_row_blocks(nll, h, y, rows=_ROWS)), h, y)


def loss_sum(params, x, y, quant=None, conf=None):
    """Sum over the records of each record's mean, over its positions, of
    the next token's negative log-likelihood.  The harness's call carries
    no configuration: ``conf`` defaults to the configuration this module
    was last asked to describe (``param_specs`` runs before every
    reference)."""
    conf = conf or _LAST_CONF
    if conf is None:
        raise ValueError("loss_sum before param_specs(conf): which "
                         "configuration?")
    return jnp.sum(_loss_of_each(params, conf, x, y, quant))
