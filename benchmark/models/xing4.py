"""Xing4.0-29B-A4B (XingChen-AGI; ``model_type`` ``xing4_0``), one chip's
share of an expert-parallel-8 stage: the program's builder and the plain
float32 reference of the same mathematics.

The model, as both compute it (``Norm(x) = w x / sqrt(mean(x^2) + eps)``,
``w`` from 1; no bias anywhere but the router's choosing bias and the
residual path's ``b``; ``n`` = ``hc_mult`` residual streams of ``C`` =
``hidden_size``; what the published ``config.json`` leaves open is listed
under ``assumed`` in the configuration's file):

- Streams: ``X_0[i] = E[token]`` for every ``i`` (the embedding, copied).
  After the last layer ``h = sum_i X[i]``, ``logits = Norm_f(h) W_head``,
  log-softmax, mean negative log-likelihood over the positions.
- A sub-layer ``F`` on a token's streams ``X`` in ``R^{n x C}``
  (manifold-constrained hyper-connections, arXiv:2512.24880):
  ``x^ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)`` over all ``nC`` entries;
  ``[p, q, R] = x^ Phi^T`` (``Phi`` ``[n + n + n^2, nC]``);
  ``H_pre = sigmoid(a_pre p + b_pre)``; ``H_post = 2 sigmoid(a_post q +
  b_post)``; ``M = exp(clip(a_res mat(R) + b_res, clamp_min, clamp_max))``
  (``mat`` row-major), then ``hc_sinkhorn_iters`` times: every column
  divided by its sum, then every row by its sum, which gives ``H_res``;
  ``u = sum_i H_pre[i] X[i]``; ``f = F(u)``; ``X'[i] = sum_j H_res[i, j]
  X[j] + H_post[i] f``.  A layer is two of them, each with its own
  ``Phi``, ``b`` and ``a``: ``F_1(u) = Attn(Norm_1(u))``, ``F_2(u) =
  FFN(Norm_2(u))``.
- ``Attn(u)`` (latent attention): ``c_q = Norm_q(u W_qa)``; ``[q_nope,
  q_rope] = split_heads(c_q W_qb, H x [D, R])``; ``[c_kv, k_rope] =
  split(u W_kva, [kv_lora_rank, R])``; ``c_kv <- Norm_kv(c_kv)``;
  ``[k_nope, v] = split_heads(c_kv W_kvb, H x [D, V])``; rotary (YaRN,
  rotate-half pairing, cos and sin times ``mscale / mscale_all_dim``) on
  ``q_rope`` of every head and on the ONE ``k_rope`` every head shares;
  ``s_ij = scale (q_nope_i . k_nope_j + rope(q_rope_i) . rope(k_rope_j))``
  for ``j <= i``, which is ``scale q_i . k_j`` on the concatenated heads,
  ``scale = (D + R)^-0.5 (0.1 mscale_all_dim ln factor + 1)^2``; softmax;
  ``o_h = sum_j p_ij v_j`` (``V`` wide); ``concat_h(o_h) W_o``.
- Dense ``FFN(u) = (silu(u W_g) * (u W_u)) W_d``.  Sparse: ``s =
  sigmoid(u W_r)`` over the published experts; the ``num_experts_per_tok``
  largest of ``s + b``; ``w_e = routed_scaling_factor s_e / (sum of the
  chosen s + 1e-6)``; ``FFN(u) = Shared(u) + sum over chosen e that are
  HELD here of w_e Expert_e(u)``, every expert and the shared expert the
  gated SiLU form (``models/lfm2.py``'s routing, which is this one).

The reference is straightforward ``jax.numpy`` float32 with
``precision=HIGHEST``: Sinkhorn as the loop it is, attention a masked
softmax a head and a block of queries (``[32, 8192, 8192]`` scores are
never whole), routing a dense mask over the held experts.  It imports
nothing of the program.

Memory, which decides its shape: the harness's ``follow`` keeps four
float32 trees of 3.04 GB on the chip from the second step on, and a
token's four streams in float32 are ``[8192, 4, 3584]`` = 470 MB an array.
Everything a token does on its own (the whole residual path, the
feed-forward, the head) runs in blocks of ``_ROWS`` positions, each
recomputed in the backward pass, and a layer's backward computes its input
again from the embedding (``_record_loss``), so that one layer's streams
are alive at a time.
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import reference
from benchmark.kernels import latent_attention as mla_kernel
from benchmark.models import lfm2
from benchmark.models import plain_ops as P
from benchmark.models.laguna import (_gated, _gated_specs, _mm, _rms_norm,
                                     _rotate, _scale, _sigmoid, _w,
                                     rotary_tables)
from benchmark.models.qwen3_next import _by_row_blocks

#: no layer couples the records of a batch; one record is a step
BLOCK_ROWS = 1

#: positions a block of the reference's row-wise work and of its
#: attention's queries
_ROWS = 1024

#: what the plan needs of the program beyond the older families
PLAN_FIELDS = ("q_rank", "kv_rank", "rope_dim", "value_dim",
               "residual_streams", "sinkhorn_iters", "residual_clamp",
               "residual_eps")


def layers_of(conf: Dict) -> List[str]:
    """The feed-forward of each layer of the cut, ``"dense"`` or
    ``"sparse"``, from the published count of leading dense layers and
    where the cut starts (every mixer is latent attention)."""
    first, n = conf["first_layer"], conf["num_hidden_layers"]
    return ["dense" if i < conf["first_k_dense_replace"] else "sparse"
            for i in range(first, first + n)]


def qk_dim(conf: Dict) -> int:
    return conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(conf: Dict) -> float:
    """``qk_dim^-0.5`` times YaRN's ``mscale`` squared (``0.1
    mscale_all_dim ln factor + 1``, the DeepSeek-V2 line's rule)."""
    r = conf["rope_scaling"]
    return qk_dim(conf) ** -0.5 \
        * _yarn_mscale(r["factor"], r["mscale_all_dim"]) ** 2


def _rotary_conf(conf: Dict) -> Dict:
    """The published ``rope_scaling`` under the keys
    ``laguna.rotary_tables`` reads; cos and sin are multiplied by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    r = conf["rope_scaling"]
    return {"partial_rotary_factor": 1.0, "rope_theta": conf["rope_theta"],
            "rope_type": r["type"], "factor": r["factor"],
            "original_max_position_embeddings":
                r["original_max_position_embeddings"],
            "beta_fast": r["beta_fast"], "beta_slow": r["beta_slow"],
            "attention_factor": _yarn_mscale(r["factor"], r["mscale"])
            / _yarn_mscale(r["factor"], r["mscale_all_dim"])}


def hc_columns(conf: Dict) -> int:
    n = conf["hc_mult"]
    return 2 * n + n * n


# -- the program --------------------------------------------------------------

def build(conf: Dict):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import models

    lacks = [f for f in PLAN_FIELDS if f not in models.DecoderPlan._fields]
    if lacks or not hasattr(nn, "LatentAttention"):
        raise SystemExit(f"this program's DecoderPlan has no "
                         f"{', '.join(lacks) or 'latent attention'}: it "
                         f"cannot build the xing4 family's latent attention "
                         f"or its multi-stream residual path")
    r = _rotary_conf(conf)
    plan = models.DecoderPlan(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        head_dim=conf["qk_nope_head_dim"],
        kv_heads=conf["num_key_value_heads"],
        layers=[models.LayerPlan("latent", conf["num_attention_heads"], ffn)
                for ffn in layers_of(conf)],
        window=0, rotary_window=None,
        rotary_full=nn.Rotary(
            conf["qk_rope_head_dim"], theta=r["rope_theta"],
            factor=r["factor"],
            original_max_position=r["original_max_position_embeddings"],
            beta_fast=r["beta_fast"], beta_slow=r["beta_slow"],
            attention_factor=r["attention_factor"]),
        dense_width=conf["intermediate_size"],
        expert_width=conf["moe_intermediate_size"],
        shared_width=conf["n_shared_experts"] * conf["moe_intermediate_size"],
        n_experts=conf["n_routed_experts_published"],
        top_k=conf["num_experts_per_tok"], held=tuple(conf["held_experts"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        normalize=conf["norm_topk_prob"], gate=None,
        eps=conf["rms_norm_eps"], router_score=conf["scoring_func"],
        router_bias=conf["topk_method"] == "noaux_tc",
        tie_embeddings=conf["tie_word_embeddings"],
        attention_scale=softmax_scale(conf),
        q_rank=conf["q_lora_rank"], kv_rank=conf["kv_lora_rank"],
        rope_dim=conf["qk_rope_head_dim"], value_dim=conf["v_head_dim"],
        residual_streams=conf["hc_mult"],
        sinkhorn_iters=conf["hc_sinkhorn_iters"],
        residual_clamp=float(conf["mhc_h_res_clamp_max"]),
        residual_eps=conf["hc_eps"])
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

#: parameters of a residual path, of the mixer and of each feed-forward
_HC_LEAVES, _ATTN_LEAVES = 3, 7
_FFN_LEAVES = {"dense": 3, "sparse": 8}


def _hc_specs(name: str, conf: Dict) -> List[Dict]:
    """``Phi`` as a projection from the ``nC`` entries of a unit-RMS
    vector (its results start at variance 1), ``b`` of unit spread (a
    weight whose ``fan_in`` is ``init_gain``: the harness draws ``sqrt(
    init_gain / fan_in) z``), ``a`` near 1: at the seeded draw ``H_res``
    is neither uniform nor the identity and moves with the token."""
    wide = conf["hc_mult"] * conf["hidden_size"]
    k = hc_columns(conf)
    return [_w(name + ".phi", (k, wide), wide),
            dict(name=name + ".b", shape=(k,), kind="weight",
                 fan_in=conf["init_gain"]),
            _scale(name + ".a", 3)]


def param_specs(conf: Dict) -> List[Dict]:
    global _LAST_CONF
    _LAST_CONF = conf
    if (conf["tie_word_embeddings"] or conf["scoring_func"] != "sigmoid"
            or conf["topk_method"] != "noaux_tc" or conf["n_group"] != 1
            or conf["attention_bias"] or conf["n_shared_experts"] != 1
            or conf["mhc_h_res_clamp_min"] != -conf["mhc_h_res_clamp_max"]):
        raise SystemExit("the xing4 family's reference has an untied head, "
                         "a sigmoid router whose bias chooses over one "
                         "group, one shared expert, no attention bias and a "
                         "symmetric clamp; the configuration says otherwise")
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    rq, rkv = conf["q_lora_rank"], conf["kv_lora_rank"]
    held, we = conf["held_experts"][1], conf["moe_intermediate_size"]
    n_exp = conf["n_routed_experts_published"]
    # an embedding row is a unit-variance vector (fan_in 1)
    specs = [_w("embed", (conf["vocab_size"], d), 1)]
    for i, ffn in enumerate(layers_of(conf)):
        b = f"layer{i}."
        specs += _hc_specs(b + "hc_attn", conf)
        specs += [_scale(b + "norm1", d),
                  _w(b + "q_a", (rq, d), d), _scale(b + "q_norm", rq),
                  _w(b + "q_b", (h * (dn + dr), rq), rq),
                  _w(b + "kv_a", (rkv + dr, d), d),
                  _scale(b + "kv_norm", rkv),
                  _w(b + "kv_b", (h * (dn + dv), rkv), rkv),
                  _w(b + "o", (d, h * dv), h * dv)]
        specs += _hc_specs(b + "hc_ffn", conf)
        specs.append(_scale(b + "norm2", d))
        if ffn == "dense":
            specs += _gated_specs(b + "mlp", d, conf["intermediate_size"])
        else:
            specs += [_w(b + "experts.gate", (held, d, we), d),
                      _w(b + "experts.up", (held, d, we), d),
                      _w(b + "experts.down", (held, we, d), we),
                      dict(name=b + "expert_bias", shape=(n_exp,),
                           kind="bias"),
                      _w(b + "router", (n_exp, d), d)]
            specs += _gated_specs(b + "shared", d,
                                  conf["n_shared_experts"] * we)
    return specs + [_scale("norm_f", d),
                    _w("head", (conf["vocab_size"], d), d)]


# -- FLOPs ---------------------------------------------------------------------

def attention_shape(conf: Dict) -> Dict:
    """One layer's attention call, as ``kernels/latent_attention.py``
    counts it."""
    return dict(heads=conf["num_attention_heads"],
                seq=conf["sequence_length"], qk_dim=qk_dim(conf),
                value_dim=conf["v_head_dim"])


def flops_per_record(conf: Dict) -> Dict[str, int]:
    """Forward + backward of one record, 2 FLOPs a multiply-add, backward
    twice the forward; recomputation, norms, rotary, softmax, Sinkhorn,
    the residual path's weighted sums and the update are not counted.
    Matrix products by active parameters a token (a routed expert counts
    the assignments that land here in expectation, ``tokens * k * held /
    experts`` rows a layer; the head is a product, the embedding a
    lookup); the residual path's projection (``2n + n^2`` columns over
    ``nC``, two a layer) on its own line; attention by the score elements
    the causal mask keeps, exactly, ``q k^T`` over ``qk_dim`` and ``p v``
    over ``value_dim``."""
    s, d, h = conf["sequence_length"], conf["hidden_size"], \
        conf["num_attention_heads"]
    dn, dv = conf["qk_nope_head_dim"], conf["v_head_dim"]
    rq, rkv, dr = conf["q_lora_rank"], conf["kv_lora_rank"], \
        conf["qk_rope_head_dim"]
    share = conf["num_experts_per_tok"] * conf["held_experts"][1] \
        / conf["n_routed_experts_published"]
    gated = lambda width: 3 * d * width  # noqa: E731
    we = conf["moe_intermediate_size"]
    attn = d * rq + rq * h * (dn + dr) + d * (rkv + dr) \
        + rkv * h * (dn + dv) + h * dv * d
    params, paths, attention = 0.0, 0, 0
    for ffn in layers_of(conf):
        params += attn
        paths += 2 * hc_columns(conf) * conf["hc_mult"] * d
        attention += 3 * mla_kernel.flops("fwd", **attention_shape(conf))
        if ffn == "dense":
            params += gated(conf["intermediate_size"])
        else:
            params += d * conf["n_routed_experts_published"] \
                + gated(conf["n_shared_experts"] * we) + share * gated(we)
    params += d * conf["vocab_size"]                 # the head
    products = int(round(3 * 2 * params * s))
    residual = 3 * 2 * paths * s
    return {"matrix_products": products, "residual_path": residual,
            "attention": attention,
            "total": products + residual + attention}


# -- the reference --------------------------------------------------------------

def sinkhorn(m, iters: int):
    """``m`` [..., n, n] positive: ``iters`` times every column divided by
    its sum, then every row by its sum."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-2, keepdims=True)
        m = m / jnp.sum(m, axis=-1, keepdims=True)
    return m


def hc_coefficients(x, p, conf, quant=None):
    """x [T, n, C] -> ``H_pre`` [T, n], ``H_post`` [T, n], ``H_res`` [T,
    n, n]."""
    phi, b, a = p
    t, n, _ = x.shape
    flat = x.reshape(t, -1)
    unit = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                           + conf["hc_eps"])
    proj = _mm(unit, phi, quant)
    pre = _sigmoid(a[0] * proj[:, :n] + b[:n])
    post = 2.0 * _sigmoid(a[1] * proj[:, n:2 * n] + b[n:2 * n])
    logits = jnp.clip(a[2] * proj[:, 2 * n:] + b[2 * n:],
                      conf["mhc_h_res_clamp_min"],
                      conf["mhc_h_res_clamp_max"])
    res = sinkhorn(jnp.exp(logits).reshape(t, n, n),
                   conf["hc_sinkhorn_iters"])
    return pre, post, res


def hyper_connected(x, p, fn, conf, quant=None, rowwise=False):
    """One sub-layer ``fn`` ([S, C] -> [S, C]) inside its residual path on
    the streams x [S, n, C].  ``rowwise``: ``fn`` takes every position on
    its own (a feed-forward), so a block of positions goes through read,
    ``fn`` and write in one piece and the streams are read whole once."""

    def read(xb):
        pre, post, res = hc_coefficients(xb, p, conf, quant)
        return jnp.sum(pre[:, :, None] * xb, axis=1), post, res

    def write(xb, fb, post, res):
        return jnp.einsum("tij,tjc->tic", res, xb, precision=P.HIGHEST) \
            + post[:, :, None] * fb[:, None, :]

    def block(xb):
        u, post, res = read(xb)
        return write(xb, fn(u), post, res)

    def whole(x):
        u, post, res = _by_row_blocks(read, x, rows=_ROWS)
        return _by_row_blocks(write, x, fn(u), post, res, rows=_ROWS)

    if rowwise:
        return _by_row_blocks(block, x, rows=_ROWS)
    # a sub-layer's own residuals live only while ITS backward runs
    return jax.checkpoint(whole)(x)


def latent_attention(u, p, conf, quant=None):
    """u [S, C], already normed.  A head's queries, keys and values are
    projected out of the two latents INSIDE the loop over the heads (the
    rows of ``W_qb`` and ``W_kvb`` that are that head's), so that no ``[S,
    H, 192]`` array is ever whole: the latents are 768 and 512 wide."""
    wqa, q_norm, wqb, wkva, kv_norm, wkvb, wo = p
    s, h = u.shape[0], conf["num_attention_heads"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    rkv = conf["kv_lora_rank"]
    eps, scale = conf["rms_norm_eps"], softmax_scale(conf)
    tables = rotary_tables(_rotary_conf(conf), dr, s)
    c_q = P.lower(_rms_norm(_mm(u, wqa, quant), q_norm, eps), quant)
    latent = _mm(u, wkva, quant)
    c_kv = P.lower(_rms_norm(latent[:, :rkv], kv_norm, eps), quant)
    shared_key = P.lower(_rotate(latent[:, None, rkv:], tables)[:, 0], quant)
    bq = math.gcd(s, _ROWS)
    k_pos = jnp.arange(s)[None, :]

    def head(args):
        wq, wkv = args                    # this head's rows: [D + R, rq], ...
        q = P.lower_out(jnp.dot(c_q, wq.T, precision=P.HIGHEST), quant)
        kv = P.lower_out(jnp.dot(c_kv, wkv.T, precision=P.HIGHEST), quant)
        q_nope = P.lower(q[:, :dn], quant)
        q_rope = P.lower(_rotate(q[:, None, dn:], tables)[:, 0], quant)
        k_nope, v = P.lower(kv[:, :dn], quant), P.lower(kv[:, dn:], quant)

        def block(a):
            i, qn_i, qr_i = a
            scores = scale * P.lower_out(
                jnp.dot(qn_i, k_nope.T, precision=P.HIGHEST)
                + jnp.dot(qr_i, shared_key.T, precision=P.HIGHEST), quant)
            keep = k_pos <= i * bq + jnp.arange(bq)[:, None]
            scores = jnp.where(keep, scores, -jnp.inf)
            scores = scores - jnp.max(scores, axis=-1, keepdims=True)
            prob = jnp.exp(scores)
            prob = prob / jnp.sum(prob, axis=-1, keepdims=True)
            return P.lower_out(jnp.dot(P.lower(prob, quant), v,
                                       precision=P.HIGHEST), quant)

        blocks = (jnp.arange(s // bq), q_nope.reshape(s // bq, bq, dn),
                  q_rope.reshape(s // bq, bq, dr))
        return lax.map(jax.checkpoint(block), blocks).reshape(s, dv)

    out = lax.map(jax.checkpoint(head),
                  (P.lower(wqb, quant).reshape(h, dn + dr, -1),
                   P.lower(wkvb, quant).reshape(h, dn + dv, -1)))
    return _mm(jnp.moveaxis(out, 0, 1).reshape(s, h * dv), wo, quant)


def sparse(u, p, conf, quant=None, held=None):
    """The shared expert and the routed layer's result from the experts
    ``held`` (default: the configuration's) has parameters for."""
    routed, shared = p[:5], p[5:]
    return _gated(u, shared, quant) + lfm2.sparse(u, routed, conf, quant,
                                                  held)


def _layer(x, p, ffn, conf, quant):
    """x [S, n, C] through one layer's two sub-layers."""
    it = iter(p)

    def take(n):
        return [next(it) for _ in range(n)]

    eps = conf["rms_norm_eps"]
    hc1, norm1, attn = take(_HC_LEAVES), take(1)[0], take(_ATTN_LEAVES)
    hc2, norm2, mlp = take(_HC_LEAVES), take(1)[0], take(_FFN_LEAVES[ffn])
    x = hyper_connected(
        x, hc1, lambda u: latent_attention(_rms_norm(u, norm1, eps), attn,
                                           conf, quant),
        conf, quant)
    if ffn == "dense":
        fn = lambda u: _gated(u, mlp, quant)  # noqa: E731
    else:
        fn = lambda u: sparse(u, mlp, conf, quant)  # noqa: E731
    return hyper_connected(x, hc2, lambda u: fn(_rms_norm(u, norm2, eps)),
                           conf, quant, rowwise=True)


def _record_loss(params, conf, x, y, quant):
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    n = conf["hc_mult"]
    embed = take(1)[0]
    kinds = layers_of(conf)
    leaves = [take(2 * _HC_LEAVES + 2 + _ATTN_LEAVES + _FFN_LEAVES[ffn])
              for ffn in kinds]
    norm_f, head = take(2)

    def through(i):
        """``embed[x]`` -> the streams after layer ``i``.  The backward of
        layer ``i`` computes its input again from the embedding's rows and
        keeps no other layer's: one 470 MB array of streams is alive where
        a checkpoint a layer would keep five (the extra forwards, ten
        layers' worth, are a second of the chip's time a step)."""
        if i < 0:
            return lambda h: jnp.broadcast_to(
                h[:, None, :], (h.shape[0], n, h.shape[1]))
        before = through(i - 1)
        return jax.checkpoint(
            lambda h: _layer(before(h), leaves[i], kinds[i], conf, quant))

    h = through(len(kinds) - 1)(embed[x])

    def nll(hb, yb):
        logp = P.log_softmax(_mm(
            _rms_norm(jnp.sum(hb, axis=1), norm_f, conf["rms_norm_eps"]),
            head, quant))
        return -jnp.take_along_axis(logp, yb[:, None], axis=1)[:, 0]

    return jnp.mean(_by_row_blocks(nll, h, y, rows=_ROWS))


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
    return sum(_record_loss(params, conf, x[i], y[i], quant)
               for i in range(x.shape[0]))
