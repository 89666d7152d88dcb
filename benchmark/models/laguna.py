"""Laguna-S-2.1 (poolside; ``model_type`` ``laguna``), one chip's share:
the program's builder and the plain float32 reference of the same
mathematics.

The layers, as both compute them (d hidden, D head size, G kv heads,
H query heads of the layer, u always the normed input, no bias anywhere):

- ``RMSNorm(x) = w * x / sqrt(mean(x^2) + eps)``.
- Block: ``h = x + Attn(RMSNorm_1(x))``, ``y = h + FFN(RMSNorm_2(h))``;
  after the last block ``RMSNorm_f``, ``logits = x W_head`` over the ids
  held, log-softmax, mean negative log-likelihood over the positions.
- Attention: ``q = u W_q`` as [S, H, D], ``k``, ``v`` as [S, G, D].
  Rotary on q and k in the rotate-half pairing: window layers all D
  dimensions at their theta; full layers the first ``partial_rotary_factor
  * D`` with YaRN (inverse frequencies blended between the plain ones and
  the plain ones over ``factor`` along the linear ramp that ``beta_fast``
  and ``beta_slow`` bound; cos and sin times ``attention_factor``), the
  rest pass through.  Query head h reads kv head ``h // (H / G)``.
  ``s_ij = q_i . k_j / sqrt(D)`` for ``j <= i`` and, in window layers,
  ``i - j < window``; softmax; ``o_h = softmax(s) v``.  Gate: ``g =
  sigmoid(u W_g)``, one scalar a head and position; ``out = concat_h(g_h
  o_h) W_o``.
- Dense feed-forward: ``(silu(u W_gate) * (u W_up)) W_down``.
- Sparse feed-forward: ``p = softmax(u W_r)`` over all the published
  experts; the ``num_experts_per_tok`` largest (ties to the lower index);
  ``w_e = scale * p_e / sum of the chosen p``; ``y = Shared(u) + sum over
  chosen e that are HELD here of w_e Expert_e(u)``, every expert the gated
  SiLU form.  What the experts that are not held would add is left out.

The reference is straightforward ``jax.numpy``: no kernel, no sort (the
chosen experts are those with fewer than ``k`` experts ranked above
them), routing as a dense mask over the held experts, attention as
masked scores.  So that it fits beside its own weights, velocity and
gradient at the cell's size it is computed in blocks, each under
``jax.checkpoint``: attention by kv group and query block, everything
row-wise (feed-forward, head) by blocks of positions.  It imports
nothing of the program.
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import reference
from benchmark.kernels.attention import kept_elements
from benchmark.models import plain_ops as P

#: no layer couples the records of a batch; one record is a step
BLOCK_ROWS = 1

#: positions a block of the reference's row-wise work and of its
#: attention's queries (a sequence shorter than this is one block)
_ROWS = 1024


# -- the configuration, read one way by the builder and the reference --------

def layers_of(conf: Dict) -> List[Dict]:
    """Per layer: attention kind, query heads, feed-forward kind; the
    first ``num_hidden_layers`` entries of the published per-layer lists."""
    n = conf["num_hidden_layers"]
    return [dict(attention="window" if kind == "sliding_attention" else "full",
                 heads=heads, ffn=ffn)
            for kind, heads, ffn in zip(
                conf["layer_types"][:n],
                conf["num_attention_heads_per_layer"][:n],
                conf["mlp_layer_types"][:n])]


def _rotary_conf(conf: Dict, kind: str) -> Dict:
    return conf["rope_parameters"][
        "sliding_attention" if kind == "window" else "full_attention"]


# -- the program --------------------------------------------------------------

def build(conf: Dict):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import models

    if not hasattr(models, "build_decoder_lm"):
        raise SystemExit("this program has no decoder builder "
                         "(models.build_decoder_lm): it cannot run the "
                         "laguna family")

    def rotary(kind):
        r = _rotary_conf(conf, kind)
        dims = int(conf["head_dim"] * r["partial_rotary_factor"])
        if r["rope_type"] == "default":
            return nn.Rotary(dims, theta=r["rope_theta"])
        return nn.Rotary(dims, theta=r["rope_theta"], factor=r["factor"],
                         original_max_position=r[
                             "original_max_position_embeddings"],
                         beta_fast=r["beta_fast"], beta_slow=r["beta_slow"],
                         attention_factor=r["attention_factor"])

    plan = models.DecoderPlan(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        head_dim=conf["head_dim"], kv_heads=conf["num_key_value_heads"],
        layers=[models.LayerPlan(**layer) for layer in layers_of(conf)],
        window=conf["sliding_window"], rotary_full=rotary("full"),
        rotary_window=rotary("window"),
        dense_width=conf["intermediate_size"],
        expert_width=conf["moe_intermediate_size"],
        shared_width=conf["shared_expert_intermediate_size"],
        n_experts=conf["num_experts_published"],
        top_k=conf["num_experts_per_tok"],
        held=tuple(conf["held_experts"]),
        routed_scale=conf["moe_routed_scaling_factor"],
        normalize=conf["norm_topk_prob"], gate="per_head",
        eps=conf["rms_norm_eps"])
    return models.build_decoder_lm(plan, remat=True)


def criterion():
    import bigdl_tpu.nn as nn

    return nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)


def make_records(seed: int, n: int, conf: Dict):
    return reference.make_token_records(
        seed, n, conf["sequence_length"], conf["vocab_size"], conf["zipf"])


# -- the parameters, in the program's order -----------------------------------

def _w(name, shape, fan_in):
    return dict(name=name, shape=tuple(shape), kind="weight", fan_in=fan_in)


def _scale(name, n):
    return dict(name=name, shape=(n,), kind="scale")


def _gated_specs(name, d, width):
    return [_w(name + ".gate", (width, d), d), _w(name + ".up", (width, d), d),
            _w(name + ".down", (d, width), width)]


#: the configuration ``param_specs`` last described, for ``loss_sum``
_LAST_CONF = None


def param_specs(conf: Dict) -> List[Dict]:
    global _LAST_CONF
    _LAST_CONF = conf
    d, dh, g = conf["hidden_size"], conf["head_dim"], \
        conf["num_key_value_heads"]
    held = conf["held_experts"][1]
    we = conf["moe_intermediate_size"]
    # an embedding row is a unit-variance vector (fan_in 1): the stream
    # the first norm sees is the token's, not rounding noise
    specs = [_w("embed", (conf["vocab_size"], d), 1)]
    for i, layer in enumerate(layers_of(conf)):
        b, h = f"layer{i}.", layer["heads"]
        specs += [_scale(b + "norm1", d),
                  _w(b + "q", (h * dh, d), d), _w(b + "k", (g * dh, d), d),
                  _w(b + "v", (g * dh, d), d), _w(b + "gate", (h, d), d),
                  _w(b + "o", (d, h * dh), h * dh), _scale(b + "norm2", d)]
        if layer["ffn"] == "dense":
            specs += _gated_specs(b + "mlp", d, conf["intermediate_size"])
        else:
            specs += [_w(b + "experts.gate", (held, d, we), d),
                      _w(b + "experts.up", (held, d, we), d),
                      _w(b + "experts.down", (held, we, d), we),
                      _w(b + "router", (conf["num_experts_published"], d), d)]
            specs += _gated_specs(b + "shared", d,
                                  conf["shared_expert_intermediate_size"])
    return specs + [_scale("norm_f", d),
                    _w("head", (conf["vocab_size"], d), d)]


# -- FLOPs ---------------------------------------------------------------------

def flops_per_record(conf: Dict) -> Dict[str, int]:
    """Forward + backward of one record, 2 FLOPs a multiply-add, backward
    twice the forward; recomputation, norms, rotary, softmax, gates and the
    update are not counted.  Matrix products by active parameters a token
    (a routed expert counts the assignments that land here in expectation,
    ``tokens * k * held / experts`` rows a layer); attention by the score
    elements the masks keep, exactly."""
    s, d, dh, g = conf["sequence_length"], conf["hidden_size"], \
        conf["head_dim"], conf["num_key_value_heads"]
    held = conf["held_experts"][1]
    share = conf["num_experts_per_tok"] * held / conf["num_experts_published"]
    gated = lambda width: 3 * d * width  # noqa: E731
    matmul_params, scores = 0.0, 0
    for layer in layers_of(conf):
        h = layer["heads"]
        matmul_params += d * (2 * h * dh + 2 * g * dh + h)
        if layer["ffn"] == "dense":
            matmul_params += gated(conf["intermediate_size"])
        else:
            matmul_params += d * conf["num_experts_published"] \
                + gated(conf["shared_expert_intermediate_size"]) \
                + share * gated(conf["moe_intermediate_size"])
        window = conf["sliding_window"] if layer["attention"] == "window" \
            else None
        scores += h * kept_elements(s, window)
    matmul_params += d * conf["vocab_size"]          # the head
    products = int(round(3 * 2 * matmul_params * s))
    attention = 3 * 2 * 2 * dh * scores              # q.k and p.v
    return {"matrix_products": products, "attention": attention,
            "total": products + attention}


# -- the reference --------------------------------------------------------------

def _mm(x, w, quant):
    """``x W^T`` with ``W`` of shape (out, in)."""
    return P.lower_out(jnp.dot(P.lower(x, quant), P.lower(w, quant).T,
                               precision=P.HIGHEST), quant)


def _rms_norm(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _by_row_blocks(fn, *arrays):
    """``fn`` over blocks of ``_ROWS`` leading rows, each recomputed in
    the backward pass; the results stacked back."""
    n = arrays[0].shape[0]
    rows = math.gcd(n, _ROWS)
    blocks = [a.reshape((n // rows, rows) + a.shape[1:]) for a in arrays]
    out = lax.map(jax.checkpoint(lambda args: fn(*args)), tuple(blocks))
    return jax.tree.map(lambda o: o.reshape((n,) + o.shape[2:]), out)


def rotary_tables(r: Dict, head_dim: int, positions: int):
    """``(cos, sin)`` of [positions, dims / 2] and ``dims``, evaluated
    from the published ``rope_parameters`` entry as written."""
    dims = int(head_dim * r["partial_rotary_factor"])
    i = np.arange(0, dims, 2, dtype=np.float64)
    inv = 1.0 / r["rope_theta"] ** (i / dims)
    scale = 1.0
    if r["rope_type"] == "yarn":
        def dim_of(rotations):
            return dims * math.log(r["original_max_position_embeddings"] / (
                rotations * 2 * math.pi)) / (2 * math.log(r["rope_theta"]))

        low = max(math.floor(dim_of(r["beta_fast"])), 0)
        high = min(math.ceil(dim_of(r["beta_slow"])), dims - 1)
        high = high + 0.001 if low == high else high
        ramp = np.clip((np.arange(dims // 2) - low) / (high - low), 0, 1)
        inv = inv / r["factor"] * ramp + inv * (1 - ramp)
        scale = r["attention_factor"]
    angle = np.arange(positions, dtype=np.float64)[:, None] * inv[None, :]
    return ((np.cos(angle) * scale).astype(np.float32),
            (np.sin(angle) * scale).astype(np.float32), dims)


def _rotate(x, tables):
    """x [S, heads, D]: pairs (i, i + dims/2) of the first ``dims``."""
    cos, sin, dims = tables
    cos, sin = cos[:, None, :], sin[:, None, :]
    a, b, rest = x[..., :dims // 2], x[..., dims // 2:dims], x[..., dims:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _attention(u, p, layer, conf, quant):
    wq, wk, wv, wg, wo = p
    s, dh, g = u.shape[0], conf["head_dim"], conf["num_key_value_heads"]
    h = layer["heads"]
    r = h // g
    window = conf["sliding_window"] if layer["attention"] == "window" \
        else None
    tables = rotary_tables(_rotary_conf(conf, layer["attention"]), dh, s)
    q = _rotate(_mm(u, wq, quant).reshape(s, h, dh), tables)
    k = _rotate(_mm(u, wk, quant).reshape(s, g, dh), tables)
    v = _mm(u, wv, quant).reshape(s, g, dh)
    bq = math.gcd(s, _ROWS)
    # [G, blocks, R, bq, D]: query head g * R + r reads kv head g
    qb = q.reshape(s // bq, bq, g, r, dh).transpose(2, 0, 3, 1, 4)
    k_pos = jnp.arange(s)[None, :]

    def group(args):
        qg, kg, vg = args

        def block(a):
            i, qi = a
            scores = P.lower_out(jnp.einsum(
                "rqd,kd->rqk", P.lower(qi, quant), P.lower(kg, quant),
                precision=P.HIGHEST), quant) / math.sqrt(dh)
            q_pos = i * bq + jnp.arange(bq)[:, None]
            keep = k_pos <= q_pos
            if window is not None:
                keep = keep & (q_pos - k_pos < window)
            scores = jnp.where(keep[None], scores, -jnp.inf)
            scores = scores - jnp.max(scores, axis=-1, keepdims=True)
            prob = jnp.exp(scores)
            prob = prob / jnp.sum(prob, axis=-1, keepdims=True)
            return P.lower_out(jnp.einsum(
                "rqk,kd->rqd", P.lower(prob, quant), P.lower(vg, quant),
                precision=P.HIGHEST), quant)

        return lax.map(jax.checkpoint(block), (jnp.arange(s // bq), qg))

    out = lax.map(group, (qb, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(1, 3, 0, 2, 4).reshape(s, h, dh)   # [S, G*R, D]
    gate = _sigmoid(_mm(u, wg, quant))                     # [S, H]
    return _mm((out * gate[:, :, None]).reshape(s, h * dh), wo, quant)


def _gated(u, w, quant):
    w_gate, w_up, w_down = w
    return _mm(_silu(_mm(u, w_gate, quant)) * _mm(u, w_up, quant),
               w_down, quant)


def _sparse(u, p, conf, quant):
    e_gate, e_up, e_down, w_r, shared = p[0], p[1], p[2], p[3], p[4:]
    first, held = conf["held_experts"]
    k = conf["num_experts_per_tok"]
    logits = _mm(u, w_r, quant)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    prob = jnp.exp(logits)
    prob = prob / jnp.sum(prob, axis=-1, keepdims=True)  # [S, E]
    idx = jnp.arange(prob.shape[-1])
    # experts ranked above e: a larger p, or the same p at a lower index
    above = (prob[:, None, :] > prob[:, :, None]) | (
        (prob[:, None, :] == prob[:, :, None])
        & (idx[None, None, :] < idx[None, :, None]))
    chosen = jnp.sum(above, axis=-1) < k                 # [S, E]
    weight = jnp.where(chosen, prob, 0.0)
    if conf["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    weight = conf["moe_routed_scaling_factor"] * weight
    y = _gated(u, shared, quant)
    for e in range(held):
        # [d, width] stacks hold W^T of the (out, in) form _mm takes
        out = _gated(u, (e_gate[e].T, e_up[e].T, e_down[e].T), quant)
        y = y + weight[:, first + e, None] * out
    return y


def _record_loss(params, conf, x, y, quant):
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    eps = conf["rms_norm_eps"]
    h = take(1)[0][x]                                    # [S, d]
    for layer in layers_of(conf):
        norm1, attn, norm2 = take(1)[0], take(5), take(1)[0]
        ffn = take(3 if layer["ffn"] == "dense" else 7)

        def block(h, norm1=norm1, attn=attn, norm2=norm2, ffn=ffn,
                  layer=layer):
            h = h + _attention(_rms_norm(h, norm1, eps), attn, layer, conf,
                               quant)
            if layer["ffn"] == "dense":
                fn = lambda u: _gated(u, ffn, quant)  # noqa: E731
            else:
                fn = lambda u: _sparse(u, ffn, conf, quant)  # noqa: E731
            return h + _by_row_blocks(fn, _rms_norm(h, norm2, eps))

        h = jax.checkpoint(block)(h)
    norm_f, head = take(2)

    def nll(hb, yb):
        logp = P.log_softmax(_mm(_rms_norm(hb, norm_f, eps), head, quant))
        return -jnp.take_along_axis(logp, yb[:, None], axis=1)[:, 0]

    return jnp.mean(_by_row_blocks(nll, h, y))


def loss_sum(params, x, y, quant=None, conf=None):
    """Sum over the records of each record's mean, over its positions, of
    the next token's negative log-likelihood.  The harness's call carries
    no configuration, and the parameters' shapes do not show the window,
    the rotary settings or which experts are held: ``conf`` defaults to
    the configuration this module was last asked to describe
    (``param_specs`` runs before every reference)."""
    conf = conf or _LAST_CONF
    if conf is None:
        raise ValueError("loss_sum before param_specs(conf): which "
                         "configuration?")
    return sum(_record_loss(params, conf, x[i], y[i], quant)
               for i in range(x.shape[0]))
