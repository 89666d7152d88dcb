"""Qwen3-Next-80B-A3B (``model_type`` ``qwen3_next``), one chip's share:
the program's builder and the plain float32 reference of the same
mathematics.

The layers, as both compute them (d hidden, u always the normed input,
no bias anywhere; what the published ``config.json`` leaves open is
listed under ``assumed`` in the configuration's file):

- ``Norm(x) = (1 + w) x / sqrt(mean(x^2) + eps)``, ``w`` starting at 0:
  every norm but the gated one of a linear layer.
- Block: ``h = x + Mixer(Norm_1(x))``, ``y = h + Experts(Norm_2(h))``;
  layer i is full attention when ``(i + 1) % full_attention_interval ==
  0`` and linear attention otherwise; every layer is sparse.  After the
  last block ``Norm_f``, ``logits = x W_head`` over the ids held,
  log-softmax, mean negative log-likelihood over the positions.
- Linear attention (Hk key heads, Hv value heads, sizes Dk, Dv): ``[q, k,
  v, z] = u W_qkvz``, ``[b, a] = u W_ba``; ``[q, k, v] <- silu(conv([q, k,
  v]))``, causal and depthwise over the sequence (``y_t = sum_i w_i x_{t
  - (K - 1) + i}``); q and k by head ``x / sqrt(sum x^2 + 1e-6)``, q then
  over ``sqrt(Dk)``; key head ``h // (Hv / Hk)`` serves value head h;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``.  The
  recurrence a value head, from ``S_0 = 0``:

      S_t = exp(g_t) S_{t-1} + k_t (beta_t (v_t - (exp(g_t) S_{t-1})^T k_t))^T
      o_t = S_t^T q_t

  then ``o <- o / sqrt(mean(o^2) + eps) * w_o * silu(z)`` a head and the
  output projection.
- Full attention: ``[q, gate] = u W_q`` (a head's D query channels, then
  its D gate channels), ``k, v = u W_k, u W_v`` over G kv heads; q and k
  normed a head (``Norm`` of D); rotate-half rotary on the first
  ``partial_rotary_factor * D`` dimensions; ``s_ij = q_i . k_j / sqrt(D)``
  for ``j <= i``; softmax; ``out = (softmax(s) v) * sigmoid(gate)``;
  ``W_o``.
- Experts: ``p = softmax(u W_r)`` over all the published experts; the
  ``num_experts_per_tok`` largest (ties to the lower index), renormalised
  to sum 1; ``y = sigmoid(u w_s) Shared(u) + sum over chosen e that are
  HELD here of p_e Expert_e(u)``, every expert the gated SiLU form.  What
  the experts that are not held would add is left out.

The reference is straightforward ``jax.numpy``: the recurrence is run
TOKEN BY TOKEN (a ``lax.scan`` over positions in which every product is
an elementwise multiply and a sum, checkpointed in segments so that its
backward keeps a state a segment and not a token; a layer's heads in
four groups, one after the other), attention is a masked
softmax in blocks, routing a dense mask over the held experts with no
sort.  It knows nothing of chunks, triangular systems or kernels, and
imports nothing of the program.  Under the control's ``quant`` every
projection, the convolution and the attention products are rounded; the
recurrence's own multiply-and-sums stay float32.
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import reference
from benchmark.kernels import delta_rule as delta_kernel
from benchmark.kernels.attention import kept_elements
from benchmark.models import plain_ops as P
from benchmark.models.laguna import (_gated, _gated_specs, _mm, _rotate,
                                     _scale, _sigmoid, _silu, _w,
                                     rotary_tables)

#: no layer couples the records of a batch; one record is a step
BLOCK_ROWS = 1

#: positions a block of the reference's row-wise work and of its
#: attention's queries; of its routing (which compares every pair of
#: router outputs); tokens a checkpointed segment of the recurrence;
#: groups a linear layer's heads are taken in
_ROWS, _ROUTING_ROWS, _SEGMENT, _HEAD_GROUPS = 1024, 256, 128, 4


def layers_of(conf: Dict) -> List[str]:
    """The mixer of each layer: ``"linear"`` or ``"full"``."""
    period = conf["full_attention_interval"]
    return ["full" if (i + 1) % period == 0 else "linear"
            for i in range(conf["num_hidden_layers"])]


def _rotary_conf(conf: Dict) -> Dict:
    return {"rope_type": "default", "rope_theta": conf["rope_theta"],
            "partial_rotary_factor": conf["partial_rotary_factor"]}


# -- the program --------------------------------------------------------------

def build(conf: Dict):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import models

    if not hasattr(nn, "GatedDeltaNet"):
        raise SystemExit("this program has no linear-attention layer "
                         "(nn.GatedDeltaNet): it cannot run the "
                         "qwen3_next family")
    from bigdl_tpu.ops import delta_rule as program_rule

    if conf["delta_chunk"] != program_rule.CHUNK:
        raise SystemExit(f"the configuration counts the rule's FLOPs at "
                         f"chunks of {conf['delta_chunk']}, the program "
                         f"runs chunks of {program_rule.CHUNK}")
    heads = {"full": conf["num_attention_heads"],
             "linear": conf["linear_num_value_heads"]}
    plan = models.DecoderPlan(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        head_dim=conf["head_dim"], kv_heads=conf["num_key_value_heads"],
        layers=[models.LayerPlan(kind, heads[kind], "sparse")
                for kind in layers_of(conf)],
        window=0, rotary_window=None,
        rotary_full=nn.Rotary(
            int(conf["head_dim"] * conf["partial_rotary_factor"]),
            theta=conf["rope_theta"]),
        dense_width=conf["intermediate_size"],
        expert_width=conf["moe_intermediate_size"],
        shared_width=conf["shared_expert_intermediate_size"],
        n_experts=conf["num_experts_published"],
        top_k=conf["num_experts_per_tok"],
        held=tuple(conf["held_experts"]), routed_scale=1.0,
        normalize=conf["norm_topk_prob"], gate="per_channel",
        eps=conf["rms_norm_eps"], qk_norm=True, zero_centred_norm=True,
        shared_gate=True,
        linear_key_heads=conf["linear_num_key_heads"],
        linear_key_dim=conf["linear_key_head_dim"],
        linear_value_dim=conf["linear_value_head_dim"],
        linear_conv=conf["linear_conv_kernel_dim"])
    return models.build_decoder_lm(plan, remat=True)


def criterion():
    import bigdl_tpu.nn as nn

    return nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)


def make_records(seed: int, n: int, conf: Dict):
    return reference.make_token_records(
        seed, n, conf["sequence_length"], conf["vocab_size"], conf["zipf"])


# -- the parameters, in the program's order -----------------------------------

def _zero(name, n):
    """A parameter that starts at 0 in a trained model (a zero-centred
    norm's ``w``, ``A_log``): drawn small around 0."""
    return dict(name=name, shape=(n,), kind="bias")


#: standard deviation of a linear layer's seeded ``dt_bias``
DT_BIAS_STD = 6.0


def _dt_bias(name, n, conf):
    """``dt_bias``, a value head: normal around 0 with a deviation of
    ``DT_BIAS_STD`` (a weight whose ``fan_in`` says so: the harness draws
    ``sqrt(init_gain / fan_in) z`` and knows no other spread).  With ``a``
    about unit normal a head's decay ``exp(-softplus(a + dt_bias))`` then
    lies anywhere between 0 and 1: about three heads in ten keep more
    than 0.95 of their state a token and two in ten more than 0.99, so
    that most of what they hold came in through earlier chunks, as in a
    trained model (dt in [1e-3, 0.1]); the heads drawn far above 0 forget
    at once.  Drawn near 0 every head forgets at about 0.5 a token and
    the state entering a chunk is gone after its first few tokens."""
    return dict(name=name, shape=(n,), kind="weight",
                fan_in=conf["init_gain"] / DT_BIAS_STD ** 2)


#: the configuration ``param_specs`` last described, for ``loss_sum``
_LAST_CONF = None


def _linear_sizes(conf: Dict):
    return (conf["linear_num_key_heads"], conf["linear_num_value_heads"],
            conf["linear_key_head_dim"], conf["linear_value_head_dim"])


def param_specs(conf: Dict) -> List[Dict]:
    global _LAST_CONF
    _LAST_CONF = conf
    d, dh = conf["hidden_size"], conf["head_dim"]
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    hk, hv, dk, dv = _linear_sizes(conf)
    keys, values = hk * dk, hv * dv
    held, we = conf["held_experts"][1], conf["moe_intermediate_size"]
    taps = conf["linear_conv_kernel_dim"]
    specs = [_w("embed", (conf["vocab_size"], d), 1)]
    for i, kind in enumerate(layers_of(conf)):
        b = f"layer{i}."
        specs.append(_zero(b + "norm1", d))
        if kind == "linear":
            specs += [_w(b + "conv", (2 * keys + values, taps), taps),
                      _zero(b + "A_log", hv), _dt_bias(b + "dt_bias", hv, conf),
                      _w(b + "qkvz", (2 * keys + 2 * values, d), d),
                      _w(b + "ba", (2 * hv, d), d),
                      _scale(b + "head_norm", dv),
                      _w(b + "o", (d, values), values)]
        else:
            specs += [_w(b + "q", (h * 2 * dh, d), d),
                      _w(b + "k", (g * dh, d), d), _w(b + "v", (g * dh, d), d),
                      _zero(b + "q_norm", dh), _zero(b + "k_norm", dh),
                      _w(b + "o", (d, h * dh), h * dh)]
        specs += [_zero(b + "norm2", d),
                  _w(b + "experts.gate", (held, d, we), d),
                  _w(b + "experts.up", (held, d, we), d),
                  _w(b + "experts.down", (held, we, d), we),
                  _w(b + "router", (conf["num_experts_published"], d), d)]
        specs += _gated_specs(b + "shared", d,
                              conf["shared_expert_intermediate_size"])
        specs.append(_w(b + "shared_gate", (1, d), d))
    return specs + [_zero("norm_f", d),
                    _w("head", (conf["vocab_size"], d), d)]


# -- FLOPs ---------------------------------------------------------------------

def delta_shape(conf: Dict) -> Dict:
    """One linear layer's call of the rule, as ``kernels/delta_rule.py``
    counts it."""
    _, hv, dk, dv = _linear_sizes(conf)
    return dict(heads=hv, seq=conf["sequence_length"], key_dim=dk,
                value_dim=dv, chunk=conf["delta_chunk"])


def flops_per_record(conf: Dict) -> Dict[str, int]:
    """Forward + backward of one record, 2 FLOPs a multiply-add, backward
    twice the forward; recomputation, norms, rotary, softmax, gates, the
    short convolution's activation and the update are not counted.
    Matrix products by active parameters a token (a routed expert counts
    the assignments that land here in expectation, ``tokens * k * held /
    experts`` rows a layer; the convolution's taps are parameters a token
    too); attention by the score elements the causal mask keeps, exactly;
    the delta rule by the products of its chunked form at the
    configuration's chunk (``kernels/delta_rule.py``)."""
    s, d, dh = conf["sequence_length"], conf["hidden_size"], conf["head_dim"]
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    hk, hv, dk, dv = _linear_sizes(conf)
    keys, values = hk * dk, hv * dv
    share = conf["num_experts_per_tok"] * conf["held_experts"][1] \
        / conf["num_experts_published"]
    gated = lambda width: 3 * d * width  # noqa: E731
    sparse = d * conf["num_experts_published"] + d \
        + gated(conf["shared_expert_intermediate_size"]) \
        + share * gated(conf["moe_intermediate_size"])
    params, scores, rule, taps = 0.0, 0, 0, 0
    for kind in layers_of(conf):
        if kind == "linear":
            params += d * (2 * keys + 2 * values + 2 * hv) + values * d
            taps += (2 * keys + values) * conf["linear_conv_kernel_dim"]
            rule += 3 * delta_kernel.flops("fwd", **delta_shape(conf))
        else:
            params += d * (2 * h * dh + 2 * g * dh) + h * dh * d
            scores += h * kept_elements(s)
        params += sparse
    params += d * conf["vocab_size"]                 # the head
    products = int(round(3 * 2 * params * s))
    attention = 3 * 2 * 2 * dh * scores              # q.k and p.v
    conv = 3 * 2 * taps * s
    return {"matrix_products": products, "attention": attention,
            "delta_rule": rule, "convolution": conv,
            "total": products + attention + rule + conv}


# -- the reference --------------------------------------------------------------

def _norm(x, w, eps):
    return (1.0 + w) * x / jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _by_row_blocks(fn, *arrays, rows=_ROWS):
    """``fn`` over blocks of ``rows`` leading rows, each recomputed in the
    backward pass; the results stacked back."""
    n = arrays[0].shape[0]
    rows = math.gcd(n, rows)
    blocks = [a.reshape((n // rows, rows) + a.shape[1:]) for a in arrays]
    out = lax.map(jax.checkpoint(lambda args: fn(*args)), tuple(blocks))
    return jax.tree.map(lambda o: o.reshape((n,) + o.shape[2:]), out)


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token.  q, k [S, H, Dk], v [S, H, Dv], g,
    beta [S, H]; o [S, H, Dv]."""
    s, h, dk = q.shape
    seg = math.gcd(s, _SEGMENT)

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[:, None, None]
        seen = jnp.sum(state * kt[:, :, None], axis=1)       # S^T k
        u = bt[:, None] * (vt - seen)
        state = state + kt[:, :, None] * u[:, None, :]       # + k u^T
        return state, jnp.sum(state * qt[:, :, None], axis=1)

    def segment(state, xs):
        return lax.scan(token, state, xs)

    xs = tuple(a.reshape((s // seg, seg) + a.shape[1:])
               for a in (q, k, v, g, beta))
    _, out = lax.scan(jax.checkpoint(segment),
                      jnp.zeros((h, dk, v.shape[-1]), jnp.float32), xs)
    return out.reshape(s, h, v.shape[-1])


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _conv_silu(x, w, quant):
    """x [S, C], w [C, taps]: the causal depthwise convolution, then SiLU."""
    s, taps = x.shape[0], w.shape[1]
    padded = jnp.pad(P.lower(x, quant), ((taps - 1, 0), (0, 0)))
    w = P.lower(w, quant)
    return _silu(P.lower_out(
        sum(padded[i:i + s] * w[:, i] for i in range(taps)), quant))


def _linear_attention(u, p, conf, quant):
    """A layer's heads do not meet between the input projection and the
    output projection, so they are taken ``_HEAD_GROUPS`` groups one after
    the other (a group: some key heads and the value heads they serve),
    each recomputed in the backward pass: float32 copies of a whole
    layer's q, k, v, o and their cotangents at once would not leave room
    for the update rule's state beside them."""
    w_conv, a_log, dt_bias, w_qkvz, w_ba, w_head, w_o = p
    s = u.shape[0]
    hk, hv, dk, dv = _linear_sizes(conf)
    keys, values = hk * dk, hv * dv
    n = math.gcd(hk, _HEAD_GROUPS)
    r = hv // hk                                  # value heads a key head
    qkvz = _by_row_blocks(lambda ub: _mm(ub, w_qkvz, quant), u)
    ba = _mm(u, w_ba, quant)

    def by_group(x, heads, dim):
        """[..., heads * dim] columns (or conv rows) -> [n, ..., cols]."""
        lead = x.shape[:-1]
        x = x.reshape(lead + (n, heads // n * dim))
        return jnp.moveaxis(x, -2, 0)

    def conv_rows(lo, heads, dim):
        w = w_conv[lo:lo + heads * dim]
        return w.reshape(n, heads // n * dim, w.shape[1])

    cols = (qkvz[:, :keys], qkvz[:, keys:2 * keys],
            qkvz[:, 2 * keys:2 * keys + values], qkvz[:, 2 * keys + values:])
    args = (by_group(cols[0], hk, dk), by_group(cols[1], hk, dk),
            by_group(cols[2], hv, dv), by_group(cols[3], hv, dv),
            conv_rows(0, hk, dk), conv_rows(keys, hk, dk),
            conv_rows(2 * keys, hv, dv),
            by_group(ba[:, :hv], hv, 1), by_group(ba[:, hv:], hv, 1),
            a_log.reshape(n, hv // n), dt_bias.reshape(n, hv // n))

    def group(a):
        q, k, v, z, wq, wk, wv, b, a_, a_log, dt_bias = a
        q = _unit(_conv_silu(q, wq, quant).reshape(s, hk // n, dk)) \
            / math.sqrt(dk)
        k = _unit(_conv_silu(k, wk, quant).reshape(s, hk // n, dk))
        v = _conv_silu(v, wv, quant).reshape(s, hv // n, dv)
        q, k = (jnp.repeat(x, r, axis=1) for x in (q, k))
        g = -jnp.exp(a_log) * _softplus(a_ + dt_bias)
        o = delta_rule(q, k, v, g, _sigmoid(b))
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                         + conf["rms_norm_eps"])
        return (o * w_head * _silu(z.reshape(s, hv // n, dv))).reshape(
            s, hv // n * dv)

    o = lax.map(jax.checkpoint(group), args)             # [n, S, cols]
    return _by_row_blocks(lambda ob: _mm(ob, w_o, quant),
                          jnp.moveaxis(o, 0, 1).reshape(s, values))


def _causal_softmax(q, k, v, quant):
    """q [S, H, D] over k, v [S, G, D], query head h on kv head ``h // (H
    / G)``: masked scores by kv group and query block, each block
    recomputed in the backward pass."""
    s, h, dh = q.shape
    g = k.shape[1]
    r = h // g
    bq = math.gcd(s, _ROWS)
    qb = q.reshape(s // bq, bq, g, r, dh).transpose(2, 0, 3, 1, 4)
    k_pos = jnp.arange(s)[None, :]

    def group(args):
        qg, kg, vg = args

        def block(a):
            i, qi = a
            scores = P.lower_out(jnp.einsum(
                "rqd,kd->rqk", P.lower(qi, quant), P.lower(kg, quant),
                precision=P.HIGHEST), quant) / math.sqrt(dh)
            keep = k_pos <= i * bq + jnp.arange(bq)[:, None]
            scores = jnp.where(keep[None], scores, -jnp.inf)
            scores = scores - jnp.max(scores, axis=-1, keepdims=True)
            prob = jnp.exp(scores)
            prob = prob / jnp.sum(prob, axis=-1, keepdims=True)
            return P.lower_out(jnp.einsum(
                "rqk,kd->rqd", P.lower(prob, quant), P.lower(vg, quant),
                precision=P.HIGHEST), quant)

        return lax.map(jax.checkpoint(block), (jnp.arange(s // bq), qg))

    out = lax.map(group, (qb, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 3, 0, 2, 4).reshape(s, h, dh)


def _full_attention(u, p, conf, quant):
    wq, wk, wv, q_norm, k_norm, wo = p
    s, dh = u.shape[0], conf["head_dim"]
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    eps = conf["rms_norm_eps"]
    tables = rotary_tables(_rotary_conf(conf), dh, s)
    q_gate = _mm(u, wq, quant).reshape(s, h, 2 * dh)
    q = _rotate(_norm(q_gate[..., :dh], q_norm, eps), tables)
    k = _rotate(_norm(_mm(u, wk, quant).reshape(s, g, dh), k_norm, eps),
                tables)
    v = _mm(u, wv, quant).reshape(s, g, dh)
    out = _causal_softmax(q, k, v, quant) * _sigmoid(q_gate[..., dh:])
    return _mm(out.reshape(s, h * dh), wo, quant)


def _sparse(u, p, conf, quant):
    e_gate, e_up, e_down, w_r, shared, w_sg = p[0], p[1], p[2], p[3], \
        p[4:7], p[7]
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
    y = _sigmoid(_mm(u, w_sg, quant)) * _gated(u, shared, quant)
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
    for kind in layers_of(conf):
        norm1 = take(1)[0]
        mixer = take(7 if kind == "linear" else 6)
        norm2, ffn = take(1)[0], take(8)

        def block(h, norm1=norm1, mixer=mixer, norm2=norm2, ffn=ffn,
                  kind=kind):
            mix = _linear_attention if kind == "linear" else _full_attention
            h = h + mix(_norm(h, norm1, eps), mixer, conf, quant)
            return h + _by_row_blocks(
                lambda u: _sparse(u, ffn, conf, quant),
                _norm(h, norm2, eps), rows=_ROUTING_ROWS)

        h = jax.checkpoint(block)(h)
    norm_f, head = take(2)

    def nll(hb, yb):
        logp = P.log_softmax(_mm(_norm(hb, norm_f, eps), head, quant))
        return -jnp.take_along_axis(logp, yb[:, None], axis=1)[:, 0]

    return jnp.mean(_by_row_blocks(nll, h, y))


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
