"""NVIDIA-Nemotron-3-Super-120B-A12B (``model_type`` ``nemotron_h``), one
chip's share: the program's builder and the plain float32 reference of
the same mathematics.

The layers, as both compute them (d hidden, u always the normed input,
no bias but the convolution's; what the published ``config.json`` leaves
open is listed under ``assumed`` in the configuration's file):

- ``Norm(x) = w x / sqrt(mean(x^2) + eps)``, ``w`` starting at 1.
- Layer i is ONE sub-layer, by the i-th letter of
  ``hybrid_override_pattern``: ``x <- x + f_i(Norm_i(x))``, one norm, one
  residual add.  The cut runs the published layers ``first_layer ..
  first_layer + num_hidden_layers - 1``.  After the last layer ``Norm_f``,
  ``logits = x W_head`` over the ids held, log-softmax, mean negative
  log-likelihood over the positions.  No positional term anywhere.
- ``M``, the Mamba-2 mixer (H heads of P, G groups of H / G heads, state
  N): ``[z, xBC, dt] = split(u W_in, [H P, H P + 2 G N, H])``; ``xBC =
  silu(conv(xBC) + b_conv)``, ``conv`` causal and depthwise over the
  sequence (``y_t = sum_i w_i x_{t - (K - 1) + i}``, zeros before
  position 0); ``[x, B, C] = split(xBC, [H P, G N, G N])``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head.  The recurrence a
  head, from ``S_0 = 0`` (``B``, ``C`` of the head's group ``h // (H /
  G)``):

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
      y_t = S_t C_t + D x_t

  then ``y <- w * GroupNorm(y * silu(z))``: the gate FIRST, then an RMS
  norm over each group's ``H P / G`` channels on its own; ``out = y
  W_out``.
- ``*``, attention: ``q = u W_q`` over H heads of D, ``k, v`` over G kv
  heads; ``s_ij = q_i . k_j / sqrt(D)`` for ``j <= i``; softmax; ``W_o``.
  No rotary, no gate, no q/k norm.
- ``E``, the latent expert layer: ``s = sigmoid(u W_r)`` over all the
  published experts; the ``num_experts_per_tok`` with the largest ``s +
  b`` (``b`` one number an expert; ties to the lower index); ``w_e =
  scale * s_e / (sum of the chosen s + 1e-6)``; ``l = u W_in^lat``; ``r =
  sum over chosen e that are HELD here of w_e (relu(l U_e)^2) V_e`` (no
  gate matrix); result ``r W_out^lat + (relu(u U_s)^2) V_s``, the shared
  expert on the model's own width.  What the experts that are not held
  would add is left out.

The reference is straightforward ``jax.numpy``: the recurrence is run
TOKEN BY TOKEN (a ``lax.scan`` over positions in which every product is
an elementwise multiply and a sum, checkpointed in segments so that its
backward keeps a state a segment and not a token), the convolution is a
sum of shifted copies, attention a masked softmax in blocks, routing a
dense mask over the held experts with no sort and no top-k.  It knows
nothing of chunks, decay matrices, capacities or kernels, and imports
nothing of the program.  Under the control's ``quant`` every projection,
the convolution and the attention products are rounded; the recurrence's
own multiply-and-sums stay float32.
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import reference
from benchmark.kernels import ssd as ssd_kernel
from benchmark.kernels.attention import kept_elements
from benchmark.models import plain_ops as P
from benchmark.models.laguna import _mm, _rms_norm, _scale, _silu, _w
from benchmark.models.lfm2 import route
from benchmark.models.qwen3_next import (_by_row_blocks, _causal_softmax,
                                         _softplus, _zero)

#: no layer couples the records of a batch; one record is a step
BLOCK_ROWS = 1

#: positions a block of the reference's row-wise work; of its routing
#: (which compares every pair of router outputs); tokens a checkpointed
#: segment of the recurrence
_ROWS, _ROUTING_ROWS, _SEGMENT = 1024, 256, 128

#: the sub-layer each letter of ``hybrid_override_pattern`` names
KINDS = {"M": "ssm", "*": "full", "E": "sparse"}

#: standard deviation of a mixer's seeded ``dt_bias``
DT_BIAS_STD = 6.0


def layers_of(conf: Dict) -> List[str]:
    """The one sub-layer of each layer of the cut: ``"ssm"``, ``"full"``
    or ``"sparse"``, from the published pattern and where the cut
    starts."""
    first, n = conf["first_layer"], conf["num_hidden_layers"]
    return [KINDS[c]
            for c in conf["hybrid_override_pattern"][first:first + n]]


def ssm_sizes(conf: Dict):
    """``(heads, head_dim, groups, state)`` of a mixer, as held here."""
    return (conf["mamba_num_heads"], conf["mamba_head_dim"],
            conf["n_groups"], conf["ssm_state_size"])


# -- the program --------------------------------------------------------------

def build(conf: Dict):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import models

    if not hasattr(nn, "Mamba2Mixer"):
        raise SystemExit("this program has no state-space mixer "
                         "(nn.Mamba2Mixer): it cannot run the nemotron_h "
                         "family")
    from bigdl_tpu.ops import ssd as program_scan

    if conf["chunk_size"] != program_scan.CHUNK:
        raise SystemExit(f"the configuration counts the scan's FLOPs at "
                         f"chunks of {conf['chunk_size']}, the program "
                         f"runs chunks of {program_scan.CHUNK}")
    heads, p, groups, state = ssm_sizes(conf)
    plans = {"ssm": models.LayerPlan("ssm", heads, "none"),
             "full": models.LayerPlan("full", conf["num_attention_heads"],
                                      "none"),
             "sparse": models.LayerPlan("none", 0, "sparse")}
    plan = models.DecoderPlan(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        head_dim=conf["head_dim"], kv_heads=conf["num_key_value_heads"],
        layers=[plans[kind] for kind in layers_of(conf)],
        window=0, rotary_full=None, rotary_window=None, dense_width=0,
        expert_width=conf["moe_intermediate_size"],
        shared_width=conf["moe_shared_expert_intermediate_size"],
        n_experts=conf["n_routed_experts_published"],
        top_k=conf["num_experts_per_tok"],
        held=tuple(conf["held_experts"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        normalize=conf["norm_topk_prob"], gate=None, eps=conf["norm_eps"],
        router_score="sigmoid", router_bias=True,
        ssm_head_dim=p, ssm_state=state, ssm_groups=groups,
        ssm_conv=conf["conv_kernel"],
        expert_latent=conf["moe_latent_size"],
        expert_activation=conf["mlp_hidden_act"])
    return models.build_decoder_lm(plan, remat=True)


def criterion():
    import bigdl_tpu.nn as nn

    return nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)


def make_records(seed: int, n: int, conf: Dict):
    return reference.make_token_records(
        seed, n, conf["sequence_length"], conf["vocab_size"], conf["zipf"])


# -- the parameters, in the program's order -----------------------------------

def _dt_bias(name, n, conf):
    """``dt_bias``, a head: normal around 0 with a deviation of
    ``DT_BIAS_STD`` (a weight whose ``fan_in`` says so: the harness draws
    ``sqrt(init_gain / fan_in) z`` and knows no other spread), as
    Qwen3-Next's is and as ISSUE 40 names it, while ``A_log`` is drawn
    near 0 (``A`` about -1).  ``dt = softplus(dt + dt_bias)`` then lies
    anywhere between 1e-5 and 20 and a head's decay a token ``exp(-dt)``
    anywhere between 0 and 1: about three heads in ten keep more than 0.95
    of their state a token, so that most of what they hold came in through
    earlier chunks, as in a trained model (``time_step_min`` 0.001 ..
    ``time_step_max`` 0.1 at ``A`` of 1 to 16); about three in ten forget
    at once.  Drawn near 0 every head forgets at about 0.5 a token and the
    state entering a chunk is gone after its first few tokens.

    The spread is ``dt``'s and not ``A``'s because ``dt`` also multiplies
    the head's input: a head that remembers long takes little of each
    token and its state stays of one input's size, the layer's design
    (``ssm/state_norm_max`` 280-760 on the chip).  The other way round
    (``A_log`` 3 z at ``dt`` about 0.8, which this family drew first) the
    long-memory heads' states grow to 3e3-7e4 and the model DIVERGES
    under the cell's SGD 0.01 with momentum 0.9: in float32 on the CPU at
    the real widths the plain reference reached NaN at step 29 and the
    program 18.5, and on the chip the loss read 11-24 after 90 steps on 9
    of 9 seeds at ``init_gain`` 1 and 0.25, where this draw reads 1.9-2.3
    (PERF.md section 6).

    What this draw costs: a zero-mean normal also puts a third of the
    heads at ``dt`` above 3, which a trained model never has; they take
    over their group's norm, and the first gradient's leaves then move by
    0.03-0.24 in bfloat16, as far as they do in the int8 control
    (0.15-0.24).  The cell's gradient limits therefore only tell a
    gradient from none, and the losses of steps 2 and 3 are what refuses a
    lower precision (the workload file's ``limits_set_from``).  A draw
    with ``dt`` small on every head needs a generator with a mean
    (``benchmark/reference.py``, a ``benchmark`` PR's edit)."""
    return dict(name=name, shape=(n,), kind="weight",
                fan_in=conf["init_gain"] / DT_BIAS_STD ** 2)


#: the configuration ``param_specs`` last described, for ``loss_sum``
_LAST_CONF = None

#: parameters of each kind of sub-layer, its norm not counted
_LEAVES = {"ssm": 8, "full": 4, "sparse": 8}


def param_specs(conf: Dict) -> List[Dict]:
    global _LAST_CONF
    _LAST_CONF = conf
    if not (conf["use_conv_bias"] and conf["mlp_hidden_act"] == "relu2"
            and conf["n_group"] == conf["topk_group"] == 1):
        raise SystemExit("the nemotron_h family's reference has a biased "
                         "convolution, squared-ReLU experts and one routing "
                         "group; the configuration says otherwise")
    d, dh = conf["hidden_size"], conf["head_dim"]
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    heads, p, groups, state = ssm_sizes(conf)
    inner, bc = heads * p, groups * state
    held, we = conf["held_experts"][1], conf["moe_intermediate_size"]
    n, latent = conf["n_routed_experts_published"], conf["moe_latent_size"]
    ws, taps = conf["moe_shared_expert_intermediate_size"], \
        conf["conv_kernel"]
    specs = [_w("embed", (conf["vocab_size"], d), 1)]
    for i, kind in enumerate(layers_of(conf)):
        b = f"layer{i}."
        specs.append(_scale(b + "norm", d))
        if kind == "ssm":
            specs += [_w(b + "conv", (inner + 2 * bc, taps), taps),
                      dict(name=b + "conv_bias", shape=(inner + 2 * bc,),
                           kind="bias"),
                      _zero(b + "A_log", heads),
                      _scale(b + "D", heads),
                      _dt_bias(b + "dt_bias", heads, conf),
                      _w(b + "in", (2 * inner + 2 * bc + heads, d), d),
                      _scale(b + "gated_norm", inner),
                      _w(b + "out", (d, inner), inner)]
        elif kind == "full":
            specs += [_w(b + "q", (h * dh, d), d),
                      _w(b + "k", (g * dh, d), d), _w(b + "v", (g * dh, d), d),
                      _w(b + "o", (d, h * dh), h * dh)]
        else:
            specs += [_w(b + "experts.up", (held, latent, we), latent),
                      _w(b + "experts.down", (held, we, latent), we),
                      dict(name=b + "expert_bias", shape=(n,), kind="bias"),
                      _w(b + "router", (n, d), d),
                      _w(b + "latent_in", (latent, d), d),
                      _w(b + "latent_out", (d, latent), latent),
                      _w(b + "shared.up", (ws, d), d),
                      _w(b + "shared.down", (d, ws), ws)]
    return specs + [_scale("norm_f", d),
                    _w("head", (conf["vocab_size"], d), d)]


# -- FLOPs ---------------------------------------------------------------------

def ssd_shape(conf: Dict) -> Dict:
    """One mixer's call of the scan, as ``kernels/ssd.py`` counts it."""
    heads, p, groups, state = ssm_sizes(conf)
    return dict(heads=heads, groups=groups, seq=conf["sequence_length"],
                head_dim=p, state=state, chunk=conf["chunk_size"])


def flops_per_record(conf: Dict) -> Dict[str, int]:
    """Forward + backward of one record, 2 FLOPs a multiply-add, backward
    twice the forward; recomputation, norms, softmax, gates, activations
    and the update are not counted.  Matrix products by active parameters
    a token (a routed expert counts the assignments that land here in
    expectation, ``tokens * k * held / experts`` rows a layer; the
    convolution's taps are parameters a token too); attention by the score
    elements the causal mask keeps, exactly; the state-space scan by the
    products of its chunked form at the configuration's chunk
    (``kernels/ssd.py``)."""
    s, d, dh = conf["sequence_length"], conf["hidden_size"], conf["head_dim"]
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    heads, p, groups, state = ssm_sizes(conf)
    inner, bc = heads * p, groups * state
    latent = conf["moe_latent_size"]
    share = conf["num_experts_per_tok"] * conf["held_experts"][1] \
        / conf["n_routed_experts_published"]
    sparse = d * conf["n_routed_experts_published"] + 2 * d * latent \
        + 2 * d * conf["moe_shared_expert_intermediate_size"] \
        + share * 2 * latent * conf["moe_intermediate_size"]
    parts = {"ssm": 0.0, "full": 0.0, "sparse": 0.0}
    scores, scan, taps = 0, 0, 0
    for kind in layers_of(conf):
        if kind == "ssm":
            parts[kind] += d * (2 * inner + 2 * bc + heads) + inner * d
            taps += (inner + 2 * bc) * conf["conv_kernel"]
            scan += 3 * ssd_kernel.flops("fwd", **ssd_shape(conf))
        elif kind == "full":
            parts[kind] += d * (h * dh + 2 * g * dh) + h * dh * d
            scores += h * kept_elements(s)
        else:
            parts[kind] += sparse
    per_token = lambda n: int(round(3 * 2 * n * s))  # noqa: E731
    products = per_token(sum(parts.values()) + d * conf["vocab_size"])
    attention = 3 * 2 * 2 * dh * scores              # q.k and p.v
    conv = 3 * 2 * taps * s
    return {"matrix_products": products,
            "of_which_ssm_projections": per_token(parts["ssm"]),
            "of_which_expert_layers": per_token(parts["sparse"]),
            "attention": attention, "ssd": scan, "convolution": conv,
            "total": products + attention + scan + conv}


# -- the reference --------------------------------------------------------------

def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def ssm_scan(x, dt, a, b, c):
    """The recurrence token by token.  x [S, H, P], dt [S, H] (after its
    softplus), a [H] (negative), b, c [S, G, N]; y [S, H, P] without the
    skip.  Head h reads group ``h // (H / G)``."""
    s, h, p = x.shape
    g, n = b.shape[1:]
    seg = math.gcd(s, _SEGMENT)

    def token(state, inp):
        xt, dtt, bt, ct = inp
        bt, ct = (jnp.repeat(v, h // g, axis=0) for v in (bt, ct))  # [H, N]
        state = state * jnp.exp(dtt * a)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return state, jnp.sum(state * ct[:, None, :], axis=-1)

    def segment(state, xs):
        return lax.scan(token, state, xs)

    xs = tuple(v.reshape((s // seg, seg) + v.shape[1:])
               for v in (x, dt, b, c))
    _, y = lax.scan(jax.checkpoint(segment),
                    jnp.zeros((h, p, n), jnp.float32), xs)
    return y.reshape(s, h, p)


def _conv_silu(x, w, bias, quant):
    """x [S, C], w [C, taps], bias [C]: the causal depthwise convolution,
    its bias, then SiLU."""
    s, taps = x.shape[0], w.shape[1]
    padded = jnp.pad(P.lower(x, quant), ((taps - 1, 0), (0, 0)))
    w = P.lower(w, quant)
    return _silu(P.lower_out(
        sum(padded[i:i + s] * w[:, i] for i in range(taps)), quant) + bias)


def mamba_mixer(u, p, conf, quant=None):
    """u [S, d] -> [S, d]; the counts are ``ssm_sizes(conf)`` (a share of
    the heads is the same layer at its own counts)."""
    w_conv, b_conv, a_log, d_skip, dt_bias, w_in, w_norm, w_out = p
    s = u.shape[0]
    heads, hp, groups, state = ssm_sizes(conf)
    inner, bc = heads * hp, groups * state
    proj = _by_row_blocks(lambda ub: _mm(ub, w_in, quant), u, rows=_ROWS)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * bc],
                  proj[:, 2 * inner + 2 * bc:])
    xbc = _conv_silu(xbc, w_conv, b_conv, quant)
    x = xbc[:, :inner].reshape(s, heads, hp)
    b = xbc[:, inner:inner + bc].reshape(s, groups, state)
    c = xbc[:, inner + bc:].reshape(s, groups, state)
    y = ssm_scan(x, _softplus(dt + dt_bias), -jnp.exp(a_log), b, c) \
        + d_skip[:, None] * x
    # the gate first, then each group's channels normed on their own
    y = (y.reshape(s, inner) * _silu(z)).reshape(s, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                     + conf["norm_eps"])
    return _by_row_blocks(lambda yb: _mm(yb, w_out, quant),
                          y.reshape(s, inner) * w_norm, rows=_ROWS)


def attention(u, p, conf, quant=None):
    wq, wk, wv, wo = p
    s, dh = u.shape[0], conf["head_dim"]
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    q = _mm(u, wq, quant).reshape(s, h, dh)
    k = _mm(u, wk, quant).reshape(s, g, dh)
    v = _mm(u, wv, quant).reshape(s, g, dh)
    out = _causal_softmax(q, k, v, quant)
    return _mm(out.reshape(s, h * dh), wo, quant)


def shared_expert(u, p, quant=None):
    up, down = p
    return _mm(_relu2(_mm(u, up, quant)), down, quant)


def routed_latent(u, p, conf, quant=None, held=None):
    """The routed part alone, back on the model's width: the experts
    ``held = (first, count)`` has parameters for (default: the
    configuration's), each on the latent rows."""
    e_up, e_down, bias, w_r, w_lin, w_lout = p
    first, count = held or conf["held_experts"]
    weight = route(u, w_r, bias, conf, quant)
    latent = _mm(u, w_lin, quant)

    def add_expert(y, e):
        # [latent, width] stacks hold W^T of the (out, in) form _mm takes
        up, down, w_e = e
        out = _mm(_relu2(_mm(latent, up.T, quant)), down.T, quant)
        return y + w_e[:, None] * out, None

    r, _ = lax.scan(jax.checkpoint(add_expert), jnp.zeros_like(latent),
                    (e_up, e_down, weight[:, first:first + count].T))
    return _mm(r, w_lout, quant)


def latent_experts(u, p, conf, quant=None, held=None):
    return routed_latent(u, p[:6], conf, quant, held) \
        + shared_expert(u, p[6:], quant)


def _record_loss(params, conf, x, y, quant):
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    eps = conf["norm_eps"]
    h = take(1)[0][x]                                    # [S, d]
    for kind in layers_of(conf):
        norm, p = take(1)[0], take(_LEAVES[kind])

        def block(h, norm=norm, p=p, kind=kind):
            u = _rms_norm(h, norm, eps)
            if kind == "ssm":
                return h + mamba_mixer(u, p, conf, quant)
            if kind == "full":
                return h + attention(u, p, conf, quant)
            return h + _by_row_blocks(
                lambda ub: latent_experts(ub, p, conf, quant), u,
                rows=_ROUTING_ROWS)

        h = jax.checkpoint(block)(h)
    norm_f, head = take(2)

    def nll(hb, yb):
        logp = P.log_softmax(_mm(_rms_norm(hb, norm_f, eps), head, quant))
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
