"""IBM Granite 4.0-H (``model_type`` ``granitemoehybrid``; the dense
members, ``num_local_experts`` 0), a run of its published layers: the
program's builder and the plain float32 reference of the same
mathematics.

The model, as both compute it (d hidden, u always the normed input, no
bias but the convolution's; what the published ``config.json`` leaves
open is listed under ``assumed`` in the configuration's file):

- ``Norm(x) = w x / sqrt(mean(x^2) + eps)``, ``w`` starting at 1.
- ``h_0 = embedding_multiplier * E[token]``.
- Layer i is two parts, ``h <- h + residual_multiplier * Mixer_i(Norm1_i(
  h))`` and then ``h <- h + residual_multiplier * MLP_i(Norm2_i(h))``; its
  mixer is ``layer_types[i]``.  The cut runs the published layers
  ``first_layer .. first_layer + num_hidden_layers - 1``.
- ``MLP(u) = (silu(u W_gate) * (u W_up)) W_down`` at
  ``shared_intermediate_size`` (the published ``[gate, up]`` matrix is
  the two stacked).
- ``mamba``, the Mamba-2 mixer (H heads of P on G groups, state N):
  ``[z, xBC, dt] = split(u W_in, [H P, H P + 2 G N, H])``; ``xBC =
  silu(conv(xBC) + b_conv)``, ``conv`` causal and depthwise over the
  sequence; ``[x, B, C] = split(xBC, [H P, G N, G N])``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; from ``S_0 = 0``

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
      y_t = S_t C_t + D x_t

  every head of a group reading that group's ``B``, ``C``; then ``y <- w
  * Norm(y * silu(z))``, the gate FIRST, the mean of squares over a
  group's ``H P / G`` channels (with one group: over all of them); ``y
  W_out``.  These are the ``nemotron_h`` family's equations at other
  counts, and that family's plain functions compute them here
  (``mamba_mixer``, token by token).
- ``attention``: ``q = u W_q`` over H heads of D, ``k, v`` over G kv
  heads; ``s_ij = attention_multiplier * q_i . k_j`` for ``j <= i``
  (NOT ``1 / sqrt(D)``); softmax; ``W_o``.  No positional term
  (``position_embedding_type`` ``nope``), no gate, no q/k norm.
- ``logits = Norm_f(h) E^T / logits_scaling`` with ``E`` the EMBEDDING's
  matrix over the ids held (tied: one parameter, read twice: its gradient
  is the lookup's, scaled by ``embedding_multiplier``, plus the head's,
  scaled by ``1 / logits_scaling``), log-softmax, mean negative
  log-likelihood over the positions.

The reference is straightforward ``jax.numpy``: the recurrence is run
token by token, the convolution is a sum of shifted copies, attention a
masked softmax in blocks.  It knows nothing of chunks, decay matrices or
kernels, and imports nothing of the program.  Under the control's
``quant`` every projection, the convolution and the attention products
are rounded; the recurrence's own multiply-and-sums stay float32.
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.kernels import ssd as ssd_kernel
from benchmark.kernels.attention import kept_elements
from benchmark.models import nemotron_h
from benchmark.models import plain_ops as P
from benchmark.models.laguna import (_gated, _gated_specs, _mm, _rms_norm,
                                     _scale, _w)
from benchmark.models.qwen3_next import (_by_row_blocks, _causal_softmax,
                                         _zero)

#: no layer couples the records of a batch; one record is a step
BLOCK_ROWS = 1

#: positions a block of the reference's row-wise work
_ROWS = 1024

#: the mixer each entry of ``layer_types`` names
KINDS = {"mamba": "ssm", "attention": "full"}

#: what the plan needs of the program beyond the ``nemotron_h`` family
PLAN_FIELDS = ("embedding_scale", "attention_scale", "residual_scale",
               "logit_scale")


def layers_of(conf: Dict) -> List[str]:
    """The mixer of each layer of the cut, ``"ssm"`` or ``"full"``, from
    the published ``layer_types`` and where the cut starts."""
    first, n = conf["first_layer"], conf["num_hidden_layers"]
    return [KINDS[t] for t in conf["layer_types"][first:first + n]]


def head_dim(conf: Dict) -> int:
    return conf["hidden_size"] // conf["num_attention_heads"]


def ssm_sizes(conf: Dict):
    """``(heads, head_dim, groups, state)`` of a mixer."""
    return (conf["mamba_n_heads"], conf["mamba_d_head"],
            conf["mamba_n_groups"], conf["mamba_d_state"])


def _mixer_conf(conf: Dict) -> Dict:
    """A mixer's counts under the keys ``nemotron_h.mamba_mixer`` reads."""
    heads, p, groups, state = ssm_sizes(conf)
    return {"mamba_num_heads": heads, "mamba_head_dim": p,
            "n_groups": groups, "ssm_state_size": state,
            "norm_eps": conf["rms_norm_eps"]}


# -- the program --------------------------------------------------------------

def build(conf: Dict):
    from bigdl_tpu import models

    lacks = [f for f in PLAN_FIELDS if f not in models.DecoderPlan._fields]
    if lacks:
        raise SystemExit(f"this program's DecoderPlan has no "
                         f"{', '.join(lacks)}: it cannot multiply the "
                         f"granite_hybrid family's embedding, scores, "
                         f"residual parts and logits by the model's four "
                         f"scalars")
    heads, p, groups, state = ssm_sizes(conf)
    plans = {"ssm": models.LayerPlan("ssm", heads, "dense"),
             "full": models.LayerPlan("full", conf["num_attention_heads"],
                                      "dense")}
    plan = models.DecoderPlan(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        head_dim=head_dim(conf), kv_heads=conf["num_key_value_heads"],
        layers=[plans[kind] for kind in layers_of(conf)],
        window=0, rotary_full=None, rotary_window=None,
        dense_width=conf["shared_intermediate_size"], gate=None,
        eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"],
        ssm_head_dim=p, ssm_state=state, ssm_groups=groups,
        ssm_conv=conf["mamba_d_conv"],
        embedding_scale=float(conf["embedding_multiplier"]),
        attention_scale=float(conf["attention_multiplier"]),
        residual_scale=float(conf["residual_multiplier"]),
        logit_scale=float(conf["logits_scaling"]))
    return models.build_decoder_lm(plan, remat=True)


def criterion():
    import bigdl_tpu.nn as nn

    return nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)


def make_records(seed: int, n: int, conf: Dict):
    return reference.make_token_records(
        seed, n, conf["sequence_length"], conf["vocab_size"], conf["zipf"])


# -- the parameters, in the program's order -----------------------------------

#: standard deviation of a mixer's seeded ``dt_bias``
DT_BIAS_STD = 3.0


def _dt_bias(name, n, conf):
    """``dt_bias``, a head: normal around 0 with a deviation of
    ``DT_BIAS_STD`` (a weight whose ``fan_in`` says so: the harness draws
    ``sqrt(init_gain / fan_in) z`` and knows no other spread), while
    ``A_log`` is drawn near 0 (``A`` about -1).  ``dt = softplus(dt +
    dt_bias)`` then lies between 1e-3 and 10 and a head's decay a token
    ``exp(-dt)`` anywhere in (0, 1): about one head in six keeps more than
    0.95 of its state a token, so that most of what it holds came in
    through earlier chunks; drawn near 0 every head would forget at about
    0.5 a token and ``correct`` could not see the carried state.

    Why 3 and not the 6 of the two older hybrid families (ISSUE 43 allows
    either): at 6 z bfloat16 moves the first gradient's leaves as far as
    the int8 control does (0.278 against 0.201 on the chip, as in the
    Nemotron cell), so no limit lies between them; at 3 z nineteen sound
    seeds in twenty read 0.02-0.06 and the control 0.11-0.21.  The
    twentieth (4310000009) reads 0.185, at the scan's other chunk 0.175,
    and WHY is not found: its ``dt`` are no wider than a quiet seed's (at
    3 z every seed has a head at ``dt`` 6 to 9.5 in most layers, holding
    0.08-0.29 of its mixer's one norm; this seed's largest is 0.24), and
    what differs sits in one mixer's input side (its layer 1's filter,
    bias, input projection and first norm read 10-17% small: the workload
    file's ``limits_set_from``).  So the cell's gradient limits are wide
    and its losses refuse the control."""
    return dict(name=name, shape=(n,), kind="weight",
                fan_in=conf["init_gain"] / DT_BIAS_STD ** 2)


#: the configuration ``param_specs`` last described, for ``loss_sum``
_LAST_CONF = None

#: parameters of each kind of mixer
_MIXER_LEAVES = {"ssm": 8, "full": 4}


def param_specs(conf: Dict) -> List[Dict]:
    global _LAST_CONF
    _LAST_CONF = conf
    if not (conf["tie_word_embeddings"] and conf["mamba_conv_bias"]
            and conf["num_local_experts"] == 0
            and conf["position_embedding_type"] == "nope"
            and not conf["mamba_proj_bias"]):
        raise SystemExit("the granite_hybrid family's reference ties "
                         "embedding and head, biases its convolution and "
                         "nothing else, has no routed expert and no "
                         "positional term; the configuration says "
                         "otherwise")
    d, dh = conf["hidden_size"], head_dim(conf)
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    heads, p, groups, state = ssm_sizes(conf)
    inner, bc, taps = heads * p, groups * state, conf["mamba_d_conv"]
    # the one [vocab, d] matrix is drawn as a projection from d (rows of
    # norm about one: 0.022 an entry at d = 2048, the published
    # initializer's 0.02): the first norm rescales what the embedding
    # reads of it, and the tied head's logits start near zero
    specs = [_w("embed", (conf["vocab_size"], d), d)]
    for i, kind in enumerate(layers_of(conf)):
        b = f"layer{i}."
        specs.append(_scale(b + "norm1", d))
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
        else:
            specs += [_w(b + "q", (h * dh, d), d),
                      _w(b + "k", (g * dh, d), d), _w(b + "v", (g * dh, d), d),
                      _w(b + "o", (d, h * dh), h * dh)]
        specs.append(_scale(b + "norm2", d))
        specs += _gated_specs(b + "mlp", d, conf["shared_intermediate_size"])
    return specs + [_scale("norm_f", d)]


# -- FLOPs ---------------------------------------------------------------------

def ssd_shape(conf: Dict) -> Dict:
    """One mixer's call of the scan, as ``kernels/ssd.py`` counts it, at
    the chunk the PROGRAM runs (``ssd_kernel_args``, which the scan's
    roofline reads too).  The published ``mamba_chunk_size`` is how
    another implementation tiles its kernels: it changes no answer, the
    reference runs token by token, and nothing here reads it."""
    heads, p, groups, state = ssm_sizes(conf)
    return dict(heads=heads, groups=groups, seq=conf["sequence_length"],
                head_dim=p, state=state,
                chunk=conf["ssd_kernel_args"]["chunk"])


def flops_per_record(conf: Dict) -> Dict[str, int]:
    """Forward + backward of one record, 2 FLOPs a multiply-add, backward
    twice the forward; recomputation, norms, softmax, gates, the four
    scalars and the update are not counted.  Matrix products by
    parameters a token (the tied head is a product, the embedding a
    lookup; the convolution's taps are parameters a token too); attention
    by the score elements the causal mask keeps, exactly; the state-space
    scan by the products of its chunked form at the chunk the program
    runs (``kernels/ssd.py``, :func:`ssd_shape`)."""
    s, d, dh = conf["sequence_length"], conf["hidden_size"], head_dim(conf)
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    heads, p, groups, state = ssm_sizes(conf)
    inner, bc = heads * p, groups * state
    parts = {"ssm": 0, "full": 0, "mlp": 0}
    scores, scan, taps = 0, 0, 0
    for kind in layers_of(conf):
        if kind == "ssm":
            parts[kind] += d * (2 * inner + 2 * bc + heads) + inner * d
            taps += (inner + 2 * bc) * conf["mamba_d_conv"]
            scan += 3 * ssd_kernel.flops("fwd", **ssd_shape(conf))
        else:
            parts[kind] += d * (h * dh + 2 * g * dh) + h * dh * d
            scores += h * kept_elements(s)
        parts["mlp"] += 3 * d * conf["shared_intermediate_size"]
    per_token = lambda n: 3 * 2 * n * s  # noqa: E731
    products = per_token(sum(parts.values()) + d * conf["vocab_size"])
    attention = 3 * 2 * 2 * dh * scores              # q.k and p.v
    conv = 3 * 2 * taps * s
    return {"matrix_products": products,
            "of_which_ssm_projections": per_token(parts["ssm"]),
            "of_which_mlp": per_token(parts["mlp"]),
            "attention": attention, "ssd": scan, "convolution": conv,
            "total": products + attention + scan + conv}


# -- the reference --------------------------------------------------------------

def attention(u, p, conf, quant=None):
    """The scores are multiplied by ``attention_multiplier`` and by
    nothing else: the shared masked softmax divides by ``sqrt(D)``, so
    the queries bring that factor with them."""
    wq, wk, wv, wo = p
    s, dh = u.shape[0], head_dim(conf)
    h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    q = _mm(u, wq, quant).reshape(s, h, dh) \
        * (conf["attention_multiplier"] * math.sqrt(dh))
    k = _mm(u, wk, quant).reshape(s, g, dh)
    v = _mm(u, wv, quant).reshape(s, g, dh)
    out = _causal_softmax(q, k, v, quant)
    return _mm(out.reshape(s, h * dh), wo, quant)


def _record_loss(params, conf, x, y, quant):
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    eps, r = conf["rms_norm_eps"], conf["residual_multiplier"]
    mixer_conf = _mixer_conf(conf)
    embed = take(1)[0]
    h = conf["embedding_multiplier"] * embed[x]          # [S, d]
    for kind in layers_of(conf):
        norm1, mixer = take(1)[0], take(_MIXER_LEAVES[kind])
        norm2, mlp = take(1)[0], take(3)

        def block(h, norm1=norm1, mixer=mixer, norm2=norm2, mlp=mlp,
                  kind=kind):
            u = _rms_norm(h, norm1, eps)
            if kind == "ssm":
                h = h + r * nemotron_h.mamba_mixer(u, mixer, mixer_conf,
                                                   quant)
            else:
                h = h + r * attention(u, mixer, conf, quant)
            return h + r * _by_row_blocks(
                lambda ub: _gated(ub, mlp, quant), _rms_norm(h, norm2, eps),
                rows=_ROWS)

        h = jax.checkpoint(block)(h)
    norm_f = take(1)[0]

    def nll(hb, yb):
        logits = _mm(_rms_norm(hb, norm_f, eps), embed, quant) \
            / conf["logits_scaling"]
        return -jnp.take_along_axis(P.log_softmax(logits), yb[:, None],
                                    axis=1)[:, 0]

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
