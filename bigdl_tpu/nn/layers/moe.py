"""Mixture-of-experts layers.  Two stand here, with different jobs:

- :class:`RoutedExperts` — what a current decoder's sparse feed-forward
  is, as ONE chip of an expert-parallel deployment computes it: a
  float32 router over all ``n_experts`` (softmax scores, or sigmoid
  scores with a bias an expert that enters the choice only), the
  ``top_k`` largest
  per token with renormalised weights, experts (gated SiLU, or ungated
  under another activation; on the model's width or in a narrower
  latent) of which this layer HOLDS a contiguous share (``held=(first,
  count)``), an optional shared expert, and no token ever dropped.  It computes its own
  experts' part of the result and nothing that stands in for the other
  chips or their exchange.  This is the layer that runs on a chip (the
  benchmark's decoder cells).
- :class:`MixtureOfExperts` — the GShard/Mesh-TensorFlow DENSE dispatch
  (one-hot capacity-bucketed einsums in float32, ReLU experts, tokens
  over capacity zeroed) whose stacked parameters shard over a mesh
  ``expert`` axis so that XLA lowers dispatch and combine to
  all-to-alls.  It has run only in the CPU dry run
  (``__graft_entry__.dryrun_multichip``, ``tests/test_pipeline_moe.py``)
  and never on a chip.

The reference's nearest relative is the local gating container
``MixtureTable`` (``nn/MixtureTable.scala``): gate weights blend expert
outputs on one machine.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import Module, Parameter
from bigdl_tpu.nn.init import Xavier
from bigdl_tpu.utils.rng import next_rng_id, require_rng

__all__ = ["MixtureOfExperts", "expert_sharding_rules", "GatedMLP",
           "FeedForward", "RoutedExperts"]


def expert_sharding_rules(axis: str = "expert"):
    """``extra_sharding_rules`` hook for TrainStep: shards every
    parameter whose path contains ``experts`` on its leading (expert)
    dimension."""
    from jax.sharding import PartitionSpec as P

    def rule(path: str, arr):
        if "expert" in path and getattr(arr, "ndim", 0) >= 1:
            return P(axis, *([None] * (arr.ndim - 1)))
        return None

    return rule


class MixtureOfExperts(Module):
    """Token-routed MoE FFN block.

    Input [tokens, d_model] (or [batch, seq, d_model], flattened for
    routing); output the same shape.  Experts are two-layer FFNs with
    stacked parameters ``experts_w1 [E, D, H]`` etc.; under a mesh with
    an ``expert`` axis, pass ``expert_sharding_rules()`` to TrainStep so
    the stacks shard and dispatch/combine einsums become all-to-alls."""

    def __init__(self, d_model: int, d_hidden: int, n_experts: int,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 noise_std: float = 0.0):
        super().__init__()
        self.d_model, self.d_hidden, self.n_experts = \
            d_model, d_hidden, n_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.noise_std = noise_std
        self._rng_id = next_rng_id()
        init = Xavier
        self.gate_weight = Parameter(
            init.init((d_model, n_experts), fan_in=d_model,
                      fan_out=n_experts))
        self.experts_w1 = Parameter(init.init(
            (n_experts, d_model, d_hidden), fan_in=d_model,
            fan_out=d_hidden))
        self.experts_b1 = Parameter(
            jnp.zeros((n_experts, d_hidden), jnp.float32))
        self.experts_w2 = Parameter(init.init(
            (n_experts, d_hidden, d_model), fan_in=d_hidden,
            fan_out=d_model))
        self.experts_b2 = Parameter(
            jnp.zeros((n_experts, d_model), jnp.float32))

    def capacity(self, n_tokens: int) -> int:
        return max(1, int(math.ceil(
            self.top_k * n_tokens / self.n_experts * self.capacity_factor)))

    def _route(self, x):
        """x [T, D] -> (dispatch [T, E, C] one-hot, combine [T, E, C])."""
        t = x.shape[0]
        e = self.n_experts
        c = self.capacity(t)
        logits = x @ self.gate_weight.astype(x.dtype)
        if self.training and self.noise_std > 0.0:
            # noisy top-k gating: exploration noise on the router logits
            key = require_rng(self._rng_id)
            logits = logits + self.noise_std * jax.random.normal(
                key, logits.shape, logits.dtype)
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        # top-k per token, processed one choice at a time so capacity
        # counters accumulate across choices (GShard's sequential greedy)
        _, topk_idx = jax.lax.top_k(gates, self.top_k)
        dispatch = jnp.zeros((t, e, c), jnp.float32)
        combine = jnp.zeros((t, e, c), jnp.float32)
        counts = jnp.zeros((e,), jnp.int32)
        for k in range(self.top_k):
            idx = topk_idx[:, k]                     # [T]
            onehot = jax.nn.one_hot(idx, e)          # [T, E]
            # position of each token within its expert's bucket:
            # running count over the token dim, offset by prior choices
            pos_in_e = (jnp.cumsum(onehot, axis=0) - 1.0) \
                + counts[None, :].astype(jnp.float32)
            pos = jnp.sum(pos_in_e * onehot, axis=1).astype(jnp.int32)
            keep = pos < c                            # capacity drop
            pos_oh = jax.nn.one_hot(jnp.where(keep, pos, 0), c)
            slot = onehot[:, :, None] * pos_oh[:, None, :] \
                * keep[:, None, None]
            dispatch = dispatch + slot
            gate_k = jnp.sum(gates * onehot, axis=1)
            combine = combine + slot * gate_k[:, None, None]
            counts = counts + jnp.sum(
                onehot * keep[:, None], axis=0).astype(jnp.int32)
        return dispatch, combine

    def update_output(self, input):
        x = input
        lead = x.shape[:-1]
        x2 = x.reshape(-1, self.d_model)
        dispatch, combine = self._route(x2)
        xd = x2.astype(jnp.float32)
        # [T,E,C],[T,D] -> [E,C,D]: the all-to-all dispatch einsum
        expert_in = jnp.einsum("tec,td->ecd", dispatch, xd)
        h = jnp.einsum("ecd,edh->ech", expert_in,
                       self.experts_w1.astype(jnp.float32))
        h = jax.nn.relu(h + self.experts_b1[:, None, :])
        out = jnp.einsum("ech,ehd->ecd", h,
                         self.experts_w2.astype(jnp.float32))
        out = out + self.experts_b2[:, None, :]
        y = jnp.einsum("tec,ecd->td", combine, out)
        return y.reshape(lead + (self.d_model,)).astype(x.dtype)

    def aux_load_balancing_loss(self, input) -> jax.Array:
        """GShard/Switch auxiliary loss: E * dot(mean gate fraction,
        mean dispatch fraction) — add to the criterion to keep experts
        balanced."""
        x2 = input.reshape(-1, self.d_model)
        logits = x2 @ self.gate_weight.astype(x2.dtype)
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top1 = jnp.argmax(gates, axis=-1)
        frac_tokens = jnp.mean(jax.nn.one_hot(top1, self.n_experts), axis=0)
        frac_gates = jnp.mean(gates, axis=0)
        return self.n_experts * jnp.sum(frac_tokens * frac_gates)


class GatedMLP(Module):
    """Gated feed-forward (Shazeer 2020, the SiLU form): ``(silu(x W_gate)
    * (x W_up)) W_down``, no bias."""

    def __init__(self, d_model: int, width: int):
        super().__init__()
        from bigdl_tpu.nn.layers.linear import Linear

        self.d_model, self.width = d_model, width
        self.gate_proj = Linear(d_model, width, with_bias=False)
        self.up_proj = Linear(d_model, width, with_bias=False)
        self.down_proj = Linear(width, d_model, with_bias=False)

    def update_output(self, input):
        return self.down_proj.forward(
            jax.nn.silu(self.gate_proj.forward(input))
            * self.up_proj.forward(input))

    def __repr__(self):
        return f"GatedMLP({self.d_model}, {self.width})"


#: the activations an expert (and a plain feed-forward) may have;
#: ``relu2`` is the squared ReLU
ACTIVATIONS = {"silu": jax.nn.silu,
               "relu2": lambda x: jnp.square(jax.nn.relu(x))}


class FeedForward(Module):
    """Ungated feed-forward: ``act(x W_up) W_down``, no bias;
    ``activation`` one of :data:`ACTIVATIONS`."""

    def __init__(self, d_model: int, width: int, activation: str = "relu2"):
        super().__init__()
        from bigdl_tpu.nn.layers.linear import Linear

        if activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {activation!r}; known: "
                             f"{', '.join(ACTIVATIONS)}")
        self.d_model, self.width, self.activation = d_model, width, activation
        self.up_proj = Linear(d_model, width, with_bias=False)
        self.down_proj = Linear(width, d_model, with_bias=False)

    def update_output(self, input):
        return self.down_proj.forward(
            ACTIVATIONS[self.activation](self.up_proj.forward(input)))

    def __repr__(self):
        return (f"FeedForward({self.d_model}, {self.width}, "
                f"{self.activation})")


def _places(order, top_k: int):
    """The inverse of a WHOLE sorted order of the ``(token, choice)``
    pairs, choice-major: ``places[j * tokens + t]`` is the sorted row that
    holds assignment ``(t, j)``, so a token's rows are read in ``top_k``
    runs of ``tokens`` indices and summed run by run."""
    slot = (order % top_k) * (order.shape[0] // top_k) + order // top_k
    # ``slot`` is a permutation, so its sorting order is its inverse (on
    # a TPU half the time of an int32 scatter of an iota, and the same
    # sort program as the order's own)
    return jnp.argsort(slot)


def _gather_sum(rows, w_row, places, top_k: int):
    """``y[t] = sum over j < top_k of w_row[r] * float32(rows[r])`` at ``r =
    places[j * tokens + t]`` (``w_row`` None: unit weights): one gather
    of the flat ``[tokens * top_k, d]`` rows in their own dtype, then
    converted, weighed and summed in float32."""
    tokens = places.shape[0] // top_k
    picked = rows[places]
    weights = None if w_row is None else w_row[places]
    y = None
    for start in range(0, places.shape[0], tokens):
        # a run is sliced before it is converted: converted whole, the
        # gathered rows would pass through memory once more in float32
        run = picked[start:start + tokens].astype(jnp.float32)
        if weights is not None:
            run = run * weights[start:start + tokens, None]
        y = run if y is None else y + run
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _spread(x, token, places, top_k: int):
    """``rows[r] = x[token[r]]`` where ``token = order // top_k`` of a
    WHOLE sorted order and ``places`` is that order's inverse
    (``_places``).  The transpose of a permutation is its inverse, so the
    backward pass reads rows where autodiff would scatter-add them:
    ``dx[t]`` is the float32 sum of ``drows`` at ``t``'s ``top_k`` places
    (``_fold`` with unit weights)."""
    return x[token]


def _spread_fwd(x, token, places, top_k):
    return x[token], places


def _spread_bwd(top_k, places, drows):
    dx = _gather_sum(drows, None, places, top_k)
    return dx.astype(drows.dtype), None, None


_spread.defvjp(_spread_fwd, _spread_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fold(rows, w_row, token, places, top_k: int):
    """``_spread``'s transpose with weights, float32 ``[tokens, d]``:
    ``y[t] = sum of w_row[r] * float32(rows[r])`` over ``t``'s ``top_k``
    places, which is what ``zeros.at[token].add(float32(rows) *
    w_row[:, None])`` gives under a whole order.  Its backward is that
    scatter-add's own: ``dy`` gathered by ``token``, times ``w_row`` for
    ``drows``, times ``rows`` and summed over ``d`` for ``dw_row``."""
    return _gather_sum(rows, w_row, places, top_k)


def _fold_fwd(rows, w_row, token, places, top_k):
    return _gather_sum(rows, w_row, places, top_k), (rows, w_row, token)


def _fold_bwd(top_k, res, dy):
    rows, w_row, token = res
    g = dy[token]
    drows = (g * w_row[:, None]).astype(rows.dtype)
    dw_row = jnp.sum(g * rows.astype(jnp.float32), axis=1)
    return drows, dw_row.astype(w_row.dtype), None, None


_fold.defvjp(_fold_fwd, _fold_bwd)


class RoutedExperts(Module):
    """Dropless top-k routing over the experts held here.

    Input [..., d_model], output the same shape.  Router, in float32
    over all ``n_experts``: scores ``p = softmax(x W_r)``
    (``score="softmax"``) or ``p = sigmoid(x W_r)``, each expert on its
    own (``score="sigmoid"``); the ``top_k`` experts with the largest
    ``p``, or with the largest ``p + b`` under ``select_bias`` (``b``
    one number an expert, a parameter whose gradient is exactly zero:
    it moves the CHOICE and never a weight; a recipe that balances the
    load moves it without a gradient, and none does here); ``w_e =
    routed_scale * p_e / sum of the chosen p`` (``normalize``; sigmoid
    scores add ``SIGMOID_NORM_EPS`` to that sum, since it can be near
    zero).  Result: ``shared(x) + sum over the chosen experts
    that are HELD here of w_e * expert_e(x)``.  An expert is ``(act(x
    W_gate) * (x W_up)) W_down`` under ``activation="silu"`` (the
    default: the gated-SiLU form) and ``act(x W_up) W_down`` with no
    gate matrix under any other of :data:`ACTIVATIONS` (``"relu2"`` is
    the squared ReLU): the activation decides the form; the shared
    expert has the same form on the model's own width (``shared_width``
    0 or None: no shared expert).  ``latent``: the routed experts work
    in a space narrower than the model: ``l = x W_in`` (``d_model ->
    latent``) BEFORE the dispatch, experts of ``[latent, width]`` and
    ``[width, latent]``, and ``r W_out`` (``latent -> d_model``) AFTER
    the combine, so gather, grouped product and scatter-add (or fold)
    move ``latent``-wide rows; the router and the shared expert read
    ``x`` itself.  ``shared_gate``: the
    shared expert's result is scaled by ``sigmoid(x w_s)``, one scalar a
    token from a weight of its own.  ``held=(first, count)``
    names the contiguous experts this layer has parameters for (default:
    all); what the others would add is left out, as it is on one chip
    of an expert-parallel deployment before the combine.

    Static shapes, no dropped token.  The assignments that land here
    are sorted by expert and the experts run as ONE grouped matrix
    product (``lax.ragged_dot``) over the first ``capacity`` rows of
    that order, so the work follows the load and not ``count`` experts x
    every token.  ``capacity`` is ``CAPACITY_FACTOR`` times the expected
    load (rounded up to 8 rows, never above the worst case); a step with
    more assignments than that takes the exact path: every held expert
    over every token under a dense mask, the same sum.  Either way the
    result is the reference's for ANY routing.  (The factor is generous
    because a router trained on a share of the experts learns to prefer
    the ones that are held, since only they lower the loss: in the
    benchmark's cell the load of a layer tripled within 60 steps.)

    The rows' way back to their tokens takes the cheaper of two exact
    forms, chosen by a shape.  Where ``capacity == tokens x top_k`` (the
    layer holds every expert, or a share large enough that the factor
    reaches the worst case) the sorted order is a WHOLE permutation of the
    ``(token, choice)`` pairs, and the transpose of a permutation is its
    inverse: the combine gathers each token's ``top_k`` rows by the
    inverse order and sums them in float32 (``_fold``), and the dispatch
    gather's backward is the same movement with unit weights
    (``_spread``), so neither pass scatter-adds (on a TPU a gather of the
    65,536 x 2,048 bfloat16 rows takes 2.2 ms and its sum 0.5-1.1, a
    scatter-add of them 5.2-6.5).  A prefix of the order (``capacity <
    tokens x top_k``) keeps the float32 scatter-add of its ``capacity``
    rows and autodiff's transpose of the gather: folding would read
    ``tokens x top_k`` rows where the scatter-add touches ``capacity``.
    Both give the same sum of the same values; rows of experts that are
    not held are zero on the way out of every product and add nothing.

    ``held_load`` (a buffer, so it rides the step's state and costs no
    sync): rows each held expert received in the last forward, then the
    rows that took the exact path.  A ``moe/route`` instant at trace
    time says how the layer was built (``combine``: ``"fold"`` or
    ``"scatter_add"``; ``latent``, ``activation``, ``gated``)."""

    #: the score functions a router may have
    SCORES = ("softmax", "sigmoid")
    #: added to the sum of the chosen sigmoid scores before it divides
    SIGMOID_NORM_EPS = 1e-6
    #: the fast path's rows over the expected load
    CAPACITY_FACTOR = 4.0
    #: held experts x rows of one block of the exact path: the largest
    #: that leaves a step of 8 held experts x 8,192 tokens one block, the
    #: compiled step it was before the blocks
    EXACT_ROWS = 65536

    def __init__(self, d_model: int, width: int, n_experts: int, top_k: int,
                 held: Optional[Tuple[int, int]] = None,
                 shared_width: Optional[int] = None,
                 routed_scale: float = 1.0, normalize: bool = True,
                 shared_gate: bool = False, score: str = "softmax",
                 select_bias: bool = False, activation: str = "silu",
                 latent: Optional[int] = None):
        super().__init__()
        from bigdl_tpu.nn.init import RandomUniform
        from bigdl_tpu.nn.layers.linear import Linear

        first, count = held if held is not None else (0, n_experts)
        if not (0 <= first and first + count <= n_experts and count > 0):
            raise ValueError(f"held={held} outside {n_experts} experts")
        if score not in self.SCORES:
            raise ValueError(f"unknown router score {score!r}; known: "
                             f"{', '.join(self.SCORES)}")
        if activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {activation!r}; known: "
                             f"{', '.join(ACTIVATIONS)}")
        self.score = score
        self.activation, self.latent = activation, latent
        #: SiLU experts have a gate matrix, the others none
        self.gated = gated = activation == "silu"
        #: the width the routed rows have: the latent, or the model's own
        self.row_dim = row_dim = latent or d_model
        self.d_model, self.width = d_model, width
        self.n_experts, self.top_k = n_experts, top_k
        self.first, self.count = first, count
        self.routed_scale, self.normalize = routed_scale, normalize
        init = RandomUniform()
        if gated:
            self.experts_gate = Parameter(init.init(
                (count, row_dim, width), fan_in=row_dim))
        self.experts_up = Parameter(init.init(
            (count, row_dim, width), fan_in=row_dim))
        self.experts_down = Parameter(init.init(
            (count, width, row_dim), fan_in=width))
        self.router = Linear(d_model, n_experts, with_bias=False)
        self.has_select_bias = bool(select_bias)
        if select_bias:
            self.select_bias = Parameter(jnp.zeros((n_experts,),
                                                   jnp.float32))
        if latent:
            self.latent_in = Linear(d_model, latent, with_bias=False)
            self.latent_out = Linear(latent, d_model, with_bias=False)
        if shared_width:
            self.shared = GatedMLP(d_model, shared_width) if gated \
                else FeedForward(d_model, shared_width, activation)
        self.shared_width = shared_width
        self.shared_gated = bool(shared_gate and shared_width)
        if self.shared_gated:
            self.shared_gate = Linear(d_model, 1, with_bias=False)
        self.register_buffer("held_load", jnp.zeros((count + 1,), jnp.int32))

    def capacity(self, n_tokens: int) -> int:
        """Rows of the grouped product: the fast path's static size."""
        worst = n_tokens * min(self.top_k, self.count)
        expected = n_tokens * self.top_k * self.count / self.n_experts
        rows = int(math.ceil(self.CAPACITY_FACTOR * expected / 8.0)) * 8
        return max(8, min(worst, rows))

    def route(self, x2):
        """x2 [T, D] -> (weights [T, k] float32, experts [T, k] int32)."""
        logits = jnp.dot(x2.astype(jnp.float32),
                         self.router.weight.T.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if self.score == "softmax":
            scores, eps = jax.nn.softmax(logits, axis=-1), None
        else:
            scores, eps = jax.nn.sigmoid(logits), self.SIGMOID_NORM_EPS
        if self.has_select_bias:
            # the bias chooses and does not weigh: the indices carry no
            # gradient, and the weights are the scores themselves
            _, top_i = jax.lax.top_k(
                scores + self.select_bias.astype(jnp.float32), self.top_k)
            top_p = jnp.take_along_axis(scores, top_i, axis=-1)
        else:
            top_p, top_i = jax.lax.top_k(scores, self.top_k)
        if self.normalize:
            total = jnp.sum(top_p, axis=-1, keepdims=True)
            top_p = top_p / (total if eps is None else total + eps)
        return self.routed_scale * top_p, top_i

    def _hidden(self, product, stacks):
        """An expert's hidden rows from ``product(stack)`` of its input
        with each of ``stacks`` (gate and up, or up alone)."""
        act = ACTIVATIONS[self.activation]
        if self.gated:
            return act(product(stacks[0])) * product(stacks[1])
        return act(product(stacks[0]))

    def _stacks(self):
        """The experts' input-side stacks, in the order ``_hidden`` takes
        them."""
        return (self.experts_gate, self.experts_up) if self.gated \
            else (self.experts_up,)

    def _grouped(self, x2, w, local, counts, cap, fold):
        """The fast path: ``cap`` rows sorted by held expert.  ``fold``:
        the order is whole, and rows move by ``_spread`` and ``_fold``."""
        k = self.top_k
        key = local.reshape(-1)
        order = jnp.argsort(key, stable=True)[:cap]
        token = order // k
        live = key[order] < self.count
        w_row = jnp.where(live, w.reshape(-1)[order], 0.0)
        if fold:
            places = _places(order, k)
            rows = _spread(x2, token, places, k)
        else:
            rows = x2[token]
        sizes = counts[:self.count]

        def product(a, p):
            # rows past the last group belong to no expert, and what a
            # grouped product (or its transpose, in the backward pass)
            # leaves there is unspecified: on a TPU, whatever the memory
            # held.  Masked on the way in, their cotangent is zero; masked
            # on the way out, their value is
            a = jnp.where(live[:, None], a, 0)
            return jnp.where(live[:, None], jax.lax.ragged_dot(
                a, p.astype(a.dtype), sizes), 0)

        h = self._hidden(lambda p: product(rows, p), self._stacks())
        out = product(h, self.experts_down)
        if fold:
            return _fold(out, w_row, token, places, k)
        out = out.astype(jnp.float32) * w_row[:, None]
        return jnp.zeros((x2.shape[0], self.row_dim), jnp.float32).at[
            token].add(out)

    def _masked(self, x2, w, local, counts=None):
        """The exact path: each held expert over every token, the tokens
        in blocks of at most ``EXACT_ROWS / count`` rows, each block
        recomputed in the backward pass (a ``lax.cond`` holds the memory
        of the branch it does not take too, and what a pass over every
        token keeps for its backward grows with tokens x experts)."""
        hit = local[:, :, None] == jnp.arange(self.count)[None, None, :]
        w_dense = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)  # [T,C]
        t = rows = x2.shape[0]
        while rows * self.count > self.EXACT_ROWS and rows % 2 == 0:
            rows //= 2
        if rows == t:
            return self._masked_rows(x2, w_dense)
        y = jax.lax.map(
            jax.checkpoint(lambda block: self._masked_rows(*block)),
            (x2.reshape(t // rows, rows, -1),
             w_dense.reshape(t // rows, rows, -1)))
        return y.reshape(t, self.row_dim)

    def _masked_rows(self, x2, w_dense):
        def one(y, e):
            *stacks, wd, we = e
            h = self._hidden(lambda p: x2 @ p.astype(x2.dtype), stacks)
            out = (h @ wd.astype(x2.dtype)).astype(jnp.float32)
            return y + we[:, None] * out, None

        y, _ = jax.lax.scan(
            one, jnp.zeros((x2.shape[0], self.row_dim), jnp.float32),
            self._stacks() + (self.experts_down, w_dense.T))
        return y

    def update_output(self, input):
        from bigdl_tpu import telemetry

        x2 = input.reshape(-1, self.d_model)
        t = x2.shape[0]
        cap = self.capacity(t)
        worst = t * min(self.top_k, self.count)
        # the sorted rows are ALL the (token, choice) pairs: a shape fact
        fold = cap == t * self.top_k
        telemetry.instant("moe/route", experts=self.n_experts,
                          held_first=self.first, held=self.count,
                          top_k=self.top_k, tokens=t, capacity=cap,
                          worst=worst,
                          combine="fold" if fold else "scatter_add",
                          score=self.score,
                          select_bias=self.has_select_bias,
                          shared=bool(self.shared_width),
                          latent=self.latent, activation=self.activation,
                          gated=self.gated)
        w, experts = self.route(x2)
        # the rows the experts take: the tokens, or their latent projection
        rows = self.latent_in.forward(x2) if self.latent else x2
        local = experts - self.first
        # an assignment to an expert that is not held sorts last
        local = jnp.where((local >= 0) & (local < self.count), local,
                          self.count)
        counts = jnp.sum(
            local.reshape(-1, 1) == jnp.arange(self.count + 1)[None, :],
            axis=0, dtype=jnp.int32)
        n_held = t * self.top_k - counts[self.count]
        grouped = functools.partial(self._grouped, cap=cap, fold=fold)
        if cap >= worst:
            y = grouped(rows, w, local, counts)
        else:
            y = jax.lax.cond(n_held <= cap, grouped, self._masked,
                             rows, w, local, counts)
        spilled = jnp.where(n_held > cap, n_held, 0)
        self.held_load = jax.lax.stop_gradient(jnp.concatenate(
            [counts[:self.count], spilled[None]]))
        y = y.astype(input.dtype)
        if self.latent:
            y = self.latent_out.forward(y)
        if self.shared_width:
            shared = self.shared.forward(x2)
            if self.shared_gated:
                shared = (shared * jax.nn.sigmoid(self.shared_gate.forward(
                    x2).astype(jnp.float32))).astype(y.dtype)
            y = y + shared
        return y.reshape(input.shape)

    def step_counters(self, buffers, tele, layer: str):
        """``moe/load`` per held expert, their sum ``moe/held_rows`` (what
        a cut to a share of the experts drifts in), the largest and the
        mean of them (``moe/held_rows_max`` over ``moe/held_rows_mean`` is
        the imbalance the grouped product sees) and ``moe/exact_rows`` of
        the last step, from this layer's buffers as the step left them
        (the Optimizer calls this where it has the loss on the host)."""
        load = np.asarray(buffers["held_load"])
        held = load[:-1]
        for i, rows in enumerate(held):
            tele.counter("moe/load", int(rows), layer=layer,
                         expert=self.first + i)
        tele.counter("moe/held_rows", int(held.sum()), layer=layer)
        tele.counter("moe/held_rows_max", int(held.max()), layer=layer)
        tele.counter("moe/held_rows_mean", float(held.mean()), layer=layer)
        tele.counter("moe/exact_rows", int(load[-1]), layer=layer)

    def __repr__(self):
        return (f"RoutedExperts({self.d_model}, {self.width}, experts="
                f"{self.first}..{self.first + self.count - 1} of "
                f"{self.n_experts}, top_k={self.top_k}, latent="
                f"{self.latent}, activation={self.activation})")
