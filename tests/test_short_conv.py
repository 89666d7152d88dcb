"""The double-gated short convolution and what the ``lfm2`` plan brought
with it, in float32 on the CPU: ``nn.GatedShortConv`` against the plain
reference of ``benchmark/models/lfm2.py`` (values and every gradient),
its causality and tap order; the depthwise convolution it shares with
``nn.GatedDeltaNet``, bit for bit what that layer computed before; the
sigmoid router whose bias chooses and does not weigh; the share test at
64 experts on 4 ranks; the head tied to the embedding; the tiny plan
whole; the counters and instants of a telemetry run.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import decoder_cases
from bigdl_tpu import models
from bigdl_tpu.models.transformer import VocabHead
from bigdl_tpu.nn.layers import short_conv
from bigdl_tpu.nn.module import functional_call, load_state_dict, state_dict
from decoder_cases import (call, check_loss_and_every_gradient, draw,
                           train_through_local_optimizer)

tiny_conf = functools.partial(decoder_cases.tiny_conf, "lfm2")


@pytest.fixture(scope="module")
def family():
    return decoder_cases.family("lfm2")


# -- the mixer ------------------------------------------------------------------

def _mixer(seed, d=16, taps=3):
    rng = np.random.default_rng(seed)
    weights = [draw(rng, d, taps, fan_in=taps), draw(rng, 3 * d, d, fan_in=d),
               draw(rng, d, d, fan_in=d)]
    layer = nn.GatedShortConv(d, taps=taps)
    load_state_dict(layer, dict(zip(
        ("conv_weight", "in_proj.weight", "out_proj.weight"), weights)),
        strict=False)
    return layer, weights


def test_gated_short_conv_is_the_reference_layer(family):
    """Values and the gradient of every parameter and of the input, two
    records of 24 positions, against the reference's equations a record."""
    layer, weights = _mixer(1)
    assert list(state_dict(layer, kind="param")) == [
        "conv_weight", "in_proj.weight", "out_proj.weight"]
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.standard_normal((2, 24, 16)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((2, 24, 16)), jnp.float32)
    keys, buffers = list(state_dict(layer, kind="param")), \
        state_dict(layer, kind="buffer")

    def got_fn(ws, x):
        out, _ = functional_call(layer, {**dict(zip(keys, ws)), **buffers}, x)
        return jnp.sum(out * do)

    def want_fn(ws, x):
        return sum(jnp.sum(family.conv_mixer(x[i], ws) * do[i])
                   for i in range(2))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(got_fn, argnums=(0, 1)))(weights, u)
        want = jax.jit(jax.value_and_grad(want_fn, argnums=(0, 1)))(weights, u)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_changing_a_token_moves_no_earlier_output_and_no_other_record():
    layer, _ = _mixer(3)
    u = jnp.asarray(np.random.default_rng(4).standard_normal((2, 20, 16)),
                    jnp.float32)
    base, _ = call(layer, u)
    moved, _ = call(layer, u.at[0, 9].add(1.0))
    np.testing.assert_array_equal(moved[0, :9], base[0, :9])
    np.testing.assert_array_equal(moved[1], base[1])
    # three taps: positions 9, 10 and 11 see token 9, position 12 does not
    changed = np.abs(np.asarray(moved[0] - base[0])).max(axis=-1) > 0
    assert changed[9:12].all() and not changed[12:].any()


def test_a_filter_of_the_last_tap_alone_is_the_two_gates(family):
    """Cross-correlation order: the last tap is the position itself, so a
    filter (0, 0, 1) leaves ``C * B * u``; (1, 0, 0) reads two positions
    back, zeros before the first."""
    d = 8
    layer = nn.GatedShortConv(d, taps=3)
    rng = np.random.default_rng(5)
    w_in = draw(rng, 3 * d, d, fan_in=d)
    u = jnp.asarray(rng.standard_normal((1, 12, d)), jnp.float32)

    def run(taps):
        load_state_dict(layer, {
            "conv_weight": jnp.tile(jnp.asarray(taps, jnp.float32), (d, 1)),
            "in_proj.weight": w_in, "out_proj.weight": jnp.eye(d)},
            strict=False)
        return call(layer, u)[0]

    with jax.default_matmul_precision("highest"):
        gate_in, gate_out, x = jnp.split(u @ w_in.T, 3, axis=-1)
    np.testing.assert_allclose(run((0, 0, 1)), gate_out * gate_in * x,
                               rtol=1e-5, atol=1e-6)
    back = jnp.pad(gate_in * x, ((0, 0), (2, 0), (0, 0)))[:, :12]
    np.testing.assert_allclose(run((1, 0, 0)), gate_out * back,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        family.short_conv(u[0], jnp.tile(jnp.asarray((1., 0., 0.)), (d, 1))),
        jnp.pad(u[0], ((2, 0), (0, 0)))[:12], rtol=0, atol=0)


# -- the convolution both layers call --------------------------------------------

def _conv_as_gated_delta_net_had_it(x, weight, width):
    """``GatedDeltaNet._conv`` of the parent commit, word for word."""
    s = x.shape[1]
    w = weight.astype(jnp.float32)
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(padded[:, i:i + s].astype(jnp.float32) * w[:, i]
            for i in range(width))
    return jax.nn.silu(y).astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_shared_convolution_is_bit_equal_to_the_hybrid_layers_own(dtype):
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((2, 37, 24)), dtype)
    w = jnp.asarray(rng.standard_normal((24, 4)), jnp.float32)
    old = jax.jit(lambda a, b: _conv_as_gated_delta_net_had_it(a, b, 4))
    new = jax.jit(lambda a, b: jax.nn.silu(
        short_conv.causal_depthwise_conv(a, b)).astype(a.dtype))
    np.testing.assert_array_equal(np.asarray(new(x, w), np.float32),
                                  np.asarray(old(x, w), np.float32))
    assert short_conv.causal_depthwise_conv(x, w).dtype == jnp.float32


class _ParentGatedDeltaNet(nn.GatedDeltaNet):
    """``GatedDeltaNet`` as the parent commit had it: its private
    convolution, SiLU inside, and the forward that called it."""

    def _conv(self, x):
        return _conv_as_gated_delta_net_had_it(x, self.conv_weight,
                                               self.conv_width)

    def update_output(self, input):
        import math

        from bigdl_tpu.ops.delta_rule import gated_delta_rule

        b, s, _ = input.shape
        hk, hv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        keys, values = hk * dk, hv * dv
        f32 = jnp.float32
        qkvz = self.in_proj_qkvz.forward(input)
        ba = self.in_proj_ba.forward(input).astype(f32)
        mixed, z = qkvz[..., :2 * keys + values], qkvz[..., 2 * keys + values:]
        q, k, v = jnp.split(self._conv(mixed), [keys, 2 * keys], axis=-1)

        def unit(x):
            x = x.reshape(b, s, hk, dk).astype(f32)
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

        q, k = (jnp.repeat(x.astype(input.dtype).transpose(0, 2, 1, 3),
                           hv // hk, axis=1)
                for x in (unit(q) / math.sqrt(dk), unit(k)))
        v = v.reshape(b, s, hv, dv).transpose(0, 2, 1, 3)
        beta = jax.nn.sigmoid(ba[..., :hv]).transpose(0, 2, 1)
        g = (-jnp.exp(self.A_log.astype(f32)) * jax.nn.softplus(
            ba[..., hv:] + self.dt_bias.astype(f32))).transpose(0, 2, 1)
        out, state = gated_delta_rule(q, k, v, g, beta, return_state=True)
        self.state_stats = jax.lax.stop_gradient(jnp.stack([
            jnp.mean(jnp.exp(g)), jnp.mean(beta),
            jnp.max(jnp.sqrt(jnp.sum(state * state, axis=(-2, -1))))]))
        out = self.norm.forward((out.transpose(0, 2, 1, 3),
                                 z.reshape(b, s, hv, dv)))
        return self.out_proj.forward(out.reshape(b, s, values))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_gated_delta_net_gives_what_it_gave_before_the_convolution_left(
        dtype, monkeypatch):
    """The hybrid layer calls the shared function once, on q, k and v
    together, and its output and state are the parent's to the bit."""
    now = nn.GatedDeltaNet(32, 2, 4, 8, 8, conv_width=4)
    before = _ParentGatedDeltaNet(32, 2, 4, 8, 8, conv_width=4)
    load_state_dict(before, state_dict(now))
    u = jnp.asarray(np.random.default_rng(7).standard_normal((2, 40, 32)),
                    dtype)
    seen, shared = [], short_conv.causal_depthwise_conv

    def spy(x, weight):
        seen.append(x.shape)
        return shared(x, weight)

    monkeypatch.setattr(short_conv, "causal_depthwise_conv", spy)
    got, got_state = call(now, u)
    assert seen == [(2, 40, 2 * 2 * 8 + 4 * 8)]
    want, want_state = call(before, u)
    assert len(seen) == 1                       # the parent's never calls it
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(got_state["state_stats"]),
                                  np.asarray(want_state["state_stats"]))


# -- the router -------------------------------------------------------------------

def _router(bias, n=16, k=4, d=8, normalize=True, seed=8):
    layer = nn.RoutedExperts(d, 4, n, k, held=(0, 4), score="sigmoid",
                             select_bias=True, normalize=normalize)
    rng = np.random.default_rng(seed)
    load_state_dict(layer, {"router.weight": draw(rng, n, d, fan_in=d),
                            "select_bias": jnp.asarray(bias, jnp.float32)},
                    strict=False)
    x = jnp.asarray(rng.standard_normal((32, d)), jnp.float32)
    return layer, x


def test_the_bias_moves_the_choice_and_not_the_weights():
    n = 16
    plain, x = _router(np.zeros(n))
    bias = np.zeros(n)
    bias[3] = 10.0                                  # always chosen
    bias[5] = -10.0                                 # never chosen
    tilted, _ = _router(bias)
    w0, e0 = jax.jit(plain.route)(x)
    w1, e1 = jax.jit(tilted.route)(x)
    assert (np.asarray(e1) == 3).any(axis=1).all()
    assert not (np.asarray(e1) == 5).any()
    assert (np.asarray(e0) != np.asarray(e1)).any()
    # the weights are the sigmoid scores of the chosen experts, whatever
    # the bias: renormalised over the four, never 10 larger
    logits = x @ plain.router.weight.T
    score = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(score, np.asarray(e1), axis=1)
    want = picked / (picked.sum(axis=1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(w1, want, rtol=1e-5)
    # a token whose choice the bias did not change keeps its weights
    same = (np.sort(np.asarray(e0), axis=1)
            == np.sort(np.asarray(e1), axis=1)).all(axis=1)
    if same.any():
        np.testing.assert_allclose(np.sort(np.asarray(w0)[same], axis=1),
                                   np.sort(np.asarray(w1)[same], axis=1),
                                   rtol=1e-6)


def test_the_four_weights_sum_to_s_over_s_plus_eps():
    layer, x = _router(0.05 * np.random.default_rng(9).standard_normal(16))
    w, e = jax.jit(layer.route)(x)
    score = np.asarray(jax.nn.sigmoid(x @ layer.router.weight.T))
    s = np.take_along_axis(score, np.asarray(e), axis=1).sum(axis=1)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), s / (s + 1e-6),
                               rtol=1e-6)
    assert nn.RoutedExperts.SIGMOID_NORM_EPS == 1e-6
    # without normalisation the weights are the scores themselves
    raw, x = _router(np.zeros(16), normalize=False)
    w, e = jax.jit(raw.route)(x)
    score = np.asarray(jax.nn.sigmoid(x @ raw.router.weight.T))
    np.testing.assert_allclose(w, np.take_along_axis(score, np.asarray(e), 1),
                               rtol=1e-6)


def test_the_bias_has_a_zero_gradient_and_the_router_does_not():
    layer, x = _router(0.05 * np.random.default_rng(10).standard_normal(16))
    params = state_dict(layer, kind="param")
    assert "select_bias" in params and params["select_bias"].shape == (16,)
    buffers = state_dict(layer, kind="buffer")

    def loss(p):
        out, _ = functional_call(layer, {**p, **buffers}, x)
        return jnp.sum(out * out)

    grads = jax.jit(jax.grad(loss))(params)
    assert not np.asarray(grads["select_bias"]).any()
    assert np.abs(np.asarray(grads["router.weight"])).max() > 0
    # a softmax router has no such parameter, and its route is what it was
    soft = nn.RoutedExperts(8, 4, 16, 4, held=(0, 4))
    assert "select_bias" not in state_dict(soft, kind="param")
    with pytest.raises(ValueError, match="'tanh'.*softmax, sigmoid"):
        nn.RoutedExperts(8, 4, 16, 4, score="tanh")


# -- the share test ---------------------------------------------------------------

def test_four_ranks_of_sixteen_experts_add_up_to_the_uncut_layer(family):
    """The guide's share test at the deployment's split: ranks 0-3 of 4
    hold experts 0-15, 16-31, 32-47, 48-63 of 64, four a token by the
    biased sigmoid score, no shared expert; their parts add up to what
    the uncut reference gives for the whole layer, every assignment on
    exactly one rank."""
    d, width, n, k, t = 32, 16, 64, 4, 48
    conf = dict(num_experts_per_tok=k, norm_topk_prob=True,
                routed_scaling_factor=1, held_experts=[0, n])
    rng = np.random.default_rng(11)
    weights = [draw(rng, n, d, width, fan_in=d),
               draw(rng, n, d, width, fan_in=d),
               draw(rng, n, width, d, fan_in=width),
               jnp.asarray(0.05 * rng.standard_normal(n), jnp.float32),
               draw(rng, n, d, fan_in=d)]
    u = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = family.sparse(u, weights, conf)
    parts, rows = [], 0
    for first in (0, 16, 32, 48):
        layer = nn.RoutedExperts(d, width, n, k, held=(first, 16),
                                 score="sigmoid", select_bias=True)
        e_gate, e_up, e_down, bias, w_r = weights
        load_state_dict(layer, {
            "experts_gate": e_gate[first:first + 16],
            "experts_up": e_up[first:first + 16],
            "experts_down": e_down[first:first + 16],
            "select_bias": bias, "router.weight": w_r}, strict=False)
        out, state = call(layer, u)
        parts.append(out)
        rows += int(np.asarray(state["held_load"])[:-1].sum())
        with jax.default_matmul_precision("highest"):
            alone = family.sparse(u, [e_gate[first:first + 16],
                                      e_up[first:first + 16],
                                      e_down[first:first + 16], bias, w_r],
                                  conf, held=(first, 16))
        np.testing.assert_allclose(out, alone, rtol=1e-5, atol=1e-5)
    assert rows == t * k                        # every assignment once
    np.testing.assert_allclose(sum(parts), want, rtol=1e-5, atol=1e-5)


# -- the tied head -----------------------------------------------------------------

def _tied_plan(tie):
    return models.DecoderPlan(
        vocab_size=32, hidden_size=16, head_dim=8, kv_heads=1,
        layers=[models.LayerPlan("conv", 2, "dense")], window=0,
        rotary_full=None, rotary_window=None, dense_width=24,
        tie_embeddings=tie)


def test_a_tied_head_is_one_leaf_whose_gradient_is_both_uses(family):
    model = models.build_decoder_lm(_tied_plan(True), remat=False)
    untied = models.build_decoder_lm(_tied_plan(False), remat=False)
    own = state_dict(model, kind="param")
    assert [k for k, v in own.items() if v.shape == (32, 16)] == ["0.weight"]
    assert len(state_dict(untied, kind="param")) == len(own) + 1
    rng = np.random.default_rng(12)
    weights = {k: draw(rng, *v.shape, fan_in=v.shape[-1])
               for k, v in own.items()}
    ids = jnp.asarray(rng.integers(0, 32, (2, 10)), jnp.int32)
    y = jnp.asarray(rng.integers(0, 32, (2, 10)), jnp.int32)
    buffers = state_dict(model, kind="buffer")
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)

    def loss(params, table_as_head=None):
        if table_as_head is not None:
            # the same model with the head's copy of the table held apart
            model.layers[-1].borrow("embedding", _Table(table_as_head))
        try:
            out, _ = functional_call(model, {**params, **buffers}, ids)
        finally:
            model.layers[-1].borrow("embedding", model.layers[0])
        return crit.update_output(out, y)

    with jax.default_matmul_precision("highest"):
        tied = jax.jit(jax.grad(loss))(weights)["0.weight"]
        apart = jax.jit(jax.grad(loss, argnums=(0, 1)))(
            weights, weights["0.weight"])
    embedding_use, head_use = apart[0]["0.weight"], apart[1]
    assert np.abs(np.asarray(embedding_use)).max() > 0
    assert np.abs(np.asarray(head_use)).max() > 0
    np.testing.assert_allclose(tied, embedding_use + head_use, rtol=1e-5,
                               atol=1e-7)
    # binding state to the tree reaches the head too, and is undone
    out0, _ = functional_call(model, {**weights, **buffers}, ids)
    doubled = dict(weights, **{"0.weight": 2.0 * weights["0.weight"]})
    out1, _ = functional_call(model, {**doubled, **buffers}, ids)
    assert float(jnp.max(jnp.abs(out1 - out0))) > 1e-3
    assert model.layers[-1].embedding is model.layers[0]
    with pytest.raises(ValueError, match="tied to a table"):
        VocabHead(16, 33, tied_to=model.layers[0])


class _Table:
    def __init__(self, weight):
        self.weight = weight


# -- the plan ------------------------------------------------------------------------

PLANS = {"conv-dense": dict(num_hidden_layers=1),
         "full-sparse": dict(num_hidden_layers=1, first_layer=2),
         "the-cut": {}}


@pytest.mark.parametrize("over", PLANS.values(), ids=PLANS)
def test_lfm2_plan_loss_and_every_gradient_match_the_reference(over, family):
    """``build_decoder_lm`` on a convolution layer with a dense
    feed-forward, on an attention layer with the routed one, and on the
    cut (published layers 1-5: conv dense; full, conv, conv, conv sparse),
    head tied: the loss and every leaf's gradient, on seeded weights."""
    assert [(layer["mixer"], layer["ffn"]) for layer in
            family.layers_of(tiny_conf())] == [
        ("conv", "dense"), ("full", "sparse")] + [("conv", "sparse")] * 3
    check_loss_and_every_gradient(family, tiny_conf(**over), 13,
                                  zero_gradient_leaves=("expert_bias",))


def test_the_builder_names_the_conv_kind_and_leaves_other_plans_alone():
    plan = models.tiny_decoder_plan(64)
    bad = list(plan.layers) + [models.LayerPlan("mamba", 4, "dense")]
    with pytest.raises(ValueError, match="full, window, latent, linear, conv"):
        models.build_decoder_lm(plan._replace(layers=bad))
    assert (plan.conv_taps, plan.router_score, plan.router_bias,
            plan.tie_embeddings) == (3, "softmax", False, False)
    own = state_dict(models.build_decoder_lm(plan), kind="param")
    assert not [k for k in own if "select_bias" in k or "conv" in k]
    assert [k for k in own if k.endswith("proj.weight")][-1] == \
        "6.proj.weight"                       # the head's own matrix


# -- through the Optimizer, traced ----------------------------------------------------

def test_lfm2_plan_trains_through_local_optimizer_and_is_traced(tmp_path,
                                                                family):
    """The tiny cut through ``LocalOptimizer``: the loss falls, the run log
    carries the convolution's ``kernel/dispatch`` instants, the router's
    ``moe/route`` facts and the per-step counters of both, and the
    Optimizer's own log the last step's."""
    conf = tiny_conf()
    x, y = family.make_records(3, 8, conf)
    events, said = train_through_local_optimizer(
        family.build(conf), family.criterion(), zip(x, y), tmp_path, epochs=5)
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 10 and steps[-1]["loss"] < steps[0]["loss"]
    legs = [e for e in events if e.get("name") == "kernel/dispatch"]
    conv = [e for e in legs if e["op"] == "gated_short_conv"]
    assert conv and {(e["backend"], e["reason"], e["taps"], e["channels"],
                      e["tokens"]) for e in conv} == {
        ("xla", "only-leg", 3, 64, 4 * 48)}
    attn = [e for e in legs if e["op"] == "attention"]
    assert {(e["q_heads"], e["kv_heads"], e["head_dim"], e["gate"],
             e["qk_norm"]) for e in attn} == {(4, 2, 16, None, True)}
    routes = [e for e in events if e.get("name") == "moe/route"]
    assert routes and {(e["score"], e["select_bias"], e["shared"],
                        e["experts"], e["held"], e["top_k"])
                       for e in routes} == {("sigmoid", True, False, 16, 4, 4)}
    for name in ("short_conv/gate_in_rms", "short_conv/out_rms"):
        seen = [e for e in events if e.get("name") == name]
        assert len(seen) == 10 * 4              # steps x convolution layers
        assert {e["layer"] for e in seen} == {"1.0.attn", "3.0.attn",
                                              "4.0.attn", "5.0.attn"}
        assert all(e["value"] > 0 for e in seen)
    load = [e for e in events if e.get("name") == "moe/load"]
    total = [e for e in events if e.get("name") == "moe/held_rows"]
    worst = [e for e in events if e.get("name") == "moe/held_rows_max"]
    mean = [e for e in events if e.get("name") == "moe/held_rows_mean"]
    assert len(total) == len(worst) == len(mean) == 10 * 4  # sparse layers
    assert total[0]["value"] == sum(e["value"] for e in load[:4])
    assert worst[0]["value"] == max(e["value"] for e in load[:4])
    assert mean[0]["value"] == pytest.approx(total[0]["value"] / 4)
    assert len([m for m in said if "short_conv/" in m]) == 4 * 2
    assert len([m for m in said if "moe/held_rows" in m]) == 4 * 3
