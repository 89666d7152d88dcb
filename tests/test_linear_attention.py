"""The gated delta rule and the hybrid decoder built on it, in float32 on
the CPU: the chunked scan against the token-by-token definition (values
and every gradient); ``nn.GatedDeltaNet``, the gated and normed
``nn.GroupedQueryAttention``, the zero-centred ``nn.RMSNorm`` and the
gated shared expert each against the plain reference of
``benchmark/models/qwen3_next.py`` on seeded weights; a tiny hybrid plan
(linear, linear, linear, full) whole; the share test of this layer; the
builder's check of a plan; the counters and instants of a telemetry run.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import decoder_cases
from bigdl_tpu import models
from bigdl_tpu.nn.module import load_state_dict, state_dict
from bigdl_tpu.ops import dispatch
from bigdl_tpu.ops.delta_rule import (gated_delta_rule,
                                      gated_delta_rule_recurrent)
from decoder_cases import (call, check_loss_and_every_gradient,
                           check_routed_gradients, compiled, draw, drawn,
                           routed, train_through_local_optimizer)

tiny_conf = functools.partial(decoder_cases.tiny_conf, "qwen3_next")
sparse_weights = functools.partial(decoder_cases.sparse_weights,
                                   shared_gate=True)


@pytest.fixture(scope="module")
def family():
    return decoder_cases.family("qwen3_next")


# -- the rule -------------------------------------------------------------------

# (sequence, chunk, decay, beta): chunks that do and do not divide the
# length, one chunk longer than the sequence, decays near 0 and near 1,
# beta at both ends
RULE_CASES = {
    "divides": (32, 8, "mid", "mid"),
    "does-not-divide": (37, 8, "mid", "mid"),
    "one-short-chunk": (24, 64, "mid", "mid"),
    "decay-near-one-beta-one": (40, 16, "near1", "one"),
    "decay-near-zero": (40, 16, "near0", "mid"),
    "beta-zero": (24, 8, "mid", "zero"),
    "long-memory": (96, 32, "near1", "mid"),
}


def _rule_inputs(s, decay, strength, b=2, h=3, dk=8, dv=16):
    keys = jax.random.split(jax.random.key(s), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1,  # noqa: E731
                                         keepdims=True)
    q = unit(drawn(jax.random.normal, keys[0], (b, h, s, dk)))
    k = unit(drawn(jax.random.normal, keys[1], (b, h, s, dk)))
    v = drawn(jax.random.normal, keys[2], (b, h, s, dv))
    draw = drawn(jax.random.uniform, keys[3], (b, h, s))
    g = {"mid": -2.0 * draw, "near1": -1e-3 * draw,
         "near0": -20.0 - 10.0 * draw}[decay]
    beta = {"mid": drawn(jax.random.uniform, keys[4], (b, h, s)),
            "zero": jnp.zeros((b, h, s)), "one": jnp.ones((b, h, s))}[
                strength]
    return (q, k, v, g, beta), drawn(jax.random.normal, keys[5],
                                     (b, h, s, dv))


@pytest.mark.parametrize("s,chunk,decay,strength", RULE_CASES.values(),
                         ids=RULE_CASES)
def test_chunked_rule_is_the_token_by_token_rule(s, chunk, decay, strength):
    args, do = _rule_inputs(s, decay, strength)

    def both(rule):
        def run(*a):
            out, vjp = jax.vjp(rule, *a)
            return (out,) + vjp(do)
        with jax.default_matmul_precision("highest"):
            return [np.asarray(x) for x in jax.jit(run)(*args)]

    got = both(lambda *a: gated_delta_rule(*a, chunk=chunk))
    want = both(gated_delta_rule_recurrent)
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        scale = max(float(np.abs(b).max()), 1e-3)
        assert float(np.abs(a - b).max()) < 2e-5 * scale, name
    if strength == "zero":           # nothing is ever written to the state
        assert float(np.abs(got[0]).max()) == 0.0


def test_the_rule_hands_out_its_last_state_and_says_how_it_ran():
    args, _ = _rule_inputs(37, "mid", "mid")
    dispatch.clear_decisions()
    with jax.default_matmul_precision("highest"):
        out, state = jax.jit(lambda *a: gated_delta_rule(
            *a, chunk=8, return_state=True))(*args)
        want_out, want = jax.jit(lambda *a: gated_delta_rule_recurrent(
            *a, return_state=True))(*args)
    assert state.shape == (2, 3, 8, 16) and state.dtype == jnp.float32
    np.testing.assert_allclose(state, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out, want_out, rtol=1e-4, atol=1e-5)
    (said,) = [d for d in dispatch.decisions() if d[0] == "gated_delta_rule"]
    assert said[1] == "xla" and said.launch == dict(
        leg="chunked-scan", chunk=8, chunks=5, heads=3, key_dim=8,
        value_dim=16)


def test_bfloat16_inputs_keep_their_type_and_a_float32_state():
    args, _ = _rule_inputs(64, "mid", "mid")
    q, k, v, g, beta = args
    low = [a.astype(jnp.bfloat16) for a in (q, k, v)]
    out, state = jax.jit(lambda *a: gated_delta_rule(
        *a, chunk=16, return_state=True))(*low, g, beta)
    assert out.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(gated_delta_rule_recurrent)(*low, g, beta))
    gap = float(np.abs(np.asarray(out, np.float32) - want).max())
    assert gap <= 2.0 ** -6 * float(np.abs(want).max())


# -- the Pallas leg (interpret mode here) against the XLA leg and the definition -------

# (sequence, decay, beta, dtype) at 2 heads of 128 x 128, chunks of 64 in
# grid steps of CHUNK_BLOCK: a state forgotten at once and one kept at
# 0.999 a token, beta at both ends, lengths the chunk block does and
# does not divide, bfloat16 in and out
KERNEL_CASES = {
    "forgets-at-once": (130, "near0", "mid", jnp.float32),
    "keeps-0.999-beta-near-one": (200, "near1", "near-one", jnp.float32),
    "beta-near-zero": (150, "mid", "near-zero", jnp.float32),
    "padded-to-the-chunk-block": (300, "mid", "mid", jnp.float32),
    "whole-chunk-blocks": (512, "mid", "mid", jnp.float32),
    "bfloat16": (200, "mid", "mid", jnp.bfloat16),
}


def _kernel_inputs(s, decay, strength, dtype):
    (q, k, v, g, beta), do = _rule_inputs(s, decay, "mid", b=1, h=2, dk=128,
                                          dv=128)
    beta = {"mid": beta, "near-zero": 1e-3 * beta,
            "near-one": 1.0 - 1e-3 * beta}[strength]
    return tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta), \
        do.astype(dtype)


@pytest.mark.parametrize("s,decay,strength,dtype", KERNEL_CASES.values(),
                         ids=KERNEL_CASES)
def test_pallas_leg_is_the_xla_leg_and_the_definition(s, decay, strength,
                                                      dtype, monkeypatch):
    args, do = _kernel_inputs(s, decay, strength, dtype)

    def all_of(rule, mode):
        def run(*a):
            (out, state), vjp = jax.vjp(
                lambda *x: rule(*x, return_state=True), *a)
            return (out, state) + vjp((do.astype(out.dtype),
                                       0.1 * jnp.ones_like(state)))
        monkeypatch.setenv("BIGDL_KERNELS", mode)
        dispatch.clear_decisions()
        with jax.default_matmul_precision("highest"):
            got = jax.jit(run)(*args)
        return got, [d for d in dispatch.decisions()
                     if d[0] == "gated_delta_rule"]

    got, said = all_of(gated_delta_rule, "pallas")
    assert said and all(d[1] == "pallas" for d in said)
    xla, said = all_of(gated_delta_rule, "xla")
    assert said and all(d[1] == "xla" for d in said)
    want, _ = all_of(gated_delta_rule_recurrent, "xla")
    assert got[0].dtype == dtype and got[1].dtype == jnp.float32
    # float32: both legs are the definition to rounding; bfloat16: the
    # legs round the same products, and sit as far from the definition
    close, far = (2e-5, 2e-5) if dtype == jnp.float32 else (2.0 ** -6,
                                                              2.0 ** -4)
    for name, a, b, c in zip(("o", "state", "dq", "dk", "dv", "dg", "dbeta"),
                             got, xla, want):
        a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
        scale = max(float(np.abs(c).max()), 1e-3)
        assert float(np.abs(a - b).max()) < close * scale, name
        assert float(np.abs(a - c).max()) < far * scale, name


# (kernel mode, on a TPU, under a mesh, head size) -> (backend, reason)
DISPATCH_CASES = {
    "auto-off-tpu": (None, False, False, 128, "xla", "auto:off-tpu"),
    "dims-of-16": ("pallas", False, False, 16, "xla", "unsupported-shape"),
    "partitioned": (None, True, True, 128, "xla", "auto:spmd-partitioned"),
    "auto-on-tpu": (None, True, False, 128, "pallas", "auto:tpu"),
    "forced": ("pallas", False, False, 128, "pallas",
               "forced:BIGDL_KERNELS=pallas"),
    "switched-off": ("xla", True, False, 128, "xla",
                     "forced:BIGDL_KERNELS=xla"),
}


@pytest.mark.parametrize("mode,on_tpu,meshed,dim,backend,reason",
                         DISPATCH_CASES.values(), ids=DISPATCH_CASES)
def test_the_rule_picks_its_leg_from_what_it_can_see(mode, on_tpu, meshed, dim,
                                                     backend, reason,
                                                     monkeypatch):
    from bigdl_tpu.ops import attention, delta_rule
    from bigdl_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(attention, "is_tpu_device", lambda: on_tpu)
    if mode is None:
        monkeypatch.delenv("BIGDL_KERNELS", raising=False)
    else:
        monkeypatch.setenv("BIGDL_KERNELS", mode)
    wide = jax.ShapeDtypeStruct((1, 2, 1100, dim), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((1, 2, 1100), jnp.float32)
    mesh = make_mesh((2,), devices=jax.devices()[:2]) if meshed else None
    dispatch.clear_decisions()
    with dispatch.spmd_partitioned(mesh):
        # a function of its own: a trace that is cached decides nothing
        out = jax.eval_shape(lambda *a: gated_delta_rule(*a), wide, wide,
                             wide, row, row)
    assert out.shape == wide.shape and out.dtype == jnp.bfloat16
    (said,) = [d for d in dispatch.decisions() if d[0] == "gated_delta_rule"]
    assert tuple(said) == ("gated_delta_rule", backend, reason)
    assert said.launch["chunk"] == 64 and said.launch["heads"] == 2
    if backend == "pallas":
        per_step = delta_rule.CHUNK_BLOCK
        chunks = -(-1100 // (64 * per_step)) * per_step
        assert said.launch == dict(
            leg="chunk-kernels-scan", chunk=64, chunks=chunks, heads=2,
            key_dim=dim, value_dim=dim, chunks_per_block=per_step,
            grid=(1, 2, chunks // per_step))
    else:
        assert said.launch == dict(leg="chunked-scan", chunk=64, chunks=18,
                                   heads=2, key_dim=dim, value_dim=dim)


# -- the layers, each against the family's plain reference ------------------------

def test_gated_delta_net_is_the_reference_layer(family):
    conf = tiny_conf()
    rng = np.random.default_rng(1)
    d, hk, hv, dk, dv = 64, 2, 4, 8, 8
    keys, values = hk * dk, hv * dv
    weights = [draw(rng, 2 * keys + values, 4, fan_in=4),
               draw(rng, hv, fan_in=4), draw(rng, hv, fan_in=4),
               draw(rng, 2 * keys + 2 * values, d, fan_in=d),
               draw(rng, 2 * hv, d, fan_in=d),
               1.0 + draw(rng, dv, fan_in=100),
               draw(rng, d, values, fan_in=values)]
    layer = nn.GatedDeltaNet(d, hk, hv, dk, dv, conv_width=4)
    names = list(state_dict(layer, kind="param"))
    assert names == ["conv_weight", "A_log", "dt_bias",
                     "in_proj_qkvz.weight", "in_proj_ba.weight",
                     "norm.weight", "out_proj.weight"]
    load_state_dict(layer, dict(zip(names, weights)), strict=False)
    # two whole chunks of the rule's 64 tokens and a part of a third
    u = jnp.asarray(rng.standard_normal((2, 136, d)), jnp.float32)
    out, state = call(layer, u)
    want = compiled(lambda rows, ws: jnp.stack(
        [family._linear_attention(row, ws, conf, None) for row in rows]),
        u, weights)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    decay, beta, norm = np.asarray(state["state_stats"])
    assert 0.0 < decay < 1.0 and 0.0 < beta < 1.0 and norm > 0.0
    # causal: a later token moves no earlier output
    moved, _ = call(layer, u.at[:, 30].add(1.0))
    np.testing.assert_allclose(moved[:, :30], out[:, :30], rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(moved[:, 33] - out[:, 33]))) > 1e-4
    with pytest.raises(ValueError):
        nn.GatedDeltaNet(d, 3, 4, dk, dv)


def test_gated_normed_attention_is_the_reference_layer(family):
    conf = tiny_conf()
    rng = np.random.default_rng(2)
    d, h, g, dh = 64, 4, 2, 16
    weights = [draw(rng, h * 2 * dh, d, fan_in=d),
               draw(rng, g * dh, d, fan_in=d), draw(rng, g * dh, d, fan_in=d),
               draw(rng, dh, fan_in=25), draw(rng, dh, fan_in=25),
               draw(rng, d, h * dh, fan_in=h * dh)]
    layer = nn.GroupedQueryAttention(
        d, h, g, dh, rotary=nn.Rotary(4, theta=1e7), gate="per_channel",
        qk_norm=lambda n: nn.RMSNorm(n, 1e-6, zero_centred=True))
    names = list(state_dict(layer, kind="param"))
    assert names == ["q_proj.weight", "k_proj.weight", "v_proj.weight",
                     "q_norm.weight", "k_norm.weight", "out_proj.weight"]
    load_state_dict(layer, dict(zip(names, weights)), strict=False)
    u = jnp.asarray(rng.standard_normal((2, 24, d)), jnp.float32)
    dispatch.clear_decisions()
    out, _ = call(layer, u)
    want = compiled(lambda rows, ws: jnp.stack(
        [family._full_attention(row, ws, conf, None) for row in rows]),
        u, weights)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    (said,) = [d_ for d_ in dispatch.decisions() if d_[0] == "attention"]
    assert said.launch["head_dim"] == 16 and said.launch["qk_norm"] is True
    assert said.launch["gate"] == "per_channel"


@pytest.mark.parametrize("zero_centred", [False, True])
def test_rms_norm_scales_by_w_or_by_one_plus_w(zero_centred, family):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((5, 32)) * 3, jnp.float32)
    w = draw(rng, 32, fan_in=16)
    norm = nn.RMSNorm(32, 1e-6, zero_centred=zero_centred)
    assert float(norm.weight[0]) == (0.0 if zero_centred else 1.0)
    load_state_dict(norm, {"weight": w}, strict=False)
    want = family._norm(x, w if zero_centred else w - 1.0, 1e-6)
    np.testing.assert_allclose(norm.forward(x), want, rtol=1e-5, atol=1e-6)
    if not zero_centred:
        # a linear layer's head norm: the same, then gated by silu(z)
        gated = nn.GatedRMSNorm(32, 1e-6)
        load_state_dict(gated, {"weight": w}, strict=False)
        z = jnp.asarray(rng.standard_normal((5, 32)), jnp.float32)
        np.testing.assert_allclose(gated.forward((x, z)),
                                   want * jax.nn.silu(z),
                                   rtol=1e-5, atol=1e-6)


def test_gated_shared_expert_is_the_reference_layer(family):
    conf = tiny_conf()
    weights = sparse_weights(conf, 5, experts=4)
    u = jnp.asarray(np.random.default_rng(6).standard_normal((48, 64)),
                    jnp.float32)
    layer = routed(conf, (0, 4), weights)
    assert list(state_dict(layer, kind="param"))[-1] == "shared_gate.weight"
    out, _ = call(layer, u)
    sparse = lambda v, ws: family._sparse(v, ws, conf, None)  # noqa: E731
    want = compiled(sparse, u, weights)
    ungated = compiled(sparse, u,
                       weights[:7] + [jnp.zeros_like(weights[7])])
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(want - ungated))) > 1e-3  # the gate acts
    # without the option the layer has no such parameter
    plain = nn.RoutedExperts(64, 32, 16, 3, held=(0, 4), shared_width=32)
    assert "shared_gate.weight" not in state_dict(plain, kind="param")


def test_the_shares_of_this_layer_add_up_to_the_uncut_layer(family):
    """The guide's share test: 4 shares of 4 of the 16 experts, the gated
    shared expert (which every chip computes alike) counted once, give
    what the uncut reference gives for the whole layer."""
    conf = tiny_conf()
    weights = sparse_weights(conf, 7, experts=16)
    u = jnp.asarray(np.random.default_rng(8).standard_normal((48, 64)),
                    jnp.float32)
    want = compiled(lambda v, ws: family._sparse(
        v, ws, dict(conf, held_experts=[0, 16]), None), u, weights)
    shared = compiled(lambda v, ws: family._sigmoid(
        v @ ws[7].T) * family._gated(v, ws[4:7], None), u, weights)
    parts, rows = [], 0
    for share in range(4):
        out, state = call(routed(conf, (4 * share, 4), weights), u)
        parts.append(out - shared)
        rows += int(np.asarray(state["held_load"])[:-1].sum())
    assert rows == 48 * conf["num_experts_per_tok"]  # every assignment once
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-4,
                               atol=2e-5)


# -- the plan ---------------------------------------------------------------------

PLANS = {"linear": 1, "one-period": 4}


@pytest.mark.parametrize("layers", PLANS.values(), ids=PLANS)
def test_hybrid_plan_loss_and_every_gradient_match_the_reference(layers,
                                                                 family):
    """``build_decoder_lm`` on a linear layer alone and on a whole period
    (linear, linear, linear, full), every layer sparse with a gated shared
    expert: the loss and every leaf's gradient, on seeded weights."""
    assert family.layers_of(tiny_conf()) == ["linear"] * 3 + ["full"]
    check_loss_and_every_gradient(
        family, tiny_conf(num_hidden_layers=layers), 13)


def test_the_builder_checks_a_plan_before_it_builds_a_module(monkeypatch):
    plan = models.tiny_decoder_plan(64)
    built = []
    monkeypatch.setattr(nn, "GroupedQueryAttention",
                        lambda *a, **k: built.append(a))
    bad = list(plan.layers) + [models.LayerPlan("mamba", 4, "dense")]
    with pytest.raises(ValueError, match="layer 4.*'mamba'.*full, window, "
                                         "latent, linear"):
        models.build_decoder_lm(plan._replace(layers=bad))
    bad = list(plan.layers) + [models.LayerPlan("full", 4, "soft")]
    with pytest.raises(ValueError, match="'soft'.*dense, sparse"):
        models.build_decoder_lm(plan._replace(layers=bad))
    assert not built


def test_defaults_leave_the_registrys_decoder_as_it_was():
    """No option of this file reaches a plan that does not ask for it:
    the registry's ``decoder_lm`` has the parameters it had."""
    model = models.build_decoder_lm(models.tiny_decoder_plan(64))
    names = list(state_dict(model, kind="param"))
    assert len(names) == 55
    assert not [n for n in names if "q_norm" in n or "shared_gate" in n
                or "conv_weight" in n]
    assert names[1:7] == ["1.0.norm1.weight", "1.0.attn.q_proj.weight",
                          "1.0.attn.k_proj.weight", "1.0.attn.v_proj.weight",
                          "1.0.attn.gate_proj.weight",
                          "1.0.attn.out_proj.weight"]
    assert float(state_dict(model)["1.0.norm1.weight"][0]) == 1.0


def test_hybrid_plan_trains_through_local_optimizer_and_is_traced(
        tmp_path, family):
    """The tiny hybrid plan through ``LocalOptimizer``: the loss falls,
    the run log carries the rule's and the attention's ``kernel/dispatch``
    instants and the ``linear_attn/*`` counters of every linear layer, and
    the Optimizer's own log the last step's."""
    conf = tiny_conf()
    x, y = family.make_records(3, 8, conf)
    events, said = train_through_local_optimizer(
        family.build(conf), family.criterion(), zip(x, y), tmp_path, epochs=5)
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 10 and steps[-1]["loss"] < steps[0]["loss"]
    legs = [e for e in events if e.get("name") == "kernel/dispatch"]
    rule = [e for e in legs if e["op"] == "gated_delta_rule"]
    assert rule and {(e["leg"], e["chunk"], e["chunks"], e["heads"],
                      e["key_dim"], e["value_dim"]) for e in rule} == \
        {("chunked-scan", 64, 3, 4, 8, 8)}
    attn = [e for e in legs if e["op"] == "attention"]
    assert {(e["q_heads"], e["kv_heads"], e["head_dim"], e["gate"],
             e["qk_norm"]) for e in attn} == {(4, 2, 16, "per_channel", True)}
    names = ("linear_attn/decay_mean", "linear_attn/beta_mean",
             "linear_attn/state_norm_max")
    for name in names:
        seen = [e for e in events if e.get("name") == name]
        assert len(seen) == 10 * 3              # steps x linear layers
        assert {e["layer"] for e in seen} == {"1.0.attn", "2.0.attn",
                                              "3.0.attn"}
        assert all(e["value"] > 0 for e in seen)
    decay = [e["value"] for e in events
             if e.get("name") == "linear_attn/decay_mean"]
    assert all(v < 1.0 for v in decay)
    said = [m for m in said if "linear_attn/" in m]
    assert len(said) == 3 * 3                   # linear layers x names
    assert float(said[0].split("linear_attn/decay_mean ")[1]) == \
        pytest.approx(decay[-3])


def test_the_exact_path_in_blocks_of_rows_is_the_exact_path(monkeypatch,
                                                            family):
    """A router biased so that every token picks every held expert takes
    the exact path; with ``EXACT_ROWS`` small it runs in 4 blocks of 16
    rows, each recomputed in the backward pass: same values, same
    gradients."""
    conf = tiny_conf(held_experts=[0, 3], num_experts=3,
                     num_experts_published=32)
    weights = sparse_weights(conf, 9, experts=3)
    weights[3] = weights[3].at[:3].add(10.0 / 64.0)
    u = jnp.asarray(np.random.default_rng(10).standard_normal((64, 64)) + 2.0,
                    jnp.float32)
    monkeypatch.setattr(nn.RoutedExperts, "EXACT_ROWS", 48)
    layer = routed(conf, (0, 3), weights)
    out, state = call(layer, u)
    assert list(np.asarray(state["held_load"])) == [64, 64, 64, 192]
    sparse = lambda v, ws: family._sparse(v, ws, conf, None)  # noqa: E731
    np.testing.assert_allclose(out, compiled(sparse, u, weights),
                               rtol=2e-4, atol=2e-5)
    check_routed_gradients(layer, u, sparse, weights)
