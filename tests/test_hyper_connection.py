"""Latent attention and the multi-stream residual path, and what the
``xing4`` plan brought with it, in float32 on the CPU: the flash kernels
with values narrower than queries against the dense form (forward and all
three gradients); ``nn.LatentAttention`` against a plain masked softmax
with the rotary key broadcast; ``nn.HyperConnection`` against
``benchmark/models/xing4.py``'s plain reference (Sinkhorn as the loop it
is, the clamp, the one-stream limit, the seeded draw's spread); the share
test at 64 experts on 8 ranks with the shared expert counted once; the
tiny plan whole; the counters and instants of a telemetry run.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import decoder_cases
from bigdl_tpu import models
from bigdl_tpu.nn.layers import hyper_connection
from bigdl_tpu.nn.module import functional_call, load_state_dict, state_dict
from bigdl_tpu.ops import attention
from decoder_cases import (call, check_loss_and_every_gradient, compiled,
                           draw, train_through_local_optimizer)

tiny_conf = functools.partial(decoder_cases.tiny_conf, "xing4")


@pytest.fixture(scope="module")
def family():
    return decoder_cases.family("xing4")


# -- the kernels' value width -------------------------------------------------

@pytest.mark.parametrize("heads,kv_heads,window", [(4, 4, None), (4, 2, 96)],
                         ids=["own-kv", "grouped-window"])
def test_flash_takes_values_narrower_than_queries(heads, kv_heads, window):
    """Queries and keys of 48 over values of 32, interpreted kernels in
    blocks of 128 / 64: the forward and dq, dk, dv against the dense form
    of the same scores."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, heads, 256, 48)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, kv_heads, 256, 48)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, kv_heads, 256, 32)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((1, heads, 256, 32)), jnp.float32)

    def both(attend):
        out, vjp = jax.vjp(lambda *a: attend(*a, causal=True, scale=0.2,
                                             window=window), q, k, v)
        return (out,) + vjp(do)

    flash = functools.partial(attention.flash_attention, block_q=128,
                              block_k=64, interpret=True)
    got = compiled(lambda: both(flash))
    want = compiled(lambda: both(attention.dot_product_attention))
    assert [a.shape for a in got] == [do.shape, q.shape, k.shape, v.shape]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# -- latent attention ------------------------------------------------------------

LATENT_LEAVES = ("q_a.weight", "q_norm.weight", "q_b.weight", "kv_a.weight",
                 "kv_norm.weight", "kv_b.weight", "out_proj.weight")


def _latent(seed, backend="dense", d=32, h=4, dn=16, dr=8, dv=12, rq=24,
            rkv=16):
    rng = np.random.default_rng(seed)
    weights = [draw(rng, rq, d, fan_in=d),
               jnp.asarray(1 + 0.1 * rng.standard_normal(rq), jnp.float32),
               draw(rng, h * (dn + dr), rq, fan_in=rq),
               draw(rng, rkv + dr, d, fan_in=d),
               jnp.asarray(1 + 0.1 * rng.standard_normal(rkv), jnp.float32),
               draw(rng, h * (dn + dv), rkv, fan_in=rkv),
               draw(rng, d, h * dv, fan_in=h * dv)]
    rotary = nn.Rotary(dr, theta=10000.0, factor=4.0,
                       original_max_position=16)
    layer = nn.LatentAttention(d, h, dn, dr, dv, rq, rkv, rotary=rotary,
                               scale=0.31, backend=backend)
    load_state_dict(layer, dict(zip(LATENT_LEAVES, weights)), strict=False)
    return layer, weights, rotary


def _plain_latent(u, weights, rotary, h=4, dn=16, dr=8, dv=12, rkv=16,
                  scale=0.31, eps=1e-6):
    """The layer's equations with nothing shared: the rotary key is
    broadcast to every head, the heads are concatenated, the scores are a
    whole masked matrix."""
    wqa, q_norm, wqb, wkva, kv_norm, wkvb, wo = weights
    b, s, _ = u.shape
    norm = lambda x, w: w * x / jnp.sqrt(  # noqa: E731
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    q = (norm(u @ wqa.T, q_norm) @ wqb.T).reshape(b, s, h, dn + dr)
    latent = u @ wkva.T
    kv = (norm(latent[..., :rkv], kv_norm) @ wkvb.T).reshape(b, s, h, dn + dv)
    k_rope = jnp.broadcast_to(latent[..., None, rkv:], (b, s, h, dr))
    q = jnp.concatenate([q[..., :dn], rotary.apply(q[..., dn:])], -1)
    k = jnp.concatenate([kv[..., :dn], rotary.apply(k_rope)], -1)
    scores = scale * jnp.einsum("bqhd,bkhd->bhqk", q, k)
    keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    prob = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", prob, kv[..., dn:])
    return out.reshape(b, s, h * dv) @ wo.T


@pytest.mark.parametrize("backend", ["dense", "flash"])
def test_latent_attention_is_the_plain_masked_softmax(backend):
    layer, weights, rotary = _latent(3, backend)
    u = jnp.asarray(np.random.default_rng(4).standard_normal((2, 40, 32)),
                    jnp.float32)
    out, _ = call(layer, u)
    want = compiled(lambda: _plain_latent(u, weights, rotary))
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    # the rotation matters, and a token reads nothing after itself
    moved = compiled(lambda: _plain_latent(u, weights, nn.Rotary(8)))
    assert float(np.abs(np.asarray(moved) - np.asarray(want)).max()) > 1e-2
    later = u.at[:, 25].add(1.0)
    out2, _ = call(layer, later)
    np.testing.assert_array_equal(np.asarray(out2)[:, :25],
                                  np.asarray(out)[:, :25])


def test_latent_attention_refuses_a_rotary_of_another_width():
    with pytest.raises(ValueError, match="rotary of 16"):
        nn.LatentAttention(32, 4, 16, 8, 12, 24, 16, rotary=nn.Rotary(16))
    layer = nn.LatentAttention(32, 4, 16, 8, 12, 24, 16)
    assert layer.scale == pytest.approx(1 / math.sqrt(24))


# -- the residual path alone -----------------------------------------------------

def _path(seed, d=16, n=4, **kw):
    rng = np.random.default_rng(seed)
    k = 2 * n + n * n
    weights = [draw(rng, k, n * d, fan_in=n * d),
               jnp.asarray(rng.standard_normal(k), jnp.float32),
               jnp.asarray(1 + 0.1 * rng.standard_normal(3), jnp.float32)]
    layer = nn.HyperConnection(d, n, **kw)
    load_state_dict(layer, dict(zip(("phi", "bias", "alpha"), weights)),
                    strict=False)
    return layer, weights


def test_the_path_is_the_references(family):
    """Read, coefficients and write of ``nn.HyperConnection`` on seeded
    weights against the family's plain form: ``u``, the new streams, and
    the gradient of a sum of both by the streams and the three leaves."""
    layer, weights = _path(21)
    rng = np.random.default_rng(22)
    x = jnp.asarray(rng.standard_normal((2, 24, 4 * 16)), jnp.float32)
    conf = tiny_conf()

    def program(params, x):
        (u, mix), _ = functional_call(layer, {**params, **buffers}, x)
        f = jnp.tanh(u) * 1.5
        return u, layer.merge(x, f, mix)

    def plain(ws, x):
        def one(xr):
            pre, post, res = family.hc_coefficients(xr, ws, conf)
            u = jnp.sum(pre[:, :, None] * xr, axis=1)
            f = jnp.tanh(u) * 1.5
            return u, jnp.einsum("tij,tjc->tic", res, xr) \
                + post[:, :, None] * f[:, None, :]

        u, new = jax.vmap(one)(x.reshape(2, 24, 4, 16))
        return u, new.reshape(2, 24, 64)

    buffers = state_dict(layer, kind="buffer")
    params = state_dict(layer, kind="param")
    got, want = compiled(program, params, x), compiled(plain, weights, x)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    loss = lambda fn: lambda p, x: sum(  # noqa: E731
        jnp.sum(o * o) for o in fn(p, x))
    g_got = compiled(jax.grad(loss(program), argnums=(0, 1)), params, x)
    g_want = compiled(jax.grad(loss(plain), argnums=(0, 1)), weights, x)
    for key, w in zip(("phi", "bias", "alpha"), g_want[0]):
        scale = float(np.abs(w).max())
        np.testing.assert_allclose(g_got[0][key], w, rtol=2e-3,
                                   atol=2e-4 * scale)
    np.testing.assert_allclose(g_got[1], g_want[1], rtol=2e-3, atol=2e-4)


def test_sinkhorn_is_the_references_loop_with_rows_of_one(family):
    rng = np.random.default_rng(31)
    mild = np.exp(rng.uniform(-1, 1, (4, 4, 3, 50))).astype(np.float32)
    got = np.asarray(compiled(
        lambda m: hyper_connection.sinkhorn(m, 20), jnp.asarray(mild)))
    # the reference takes [..., row, column]
    want = np.asarray(compiled(lambda m: family.sinkhorn(m, 20),
                               jnp.asarray(np.moveaxis(mild, (0, 1),
                                                       (-2, -1)))))
    np.testing.assert_allclose(np.moveaxis(got, (0, 1), (-2, -1)), want,
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(got.sum(axis=1), 1.0, atol=1e-6)   # rows
    np.testing.assert_allclose(got.sum(axis=0), 1.0, atol=1e-5)   # columns
    # one iteration is another matrix: the columns are not there yet
    once = np.asarray(compiled(lambda m: hyper_connection.sinkhorn(m, 1),
                               jnp.asarray(mild)))
    assert np.abs(once.sum(axis=0) - 1.0).max() > 1e-2


def test_the_clamp_holds_at_logits_of_a_hundred():
    """A path whose mixing logits are +-100: the clamp keeps the
    exponential finite, rows still sum to one, and the gradient is
    finite."""
    layer, weights = _path(41, clamp=30.0)
    bias = np.asarray(weights[1]).copy()
    bias[8:] = np.where(np.arange(16) % 3 == 0, 100.0, -100.0)
    load_state_dict(layer, {"bias": jnp.asarray(bias)}, strict=False)
    x = jnp.asarray(np.random.default_rng(42).standard_normal((1, 10, 64)),
                    jnp.float32)
    pre, post, res = compiled(layer.coefficients, x)
    res = np.asarray(res)
    assert np.isfinite(res).all() and res.min() >= 0
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=1e-6)
    # e^30 / e^-30: what the clamp allows, and no more
    loose, _ = _path(41, clamp=200.0)
    load_state_dict(loose, {"bias": jnp.asarray(bias)}, strict=False)
    unclamped = np.asarray(compiled(loose.coefficients, x)[2])
    assert not np.isfinite(unclamped).all()      # e^100 overflows float32
    grad = compiled(jax.grad(lambda v: jnp.sum(layer.coefficients(v)[2]
                                               ** 2)), x)
    assert np.isfinite(np.asarray(grad)).all()


def _block(streams, seed=51, d=16):
    rng = np.random.default_rng(seed)
    attn = nn.GroupedQueryAttention(d, 2, 1, 8, rotary=nn.Rotary(8))
    block = nn.DecoderBlock(d, attn, nn.GatedMLP(d, 24), streams=streams)
    own = state_dict(block, kind="param")
    shared = {k: draw(rng, *v.shape, fan_in=v.shape[-1])
              for k, v in own.items() if not k.startswith("hc_")}
    return block, shared


def test_one_stream_builds_nothing_and_a_forced_path_is_the_plain_block():
    """``streams=1`` is the block as it was (no leaf, no buffer, no
    instruction of the path), and four streams whose ``H_res`` is forced
    to the identity and whose ``H_pre`` and ``H_post`` to the first unit
    vector carry ``x + f(norm(x))`` in stream 0 and leave the others
    alone."""
    plain, shared = _block(1)
    assert not [k for k in state_dict(plain) if "hc_" in k or "mhc" in k]
    load_state_dict(plain, shared, strict=False)
    wide, _ = _block(4)
    load_state_dict(wide, shared, strict=False)
    forced = np.full((24,), -60.0, np.float32)
    forced[0] = 60.0                  # H_pre = (1, 0, 0, 0)
    forced[4] = 0.0                   # H_post = (2 sigmoid(0), 0, 0, 0)
    forced[8::5] = 60.0               # clipped to +-30: the identity
    for name in ("hc_attn", "hc_ffn"):
        load_state_dict(wide, {
            name + ".bias": jnp.asarray(forced),
            name + ".phi": jnp.zeros((24, 64), jnp.float32)}, strict=False)
    rng = np.random.default_rng(52)
    x = jnp.asarray(rng.standard_normal((2, 12, 16)), jnp.float32)
    others = jnp.asarray(rng.standard_normal((2, 12, 48)), jnp.float32)
    want, _ = call(plain, x)
    got, _ = call(wide, jnp.concatenate([x, others], axis=-1))
    np.testing.assert_allclose(got[..., :16], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[..., 16:], others, rtol=1e-6, atol=1e-6)
    text = jax.jit(lambda s, v: functional_call(plain, s, v)).lower(
        state_dict(plain), x).as_text()
    assert "exponential" in text and "mhc" not in text
    with pytest.raises(ValueError, match="one stream is the plain"):
        nn.HyperConnection(16, 1)


def test_at_the_seeded_draw_the_mixing_moves_with_the_token(family):
    """The configuration's draw (``Phi`` a projection from a unit-RMS
    vector, ``b`` of unit spread, ``a`` near 1): ``H_res`` is neither
    uniform nor the identity, its entries spread around 1/4 and differ
    between tokens."""
    from benchmark import reference

    conf = tiny_conf()
    specs = family.param_specs(conf)
    weights = reference.make_weights(specs, 61, conf["init_gain"])
    by_name = {s["name"]: w for s, w in zip(specs, weights)}
    ws = [by_name[f"layer0.hc_attn.{leaf}"] for leaf in ("phi", "b", "a")]
    x, _ = family.make_records(61, 1, conf)
    streams = jnp.broadcast_to(by_name["embed"][x[0]][:, None, :],
                               (x.shape[1], 4, conf["hidden_size"]))
    _, _, res = compiled(lambda s: family.hc_coefficients(s, ws, conf),
                         streams)
    res = np.asarray(res)                       # [tokens, 4, 4]
    assert res.std() > 0.05 and np.abs(res - 0.25).max() > 0.2
    assert np.abs(res - np.eye(4)).max() > 0.5
    # the first two tokens may be one id; some pair of tokens differs widely
    assert np.abs(res[:, None] - res[None]).max() > 0.2
    np.testing.assert_allclose(res.sum(axis=-1), 1.0, atol=1e-5)


# -- the share test ------------------------------------------------------------------

def test_eight_ranks_add_up_to_the_uncut_layer_with_the_shared_expert_once(
        family):
    """The guide's share test at the deployment's split: ranks 0-7 of 8
    hold experts 0-7, ..., 56-63 of 64, four a token by the biased sigmoid
    score, weights renormalised and doubled, and EVERY rank computes the
    shared expert; the routed parts add up, with the shared expert counted
    once, to what the uncut reference gives for the whole layer."""
    d, width, n, k, t = 32, 16, 64, 4, 48
    conf = dict(num_experts_per_tok=k, norm_topk_prob=True,
                routed_scaling_factor=2, held_experts=[0, n])
    rng = np.random.default_rng(71)
    routed = [draw(rng, n, d, width, fan_in=d),
              draw(rng, n, d, width, fan_in=d),
              draw(rng, n, width, d, fan_in=width),
              jnp.asarray(0.05 * rng.standard_normal(n), jnp.float32),
              draw(rng, n, d, fan_in=d)]
    shared = [draw(rng, width, d, fan_in=d), draw(rng, width, d, fan_in=d),
              draw(rng, d, width, fan_in=width)]
    u = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    want = compiled(lambda: family.sparse(u, routed + shared, conf))
    alone = compiled(lambda: family.sparse(
        u, [w * 0 for w in routed[:3]] + routed[3:] + shared, conf))
    parts, rows = [], 0
    for first in range(0, n, 8):
        layer = nn.RoutedExperts(d, width, n, k, held=(first, 8),
                                 shared_width=width, routed_scale=2.0,
                                 score="sigmoid", select_bias=True)
        e_gate, e_up, e_down, bias, w_r = routed
        load_state_dict(layer, {
            "experts_gate": e_gate[first:first + 8],
            "experts_up": e_up[first:first + 8],
            "experts_down": e_down[first:first + 8],
            "select_bias": bias, "router.weight": w_r,
            "shared.gate_proj.weight": shared[0],
            "shared.up_proj.weight": shared[1],
            "shared.down_proj.weight": shared[2]}, strict=False)
        out, state = call(layer, u)
        parts.append(out)
        rows += int(np.asarray(state["held_load"])[:-1].sum())
    assert rows == t * k                        # every assignment once
    np.testing.assert_allclose(sum(parts) - 7 * np.asarray(alone), want,
                               rtol=2e-5, atol=2e-5)


# -- the plan -------------------------------------------------------------------------

PLANS = {"expert-layer": dict(num_hidden_layers=1, first_layer=2),
         "the-cut": {}}


@pytest.mark.parametrize("over", PLANS.values(), ids=PLANS)
def test_xing4_plan_loss_and_every_gradient_match_the_reference(over, family):
    """``build_decoder_lm`` on a latent-attention layer with the routed
    feed-forward, and on the cut (published layers 1-3 of the tiny plan:
    dense, sparse, sparse), four streams, head untied: the loss and every
    leaf's gradient, on seeded weights."""
    assert family.layers_of(tiny_conf()) == ["dense", "sparse", "sparse"]
    check_loss_and_every_gradient(family, tiny_conf(**over), 13,
                                  zero_gradient_leaves=("expert_bias",))


def test_the_builder_names_the_latent_kind_and_leaves_other_plans_alone():
    plan = models.tiny_decoder_plan(64)
    bad = list(plan.layers) + [models.LayerPlan("mla", 4, "dense")]
    with pytest.raises(ValueError, match="full, window, latent, linear"):
        models.build_decoder_lm(plan._replace(layers=bad))
    assert (plan.q_rank, plan.kv_rank, plan.rope_dim, plan.value_dim,
            plan.residual_streams, plan.sinkhorn_iters, plan.residual_clamp,
            plan.residual_eps) == (0, 0, 0, 0, 1, 20, 30.0, 1e-6)
    model = models.build_decoder_lm(plan)
    assert not [k for k in state_dict(model) if "hc_" in k or "mhc" in k]
    assert len(model.layers) == 1 + 4 + 2       # no expansion, no sum


# -- through the Optimizer, traced -----------------------------------------------------

def test_xing4_plan_trains_through_local_optimizer_and_is_traced(tmp_path,
                                                                 family):
    """The tiny cut through ``LocalOptimizer``: the loss falls, the run log
    carries the latent layers' ``kernel/dispatch`` instants, a
    ``residual/mhc`` instant a path, the kept four-stream inputs and the
    ``mhc/*`` counters of every path of every layer, and the Optimizer's
    own log the last step's."""
    conf = tiny_conf()
    x, y = family.make_records(3, 8, conf)
    events, said = train_through_local_optimizer(
        family.build(conf), family.criterion(), zip(x, y), tmp_path, epochs=5)
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 10 and steps[-1]["loss"] < steps[0]["loss"]
    legs = [e for e in events if e.get("name") == "kernel/dispatch"
            and e["op"] == "latent_attention"]
    assert legs and {(e["backend"], e["heads"], e["qk_dim"], e["rope_dim"],
                      e["value_dim"], e["q_rank"], e["kv_rank"])
                     for e in legs} == {("xla", 4, 24, 8, 12, 24, 16)}
    assert legs[0]["scale"] == pytest.approx(family.softmax_scale(conf))
    paths = [e for e in events if e.get("name") == "residual/mhc"]
    assert paths and {(e["streams"], e["sinkhorn_iters"], e["clamp"],
                       e["embed_dim"], e["dtype"]) for e in paths} == {
        (4, 20, 30.0, 64, "float32")}
    kept = [e for e in events if e.get("name") == "remat/keep"
            and e["kept"] == "residual_streams"]
    assert kept and {(e["streams"], tuple(e["shape"]), e["bytes"])
                     for e in kept} == {(4, (4, 48, 256), 4 * 48 * 256 * 4)}
    routes = [e for e in events if e.get("name") == "moe/route"]
    assert routes and {(e["score"], e["select_bias"], e["shared"],
                        e["experts"], e["held"], e["top_k"])
                       for e in routes} == {("sigmoid", True, True, 16, 4, 4)}
    wanted = {f"{i}.0.hc_{part}" for i in (2, 3, 4)
              for part in ("attn", "ffn")}
    for name in ("mhc/col_err_max", "mhc/res_offdiag_mean", "mhc/pre_mean",
                 "mhc/post_mean"):
        seen = [e for e in events if e.get("name") == name]
        assert len(seen) == 10 * 6              # steps x paths
        assert {e["layer"] for e in seen} == wanted
    # the builder's own start is one stream's: H_res near the identity,
    # H_pre reading stream 0 alone, H_post writing 1 to each
    value = lambda name: [e["value"] for e in events  # noqa: E731
                          if e.get("name") == name]
    assert all(0 <= v < 1e-3 for v in value("mhc/col_err_max"))
    assert all(0 <= v < 0.01 for v in value("mhc/res_offdiag_mean"))
    assert all(0.24 < v < 0.3 for v in value("mhc/pre_mean"))
    assert all(0.8 < v < 1.3 for v in value("mhc/post_mean"))
    assert len([m for m in said if "mhc/" in m]) == 6 * 4
