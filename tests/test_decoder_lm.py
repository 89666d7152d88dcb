"""The decoder builder and its layers (RMS norm, rotary, grouped-query
window and full attention, per-head gate, gated and routed feed-forward)
against the plain reference ``benchmark/models/laguna.py`` at a tiny
plan, in float32 on the CPU: loss and every leaf's gradient; the share
test (what all shares of a sparse layer give adds up to the uncut
layer); dropless routing at both extremes of imbalance; the windowed
flash kernels in interpret mode; the rotary tables against the formula.
"""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.module import functional_call, state_dict
from bigdl_tpu.ops.attention import (dot_product_attention, flash_attention,
                                     flash_blocks)
import decoder_cases
from decoder_cases import (ROOT, call, check_loss_and_every_gradient,
                           check_routed_gradients, compiled, drawn, routed,
                           sparse_weights, train_through_local_optimizer)

tiny_conf = functools.partial(decoder_cases.tiny_conf, "laguna")


@pytest.fixture(scope="module")
def laguna():
    return decoder_cases.family("laguna")


def _plan_of(kinds):
    """layer_types / mlp_layer_types / heads for a list of (attention,
    ffn) pairs: window layers carry 6 query heads, full layers 4."""
    return dict(
        num_hidden_layers=len(kinds),
        layer_types=["sliding_attention" if a == "window"
                     else "full_attention" for a, _ in kinds],
        mlp_layer_types=[f for _, f in kinds],
        num_attention_heads_per_layer=[6 if a == "window" else 4
                                       for a, _ in kinds])


LAYER_KINDS = {
    "full-dense": [("full", "dense")],
    "window-sparse": [("window", "sparse")],
    "full-sparse": [("full", "sparse")],
    "window-dense": [("window", "dense")],
    "whole-plan": [("full", "dense"), ("window", "sparse"),
                   ("window", "sparse"), ("full", "sparse")],
}


@pytest.mark.parametrize("kinds", LAYER_KINDS.values(), ids=LAYER_KINDS)
def test_loss_and_every_gradient_match_the_plain_reference(kinds, laguna):
    check_loss_and_every_gradient(laguna, tiny_conf(**_plan_of(kinds)), 11)


def test_the_shares_of_a_sparse_layer_add_up_to_the_uncut_layer(laguna):
    """The guide's share test: 4 shares of 4 of the 16 experts, the
    shared expert (which every chip computes alike) counted once, give
    what the uncut reference gives for the whole layer."""
    conf = tiny_conf()
    weights = sparse_weights(conf, 3, experts=16)
    u = jnp.asarray(np.random.default_rng(4).standard_normal((48, 64)),
                    jnp.float32)
    whole = dict(conf, held_experts=[0, 16])
    want = compiled(lambda v, ws: laguna._sparse(v, ws, whole, None),
                    u, weights)
    shared = compiled(lambda v, ws: laguna._gated(v, ws, None),
                      u, weights[4:])
    parts, rows = [], 0
    for share in range(4):
        out, state = call(routed(conf, (4 * share, 4), weights), u)
        parts.append(out - shared)
        rows += int(np.asarray(state["held_load"])[:-1].sum())
    assert rows == 48 * conf["num_experts_per_tok"]  # every assignment once
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("every_token,published", [(True, 32), (True, 3),
                                                   (False, 32)],
                         ids=["all-held-chosen-exact-path",
                              "all-held-chosen-fast-path",
                              "none-held-chosen"])
def test_routing_drops_nothing_at_either_extreme(every_token, published,
                                                 laguna):
    """A router biased so that every token picks every held expert (the 3
    held: top-3; the load is 3 x tokens.  Of 32 experts that is over the
    fast path's capacity and the exact path runs; of 3, all held, the
    capacity is the worst case and no exact path is built) and one so
    that none does: all equal the dense-mask reference."""
    conf = tiny_conf(held_experts=[0, 3], num_experts=3,
                     num_experts_published=published)
    weights = sparse_weights(conf, 5, experts=3)
    u = np.random.default_rng(6).standard_normal((64, 64)) + 2.0
    u = jnp.asarray(u, jnp.float32)
    push = 10.0 if every_token else -10.0
    weights[3] = weights[3].at[:3].add(push / 64.0)  # u . 1 is ~128
    layer = routed(conf, (0, 3), weights)
    assert layer.capacity(64) == (72 if published == 32 else 192)
    out, state = call(layer, u)
    load = np.asarray(state["held_load"])
    sparse = lambda v, ws: laguna._sparse(v, ws, conf, None)  # noqa: E731
    np.testing.assert_allclose(out, compiled(sparse, u, weights),
                               rtol=2e-4, atol=2e-5)
    if every_token:
        # rows an expert, then the rows that took the exact path
        assert list(load) == [64, 64, 64, 192 if published == 32 else 0]
        # and the path taken is differentiated like the reference
        check_routed_gradients(layer, u, sparse, weights)
    else:
        assert list(load) == [0, 0, 0, 0]
        shared = compiled(lambda v, ws: laguna._gated(v, ws, None),
                          u, weights[4:])
        np.testing.assert_allclose(out, shared, rtol=2e-4, atol=2e-5)


def test_a_held_expert_without_rows_has_a_zero_gradient():
    conf = tiny_conf()
    weights = sparse_weights(conf, 7, experts=4)
    weights[3] = weights[3].at[1].add(-8.0 / 64.0)   # nobody picks expert 1
    layer = routed(conf, (0, 4), weights)
    u = jnp.asarray(np.random.default_rng(8).standard_normal((32, 64)) + 2.0,
                    jnp.float32)
    params = state_dict(layer, kind="param")
    buffers = state_dict(layer, kind="buffer")
    grads = jax.jit(jax.grad(lambda p: jnp.sum(functional_call(
        layer, {**p, **buffers}, u)[0] ** 2)))(params)
    for name in ("experts_gate", "experts_up", "experts_down"):
        assert float(jnp.max(jnp.abs(grads[name][1]))) == 0.0
        assert float(jnp.max(jnp.abs(grads[name][0]))) > 0.0


class _FastPath(nn.RoutedExperts):
    """``RoutedExperts``' fast path alone over a routing handed in:
    input ``(x2, w, local)``, the order whole, its last movement in the
    form ``fold`` names."""

    fold = True

    def update_output(self, input):
        x2, w, local = input
        counts = jnp.sum(
            local.reshape(-1, 1) == jnp.arange(self.count + 1)[None, :],
            axis=0, dtype=jnp.int32)
        return self._grouped(x2, w, local, counts, cap=local.size,
                             fold=self.fold)


@functools.lru_cache(maxsize=None)
def _both_forms(top_k):
    """One compiled program a ``top_k``: value and every gradient of the
    fast path over 4 held experts, folded and scatter-added."""
    layer = _FastPath(16, 24, 8, top_k, held=(0, 4))
    params = {k: v for k, v in state_dict(layer, kind="param").items()
              if k.startswith("experts_")}
    buffers = state_dict(layer, kind="buffer")

    def value_and_gradients(fold, p, x2, w, local):
        def loss(p, x2, w):
            layer.fold = fold
            y, _ = functional_call(layer, {**p, **buffers}, (x2, w, local))
            return jnp.sum(y ** 2), y
        (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)(p, x2, w)
        return y, grads

    def both(x2, w, local):
        return tuple(value_and_gradients(fold, params, x2, w, local)
                     for fold in (True, False))

    return jax.jit(both)


def _routing(name, tokens=12, held=4):
    """``local [tokens, top_k]``: the held expert of each choice, ``held``
    where the chosen expert is not held."""
    rng = np.random.default_rng(len(name))
    if name == "an-expert-without-rows":
        return rng.choice([0, 2, 3, held], (tokens, 3))
    if name == "every-row-on-one-expert":
        return np.stack([np.full(tokens, 2), np.full(tokens, held),
                         np.full(tokens, held)], axis=1)
    if name == "nothing-held":
        return np.full((tokens, 3), held)
    if name == "everything-held":
        return np.stack([rng.permutation(held)[:3] for _ in range(tokens)])
    if name == "top-1":
        return rng.integers(0, held + 1, (tokens, 1))
    assert name == "tokens-all-held-and-none-held"
    local = rng.integers(0, held + 1, (tokens, 3))
    local[::3] = [0, 1, 3]
    local[1::3] = held
    return local


ROUTINGS = ("an-expert-without-rows", "every-row-on-one-expert",
            "nothing-held", "everything-held", "top-1",
            "tokens-all-held-and-none-held")


@pytest.mark.parametrize("name", ROUTINGS)
def test_fold_gives_what_the_scatter_add_gives(name):
    """Where the sorted order is whole, the fast path ends in a gather by
    the order's inverse and a sum over ``top_k``; the same inputs through
    the scatter-add form give the same values and the same gradients by
    ``x``, the weights and the three expert stacks."""
    local = _routing(name)
    rng = np.random.default_rng(21)
    x2 = rng.standard_normal((local.shape[0], 16)).astype(np.float32)
    w = rng.uniform(0.1, 1.0, local.shape).astype(np.float32)
    folded, scattered = _both_forms(local.shape[1])(
        x2, w, local.astype(np.int32))
    got, want = jax.tree.leaves(folded), jax.tree.leaves(scattered)
    assert len(got) == 1 + 3 + 2
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    live = bool((local < 4).any())
    assert bool(np.any(np.asarray(got[0]))) == live


@pytest.mark.parametrize("dtype,top_k", [("float32", 3), ("float32", 1),
                                         ("bfloat16", 4)])
def test_spread_and_fold_are_each_the_others_transpose(dtype, top_k):
    """``_spread`` and ``_fold`` against autodiff of their plain forms
    ``x[token]`` and ``zeros.at[token].add(float32(rows) * w_row)``: the
    values, and the cotangents by ``x``, the rows and the weights.  In
    bfloat16 the plain gather's transpose adds in bfloat16 and
    ``_spread``'s in float32: never further from the float32 sum."""
    from bigdl_tpu.nn.layers import moe

    tokens, d = 10, 8
    rng = np.random.default_rng(top_k)
    order = rng.permutation(tokens * top_k).astype(np.int32)
    token = order // top_k
    x = jnp.asarray(rng.standard_normal((tokens, d)), dtype)
    rows = jnp.asarray(rng.standard_normal((tokens * top_k, d)), dtype)
    w_row = rng.uniform(0.0, 1.0, tokens * top_k).astype(np.float32)
    dy = rng.standard_normal((tokens, d)).astype(np.float32)

    def run(x, rows, w_row, dy):
        places = moe._places(order, top_k)
        plain_spread = lambda v: v[token]                    # noqa: E731
        plain_fold = lambda r, w: jnp.zeros(                 # noqa: E731
            (tokens, d), jnp.float32).at[token].add(
                r.astype(jnp.float32) * w[:, None])
        out = []
        for spread, fold in (
                (lambda v: moe._spread(v, token, places, top_k),
                 lambda r, w: moe._fold(r, w, token, places, top_k)),
                (plain_spread, plain_fold)):
            moved, back = jax.vjp(spread, x)
            y, fold_back = jax.vjp(fold, rows, w_row)
            out.append((moved, back(rows)[0], y) + fold_back(dy))
        exact = jnp.zeros(
            (tokens, d), jnp.float32).at[token].add(
                rows.astype(jnp.float32))
        return out[0], out[1], exact

    got, want, exact = jax.jit(run)(x, rows, w_row, dy)
    for name, a, b in zip(("rows", "dx", "y", "drows", "dw_row"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        if dtype == "bfloat16" and name == "dx":
            continue
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6, err_msg=name)
    exact = np.asarray(exact)
    gap = [float(np.abs(np.asarray(v[1], np.float32) - exact).max())
           for v in (got, want)]
    assert gap[0] <= gap[1] + 1e-6
    assert gap[0] <= 2.0 ** -8 * float(np.abs(exact).max()) + 1e-6


@pytest.mark.parametrize("held,combine", [((0, 16), "fold"),
                                          ((0, 2), "scatter_add")],
                         ids=["whole-order", "prefix-under-cond"])
def test_route_instant_names_the_combine(held, combine, monkeypatch):
    """A layer that holds every expert sorts ALL its assignments
    (``capacity`` = ``tokens x top_k``) and folds; one that holds a share
    sorts a prefix under ``lax.cond`` and keeps the scatter-add."""
    from bigdl_tpu import telemetry

    said = []
    monkeypatch.setattr(telemetry, "instant",
                        lambda name, **attrs: said.append((name, attrs)))
    layer = nn.RoutedExperts(16, 24, 16, 2, held=held)
    text = jax.jit(lambda s, v: functional_call(layer, s, v)[0]).lower(
        state_dict(layer), jnp.zeros((64, 16), jnp.float32)).as_text()
    (route,) = [attrs for name, attrs in said if name == "moe/route"]
    assert route["combine"] == combine
    assert (route["capacity"] == 64 * 2) == (combine == "fold")
    assert route["worst"] == 64 * 2
    branches = "stablehlo.case" in text or "stablehlo.if" in text
    assert branches == (combine == "scatter_add")


# (query heads, kv heads, sequence, window, block_q, block_k): group sizes
# 6 and 9, a sequence the preferred block does not divide, windows smaller
# and larger than a block, and no window
FLASH_CASES = [(12, 2, 48, 8, 16, 16), (18, 2, 40, 8, 16, 16),
               (12, 2, 48, 24, 16, 8), (9, 1, 64, 5, 32, 16),
               (6, 1, 64, None, 16, 16)]


@pytest.mark.parametrize("h,g,s,window,bq,bk", FLASH_CASES)
def test_windowed_grouped_flash_matches_dense(h, g, s, window, bq, bk):
    keys = jax.random.split(jax.random.key(h + s), 4)
    q, k, v, do = (drawn(jax.random.normal, key, (2, heads, s, 16))
                   for key, heads in zip(keys, (h, g, g, h)))

    def both(attend):
        def run(q, k, v):
            out, vjp = jax.vjp(attend, q, k, v)
            return (out,) + vjp(do)
        with jax.default_matmul_precision("highest"):
            return jax.jit(run)(q, k, v)

    got = both(lambda *a: flash_attention(
        *a, causal=True, window=window, block_q=bq, block_k=bk,
        interpret=True))
    want = both(lambda *a: dot_product_attention(
        *a, causal=True, window=window))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("h,g,s,window,bq,bk", [(4, 4, 128, None, 32, 32),
                                                (12, 2, 48, 8, 16, 16)],
                         ids=["equal-heads-as-the-older-callers", "windowed"])
def test_flash_in_bfloat16_stays_within_two_roundings(h, g, s, window, bq, bk):
    """The kernels multiply in the inputs' type (float32 accumulation,
    float32 softmax statistics), so with bfloat16 inputs the
    probabilities are rounded before the products; every caller
    (``transformer``, ``MultiHeadAttention``, sequence parallelism) gets
    this.  Held: output and the three gradients within 2**-7 of the
    float32 result's largest element (worst measured 0.0053; the kernels
    that upcast their operands read 0.0033, the output's own rounding)."""
    keys = jax.random.split(jax.random.key(h + s), 4)
    shapes = [(2, h, s, 16), (2, g, s, 16), (2, g, s, 16), (2, h, s, 16)]
    low = [drawn(jax.random.normal, k, sh).astype(jnp.bfloat16)
           for k, sh in zip(keys, shapes)]

    def both(attend, q, k, v, do):
        def run(q, k, v, do):
            out, vjp = jax.vjp(attend, q, k, v)
            return (out,) + vjp(do)
        return jax.jit(run)(q, k, v, do)

    got = both(lambda *a: flash_attention(
        *a, causal=True, window=window, block_q=bq, block_k=bk,
        interpret=True), *low)
    with jax.default_matmul_precision("highest"):
        want = both(lambda *a: dot_product_attention(
            *a, causal=True, window=window),
            *[a.astype(jnp.float32) for a in low])
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        b = np.asarray(b)
        gap = float(np.abs(np.asarray(a, np.float32) - b).max())
        assert gap <= 2.0 ** -7 * float(np.abs(b).max())


def test_a_window_bounds_the_key_blocks_a_query_block_visits():
    bq, bk, visited, total = flash_blocks(8192, 8192, True, 512)
    assert (bq, bk) == (512, 512) and total == 16 * 16
    assert visited == 1 + 15 * 2              # 2 key blocks a query block
    _, _, causal_visited, causal_total = flash_blocks(8192, 8192, True)
    assert causal_visited == sum(2 * (i + 1) for i in range(8))
    assert causal_total == 8 * 16


def _direct_tables(r, head_dim, positions):
    """The published ``rope_parameters`` entry evaluated element by
    element, with nothing shared with the program or the reference."""
    dims = int(head_dim * r["partial_rotary_factor"])
    cos = np.zeros((positions, dims // 2))
    sin = np.zeros((positions, dims // 2))
    for j in range(dims // 2):
        inv = r["rope_theta"] ** (-2.0 * j / dims)
        scale = 1.0
        if r["rope_type"] == "yarn":
            def dim_of(rot):
                return dims * math.log(
                    r["original_max_position_embeddings"]
                    / (rot * 2 * math.pi)) / (2 * math.log(r["rope_theta"]))
            low = max(math.floor(dim_of(r["beta_fast"])), 0)
            high = min(math.ceil(dim_of(r["beta_slow"])), dims - 1)
            ramp = min(max((j - low) / (high - low), 0.0), 1.0)
            inv = inv / r["factor"] * ramp + inv * (1.0 - ramp)
            scale = r["attention_factor"]
        for p in range(positions):
            cos[p, j] = math.cos(p * inv) * scale
            sin[p, j] = math.sin(p * inv) * scale
    return cos, sin


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_rotary_tables_are_the_published_formula(kind, laguna):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna_s_2_1.json")) as fh:
        published = json.load(fh)
    r = published["rope_parameters"][kind]
    want_cos, want_sin = _direct_tables(r, 128, 40)
    ref_cos, ref_sin, dims = laguna.rotary_tables(r, 128, 40)
    assert dims == (64 if kind == "full_attention" else 128)
    rotary = nn.Rotary(dims, theta=r["rope_theta"],
                       factor=r.get("factor", 1.0),
                       original_max_position=r.get(
                           "original_max_position_embeddings", 0),
                       beta_fast=r.get("beta_fast", 32.0),
                       beta_slow=r.get("beta_slow", 1.0),
                       attention_factor=r.get("attention_factor", 1.0))
    cos, sin = rotary.tables(40)
    for got in ((cos, sin), (ref_cos, ref_sin)):
        np.testing.assert_allclose(got[0], want_cos, rtol=0, atol=2e-6)
        np.testing.assert_allclose(got[1], want_sin, rtol=0, atol=2e-6)
    if kind == "full_attention":   # YaRN: slow dimensions are interpolated
        plain = nn.Rotary(dims, theta=r["rope_theta"]).inv_freq()
        assert rotary.inv_freq()[0] == pytest.approx(plain[0])
        assert rotary.inv_freq()[-1] == pytest.approx(plain[-1] / 128)


def test_rms_norm_takes_its_statistics_in_float32():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((4, 64)) * 50,
                    jnp.bfloat16)
    norm = nn.RMSNorm(64, eps=1e-6)
    out = norm.forward(x)
    x32 = np.asarray(x, np.float32)
    want = x32 / np.sqrt((x32 ** 2).mean(-1, keepdims=True) + 1e-6)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), want, rtol=1e-2)


def test_decoder_block_is_two_residual_branches():
    """``nn.DecoderBlock`` over a ``nn.GroupedQueryAttention`` and a
    ``nn.GatedMLP``: ``h = x + attn(norm1(x))``, ``y = h + ffn(norm2(h))``,
    and the window reaches the attention's XLA leg."""
    attn = nn.GroupedQueryAttention(64, 6, 2, 16, window=4,
                                    rotary=nn.Rotary(16), gate="per_head")
    ffn = nn.GatedMLP(64, 96)
    block = nn.DecoderBlock(64, attn, ffn, eps=1e-6)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 12, 64)),
                    jnp.float32)
    h = x + call(attn, call(block.norm1, x)[0])[0]
    want = h + call(ffn, call(block.norm2, h)[0])[0]
    np.testing.assert_allclose(call(block, x)[0], want, rtol=1e-5, atol=1e-6)
    # position 11 sees positions 8..11 only: an earlier token moves nothing
    moved = call(block, x.at[:, 3].add(1.0))[0]
    np.testing.assert_allclose(moved[:, 11], want[:, 11], rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(moved[:, 5] - want[:, 5]))) > 1e-3
    with pytest.raises(ValueError):
        nn.GroupedQueryAttention(64, 6, 4, 16)


def test_registry_decoder_trains_through_local_optimizer_and_is_traced(
        tmp_path):
    """``cli train --model decoder_lm`` in small: the registry's plan
    through ``LocalOptimizer`` with the LM criterion; the run log carries
    the attention legs, the routed layers and their load, and the
    Optimizer's own log the last step's load of every routed layer."""
    from bigdl_tpu.models import registry

    model = registry.build_model("decoder_lm", 64)
    crit, target = registry.train_pieces("decoder_lm", 4)
    assert target.shape == (4, registry.LM_SEQ_LEN)
    ids = np.random.default_rng(0).integers(0, 64, (8, 33)).astype(np.int32)
    events, said = train_through_local_optimizer(
        model, crit, [(row[:-1], row[1:]) for row in ids], tmp_path, epochs=6)
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 12 and steps[-1]["loss"] < steps[0]["loss"]
    legs = [e for e in events if e.get("name") == "kernel/dispatch"
            and e["op"] == "attention"]
    assert {(e["window"], e["q_heads"], e["kv_heads"]) for e in legs} == \
        {(None, 4, 2), (8, 6, 2)}
    routes = [e for e in events if e.get("name") == "moe/route"]
    assert routes and routes[0]["experts"] == 16 and routes[0]["held"] == 4 \
        and routes[0]["top_k"] == 3 and routes[0]["capacity"] == 384 \
        and routes[0]["combine"] == "fold"          # 384 = 4 x 32 x 3
    load = [e for e in events if e.get("name") == "moe/load"]
    assert len(load) == 12 * 3 * 4          # steps x sparse layers x held
    by_step = sum(e["value"] for e in load[:12])  # the first step's
    assert 0 < by_step <= 3 * 4 * 32 * 3
    assert {e["name"] for e in events if e["kind"] == "counter"} >= \
        {"moe/load", "moe/exact_rows"}
    assert len(said) == 3 * 5               # sparse layers x counter names
    assert [int(v) for v in said[0].split("moe/load ")[1].split()] == \
        [e["value"] for e in load[-12:-8]]  # the last step's first layer


def test_cli_perf_runs_the_decoder(capsys):
    from bigdl_tpu.models import cli

    cli.main(["perf", "--model", "decoder_lm", "-b", "2", "-i", "1",
              "--warmup", "1", "--no-bf16"])
    assert "records/sec" in capsys.readouterr().out
