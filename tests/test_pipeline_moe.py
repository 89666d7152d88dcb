"""Pipeline parallelism (``parallel/pipeline.py``) and expert-parallel
MoE (``nn/layers/moe.py``) on the virtual 8-device CPU mesh: pipelined /
expert-sharded execution must be numerically equivalent to the plain
sequential computation, including gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.parallel.mesh import make_mesh
from bigdl_tpu.parallel.pipeline import make_pipeline_fn


def _block(params, h):
    w, b = params
    return jnp.tanh(h @ w + b)


def _stacked_blocks(s, d, seed=0):
    rng = np.random.RandomState(seed)
    w = jnp.asarray(rng.randn(s, d, d).astype(np.float32) * 0.4)
    b = jnp.asarray(rng.randn(s, d).astype(np.float32) * 0.1)
    return (w, b)


def _sequential_ref(stacked, x):
    w, b = stacked

    def body(h, wb):
        return _block(wb, h), None

    h, _ = jax.lax.scan(body, x, (w, b))
    return h


@pytest.mark.parametrize("n_micro", [4, 8])
def test_pipeline_matches_sequential(n_micro):
    s, d, batch = 4, 6, 16
    mesh = make_mesh((s,), ("pipe",), devices=jax.devices()[:s])
    stacked = _stacked_blocks(s, d)
    x = jnp.asarray(np.random.RandomState(1).randn(batch, d)
                    .astype(np.float32))
    fn = jax.jit(make_pipeline_fn(_block, mesh, n_micro))
    got = fn(stacked, x)
    want = _sequential_ref(stacked, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_gradients_match_sequential():
    """jax.grad through the ppermute schedule IS pipelined backprop; it
    must agree with plain backprop."""
    s, d, batch, n_micro = 4, 5, 8, 4
    mesh = make_mesh((s,), ("pipe",), devices=jax.devices()[:s])
    stacked = _stacked_blocks(s, d, seed=2)
    x = jnp.asarray(np.random.RandomState(3).randn(batch, d)
                    .astype(np.float32))
    fn = make_pipeline_fn(_block, mesh, n_micro)

    # under jit, as a train step differentiates it: an eager shard_map
    # dispatches every primitive of the schedule as its own 4-device program
    g_pipe = jax.jit(jax.grad(lambda p: jnp.sum(fn(p, x) ** 2)))(stacked)
    g_ref = jax.grad(lambda p: jnp.sum(_sequential_ref(p, x) ** 2))(stacked)
    for a, b in zip(g_pipe, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_under_jit_with_data_axis():
    """pipe composes with a data axis: jit the pipelined fn over a
    (data=2, pipe=4) mesh."""
    s, d, batch = 4, 4, 8
    mesh = make_mesh((2, s), ("data", "pipe"))
    stacked = _stacked_blocks(s, d, seed=4)
    x = jnp.asarray(np.random.RandomState(5).randn(batch, d)
                    .astype(np.float32))
    fn = jax.jit(make_pipeline_fn(_block, mesh, 4))
    np.testing.assert_allclose(np.asarray(fn(stacked, x)),
                               np.asarray(_sequential_ref(stacked, x)),
                               rtol=1e-5, atol=1e-6)


# ----------------------------- MoE ----------------------------------------

def _moe_reference(m, x):
    """Direct per-token computation honoring the router's dispatch/combine
    (including capacity drops)."""
    dispatch, combine = m._route(x)
    t, e, c = dispatch.shape
    y = np.zeros((t, m.d_model), np.float32)
    w1, b1 = np.asarray(m.experts_w1), np.asarray(m.experts_b1)
    w2, b2 = np.asarray(m.experts_w2), np.asarray(m.experts_b2)
    xd = np.asarray(x, np.float32)
    disp = np.asarray(dispatch)
    comb = np.asarray(combine)
    for ti in range(t):
        for ei in range(e):
            for ci in range(c):
                if disp[ti, ei, ci] > 0:
                    h = np.maximum(xd[ti] @ w1[ei] + b1[ei], 0.0)
                    y[ti] += comb[ti, ei, ci] * (h @ w2[ei] + b2[ei])
    return y


def test_moe_matches_per_token_reference():
    m = nn.MixtureOfExperts(8, 16, 4, top_k=2, capacity_factor=1.0)
    x = jnp.asarray(np.random.RandomState(6).randn(20, 8)
                    .astype(np.float32))
    got = np.asarray(m.forward(x))
    want = _moe_reference(m, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_tokens():
    """With capacity_factor tiny, overflow tokens get zero output."""
    m = nn.MixtureOfExperts(4, 8, 2, top_k=1, capacity_factor=0.25)
    x = jnp.asarray(np.random.RandomState(7).randn(16, 4)
                    .astype(np.float32))
    dispatch, _ = m._route(x)
    routed = float(jnp.sum(dispatch))
    assert routed <= 2 * m.capacity(16)  # at most E * C slots filled
    assert routed < 16  # some tokens actually dropped


def test_moe_trains_expert_sharded():
    """MoE trains under the TrainStep with experts sharded over the
    'expert' mesh axis (all-to-all layout), and matches the same training
    run on a single device."""
    import bigdl_tpu.optim as optim
    from bigdl_tpu.nn.layers.moe import expert_sharding_rules
    from bigdl_tpu.parallel.train_step import TrainStep
    from bigdl_tpu.utils.rng import RNG

    def build():
        RNG.set_seed(11)
        return nn.Sequential(
            nn.Linear(6, 8), nn.MixtureOfExperts(8, 16, 4, top_k=2),
            nn.Linear(8, 3), nn.LogSoftMax())

    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(32, 6).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 3, 32))

    mesh = make_mesh((2, 4), ("data", "expert"))
    step = TrainStep(build(), nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.2), mesh=mesh,
                     extra_sharding_rules=expert_sharding_rules())
    ref = TrainStep(build(), nn.ClassNLLCriterion(),
                    optim.SGD(learning_rate=0.2))
    for i in range(4):
        l_sharded = float(step.run(x, y, jax.random.key(i)))
        l_ref = float(ref.run(x, y, jax.random.key(i)))
    assert l_sharded == pytest.approx(l_ref, rel=1e-4)
    # expert stacks actually sharded over the expert axis
    w1 = step.params["1.experts_w1"]
    assert "expert" in str(w1.sharding.spec)
