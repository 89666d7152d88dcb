"""What the tests of the three decoder families share (``test_decoder_lm``:
laguna, ``test_linear_attention``: qwen3_next, ``test_short_conv``: lfm2):
the family module and its tiny configuration, seeded weights of a routed
layer and the layer that holds a share of them, a layer's compiled
forward, the whole-plan check of loss and every gradient against the
family's plain reference, and a short ``LocalOptimizer`` run under
telemetry.  A helper module: it holds no test."""

import importlib
import json
import logging
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.module import functional_call, load_state_dict, state_dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def family(name):
    """``benchmark.models.<name>`` (``benchmark`` is importable from the
    repo root, which the suite runs from)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module("benchmark.models." + name)


def tiny_conf(name, **over):
    """The family's tiny configuration with ``over`` on top: the files
    are the benchmark's, a test that needs another size says so here."""
    with open(os.path.join(ROOT, "benchmark", "tests", "data",
                           f"tiny_{name}.config.json")) as fh:
        conf = json.load(fh)
    conf.update(over)
    return conf


def draw(rng, *shape, fan_in):
    return jnp.asarray(rng.standard_normal(shape) / math.sqrt(fan_in),
                       jnp.float32)


def drawn(sampler, key, shape):
    """``sampler(key, shape)`` of ``jax.random`` to the bit, from ONE
    compiled draw of a fixed length: the stream gives element ``i`` of an
    array the same bits whatever the array's shape, and an eager draw
    compiles a program a shape (1.2 s each at the kernels' test sizes)."""
    flat = np.asarray(sampler(key, (1 << 17,)))
    return jnp.asarray(flat[:math.prod(shape)].reshape(shape))


#: a routed layer's leaves in the order the references take them; the
#: last is there where the shared expert is gated
ROUTED_LEAVES = ("experts_gate", "experts_up", "experts_down",
                 "router.weight", "shared.gate_proj.weight",
                 "shared.up_proj.weight", "shared.down_proj.weight",
                 "shared_gate.weight")


def sparse_weights(conf, seed, experts, shared_gate=False):
    d, w = conf["hidden_size"], conf["moe_intermediate_size"]
    ws = conf["shared_expert_intermediate_size"]
    rng = np.random.default_rng(seed)
    weights = [draw(rng, experts, d, w, fan_in=d),
               draw(rng, experts, d, w, fan_in=d),
               draw(rng, experts, w, d, fan_in=w),
               draw(rng, conf["num_experts_published"], d, fan_in=d),
               draw(rng, ws, d, fan_in=d), draw(rng, ws, d, fan_in=d),
               draw(rng, d, ws, fan_in=ws)]
    return weights + [draw(rng, 1, d, fan_in=d)] if shared_gate else weights


def routed(conf, held, weights):
    """``nn.RoutedExperts`` holding ``held = (first, count)`` of the
    experts of ``sparse_weights``; eight weights gate the shared expert."""
    first, count = held
    layer = nn.RoutedExperts(
        conf["hidden_size"], conf["moe_intermediate_size"],
        conf["num_experts_published"], conf["num_experts_per_tok"],
        held=held, shared_width=conf["shared_expert_intermediate_size"],
        routed_scale=conf.get("moe_routed_scaling_factor", 1.0),
        shared_gate=len(weights) == 8)
    own = [w[first:first + count] for w in weights[:3]] + list(weights[3:])
    load_state_dict(layer, dict(zip(ROUTED_LEAVES, own)), strict=False)
    return layer


def compiled(fn, *args):
    """``fn(*args)`` as one compiled program at the precision the plain
    references take: called eagerly, every primitive of a reference or
    a layer is a program of its own."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


def call(layer, u):
    """``(output, state)`` of the layer's compiled forward."""
    return compiled(lambda s, v: functional_call(layer, s, v),
                     state_dict(layer), u)


def check_routed_gradients(layer, u, sparse, weights):
    """The gradient of ``sum(out ** 2)`` by the routed leaves of ``layer``
    against that of the reference ``sparse(u, weights)``."""
    params = state_dict(layer, kind="param")
    buffers = state_dict(layer, kind="buffer")
    got = compiled(jax.grad(lambda p: jnp.sum(functional_call(
        layer, {**p, **buffers}, u)[0] ** 2)), params)
    ref = compiled(jax.grad(lambda ws: jnp.sum(sparse(u, ws) ** 2)),
                    weights)
    for name, g in zip(ROUTED_LEAVES[:4], ref):
        np.testing.assert_allclose(got[name], g, rtol=2e-3,
                                   atol=1e-3 * float(np.abs(g).max()))


def check_loss_and_every_gradient(family, conf, seed,
                                  zero_gradient_leaves=()):
    """``family.build(conf)`` against ``family.loss_sum`` on the weights
    and two records of ``seed``: the leaves' shapes, the loss, and every
    leaf's gradient relative to its largest element; a leaf whose name
    ends with one of ``zero_gradient_leaves`` has none on either side."""
    from benchmark import reference

    specs = family.param_specs(conf)
    weights = reference.make_weights(specs, seed, conf["init_gain"])
    x, y = family.make_records(seed, 2, conf)
    model = family.build(conf)
    own = state_dict(model, kind="param")
    assert [tuple(v.shape) for v in own.values()] == \
        [tuple(s["shape"]) for s in specs]
    keys, buffers = list(own), state_dict(model, kind="buffer")
    crit = family.criterion()

    def system_loss(params):
        out, _ = functional_call(model, {**params, **buffers},
                                 jnp.asarray(x), training=True,
                                 rng=jax.random.key(0))
        return crit.update_output(out, jnp.asarray(y))

    def reference_loss(params):
        # the family's sum over the records, a record at a time: its
        # program is traced and compiled once, not once a record
        each = jax.lax.map(
            lambda r: family.loss_sum(params, r[0][None], r[1][None],
                                      conf=conf),
            (jnp.asarray(x), jnp.asarray(y)))
        return jnp.sum(each) / len(x)

    with jax.default_matmul_precision("highest"):
        got_loss, got = jax.jit(jax.value_and_grad(system_loss))(
            dict(zip(keys, weights)))
        want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(
            list(weights))
    assert abs(float(got_loss) - float(want_loss)) < 2e-5
    for spec, key, w in zip(specs, keys, want):
        g, w = np.asarray(got[key]), np.asarray(w)    # compared on the host
        if spec["name"].endswith(tuple(zero_gradient_leaves)):
            assert not g.any() and not w.any()
            continue
        scale = max(float(np.abs(w).max()), 1e-6)
        gap = float(np.abs(g - w).max()) / scale
        assert gap < 2e-3, (spec["name"], gap)


class _Keep(logging.Handler):
    """The messages of one logger, whatever an earlier test's redirect
    did to its propagation."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.said = []

    def emit(self, record):
        self.said.append(record.getMessage())


def train_through_local_optimizer(model, criterion, records, tmp_path,
                                  epochs):
    """``records`` (pairs of ids and next ids) through ``LocalOptimizer``
    in batches of 4 under a telemetry run in ``tmp_path``: the run log's
    events, checked against the schema, and the ``[Layer ...`` lines of
    the Optimizer's own log."""
    import bigdl_tpu.optim as optim
    from bigdl_tpu import telemetry
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.telemetry import schema

    logger, keep = logging.getLogger("bigdl_tpu.optim"), _Keep()
    level = logger.level
    logger.addHandler(keep)
    logger.setLevel(logging.INFO)
    telemetry.start_run(str(tmp_path))
    try:
        o = optim.LocalOptimizer(model, [Sample(a, b) for a, b in records],
                                 criterion, batch_size=4,
                                 end_trigger=optim.Trigger.max_epoch(epochs))
        o.set_optim_method(optim.SGD(learning_rate=0.1, momentum=0.9))
        o.optimize()
    finally:
        telemetry.end_run()
        logger.removeHandler(keep)
        logger.setLevel(level)
    events, errors = schema.read_events(str(next(tmp_path.glob("*.jsonl"))))
    assert not errors and not schema.validate_events(events)
    return events, [m for m in keep.said if m.startswith("[Layer ")]
