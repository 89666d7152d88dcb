"""Each plain reference against the system, at a tiny batch on the CPU
in float32, where the two must agree to rounding: same loss, same
gradient, leaf by leaf.  Also pins ``flops_per_record`` of each
configuration file to the derivation in its family module."""

import json
import os

import numpy as np
import pytest

from benchmark import models

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

#: (configuration, batch, worst-leaf tolerance).  ResNet-50 at a test's
#: batch is ill-conditioned: a 1e-6 change of the input moves the SYSTEM's
#: own float32 gradient by 3% (norm of the difference) through 53 batch
#: normalisations over a handful of rows, so two float32 programs that
#: order their sums differently agree on leaf norms only to a few percent
FAMILIES = [("inception_v1_imagenet", 4, 2e-3),
            ("resnet50_imagenet", 16, 5e-2)]


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name,batch,tol", FAMILIES)
def test_reference_matches_system_in_float32(name, batch, tol):
    import jax
    import jax.numpy as jnp

    from benchmark import optimizers, reference
    from bigdl_tpu.nn.module import functional_call, load_state_dict, \
        state_dict

    conf = dict(config(name), classes=10)
    family = models.load(conf)
    specs = family.param_specs(conf)
    weights = reference.make_weights(specs, 7, conf["init_gain"])
    x, y = models.make_records(family, 7, batch, conf)
    model = family.build(conf)
    own = state_dict(model, kind="param")
    assert [tuple(v.shape) for v in own.values()] == \
        [tuple(s["shape"]) for s in specs]
    load_state_dict(model, dict(zip(own, weights)), strict=False)
    keys = list(own)
    buffers = state_dict(model, kind="buffer")
    crit = family.criterion()

    def system_loss(params):
        with jax.default_matmul_precision("highest"):
            out, _ = functional_call(model, {**params, **buffers},
                                     jnp.asarray(x), training=True,
                                     rng=jax.random.key(0))
            return crit.update_output(out, jnp.asarray(y))

    got_loss, got = jax.value_and_grad(system_loss)(
        dict(zip(keys, weights)))
    want = reference.follow(family, weights, [(x, y)],
                            optimizers.load(conf), conf)
    assert abs(float(got_loss) - want["losses"][0]) < 1e-4
    got_norms = reference.leaf_norms([got[k] for k in keys])
    scale = np.maximum(want["grad1_norms"], np.median(want["grad1_norms"]))
    assert np.max(np.abs(got_norms - want["grad1_norms"]) / scale) < tol


@pytest.mark.parametrize("name,batch,tol", FAMILIES)
def test_flops_per_record_is_the_derivation(name, batch, tol):
    conf = config(name)
    family = models.load(conf)
    assert family.flops_per_record(conf)["total"] == conf["flops_per_record"]
    assert sum(int(np.prod(s["shape"])) for s in family.param_specs(conf)) \
        == conf["parameters"]
