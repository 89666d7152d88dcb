"""Numerical-gradient validation for layers WITHOUT a PyTorch oracle
(SURVEY §4's gradient-check discipline — the reference cross-checks every
layer's backward against either Torch or a numeric differentiator).
``jax.test_util.check_grads`` compares each layer's VJP against finite
differences, so custom-VJP layers and composite normalizations get a
backward check even where no framework oracle exists.

Every case runs under BOTH kernel-dispatch legs (``BIGDL_KERNELS=xla``
and ``=pallas``): each of these layers routes through a
``bigdl_tpu.ops`` custom-VJP op whose hand-derived exact cotangent must
hold whether the backend is the XLA reference or the Pallas kernel (in
interpret mode on the CPU suite — the identical code path that Mosaic
compiles on TPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.test_util import check_grads

_enable_x64 = jax.enable_x64

import bigdl_tpu.nn as nn
from bigdl_tpu.utils.rng import RNG


def _layer_fn(layer):
    layer.evaluate()  # freeze any stochastic/stat behavior

    def fn(x):
        return layer.update_output(x)

    # compiled: check_grads evaluates fn and its VJP several times, and
    # run eagerly a Pallas kernel in interpret mode dispatches every
    # primitive of its body as a program of its own, each time
    return jax.jit(fn)


CASES = [
    # composite normalizations (no torch counterpart)
    ("within_channel_lrn", lambda: nn.SpatialWithinChannelLRN(3, 0.01, 0.75),
     (2, 4, 6, 6)),
    ("subtractive_norm", lambda: nn.SpatialSubtractiveNormalization(4),
     (2, 4, 7, 7)),
    ("divisive_norm", lambda: nn.SpatialDivisiveNormalization(4),
     (2, 4, 7, 7)),
    ("contrastive_norm", lambda: nn.SpatialContrastiveNormalization(4),
     (2, 4, 7, 7)),
    # custom-VJP paths
    ("maxpool_tie_split", lambda: nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1)
     .split_ties(), (2, 3, 9, 9)),
    # one leg since PR 44: the knob does not reach it, so both modes
    # check the same banded product
    ("lrn_banded_conv", lambda: nn.SpatialCrossMapLRN(5, 0.0001, 0.75),
     (2, 7, 5, 5)),
    # ceil-mode average pooling (asymmetric declared-vs-overflow padding
    # divisors — the subtle Torch semantics in _PoolBase._avg)
    ("ceil_avg_pool", lambda: nn.SpatialAveragePooling(3, 3, 2, 2, 1, 1,
                                                       ceil_mode=True),
     (2, 3, 9, 9)),
]


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
@pytest.mark.parametrize("case", CASES, ids=lambda c: c[0])
def test_vjp_matches_finite_differences(case, kernels, monkeypatch):
    name, build, shape = case
    monkeypatch.setenv("BIGDL_KERNELS", kernels)
    RNG.set_seed(0)
    # finite differences need f64 — scoped, so the rest of the suite
    # keeps the default f32 world
    with _enable_x64():
        layer = build()
        x = jnp.asarray(
            np.random.RandomState(0).randn(*shape).astype(np.float64))
        # order=1 reverse mode: forward value + VJP vs central differences
        check_grads(_layer_fn(layer), (x,), order=1, modes=("rev",),
                    atol=1e-3, rtol=1e-3)
