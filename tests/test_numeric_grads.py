"""Numerical-gradient validation for layers WITHOUT a PyTorch oracle
(SURVEY §4's gradient-check discipline — the reference cross-checks every
layer's backward against either Torch or a numeric differentiator).
``jax.test_util.check_grads`` compares each layer's VJP against finite
differences, so custom-VJP layers and composite normalizations get a
backward check even where no framework oracle exists.

Each of these layers routes through a ``bigdl_tpu.ops`` custom-VJP op
with a hand-derived cotangent (``ops/lrn.py``, ``ops/norm.py``,
``ops/pool.py``: one form each, on every platform)."""

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

    # compiled: check_grads evaluates fn and its VJP several times
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
    ("lrn_banded_conv", lambda: nn.SpatialCrossMapLRN(5, 0.0001, 0.75),
     (2, 7, 5, 5)),
    # ceil-mode average pooling (asymmetric declared-vs-overflow padding
    # divisors — the subtle Torch semantics in _PoolBase._avg)
    ("ceil_avg_pool", lambda: nn.SpatialAveragePooling(3, 3, 2, 2, 1, 1,
                                                       ceil_mode=True),
     (2, 3, 9, 9)),
    # the Inception branches' 3x3/s1 "same" pool: overlapping windows
    ("same_avg_pool", lambda: nn.SpatialAveragePooling(3, 3, 1, 1, 1, 1),
     (2, 3, 6, 6)),
    # padding left out of the divisor, with a ceil-overflow row
    ("ceil_avg_pool_data_only", lambda: nn.SpatialAveragePooling(
        3, 3, 2, 2, 1, 1, ceil_mode=True, count_include_pad=False),
     (2, 3, 8, 8)),
    # floor mode stops short of the last row and column: no window
    # reaches them and their gradient is zero
    ("floor_avg_pool_short", lambda: nn.SpatialAveragePooling(3, 3, 2, 2),
     (1, 2, 8, 8)),
    ("maxpool_tie_split_overlap", lambda: nn.SpatialMaxPooling(
        3, 3, 1, 1, 1, 1).split_ties(), (1, 2, 6, 6)),
    # EVEN window: asymmetric (lo, hi) pads, swapped in the transpose
    ("within_channel_lrn_even", lambda: nn.SpatialWithinChannelLRN(
        4, 0.01, 0.75), (1, 2, 7, 5)),
    ("lrn_banded_conv_nhwc", lambda: nn.SpatialCrossMapLRN(
        5, 0.0001, 0.75, format="NHWC"), (2, 5, 5, 7)),
    ("volumetric_avg_pool", lambda: nn.VolumetricAveragePooling(
        2, 3, 3, 2, 2, 2, 0, 1, 1), (1, 2, 4, 7, 7)),
    ("temporal_maxpool_tie_split", lambda: nn.TemporalMaxPooling(3, 2)
     .split_ties(), (2, 9, 4)),
]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c[0])
def test_vjp_matches_finite_differences(case):
    name, build, shape = case
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
