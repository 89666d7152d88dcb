"""Ops-library tests (bigdl_tpu/ops/): every op against its definition,
and the dispatch contract.

The plane family (both LRNs, the three Torch-legacy normalisations, the
tie-split max pool, the average pool) has ONE leg each, plain ``jnp``
under a ``jax.custom_vjp`` with a hand-derived backward.  Each is held
to its definition: the same math written the obvious way in float32
with autodiff's backward, across odd shapes, dtypes and
ceil/asymmetric-padding edges; ``tests/test_numeric_grads.py``
separately pins the backwards against finite differences.

The dispatch layer's contract is pinned here too, on a specimen op of
two trivial legs: ``BIGDL_KERNELS=xla`` bypasses Pallas EVERYWHERE (the
process-wide kill switch), ``pallas`` forces the kernel leg, a typo'd
value raises instead of silently defaulting, every decision lands in
the decision ring + the ``kernel/dispatch`` telemetry stream, and no
mode reaches an op of the plane family.  The three ops that do have a
kernel are tested where their layers are (``test_attention.py``,
``test_linear_attention.py``, ``test_ssm.py``).
"""

import itertools
import pathlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.ops import dispatch
from bigdl_tpu.ops.lrn import cross_map_lrn, within_channel_lrn
from bigdl_tpu.ops.norm import (contrastive_norm, divisive_norm,
                                subtractive_norm)
from bigdl_tpu.ops.pool import avg_pool, maxpool_tie_split

BF16_TOL = dict(rtol=2e-2, atol=2e-2)


def _rng(seed=0):
    return np.random.RandomState(seed)


def _only_leg(*ops):
    return {(f"{op}.{leg}", "xla", "only-leg")
            for op in ops for leg in ("fwd", "bwd")}


def _against_definition(fn, definition, x, seed=1, rtol=1e-5, atol=1e-6):
    """Value and VJP of an op's one leg against its definition's, which
    is given ``x`` in float32 and differentiated by autodiff; returns
    the decisions the op's trace announced."""
    dispatch.clear_decisions()
    y, vjp = jax.vjp(jax.jit(fn), x)
    said = set(dispatch.decisions())
    y0, vjp0 = jax.vjp(jax.jit(definition), x.astype(jnp.float32))
    assert y.dtype == x.dtype and y.shape == y0.shape
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y0),
                               rtol=rtol, atol=atol)
    gy = jnp.asarray(_rng(seed).randn(*y.shape).astype(np.float32), y.dtype)
    dx = vjp(gy)[0]
    assert dx.dtype == x.dtype and dx.shape == x.shape
    np.testing.assert_allclose(
        np.asarray(dx, np.float32),
        np.asarray(vjp0(gy.astype(jnp.float32))[0]), rtol=rtol, atol=atol)
    return said | set(dispatch.decisions())


# ---------------------------------------------------------------------------
# the LRN family
# ---------------------------------------------------------------------------

ONLY_LEG = _only_leg("lrn_cross_map")


def _lrn_definition(x, size, alpha, beta, k, c_ax=1):
    """The cross-map LRN as ``SpatialCrossMapLRN``'s rank-5 path has it:
    a ``lax.reduce_window`` sum of squares over the channel window, in
    float32; its backward is autodiff's."""
    x = x.astype(jnp.float32)
    half = (size - 1) // 2
    dims, pads = [1] * x.ndim, [(0, 0)] * x.ndim
    dims[c_ax], pads[c_ax] = size, (half, size - 1 - half)
    window_sum = lax.reduce_window(x * x, 0.0, lax.add, tuple(dims),
                                   (1,) * x.ndim, pads)
    return x * jnp.power(k + window_sum * (alpha / size), -beta)


def _cross_map_against_definition(x, size, alpha, beta, k, **tol):
    assert _against_definition(
        lambda a: cross_map_lrn(a, size, alpha, beta, k),
        lambda a: _lrn_definition(a, size, alpha, beta, k), x,
        **tol) == ONLY_LEG


@pytest.mark.parametrize("shape,size", [
    ((2, 7, 5, 5), 5),      # band wider than half the channels
    ((1, 3, 4, 4), 3),      # tiny channel count
    ((2, 16, 7, 9), 5),     # non-square odd spatial
    ((1, 192, 56, 56), 5),  # Inception-v1's conv2/norm2 plane
])
def test_cross_map_lrn_parity(shape, size):
    x = jnp.asarray(_rng().randn(*shape).astype(np.float32))
    _cross_map_against_definition(x, size, 1e-4, 0.75, 1.0)


@pytest.mark.parametrize("shape,size", [
    ((2, 64, 56, 56), 5),   # pool1/norm1, cut to batch 2
    ((2, 192, 56, 56), 5),  # conv2/norm2, cut to batch 2
    ((2, 3, 5, 5), 7),      # a band wider than the channels
])
def test_cross_map_lrn_is_one_path_for_both_layouts(shape, size):
    """NCHW and NHWC are one code path (the banded product in the
    input's own layout): values and gradients agree through a transpose
    of the data, and each layout announces itself."""
    to_last, to_first = (0, 2, 3, 1), (0, 3, 1, 2)
    x = jnp.asarray(_rng(30).randn(*shape).astype(np.float32))
    gy = jnp.asarray(_rng(31).randn(*shape).astype(np.float32))
    dispatch.clear_decisions()
    y_c, vjp_c = jax.vjp(jax.jit(
        lambda a: cross_map_lrn(a, size, 1e-4, 0.75, 1.0, "NCHW")), x)
    y_l, vjp_l = jax.vjp(jax.jit(
        lambda a: cross_map_lrn(a, size, 1e-4, 0.75, 1.0, "NHWC")),
        jnp.transpose(x, to_last))
    np.testing.assert_allclose(
        np.asarray(y_c), np.asarray(jnp.transpose(y_l, to_first)),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(vjp_c(gy)[0]),
        np.asarray(jnp.transpose(vjp_l(jnp.transpose(gy, to_last))[0],
                                 to_first)), rtol=1e-5, atol=1e-6)
    assert set(dispatch.decisions()) == ONLY_LEG
    assert {(d.launch["layout"], d.launch["channels"], d.launch["size"])
            for d in dispatch.decisions()} \
        == {("NCHW", shape[1], size), ("NHWC", shape[1], size)}


@pytest.mark.parametrize("mode", ["auto", "pallas", "xla"])
def test_kernel_mode_does_not_reach_cross_map_lrn(mode, tmp_path,
                                                  monkeypatch):
    """``BIGDL_KERNELS`` chooses nothing for this op, ``pallas``
    included: the decision is ``xla`` / ``only-leg``, nothing raises,
    the traced program holds no ``pallas_call``, and the run log's
    instants say which site (channels, size, layout)."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.telemetry import schema

    monkeypatch.setenv("BIGDL_KERNELS", mode)
    dispatch.clear_decisions()
    x = jnp.asarray(_rng(32).randn(1, 6, 5, 5).astype(np.float32))

    def fwd_bwd(a):
        y, vjp = jax.vjp(lambda b: cross_map_lrn(b, 3, 1e-4, 0.75, 1.0), a)
        return vjp(y)

    telemetry.start_run(str(tmp_path))
    try:
        jaxpr = jax.make_jaxpr(fwd_bwd)(x)
    finally:
        telemetry.end_run()
    assert "pallas_call" not in str(jaxpr)
    assert set(dispatch.decisions()) == ONLY_LEG
    events, errors = schema.read_events(str(next(tmp_path.glob("*.jsonl"))))
    assert not errors and not schema.validate_events(events)
    said = [e for e in events if e.get("name") == "kernel/dispatch"]
    assert [(e["op"], e["backend"], e["reason"], e["channels"], e["size"],
             e["layout"]) for e in said] == [
        ("lrn_cross_map.fwd", "xla", "only-leg", 6, 3, "NCHW"),
        ("lrn_cross_map.bwd", "xla", "only-leg", 6, 3, "NCHW")]


def test_partitioned_step_takes_xla_leg_on_tpu(monkeypatch):
    """Inside ``spmd_partitioned`` over >1 device ``auto`` never picks a
    Mosaic kernel (it cannot be partitioned); a 1-device mesh, or no
    mesh, keeps it."""
    from bigdl_tpu.ops import attention
    from bigdl_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(attention, "is_tpu_device", lambda: True)
    monkeypatch.delenv("BIGDL_KERNELS", raising=False)
    assert dispatch.choose_backend("op", True) == ("pallas", "auto:tpu")
    with dispatch.spmd_partitioned(make_mesh((2,), devices=jax.devices()[:2])):
        assert dispatch.choose_backend("op", True) \
            == ("xla", "auto:spmd-partitioned")
        assert attention.select_attention_backend(1024, 1024) \
            == ("dense", "auto:spmd-partitioned")
    for mesh in (None, make_mesh((1,), devices=jax.devices()[:1])):
        with dispatch.spmd_partitioned(mesh):
            assert dispatch.choose_backend("op", True)[0] == "pallas"


def test_cross_map_lrn_general_beta_and_k():
    x = jnp.asarray(_rng(3).randn(1, 5, 6, 6).astype(np.float32))
    _cross_map_against_definition(x, 3, 0.001, 0.5, 2.0)


def _wcl_definition(x, size, alpha, beta):
    """``nn/SpatialWithinChannelLRN.scala``: a ``reduce_window`` mean
    of squares over the ``size x size`` window, Torch pads
    ``(half, size - 1 - half)``."""
    half = (size - 1) // 2
    pad = (half, size - 1 - half)
    window_sum = lax.reduce_window(
        x * x, 0.0, lax.add, (1, 1, size, size), (1, 1, 1, 1),
        ((0, 0), (0, 0), pad, pad))
    return x * jnp.power(1.0 + window_sum * (alpha / (size * size)), -beta)


@pytest.mark.parametrize("shape,size,dtype", [
    ((2, 4, 6, 6), 3, jnp.float32),
    ((1, 2, 7, 5), 4, jnp.float32),     # EVEN window: asymmetric pads
    ((2, 3, 9, 9), 5, jnp.float32),
    ((2, 150, 5, 5), 5, jnp.float32),   # a window as wide as the plane
    ((2, 8, 8, 8), 4, jnp.bfloat16),    # the cells' dtype
], ids=["3", "even4", "5", "plane_wide", "bf16"])
def test_within_channel_lrn_parity(shape, size, dtype):
    x = jnp.asarray(_rng(1).randn(*shape).astype(np.float32), dtype)
    tol = BF16_TOL if dtype == jnp.bfloat16 else {}
    assert _against_definition(
        lambda a: within_channel_lrn(a, size, 0.01, 0.75),
        lambda a: _wcl_definition(a, size, 0.01, 0.75), x,
        **tol) == _only_leg("lrn_within_channel")


def test_lrn_bf16_parity():
    """The bench dtype: the leg agrees with the float32 definition
    within bf16 slack."""
    x = jnp.asarray(_rng(2).randn(2, 8, 8, 8).astype(np.float32),
                    jnp.bfloat16)
    _cross_map_against_definition(x, 5, 1e-4, 0.75, 1.0, **BF16_TOL)


# ---------------------------------------------------------------------------
# subtractive / divisive / contrastive
# ---------------------------------------------------------------------------

def _gauss(k):
    from bigdl_tpu.nn.layers.normalization import _gaussian_kernel

    return jnp.asarray(_gaussian_kernel(k))


def _smooth_definition(v, kernel):
    """Kernel-weighted window sum of ``[N, H, W]`` maps under Torch's
    "same" pads ``(k // 2, (k - 1) // 2)``: a one-channel correlation."""
    kh, kw = kernel.shape
    out = lax.conv_general_dilated(
        v[:, None], kernel[None, None].astype(v.dtype), (1, 1),
        ((kh // 2, (kh - 1) // 2), (kw // 2, (kw - 1) // 2)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return out[:, 0]


def _sub_definition(x, kernel):
    """``nn/SpatialSubtractiveNormalization.scala``: less the smoothed
    channel mean over the kernel's mass inside the image."""
    coef = _smooth_definition(jnp.ones((1,) + x.shape[2:], x.dtype), kernel)
    return x - (_smooth_definition(jnp.mean(x, 1), kernel) / coef)[:, None]


def _div_definition(x, kernel, threshold=1e-4, thresval=1e-4):
    """``nn/SpatialDivisiveNormalization.scala``: over the smoothed
    local deviation, no less than its mean over the image, thresholded."""
    coef = _smooth_definition(jnp.ones((1,) + x.shape[2:], x.dtype), kernel)
    sigma = jnp.sqrt(jnp.clip(
        _smooth_definition(jnp.mean(x * x, 1), kernel) / coef, 0.0))
    e = jnp.maximum(sigma, jnp.mean(sigma, (1, 2), keepdims=True))
    return x / jnp.where(e < threshold, thresval, e)[:, None]


NORMS = {
    "subtractive": (subtractive_norm, _sub_definition),
    "divisive": (divisive_norm, _div_definition),
    "contrastive": (contrastive_norm,
                    lambda x, k: _div_definition(_sub_definition(x, k), k)),
}


@pytest.mark.parametrize("norm,shape,ksize,dtype", [
    ("subtractive", (2, 4, 7, 7), 9, jnp.float32),   # kernel > image half
    ("subtractive", (1, 3, 12, 10), 5, jnp.float32),
    ("subtractive", (2, 1, 6, 6), 4, jnp.float32),   # EVEN kernel
    ("divisive", (2, 4, 7, 7), 9, jnp.float32),
    ("divisive", (1, 2, 9, 11), 5, jnp.float32),
    ("contrastive", (2, 4, 7, 7), 9, jnp.float32),
    ("subtractive", (300, 2, 6, 6), 3, jnp.float32),  # many small planes
    ("subtractive", (2, 4, 8, 8), 5, jnp.bfloat16),   # the cells' dtype
])
def test_norm_parity(norm, shape, ksize, dtype):
    op, definition = NORMS[norm]
    x = jnp.asarray(_rng(4).randn(*shape).astype(np.float32), dtype)
    tol = BF16_TOL if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-5)
    assert _against_definition(
        lambda a: op(a, _gauss(ksize)),
        lambda a: definition(a, _gauss(ksize)), x,
        **tol) == _only_leg("norm_smooth")


def test_smoothing_kernel_gets_zero_cotangent():
    """The smoothing kernel is a BUFFER (never trained): its cotangent
    is zero by contract."""
    x = jnp.asarray(_rng(7).randn(1, 2, 5, 5).astype(np.float32))
    _, vjp = jax.vjp(jax.jit(subtractive_norm), x, _gauss(3))
    _, dk = vjp(jnp.ones((1, 2, 5, 5), jnp.float32))
    assert not np.asarray(dk).any()


# ---------------------------------------------------------------------------
# pooling (tie-split + Torch-divisor average)
# ---------------------------------------------------------------------------

def _full(k, s, p):
    return ((1, 1) + k, (1, 1) + s, ((0, 0), (0, 0)) + p)


POOL_CASES = [
    # (shape, k, s, pads) — incl. ceil-overflow + anisotropic edges
    ((2, 3, 9, 9), (3, 3), (2, 2), ((1, 1), (1, 1))),
    ((2, 3, 9, 9), (3, 3), (2, 2), ((1, 2), (1, 2))),   # ceil overflow
    ((1, 2, 7, 8), (3, 2), (2, 3), ((1, 0), (0, 1))),   # anisotropic
    ((1, 1, 6, 6), (3, 3), (1, 1), ((0, 0), (0, 0))),   # stride-1 overlap
    ((1, 2, 11, 11), (2, 2), (2, 2), ((0, 1), (0, 1))),  # residue shortfall
]


def _window_taps(x, dims, strides, pads, fill):
    """Every tap of every window, gathered explicitly from the padded
    input: ``[taps, *out_shape]``."""
    xp = jnp.pad(x, pads, constant_values=fill)
    out = [(n - k) // s + 1 for n, k, s in zip(xp.shape, dims, strides)]
    return jnp.stack([
        lax.slice(xp, off, [o + (n - 1) * s + 1
                            for o, n, s in zip(off, out, strides)], strides)
        for off in itertools.product(*[range(k) for k in dims])])


def _maxpool_definition(x, dims, strides, pads):
    """The largest tap of each window of the ``-inf``-padded input;
    autodiff's backward of ``jnp.max`` splits ties equally."""
    return jnp.max(_window_taps(x, dims, strides, pads, -jnp.inf), axis=0)


@pytest.mark.parametrize("shape,k,s,p", POOL_CASES)
@pytest.mark.parametrize("tie_heavy", [False, True])
def test_maxpool_tie_split_parity(shape, k, s, p, tie_heavy):
    x = _rng(8).randn(*shape).astype(np.float32)
    if tie_heavy:  # quantize to force equal maxima inside windows
        x = np.round(x * 2.0) / 2.0
    dims, strides, pads = _full(k, s, p)
    assert _against_definition(
        lambda a: maxpool_tie_split(a, dims, strides, pads),
        lambda a: _maxpool_definition(a, dims, strides, pads),
        jnp.asarray(x)) == _only_leg("pool_tie_split")


def _np_divisors(shape, dims, strides, pads, declared, count_include_pad):
    """Torch's divisor of each window, counted tap by tap: a tap counts
    if it lies on the data or, under ``count_include_pad``, on DECLARED
    padding; padding that ceil mode added past it never counts
    (``SpatialAveragePooling.scala:133-135``)."""
    counted = [(0, lo + n + dhi) if count_include_pad else (lo, lo + n)
               for n, (lo, _), (_, dhi) in zip(shape, pads, declared)]
    out = [(lo + n + hi - k) // s + 1
           for n, k, s, (lo, hi) in zip(shape, dims, strides, pads)]
    counts = np.zeros(out)
    for o in np.ndindex(*out):
        for tap in np.ndindex(*dims):
            at = [oi * s + t for oi, s, t in zip(o, strides, tap)]
            counts[o] += all(a <= q < b for q, (a, b) in zip(at, counted))
    return np.maximum(counts, 1.0)


def _avgpool_definition(x, dims, strides, pads, declared, count_include_pad):
    """A ``reduce_window`` sum over the zero-padded input, divided by
    the counted divisors; autodiff's backward."""
    total = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
    return total / jnp.asarray(_np_divisors(
        x.shape, dims, strides, pads, declared, count_include_pad), x.dtype)


WHOLE_PLANE = {("pool_avg.fwd", "xla", "whole-plane"),
               ("pool_avg.bwd", "xla", "whole-plane")}

F32, BF16 = jnp.float32, jnp.bfloat16
AVG_CASES = [
    # (shape, k, s, pads, declared, dtype, whole plane?)
    # declared padding below the ceil-overflow hi: the Torch divisor
    # subtlety the op must reproduce
    *[(shape, k, s, p, tuple((lo, min(hi, lo)) for lo, hi in p), F32, False)
      for shape, k, s, p in POOL_CASES],
    # many small planes: a window that IS the padded plane (one fused
    # reduction and a broadcast) ...
    ((2, 150, 7, 7), (7, 7), (1, 1), ((0, 0), (0, 0)), ((0, 0), (0, 0)),
     F32, True),
    ((3, 100, 5, 5), (7, 7), (1, 1), ((1, 1), (1, 1)), ((1, 1), (1, 1)),
     F32, True),
    ((2, 150, 4, 6), (5, 8), (1, 1), ((1, 0), (1, 1)), ((1, 0), (1, 1)),
     F32, True),
    # ... and one that slides over it, 3x3/s1 "same" padding
    ((2, 150, 6, 6), (3, 3), (1, 1), ((1, 1), (1, 1)), ((1, 1), (1, 1)),
     F32, False),
]
AVG_BF16_CASES = [
    # the benchmark's dtype: the whole-plane form accumulates in float32
    # and rounds once; the sliding form sums in the input's dtype
    ((2, 150, 7, 7), (7, 7), (1, 1), ((0, 0), (0, 0)), ((0, 0), (0, 0)),
     BF16, True),
    ((2, 16, 14, 14), (3, 3), (1, 1), ((1, 1), (1, 1)), ((1, 1), (1, 1)),
     BF16, False),          # the Inception branches' pool
    ((2, 16, 14, 14), (5, 5), (3, 3), ((0, 1), (0, 1)), ((0, 0), (0, 0)),
     BF16, False),          # the auxiliary heads' pool, one overflow row
]


@pytest.mark.parametrize("shape,k,s,p,declared,dtype,whole,include_pad", [
    *[c + (ip,) for c in AVG_CASES for ip in (True, False)],
    *[c + (True,) for c in AVG_BF16_CASES]])
def test_avg_pool_parity(shape, k, s, p, declared, dtype, whole,
                         include_pad):
    x = jnp.asarray(_rng(9).randn(*shape).astype(np.float32), dtype)
    dims, strides, pads = _full(k, s, p)
    declared = ((0, 0), (0, 0)) + declared
    tol = BF16_TOL if dtype == BF16 else {}
    said = _against_definition(
        lambda a: avg_pool(a, dims, strides, pads, declared, include_pad,
                           True),
        lambda a: _avgpool_definition(a, dims, strides, pads, declared,
                                      include_pad), x, **tol)
    assert said == (WHOLE_PLANE if whole else _only_leg("pool_avg"))


@pytest.mark.parametrize("shape,dims,strides,pads", [
    # the CIFAR ResNet's 8x8 head (models/resnet.py)
    ((4, 64, 8, 8), (1, 1, 8, 8), (1, 1, 1, 1), ((0, 0),) * 4),
    # declared padding inside the one window
    ((3, 100, 5, 5), (1, 1, 7, 7), (1, 1, 1, 1),
     ((0, 0), (0, 0), (1, 1), (1, 1))),
    # a channels-last head, strided by its own size
    ((2, 7, 7, 96), (1, 7, 7, 1), (1, 7, 7, 1), ((0, 0),) * 4),
], ids=["cifar_head", "padded", "nhwc"])
def test_whole_plane_window_takes_the_form_everywhere(shape, dims, strides,
                                                      pads, monkeypatch):
    """The form follows the shape alone: on the CPU, on a TPU and inside
    a partitioned step (the four-chip cell), a window that is the whole
    padded plane is the one reduction, said so."""
    from jax.sharding import Mesh
    from bigdl_tpu.ops import attention

    x = _rng(34).randn(*shape).astype(np.float32)
    axes = tuple(a for a, k in enumerate(dims) if k > 1)
    count = np.prod([dims[a] for a in axes])
    want = x.sum(axes, keepdims=True) / count
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    monkeypatch.delenv("BIGDL_KERNELS", raising=False)
    for tpu in (False, True):
        monkeypatch.setattr(attention, "is_tpu_device", lambda: tpu)
        for over in (None, mesh):
            dispatch.clear_decisions()

            def fn(a):
                with dispatch.spmd_partitioned(over):
                    return avg_pool(a, dims, strides, pads, pads, True,
                                    True)

            y, vjp = jax.vjp(jax.jit(fn), jnp.asarray(x))
            dx = vjp(jnp.ones_like(y))[0]
            np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5,
                                       atol=1e-6)
            np.testing.assert_allclose(np.asarray(dx),
                                       np.full(shape, 1.0 / count),
                                       rtol=1e-6)
            assert set(dispatch.decisions()) == WHOLE_PLANE


def test_tie_split_conserves_gradient_mass():
    """Equal-split semantics: summed input gradient == summed output
    gradient regardless of ties (mass conservation)."""
    x = jnp.asarray(np.ones((1, 1, 4, 4), np.float32))  # ALL ties
    dims, strides, pads = _full((2, 2), (2, 2), ((0, 0), (0, 0)))
    _, vjp = jax.vjp(
        jax.jit(lambda a: maxpool_tie_split(a, dims, strides, pads)), x)
    gy = _rng(10).randn(1, 1, 2, 2).astype(np.float32)
    dx = np.asarray(vjp(jnp.asarray(gy))[0])
    np.testing.assert_allclose(dx.sum(), gy.sum(), rtol=1e-6)
    # each of the 4 tied positions gets exactly a quarter
    np.testing.assert_allclose(dx[0, 0, :2, :2], gy[0, 0, 0, 0] / 4.0,
                               rtol=1e-6)


def test_cross_map_lrn_rank5_and_nhwc(monkeypatch):
    """Rank-5 inputs keep the generic reduce_window reference (review
    r6 finding: the op-routing rewrite briefly dropped it) and NHWC
    matches NCHW through the banded product in its native layout — with
    the exact VJP, no relayout transposes."""
    import bigdl_tpu.nn as nn

    layer = nn.SpatialCrossMapLRN(3, 0.001, 0.75)
    x5 = jnp.asarray(_rng(20).randn(2, 3, 4, 5, 5).astype(np.float32))
    y5 = layer.update_output(x5)
    assert y5.shape == x5.shape

    x = jnp.asarray(_rng(21).randn(2, 6, 5, 5).astype(np.float32))
    nchw = nn.SpatialCrossMapLRN(5, 1e-4, 0.75)
    nhwc = nn.SpatialCrossMapLRN(5, 1e-4, 0.75, format="NHWC")
    y_c, vjp_c = jax.vjp(nchw.update_output, x)
    y_l, vjp_l = jax.vjp(nhwc.update_output, jnp.transpose(x, (0, 2, 3, 1)))
    np.testing.assert_allclose(np.asarray(y_c),
                               np.asarray(jnp.transpose(y_l, (0, 3, 1, 2))),
                               rtol=1e-5, atol=1e-6)
    gy = jnp.asarray(_rng(22).randn(*y_c.shape).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(vjp_c(gy)[0]),
        np.asarray(jnp.transpose(
            vjp_l(jnp.transpose(gy, (0, 2, 3, 1)))[0], (0, 3, 1, 2))),
        rtol=1e-4, atol=1e-5)
    # and no transpose ops in the NHWC forward HLO (native layout)
    hlo = jax.jit(nhwc.update_output).lower(
        jnp.transpose(x, (0, 2, 3, 1))).as_text()
    assert "transpose" not in hlo


def test_pool_nonstandard_rank_uses_xla_leg():
    """The pools take any rank (temporal and volumetric pooling): a
    rank-3 and a rank-5 window against the definitions."""
    x3 = jnp.asarray(np.round(_rng(11).randn(2, 9, 4) * 2.0) / 2.0,
                     jnp.float32)
    d3, s3, p3 = (1, 3, 1), (1, 2, 1), ((0, 0), (1, 1), (0, 0))
    assert _against_definition(
        lambda a: maxpool_tie_split(a, d3, s3, p3),
        lambda a: _maxpool_definition(a, d3, s3, p3),
        x3) == _only_leg("pool_tie_split")
    x5 = jnp.asarray(_rng(11).randn(1, 2, 4, 6, 6).astype(np.float32))
    d5, s5 = (1, 1, 2, 3, 3), (1, 1, 2, 2, 2)
    p5 = ((0, 0), (0, 0), (0, 0), (1, 0), (1, 0))
    assert _against_definition(
        lambda a: maxpool_tie_split(a, d5, s5, p5),
        lambda a: _maxpool_definition(a, d5, s5, p5),
        x5) == _only_leg("pool_tie_split")
    assert _against_definition(
        lambda a: avg_pool(a, d5, s5, p5, p5, False, True),
        lambda a: _avgpool_definition(a, d5, s5, p5, p5, False),
        x5) == _only_leg("pool_avg")


# ---------------------------------------------------------------------------
# dispatch contract
# ---------------------------------------------------------------------------

def _specimen(x, supported=True):
    """A two-legged op as ``dispatch.dispatch`` sees one: the kernel leg
    says how it was launched, the XLA leg says nothing."""
    def kernel(a):
        dispatch.launched(grid=(2,))
        return a + 1.0

    return dispatch.dispatch("specimen", kernel, lambda a: a + 1.0,
                             supported, x)


def test_bad_kernel_mode_raises(monkeypatch):
    monkeypatch.setenv("BIGDL_KERNELS", "palas")
    with pytest.raises(ValueError, match="BIGDL_KERNELS"):
        dispatch.kernel_mode()


@pytest.mark.parametrize("mode", ["auto", "pallas", "xla"])
def test_kernel_mode_does_not_reach_the_plane_family(mode, monkeypatch):
    """No mode chooses anything for a one-legged op, ``pallas``
    included: every layer of the family traces to a program without a
    ``pallas_call`` and says ``xla`` / ``only-leg``."""
    import bigdl_tpu.nn as nn

    monkeypatch.setenv("BIGDL_KERNELS", mode)
    dispatch.clear_decisions()
    x = jnp.asarray(_rng(12).randn(2, 4, 9, 9).astype(np.float32))
    for layer in (
            nn.SpatialCrossMapLRN(5, 1e-4, 0.75),
            nn.SpatialWithinChannelLRN(3, 0.01, 0.75),
            nn.SpatialSubtractiveNormalization(4),
            nn.SpatialDivisiveNormalization(4),
            nn.SpatialContrastiveNormalization(4),
            nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1).split_ties(),
            nn.SpatialAveragePooling(3, 3, 2, 2, 1, 1, ceil_mode=True),
            nn.SpatialAveragePooling(3, 3, 1, 1, 1, 1)):
        layer.evaluate()

        def fwd_bwd(a):
            y, vjp = jax.vjp(layer.update_output, a)
            return vjp(y)

        assert "pallas_call" not in str(jax.make_jaxpr(fwd_bwd)(x))
    assert set(dispatch.decisions()) == _only_leg(
        "lrn_cross_map", "lrn_within_channel", "norm_smooth",
        "pool_tie_split", "pool_avg")


def test_xla_mode_bypasses_pallas_everywhere(monkeypatch):
    """BIGDL_KERNELS=xla is the process-wide kill switch: a supported
    two-legged op and the attention router take the XLA form."""
    from bigdl_tpu.ops.attention import select_attention_backend

    monkeypatch.setenv("BIGDL_KERNELS", "xla")
    dispatch.clear_decisions()
    jax.jit(lambda a: _specimen(a))(jnp.ones((3,), jnp.float32))
    assert dispatch.decisions() == [
        ("specimen", "xla", "forced:BIGDL_KERNELS=xla")]
    assert select_attention_backend(4096, 4096)[0] == "dense"


def test_pallas_mode_forces_kernels(monkeypatch):
    monkeypatch.setenv("BIGDL_KERNELS", "pallas")
    dispatch.clear_decisions()
    x = jnp.ones((3,), jnp.float32)
    jax.jit(lambda a: _specimen(a))(x)
    jax.jit(lambda a: _specimen(a, supported=False))(x)
    assert dispatch.decisions() == [
        ("specimen", "pallas", "forced:BIGDL_KERNELS=pallas"),
        ("specimen", "xla", "unsupported-shape")]


def test_auto_mode_off_tpu_prefers_xla(monkeypatch):
    """auto on the CPU suite = fused XLA (never the slow interpreter);
    the Pallas leg is still reachable via the explicit knob above."""
    monkeypatch.setenv("BIGDL_KERNELS", "auto")
    dispatch.clear_decisions()
    _specimen(jnp.ones((3,), jnp.float32))
    assert dispatch.decisions() == [("specimen", "xla", "auto:off-tpu")]


def test_dispatch_emits_telemetry_instant(tmp_path, monkeypatch):
    """Decisions are observable: a run log carries schema-valid
    kernel/dispatch instants naming op + backend."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.telemetry import schema

    monkeypatch.setenv("BIGDL_KERNELS", "xla")
    telemetry.start_run(str(tmp_path))
    try:
        _specimen(jnp.ones((3,), jnp.float32))
    finally:
        telemetry.end_run()
    logs = list(tmp_path.glob("*.jsonl"))
    assert len(logs) == 1
    events, errors = schema.read_events(str(logs[0]))
    assert not errors
    inst = [e for e in events if e.get("name") == "kernel/dispatch"]
    assert inst and inst[0]["op"] == "specimen" \
        and inst[0]["backend"] == "xla"
    assert not schema.validate_events(events)


def test_launch_facts_ride_on_the_dispatch_instant(tmp_path, monkeypatch):
    """What a leg says through ``dispatch.launched`` is on its decision,
    in the run log's instant as in the ring; a leg that says nothing
    adds nothing."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.telemetry import schema

    x = jnp.ones((3,), jnp.float32)
    dispatch.clear_decisions()
    telemetry.start_run(str(tmp_path))
    try:
        for mode in ("pallas", "xla"):
            monkeypatch.setenv("BIGDL_KERNELS", mode)
            jax.jit(lambda a: _specimen(a))(x)
    finally:
        telemetry.end_run()
    assert [d.launch for d in dispatch.decisions()] == [{"grid": (2,)}, {}]
    events, errors = schema.read_events(str(next(tmp_path.glob("*.jsonl"))))
    assert not errors and not schema.validate_events(events)
    by_backend = {e["backend"]: e for e in events
                  if e.get("name") == "kernel/dispatch"}
    assert list(by_backend["pallas"]["grid"]) == [2]
    assert "grid" not in by_backend["xla"]


def test_pallas_is_imported_only_where_a_cell_times_it():
    """A kernel comes with a benchmark cell that runs it and a roofline
    metric that reads it (docs/kernels.md): under ``bigdl_tpu/`` only
    the three modules whose kernels have both import Pallas.  Read off
    the sources, nothing is imported."""
    root = pathlib.Path(dispatch.__file__).resolve().parents[1]
    imports = re.compile(
        r"^\s*(from\s+jax\.experimental(\.pallas\b|\s+import\s+.*\bpallas\b)"
        r"|import\s+jax\.experimental\.pallas\b)", re.M)
    found = {str(p.relative_to(root)) for p in root.rglob("*.py")
             if imports.search(p.read_text())}
    assert found == {"ops/attention.py", "ops/delta_rule.py", "ops/ssd.py"}


def test_attention_routing_shares_predicate(monkeypatch):
    """BIGDL_KERNELS routes the attention auto-backend too, through
    the one predicate every reader shares."""
    from bigdl_tpu.ops.attention import flash_auto, select_attention_backend

    monkeypatch.setenv("BIGDL_KERNELS", "xla")
    assert select_attention_backend(4096, 4096) \
        == ("dense", "forced:BIGDL_KERNELS=xla")
    assert not flash_auto(4096, 4096)
    monkeypatch.setenv("BIGDL_KERNELS", "pallas")
    assert select_attention_backend(64, 64)[0] == "flash"
    assert select_attention_backend(64, 64, masked=True)[0] == "dense"
    monkeypatch.setenv("BIGDL_KERNELS", "auto")
    # off-TPU auto is always dense (this suite runs on CPU)
    assert select_attention_backend(4096, 4096)[0] == "dense"


def test_mha_auto_backend_records_dispatch(monkeypatch):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.utils.rng import RNG

    monkeypatch.setenv("BIGDL_KERNELS", "pallas")
    dispatch.clear_decisions()
    RNG.set_seed(0)
    mha = nn.MultiHeadAttention(16, 2, causal=True)
    mha.evaluate()
    x = jnp.asarray(_rng(16).randn(2, 8, 16).astype(np.float32))
    y = mha.forward(x)
    assert y.shape == (2, 8, 16)
    recs = [r for r in dispatch.decisions() if r[0] == "attention"]
    assert recs and recs[-1][1] == "pallas" \
        and recs[-1][2] == "forced:BIGDL_KERNELS=pallas"
