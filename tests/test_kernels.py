"""Kernel-library parity + dispatch tests (bigdl_tpu/ops/).

A fused op with a kernel keeps two legs under one ``jax.custom_vjp`` —
the Pallas kernel (interpret mode on this CPU suite: the IDENTICAL code
path that Mosaic compiles on TPU) and the XLA reference.  Parity must
hold on forward values AND the hand-derived VJP cotangents, across odd
shapes, dtypes, and ceil/asymmetric-padding edges;
``tests/test_numeric_grads.py`` separately pins both legs against
finite differences.  The cross-map LRN has ONE leg (the banded product,
in every mode): it is held to its definition instead, a channel-window
sum in float32 with autodiff's backward.

The dispatch layer's contract is pinned here too: ``BIGDL_KERNELS=xla``
bypasses Pallas EVERYWHERE (the process-wide kill switch), ``pallas``
forces the kernels, a typo'd value raises instead of silently
defaulting, and every decision lands in the decision ring + the
``kernel/dispatch`` telemetry stream.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import dispatch
from bigdl_tpu.ops.lrn_pallas import cross_map_lrn, within_channel_lrn
from bigdl_tpu.ops.norm_pallas import (contrastive_norm, divisive_norm,
                                       subtractive_norm)
from bigdl_tpu.ops.pool_pallas import avg_pool, maxpool_tie_split


def _rng(seed=0):
    return np.random.RandomState(seed)


def _both_legs(fn, x, seed=1, rtol=1e-5, atol=1e-6, monkeypatch=None):
    """Run fn's value+VJP on both dispatch legs and assert parity."""
    outs = {}
    for mode in ("xla", "pallas"):
        monkeypatch.setenv("BIGDL_KERNELS", mode)
        dispatch.clear_decisions()
        # compiled (run eagerly, a Pallas kernel in interpret mode
        # dispatches every primitive of its body as its own program),
        # through a function of this leg's own so that no trace of the
        # other leg can be found in a cache
        y, vjp = jax.vjp(jax.jit(lambda a: fn(a)), x)
        assert dispatch.decisions(), f"{mode} leg was not traced"
        outs[mode] = (y, vjp)
    y1, vjp1 = outs["xla"]
    y2, vjp2 = outs["pallas"]
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               rtol=rtol, atol=atol)
    gy = jnp.asarray(_rng(seed).randn(*y1.shape).astype(np.float32),
                     y1.dtype)
    np.testing.assert_allclose(np.asarray(vjp1(gy)[0], np.float32),
                               np.asarray(vjp2(gy)[0], np.float32),
                               rtol=rtol, atol=atol)
    return y1


# ---------------------------------------------------------------------------
# parity: LRN family
# ---------------------------------------------------------------------------

ONLY_LEG = {("lrn_cross_map.fwd", "xla", "only-leg"),
            ("lrn_cross_map.bwd", "xla", "only-leg")}


def _lrn_definition(x, size, alpha, beta, k, c_ax=1):
    """The cross-map LRN as ``SpatialCrossMapLRN``'s rank-5 path has it:
    a ``lax.reduce_window`` sum of squares over the channel window, in
    float32; its backward is autodiff's."""
    x = x.astype(jnp.float32)
    half = (size - 1) // 2
    dims, pads = [1] * x.ndim, [(0, 0)] * x.ndim
    dims[c_ax], pads[c_ax] = size, (half, size - 1 - half)
    window_sum = jax.lax.reduce_window(x * x, 0.0, jax.lax.add, tuple(dims),
                                       (1,) * x.ndim, pads)
    return x * jnp.power(k + window_sum * (alpha / size), -beta)


def _cross_map_against_definition(x, size, alpha, beta, k, seed=1,
                                  rtol=1e-5, atol=1e-6):
    """Value and VJP of the op's one leg against the definition's."""
    dispatch.clear_decisions()
    y, vjp = jax.vjp(jax.jit(
        lambda a: cross_map_lrn(a, size, alpha, beta, k)), x)
    y0, vjp0 = jax.vjp(jax.jit(
        lambda a: _lrn_definition(a, size, alpha, beta, k)), x)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y0),
                               rtol=rtol, atol=atol)
    gy = jnp.asarray(_rng(seed).randn(*y.shape).astype(np.float32), y.dtype)
    np.testing.assert_allclose(
        np.asarray(vjp(gy)[0], np.float32),
        np.asarray(vjp0(gy.astype(jnp.float32))[0], np.float32),
        rtol=rtol, atol=atol)
    assert set(dispatch.decisions()) == ONLY_LEG


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


def test_strided_pool_leaves_pallas_on_tpu_only(monkeypatch):
    """Mosaic has no strided vector slice: on a TPU the plane-pool gate
    turns a strided window to the XLA leg; the interpreter keeps it."""
    from bigdl_tpu.ops import attention
    from bigdl_tpu.ops.pool_pallas import pool_plane_supported

    x = jax.ShapeDtypeStruct((2, 4, 14, 14), jnp.float32)
    s1, s3 = (1, 1, 1, 1), (1, 1, 3, 3)
    assert pool_plane_supported(x, (1, 1, 5, 5), s3)
    monkeypatch.setattr(attention, "is_tpu_device", lambda: True)
    assert not pool_plane_supported(x, (1, 1, 5, 5), s3)
    assert pool_plane_supported(x, (1, 1, 5, 5), s1)


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


@pytest.mark.parametrize("shape,size", [
    ((2, 4, 6, 6), 3),
    ((1, 2, 7, 5), 4),      # EVEN window: asymmetric (lo, hi) pads
    ((2, 3, 9, 9), 5),
])
def test_within_channel_lrn_parity(shape, size, monkeypatch):
    x = jnp.asarray(_rng(1).randn(*shape).astype(np.float32))
    _both_legs(lambda a: within_channel_lrn(a, size, 0.01, 0.75), x,
               monkeypatch=monkeypatch)


def test_lrn_bf16_parity():
    """The bench dtype: the leg agrees with the float32 definition
    within bf16 slack."""
    x = jnp.asarray(_rng(2).randn(2, 8, 8, 8).astype(np.float32),
                    jnp.bfloat16)
    _cross_map_against_definition(x, 5, 1e-4, 0.75, 1.0,
                                  rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# parity: subtractive / divisive / contrastive
# ---------------------------------------------------------------------------

def _gauss(k):
    from bigdl_tpu.nn.layers.normalization import _gaussian_kernel

    return jnp.asarray(_gaussian_kernel(k))


@pytest.mark.parametrize("shape,ksize", [
    ((2, 4, 7, 7), 9),      # default 9x9 gaussian, kernel > image half
    ((1, 3, 12, 10), 5),
    ((2, 1, 6, 6), 4),      # EVEN kernel: asymmetric SAME pads
])
def test_subtractive_norm_parity(shape, ksize, monkeypatch):
    x = jnp.asarray(_rng(4).randn(*shape).astype(np.float32))
    _both_legs(lambda a: subtractive_norm(a, _gauss(ksize)), x,
               monkeypatch=monkeypatch)


@pytest.mark.parametrize("shape,ksize", [
    ((2, 4, 7, 7), 9),
    ((1, 2, 9, 11), 5),
])
def test_divisive_norm_parity(shape, ksize, monkeypatch):
    x = jnp.asarray(_rng(5).randn(*shape).astype(np.float32))
    _both_legs(lambda a: divisive_norm(a, _gauss(ksize)), x,
               monkeypatch=monkeypatch)


def test_contrastive_norm_parity(monkeypatch):
    x = jnp.asarray(_rng(6).randn(2, 4, 7, 7).astype(np.float32))
    _both_legs(lambda a: contrastive_norm(a, _gauss(9)), x,
               monkeypatch=monkeypatch)


def test_smoothing_kernel_gets_zero_cotangent(monkeypatch):
    """The smoothing kernel is a BUFFER (never trained): its cotangent
    is zero by contract on both legs."""
    x = jnp.asarray(_rng(7).randn(1, 2, 5, 5).astype(np.float32))
    k = _gauss(3)
    for mode in ("xla", "pallas"):
        monkeypatch.setenv("BIGDL_KERNELS", mode)
        _, vjp = jax.vjp(lambda a, w: subtractive_norm(a, w), x, k)
        _, dk = vjp(jnp.ones((1, 2, 5, 5), jnp.float32))
        assert float(jnp.max(jnp.abs(dk))) == 0.0


# ---------------------------------------------------------------------------
# parity: pooling (tie-split + Torch-divisor average)
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


@pytest.mark.parametrize("shape,k,s,p", POOL_CASES)
@pytest.mark.parametrize("tie_heavy", [False, True])
def test_maxpool_tie_split_parity(shape, k, s, p, tie_heavy, monkeypatch):
    x = _rng(8).randn(*shape).astype(np.float32)
    if tie_heavy:  # quantize to force equal maxima inside windows
        x = np.round(x * 2.0) / 2.0
    dims, strides, pads = _full(k, s, p)
    _both_legs(lambda a: maxpool_tie_split(a, dims, strides, pads),
               jnp.asarray(x), monkeypatch=monkeypatch)


@pytest.mark.parametrize("shape,k,s,p", POOL_CASES)
@pytest.mark.parametrize("count_include_pad", [True, False])
def test_avg_pool_parity(shape, k, s, p, count_include_pad, monkeypatch):
    x = jnp.asarray(_rng(9).randn(*shape).astype(np.float32))
    dims, strides, pads = _full(k, s, p)
    # declared padding below the ceil-overflow hi — the Torch divisor
    # subtlety the op must reproduce on both legs
    declared = ((0, 0), (0, 0)) \
        + tuple((lo, min(hi, lo)) for lo, hi in p)
    _both_legs(lambda a: avg_pool(a, dims, strides, pads, declared,
                                  count_include_pad, True), x,
               monkeypatch=monkeypatch)


def _launches(op_prefix):
    return {r[0]: r.launch for r in dispatch.decisions()
            if r[0].startswith(op_prefix) and r[1] == "pallas"}


# 300 planes of at most 4 KB each way: 256 share a grid step (4 MB over
# 2 buffers x 8 KB), and the second step's block is ragged, 44 of 256
BLOCKED_AVG_CASES = [
    # (shape, k, pads, declared): a window that IS the padded plane
    # (one fused reduction and a broadcast, never a kernel) ...
    ((2, 150, 7, 7), (7, 7), ((0, 0), (0, 0)), ((0, 0), (0, 0))),
    ((3, 100, 5, 5), (7, 7), ((1, 1), (1, 1)), ((1, 1), (1, 1))),
    ((2, 150, 4, 6), (5, 8), ((1, 0), (1, 1)), ((1, 0), (1, 1))),
    # ... and one that slides over it, 3x3/s1 "same" padding
    ((2, 150, 6, 6), (3, 3), ((1, 1), (1, 1)), ((1, 1), (1, 1))),
]

WHOLE_PLANE = {("pool_avg.fwd", "xla", "whole-plane"),
               ("pool_avg.bwd", "xla", "whole-plane")}


def _assert_ragged_blocks(launches):
    """300 planes of a 3x3/s1 window on 6x6: 256 a forward grid step;
    the backward's extended grid is wider (10x10 float32: two tiles of
    8 rows), so fewer planes fit; both last blocks ragged."""
    assert launches["pool_avg.fwd"] == {"planes_per_block": 256,
                                        "grid": (2,)}
    per_block = launches["pool_avg.bwd"]["planes_per_block"]
    assert 1 < per_block <= 256 and 300 % per_block
    assert launches["pool_avg.bwd"]["grid"] == (-(-300 // per_block),)


def _check_whole_plane(x, dims, strides, pads, declared, count_include_pad,
                       monkeypatch, rtol=1e-5, atol=1e-6):
    """Value and VJP of a whole-plane window in every kernel mode,
    against a NumPy mean over the padded plane and against
    ``reduce_window`` and its transpose; no mode launches a kernel."""
    (lo_h, hi_h), (lo_w, hi_w) = pads[2:]
    (_, dhi_h), (_, dhi_w) = declared[2:]
    h, w = x.shape[2:]
    # declared padding counts under count_include_pad, overflow never
    count = (lo_h + h + dhi_h) * (lo_w + w + dhi_w) \
        if count_include_pad else h * w
    xs = np.asarray(x, np.float64)
    want_y = xs.sum((2, 3), keepdims=True) / count
    # a cotangent the input's dtype holds exactly: one rounding to judge
    gy = np.asarray(jnp.asarray(_rng(1).randn(*want_y.shape), x.dtype),
                    np.float64)
    want_dx = np.broadcast_to(gy / count, xs.shape)
    y_rw, vjp_rw = jax.vjp(
        lambda a: jax.lax.reduce_window(a, 0.0, jax.lax.add, dims, strides,
                                        pads) / count,
        x.astype(jnp.float32))
    dx_rw = vjp_rw(jnp.asarray(gy, jnp.float32))[0]
    for mode in ("xla", "pallas", "auto"):
        monkeypatch.setenv("BIGDL_KERNELS", mode)
        dispatch.clear_decisions()
        y, vjp = jax.vjp(jax.jit(
            lambda a: avg_pool(a, dims, strides, pads, declared,
                               count_include_pad, True)), x)
        dx = vjp(jnp.asarray(gy, y.dtype))[0]
        assert y.shape[2:] == (1, 1) and y.dtype == x.dtype
        assert dx.shape == x.shape and dx.dtype == x.dtype
        for got, want in ((y, want_y), (y, y_rw), (dx, want_dx),
                          (dx, dx_rw)):
            np.testing.assert_allclose(np.asarray(got, np.float64),
                                       np.asarray(want, np.float64),
                                       rtol=rtol, atol=atol)
        assert set(dispatch.decisions()) == WHOLE_PLANE, mode
        assert not _launches("pool_avg")


@pytest.mark.parametrize("shape,k,p,declared", BLOCKED_AVG_CASES)
@pytest.mark.parametrize("count_include_pad", [True, False])
def test_blocked_avg_pool_parity(shape, k, p, declared, count_include_pad,
                                 monkeypatch):
    """The whole-plane rows: the one form, held to an independent mean
    in every mode.  The sliding row: many planes a grid step, the last
    block ragged, value and VJP of the blocked kernels against the XLA
    leg, with and without the padding in the divisor."""
    x = jnp.asarray(_rng(30).randn(*shape).astype(np.float32))
    dims, strides, pads = _full(k, (1, 1), p)
    declared = ((0, 0), (0, 0)) + declared
    if k != (3, 3):
        _check_whole_plane(x, dims, strides, pads, declared,
                           count_include_pad, monkeypatch)
        return
    _both_legs(lambda a: avg_pool(a, dims, strides, pads, declared,
                                  count_include_pad, True), x,
               monkeypatch=monkeypatch)
    _assert_ragged_blocks(_launches("pool_avg"))


def test_blocked_avg_pool_parity_bf16(monkeypatch):
    """The benchmark's dtype: the whole-plane form accumulates in
    float32 and rounds once, to bfloat16, forward and backward."""
    x = jnp.asarray(_rng(31).randn(2, 150, 7, 7), jnp.bfloat16)
    dims, strides, pads = _full((7, 7), (1, 1), ((0, 0), (0, 0)))
    _check_whole_plane(x, dims, strides, pads, pads, True, monkeypatch,
                       rtol=2 ** -8, atol=1e-6)


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
    """The leg follows the shape alone: on the CPU, as a TPU decides
    and inside a partitioned step (the four-chip cell), a window that
    is the whole padded plane is the one reduction, said so."""
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


@pytest.mark.parametrize("name,shape,fn,planes,grid", [
    # backward: three 10x10 extended planes in (8 KB each in float32),
    # 6x6 out (4 KB)
    ("pool_tie_split", (2, 150, 6, 6),
     lambda a: maxpool_tie_split(
         a, *_full((3, 3), (1, 1), ((1, 1), (1, 1)))), 73, (5,)),
    # backward: 8 KB (9x9 padded) + 3 x 4 KB in, 4 KB out
    ("lrn_within_channel", (2, 150, 5, 5),
     lambda a: within_channel_lrn(a, 5, 0.01, 0.75), 85, (4,)),
])
def test_blocked_launch_keeps_other_plane_kernels(name, shape, fn, planes,
                                                  grid, monkeypatch):
    """The launcher's other kernels on a ragged grid of plane blocks:
    same values as the XLA leg, as at one plane a step."""
    x = _rng(32).randn(*shape).astype(np.float32)
    if name == "pool_tie_split":
        x = np.round(x * 2.0) / 2.0     # ties inside windows
    _both_legs(fn, jnp.asarray(x), monkeypatch=monkeypatch)
    bwd = _launches(name)[name + ".bwd"]
    assert bwd == {"planes_per_block": planes, "grid": grid}


def test_blocked_smoothing_parity(monkeypatch):
    """``norm_pallas``'s smoothing stack is one plane a record: 300
    records of 6x6 share grid steps, 256 and a ragged 44."""
    x = jnp.asarray(_rng(33).randn(300, 2, 6, 6).astype(np.float32))
    _both_legs(lambda a: subtractive_norm(a, _gauss(3)), x, rtol=1e-4,
               atol=1e-5, monkeypatch=monkeypatch)
    # (``_coef``'s single plane of ones is a launch of its own)
    assert {"planes_per_block": 256, "grid": (2,)} in [
        r.launch for r in dispatch.decisions()
        if r[0] == "norm_smooth.fwd"]


def test_planes_per_block_reads_the_tiled_footprint():
    """P is the VMEM budget over the tile-rounded, double-buffered
    planes: small planes share a step, a large one keeps its own."""
    from bigdl_tpu.ops.pallas_util import VMEM_BUDGET, planes_per_block

    bf16, f32 = jnp.bfloat16, jnp.float32
    # a small plane: 7x7 (or 13x13) in bf16 is one (16, 128) tile
    assert planes_per_block([((7, 7), bf16), ((1, 1), bf16)], 262144) == 256
    assert planes_per_block([((13, 13), bf16), ((7, 7), bf16)],
                            262144) == 256
    # 17 rows of float32 are three (8, 128) tiles, 12 KB
    assert planes_per_block([((17, 130), f32)], 10 ** 6) \
        == VMEM_BUDGET // (2 * 3 * 8 * 256 * 4)
    # never more planes than there are
    assert planes_per_block([((7, 7), bf16), ((1, 1), bf16)], 6) == 6
    # cross-map LRN's [C + halo, HW tile] slabs, were they launched
    # here, and a 384x384 image: one a step
    assert planes_per_block([((196, 3200), bf16)] + [((192, 3200), bf16)] * 2,
                            256) == 1
    assert planes_per_block([((388, 388), f32)] + [((384, 384), f32)] * 2,
                            64) == 1


def test_tie_split_conserves_gradient_mass(monkeypatch):
    """Equal-split semantics: summed input gradient == summed output
    gradient regardless of ties (mass conservation), on both legs."""
    x = jnp.asarray(np.ones((1, 1, 4, 4), np.float32))  # ALL ties
    dims, strides, pads = _full((2, 2), (2, 2), ((0, 0), (0, 0)))
    for mode in ("xla", "pallas"):
        monkeypatch.setenv("BIGDL_KERNELS", mode)
        _, vjp = jax.vjp(
            lambda a: maxpool_tie_split(a, dims, strides, pads), x)
        gy = jnp.asarray(_rng(10).randn(1, 1, 2, 2).astype(np.float32))
        (dx,) = vjp(gy)
        np.testing.assert_allclose(float(jnp.sum(dx)),
                                   float(jnp.sum(gy)), rtol=1e-6)
        # each of the 4 tied positions gets exactly a quarter
        np.testing.assert_allclose(np.asarray(dx)[0, 0, :2, :2],
                                   np.asarray(gy)[0, 0, 0, 0] / 4.0,
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


def test_pool_nonstandard_rank_uses_xla_leg(monkeypatch):
    """5-D volumetric windows have no Pallas kernel — the op must fall
    back (and record it) rather than fail."""
    monkeypatch.setenv("BIGDL_KERNELS", "pallas")
    dispatch.clear_decisions()
    x = jnp.asarray(_rng(11).randn(1, 2, 4, 6, 6).astype(np.float32))
    d5, s5, p5 = (1, 1, 2, 2, 2), (1, 1, 2, 2, 2), ((0, 0),) * 5
    y, vjp = jax.vjp(lambda a: maxpool_tie_split(a, d5, s5, p5), x)
    vjp(jnp.ones_like(y))
    recs = [r for r in dispatch.decisions()
            if r[0].startswith("pool_tie_split")]
    assert recs and all(b == "xla" and reason == "unsupported-shape"
                        for _, b, reason in recs)


# ---------------------------------------------------------------------------
# dispatch contract
# ---------------------------------------------------------------------------

def test_bad_kernel_mode_raises(monkeypatch):
    monkeypatch.setenv("BIGDL_KERNELS", "palas")
    with pytest.raises(ValueError, match="BIGDL_KERNELS"):
        dispatch.kernel_mode()


def test_xla_mode_bypasses_pallas_everywhere(monkeypatch):
    """BIGDL_KERNELS=xla is the process-wide kill switch: drive every
    kernel-library layer fwd+bwd and assert not one Pallas decision."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.ops.pooling_pallas import pallas_pool_supported
    from bigdl_tpu.utils.rng import RNG

    monkeypatch.setenv("BIGDL_KERNELS", "xla")
    dispatch.clear_decisions()
    RNG.set_seed(0)
    layers = [
        nn.SpatialCrossMapLRN(5, 1e-4, 0.75),
        nn.SpatialWithinChannelLRN(3, 0.01, 0.75),
        nn.SpatialSubtractiveNormalization(4),
        nn.SpatialDivisiveNormalization(4),
        nn.SpatialContrastiveNormalization(4),
        nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1).split_ties(),
        nn.SpatialAveragePooling(3, 3, 2, 2, 1, 1, ceil_mode=True),
    ]
    x = jnp.asarray(_rng(12).randn(2, 4, 9, 9).astype(np.float32))
    for layer in layers:
        layer.evaluate()
        y, vjp = jax.vjp(jax.jit(layer.update_output), x)
        vjp(jnp.ones_like(y))
    recs = dispatch.decisions()
    assert recs, "kernel-library layers must record dispatch decisions"
    assert all(b == "xla" for _, b, _ in recs), \
        [r for r in recs if r[1] != "xla"]
    # the argmax-pool support gate honors the same switch: supported
    # under its own opt-in, vetoed the moment BIGDL_KERNELS=xla
    xb = jnp.zeros((2, 4, 8, 8), jnp.bfloat16)
    dims, strides, pads = _full((2, 2), (2, 2), ((0, 0), (0, 0)))
    monkeypatch.setenv("BIGDL_POOL_KERNEL", "interpret")
    monkeypatch.setenv("BIGDL_KERNELS", "auto")
    assert pallas_pool_supported(xb, dims, strides, pads)
    monkeypatch.setenv("BIGDL_KERNELS", "xla")
    assert not pallas_pool_supported(xb, dims, strides, pads)


def test_pallas_mode_forces_kernels(monkeypatch):
    monkeypatch.setenv("BIGDL_KERNELS", "pallas")
    dispatch.clear_decisions()
    x = jnp.asarray(_rng(13).randn(1, 4, 5, 5).astype(np.float32))
    y, vjp = jax.vjp(jax.jit(
        lambda a: within_channel_lrn(a, 3, 1e-4, 0.75)), x)
    vjp(jnp.ones_like(y))
    recs = [r for r in dispatch.decisions()
            if r[0].startswith("lrn_within_channel")]
    assert {op for op, _, _ in recs} \
        == {"lrn_within_channel.fwd", "lrn_within_channel.bwd"}
    assert all(b == "pallas" for _, b, _ in recs)


def test_auto_mode_off_tpu_prefers_xla(monkeypatch):
    """auto on the CPU suite = fused XLA (never the slow interpreter);
    the Pallas leg is still reachable via the explicit knob above."""
    monkeypatch.setenv("BIGDL_KERNELS", "auto")
    dispatch.clear_decisions()
    x = jnp.asarray(_rng(14).randn(1, 4, 5, 5).astype(np.float32))
    within_channel_lrn(x, 3, 0.01, 0.75)
    recs = [r for r in dispatch.decisions()
            if r[0] == "lrn_within_channel.fwd"]
    assert recs and recs[-1][1] == "xla" \
        and recs[-1][2] == "auto:off-tpu"


def test_dispatch_emits_telemetry_instant(tmp_path, monkeypatch):
    """Decisions are observable: a run log carries schema-valid
    kernel/dispatch instants naming op + backend."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.telemetry import schema

    monkeypatch.setenv("BIGDL_KERNELS", "xla")
    telemetry.start_run(str(tmp_path))
    try:
        x = jnp.asarray(_rng(15).randn(1, 3, 5, 5).astype(np.float32))
        within_channel_lrn(x, 3, 1e-4, 0.75)
    finally:
        telemetry.end_run()
    logs = list(tmp_path.glob("*.jsonl"))
    assert len(logs) == 1
    events, errors = schema.read_events(str(logs[0]))
    assert not errors
    inst = [e for e in events if e.get("name") == "kernel/dispatch"]
    assert inst and inst[0]["op"] == "lrn_within_channel.fwd" \
        and inst[0]["backend"] == "xla"
    assert not schema.validate_events(events)


def test_plane_launch_rides_on_the_dispatch_instant(tmp_path, monkeypatch):
    """A ``plane_call`` kernel's decision says how it was launched, in
    the run log's instant as in the ring; an XLA leg says nothing."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.telemetry import schema

    dims, strides, pads = _full((3, 3), (1, 1), ((1, 1), (1, 1)))
    x = jnp.asarray(_rng(17).randn(2, 150, 6, 6).astype(np.float32))
    telemetry.start_run(str(tmp_path))
    try:
        for mode in ("pallas", "xla"):
            monkeypatch.setenv("BIGDL_KERNELS", mode)
            jax.jit(lambda a: avg_pool(a, dims, strides, pads, pads, True,
                                       True))(x)
    finally:
        telemetry.end_run()
    events, errors = schema.read_events(str(next(tmp_path.glob("*.jsonl"))))
    assert not errors and not schema.validate_events(events)
    by_backend = {e["backend"]: e for e in events
                  if e.get("name") == "kernel/dispatch"}
    assert by_backend["pallas"]["planes_per_block"] == 256
    assert list(by_backend["pallas"]["grid"]) == [2]
    assert "planes_per_block" not in by_backend["xla"]


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
