"""Argmax-index maxpool (packed-u32 fwd + Pallas scatter bwd) vs XLA
select-and-scatter.

Parity must hold on BOTH the value and the gradient — including
first-argmax tie routing, which the reference's CPU loop defines
lexicographically over (kh, kw) (``nn/NNPrimitive.scala:594-972``).
Everything runs in bfloat16 (the kernel's dtype gate — f32 would need a
u64 pack and falls back).  Gradients are drawn from powers of two so
every overlap sum is exact in bf16 and equality can be asserted
bitwise; tie-heavy inputs are generated by quantizing activations.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.ops.pooling_pallas import maxpool_argmax, pallas_pool_supported


@pytest.fixture(autouse=True)
def _pool_kernel_interpret(monkeypatch):
    """Scope the kernel opt-in to THIS module: a process-global env set
    at import would reroute every other suite's maxpool through the
    (slow) interpreter during pytest collection."""
    monkeypatch.setenv("BIGDL_POOL_KERNEL", "interpret")


def _sas_pool(x, dims, strides, pads):
    """The default path: reduce_window max with XLA's own VJP."""
    return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pads)


CASES = [
    # (shape NCHW, k, s, pads(h, w)) — inception/resnet pool geometries
    ((2, 8, 12, 12), (3, 3), (2, 2), ((0, 1), (0, 1))),   # ceil-mode 3x3/s2
    ((2, 8, 13, 13), (3, 3), (2, 2), ((1, 1), (1, 1))),   # padded odd extent
    ((2, 4, 14, 14), (2, 2), (2, 2), ((0, 0), (0, 0))),   # even 2x2/s2
    ((1, 8, 9, 9), (3, 3), (1, 1), ((0, 0), (0, 0))),     # stride-1 overlap
    ((2, 4, 7, 7), (7, 7), (7, 7), ((0, 0), (0, 0))),     # global 7x7
    ((1, 8, 10, 8), (3, 2), (2, 3), ((1, 0), (0, 1))),    # anisotropic
    # residue shortfall: lh(6) > ho(5) + jh_max(0) — the neighbor-block
    # spec must stay in range by construction, not by Mosaic's
    # block-index clamping (round-5 advisor finding on the bottom pad)
    ((1, 8, 11, 11), (2, 2), (2, 2), ((0, 0), (0, 0))),
]


def _full(dims, strides, pads):
    return ((1, 1) + dims, (1, 1) + strides, ((0, 0), (0, 0)) + pads)


def _pow2_grads(rng, shape):
    """Gradients from {1, 2, 4, 8} (sign-flipped): bf16-exact under any
    summation order, so routing errors can't hide behind rounding."""
    mag = 2.0 ** rng.integers(0, 4, shape)
    sign = rng.choice([-1.0, 1.0], shape)
    return jnp.asarray((mag * sign).astype(np.float32), dtype=jnp.bfloat16)


@pytest.mark.parametrize("shape,k,s,pads", CASES)
@pytest.mark.parametrize("tie_heavy", [False, True])
def test_value_and_grad_parity(shape, k, s, pads, tie_heavy):
    rng = np.random.default_rng(hash((shape, k, s, pads, tie_heavy)) % 2**32)
    x = rng.normal(size=shape).astype(np.float32)
    if tie_heavy:  # quantize to force equal maxima inside windows
        x = np.round(x * 2.0) / 2.0
    x = jnp.asarray(x, dtype=jnp.bfloat16)
    dims, strides, fpads = _full(k, s, pads)
    assert pallas_pool_supported(x, dims, strides, fpads)

    y_ref, vjp_ref = jax.vjp(lambda a: _sas_pool(a, dims, strides, fpads), x)
    # compiled: run eagerly, the interpreted kernel dispatches every
    # primitive of its body as a program of its own
    y_pal, vjp_pal = jax.vjp(
        jax.jit(lambda a: maxpool_argmax(a, dims, strides, fpads)), x)
    np.testing.assert_array_equal(np.asarray(y_ref, np.float32),
                                  np.asarray(y_pal, np.float32))

    gy = _pow2_grads(rng, y_ref.shape)
    (dx_ref,), (dx_pal,) = vjp_ref(gy), vjp_pal(gy)
    np.testing.assert_array_equal(np.asarray(dx_ref, np.float32),
                                  np.asarray(dx_pal, np.float32))


def test_forward_only_matches():
    """The undifferentiated primal is plain reduce_window."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 8, 12, 12)).astype(np.float32),
                    dtype=jnp.bfloat16)
    dims, strides, fpads = _full((3, 3), (2, 2), ((0, 1), (0, 1)))
    np.testing.assert_array_equal(
        np.asarray(maxpool_argmax(x, dims, strides, fpads), np.float32),
        np.asarray(_sas_pool(x, dims, strides, fpads), np.float32))


def test_layer_uses_kernel_and_matches_oracle():
    """SpatialMaxPooling routes through the kernel for bf16 inputs and
    matches torch (fwd + input grad, computed in f32 on the oracle
    side)."""
    import torch
    import bigdl_tpu.nn as nn

    rng = np.random.default_rng(3)
    x32 = rng.normal(size=(2, 8, 11, 11)).astype(np.float32)
    x = jnp.asarray(x32, dtype=jnp.bfloat16)
    x32 = np.asarray(x, np.float32)  # snap the oracle input to bf16 grid
    layer = nn.SpatialMaxPooling(3, 3, 2, 2).ceil()
    dims, strides, pads, _ = layer._window(x)
    assert pallas_pool_supported(x, dims, strides, pads)
    y, vjp = jax.vjp(jax.jit(lambda a: layer.forward(a)), x)

    tx = torch.tensor(x32, requires_grad=True)
    ty = torch.nn.functional.max_pool2d(tx, 3, 2, 0, ceil_mode=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               ty.detach().numpy(), rtol=0, atol=0)
    gy = _pow2_grads(rng, y.shape)
    ty.backward(torch.tensor(np.asarray(gy, np.float32)))
    (dx,) = vjp(gy)
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               tx.grad.numpy(), rtol=0, atol=0)


def test_nan_propagates():
    """A NaN activation must reach the pool output (a silent NaN->-inf
    would hide a diverged run); reduce_window is the behavior model."""
    x = np.zeros((1, 8, 4, 4), np.float32)
    x[0, 0, 1, 2] = np.nan
    x = jnp.asarray(x, dtype=jnp.bfloat16)
    dims, strides, fpads = _full((2, 2), (2, 2), ((0, 0), (0, 0)))
    y = maxpool_argmax(x, dims, strides, fpads)
    y_ref = _sas_pool(x, dims, strides, fpads)
    np.testing.assert_array_equal(np.isnan(np.asarray(y, np.float32)),
                                  np.isnan(np.asarray(y_ref, np.float32)))
    y2, _ = jax.vjp(
        jax.jit(lambda a: maxpool_argmax(a, dims, strides, fpads)), x)
    assert np.isnan(np.asarray(y2, np.float32)).sum() == 1


def test_negative_nan_propagates():
    """A sign-bit-set NaN (inf-inf on a diverging run) must also win its
    window — the naive sign-flip monotonic map would drop it below
    -inf."""
    x = np.full((1, 8, 4, 4), -1.0, np.float32)
    neg_nan = np.uint16(0xFFC0).view(np.float16)  # any negative NaN
    x = jnp.asarray(x, dtype=jnp.bfloat16)
    x = x.at[0, 0, 1, 1].set(jnp.asarray(np.float32(neg_nan)))
    assert bool(jnp.isnan(x[0, 0, 1, 1]))
    dims, strides, fpads = _full((2, 2), (2, 2), ((0, 0), (0, 0)))
    y, _ = jax.vjp(
        jax.jit(lambda a: maxpool_argmax(a, dims, strides, fpads)), x)
    assert np.isnan(np.asarray(y, np.float32)).sum() == 1


def test_wrap_gate_rejects_wide_planes():
    """Padded extents past 256 would wrap the low-8 coordinate code and
    invert tie order across the boundary — the gate must reject them."""
    x = jnp.zeros((1, 8, 260, 8), jnp.bfloat16)
    dims, strides, fpads = _full((2, 1), (1, 1), ((0, 0), (0, 0)))
    assert not pallas_pool_supported(x, dims, strides, fpads)
    x2 = jnp.zeros((1, 8, 224, 224), jnp.bfloat16)  # imagenet-sized: ok
    dims2, strides2, fpads2 = _full((3, 3), (2, 2), ((1, 1), (1, 1)))
    assert pallas_pool_supported(x2, dims2, strides2, fpads2)


def test_unsupported_falls_back():
    """f32 (no u64 pack), non-NCHW windows and int inputs must not take
    the kernel."""
    dims, strides, fpads = _full((2, 2), (2, 2), ((0, 0), (0, 0)))
    assert not pallas_pool_supported(jnp.zeros((2, 4, 8, 8), jnp.float32),
                                     dims, strides, fpads)
    assert not pallas_pool_supported(jnp.zeros((2, 4, 8, 8), jnp.int32),
                                     dims, strides, fpads)
    x5 = jnp.zeros((2, 3, 4, 8, 8), jnp.bfloat16)
    assert not pallas_pool_supported(
        x5, (1, 1, 2, 2, 2), (1, 1, 2, 2, 2), ((0, 0),) * 5)
