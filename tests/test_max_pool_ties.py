"""The default max pool's tie rule, on the path every cell runs.

``nn.SpatialMaxPooling`` is ``lax.reduce_window`` forward and XLA's
select-and-scatter backward.  The reference's CPU loop sends a window's
cotangent to its FIRST maximum in (kh, kw) order
(``nn/NNPrimitive.scala:594-972``); so must this path, on value and
gradient.  Everything runs in bfloat16, the cells' dtype, under
``jax.jit``; cotangents are drawn from powers of two so every overlap
sum is exact in bf16 and equality can be asserted bitwise; tie-heavy
inputs are generated by quantizing activations.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn


def _pool(kw, kh, dw, dh, pad_w=0, pad_h=0, ceil=False):
    layer = nn.SpatialMaxPooling(kw, kh, dw, dh, pad_w, pad_h)
    return layer.ceil() if ceil else layer


CASES = [
    # (shape NCHW, layer) — inception/resnet pool geometries
    ((2, 8, 12, 12), _pool(3, 3, 2, 2, ceil=True)),    # ceil-mode 3x3/s2
    ((2, 8, 13, 13), _pool(3, 3, 2, 2, 1, 1)),         # padded odd extent
    ((2, 4, 14, 14), _pool(2, 2, 2, 2)),               # even 2x2/s2
    ((1, 8, 9, 9), _pool(3, 3, 1, 1)),                 # stride-1 overlap
    ((2, 4, 7, 7), _pool(7, 7, 7, 7)),                 # global 7x7
    ((1, 8, 10, 7), _pool(2, 3, 3, 2, 0, 1, ceil=True)),  # anisotropic
    ((1, 8, 11, 11), _pool(2, 2, 2, 2)),   # floor stops short of row 10
]


def _first_argmax_pool(x, layer):
    """The reference's loop in NumPy: each window's maximum, and the
    cotangent router that sends ``gy`` to the window's first maximum in
    (kh, kw) order; padding is never read."""
    dims, strides, pads, _ = layer._window(x)
    (kh, kw), (sh, sw), ((lo_h, hi_h), (lo_w, hi_w)) \
        = dims[2:], strides[2:], pads[2:]
    n, c, h, w = x.shape
    oh = (lo_h + h + hi_h - kh) // sh + 1
    ow = (lo_w + w + hi_w - kw) // sw + 1
    y = np.full((n, c, oh, ow), -np.inf, np.float32)
    first = np.zeros((n, c, oh, ow, 2), np.int64)
    for i, j in np.ndindex(oh, ow):
        for dh, dw in np.ndindex(kh, kw):
            r, q = i * sh - lo_h + dh, j * sw - lo_w + dw
            if 0 <= r < h and 0 <= q < w:
                better = x[:, :, r, q] > y[:, :, i, j]
                y[:, :, i, j] = np.where(better, x[:, :, r, q], y[:, :, i, j])
                first[:, :, i, j][better] = (r, q)

    def route(gy):
        dx = np.zeros(x.shape, np.float32)
        for b, ch, i, j in np.ndindex(n, c, oh, ow):
            r, q = first[b, ch, i, j]
            dx[b, ch, r, q] += gy[b, ch, i, j]
        return dx

    return y, route


def _pow2_grads(rng, shape, dtype=jnp.bfloat16):
    """Gradients from {1, 2, 4, 8} (sign-flipped): bf16-exact under any
    summation order, so routing errors can't hide behind rounding."""
    mag = 2.0 ** rng.integers(0, 4, shape)
    sign = rng.choice([-1.0, 1.0], shape)
    return jnp.asarray((mag * sign).astype(np.float32), dtype=dtype)


@pytest.mark.parametrize("shape,layer", CASES,
                         ids=[f"case{i}" for i in range(len(CASES))])
@pytest.mark.parametrize("tie_heavy", [False, True])
def test_value_and_grad_parity(shape, layer, tie_heavy):
    rng = np.random.default_rng(len(CASES) * tie_heavy + CASES.index(
        (shape, layer)))
    x = rng.normal(size=shape).astype(np.float32)
    if tie_heavy:  # quantize to force equal maxima inside windows
        x = np.round(x * 2.0) / 2.0
    x = jnp.asarray(x, dtype=jnp.bfloat16)
    y, vjp = jax.vjp(jax.jit(layer.update_output), x)
    y_ref, route = _first_argmax_pool(np.asarray(x, np.float32), layer)
    np.testing.assert_array_equal(np.asarray(y, np.float32), y_ref)
    gy = _pow2_grads(rng, y.shape)
    np.testing.assert_array_equal(np.asarray(vjp(gy)[0], np.float32),
                                  route(np.asarray(gy, np.float32)))


def _one_nan_out(x):
    layer = _pool(2, 2, 2, 2)
    y, vjp = jax.vjp(jax.jit(layer.update_output), x)
    dx = vjp(jnp.ones_like(y))[0]
    return (np.isnan(np.asarray(y, np.float32)).sum(),
            np.isnan(np.asarray(dx, np.float32)).sum())


def test_nan_propagates():
    """A NaN activation must reach the pool output (a silent NaN->-inf
    would hide a diverged run), and only its own window's."""
    x = np.zeros((1, 8, 4, 4), np.float32)
    x[0, 0, 1, 2] = np.nan
    assert _one_nan_out(jnp.asarray(x, dtype=jnp.bfloat16)) == (1, 0)


def test_negative_nan_propagates():
    """A sign-bit-set NaN (inf-inf on a diverging run) must also win its
    window."""
    x = jnp.full((1, 8, 4, 4), -1.0, jnp.bfloat16)
    neg_nan = np.uint16(0xFFC0).view(np.float16)  # any negative NaN
    x = x.at[0, 0, 1, 1].set(jnp.asarray(np.float32(neg_nan), jnp.bfloat16))
    assert bool(jnp.isnan(x[0, 0, 1, 1]))
    assert _one_nan_out(x) == (1, 0)
