"""The Mosaic dtype set, shared by the support predicates of the scan
kernels (``ops/delta_rule.py``, ``ops/ssd.py``)."""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["TPU_DTYPES", "mosaic_dtype"]

#: dtypes Mosaic compiles; anything else (f64 in the numeric-grad
#: suite) is interpret/XLA-only
TPU_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


def mosaic_dtype(dtype) -> bool:
    return dtype in TPU_DTYPES
