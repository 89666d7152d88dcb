"""Operations and bytes the flash-attention kernels need, one call of one
layer: ``[heads, seq, head_dim]`` queries over ``kv_heads`` key/value
heads, causal, optionally within a ``window``.

Score elements are counted EXACTLY under the masks (``kept_elements``;
``tests/test_laguna.py`` checks it against a brute-force mask), not by
the whole blocks a kernel computes, so a roofline share over 100% cannot
come from the count.  Each call is charged the matrix products its own
contract needs, 2 FLOPs a multiply-add over ``head_dim``:

- ``fwd`` (q, k, v -> out, logsumexp): ``q k^T`` and ``p v``: 2.
- ``dq`` (q, k, v, do, lse, delta -> dq): ``q k^T`` again, ``do v^T``,
  ``ds k``: 3.
- ``dkv`` (the same -> dk, dv): ``q k^T``, ``p^T do``, ``do v^T``,
  ``ds^T q``: 4.

Bytes: every operand read once and every result written once (k and v
once a call, not once a query head).
"""

from __future__ import annotations

from typing import Optional

PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def kept_elements(seq: int, window: Optional[int] = None) -> int:
    """(query, key) pairs of one head with ``key <= query`` and, under a
    window, ``query - key < window``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def flops(direction: str, heads: int, seq: int, head_dim: int,
          window: Optional[int] = None, **_) -> int:
    return PRODUCTS[direction] * 2 * head_dim * heads * kept_elements(
        seq, window)


def least_bytes(direction: str, heads: int, kv_heads: int, seq: int,
                head_dim: int, itemsize: int, **_) -> int:
    q = heads * seq * head_dim * itemsize      # q, out, do, dq: one each
    kv = kv_heads * seq * head_dim * itemsize  # k, v, dk, dv: one each
    row = heads * seq * 4                      # logsumexp, delta: float32
    return {"fwd": 2 * q + 2 * kv + row,
            "dq": 3 * q + 2 * kv + 2 * row,
            "dkv": 2 * q + 4 * kv + 2 * row}[direction]


def least_seconds(direction: str, peak_flops: float, peak_bytes: float,
                  **shape) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak
    bytes/s."""
    return max(flops(direction, **shape) / peak_flops,
               least_bytes(direction, **shape) / peak_bytes)
