"""Operations and bytes the flash-attention kernels need when a head's
queries and keys are of one width and its values of another (latent
attention: ``qk_dim`` = the part without positions plus the rotary part,
``value_dim`` the values'), one call of one layer: ``[heads, seq,
qk_dim]`` queries and keys, ``[heads, seq, value_dim]`` values, causal,
every head with keys and values of its own.

Score elements are counted EXACTLY under the mask
(``kernels/attention.py kept_elements``), and every product over ITS OWN
width, 2 FLOPs a multiply-add: a kernel that pads its queries and keys to
a wider tile computes more and is charged nothing for it, so it reads
lower and never higher.

- ``fwd`` (q, k, v -> out, logsumexp): ``q k^T`` over ``qk_dim``, ``p v``
  over ``value_dim``.
- ``dq`` (q, k, v, do, lse, delta -> dq): ``q k^T`` and ``ds k`` over
  ``qk_dim``, ``do v^T`` over ``value_dim``.
- ``dkv`` (the same -> dk, dv): ``q k^T`` and ``ds^T q`` over ``qk_dim``,
  ``p^T do`` and ``do v^T`` over ``value_dim``.

Bytes: every operand read once and every result written once.
"""

from __future__ import annotations

from benchmark.kernels.attention import kept_elements

#: products of each call over (qk_dim, value_dim)
PRODUCTS = {"fwd": (1, 1), "dq": (2, 1), "dkv": (2, 2)}


def flops(direction: str, heads: int, seq: int, qk_dim: int,
          value_dim: int, **_) -> int:
    over_qk, over_v = PRODUCTS[direction]
    return 2 * (over_qk * qk_dim + over_v * value_dim) * heads \
        * kept_elements(seq)


def least_bytes(direction: str, heads: int, seq: int, qk_dim: int,
                value_dim: int, itemsize: int, **_) -> int:
    qk = heads * seq * qk_dim * itemsize       # q, k, dq, dk: one each
    v = heads * seq * value_dim * itemsize     # v, out, do, dv: one each
    row = heads * seq * 4                      # logsumexp, delta: float32
    return {"fwd": 2 * qk + 2 * v + row,
            "dq": 3 * qk + 2 * v + 2 * row,
            "dkv": 3 * qk + 3 * v + 2 * row}[direction]


def least_seconds(direction: str, peak_flops: float, peak_bytes: float,
                  **shape) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak
    bytes/s."""
    return max(flops(direction, **shape) / peak_flops,
               least_bytes(direction, **shape) / peak_bytes)
