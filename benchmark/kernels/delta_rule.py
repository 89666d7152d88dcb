"""Operations and bytes the gated delta rule needs, one call of one
layer: ``[heads, seq, key_dim]`` queries and keys, ``[heads, seq,
value_dim]`` values, one decay and one ``beta`` a head and position.

FLOPs are those of the CHUNKED form at chunk ``C`` (the form a chip can
run: token by token the rule is ``seq`` dependent rank-one updates), 2 a
multiply-add, a chunk and head:

- ``k k^T`` and ``q k^T``: ``2 C^2 key_dim`` each;
- the unit-lower-triangular system by substitution, ``C^2 / 2``
  multiply-adds a right-hand column, ``value_dim + key_dim`` columns:
  ``C^2 (value_dim + key_dim)``;
- ``Kc S``, ``(exp(G) q) S`` and ``(exp(G_C - G) k)^T U`` against the
  state: ``2 C key_dim value_dim`` each;
- ``P U``: ``2 C^2 value_dim``.

Elementwise work (decays, masks, gates) is not counted.  The backward is
charged twice the forward, as every matrix product's is; a forward that
is computed again (under ``nn.Remat``) is a call of its own in the
trace.

Bytes: every operand read once and every result written once.  ``fwd``
reads q, k, v, g, beta and writes o; ``bwd`` reads those and o's
cotangent and writes the five gradients.  g, beta and their gradients
are float32.
"""

from __future__ import annotations

PRODUCTS = {"fwd": 1, "bwd": 2}


def chunk_flops(chunk: int, key_dim: int, value_dim: int) -> int:
    """Forward FLOPs of one chunk of one head."""
    c = chunk
    return (2 * 2 * c * c * key_dim + c * c * (value_dim + key_dim)
            + 3 * 2 * c * key_dim * value_dim + 2 * c * c * value_dim)


def flops(direction: str, heads: int, seq: int, key_dim: int,
          value_dim: int, chunk: int, **_) -> int:
    chunks = -(-seq // chunk)
    return PRODUCTS[direction] * heads * chunks * chunk_flops(
        chunk, key_dim, value_dim)


def least_bytes(direction: str, heads: int, seq: int, key_dim: int,
                value_dim: int, itemsize: int, **_) -> int:
    qk = heads * seq * key_dim * itemsize     # q, k, dq, dk: one each
    vo = heads * seq * value_dim * itemsize   # v, o, do, dv: one each
    row = heads * seq * 4                     # g, beta, dg, dbeta: float32
    return {"fwd": 2 * qk + 2 * vo + 2 * row,
            "bwd": 4 * qk + 3 * vo + 4 * row}[direction]


def least_seconds(direction: str, peak_flops: float, peak_bytes: float,
                  **shape) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak
    bytes/s."""
    return max(flops(direction, **shape) / peak_flops,
               least_bytes(direction, **shape) / peak_bytes)
