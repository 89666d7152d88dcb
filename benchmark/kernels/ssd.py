"""Operations and bytes the Mamba-2 state-space scan needs, one call of
one layer: ``[seq, heads, head_dim]`` inputs ``x``, one step ``dt`` a head
and position, ``[seq, groups, state]`` projections ``B`` and ``C`` (a
group serves ``heads / groups`` heads), one decay rate ``A`` and one skip
``D`` a head.

FLOPs are those of the CHUNKED form at chunk ``Q`` (the form a chip can
run: token by token the scan is ``seq`` dependent rank-one updates of a
``head_dim x state`` matrix a head), 2 a multiply-add, a chunk:

- ``C B^T``, once a GROUP: ``2 Q^2 state``;
- ``(decay . C B^T) (dt x)``, a head: ``2 Q^2 head_dim``;
- the chunk's own state ``(decay dt x)^T B`` and what the entering state
  adds, ``C S``, a head: ``2 Q head_dim state`` each.

Elementwise work (decays, masks, the skip, the carried state's 2 FLOPs an
element a chunk) is not counted.  The backward is charged twice the
forward, as every matrix product's is; a forward that is computed again
(under ``nn.Remat``) is a call of its own in the trace.

Bytes: every operand read once and every result written once.  ``fwd``
reads x, dt, B, C and writes y; ``bwd`` reads those and y's cotangent
and writes the four gradients.  dt and its gradient are float32.
"""

from __future__ import annotations

PRODUCTS = {"fwd": 1, "bwd": 2}


def chunk_flops(chunk: int, heads: int, groups: int, head_dim: int,
                state: int) -> int:
    """Forward FLOPs of one chunk of one layer's heads."""
    q = chunk
    return (groups * 2 * q * q * state
            + heads * (2 * q * q * head_dim + 2 * 2 * q * head_dim * state))


def flops(direction: str, heads: int, groups: int, seq: int, head_dim: int,
          state: int, chunk: int, **_) -> int:
    chunks = -(-seq // chunk)
    return PRODUCTS[direction] * chunks * chunk_flops(
        chunk, heads, groups, head_dim, state)


def least_bytes(direction: str, heads: int, groups: int, seq: int,
                head_dim: int, state: int, itemsize: int, **_) -> int:
    xy = heads * seq * head_dim * itemsize    # x, y, dy, dx: one each
    bc = groups * seq * state * itemsize      # B, C, dB, dC: one each
    row = heads * seq * 4                     # dt, ddt: float32
    return {"fwd": 2 * xy + 2 * bc + row,
            "bwd": 4 * xy + 4 * bc + 2 * row}[direction]


def least_seconds(direction: str, peak_flops: float, peak_bytes: float,
                  **shape) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak
    bytes/s."""
    return max(flops(direction, **shape) / peak_flops,
               least_bytes(direction, **shape) / peak_bytes)
