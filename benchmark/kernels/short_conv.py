"""Bytes the double-gated short convolution needs, one call of one layer:
``tokens`` positions of ``channels`` channels, ``[B, C, u]`` in, ``C *
conv(B * u)`` out, a filter of ``taps`` taps a channel.

The layer is elementwise work and a three-tap sum: nothing but bytes
bounds it.  Every operand read once and every result written once:

- ``fwd``: read ``B``, ``C``, ``u``, write the gated result: 4 arrays.
- ``bwd``: read them and the result's cotangent, write the three
  gradients: 7 arrays, and the filter's gradient (float32).

``gate_out="in_matmul"``: the part of that work which is left when the
compiler computes the output gate (``C *``, and ``C``'s gradient) inside
the fusion of the matrix product next to it, as XLA does with the output
projection: a forward then reads ``B`` and ``u`` and writes one array
for the product to read (3 arrays); a backward reads ``B``, ``u`` and the
cotangent of the convolution's result and writes ``B``'s and ``u``'s
gradients (5 arrays; the filter's gradient is summed beside the product
too).  What the product's fusion reads and writes for the gate is not in
it, and neither is its time in the events this is set against, so the
share cannot come out above what the bandwidth allows.
"""

from __future__ import annotations

ARRAYS = {"whole": {"fwd": 4, "bwd": 7}, "in_matmul": {"fwd": 3, "bwd": 5}}


def least_bytes(direction: str, tokens: int, channels: int, taps: int,
                itemsize: int, gate_out: str = "whole", **_) -> int:
    arrays = ARRAYS[gate_out][direction] * tokens * channels * itemsize
    filters = channels * taps * 4
    if direction == "bwd" and gate_out == "whole":
        filters *= 2                       # the filter and its gradient
    return arrays + filters


def least_seconds(direction: str, peak_bytes: float, **shape) -> float:
    return least_bytes(direction, **shape) / peak_bytes
