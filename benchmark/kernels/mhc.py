"""Bytes the multi-stream residual path (manifold-constrained
hyper-connections) needs around ONE sub-layer, one pass: ``tokens``
positions of ``streams`` residual streams of ``channels`` each.

The path is a 24-column projection, sixteen numbers a token through
Sinkhorn's iterations, and two weighted sums over the streams: next to
the streams' bytes its operations are nothing (2 x 24 FLOPs an entry the
projection, 2 x 4 the mixing), so nothing but bytes bounds it, and the
least bytes are those of a path that reads a token's streams ONCE, keeps
them in fast memory while it projects, normalises and iterates, and
writes them once:

- ``fwd``: the streams read once and written once, ``u`` (the sub-layer's
  input) written and ``f`` (its output) read: ``2 streams + 2`` arrays of
  ``tokens x channels``.
- ``bwd``: those of the forward read again (the streams and ``f``; ``u``
  is not needed), the cotangent of the new streams read and that of the
  old written, ``u``'s cotangent read and ``f``'s written: ``3 streams +
  3`` arrays.

``write="in_product"``: the part of that work which is left when the
compiler computes the forward's write (``X' = H_res X + H_post f``)
inside the fusion of the matrix product that makes ``f``, as XLA does
with the mixer's and the feed-forward's output projection: a forward then
reads the streams once and writes ``u`` (``streams + 1`` arrays).  What
the product's fusion reads and writes for the path is not in it, and
neither is its time in the events this is set against, so the share
cannot come out above what the bandwidth allows.  The backward's sums are
fusions of their own and are charged whole.

The coefficients (``streams^2 + 2 streams`` float32 a token) and the
projection's matrix are counted too; they are under 1% of it.
"""

from __future__ import annotations

ARRAYS = {"whole": {"fwd": lambda n: 2 * n + 2, "bwd": lambda n: 3 * n + 3},
          "in_product": {"fwd": lambda n: n + 1, "bwd": lambda n: 3 * n + 3}}


def least_bytes(direction: str, tokens: int, streams: int, channels: int,
                itemsize: int, write: str = "whole", **_) -> int:
    columns = 2 * streams + streams * streams
    passes = 1 if direction == "fwd" else 2
    arrays = ARRAYS[write][direction](streams) * tokens * channels * itemsize
    coefficients = tokens * columns * 4 * passes
    matrix = columns * streams * channels * itemsize * passes
    return arrays + coefficients + matrix


def least_seconds(direction: str, peak_bytes: float, **shape) -> float:
    return least_bytes(direction, **shape) / peak_bytes
