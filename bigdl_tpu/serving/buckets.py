"""Shape buckets: the fixed set of padded shapes serving compiles for.

Arrival-size variance is the production recompile hazard: every distinct
``[n, ...]`` batch shape is its own jit cache entry, and a compile in
the request path is a multi-second p99 spike (a cold Inception-v1
step compiles for 65-70 s on a v5e, PERF.md).  The policy here quantizes every
arrival onto a small, closed set of shapes:

- **batch buckets** — powers of two up to ``max_batch`` (overridable),
  so any batch of 1..max_batch rows pads to the next bucket and the
  worst-case padding waste is bounded at 50%;
- **sequence buckets** — for token models, the padded time axis also
  snaps to a bucket.  The default is the model's canonical sequence
  length (ONE bucket — numerics identical to the batch ``Predictor``);
  explicit buckets trade that equivalence for less padding compute on
  short requests (see docs/serving.md for the numerics caveat on
  non-causal models).

The bucket set is closed under ``warmup()``: the executor AOT-compiles
every (batch, seq) combination at startup, so steady-state traffic can
never meet a cold executable.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

__all__ = ["BucketPolicy", "pow2_buckets"]


def pow2_buckets(max_batch: int) -> Tuple[int, ...]:
    """1, 2, 4, ... up to (and including) ``max_batch``."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


class BucketPolicy:
    """The closed set of padded shapes one served model compiles for.

    ``batch_buckets``: ascending row-count buckets (default: powers of
    two up to ``max_batch``).  ``seq_buckets``: ascending time-axis
    buckets for token inputs (None = the feature shape is fixed and no
    axis is padded beyond batch).  ``pad_value`` fills padded cells —
    0 matches the text pipeline's reserved padding id and is inert for
    image rows (padded ROWS are sliced off the output either way).
    """

    def __init__(self, max_batch: int = 32,
                 batch_buckets: Optional[Sequence[int]] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 pad_value: float = 0.0):
        buckets = tuple(sorted(set(batch_buckets or
                                   pow2_buckets(max_batch))))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"bad batch buckets {buckets}")
        self.batch_buckets = buckets
        self.max_batch = buckets[-1]
        self.seq_buckets = tuple(sorted(set(seq_buckets))) \
            if seq_buckets else None
        if self.seq_buckets and self.seq_buckets[0] < 1:
            raise ValueError(f"bad seq buckets {self.seq_buckets}")
        self.pad_value = pad_value

    # -- selection ---------------------------------------------------------
    def batch_bucket(self, n: int) -> int:
        """Smallest bucket >= n (n > max_batch is a caller bug — the
        batcher never assembles past ``max_batch``)."""
        if n < 1:
            raise ValueError(f"batch of {n} rows")
        for b in self.batch_buckets:
            if b >= n:
                return b
        raise ValueError(f"batch of {n} rows exceeds the largest bucket "
                         f"{self.max_batch}")

    def seq_bucket(self, t: int) -> Optional[int]:
        """Smallest sequence bucket >= t; None when no seq bucketing.
        A sequence longer than every bucket clamps to the largest (the
        executor truncates — the bucket set is closed by construction)."""
        if self.seq_buckets is None:
            return None
        for s in self.seq_buckets:
            if s >= t:
                return s
        return self.seq_buckets[-1]

    def bucket_keys(self):
        """Every (batch, seq) combination — the warmup compile set."""
        seqs = self.seq_buckets or (None,)
        return [(b, s) for b in self.batch_buckets for s in seqs]

    # -- padding -----------------------------------------------------------
    def pad(self, x: np.ndarray, batch_bucket: int,
            seq_bucket: Optional[int] = None) -> np.ndarray:
        """Pad ``[n, ...]`` rows up to ``[batch_bucket, ...]`` (and the
        time axis 1 up to ``seq_bucket``); over-long sequences truncate
        to the largest bucket."""
        x = np.asarray(x)
        n = x.shape[0]
        if n > batch_bucket:
            raise ValueError(f"{n} rows > bucket {batch_bucket}")
        if seq_bucket is not None and x.ndim >= 2 \
                and x.shape[1] > seq_bucket:
            x = x[:, :seq_bucket]
        target = (batch_bucket,) + x.shape[1:]
        if seq_bucket is not None and x.ndim >= 2:
            target = (batch_bucket, seq_bucket) + x.shape[2:]
        if target == x.shape:
            return x
        out = np.full(target, self.pad_value, dtype=x.dtype)
        out[tuple(slice(0, d) for d in x.shape)] = x
        return out

    def __repr__(self):
        return (f"BucketPolicy(batch={list(self.batch_buckets)}, "
                f"seq={list(self.seq_buckets) if self.seq_buckets else None})")
