"""Arithmetic of the end-to-end metrics, kept with the benchmark so that
no later PR can change how a number is computed.

All inputs are stamps of the harness's own clock (``time.perf_counter``),
taken at the two places the Optimizer loop calls back into the harness:
the end trigger (loop top) and the train summary (after ``float(loss)``).
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), of all the values given."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    if len(v) == 1:
        return float(v[0])
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def window_metrics(tops: Sequence[float], ends: Sequence[float],
                   t_open: float, t_close: float, batch: int) -> Dict:
    """``tops[i]``/``ends[i]``: loop top and loss-on-the-host stamps of
    the steps that STARTED inside the window ``[t_open, t_close]``.  A
    rate is taken over all the work and all the time of the window: only
    steps whose loss reached the host by ``t_close`` count, over the
    whole window."""
    if len(tops) != len(ends):
        raise ValueError("one end stamp per step")
    if t_close <= t_open:
        raise ValueError("empty window")
    done = [(a, b) for a, b in zip(tops, ends) if b <= t_close]
    step_ms = [(b - a) * 1e3 for a, b in done]
    out = {
        "window_s": t_close - t_open,
        "steps_started": len(tops),
        "steps_completed": len(done),
        "records_per_s": len(done) * batch / (t_close - t_open),
    }
    if step_ms:
        out["step_p50_ms"] = percentile(step_ms, 50)
        out["step_p90_ms"] = percentile(step_ms, 90)
        out["step_max_ms"] = max(step_ms)
    return out


def diffs(totals: Sequence[float]) -> List[float]:
    """Per-step values from a running total read once per step."""
    return [b - a for a, b in zip(totals[:-1], totals[1:])]
