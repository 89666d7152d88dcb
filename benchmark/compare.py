"""The comparison that decides ``correct``: the program's first steps
against the plain reference's, number by number, each with a limit of
its own that the cell's workload file states with the readings it was
set from.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def worst_leaf_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The largest gap, over the leaves, between the program's norm of a
    leaf and the reference's, measured against the reference's norm of
    that leaf or of the median leaf, whichever is larger (some gradients
    are all but zero)."""
    got, want = np.asarray(got, float), np.asarray(want, float)
    if got.shape != want.shape:
        raise ValueError(f"{got.shape} leaves against {want.shape}")
    scale = np.maximum(want, np.median(want))
    return float(np.max(np.abs(got - want) / scale))


def numbers(got: Dict, want: Dict) -> Dict[str, float]:
    """``got``/``want``: ``losses`` of the first steps, ``grad1_norms``
    (per-leaf norms of the first gradient as the optimizer gets it) and
    ``delta_norms`` (per-leaf norms of the parameters' change after the
    last of those steps)."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        out[f"loss{i + 1}_gap"] = abs(float(a) - float(b))
    if len(got["losses"]) != len(want["losses"]):
        out["loss_steps_missing"] = float(
            abs(len(got["losses"]) - len(want["losses"])))
    out["grad1_worst_leaf_gap"] = worst_leaf_gap(got["grad1_norms"],
                                                 want["grad1_norms"])
    out["delta_worst_leaf_gap"] = worst_leaf_gap(got["delta_norms"],
                                                 want["delta_norms"])
    return out


def limit_of(name: str, limits: Dict[str, float]) -> float:
    if name in limits:
        return limits[name]
    if name.startswith("loss") and name.endswith("_gap"):
        return limits["loss_gap"]
    return 0.0  # a number nobody set a limit for may not differ at all


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(np.isfinite(v) and v <= limit_of(k, limits)
               for k, v in nums.items())


def lines(nums: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"compared {k}: {v:.6g} (limit {limit_of(k, limits):.6g})"
            f"{'' if np.isfinite(v) and v <= limit_of(k, limits) else '  <-- over'}"
            for k, v in nums.items()]
