"""Device-side facts for the telemetry stream: XLA cost analysis
(flops/bytes -> MFU denominators), compiled-executable memory analysis
(HBM breakdown, donated-buffer aliasing), and live device memory.

Levels (``BIGDL_TELEMETRY_DEVICE``):

- ``off``  — emit nothing;
- ``auto`` (default) — everything that costs at most a re-lower of the
  already-traced program: ``Lowered.cost_analysis()`` flops/bytes,
  host-computed donated-buffer bytes, ``device.memory_stats()``;
- ``full`` — additionally AOT-compiles the lowered program to read
  ``Compiled.memory_analysis()`` (argument/output/temp/alias bytes —
  the HBM breakdown).  NOTE: JAX's AOT compile does NOT share the jit
  dispatch cache, so ``full`` pays one extra XLA compile per step
  object; it is for diagnosis sessions, not always-on production runs.

MFU is *not* computed here — the log carries ``flops_per_step`` +
``peak_flops_per_device`` + ``device_count`` and the CLI divides by the
measured step time, so the estimate stays recomputable from the
artifact alone.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

__all__ = ["CHIP_PEAKS", "peak_flops_per_device",
           "peak_int8_ops_per_device", "peak_bw_per_device",
           "hbm_per_device", "normalize_cost_analysis",
           "cost_facts", "memory_facts", "live_memory_facts",
           "donated_bytes", "collect_device_facts", "mfu_estimate"]

#: THE per-chip peak table, keyed by ``device_kind`` prefix (longest
#: prefix wins: "TPU v5 lite" over "TPU v5").  Columns: dense bf16
#: FLOP/s, int8 OP/s, aggregate chip-to-chip interconnect (ICI)
#: bytes/s, HBM GiB.  Source: Google Cloud TPU documentation, the
#: "System architecture" page of each version (e.g. "TPU v5e": 197
#: TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM, 1,600 Gbit/s ICI); chips
#: with no separate int8 rate list their bf16 rate.  The telemetry
#: stream (report, comms, memory) reads this table and no other.
CHIP_PEAKS = {
    "TPU v2": (45e12, 45e12, 1.0e11, 8),
    "TPU v3": (123e12, 123e12, 1.4e11, 16),
    "TPU v4 lite": (137e12, 137e12, 3.0e11, 8),
    "TPU v4": (275e12, 275e12, 3.0e11, 32),
    "TPU v5 lite": (197e12, 393e12, 2.0e11, 16),
    "TPU v5e": (197e12, 393e12, 2.0e11, 16),
    "TPU v5p": (459e12, 918e12, 6.0e11, 95),
    "TPU v5": (459e12, 918e12, 6.0e11, 95),
    "TPU v6 lite": (918e12, 1836e12, 3.6e11, 32),
    "TPU v6e": (918e12, 1836e12, 3.6e11, 32),
}
_BF16, _INT8, _ICI, _HBM = range(4)


def _chip_peak(device_kind: str, column: int, required: bool = False):
    kind = (device_kind or "").lower()
    names = [n for n in CHIP_PEAKS if kind.startswith(n.lower())]
    if names:
        return CHIP_PEAKS[max(names, key=len)][column]
    if required and kind != "cpu":
        # a utilization against a guessed peak is a wrong number, and a
        # silently missing field hides that nothing was measured
        raise ValueError(
            f"unknown accelerator device_kind {device_kind!r}: add it "
            f"to telemetry.device.CHIP_PEAKS with its source")
    return None


def peak_flops_per_device(device_kind: str) -> Optional[float]:
    """Dense bf16 peak FLOP/s for one device.  None for the CPU (no
    meaningful MFU denominator); an accelerator that is not in
    :data:`CHIP_PEAKS` raises.  ``BIGDL_PEAK_FLOPS`` (FLOP/s) overrides
    the table."""
    env = os.environ.get("BIGDL_PEAK_FLOPS")
    if env:
        return float(env)
    return _chip_peak(device_kind, _BF16, required=True)


def peak_int8_ops_per_device(device_kind: str) -> Optional[float]:
    """int8 peak OP/s for one device — the int8 inference leg's
    utilization denominator; same unknown-device contract as
    :func:`peak_flops_per_device`."""
    return _chip_peak(device_kind, _INT8, required=True)


def peak_bw_per_device(device_kind: str) -> Optional[float]:
    """Aggregate interconnect bytes/s for one device, or None when
    unknown (CPU collectives have no meaningful peak).  ``BIGDL_PEAK_BW``
    (bytes/s) overrides the table — also the only way to describe a
    DCN-spanning slice, whose cross-slice links are far slower than
    ICI."""
    env = os.environ.get("BIGDL_PEAK_BW")
    if env:
        return float(env)
    return _chip_peak(device_kind, _ICI)


def hbm_per_device(device_kind: str) -> Optional[int]:
    """HBM bytes of one device from the per-chip table, or None when
    unknown (CPU has no fixed budget; ``BIGDL_HBM_GB`` is resolved by
    the caller, ``memory.hbm_limit_bytes``, so this stays a pure table
    lookup)."""
    gb = _chip_peak(device_kind, _HBM)
    return gb * (1 << 30) if gb else None


def normalize_cost_analysis(cost) -> Dict[str, Any]:
    """``cost_analysis()`` returns a dict on some backends/JAX versions
    and a one-element list of dicts on others — always hand back the
    dict (shared by every reader of a cost analysis)."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}


def cost_facts(lowered) -> Dict[str, Any]:
    """flops / bytes accessed from a ``jax.stages.Lowered`` (HLO-level
    cost analysis — no XLA compile)."""
    out: Dict[str, Any] = {}
    try:
        cost = normalize_cost_analysis(lowered.cost_analysis())
        if cost.get("flops"):
            out["flops_per_step"] = float(cost["flops"])
        if cost.get("bytes accessed"):
            out["bytes_accessed"] = float(cost["bytes accessed"])
    except Exception:  # noqa: BLE001 - facts are best-effort
        pass
    return out


def memory_facts(compiled) -> Dict[str, Any]:
    """HBM breakdown from ``Compiled.memory_analysis()`` (argument /
    output / temp / generated-code / donation-alias bytes)."""
    out: Dict[str, Any] = {}
    try:
        ma = compiled.memory_analysis()
        for key, attr in (("argument_bytes", "argument_size_in_bytes"),
                          ("output_bytes", "output_size_in_bytes"),
                          ("temp_bytes", "temp_size_in_bytes"),
                          ("code_bytes", "generated_code_size_in_bytes"),
                          ("alias_bytes", "alias_size_in_bytes")):
            v = getattr(ma, attr, None)
            if v is not None:
                out[key] = int(v)
    except Exception:  # noqa: BLE001
        pass
    return out


def live_memory_facts(device=None) -> Dict[str, Any]:
    """Live allocator stats of one device (``bytes_in_use`` /
    ``bytes_limit`` / ``peak_bytes_in_use`` where the backend reports
    them; CPU reports nothing)."""
    out: Dict[str, Any] = {}
    try:
        import jax

        dev = device if device is not None else jax.local_devices()[0]
        stats = dev.memory_stats()
        if stats:
            for key in ("bytes_in_use", "bytes_limit",
                        "peak_bytes_in_use", "largest_alloc_size"):
                if key in stats:
                    out[key] = int(stats[key])
    except Exception:  # noqa: BLE001
        pass
    return out


def donated_bytes(*trees) -> int:
    """Host-side accounting of the donated argument trees (params /
    opt_state / buffers): the bytes the step re-uses in place instead of
    double-buffering."""
    total = 0
    try:
        import jax

        for tree in trees:
            for leaf in jax.tree_util.tree_leaves(tree):
                nbytes = getattr(leaf, "nbytes", None)
                if nbytes is None:
                    size = getattr(leaf, "size", 0)
                    itemsize = getattr(getattr(leaf, "dtype", None),
                                       "itemsize", 0)
                    nbytes = size * itemsize
                total += int(nbytes)
    except Exception:  # noqa: BLE001
        pass
    return total


def collect_device_facts(lowered, donated_trees=(), level: str = "auto"
                         ) -> Dict[str, Any]:
    """Assemble one ``device_facts`` payload from a lowered step (see
    module docstring for what each level costs)."""
    if level == "off":
        return {}
    facts = cost_facts(lowered)
    db = donated_bytes(*donated_trees)
    if db:
        facts["donated_bytes"] = db
    # live allocator peaks ride the DEFAULT level (one attr read per
    # device — the runbook's first OOM question must not need `full`);
    # the flat device-0 keys stay for back-compat, the per-device list
    # covers multi-chip hosts
    facts.update(live_memory_facts())
    try:
        from bigdl_tpu.telemetry.memory import live_hbm

        per_dev = live_hbm()
        if len(per_dev) > 1:
            facts["live_memory"] = per_dev
    except Exception:  # noqa: BLE001 - facts are best-effort
        pass
    try:
        import jax

        dev = jax.devices()[0]
        facts["device_kind"] = dev.device_kind
        facts["device_count"] = jax.device_count()
        peak = peak_flops_per_device(dev.device_kind)
        if peak:
            facts["peak_flops_per_device"] = peak
        peak_bw = peak_bw_per_device(dev.device_kind)
        if peak_bw:
            facts["peak_bw_per_device"] = peak_bw
    except Exception:  # noqa: BLE001
        pass
    if level == "full":
        try:
            facts.update(memory_facts(lowered.compile()))
        except Exception:  # noqa: BLE001
            pass
    return facts


def mfu_estimate(flops_per_step: float, step_seconds: float,
                 peak_flops_per_dev: float, device_count: int = 1
                 ) -> Optional[float]:
    """Model FLOP utilization: achieved FLOP/s over the fleet peak.
    ``flops_per_step`` counts the GLOBAL step (XLA cost analysis of the
    SPMD program), so the denominator scales by device count."""
    if not (flops_per_step and step_seconds and peak_flops_per_dev):
        return None
    denom = peak_flops_per_dev * max(device_count, 1)
    return (flops_per_step / step_seconds) / denom
