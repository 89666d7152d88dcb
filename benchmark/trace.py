"""Reduction of a ``jax.profiler`` trace to numbers, with JAX alone
(``jax.profiler.ProfileData``): device busy time, idle gaps and who the
host was with during them, time by operation name, and the part of the
collectives' time that no other operation hides.

Everything works on plain ``(start_s, end_s)`` intervals so that
``tests/test_trace.py`` can check it on a made-up trace.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: the harness writes this annotation next to a ``perf_counter`` reading:
#: the one shared stamp that puts its own clock on the profiler's
STAMP = "bench/stamp"

#: device lines that hold one event per executed operation
_OP_LINES = ("XLA Ops",)

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.I)


@dataclass
class DeviceTrace:
    """Operation events of one device: ``(name, start_s, end_s)``."""
    name: str
    ops: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DeviceTrace]
    #: profiler-clock second at which ``STAMP`` began, if it was found
    stamp_s: Optional[float] = None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    b = list(b)
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` given the merged busy ones."""
    return subtract([(lo, hi)], clip(busy, lo, hi))


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which an operation ran, averaged over
    the devices of the trace."""
    if not trace.devices:
        return 0.0
    per = [total(clip(union((s, e) for _, s, e in d.ops), lo, hi))
           for d in trace.devices]
    return sum(per) / len(per)


HLO = re.compile(r"^%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(name: str) -> str:
    """An event of the ``XLA Ops`` line is named by its whole HLO
    instruction; this keeps the result's name, the opcode and the
    result's shape without its layout (``_.9 custom-call
    bf16[262144,7,7]``), with ``pallas`` for a Mosaic kernel."""
    m = HLO.match(name)
    if not m:
        return name[:96]
    op = "pallas" if "tpu_custom_call" in name else m.group(3)
    return f"{m.group(1)} {op} {LAYOUT.sub('', m.group(2))}"[:96]


def seconds_by_name(trace: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds by (short) operation name inside ``[lo, hi]``,
    averaged over the devices (an event that nests inside another counts
    in both)."""
    out: Dict[str, float] = {}
    for d in trace.devices:
        for name, s, e in d.ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                name = short_name(name)
                out[name] = out.get(name, 0.0) + (e - s)
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in out.items()}


def matching_seconds(trace: Trace, lo: float, hi: float,
                     pattern: str) -> float:
    """Device seconds, merged per device so that nesting does not count
    twice, of the operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    per = []
    for d in trace.devices:
        iv = union((s, e) for n, s, e in d.ops if rx.search(n))
        per.append(total(clip(iv, lo, hi)))
    return sum(per) / max(len(per), 1)


def exposed_collective_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in which a collective ran on a device and no other
    operation did, averaged over the devices."""
    per = []
    for d in trace.devices:
        coll = union((s, e) for n, s, e in d.ops if COLLECTIVE.search(n))
        rest = union((s, e) for n, s, e in d.ops
                     if not COLLECTIVE.search(n))
        per.append(total(clip(subtract(coll, rest), lo, hi)))
    return sum(per) / max(len(per), 1)


def idle_gaps_by_phase(trace: Trace, lo: float, hi: float,
                       phases: Sequence[Tuple[str, float, float]]
                       ) -> Dict[str, float]:
    """Idle seconds of the first device inside ``[lo, hi]``, split by
    what the host was doing: ``phases`` are ``(name, start_s, end_s)`` on
    the profiler's clock; idle time under no phase goes to
    ``unattributed``."""
    if not trace.devices:
        return {}
    d = trace.devices[0]
    idle = gaps(union((s, e) for _, s, e in d.ops), lo, hi)
    out: Dict[str, float] = {}
    covered: List[Interval] = []
    for name, s, e in phases:
        part = total(clip(idle, s, e))
        if part > 0:
            out[name] = out.get(name, 0.0) + part
        covered.append((s, e))
    rest = total(subtract(idle, union(covered)))
    if rest > 0:
        out["unattributed"] = rest
    return out


def top(items: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in
            sorted(items.items(), key=lambda kv: -kv[1])[:n]]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(trace_dir: str, platform: str = "tpu") -> Trace:
    """Read the newest trace under ``trace_dir``.  Device planes are
    ``/device:TPU:<n>``; on the CPU (rehearsal only) the operations sit
    on the host plane's PjRt thread lines."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    devices: List[DeviceTrace] = []
    stamp = None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:" + platform.upper())
        is_cpu_host = platform == "cpu" and plane.name == "/host:CPU"
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(STAMP) and stamp is None:
                        stamp = ev.start_ns * 1e-9
        if not (is_device or is_cpu_host):
            continue
        dev = DeviceTrace(plane.name)
        for line in plane.lines:
            if is_device and line.name not in _OP_LINES:
                continue
            if is_cpu_host and "PjRt" not in line.name:
                continue
            for ev in line.events:
                if ev.duration_ns <= 0 or ev.name.startswith("Threadpool"):
                    continue
                s = ev.start_ns * 1e-9
                dev.ops.append((ev.name, s, s + ev.duration_ns * 1e-9))
        if dev.ops:
            devices.append(dev)
    return Trace(devices, stamp)
