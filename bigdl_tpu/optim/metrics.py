"""Per-stage training metrics (``optim/Metrics.scala:31-130``).

The reference aggregates six per-stage timings via Spark accumulators
across executors (computing / get-weights / aggregate-gradient /
put-gradient / compute-weight / send-weights, set at
``DistriOptimizer.scala:158-166``).  Under SPMD the gradient exchange
stages are fused into one XLA program, so the stages worth separating are
host-observable instead: data wait, host-to-device transfer, compile,
step dispatch, device sync, validation, and checkpoint — all recorded by
the Optimizer loop into this accumulator and printed by ``summary()``.

Deeper (op-level) timing comes from the profiler hook: set
``BIGDL_PROFILE=<dir>`` to capture a ``jax.profiler`` trace of the first
few training iterations (``BIGDL_PROFILE_ITERS``, default 5).

When a telemetry run is active (``BIGDL_TELEMETRY``, see
docs/observability.md) every recorded sample is ALSO forwarded to the
event log as a ``stage`` event — the accumulator's call sites are the
instrumentation points, so the timeline and the printed summary can
never disagree about what was measured."""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, List

from bigdl_tpu import telemetry

__all__ = ["Metrics"]


class Metrics:
    """Thread-safe per-stage accumulator.  ``add``/``set``/``timer`` are
    called concurrently by the driver loop, the prefetch workers, the
    straggler runner, and the async-checkpoint pool — every read and
    write of ``_scalars`` happens under one lock (the telemetry forward
    happens outside it: the tracer has its own).  ``stages()`` and
    ``summary()`` report in STABLE pipeline order — the canonical stage
    sequence first, then unknown stages in first-recorded order — so two
    summaries of the same run are comparable line-by-line."""

    #: the host-loop pipeline order (docs/observability.md): stages are
    #: reported in execution order, not alphabetically
    _STAGE_ORDER = ("data time", "batch stack time (overlapped)",
                    "host to device time",
                    "host to device time (overlapped)", "dispatch time",
                    "compile + first iteration time", "computing time",
                    "validation time", "checkpoint time",
                    "checkpoint wait time")

    def __init__(self):
        self._lock = threading.Lock()
        self._scalars: Dict[str, List[float]] = {}

    def _ordered(self) -> List[str]:
        """Stage names in canonical order (call with the lock held)."""
        known = [n for n in self._STAGE_ORDER if n in self._scalars]
        return known + [n for n in self._scalars
                        if n not in self._STAGE_ORDER]

    def set(self, name: str, value: float):
        with self._lock:
            self._scalars[name] = [float(value)]
        telemetry.gauge(name, value)

    def add(self, name: str, value: float):
        with self._lock:
            self._scalars.setdefault(name, []).append(float(value))
        telemetry.stage(name, value)

    def get(self, name: str) -> float:
        """Mean of the recorded values (0.0 when empty)."""
        with self._lock:
            vals = self._scalars.get(name, [])
            return sum(vals) / len(vals) if vals else 0.0

    def total(self, name: str) -> float:
        with self._lock:
            return sum(self._scalars.get(name, []))

    def count(self, name: str) -> int:
        with self._lock:
            return len(self._scalars.get(name, []))

    def stages(self) -> List[str]:
        with self._lock:
            return self._ordered()

    @contextmanager
    def timer(self, name: str):
        """Accumulate the wall time of the with-block under ``name``."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def reset(self):
        with self._lock:
            self._scalars.clear()

    def summary(self, unit_scale: float = 1.0) -> str:
        """Pretty printer mirroring ``Metrics.summary``: per-stage mean,
        total, and sample count, in canonical pipeline order."""
        with self._lock:
            lines = ["========== Metrics Summary =========="]
            for name in self._ordered():
                vals = self._scalars[name]
                mean = sum(vals) / len(vals) if vals else 0.0
                lines.append(
                    f"{name} : mean {mean * unit_scale:.6f} s "
                    f"(total {sum(vals) * unit_scale:.4f} s, n={len(vals)})")
            lines.append("=====================================")
            return "\n".join(lines)
