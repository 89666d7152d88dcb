"""On-demand profiler capture: arm a ``jax.profiler`` trace window from
anywhere (HTTP ``POST /profile``, health-policy escalation, or
``BIGDL_PROFILE`` at startup) and let the training loop capture exactly
the next N steps.

This replaces capture-at-startup-only profiling: ``BIGDL_PROFILE``
used to trace the first N iterations and nothing else, which is useless
for the slowdown that appears at step 10,000.  Now the env knob merely
pre-arms the same control the live endpoints use, and the optimizer loop
polls it every iteration:

- :meth:`ProfilerControl.arm` — request a capture of the next ``steps``
  iterations into ``trace_dir`` (one in flight at a time; re-arming
  while armed/capturing is refused, not queued);
- :meth:`ProfilerControl.poll_begin` / :meth:`poll_end` — called by the
  loop around each iteration; one attribute check when idle;
- :meth:`ProfilerControl.abort` — stop an open capture on the way out
  of the loop (crash/halt), so the trace directory is always valid.

The singleton (:func:`get`) is process-wide, like the telemetry tracer:
profiling is a per-process activity (``jax.profiler`` allows one active
trace), so one control serializes all requesters.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional

__all__ = ["ProfilerControl", "get"]

IDLE, ARMED, CAPTURING = "idle", "armed", "capturing"


class ProfilerControl:
    """Arm/poll/abort state machine around ``jax.profiler`` traces."""

    def __init__(self):
        self._lock = threading.Lock()
        self.state = IDLE
        self.steps_left = 0
        self.trace_dir: Optional[str] = None
        self.source: Optional[str] = None
        self.perfetto = False
        self.last_trace_dir: Optional[str] = None
        self.captures = 0
        self.last_error: Optional[str] = None

    def arm(self, steps: int, trace_dir: str,
            source: str = "api", perfetto: bool = False) -> bool:
        """Request a capture of the next ``steps`` iterations.  Returns
        False (without queueing) when a capture is already armed or in
        flight.  ``perfetto=True`` additionally writes the
        Chrome/Perfetto JSON trace — the per-collective wall-time
        artifact ``telemetry/comms.py`` parses."""
        if steps < 1 or not trace_dir:
            return False
        with self._lock:
            if self.state != IDLE:
                return False
            self.state = ARMED
            self.steps_left = int(steps)
            self.trace_dir = trace_dir
            self.source = source
            self.perfetto = bool(perfetto)
        from bigdl_tpu import telemetry

        telemetry.instant("profile/armed", steps=int(steps),
                          dir=trace_dir, source=source,
                          perfetto=bool(perfetto))
        return True

    def poll_begin(self) -> None:
        """Iteration is about to run: start the trace if armed.  One
        attribute read when idle — safe in the hot loop."""
        if self.state != ARMED:
            return
        with self._lock:
            if self.state != ARMED:
                return
            try:
                import jax

                os.makedirs(self.trace_dir, exist_ok=True)
                jax.profiler.start_trace(
                    self.trace_dir, create_perfetto_trace=self.perfetto)
                self.state = CAPTURING
            except Exception as e:  # noqa: BLE001 - observer, never fatal
                self.last_error = f"{type(e).__name__}: {e}"
                self.state = IDLE
                self.steps_left = 0

    def poll_end(self) -> None:
        """Iteration finished: count it and stop the trace when the
        window is exhausted."""
        if self.state != CAPTURING:
            return
        done = False
        with self._lock:
            if self.state != CAPTURING:
                return
            self.steps_left -= 1
            if self.steps_left <= 0:
                done = True
        if done:
            self._stop()

    def abort(self) -> None:
        """Close an in-flight capture (loop exit / crash path); armed
        but not yet started requests are cancelled."""
        with self._lock:
            state = self.state
            if state == ARMED:
                self.state = IDLE
                self.steps_left = 0
                return
        if state == CAPTURING:
            self._stop()

    def _stop(self) -> None:
        from bigdl_tpu import telemetry

        with self._lock:
            trace_dir, source = self.trace_dir, self.source
            ok = False
            try:
                import jax

                jax.profiler.stop_trace()
                self.captures += 1
                self.last_trace_dir = trace_dir
                ok = True
            except Exception as e:  # noqa: BLE001
                self.last_error = f"{type(e).__name__}: {e}"
            self.state = IDLE
            self.steps_left = 0
            self.trace_dir = None
            self.source = None
            perfetto, self.perfetto = self.perfetto, False
        if ok:  # a failed stop wrote no trace: don't announce one
            telemetry.instant("profile/captured", dir=trace_dir,
                              source=source or "api", perfetto=perfetto)

    def status(self) -> Dict[str, Any]:
        with self._lock:
            return {"state": self.state, "steps_left": self.steps_left,
                    "trace_dir": self.trace_dir, "source": self.source,
                    "last_trace_dir": self.last_trace_dir,
                    "captures": self.captures,
                    "last_error": self.last_error}

    def default_dir(self, base: Optional[str] = None) -> str:
        """A fresh trace directory under ``base`` (or the telemetry dir,
        or the cwd)."""
        if base is None:
            from bigdl_tpu.utils.config import get_config

            base = get_config().telemetry_dir or "."
        return os.path.join(base,
                            f"profile-{time.strftime('%Y%m%d_%H%M%S')}")


_control = ProfilerControl()


def get() -> ProfilerControl:
    """The process-wide profiler control."""
    return _control
