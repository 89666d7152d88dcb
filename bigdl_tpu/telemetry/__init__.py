"""Unified runtime telemetry (docs/observability.md).

One process-wide event stream that every hot layer emits into — the
Optimizer loop stages, TrainStep/EvalStep compile + retrace events,
dataset prefetch depth, checkpointing, the straggler watchdog — persisted
as an append-only JSONL log per run and summarized by
``python -m bigdl_tpu.telemetry <run.jsonl>`` (per-stage table, step
percentiles, compile/retrace timeline, MFU, Chrome trace export).

Enable with ``BIGDL_TELEMETRY=<dir>`` (the Optimizer starts/ends the run
around ``optimize()``), or programmatically::

    from bigdl_tpu import telemetry
    with telemetry.run("/tmp/tele", meta={"job": "resnet"}):
        optimizer.optimize()

The module-level helpers (``span``/``stage``/``counter``/``gauge``/
``instant``) are no-ops costing one falsy check when no run is active,
so instrumented code needs no gating of its own.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, Optional

from bigdl_tpu.telemetry.tracer import (SCHEMA_VERSION, JsonlSink,
                                        MemorySink, Tracer)

__all__ = ["SCHEMA_VERSION", "Tracer", "JsonlSink", "MemorySink",
           "enabled", "get", "start_run", "end_run", "run", "maybe_run",
           "last_run_path", "metrics_server", "flight_recorder",
           "fleet_watcher", "goodput", "span",
           "stage", "counter", "gauge", "instant", "emit"]

_active: Optional[Tracer] = None
_last_run_path: Optional[str] = None
_metrics_server = None
_flight = None
_fleet = None
_ledger = None
_lifecycle_lock = threading.Lock()


def enabled() -> bool:
    """True when a run is active — the one-check fast path."""
    return _active is not None


def get() -> Optional[Tracer]:
    """The active tracer, or None.  Hot loops fetch it once per run and
    branch on the local."""
    return _active


def last_run_path() -> Optional[str]:
    """Path of the most recent JSONL run log (survives ``end_run`` so a
    CLI can point the user at the artifact it just produced)."""
    return _last_run_path


def metrics_server():
    """The live OpenMetrics HTTP server bound to the active run, or None
    (``BIGDL_METRICS_PORT`` unset / no run active).  ``.port`` carries
    the bound port — the way to discover an ephemeral ``:0`` bind."""
    return _metrics_server


def flight_recorder():
    """The crash flight recorder bound to the active run, or None
    (``BIGDL_FLIGHT=0`` / no run active).  ``.dump(reason)`` writes the
    ring to a ``flight-<stamp>.json``; the Optimizer calls it on
    HealthError, straggler firings, and crashes."""
    return _flight


def fleet_watcher():
    """The live cross-host fleet aggregator bound to the active run, or
    None (non-coordinator process, single-process run,
    ``BIGDL_FLEET_INTERVAL=0``, or no JSONL dir to tail).  ``.snapshot()``
    is the /status ``fleet`` block (telemetry/fleet.py)."""
    return _fleet


def goodput() -> Optional[Dict[str, Any]]:
    """Live goodput/badput decomposition of the active run (the ledger
    fold every sink shares), or None when no run is active or nothing
    has been emitted yet.  The same report is written as the run's
    final ``goodput`` event by :func:`end_run`."""
    ledger = _ledger
    return ledger.event_fields() if ledger is not None else None


def _default_meta(device_facts: bool = True) -> Dict[str, Any]:
    """Schema + incarnation + (unless ``device_facts=False``) what jax
    says of the devices — which INITIALIZES the backend, i.e. claims
    the chip: a process that only launches workers must not ask."""
    meta: Dict[str, Any] = {"schema": SCHEMA_VERSION}
    inc = os.environ.get("BIGDL_SUPERVISOR_INCARNATION")
    if inc is not None:
        try:  # stitchable chains: which supervisor incarnation is this
            meta["incarnation"] = int(inc)
        except ValueError:
            pass
    if not device_facts:
        return meta
    try:  # device facts are best-effort: telemetry must work sans jax
        import jax

        dev = jax.devices()[0]
        meta.update(device_kind=dev.device_kind,
                    device_count=jax.device_count(),
                    process_index=jax.process_index(),
                    process_count=jax.process_count())
    except Exception:  # noqa: BLE001 - meta only
        pass
    return meta


def start_run(path_or_dir: Optional[str] = None,
              meta: Optional[Dict[str, Any]] = None,
              sinks=None, device_facts: bool = True) -> Tracer:
    """Install the process-wide tracer.  ``path_or_dir``: a ``.jsonl``
    path is used as-is; a directory gets a fresh
    ``run-<stamp>-<pid>.jsonl``; None writes to no file (pass ``sinks``,
    e.g. a MemorySink, instead).  Raises if a run is already active —
    nested runs would interleave two schedules into one file.
    ``device_facts=False`` keeps the run's meta (and so this process)
    off the jax backend — the cluster supervisor's setting: its workers
    need the chip."""
    global _active, _last_run_path, _metrics_server, _flight, _fleet, \
        _ledger
    with _lifecycle_lock:
        if _active is not None:
            raise RuntimeError("a telemetry run is already active; "
                               "end_run() it first")
        full_meta = _default_meta(device_facts)
        full_meta.update(meta or {})
        all_sinks = list(sinks or [])
        try:  # the run-level goodput ledger rides as one more sink
            from bigdl_tpu.telemetry.ledger import LedgerFold

            _ledger = LedgerFold()
            all_sinks.append(_ledger)
        except Exception:  # noqa: BLE001 - observers never kill the run
            _ledger = None
        run_dir = None
        if path_or_dir is not None:
            path = path_or_dir
            if not path.endswith(".jsonl"):
                stamp = time.strftime("%Y%m%d_%H%M%S")
                pidx = full_meta.get("process_index", 0)
                path = os.path.join(
                    path_or_dir,
                    f"run-{stamp}-p{pidx}-{os.getpid()}.jsonl")
            all_sinks.append(JsonlSink(path))
            _last_run_path = path
            run_dir = os.path.dirname(os.path.abspath(path))
        _flight = _maybe_flight()
        if _flight is not None:
            all_sinks.append(_flight)
        tracer = Tracer(sinks=all_sinks, meta=full_meta)
        tracer.start()
        _active = tracer
        _metrics_server = _maybe_serve_metrics(tracer)
        _fleet = _maybe_fleet(run_dir, full_meta)
        return tracer


def _maybe_flight():
    """A FlightRecorder sink sized by ``BIGDL_FLIGHT`` (default 2048
    events; 0 disables)."""
    from bigdl_tpu.utils.config import get_config

    capacity = get_config().flight_events
    if capacity <= 0:
        return None
    try:
        from bigdl_tpu.telemetry.flight import FlightRecorder

        return FlightRecorder(capacity)
    except Exception:  # noqa: BLE001 - observers never kill the run
        return None


def _maybe_serve_metrics(tracer):
    """Bring up the OpenMetrics/status HTTP endpoint for this run when
    ``BIGDL_METRICS_PORT`` names a port (0 = ephemeral).  Failure to
    bind degrades to a warning — the exporter is an observer."""
    from bigdl_tpu.utils.config import get_config

    port = get_config().metrics_port
    if port is None:
        return None
    try:
        from bigdl_tpu.telemetry.metrics_http import start_server

        server = start_server(tracer, port)
        tracer.emit("event", name="metrics/serving", port=server.port)
        return server
    except Exception as e:  # noqa: BLE001 - observers never kill the run
        import logging

        logging.getLogger("bigdl_tpu.telemetry").warning(
            "metrics endpoint disabled (%s: %s)", type(e).__name__, e)
        return None


def _maybe_fleet(run_dir, meta):
    """A live FleetWatcher over the run-log directory, coordinator of a
    multi-process run only (``BIGDL_FLEET_INTERVAL`` seconds poll; 0
    disables).  Non-coordinators write their log and are tailed by the
    coordinator's watcher — one aggregator per fleet."""
    from bigdl_tpu.utils.config import get_config

    interval = get_config().fleet_interval
    if run_dir is None or interval <= 0:
        return None
    if meta.get("process_index", 0) != 0 \
            or meta.get("process_count", 1) < 2:
        return None
    try:
        from bigdl_tpu.telemetry.fleet import FleetWatcher

        return FleetWatcher(run_dir, interval).start()
    except Exception:  # noqa: BLE001 - observers never kill the run
        return None


def end_run() -> None:
    """Close the active run (flushes and closes sinks, stops the metrics
    endpoint and the fleet watcher); no-op when no run is active."""
    global _active, _metrics_server, _flight, _fleet, _ledger
    if _fleet is not None:
        try:
            # one final poll under the still-open tracer so a short
            # run's last flushed events make it into the fleet gauges
            _fleet.poll_once()
        except Exception:  # noqa: BLE001
            pass
    with _lifecycle_lock:
        tracer, _active = _active, None
        server, _metrics_server = _metrics_server, None
        watcher, _fleet = _fleet, None
        ledger, _ledger = _ledger, None
        _flight = None
    if tracer is not None and ledger is not None:
        try:
            # the run's last word: the goodput/badput decomposition of
            # everything emitted before it (written before run_end)
            fields = ledger.event_fields()
            if fields is not None:
                tracer.emit("goodput", **fields)
        except Exception:  # noqa: BLE001 - shutdown must never raise
            pass
    if watcher is not None:
        try:
            watcher.stop()
        except Exception:  # noqa: BLE001 - shutdown must never raise
            pass
    if server is not None:
        try:
            server.stop()
        except Exception:  # noqa: BLE001 - shutdown must never raise
            pass
    if tracer is not None:
        tracer.close()


@contextmanager
def run(path_or_dir: Optional[str] = None,
        meta: Optional[Dict[str, Any]] = None, sinks=None):
    tracer = start_run(path_or_dir, meta=meta, sinks=sinks)
    try:
        yield tracer
    finally:
        end_run()


@contextmanager
def maybe_run(meta: Optional[Dict[str, Any]] = None,
              device_facts: bool = True):
    """Config-gated run ownership for entry points (models/cli
    perf, bench_serving.py): start a JSONL run when
    ``BIGDL_TELEMETRY`` names a directory and no run is active yet.
    Yields the owned run-log path, or None when telemetry is off or an
    OUTER scope owns the stream — in which case that run is left
    untouched.  The owned run is ended on every exit path, so an
    exception inside the block can never leak the process-wide tracer
    or an unflushed log."""
    from bigdl_tpu.utils.config import get_config

    if not get_config().telemetry_dir or enabled():
        yield None
        return
    start_run(get_config().telemetry_dir, meta=meta,
              device_facts=device_facts)
    try:
        yield _last_run_path
    finally:
        end_run()


# -- no-op-when-disabled emit helpers ---------------------------------------
def span(name: str, **attrs):
    """Context manager timing a with-block as a span (nullcontext when
    disabled)."""
    tracer = _active
    if tracer is None:
        return nullcontext()
    return tracer.span(name, **attrs)


def stage(name: str, dur: float, **attrs) -> None:
    tracer = _active
    if tracer is not None:
        tracer.stage(name, dur, **attrs)


def counter(name: str, value: float, **attrs) -> None:
    tracer = _active
    if tracer is not None:
        tracer.counter(name, value, **attrs)


def gauge(name: str, value: float, **attrs) -> None:
    tracer = _active
    if tracer is not None:
        tracer.gauge(name, value, **attrs)


def instant(name: str, **attrs) -> None:
    tracer = _active
    if tracer is not None:
        tracer.instant(name, **attrs)


def emit(kind: str, **fields) -> None:
    tracer = _active
    if tracer is not None:
        tracer.emit(kind, **fields)
