"""Managed persistent-compile-cache telemetry (docs/compile.md).

``Engine.enable_compile_cache`` turns JAX's persistent executable cache
on; this module makes that cache **measured** instead of assumed:

- :class:`CompileCacheMonitor` (a process-wide singleton) hooks
  ``jax.monitoring`` and counts persistent-cache **hits**, **misses**
  and requests, plus cumulative backend **compile seconds**, cache
  retrieval seconds and the compile seconds a hit saved.  Every hit and
  miss is mirrored into the active telemetry run as a
  ``compile/cache_hit`` / ``compile/cache_miss`` instant, so
  ``telemetry diff`` and the run summary can count them per run, and
  ``/metrics``/``/status`` (telemetry/metrics_http.py) export the
  totals live.
- :func:`cache_key_ingredients` names everything that participates in
  (or invalidates) the cache key — jax/jaxlib versions, platform and
  device kind, the mesh layout, the cache dir and thresholds, and the
  XLA flag env — emitted once per run as a ``compile/cache`` instant so
  an "expected a warm restart, got a cold one" incident can be diffed
  against the previous run's ingredients instead of guessed at.

The monitor is passive and advisory: listener registration failures
degrade to "no counts", never to a broken compile path.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional

__all__ = ["CompileCacheMonitor", "monitor", "cache_key_ingredients",
           "initialized_platform"]

#: jax.monitoring keys this build observes — private strings of
#: jax._src.compilation_cache / jax._src.dispatch, present on jax 0.9.0
#: (tests/test_compile_cache.py fails if a hit stops being counted)
_HIT_KEY = "/jax/compilation_cache/cache_hits"
_MISS_KEY = "/jax/compilation_cache/cache_misses"
_REQUEST_KEY = "/jax/compilation_cache/compile_requests_use_cache"
_COMPILE_DUR_KEY = "/jax/core/compile/backend_compile_duration"
_SAVED_DUR_KEY = "/jax/compilation_cache/compile_time_saved_sec"
_RETRIEVAL_DUR_KEY = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileCacheMonitor:
    """Counts persistent-cache traffic via ``jax.monitoring`` listeners.

    One per process (:func:`monitor`).  ``install()`` is idempotent;
    listeners stay registered for process lifetime (jax offers no
    public unregister, and the monitor is a passive counter)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._installed = False
        self._announced_ref = None  # weakref: id() reuse must not dedupe
        self.hits = 0
        self.misses = 0
        self.requests = 0
        self.compile_s = 0.0      # backend compile wall (cache or not)
        self.saved_s = 0.0        # compile seconds a hit skipped
        self.retrieval_s = 0.0    # seconds spent loading cached entries

    # -- listeners ---------------------------------------------------------
    def install(self) -> bool:
        """Register the ``jax.monitoring`` listeners (once).  Returns
        whether the monitor is live."""
        with self._lock:
            if self._installed:
                return True
            import jax.monitoring as _mon  # public since jax 0.4.x

            _mon.register_event_listener(self._on_event)
            _mon.register_event_duration_secs_listener(self._on_duration)
            self._installed = True
            return True

    def _on_event(self, name: str, **kwargs) -> None:
        if name == _HIT_KEY:
            with self._lock:
                self.hits += 1
                self.requests += 1
            self._mirror(hit=True)
        elif name == _MISS_KEY:
            with self._lock:
                self.misses += 1
                self.requests += 1
            self._mirror(hit=False)

    def _on_duration(self, name: str, dur: float, **kwargs) -> None:
        with self._lock:
            if name == _COMPILE_DUR_KEY:
                self.compile_s += float(dur)
            elif name == _SAVED_DUR_KEY:
                # jax reports (cached compile time - retrieval time);
                # clamp: a hit that retrieved slower than it would have
                # compiled saved nothing, it didn't owe time
                self.saved_s += max(0.0, float(dur))
            elif name == _RETRIEVAL_DUR_KEY:
                self.retrieval_s += float(dur)

    def _mirror(self, hit: bool) -> None:
        """One instant per hit/miss into the active run (no-op off-run);
        the first mirror of a run also announces the cache-key
        ingredients as a ``compile/cache`` instant."""
        try:
            from bigdl_tpu import telemetry

            tracer = telemetry.get()
            if tracer is None:
                return
            self.announce(tracer)
            if hit:
                tracer.instant("compile/cache_hit")
            else:
                tracer.instant("compile/cache_miss")
        except Exception:  # noqa: BLE001 - observers never fail a compile
            pass

    def announce(self, tracer) -> None:
        """Emit the ``compile/cache`` ingredients instant once per run
        (a live reference to the announced tracer, NOT its id — CPython
        reuses addresses of collected objects, and a later run allocated
        at the old address must still get its announcement)."""
        import weakref

        with self._lock:
            if self._announced_ref is not None \
                    and self._announced_ref() is tracer:
                return
            try:
                self._announced_ref = weakref.ref(tracer)
            except TypeError:  # unweakrefable tracer: announce each time
                self._announced_ref = None
        try:
            tracer.instant("compile/cache", **cache_key_ingredients())
        except Exception:  # noqa: BLE001
            pass

    # -- views -------------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"installed": self._installed,
                    "hits": self.hits, "misses": self.misses,
                    "requests": self.requests,
                    "compile_s": round(self.compile_s, 4),
                    "saved_s": round(self.saved_s, 4),
                    "retrieval_s": round(self.retrieval_s, 4)}


_MONITOR = CompileCacheMonitor()


def monitor() -> CompileCacheMonitor:
    """The process-wide monitor singleton."""
    return _MONITOR


def initialized_platform() -> Optional[str]:
    """Platform of an ALREADY-initialized jax backend, else None —
    without initializing one (a status scrape, an import-time check or
    a supervisor parent must never claim the chip).  The one home of
    the private probe, shared by ``enable_compile_cache``'s CPU gate
    and :func:`cache_key_ingredients`.  Needs (checked on jax 0.9.0):
    ``jax._src.xla_bridge._backends``, the dict that ``backends()``
    fills on first use and that stays empty until then — jax has no
    public "is a backend up" query."""
    try:
        import jax
        from jax._src import xla_bridge as _xb

        if _xb._backends:
            return jax.default_backend()
    except Exception:  # noqa: BLE001 - internal probe is best-effort
        pass
    return None


def cache_key_ingredients(mesh=None) -> Dict[str, Any]:
    """Everything that feeds (or invalidates) the persistent cache key:
    jax/jaxlib versions, backend platform + device kind/count, the mesh
    layout, the cache dir and persistence thresholds, and the XLA flag
    environment.  Two runs with equal ingredients should hit each
    other's entries; a surprise recompile means one of these moved.

    ``mesh=None`` reads the Engine's mesh WITHOUT forcing backend init
    (a status scrape must never be the first device touch)."""
    out: Dict[str, Any] = {}
    try:
        import jax
        import jaxlib

        out["jax"] = jax.__version__
        out["jaxlib"] = getattr(jaxlib, "__version__", "?")
        out["cache_dir"] = jax.config.jax_compilation_cache_dir or ""
        out["min_compile_s"] = float(
            jax.config.jax_persistent_cache_min_compile_time_secs)
        try:
            if initialized_platform() is not None:
                dev = jax.devices()[0]
                out["platform"] = dev.platform
                out["device_kind"] = dev.device_kind
                out["device_count"] = jax.device_count()
        except Exception:  # noqa: BLE001 - backend facts are optional
            pass
    except Exception:  # noqa: BLE001 - ingredients must work sans jax
        pass
    if mesh is None:
        try:
            from bigdl_tpu.utils.engine import Engine

            mesh = Engine.__dict__.get("_mesh")
        except Exception:  # noqa: BLE001
            mesh = None
    if mesh is not None:
        try:
            out["mesh"] = {str(k): int(v)
                           for k, v in dict(mesh.shape).items()}
        except Exception:  # noqa: BLE001
            pass
    for var in ("XLA_FLAGS", "LIBTPU_INIT_ARGS", "JAX_PLATFORMS"):
        if os.environ.get(var):
            out[f"env_{var.lower()}"] = os.environ[var]
    return out
