"""Unified typed runtime configuration.

The reference configures its runtime through JVM system properties read
ad hoc all over the codebase (``utils/Engine.scala:113-154`` ``bigdl.*``
properties); the TPU-native equivalent is the ``BIGDL_*`` environment.
This module gives that surface ONE typed, documented object: every knob
the framework reads, its type, default, and consumer, resolved in a
single place.  Call sites keep reading through :func:`get_config` so a
test (or an embedding application) can inject overrides with
:func:`set_config` instead of mutating ``os.environ``.

| field                  | env var                     | consumer |
|------------------------|-----------------------------|----------|
| coordinator_address    | BIGDL_COORDINATOR_ADDRESS   | Engine (multi-host control plane) |
| num_processes          | BIGDL_NUM_PROCESSES         | Engine |
| process_id             | BIGDL_PROCESS_ID            | Engine |
| node_number            | BIGDL_NODE_NUMBER           | Engine (defaults to process count) |
| core_number            | BIGDL_CORE_NUMBER           | Engine (host cores for data pipeline) |
| default_pool_size      | BIGDL_DEFAULT_POOL_SIZE     | Engine.default thread pool |
| local_mode             | BIGDL_LOCAL_MODE            | Engine |
| failure_retry_times    | BIGDL_FAILURE_RETRY_TIMES   | Optimizer retry budget |
| failure_retry_interval | BIGDL_FAILURE_RETRY_INTERVAL| Optimizer retry window (s) |
| iteration_timeout      | BIGDL_ITERATION_TIMEOUT     | straggler guard ("", "0", float, "auto") |
| check_singleton_strict | BIGDL_CHECK_SINGLETON       | Engine.check_singleton raise-vs-warn |
| profile_dir            | BIGDL_PROFILE               | profiler hook |
| profile_iters          | BIGDL_PROFILE_ITERS         | profiler hook |
| telemetry_dir          | BIGDL_TELEMETRY             | telemetry run log dir (docs/observability.md) |
| telemetry_device       | BIGDL_TELEMETRY_DEVICE      | device-facts level: off / auto / full |
| module_scopes          | BIGDL_SCOPES                | jax.named_scope module paths in compiled HLO (default on; off disables attribution) |
| telemetry_attribution  | BIGDL_ATTRIBUTION           | emit per-module cost-attribution events (one re-lower + HLO parse per step object) |
| telemetry_comms        | BIGDL_COMMS                 | per-collective comms events (telemetry/comms.py): off / auto (sharded multi-device steps only) / on — one extra local XLA compile per step object |
| telemetry_memory       | BIGDL_MEMORY                | per-step memory events (telemetry/memory.py): off / auto (multi-device meshes only) / on — shares the comms compile, so on a sharded step the event is a text parse |
| fleet_interval         | BIGDL_FLEET_INTERVAL        | coordinator fleet-watcher poll seconds (telemetry/fleet.py; 0 = off; active only on multi-process runs) |
| flight_events          | BIGDL_FLIGHT                | crash flight-recorder ring capacity in events (0 = off) |
| profile_on_health      | BIGDL_PROFILE_ON_HEALTH     | arm a one-shot profiler capture (dir) when the health policy first escalates |
| metrics_port           | BIGDL_METRICS_PORT          | OpenMetrics/status HTTP endpoint port (0 = ephemeral; unset = off) |
| health_action          | BIGDL_HEALTH                | training-health policy: off / warn / skip / halt (default halt) |
| health_halt_after      | BIGDL_HEALTH_HALT_AFTER     | halt after N consecutive nonfinite steps (default 3) |
| no_native              | BIGDL_TPU_NO_NATIVE         | native kernel loader |
| log_disable            | BIGDL_LOGGER_DISABLE        | utils.logging redirect (disable) |
| log_file               | BIGDL_LOG_FILE              | utils.logging redirect target |
| log_thirdparty         | BIGDL_LOG_THIRDPARTY        | redirect third-party logs to file |
| prefetch_batches       | BIGDL_PREFETCH              | Optimizer input double-buffering depth (0 = sync) |
| async_checkpoint       | BIGDL_ASYNC_CHECKPOINT      | overlap checkpoint IO with training (default on) |
| retry_backoff          | BIGDL_RETRY_BACKOFF         | retry-loop backoff base seconds (exp + jitter, cap 30s; 0 = off) |
| resume                 | BIGDL_RESUME                | auto-resume from the checkpoint dir: auto / off (docs/fault_tolerance.md) |
| faults                 | BIGDL_FAULTS                | deterministic fault-injection plan (bigdl_tpu/faults.py) |
| faults_seed            | BIGDL_FAULTS_SEED           | seed for the plan's random choices (torn bytes) |
| cluster_dir            | BIGDL_CLUSTER_DIR           | shared dir for peer heartbeats + commit barrier (parallel/cluster.py; unset = cluster fault tolerance off) |
| cluster_deadline       | BIGDL_CLUSTER_DEADLINE      | peer-heartbeat deadline seconds (0 = derive from the straggler budget, else 120s) |
| heartbeat_interval     | BIGDL_HEARTBEAT_INTERVAL    | heartbeat publish/poll throttle seconds (default 1.0) |
| local_sync_h           | BIGDL_LOCAL_SYNC_H          | parameter_sync=local: local steps H between parameter averagings (parallel/local_sync.py; default 8) |
| local_sync_stale       | BIGDL_LOCAL_SYNC_STALE      | parameter_sync=local: staleness bound S — a peer S averaging rounds behind is shed (default 3) |
| local_sync_grace       | BIGDL_LOCAL_SYNC_GRACE      | parameter_sync=local: grace window seconds a peer AT the bound gets before the shed (0 = derive from the heartbeat interval) |
| scan_layers            | BIGDL_SCAN_LAYERS           | build registry models with repeated blocks stacked into ScanLayers (docs/compile.md; default off) |
| sparse_sync            | BIGDL_SPARSE                | sparse embedding-gradient sync (docs/sparse.md): off / auto (on when touched rows <= vocab/2) / on — numerics-exact row-sparse (indices, rows) sync instead of the dense table all-reduce |
| trace_requests         | BIGDL_TRACE                 | per-request serving traces (telemetry/request_trace.py): span timelines, /v1/trace/<id>, blame verdicts (default on; off disables recording) |
| trace_ring             | BIGDL_TRACE_RING            | recent-trace ring size per server (default 512) |
| trace_slowest          | BIGDL_TRACE_SLOWEST         | always-kept slowest-k traces per endpoint — the p99 exemplars eviction can never touch (default 8) |
| trace_spans            | BIGDL_TRACE_SPANS           | per-trace span cap; decode iterations past it are tallied in components, not recorded (default 512) |

Performance knobs read directly at their consumer (hardware-tuning
surface, not part of the typed object because they are read at trace
time inside jitted-program construction):

| env var               | consumer |
|-----------------------|----------|
| BIGDL_FLASH_BLOCK_Q/K | ops.attention flash block sizes (default 1024/512 — round-5 hardware sweep) |
| BIGDL_FLASH_MIN_SEQ   | ops.attention auto-backend threshold (default 512; dense below) |
| BIGDL_COMPILE_CACHE   | Engine.enable_compile_cache persistent XLA executable cache dir (0 = off; JAX_COMPILATION_CACHE_DIR, when set, wins; default <checkout>/.jax_cache) |
| BIGDL_COMPILE_CACHE_MIN_S | Engine.enable_compile_cache min compile seconds for an entry to persist (default 0.1) |
| BIGDL_COORDINATOR_TIMEOUT | Engine._init_distributed bounded jax.distributed join (s, default 300; 0 = unbounded) |
| BIGDL_PEAK_FLOPS      | telemetry.device MFU denominator override (FLOP/s per device) |
| BIGDL_PEAK_BW         | telemetry.device comms-bandwidth denominator override (interconnect bytes/s per device) |
| BIGDL_HBM_GB          | telemetry.memory per-device HBM budget override in GiB (fit estimator + OOM forensics; default: the per-chip table, else the live allocator limit) |
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, fields
from typing import Optional

__all__ = ["BigDLConfig", "get_config", "set_config", "retry_backoff_s"]


def retry_backoff_s(attempt: int, base: Optional[float] = None) -> float:
    """The ONE restart/retry backoff policy: exponential from ``base``
    seconds (default: the ``BIGDL_RETRY_BACKOFF`` config) with
    multiplicative jitter, capped at 30 s; ``base <= 0`` disables.
    Shared by the Optimizer retry loop and the cluster Supervisor so
    the two cannot drift apart."""
    import random

    if base is None:
        base = get_config().retry_backoff
    if base <= 0:
        return 0.0
    return min(30.0, base * (2.0 ** max(attempt - 1, 0))) \
        * random.uniform(0.5, 1.0)


def _truthy(v: Optional[str]) -> bool:
    return (v or "").lower() in ("1", "true", "yes", "on")


@dataclass
class BigDLConfig:
    # multi-host control plane
    coordinator_address: Optional[str] = None
    num_processes: int = 1
    process_id: int = 0
    # host topology
    node_number: Optional[int] = None
    core_number: Optional[int] = None
    default_pool_size: Optional[int] = None
    local_mode: bool = False
    # failure handling
    failure_retry_times: int = 5
    failure_retry_interval: float = 120.0
    iteration_timeout: str = ""  # "", "0", "<seconds>", or "auto"
    check_singleton_strict: bool = False  # BIGDL_CHECK_SINGLETON: raise vs warn
    # profiling
    profile_dir: Optional[str] = None
    profile_iters: int = 5
    # telemetry (docs/observability.md): JSONL run logs + device facts
    telemetry_dir: Optional[str] = None
    telemetry_device: str = "auto"  # off | auto | full
    # module-path scopes in compiled HLO (cost attribution substrate)
    module_scopes: bool = True
    # emit per-module attribution events (re-lower + parse per step obj)
    telemetry_attribution: bool = False
    # per-collective comms events (telemetry/comms.py): off | auto | on.
    # auto = only for steps whose mesh spans >1 device — the one case
    # collectives exist.  Costs one extra LOCAL XLA compile per step
    # object (collectives only exist post-SPMD-partitioning, and jit's
    # executable cache is not reachable from the lowered program).
    telemetry_comms: str = "auto"
    # per-step memory events (telemetry/memory.py): off | auto | on.
    # auto = only for steps whose mesh spans >1 device (the case where
    # per-device HBM is the scaling question and where the comms event
    # already pays the post-SPMD compile the walker shares).
    telemetry_memory: str = "auto"
    # coordinator-side live fleet watcher poll seconds (0 disables)
    fleet_interval: float = 2.0
    # crash flight recorder: event-ring capacity (0 disables)
    flight_events: int = 2048
    # arm a one-shot profiler capture when health first escalates
    profile_on_health: Optional[str] = None
    # live metrics endpoint: None = off, 0 = ephemeral port
    metrics_port: Optional[int] = None
    # training health (telemetry/health.py): off | warn | skip | halt
    health_action: str = "halt"
    health_halt_after: int = 3
    # native layer
    no_native: bool = False
    # log management (LoggerFilter.scala property family)
    log_disable: bool = False
    log_file: Optional[str] = None
    log_thirdparty: bool = True
    # input pipeline: batches to transform+transfer ahead of the device
    prefetch_batches: int = 2
    # overlap checkpoint byte-writes with the next training iterations
    async_checkpoint: bool = True
    # failure-retry backoff base (seconds); exponential with jitter,
    # capped at 30s; 0 disables the sleep
    retry_backoff: float = 1.0
    # auto-resume from the configured checkpoint dir at optimize() start
    resume: str = "auto"  # auto | off
    # deterministic fault injection (bigdl_tpu/faults.py); "" = none
    faults: str = ""
    faults_seed: int = 0
    # cluster fault tolerance (bigdl_tpu/parallel/cluster.py): shared
    # heartbeat/commit dir (None = off), peer deadline (0 = derived),
    # heartbeat write/poll throttle
    cluster_dir: Optional[str] = None
    cluster_deadline: float = 0.0
    heartbeat_interval: float = 1.0
    # local-SGD (parallel/local_sync.py, docs/fault_tolerance.md
    # "Straggler tolerance"): H local steps between parameter
    # averagings; a peer whose averaging round falls S rounds behind
    # the fleet is shed.  Read by the Optimizer when
    # parameter_sync="local".
    local_sync_h: int = 8
    local_sync_stale: int = 3
    local_sync_grace: float = 0.0
    # scan-over-layers (nn/layers/scan.py, docs/compile.md): build the
    # registry models with repeated-block runs stacked into ScanLayers
    # so XLA compiles ONE block body instead of N
    scan_layers: bool = False
    # sparse embedding-gradient sync (nn/layers/embedding.py,
    # docs/sparse.md): off | auto | on.  auto (default) routes a
    # sparse-capable table through the row-sparse (indices, rows)
    # cotangent when the batch's worst-case touched rows are at most
    # half the vocab; on forces every capable table; off is the dense
    # A/B baseline.  Numerics-exact either way.
    sparse_sync: str = "auto"
    # request-level serving traces (telemetry/request_trace.py,
    # docs/observability.md "Tracing a request"): recording on/off,
    # recent-ring size, pinned slowest-k per endpoint, per-trace span cap
    trace_requests: bool = True
    trace_ring: int = 512
    trace_slowest: int = 8
    trace_spans: int = 512

    @classmethod
    def from_env(cls, env=os.environ) -> "BigDLConfig":
        def _int(name, default):
            v = env.get(name)
            return int(v) if v else default

        def _float(name, default):
            v = env.get(name)
            return float(v) if v else default

        return cls(
            coordinator_address=env.get("BIGDL_COORDINATOR_ADDRESS") or None,
            num_processes=_int("BIGDL_NUM_PROCESSES", 1),
            process_id=_int("BIGDL_PROCESS_ID", 0),
            node_number=_int("BIGDL_NODE_NUMBER", 0) or None,
            core_number=_int("BIGDL_CORE_NUMBER", 0) or None,
            default_pool_size=_int("BIGDL_DEFAULT_POOL_SIZE", 0) or None,
            local_mode=_truthy(env.get("BIGDL_LOCAL_MODE")),
            failure_retry_times=_int("BIGDL_FAILURE_RETRY_TIMES", 5),
            failure_retry_interval=_float("BIGDL_FAILURE_RETRY_INTERVAL", 120.0),
            iteration_timeout=(env.get("BIGDL_ITERATION_TIMEOUT") or "").strip(),
            check_singleton_strict=_truthy(env.get("BIGDL_CHECK_SINGLETON")),
            profile_dir=env.get("BIGDL_PROFILE") or None,
            profile_iters=_int("BIGDL_PROFILE_ITERS", 5),
            telemetry_dir=env.get("BIGDL_TELEMETRY") or None,
            telemetry_device=(env.get("BIGDL_TELEMETRY_DEVICE")
                              or "auto").strip().lower(),
            module_scopes=(env.get("BIGDL_SCOPES") or "on").strip().lower()
            not in ("0", "off", "false", "no"),
            telemetry_attribution=_truthy(env.get("BIGDL_ATTRIBUTION")),
            telemetry_comms=(env.get("BIGDL_COMMS")
                             or "auto").strip().lower(),
            telemetry_memory=(env.get("BIGDL_MEMORY")
                              or "auto").strip().lower(),
            fleet_interval=_float("BIGDL_FLEET_INTERVAL", 2.0),
            flight_events=_int("BIGDL_FLIGHT", 2048),
            profile_on_health=env.get("BIGDL_PROFILE_ON_HEALTH") or None,
            # NB: "0" is a VALID port request (ephemeral), so the usual
            # `_int(...) or None` falsiness shortcut would drop it
            metrics_port=(int(env["BIGDL_METRICS_PORT"])
                          if env.get("BIGDL_METRICS_PORT") not in
                          (None, "") else None),
            health_action=(env.get("BIGDL_HEALTH")
                           or "halt").strip().lower(),
            health_halt_after=_int("BIGDL_HEALTH_HALT_AFTER", 3),
            no_native=_truthy(env.get("BIGDL_TPU_NO_NATIVE")),
            log_disable=_truthy(env.get("BIGDL_LOGGER_DISABLE")),
            log_file=env.get("BIGDL_LOG_FILE") or None,
            log_thirdparty=_truthy(env.get("BIGDL_LOG_THIRDPARTY") or "true"),
            prefetch_batches=_int("BIGDL_PREFETCH", 2),
            async_checkpoint=_truthy(
                env.get("BIGDL_ASYNC_CHECKPOINT") or "true"),
            retry_backoff=_float("BIGDL_RETRY_BACKOFF", 1.0),
            resume=(env.get("BIGDL_RESUME") or "auto").strip().lower(),
            faults=(env.get("BIGDL_FAULTS") or "").strip(),
            faults_seed=_int("BIGDL_FAULTS_SEED", 0),
            cluster_dir=env.get("BIGDL_CLUSTER_DIR") or None,
            cluster_deadline=_float("BIGDL_CLUSTER_DEADLINE", 0.0),
            heartbeat_interval=_float("BIGDL_HEARTBEAT_INTERVAL", 1.0),
            local_sync_h=_int("BIGDL_LOCAL_SYNC_H", 8),
            local_sync_stale=_int("BIGDL_LOCAL_SYNC_STALE", 3),
            local_sync_grace=_float("BIGDL_LOCAL_SYNC_GRACE", 0.0),
            scan_layers=_truthy(env.get("BIGDL_SCAN_LAYERS")),
            sparse_sync=(env.get("BIGDL_SPARSE")
                         or "auto").strip().lower(),
            trace_requests=(env.get("BIGDL_TRACE") or "on").strip().lower()
            not in ("0", "off", "false", "no"),
            trace_ring=_int("BIGDL_TRACE_RING", 512),
            trace_slowest=_int("BIGDL_TRACE_SLOWEST", 8),
            trace_spans=_int("BIGDL_TRACE_SPANS", 512),
        )


_config: Optional[BigDLConfig] = None


def get_config() -> BigDLConfig:
    """The process-wide config.  An explicitly installed config
    (:func:`set_config`) wins; otherwise the environment is re-resolved
    on each call — call sites read it once per operation (not per
    iteration), so env mutations (e.g. in tests) take effect at the next
    operation boundary."""
    if _config is not None:
        return _config
    return BigDLConfig.from_env()


def set_config(cfg: Optional[BigDLConfig]) -> None:
    """Install an explicit config (tests / embedding apps); ``None``
    reverts to env resolution on next :func:`get_config`."""
    global _config
    _config = cfg
