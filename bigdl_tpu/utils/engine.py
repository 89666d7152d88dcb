"""Engine — the global runtime singleton.

Capability parity with ``utils/Engine.scala``: the reference's
``Engine.init`` discovers node count and cores per executor from the Spark
conf, owns the task/model thread pools, and verifies the runtime contract.
On TPU the executor topology is the **device mesh**: ``Engine.init``
discovers ``jax.devices()``, builds the default ``jax.sharding.Mesh``, and
owns host-side worker pools for the input pipeline (the reference's
``ThreadPool``/``Engine.default`` role — compute parallelism itself lives
inside XLA, so there is no ``_model`` pool).

Config parity (``Engine.scala:113-154`` system properties): environment
variables ``BIGDL_*`` replace JVM ``-Dbigdl.*`` properties.

Multi-host runtime (``Engine.scala:93-106,344-418`` capability): where the
reference's ``Engine.init`` discovers the executor topology from the Spark
master and coordinates N JVMs, here ``Engine.init`` calls
``jax.distributed.initialize`` when the coordinator env vars are present —
``BIGDL_COORDINATOR_ADDRESS`` (host:port), ``BIGDL_NUM_PROCESSES``,
``BIGDL_PROCESS_ID`` — and builds the **global** mesh over every device of
every process.  Each process then feeds its own shard of the global batch
(``jax.make_array_from_process_local_data`` inside TrainStep) and XLA's
collectives ride ICI/DCN; there is no user-level parameter server.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence, Tuple

import numpy as np

from bigdl_tpu.utils.config import get_config

__all__ = ["Engine", "enable_compile_cache", "DEFAULT_COMPILE_CACHE"]

#: where the persistent executable cache lives when nothing else says:
#: a FIXED path inside the checkout (``.gitignore`` lists it).  The
#: directory is part of the cache key's environment — a path built from
#: a pid, the time or ``tempfile`` never hits.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_CACHE_OFF = ("", "0", "off", "false")


def enable_compile_cache(path: Optional[str] = None) -> str:
    """Turn on JAX's persistent executable cache and install the
    hit/miss monitor (``utils/compile_cache.py``); returns the cache
    directory, or ``""`` when the cache stays off.

    Re-runs then LOAD the serialized executable instead of re-compiling.
    Reference analogue: the engine-level environment bootstrap in
    ``utils/Engine.scala:165`` owns process-wide runtime knobs the same
    way.  The cache is MANAGED, not just enabled (docs/compile.md):
    every hit and miss is counted (and mirrored into the telemetry run
    as ``compile/cache_hit``/``compile/cache_miss`` instants), and the
    cache-key ingredients are announced per run so a cold restart that
    should have been warm is diagnosable.  Every path that compiles a
    step calls this before its first compile: ``TrainStep`` (the
    per-step jit) and ``BucketedExecutor.warmup`` (serving cold start).

    Which directory, in this order:

    1. ``BIGDL_COMPILE_CACHE`` set to ``0``/``off``/``false``/empty:
       this function enables nothing.
    2. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that variable
       itself, so the directory is used as it stands and NO directory
       is set from code (a harness places the cache from outside).
    3. the ``path`` argument, else ``BIGDL_COMPILE_CACHE=<dir>``.
    4. :data:`DEFAULT_COMPILE_CACHE` — ``<checkout>/.jax_cache``.

    On the CPU backend steps 3-4 need the explicit ``path`` or
    ``BIGDL_COMPILE_CACHE=<dir>`` opt-in: on this jaxlib,
    (de)serializing CPU executables built under a forced multi-device
    host platform (the tier-1 rig's
    ``--xla_force_host_platform_device_count=8``) segfaults inside XLA,
    and plain CPU pays no compile bill worth caching.  The platform is
    read WITHOUT initializing a backend (a call made before the
    backend is up must not claim the chip): an initialized backend answers
    exactly, else ``JAX_PLATFORMS`` is trusted, and with neither the
    call defers — the step-level callers run again after the backend is
    up and before the first real compile.

    The entry floor is 0.1 s of compile time
    (``BIGDL_COMPILE_CACHE_MIN_S`` overrides; the jax default of 1 s
    skips the small programs around a step)."""
    from bigdl_tpu.utils import compile_cache as _cc

    _cc.monitor().install()  # compile_s counts whether or not we cache
    env = os.environ.get("BIGDL_COMPILE_CACHE")
    if env is not None and env.strip().lower() in _CACHE_OFF:
        return ""
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not (external or path or env):
        platform = _cc.initialized_platform()
        if platform is None:
            platform = (os.environ.get("JAX_PLATFORMS") or "") \
                .split(",")[0].strip().lower() or None
        if platform is None or platform == "cpu":
            return ""

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not external:
        path = path or env or DEFAULT_COMPILE_CACHE
        if jax.config.jax_compilation_cache_dir != path:
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
            # jax latches "is the cache in use" on the FIRST compile of
            # the process; a jit that ran before this call (model
            # construction) would otherwise pin "no cache" for good
            compilation_cache.reset_cache()
    try:
        min_s = float(os.environ.get("BIGDL_COMPILE_CACHE_MIN_S", "0.1"))
    except ValueError:
        min_s = 0.1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return external or path


class _Engine:
    def __init__(self):
        self._initialized = False
        self._mesh = None
        self._devices = None
        self._node_number = 1
        self._core_number = 1
        self._process_count = 1
        self._process_index = 0
        self._distributed = False
        self._pool: Optional[ThreadPoolExecutor] = None
        self._singleton_fd: Optional[int] = None

    @property
    def local_mode(self) -> bool:
        # read per use, not baked into the import-time singleton, so
        # set_config()/env overrides behave like every other knob
        return get_config().local_mode

    # -- multi-host ---------------------------------------------------------
    def _init_distributed(self):
        """Join the cluster when coordinator env vars are present — the
        reference's topology discovery (``Engine.scala:344-418``), with
        ``jax.distributed`` as the control plane instead of Spark."""
        import jax

        cfg = get_config()
        if cfg.coordinator_address is None or self._distributed:
            return
        # a multi-process CPU cluster (the test rig, and any CPU-fleet
        # deployment) needs a real cross-process collectives backend —
        # without it every device_put onto a cross-process sharding
        # dies with "Multiprocess computations aren't implemented on
        # the CPU backend".  Must be set BEFORE the backend client is
        # created; a no-op for TPU platforms.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # bounded join: under the cluster supervisor
        # (parallel/cluster.py) a restart incarnation re-dials a FRESH
        # coordinator — if the coordinator slot died before serving, an
        # unbounded initialize would hang this incarnation forever and
        # eat the supervisor's restart budget as a silent stall
        kwargs = {}
        timeout = int(float(os.environ.get("BIGDL_COORDINATOR_TIMEOUT",
                                           "300")))
        if timeout > 0:
            kwargs["initialization_timeout"] = timeout
        jax.distributed.initialize(
            coordinator_address=cfg.coordinator_address,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id, **kwargs)
        self._distributed = True

    # -- init ---------------------------------------------------------------
    def init(self, devices=None, mesh_shape: Optional[Sequence[int]] = None,
             axis_names: Sequence[str] = ("data",)) -> "_Engine":
        """Discover devices and build the default mesh.

        ``mesh_shape=None`` puts every addressable device on the leading
        axis (pure data parallelism, the reference's only mode); richer
        layouts (data × model × sequence) are first-class via
        ``bigdl_tpu.parallel.mesh``.
        """
        import jax

        # BEFORE the first jax.devices(): a second driver must be caught
        # while this process can still report it rather than hang in the
        # device claim (see check_singleton)
        self.check_singleton()
        self._init_distributed()
        self._devices = list(devices) if devices is not None else jax.devices()
        n = len(self._devices)
        if mesh_shape is None:
            mesh_shape = (n,)
            axis_names = tuple(axis_names[:1])
        arr = np.array(self._devices).reshape(tuple(mesh_shape))
        from jax.sharding import Mesh

        self._mesh = Mesh(arr, tuple(axis_names))
        cfg = get_config()
        self._process_count = jax.process_count()
        self._process_index = jax.process_index()
        self._node_number = cfg.node_number or self._process_count
        self._core_number = cfg.core_number or os.cpu_count() or 1
        pool_size = cfg.default_pool_size or max(4, self._core_number)
        if self._pool is not None:
            self._pool.shutdown(wait=False)
        self._pool = ThreadPoolExecutor(max_workers=pool_size, thread_name_prefix="bigdl")
        self._initialized = True
        return self

    def _require_init(self):
        if not self._initialized:
            self.init()

    # -- accessors (Engine.coreNumber/nodeNumber/default parity) ------------
    @property
    def mesh(self):
        self._require_init()
        return self._mesh

    @property
    def devices(self):
        self._require_init()
        return self._devices

    def node_number(self) -> int:
        self._require_init()
        return self._node_number

    def core_number(self) -> int:
        self._require_init()
        return self._core_number

    def device_count(self) -> int:
        self._require_init()
        return len(self._devices)

    def process_count(self) -> int:
        """Number of host processes in the cluster (the reference's node
        count, ``Engine.nodeNumber``)."""
        self._require_init()
        return self._process_count

    def process_index(self) -> int:
        """This process's rank; drives per-process data sharding."""
        self._require_init()
        return self._process_index

    def is_coordinator(self) -> bool:
        """True on the single process that owns checkpoint writes."""
        return self.process_index() == 0

    def local_devices(self):
        """Devices attached to THIS process (vs the global ``devices``)."""
        self._require_init()
        return [d for d in self._devices
                if d.process_index == self._process_index]

    @property
    def default(self) -> ThreadPoolExecutor:
        """Host-side worker pool (data loading / IO), the analogue of
        ``Engine.default`` (``Engine.scala:241-246``)."""
        self._require_init()
        return self._pool

    def invoke_and_wait(self, fns, timeout: Optional[float] = None):
        """Run thunks on the pool and gather results — ``ThreadPool.
        invokeAndWait`` (``utils/ThreadPool.scala:92-104``)."""
        self._require_init()
        futures = [self._pool.submit(f) for f in fns]
        return [f.result(timeout=timeout) for f in futures]

    # -- singleton guard ----------------------------------------------------
    def _singleton_platform(self) -> str:
        """Normalized platform tag WITHOUT touching jax (initializing the
        backend IS the device claim the guard exists to protect): first
        entry of JAX_PLATFORMS (falling back to the legacy
        JAX_PLATFORM_NAME alias jax still honors), lowercased;
        empty/unset -> 'default'."""
        plats = (os.environ.get("JAX_PLATFORMS")
                 or os.environ.get("JAX_PLATFORM_NAME") or "").strip().lower()
        return plats.split(",")[0].strip() or "default"

    def _singleton_lock_path(self) -> str:
        """Lock identity from env/config only.  Best-effort by design:
        two processes must agree on JAX_PLATFORMS/TPU_VISIBLE_DEVICES
        spelling to collide on the same lockfile (an advisory guard for
        the common same-launcher case, not a security boundary).  The
        path is scoped per-user (XDG_RUNTIME_DIR when available, else a
        uid-tagged name under the shared tmpdir) so one user's lockfile
        can neither be pre-planted nor flock-held by another.  Deliberate
        tradeoff: CROSS-user double-driver contention is no longer
        pre-empted here — a world-writable rendezvous path is exactly the
        symlink/DoS surface this scoping removes; cross-user claims
        surface as the device claim error instead."""
        import tempfile

        parts = [self._singleton_platform(),
                 (os.environ.get("TPU_VISIBLE_DEVICES") or "").strip(),
                 f"p{get_config().process_id}"]
        tag = "".join(c if c.isalnum() or c in "p_" else "_"
                      for c in "_".join(parts))
        uid = os.getuid() if hasattr(os, "getuid") else 0
        run_dir = os.environ.get("XDG_RUNTIME_DIR")
        if run_dir and os.path.isdir(run_dir):
            return os.path.join(run_dir, f"bigdl_tpu_{tag}.lock")
        return os.path.join(tempfile.gettempdir(),
                            f"bigdl_tpu_u{uid}_{tag}.lock")

    def check_singleton(self, raise_on_conflict: Optional[bool] = None,
                        force: bool = False) -> bool:
        """Detect a SECOND process about to drive the same accelerator —
        the reference's ``Engine.checkSingleton`` (``Engine.scala:165``,
        enforced at ``DistriOptimizer.scala:543-554``) which catches two
        task-sets sharing one JVM.  The TPU failure mode is two host
        processes contending for one chip: the loser fails or blocks in
        the device claim, which looks exactly like a hang — so this
        guard deliberately never touches jax itself (``Engine.init``
        runs it BEFORE the first ``jax.devices()``).  Advisory ``flock``
        on a per-platform, per-process-slot lockfile of our own,
        released on process exit.  libtpu keeps a lock file of its own;
        this code never reads or removes it.

        Returns True when this process holds (or newly acquired) the
        lock, or when the lockfile is unusable (permissions on a shared
        tmpdir) — the guard is advisory, never a new failure mode.  On
        conflict: warns and returns False, or raises when
        ``raise_on_conflict`` (default: the ``BIGDL_CHECK_SINGLETON``
        config, mirroring ``bigdl.check.singleton``) is true."""
        import errno
        import fcntl
        import logging

        log = logging.getLogger("bigdl_tpu")
        if self._singleton_fd is not None:
            return True
        # CPU backends support unlimited concurrent processes — the claim
        # deadlock is an accelerator failure mode (force=True for tests)
        if self._singleton_platform() == "cpu" and not force:
            return True
        if raise_on_conflict is None:
            raise_on_conflict = get_config().check_singleton_strict
        path = self._singleton_lock_path()
        flags = os.O_CREAT | os.O_RDWR
        # never follow a pre-planted symlink at the (predictable) path;
        # ELOOP from O_NOFOLLOW lands in the advisory-skip branch below
        flags |= getattr(os, "O_NOFOLLOW", 0) | getattr(os, "O_CLOEXEC", 0)
        try:
            fd = os.open(path, flags, 0o600)
        except OSError as e:
            log.warning(f"singleton check skipped: cannot open {path}: {e}")
            return True
        try:
            fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError as e:
            os.close(fd)
            if e.errno not in (errno.EWOULDBLOCK, errno.EAGAIN,
                               errno.EACCES):
                # not contention (e.g. ENOLCK on a no-flock fs):
                # advisory-skip, never a misdiagnosed "second driver"
                log.warning(f"singleton check skipped: flock on {path} "
                            f"failed: {e}")
                return True
            msg = (f"another process already drives this platform "
                   f"(lock {path}); two device clients on one chip "
                   f"deadlock in claim")
            if raise_on_conflict:
                raise RuntimeError(msg) from None
            log.warning(msg)
            return False
        os.ftruncate(fd, 0)
        os.write(fd, str(os.getpid()).encode())
        self._singleton_fd = fd
        return True

    def reset(self):
        self._initialized = False
        self._mesh = None
        if self._singleton_fd is not None:
            os.close(self._singleton_fd)  # closing drops the flock
            self._singleton_fd = None
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None


Engine = _Engine()
