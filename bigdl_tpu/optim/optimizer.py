"""The Optimizer — host-side training driver (SURVEY §2.8 / §3.1-3.2).

Reproduces the reference ``Optimizer`` capabilities (``optim/Optimizer.scala:42``,
``optim/DistriOptimizer.scala``, ``optim/LocalOptimizer.scala``):
fluent configuration (optim method, validation, checkpoint, summaries, end
trigger), epoch/iteration accounting with throughput logging, trigger-driven
validation + checkpointing + TensorBoard summaries, checkpoint-resume, and
the failure-retry loop (``DistriOptimizer.scala:790-856``).

The compute core is ONE compiled :class:`~bigdl_tpu.parallel.train_step.TrainStep`
per run — the reference's two-Spark-jobs-per-iteration collapse into it
(see that module's docstring).  ``LocalOptimizer`` = single-device mesh;
``DistriOptimizer`` = the full Engine mesh; both drive the same loop, as the
reference's two classes drive the same semantics.
"""

from __future__ import annotations

import atexit
import logging
import os
import re
import signal
import threading
import time
import weakref
from datetime import datetime
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from bigdl_tpu import faults as _faults
from bigdl_tpu import telemetry
from bigdl_tpu.parallel import cluster as _cluster
from bigdl_tpu.dataset.dataset import AbstractDataSet, DataSet
from bigdl_tpu.dataset.minibatch import MiniBatch
from bigdl_tpu.dataset.transformer import SampleToMiniBatch
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import ValidationMethod
from bigdl_tpu.parallel.train_step import EvalStep, TrainStep
from bigdl_tpu.telemetry.memory import MemoryExhaustedError
from bigdl_tpu.telemetry.health import (HealthError, HealthPolicy,
                                        probe_stats)
from bigdl_tpu.utils.ckpt_topology import TopologyMismatchError
from bigdl_tpu.utils import file as File
from bigdl_tpu.utils.config import get_config
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.rng import RNG

__all__ = ["Optimizer", "LocalOptimizer", "DistriOptimizer",
           "StragglerTimeout", "HealthError", "HealthPolicy"]


class StragglerTimeout(RuntimeError):
    """A training iteration exceeded the host-level straggler budget
    (see docs/straggler.md).  Raised into the retry loop, which restores
    the latest checkpoint — the SPMD analogue of the reference's
    drop-gradients-and-continue (``DistriOptimizer.scala:415-420``)."""


#: BIGDL_RESUME spellings — every other boolean knob accepts 0/false/no,
#: so auto-resume must too (a knob meant to DISABLE resuming that
#: silently resumed would be the worst possible failure mode)
_RESUME_ON = frozenset({"auto", "on", "1", "true", "yes"})
_RESUME_OFF = frozenset({"off", "0", "false", "no"})

#: optimizers with an async checkpoint write possibly in flight — a
#: clean interpreter exit right after the last step must JOIN them, or
#: the tail of the write (meta commit included) is silently abandoned
#: and the newest checkpoint never becomes discoverable
_LIVE_CKPT_WRITERS: "weakref.WeakSet" = weakref.WeakSet()


@atexit.register
def _drain_ckpt_writes_at_exit():
    for o in list(_LIVE_CKPT_WRITERS):
        try:
            o._join_checkpoint_write()
        except Exception:  # noqa: BLE001 - exit path must not raise
            pass


class _PreemptGuard:
    """Grace-window SIGTERM/SIGINT handling (docs/fault_tolerance.md).

    The first signal only sets a flag: the training loop finishes the
    in-flight step, commits a final checkpoint carrying the dataset /
    epoch position and host-RNG state, emits ``run/preempted``, and
    returns normally (the process exits 0) — the shape of a TPU-slice
    preemption notice honored.  A second signal means "now": the
    original disposition is restored and re-raised, so a stuck grace
    window can still be killed.

    Installable only on the main thread (CPython restricts
    ``signal.signal``); elsewhere it degrades to a no-op and SIGTERM
    keeps its default (kill) semantics.
    """

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self):
        self.requested = threading.Event()
        self.signum: Optional[int] = None
        self._old = {}
        self._installed = False

    def _handler(self, signum, frame):
        if self.requested.is_set():
            # second signal: restore + re-deliver — immediate semantics
            self.uninstall()
            os.kill(os.getpid(), signum)
            return
        self.signum = signum
        self.requested.set()
        log.warning(f"[Preempt] received signal {signum}: finishing the "
                    f"in-flight step, then committing a final checkpoint "
                    f"(send again to stop immediately)")

    def install(self) -> "_PreemptGuard":
        if threading.current_thread() is not threading.main_thread():
            return self
        try:
            for sig in self.SIGNALS:
                self._old[sig] = signal.signal(sig, self._handler)
            self._installed = True
        except (ValueError, OSError):  # non-main interpreter contexts
            self._old.clear()
        return self

    def uninstall(self):
        if not self._installed:
            return
        for sig, old in self._old.items():
            try:
                signal.signal(sig, old)
            except (ValueError, OSError):
                pass
        self._old.clear()
        self._installed = False

log = logging.getLogger("bigdl_tpu.optim")
if not log.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("%(asctime)s %(levelname)s %(name)s: %(message)s"))
    log.addHandler(_h)
    log.setLevel(logging.INFO)


def _may_read(leaf, buffers: Sequence[np.ndarray]) -> bool:
    """Whether ``leaf``, a leaf of what a placement returned, may be
    backed by the memory of one of the host arrays ``buffers``.  Decided
    from what the leaf shows: a ``jax.Array`` on devices with memory of
    their own holds a copy, one of the CPU client may BE the host array
    (it takes an aligned one without a copy); a NumPy array says itself
    what it shares.  Anything that is not an array or a number is taken
    to hold what it was given."""
    if isinstance(leaf, jax.Array):
        return any(d.platform == "cpu" for d in leaf.devices())
    if isinstance(leaf, np.ndarray):
        return any(np.may_share_memory(leaf, b) for b in buffers)
    return not isinstance(leaf, (int, float, complex, np.generic))


class _StagingBuffers:
    """The host arrays ONE feeder worker stacks its batches into, and the
    rule for filling them again.  ``np.stack`` into a fresh allocation
    touches every page first (1.0 GB/s for a 616 MB batch, and threads do
    not add up); into pages that are there, 2.45 GB/s a thread (PERF.md
    section 6, PR 29 and PR 31).  So a worker keeps ONE set, one array a
    leaf of the batch; a batch of another shape or dtype gets fresh
    arrays, which replace the set.

    The lifetime rule.  Between :meth:`stack` and :meth:`release` the
    set belongs to the batch being placed.  :meth:`release` takes it
    back only when (a) what was placed from it is ready: the runtime
    may read a host buffer until its transfer completes; and (b) no
    placed array is backed by it (:func:`_may_read`).  Otherwise the
    arrays stay the batch's for good and the next batch is stacked into
    fresh ones, as if this class were not there.  Nothing placed is held
    past :meth:`release`, so no staged batch outlives its step here."""

    def __init__(self):
        self._free: List[np.ndarray] = []  # nothing reads these any more
        self._lent: List[np.ndarray] = []  # what the last batch was stacked into

    def stack(self, batch: MiniBatch) -> Tuple[MiniBatch, bool]:
        """``batch`` stacked, and whether every array went into one that
        was kept.  A deferred batch comes back as a new one over this
        set's arrays and stays deferred itself: whoever holds it (a
        dataset that hands the same batches out every epoch) holds
        nothing that will be filled again.  One that came stacked comes
        back as it is, and nothing of it is ever kept or written."""
        free, self._free = self._free, []
        staged = batch._stacked(out=free)
        self._lent = [] if staged is batch else staged.inputs + staged.targets
        return staged, bool(self._lent) and all(
            any(a is f for f in free) for a in self._lent)

    def release(self, placed) -> None:
        """``placed`` is what the placement made of the batch last
        stacked: wait until it is ready, then keep the arrays for the
        next batch unless something placed still reads them.  The wait
        is made for a batch that lent nothing too (one that came
        stacked), so that the feeder's place stage times the transfer
        for every batch alike."""
        lent, self._lent = self._lent, []
        jax.block_until_ready(placed)
        if not any(_may_read(leaf, lent) for leaf in jax.tree.leaves(placed)):
            self._free = lent


class _BatchPrefetcher:
    """The input pipeline's overlapped half: ``WORKERS`` host threads
    feed the mesh while the device crunches earlier steps.  The reference
    overlaps input the same way with its dedicated multithreaded
    transform+batch pipeline
    (``dataset/image/MTLabeledBGRImgToBatch.scala:31``).

    The dataset iterator (the whole host transform chain) stays ONE
    iterator, pulled under a lock and in order, so observers, random
    transforms, injected data faults and the epoch permutation see the
    sequence the synchronous path shows them.  A pull hands its worker a
    batch that is not stacked yet (``MiniBatch.from_samples``) and a
    sequence number; off the lock the worker stacks it into the staging
    arrays it keeps (:class:`_StagingBuffers`; Metrics ``batch stack time
    (overlapped)``, span ``feeder/stack``), places it and waits until
    the placed arrays are ready (``host to device time (overlapped)``,
    span ``feeder/place``: the transfer, not only its issue), both
    summed over workers: producer-side busy time, not driver stall.  The
    driver's stall is ``data time``, the wait in :meth:`next`, ~0 when
    the pipeline keeps up.  Gauge ``prefetch/staging_reuse`` is the share
    of batches so far that were stacked into kept arrays.

    :meth:`next` hands batches out strictly in sequence, an error or the
    end of the data in its place in the sequence.  ``depth + WORKERS``
    bounds the batches pulled and not yet handed out (gauge
    ``prefetch/in_flight``; pinned at 1, the pool never engaged), and
    with them the device memory for staged inputs; a worker with no slot
    blocks and costs nothing, which is why one ``WORKERS`` serves a 64 KB
    batch and a 616 MB one alike."""

    #: from sweeps on the chip (PERF.md section 6).  PR 29, stacking into
    #: fresh pages: one chip's cells stopped gaining at 4, the four-chip
    #: cell at 8.  PR 31, into kept arrays: the feeder alone delivers
    #: 17.8 / 24.5 / 23.2 / 21.9 batches of 616 MB a second from 2 / 4 / 8 /
    #: 16 workers (the host's memory bandwidth) where the four-chip loop
    #: takes 16.7, so 8 has room and 2 has none.  Not a knob
    WORKERS = min(8, os.cpu_count() or 1)

    class _Error:
        def __init__(self, exc):
            self.exc = exc

    _END = object()

    def __init__(self, data_iter, place_fn, depth: int, metrics: Metrics):
        self._it = data_iter
        self._place = place_fn
        self._metrics = metrics
        self._limit = max(depth, 1) + self.WORKERS
        self._pull_lock = threading.Lock()  # the iterator, in order
        self._cv = threading.Condition()    # everything below
        self._ready: Dict[int, object] = {}  # sequence number -> item
        self._pulled = 0   # sequence numbers given out
        self._handed = 0   # the next one next() hands out
        self._done = False  # the end or an error is in the sequence
        self._stop = False
        self._cycle = 0.0  # seconds a worker lately took over one batch
        self._last_pull = 0.0
        self._stacked = 0  # batches the workers stacked and placed,
        self._reused = 0   # and those of them that went into kept arrays
        self._threads = [
            threading.Thread(target=self._worker, name="bigdl-prefetch",
                             daemon=True) for _ in range(self.WORKERS)]
        for t in self._threads:
            t.start()

    def _pull(self):
        """The next ``(sequence number, batch)`` of the iterator, its end
        or its error in a batch's place; None when there is nothing more
        to pull.  Waits, in the lock, for a slot and for its turn: the
        workers behind it could not go ahead of it anyway."""
        with self._pull_lock:
            with self._cv:
                while not self._stop and not self._done:
                    if self._pulled - self._handed >= self._limit:
                        self._cv.wait()
                        continue
                    # pulls keep a WORKERS-th of a batch's cycle apart.
                    # Workers that start together finish together, and
                    # nothing else ever parts them: the loop would get
                    # WORKERS batches at once and then none for a whole
                    # cycle (on the chip a p90 step of 997 ms beside a
                    # median of 120, PERF.md section 6, PR 29)
                    wait = self._last_pull + self._cycle / self.WORKERS \
                        - time.monotonic()
                    if wait <= 0:
                        break
                    self._cv.wait(wait)
                if self._stop or self._done:
                    return None
                self._last_pull = time.monotonic()
            try:
                batch = next(self._it, self._END)
            except BaseException as e:  # noqa: BLE001 — surfaced on next()
                batch = self._Error(e)
            with self._cv:
                seq = self._pulled
                self._pulled += 1
                if batch is self._END or isinstance(batch, self._Error):
                    self._done = True
                    self._cv.notify_all()
                in_flight = self._pulled - self._handed
        telemetry.gauge("prefetch/in_flight", in_flight)
        return seq, batch

    def _worker(self):
        staging = _StagingBuffers()
        while True:
            pulled = self._pull()
            if pulled is None:
                return
            seq, item = pulled
            cycle = reused = None
            if item is not self._END and not isinstance(item, self._Error):
                try:
                    item, cycle, reused = self._stack_and_place(item, staging)
                except BaseException as e:  # noqa: BLE001 — surfaced on next()
                    item = self._Error(e)
            with self._cv:
                if isinstance(item, self._Error):
                    self._done = True  # nothing is pulled past an error
                if cycle is not None:
                    self._cycle = (self._cycle + cycle) / 2 \
                        if self._cycle else cycle
                    self._stacked += 1
                    self._reused += reused
                if not self._stop:
                    self._ready[seq] = item
                depth = len(self._ready)
                reuse = self._reused / max(self._stacked, 1)
                self._cv.notify_all()
            # producer-side fill level: pinned at 0 means the input
            # pipeline is the bottleneck; at the bound, the device is
            # (docs/observability.md)
            telemetry.gauge("prefetch/queue_depth", depth)
            telemetry.gauge("prefetch/staging_reuse", reuse)

    def _stack_and_place(self, batch, staging):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("feeder/stack"):
            staged, reused = staging.stack(batch)
            x, y = staged.get_input(), staged.get_target()
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("feeder/place"):
            placed = self._place(x, y)
            staging.release(placed)
        t2 = time.perf_counter()
        self._metrics.add("batch stack time (overlapped)", t1 - t0)
        self._metrics.add("host to device time (overlapped)", t2 - t1)
        return (batch.size(), placed), t2 - t0, reused

    def next(self):
        """(global_batch_size, placed_arrays) or None when exhausted;
        re-raises any producer-side failure on the driver thread (so the
        retry loop sees data errors exactly like compute errors)."""
        with self._cv:
            while self._handed not in self._ready:
                self._cv.wait()
            item = self._ready.pop(self._handed)
            self._handed += 1
            self._cv.notify_all()  # a slot is free
        if isinstance(item, self._Error):
            raise item.exc
        return None if item is self._END else item

    def close(self):
        with self._cv:
            self._stop = True
            self._ready.clear()
            self._cv.notify_all()  # workers waiting for a slot
        deadline = time.monotonic() + 5.0
        for t in self._threads:
            t.join(timeout=max(deadline - time.monotonic(), 0.0))


class _CounterLines:
    """Takes a layer's counters as a telemetry run does and keeps them
    as one log line a layer and name: ``[Layer 2.0.ffn] moe/load 213 81
    ...`` says at the end of a run what no run log was open to hear."""

    def __init__(self):
        self.values = {}

    def counter(self, name, value, layer="", **labels):
        self.values.setdefault((layer, name), []).append(value)

    def lines(self):
        return [f"[Layer {layer}] {name} " + " ".join(map(str, v))
                for (layer, name), v in self.values.items()]


class Optimizer:
    """Factory + base driver.  ``Optimizer(model=..., dataset=...,
    criterion=...)`` picks Local vs Distri by Engine topology, mirroring
    ``Optimizer.apply`` (``optim/Optimizer.scala:411-430``)."""

    def __new__(cls, *args, **kwargs):
        if cls is Optimizer:
            target = DistriOptimizer if Engine.device_count() > 1 else LocalOptimizer
            obj = object.__new__(target)
            return obj
        return object.__new__(cls)

    def __init__(self, model, dataset, criterion, batch_size: Optional[int] = None,
                 end_trigger: Optional[Trigger] = None, *,
                 optim_method: Optional[OptimMethod] = None):
        if isinstance(dataset, (list, tuple)):
            if batch_size is None:
                raise ValueError("batch_size required when passing raw samples")
            # multi-host: each process keeps 1/N of the records and batches
            # its LOCAL share of the global batch (the reference's
            # one-cached-partition-per-node layout, DataSet.scala:164-240)
            nproc, pidx = Engine.process_count(), Engine.process_index()
            if nproc > 1:
                if batch_size % nproc != 0:
                    raise ValueError(
                        f"global batch_size {batch_size} must divide by the "
                        f"{nproc} host processes")
                dataset = DataSet.array(
                    list(dataset), num_shards=nproc, shard_index=pidx
                ).transform(SampleToMiniBatch(batch_size // nproc))
            else:
                dataset = DataSet.array(list(dataset)).transform(
                    SampleToMiniBatch(batch_size))
        self.model = model
        self.dataset: AbstractDataSet = dataset
        self.criterion = criterion
        # constructor kwarg for parity with the reference Python API
        # (optimizer.py Optimizer(..., optim_method=...)); set_optim_method
        # remains the fluent route
        self.optim_method: OptimMethod = optim_method or SGD()
        self.end_when: Trigger = end_trigger or Trigger.max_iteration(2**62)
        self.state: Dict = {"epoch": 1, "neval": 0}
        self.metrics = Metrics()
        from collections import deque

        self._iteration_times = deque(maxlen=20)  # straggler auto budget
        # validation
        self._val_trigger = None
        self._val_dataset = None
        self._val_methods: Sequence[ValidationMethod] = ()
        # checkpoint
        self._ckpt_path = None
        self._ckpt_trigger = None
        self._ckpt_overwrite = False
        self._ckpt_backend = "btpu"
        self._ckpt_keep = None
        self._pending_sharded_restore = None
        # summaries
        self._train_summary = None
        self._val_summary = None
        # step config
        self.parameter_sync = "allreduce"
        self.gradient_compression: Optional[str] = None
        self.compute_dtype = None
        self._grad_clip = None
        self._grad_clip_norm = None
        self._mesh = None  # set by subclass
        # training health (docs/observability.md): None = resolve from
        # BIGDL_HEALTH at optimize() time
        self._health_policy: Optional[HealthPolicy] = None

    # -- fluent config (Optimizer.scala:42-265) ----------------------------
    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset, methods: Sequence[ValidationMethod],
                       batch_size: Optional[int] = None) -> "Optimizer":
        if isinstance(dataset, (list, tuple)):
            dataset = DataSet.array(list(dataset)).transform(
                SampleToMiniBatch(batch_size or 32))
        self._val_trigger = trigger
        self._val_dataset = dataset
        self._val_methods = list(methods)
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       backend: str = "btpu",
                       keep: Optional[int] = None) -> "Optimizer":
        """``backend="btpu"`` (default): gather to the coordinator and
        write whole-model BTPU files — the reference's driver-side
        saveModel (``Optimizer.scala:284-322``).  ``backend="sharded"``:
        every host writes only its own array shards via orbax
        (``utils/sharded_ckpt.py``) — the pod-scale layout where the
        model may not fit one host.  ``keep=N`` retains only the newest N
        checkpoints (retention the reference lacks — its ``model.n``
        files accumulate forever); ``None`` keeps everything."""
        if backend not in ("btpu", "sharded"):
            raise ValueError(f"unknown checkpoint backend {backend!r}")
        if keep is not None and keep < 1:
            raise ValueError("keep must be >= 1")
        self._ckpt_path = path
        self._ckpt_trigger = trigger
        self._ckpt_backend = backend
        self._ckpt_keep = keep
        return self

    def overwrite_checkpoint(self) -> "Optimizer":
        self._ckpt_overwrite = True
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self._train_summary = summary
        return self

    def set_validation_summary(self, summary) -> "Optimizer":
        self._val_summary = summary
        return self

    def set_model(self, model) -> "Optimizer":
        self.model = model
        return self

    def set_state(self, state: Dict) -> "Optimizer":
        self.state.update(state)
        return self

    def set_parameter_sync(self, mode: str) -> "Optimizer":
        """'allreduce', 'sharded' (ZeRO-1: optimizer state over the data
        axis), 'fsdp' (ZeRO-3: parameters too — no whole replica per
        device), or 'local' (local SGD: every data-axis device trains
        its own island, parameters average every ``BIGDL_LOCAL_SYNC_H``
        steps under a bounded-staleness barrier —
        parallel/local_sync.py, docs/fault_tolerance.md "Straggler
        tolerance")."""
        if mode not in ("allreduce", "sharded", "fsdp", "local"):
            raise ValueError(f"unknown parameter_sync mode {mode!r}")
        self.parameter_sync = mode
        return self

    def set_gradient_compression(self, mode: Optional[str]) -> "Optimizer":
        """'bf16' reproduces the reference FP16CompressedTensor truncation."""
        self.gradient_compression = mode
        return self

    def set_compute_dtype(self, dtype) -> "Optimizer":
        self.compute_dtype = dtype
        return self

    def set_constant_gradient_clipping(self, lo: float, hi: float) -> "Optimizer":
        self._grad_clip = (lo, hi)
        return self

    def set_gradient_clipping_by_l2_norm(self, max_norm: float) -> "Optimizer":
        self._grad_clip_norm = max_norm
        return self

    def set_health_policy(self, policy: Optional[HealthPolicy]) -> "Optimizer":
        """Install a training-health policy (``telemetry/health.py``):
        numeric-health probes in the compiled step, loss-spike/plateau
        EWMA detection, and warn / skip-step / halt actions.  When never
        called, the policy comes from ``BIGDL_HEALTH`` /
        ``BIGDL_HEALTH_HALT_AFTER`` (default: halt after 3 consecutive
        nonfinite steps).  Pass a policy with ``on_nonfinite="off"`` (or
        set ``BIGDL_HEALTH=off``) to disable the probes entirely."""
        self._health_policy = policy
        return self

    # -- checkpointing -----------------------------------------------------
    def _checkpoint_dir(self) -> Optional[str]:
        return getattr(self, "_ckpt_dir", None)

    def _init_checkpoint_dir(self):
        if self._ckpt_path is None:
            return
        if self._ckpt_overwrite:
            self._ckpt_dir = self._ckpt_path
        else:
            stamp = datetime.now().strftime("%Y%m%d_%H%M%S")
            self._ckpt_dir = File.join(self._ckpt_path, stamp)
        File.makedirs(self._ckpt_dir)

    def _join_checkpoint_write(self):
        """Block until the in-flight async checkpoint write (if any) has
        landed — called before restores, before the next checkpoint, and
        at run end, so a reader can never observe a half-written file
        set."""
        fut = getattr(self, "_ckpt_future", None)
        if fut is not None:
            with self.metrics.timer("checkpoint wait time"):
                fut.result()
            self._ckpt_future = None

    def _driver_state_snapshot(self) -> Dict:
        """The driver state a checkpoint carries: epoch/iteration/record
        position PLUS the host-RNG state and the run's step-key seed —
        everything a fresh process needs to resume mid-epoch on the
        exact batch and random stream the interrupted run would have
        used next (docs/fault_tolerance.md)."""
        snap = dict(self.state)
        snap["rng_state"] = RNG.get_state()
        return snap

    def _save_checkpoint(self, step: TrainStep):
        if self._checkpoint_dir() is None:
            return
        if self._ckpt_backend == "sharded":
            # per-host shard writes — no gather, no single writer.  The
            # device-side dispatch happens NOW (orbax snapshots the
            # arrays); under BIGDL_ASYNC_CHECKPOINT the durable-write +
            # meta-commit tail overlaps the next training steps behind
            # the same _join_checkpoint_write barrier as the BTPU path.
            from bigdl_tpu.utils import sharded_ckpt

            self._join_checkpoint_write()  # meta commits stay ordered
            n = self.state["neval"]
            dest = File.join(self._ckpt_dir, f"sharded.{n}")
            use_async = get_config().async_checkpoint
            finish = sharded_ckpt.save_train_step(
                step, dest,
                extra={"driver_state": self._driver_state_snapshot()},
                wait=not use_async)

            def tail():
                if finish is not None:
                    finish()
                svc = _cluster.get()
                if svc is not None:
                    # two-phase cluster commit (parallel/cluster.py):
                    # THIS host's shards are durable — ack; the
                    # coordinator rolls all acks into the cluster
                    # manifest that gates restore eligibility
                    svc.commit_step(self._ckpt_dir, n)
                if self._ckpt_keep and Engine.is_coordinator():
                    # the manifest step is pinned: cluster restores CAP
                    # at it, so pruning it (because newer, possibly
                    # uncertified checkpoints fill the keep window)
                    # would strand the whole cluster
                    cap = (svc.restore_cap(self._ckpt_dir)
                           if svc is not None else None)
                    for p in sharded_ckpt.prune_old(
                            self._ckpt_dir, self._ckpt_keep,
                            trusted=dest, keep_step=cap,
                            # mixed-topology dirs: never delete the last
                            # checkpoint restorable onto the CURRENT
                            # width (docs/fault_tolerance.md "Elastic
                            # recovery")
                            restorable_fn=sharded_ckpt.restorable_onto_fn(
                                self._mesh)):
                        log.info(f"[Checkpoint] pruned {p}")
                log.info(f"[Checkpoint] saved sharded.{n} "
                         f"to {self._ckpt_dir}")
                telemetry.instant("checkpoint/saved", step=n,
                                  backend="sharded")

            if use_async:
                self._ckpt_future = self._ckpt_pool_submit(tail)
            else:
                tail()
            return
        from bigdl_tpu.utils.module_format import dumps

        # every process participates in the gathers (collectives on a
        # multi-host mesh); only the coordinator writes files —
        # single-writer-safe checkpointing
        step.sync_to_model()
        n = self.state["neval"]
        self.optim_method.state["driver_state"] = self._driver_state_snapshot()
        self.optim_method.state["func_state"] = jax.tree.map(
            np.asarray, step.gather_replicated(step.opt_state))
        if not Engine.is_coordinator():
            svc = _cluster.get()
            if svc is not None:
                # BTPU writes are coordinator-only, but the commit
                # barrier still needs every host's ack: "I reached the
                # step-n commit point with consistent driver state"
                svc.commit_step(self._ckpt_dir, n)
            return
        # snapshot to bytes NOW (consistent state); the IO can overlap
        # with the next training iterations (BIGDL_ASYNC_CHECKPOINT)
        self._join_checkpoint_write()
        from bigdl_tpu.utils import ckpt_digest, ckpt_topology

        blobs = [(dumps(self.model, kind="module"),
                  os.path.join(self._ckpt_dir, f"model.{n}")),
                 (dumps(self.optim_method, kind="optim"),
                  os.path.join(self._ckpt_dir, f"optimMethod.{n}"))]
        # content digests of the exact bytes being written, committed in
        # a meta marker AFTER the payload lands — restore verifies them
        # before loading, so a torn/bit-rotted pair is quarantined, not
        # silently deserialized.  The topology record rides along (own
        # digest): BTPU state is gathered whole-model — portable by
        # construction — but a restore onto a different width still
        # announces the reshard and the resume hint still names the
        # widths the sharded layout would accept.
        topo = ckpt_topology.topology_of(step)
        meta = {"neval": n,
                "digests": {os.path.basename(p): ckpt_digest.digest_bytes(b)
                            for b, p in blobs},
                "topology": topo,
                "topology_digest": ckpt_topology.digest(topo)}
        meta_path = os.path.join(self._ckpt_dir, f"ckptmeta.{n}.json")

        def write():
            import json as _json

            for blob, path in blobs:
                File.save(blob, path, overwrite=True)
            File.save(_json.dumps(meta).encode(), meta_path, overwrite=True)
            try:  # fault injection: tear the committed model payload
                _faults.get_plan().poll_checkpoint(blobs[0][1], n)
            except Exception:  # noqa: BLE001 - injection never fails a save
                pass
            svc = _cluster.get()
            if svc is not None:
                # coordinator ack + manifest roll-up: the per-host
                # digests recorded in the meta marker travel with the
                # ack into the cluster manifest
                svc.commit_step(self._ckpt_dir, n,
                                digests=meta["digests"])
            if self._ckpt_keep:
                self._prune_btpu(trusted=n)
            log.info(f"[Checkpoint] saved model.{n} / optimMethod.{n} "
                     f"to {self._ckpt_dir}")
            telemetry.instant("checkpoint/saved", step=n, backend="btpu")

        if get_config().async_checkpoint:
            self._ckpt_future = self._ckpt_pool_submit(write)
        else:
            write()

    def _ckpt_pool_submit(self, fn):
        from concurrent.futures import ThreadPoolExecutor

        if getattr(self, "_ckpt_pool", None) is None:
            self._ckpt_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="bigdl-ckpt")
        # interpreter exit joins this write (atexit drain): a fast exit
        # right after the last step must not abandon the meta commit
        _LIVE_CKPT_WRITERS.add(self)
        return self._ckpt_pool.submit(fn)

    def _prune_btpu(self, trusted: Optional[int] = None):
        """Keep only the newest ``keep`` model/optimMethod pairs (meta
        markers pruned with them) — coordinator-only (the btpu write
        path already is).  The newest VERIFIED pair is never deleted:
        if every newer checkpoint turns out torn, it is the only state
        a restore can still fall back to.  ``trusted`` marks the step
        number this very write just produced and digested, sparing a
        re-read+hash per save."""
        d = self._ckpt_dir
        nums = sorted(int(m.group(1))
                      for f in File.listdir(d)
                      if (m := re.match(r"model\.(\d+)$", f)))
        victims = nums[:-self._ckpt_keep]
        svc = _cluster.get()
        if svc is not None:
            # never prune the cluster-manifest step: cluster restores
            # cap at it, and newer (uncertified) pairs can't replace it
            cap = svc.restore_cap(d)
            victims = [n for n in victims if n != cap]
        if victims and not any(n == trusted or self._btpu_verify(d, n)[0]
                               for n in
                               reversed(nums[-self._ckpt_keep:])):
            for n in reversed(victims):
                if self._btpu_verify(d, n)[0]:
                    victims = [v for v in victims if v != n]
                    log.warning(f"[Checkpoint] retaining checkpoint {n} "
                                f"beyond keep={self._ckpt_keep}: it is "
                                f"the last verified-good one")
                    break
        for n in victims:
            for name in (f"model.{n}", f"optimMethod.{n}",
                         f"ckptmeta.{n}.json"):
                p = File.join(d, name)
                if File.exists(p):
                    File.remove(p)
            log.info(f"[Checkpoint] pruned model.{n} / optimMethod.{n}")

    @staticmethod
    def get_latest_file(path: str, prefix: str) -> Optional[str]:
        """(``DistriOptimizer.scala:868-885``); local or remote
        (``gs://...``) checkpoint directories."""
        best, best_n = None, -1
        pat = re.compile(re.escape(prefix) + r"\.(\d+)$")
        for f in File.listdir(path):
            m = pat.match(f)
            if m and int(m.group(1)) > best_n:
                best_n = int(m.group(1))
                best = File.join(path, f)
        return best

    def _restore_latest(self) -> bool:
        d = self._checkpoint_dir()
        if d is None:
            return False
        self._join_checkpoint_write()
        return self._restore_from(d)

    def _restore_from(self, d: str) -> bool:
        """Timed wrapper around :meth:`_restore_from_verified`: the
        restore interval is checkpoint badput the goodput ledger
        (telemetry/ledger.py) must see as a measured out-of-step
        interval, not unattributable idle."""
        t0 = time.perf_counter()
        try:
            return self._restore_from_verified(d)
        finally:
            telemetry.stage("checkpoint/restore",
                            time.perf_counter() - t0, source=d)

    def _restore_from_verified(self, d: str) -> bool:
        """Restore the newest VERIFIED checkpoint under ``d``: content
        digests are checked before anything is loaded, torn candidates
        are quarantined (``*.corrupt`` + ``checkpoint/quarantined``)
        and the walk falls back to the previous good step — a restore
        either loads a byte-verified checkpoint fully or reports there
        is none (``docs/fault_tolerance.md``)."""
        # cluster runs restore ONLY what the commit barrier certified:
        # the manifest step caps the walk, so a checkpoint some host
        # wrote but the cluster never acked is structurally invisible —
        # every host lands on the same step (parallel/cluster.py)
        svc = _cluster.get()
        cap = svc.restore_cap(d) if svc is not None else None
        if cap is not None:
            log.info(f"[Recovery] cluster manifest caps restore at "
                     f"step {cap} under {d}")
        if self._ckpt_backend == "sharded":
            from bigdl_tpu.utils.sharded_ckpt import (
                latest_verified_step_dir, restorable_onto_fn)

            # elastic walk: a verified step whose recorded topology the
            # CURRENT mesh cannot take is skipped (not quarantined) in
            # favor of the newest one this width can restore.  The walk
            # probes restorability only on VERIFIED candidates, so a
            # wrapper recording rejections distinguishes "nothing to
            # resume" from "none restores at this width" without
            # re-hashing every dir a second time.
            base_fn = restorable_onto_fn(self._mesh)
            unrestorable: List[str] = []

            def probing_fn(p: str) -> bool:
                ok = base_fn(p)
                if not ok:
                    unrestorable.append(p)
                return ok

            latest = latest_verified_step_dir(d, max_step=cap,
                                              restorable_fn=probing_fn)
            if latest is None:
                if unrestorable:
                    # checkpoints exist but NONE restores at this width
                    # — silently restarting from step 0 would throw
                    # away all progress behind a log line (e.g. a
                    # --min-n width outside the restorable sizes)
                    raise TopologyMismatchError(
                        f"checkpoints exist under {d} "
                        f"({len(unrestorable)} verified) but none is "
                        f"restorable onto the current mesh — pick a "
                        f"width from the checkpoint's restorable sizes "
                        f"(the preemption resume hint prints them) or "
                        f"resume at the writing width")
                return False
            # applied onto the fresh TrainStep inside _optimize_once (the
            # restore needs the live mesh placement, which the step owns)
            self._pending_sharded_restore = latest
            log.info(f"[Recovery] will restore sharded state from {latest}")
            return True
        from bigdl_tpu.utils.serializer import load_module, load_optim_method

        nums = sorted({int(m.group(1)) for f in File.listdir(d)
                       if (m := re.match(r"model\.(\d+)$", f))},
                      reverse=True)
        if cap is not None:
            nums = [n for n in nums if n <= cap]
        for n in nums:
            ok, problems = self._btpu_verify(d, n)
            mfile = File.join(d, f"model.{n}")
            ofile = File.join(d, f"optimMethod.{n}")
            if ok:
                try:
                    model = load_module(mfile)
                    optim_method = load_optim_method(ofile)
                except Exception as e:  # noqa: BLE001 - treat as torn
                    ok, problems = False, [f"load failed: {e}"]
            if not ok:
                self._quarantine_btpu(d, n, problems)
                continue
            self.model = model
            self.optim_method = optim_method
            self._apply_driver_state(
                self.optim_method.state.get("driver_state", {}))
            log.info(f"[Recovery] restored {mfile} and {ofile}")
            self._announce_btpu_reshard(d, n)
            return True
        return False

    def _announce_btpu_reshard(self, d: str, n: int) -> None:
        """BTPU state is gathered whole-model — portable onto any width
        by construction — but a restore whose recorded topology differs
        from the live one is still a membership change the fleet view
        and the flight recorder must see: announce it as a
        ``cluster/reshard`` instant (docs/fault_tolerance.md "Elastic
        recovery")."""
        from bigdl_tpu.utils import ckpt_topology

        topo = (self._btpu_meta(d, n) or {}).get("topology")
        if not topo:
            return
        fields = ckpt_topology.reshard_fields(topo, self._mesh,
                                              source="restore", step=n)
        if fields is not None:
            log.info(f"[Reshard] restoring a checkpoint "
                     f"{ckpt_topology.describe(topo)} onto "
                     f"{fields['to_processes']} process(es) / "
                     f"{fields['to_devices']} device(s)")
            telemetry.instant("cluster/reshard", **fields)

    def _btpu_meta(self, d: str, n: int) -> Optional[Dict]:
        import json as _json

        try:
            return _json.loads(File.load(
                File.join(d, f"ckptmeta.{n}.json")).decode())
        except (OSError, ValueError):
            return None

    def _btpu_verify(self, d: str, n: int) -> Tuple[bool, List[str]]:
        """Digest check of the ``model.n``/``optimMethod.n`` pair against
        its ``ckptmeta.n.json`` marker — the topology record (when
        present) verifies against its own digest too.  Pairs from before
        the digest era (no marker) pass when both files exist —
        rejecting them would strand every old checkpoint."""
        from bigdl_tpu.utils import ckpt_digest, ckpt_topology

        meta = self._btpu_meta(d, n)
        if meta is None:
            both = all(File.exists(File.join(d, f"{p}.{n}"))
                       for p in ("model", "optimMethod"))
            return both, ([] if both else
                          [f"incomplete pair at {n} (no meta marker)"])
        problems = list(ckpt_topology.verify_digest(meta))
        problems.extend(
            ckpt_digest.verify_digests(d, meta.get("digests") or {}))
        return not problems, problems

    def _quarantine_btpu(self, d: str, n: int, problems: List[str]):
        """Move a torn BTPU pair aside as ``*.corrupt`` (postmortem
        evidence; discovery can never pick it again)."""
        moved = []
        for name in (f"model.{n}", f"optimMethod.{n}", f"ckptmeta.{n}.json"):
            p = File.join(d, name)
            if File.exists(p):
                dest = p + ".corrupt"
                k = 1
                while File.exists(dest):  # never overwrite prior evidence
                    dest = p + f".corrupt.{k}"
                    k += 1
                try:
                    File.rename(p, dest)
                    moved.append(name)
                except OSError:
                    log.error(f"[Checkpoint] could not quarantine {p}")
        log.error(f"[Checkpoint] quarantined checkpoint {n} ({moved}): "
                  f"{'; '.join(problems) or 'integrity check failed'}")
        telemetry.instant("checkpoint/quarantined", step=n, backend="btpu",
                          problems=list(problems))

    def _apply_driver_state(self, driver_state: Dict):
        """Fold a checkpoint's driver state into the live run: position
        counters into ``self.state``, host-RNG state back into ``RNG``
        (so transform randomness and key draws continue the interrupted
        stream instead of forking)."""
        ds = dict(driver_state or {})
        rng_state = ds.pop("rng_state", None)
        self.state.update(ds)
        if rng_state:
            try:
                RNG.set_state(rng_state)
            except Exception as e:  # noqa: BLE001 - resume still works,
                # only host-random reproducibility degrades
                log.warning(f"[Recovery] could not restore RNG state "
                            f"({type(e).__name__}: {e})")

    def resume_hint(self) -> Optional[str]:
        """Operator-facing resume guidance after a preemption: the
        topology the newest checkpoint was written under, the widths it
        can restore onto (topology-portable — docs/fault_tolerance.md
        "Elastic recovery"), and the capacity-aware ``supervise
        --min-n`` recipe.  None when no checkpoint/topology exists."""
        from bigdl_tpu.utils import ckpt_topology

        d = self._checkpoint_dir()
        if d is None:
            return None
        topo = None
        try:
            if self._ckpt_backend == "sharded":
                from bigdl_tpu.utils.sharded_ckpt import (latest_step_dir,
                                                          read_topology)

                latest = latest_step_dir(d)
                if latest:
                    topo = read_topology(latest)
            else:
                nums = [int(m.group(1)) for f in File.listdir(d)
                        if (m := re.match(r"ckptmeta\.(\d+)\.json$", f))]
                if nums:
                    topo = (self._btpu_meta(d, max(nums))
                            or {}).get("topology")
        except OSError:
            return None
        if not topo:
            return None
        lines = [f"checkpoint topology: {ckpt_topology.describe(topo)}"]
        nproc = int(topo.get("process_count") or 1)
        if nproc > 1:
            # suggest a width the checkpoint can actually take: the
            # restorable sizes are MESH sizes, so a candidate process
            # count m maps to m × devices-per-process; prefer the
            # largest restorable width at or below half capacity
            sizes = ckpt_topology.restorable_mesh_sizes(topo)
            dpp = max(1, int(topo.get("device_count") or nproc) // nproc)
            cands = [m for m in range(1, nproc)
                     if sizes is None or m * dpp in sizes]
            if cands:
                min_n = max([m for m in cands if m <= nproc // 2]
                            or cands)
                lines.append(
                    f"shrunk slice? resume on fewer chips: "
                    f"python -m bigdl_tpu.models.cli supervise "
                    f"-n {nproc} --min-n {min_n} -- <your train "
                    f"command> — restart attempts that keep losing "
                    f"the same peer relaunch at {min_n} process(es); "
                    f"this checkpoint reshards onto the smaller mesh "
                    f"on load")
        return "\n".join(lines)

    def _resume_sources(self) -> List[str]:
        """Candidate directories a fresh ``optimize()`` may auto-resume
        from, best first: the checkpoint dir itself under
        ``overwrite_checkpoint`` (stable path), else every PREVIOUS
        stamped subdir holding checkpoint-like files, newest first —
        ALL of them, so a newest run whose only checkpoint turned out
        torn falls back to the run before it."""
        if self._ckpt_overwrite:
            return [self._ckpt_dir]
        stamps = sorted((s for s in File.listdir(self._ckpt_path)
                         if re.fullmatch(r"\d{8}_\d{6}", s)), reverse=True)
        me = os.path.basename(self._ckpt_dir)
        out = []
        for s in stamps:
            if s == me:
                continue
            d = File.join(self._ckpt_path, s)
            if any(f.startswith(("model.", "sharded."))
                   for f in File.listdir(d)):
                out.append(d)
        return out

    def _maybe_resume(self):
        """Preemption-safe resume: when a checkpoint path is configured
        and holds a verified checkpoint, a FRESH run continues from it —
        mid-epoch, on the exact next batch — instead of starting over.
        ``BIGDL_RESUME=off`` restores start-from-scratch semantics; an
        explicitly ``set_state``-positioned run is left alone."""
        if self._ckpt_path is None or get_config().resume in _RESUME_OFF:
            return
        if self.state.get("neval", 0) > 0:
            return
        for src in self._resume_sources():
            if not self._restore_from(src):
                log.warning(f"[Resume] no loadable checkpoint under "
                            f"{src}; trying the run before it")
                continue
            self.state["_resumed_from"] = src
            telemetry.instant("run/resumed", source=src,
                              step=self.state.get("neval", 0))
            log.info(f"[Resume] continuing from {src} at iteration "
                     f"{self.state.get('neval', 0)} "
                     f"(epoch {self.state.get('epoch', 1)}, "
                     f"{self.state.get('records', 0)} records into it)")
            return

    def _fast_forward(self, data_iter, records: int, record_scale: int):
        """Skip the batches a restored position says were already
        consumed this epoch — the second half of mid-epoch resume (the
        first half is the dataset's deterministic epoch order).  Host
        transform work only; no device dispatch."""
        t0 = time.perf_counter()
        skipped = 0
        while skipped < records:
            batch = next(data_iter, None)
            if batch is None:
                log.warning(f"[Resume] dataset exhausted after skipping "
                            f"{skipped}/{records} records")
                break
            skipped += batch.size() * record_scale
        if skipped != records:
            log.warning(f"[Resume] fast-forward skipped {skipped} records "
                        f"but the checkpoint recorded {records} — batch "
                        f"size changed between runs?")
        else:
            log.info(f"[Resume] fast-forwarded {skipped} records in "
                     f"{time.perf_counter() - t0:.2f}s to resume "
                     f"mid-epoch")
        telemetry.stage("resume/fast_forward",
                        time.perf_counter() - t0, records=skipped)
        return data_iter

    # -- validation --------------------------------------------------------
    def _validate(self, eval_step: EvalStep):
        if self._val_dataset is None:
            return
        t0 = time.perf_counter()
        results = None
        count = 0
        # multi-host: round-robin the validation batches across processes
        # and merge collectively — the reference shards validation over
        # the cluster the same way (optim/DistriValidator.scala:35,
        # DistriOptimizer.scala:632) instead of evaluating the full set
        # everywhere.  A DistributedDataSet is ALREADY per-process
        # sharded — iterate it fully and only merge.
        from bigdl_tpu.dataset.dataset import DistributedDataSet

        nproc, pidx = Engine.process_count(), Engine.process_index()
        presharded = isinstance(self._val_dataset, DistributedDataSet) \
            and getattr(self._val_dataset, "num_shards", 1) > 1
        for i, batch in enumerate(self._val_dataset.data(train=False)):
            if nproc > 1 and not presharded and i % nproc != pidx:
                continue
            out = eval_step.run(batch.get_input())
            target = batch.get_target()
            rs = [m(out, target) for m in self._val_methods]
            results = rs if results is None else [a + b for a, b in zip(results, rs)]
            count += batch.size()
        if nproc > 1:
            from bigdl_tpu.optim.validation import merge_across_processes

            results = merge_across_processes(results, self._val_methods)
            count = int(results[0].result()[1]) if results else count
            if count == 0:
                results = None  # no process saw a batch: nothing measured
        if results is None:
            return
        wall = time.perf_counter() - t0
        log.info(f"[Validation] {count} records in {wall:.2f}s, "
                 f"throughput {count / max(wall, 1e-9):.1f} records/s")
        for m, r in zip(self._val_methods, results):
            log.info(f"[Validation] {m} is {r}")
            val, _ = r.result()
            self.state["score"] = val
            if self._val_summary is not None:
                self._val_summary.add_scalar(str(m), val, self.state["neval"])
            sched = getattr(self.optim_method, "schedule", None)
            if sched is not None and hasattr(sched, "on_metric"):
                sched.on_metric(val)

    # -- the loop ----------------------------------------------------------
    def _telemetry_begin(self, cfg):
        """Run-scoped telemetry wiring: auto-start a JSONL run when
        ``BIGDL_TELEMETRY`` names a directory (owned = ended by us),
        attach the retrace-attribution bridge to the dispatch hook bus,
        and forward counter/gauge streams into the TrainSummary writers
        so TensorBoard stays the visual frontend."""
        self._tele_owner = False
        self._tele_retrace = None
        self._tele_summary_sink = None
        try:
            if cfg.telemetry_dir and not telemetry.enabled():
                meta = {"model": type(self.model).__name__,
                        "optimizer": type(self).__name__,
                        "parameter_sync": self.parameter_sync}
                telemetry.start_run(cfg.telemetry_dir, meta=meta)
                self._tele_owner = True
            tracer = telemetry.get()
            if tracer is None:
                return
            from bigdl_tpu.telemetry.bridge import (RetraceBridge,
                                                    SummaryBridge)

            self._tele_retrace = RetraceBridge(tracer).install()
            if self._train_summary is not None:
                self._tele_summary_sink = SummaryBridge(self._train_summary)
                tracer.add_sink(self._tele_summary_sink)
        except Exception as e:  # noqa: BLE001 - observers never kill the run
            log.warning(f"[Telemetry] disabled for this run "
                        f"({type(e).__name__}: {e})")
            try:
                self._telemetry_end()
            except Exception:  # noqa: BLE001
                pass

    def _telemetry_end(self):
        tracer = telemetry.get()
        if self._tele_retrace is not None:
            self._tele_retrace.remove()
            self._tele_retrace = None
        if tracer is not None and self._tele_summary_sink is not None:
            tracer.remove_sink(self._tele_summary_sink)
            self._tele_summary_sink = None
        if self._tele_owner:
            telemetry.end_run()
            self._tele_owner = False
            log.info(f"[Telemetry] run log: {telemetry.last_run_path()} "
                     f"(inspect: python -m bigdl_tpu.telemetry <log>)")

    def optimize(self):
        cfg = get_config()
        # two device clients on one chip deadlock in claim — detect the
        # second driver up front (Engine.checkSingleton parity,
        # DistriOptimizer.scala:543-554)
        Engine.check_singleton()
        retry_times = cfg.failure_retry_times
        retry_window = cfg.failure_retry_interval
        failures: List[float] = []
        # a bad BIGDL_HEALTH / halt_after / BIGDL_FAULTS / BIGDL_RESUME
        # is a CONFIG error — surface it here, before the retry loop, or
        # it would be retried to budget exhaustion as if it were a
        # transient training failure
        self._resolve_health_policy()
        _faults.get_plan()
        if cfg.resume not in _RESUME_ON | _RESUME_OFF:
            raise ValueError(
                f"BIGDL_RESUME={cfg.resume!r}: want auto/on or off "
                f"(falsy spellings 0/false/no also read as off)")
        self._init_checkpoint_dir()
        self._telemetry_begin(cfg)
        # cluster fault tolerance (parallel/cluster.py): peer heartbeat
        # + collective watchdog + commit barrier, active only when
        # BIGDL_CLUSTER_DIR is set on a multi-process run
        _cluster.activate()
        self.preempted = False
        # graceful SIGTERM/SIGINT: finish the step, commit a final
        # checkpoint, return — the TPU-slice preemption contract
        self._preempt = _PreemptGuard().install()
        _LIVE_CKPT_WRITERS.add(self)
        # explicit clean-exit flag for the final heartbeat status:
        # sys.exc_info() in the finally would also see an exception a
        # CALLER is currently handling (optimize() invoked from inside
        # an except block) and misreport a clean run as failed
        self._run_completed = False
        try:
            self._maybe_resume()
            while True:
                try:
                    result = self._optimize_once()
                    self._run_completed = True
                    return result
                except KeyboardInterrupt:
                    self._flight_dump("keyboard_interrupt")
                    raise
                except HealthError as e:
                    # a policy halt is a VERDICT, not a failure — the
                    # model is diverged and a checkpoint restore would
                    # just replay the divergence; never burn the retry
                    # budget on it.  The flight recorder dumps the final
                    # steps' events + the halting evidence for the
                    # postmortem.
                    self._flight_dump("health_halt", e.evidence)
                    raise
                except MemoryExhaustedError:
                    # OOM is deterministic for a fixed program: a
                    # checkpoint restore replays the same allocation
                    # and dies again, so burning the retry budget on it
                    # only delays the verdict.  The evidence (largest
                    # buffers, categories, live-vs-limit) was flight-
                    # dumped at the raise site (telemetry/memory.py).
                    raise
                except TopologyMismatchError:
                    # likewise deterministic: the checkpoint cannot
                    # restore onto this mesh, and a retry replays the
                    # same verdict — surface it (pick a restorable
                    # width) instead of burning the budget
                    raise
                except Exception as e:  # noqa: BLE001 — retry loop parity
                    now = time.time()
                    failures = [t for t in failures if now - t < retry_window] + [now]
                    backoff = self._retry_backoff(len(failures))
                    telemetry.instant("run/retry", error=type(e).__name__,
                                      message=str(e)[:200],
                                      attempt=len(failures),
                                      budget=retry_times,
                                      backoff_s=round(backoff, 3))
                    if isinstance(e, StragglerTimeout):
                        # each firing gets its own dump: the ring holds
                        # the steps LEADING INTO the stall, which a
                        # post-restore log can no longer show
                        self._flight_dump("straggler_timeout")
                    if len(failures) > retry_times:
                        log.error(f"retry budget exhausted ({retry_times} in {retry_window}s)")
                        self._flight_dump(
                            f"retry_exhausted:{type(e).__name__}")
                        raise
                    log.warning(f"training failed with {type(e).__name__}: {e}; "
                                f"retry {len(failures)}/{retry_times} "
                                f"after {backoff:.2f}s backoff")
                    if backoff > 0:
                        # wait on the preempt guard's event, not a bare
                        # sleep: a SIGTERM landing mid-backoff must reach
                        # the grace path NOW, not after the full sleep
                        self._preempt.requested.wait(backoff)
                    if self._preempt.requested.is_set():
                        # preempted between attempts: there is no
                        # in-flight step to finish — join any pending
                        # write and exit clean; the last committed
                        # checkpoint is the resume point
                        self._join_checkpoint_write()
                        self.preempted = True
                        telemetry.instant(
                            "run/preempted",
                            step=self.state.get("neval", 0),
                            epoch=self.state.get("epoch", 1),
                            signum=self._preempt.signum or 0)
                        log.warning(
                            "[Preempt] preemption during retry backoff: "
                            "exiting with the last committed checkpoint "
                            "as the resume point")
                        return self.model
                    if not self._restore_latest():
                        log.warning("no checkpoint to restore; restarting from current weights")
        finally:
            self._preempt.uninstall()
            try:  # an in-flight async write must not be abandoned by an
                # exception unwinding past the happy path's join
                self._join_checkpoint_write()
            except Exception:  # noqa: BLE001 - never mask the real error
                pass
            # final heartbeat status AFTER the write join (the barrier
            # ack rides the write tail): peers read done/preempted as a
            # clean exit, failed as an immediate peer loss
            _cluster.deactivate(
                "preempted" if getattr(self, "preempted", False)
                else ("done" if getattr(self, "_run_completed", False)
                      else "failed"))
            self._telemetry_end()

    def _retry_backoff(self, attempt: int) -> float:
        """Exponential backoff with jitter between restore attempts
        (``BIGDL_RETRY_BACKOFF`` base seconds, cap 30s): a persistently
        failing step must not hot-loop through the retry budget in
        milliseconds.  Jitter desynchronizes a fleet of workers retrying
        the same shared-storage restore.  One shared policy with the
        cluster Supervisor (``utils.config.retry_backoff_s``)."""
        from bigdl_tpu.utils.config import retry_backoff_s

        return retry_backoff_s(attempt)

    def _flight_dump(self, reason: str, evidence: Optional[Dict] = None):
        """Dump the flight recorder (telemetry/flight.py) on the way out
        of a dying run — called BEFORE _telemetry_end so the recorder is
        still attached.  Never raises: the run is already dying."""
        recorder = telemetry.flight_recorder()
        if recorder is None:
            return
        try:
            path = recorder.dump(reason, evidence)
            if path:
                log.info(f"[Flight] recorder dumped to {path}")
        except Exception:  # noqa: BLE001 - a dying run must not die harder
            pass

    def _resolve_health_policy(self) -> Optional[HealthPolicy]:
        policy = self._health_policy
        if policy is None:
            policy = HealthPolicy.from_config(get_config())
        if policy is not None and not policy.enabled:
            return None
        # fresh state per run ATTEMPT: a checkpoint restore rewinds the
        # steps the old counters/EWMA were built on
        return policy.fresh() if policy is not None else None

    def _optimize_once(self):
        mesh = self._mesh
        health = self._resolve_health_policy()
        fault_plan = _faults.get_plan()
        step = TrainStep(
            self.model, self.criterion, self.optim_method, mesh=mesh,
            parameter_sync=self.parameter_sync,
            gradient_compression=self.gradient_compression,
            compute_dtype=self.compute_dtype,
            gradient_clipping=self._grad_clip, max_norm=self._grad_clip_norm,
            health_probe=health is not None,
            skip_nonfinite=health is not None and health.skip_nonfinite,
            grad_fault=fault_plan.has("nan_grads"))
        # exposed for tests/tools that need the compiled-step view of
        # the run just performed (e.g. sparse-sync engagement evidence)
        self.last_train_step = step
        # resume functional optimizer state if the method carries it
        if "func_state" in self.optim_method.state:
            restored = jax.tree.map(np.asarray, self.optim_method.state["func_state"])
            step.opt_state = jax.tree.map(
                lambda a, b: jax.device_put(np.asarray(a), b.sharding) if mesh is not None else jax.numpy.asarray(np.asarray(a)),
                restored, step.opt_state)
        if self._pending_sharded_restore is not None:
            from bigdl_tpu.utils.sharded_ckpt import restore_train_step

            extra = restore_train_step(step, self._pending_sharded_restore)
            self._pending_sharded_restore = None
            self._apply_driver_state(extra.get("driver_state", {}))
            step.sync_to_model()
        from bigdl_tpu.dataset.dataset import DistributedDataSet
        from bigdl_tpu.parallel.mesh import mesh_process_count

        # multi-host validation runs process-locally: a pure data-parallel
        # forward needs no collectives, so each process evaluates the full
        # validation set and reaches identical results
        multihost = mesh_process_count(mesh) > 1
        eval_step = EvalStep(self.model, mesh=None if multihost else mesh)
        if isinstance(self.dataset, DistributedDataSet):
            # epoch accounting is GLOBAL so every process flips the epoch
            # on the same iteration (schedules must stay SPMD-consistent)
            dataset_size = self.dataset.global_size()
            record_scale = self.dataset.num_shards
        else:
            dataset_size = self.dataset.size()
            record_scale = 1
        records_this_epoch = self.state.get("records", 0)
        # dataset position: every attempt (fresh resume OR retry-restore)
        # re-enters the CURRENT epoch's deterministic order and skips the
        # records already consumed — no replayed, no skipped batches
        # (before this, a restore replayed the epoch from its start)
        if hasattr(self.dataset, "set_position"):
            self.dataset.set_position(self.state.get("epoch", 1) - 1)
        data_iter = self.dataset.data(train=True)
        data_iter = fault_plan.wrap_data_iter(data_iter)
        if records_this_epoch > 0:
            data_iter = self._fast_forward(data_iter, records_this_epoch,
                                           record_scale)
        # the step-key seed persists in the driver state: every resume /
        # retry attempt folds the SAME base key by iteration number, so
        # stochastic layers replay the interrupted trajectory instead of
        # forking it.  The draw happens BEFORE the prefetch thread starts
        # pulling batches through (possibly random) transforms, so the
        # shared host RNG sees the same draw order as the synchronous path
        if "key0_seed" not in self.state:
            self.state["key0_seed"] = int(RNG.randint(0, 2**31 - 1))
        key0 = jax.random.key(self.state["key0_seed"])
        # async input: transform + h2d run ahead of the device step on a
        # host thread (BIGDL_PREFETCH=0 restores the synchronous path)
        prefetch_depth = get_config().prefetch_batches
        prefetcher = _BatchPrefetcher(
            data_iter, step._shard_batch, prefetch_depth, self.metrics) \
            if prefetch_depth > 0 else None
        epoch_start = time.perf_counter()

        # on-demand profiler (telemetry/profiler.py): the loop polls one
        # process-wide control each iteration, so a capture can be armed
        # at ANY step — POST /profile on the live endpoint, the health
        # policy's escalation hook, or BIGDL_PROFILE, which now merely
        # pre-arms the same control with the first N iterations
        cfg = get_config()
        from bigdl_tpu.telemetry import profiler as _profiler

        profile_ctl = _profiler.get()
        if cfg.profile_dir and cfg.profile_iters > 0:
            profile_ctl.arm(cfg.profile_iters, cfg.profile_dir,
                            source="startup")
        # BIGDL_PROFILE_ON_HEALTH is one-shot PER RUN ATTEMPT: without
        # this latch a chronic warn-level finding would re-arm after
        # every completed capture and keep the profiler on for the rest
        # of the (sick, already slow) run
        self._health_profile_armed = False
        first_iteration = True

        log.info(f"[Optimizer] start training to {mesh} "
                 f"(sync={self.parameter_sync}, compression={self.gradient_compression})")
        tele = telemetry.get()
        tele_base = tele.depth() if tele else 0
        cluster_svc = _cluster.get()
        local_sync = None
        if self.parameter_sync == "local":
            from bigdl_tpu.parallel.local_sync import LocalSyncDriver

            local_sync = LocalSyncDriver(step, cluster=cluster_svc)
        try:
            while not self.end_when(self.state):
                # peer heartbeat FIRST (parallel/cluster.py): a fault
                # killing this process mid-iteration must leave the
                # step-started beat behind for the peers' watchdogs
                if cluster_svc is not None:
                    cluster_svc.beat(self.state["neval"] + 1)
                # fault plan, iteration point: crash raises into the
                # retry loop, kill_worker/preempt signal this process,
                # wedge stalls INSIDE the straggler-guarded region below
                wedge = fault_plan.poll_iteration(self.state["neval"] + 1)
                profile_ctl.poll_begin()
                t_start = time.perf_counter()
                it_sid = tele.begin("train/iteration",
                                    step=self.state["neval"] + 1) \
                    if tele else None
                dw_sid = tele.begin("data_wait") if tele else None
                if prefetcher is not None:
                    item = prefetcher.next()
                    if item is None:
                        if tele:
                            tele.end(dw_sid)
                            tele.end(it_sid)
                        break  # iterator exhausted (finite feeds)
                    batch_n, placed = item
                else:
                    batch: MiniBatch = next(data_iter)
                    batch_n, placed = batch.size(), None
                if tele:
                    tele.end(dw_sid)
                t_data = time.perf_counter()
                key = jax.random.fold_in(key0, self.state["neval"])

                def one_iteration():
                    th0 = time.perf_counter()
                    if wedge is not None:  # injected stall: the
                        # watchdog, not the iteration, must end this
                        fault_plan.wedge_stall()
                    if placed is not None:
                        xs, ys = placed  # h2d already done by the prefetcher
                    else:
                        xs, ys = step._shard_batch(batch.get_input(),
                                                   batch.get_target())
                    t0 = time.perf_counter()
                    if step.grad_fault:
                        out = step.run_sharded(
                            xs, ys, key, grad_scale=fault_plan.grad_scale(
                                self.state["neval"] + 1))
                    else:  # kwarg omitted: keeps stubbed/run-compatible
                        # run_sharded signatures working unchanged
                        out = step.run_sharded(xs, ys, key)
                    t1 = time.perf_counter()
                    out = float(out)  # device sync: the step actually runs
                    t2 = time.perf_counter()
                    # timings are recorded by the CALLER so an abandoned
                    # straggler thread can't pollute Metrics
                    return out, (t0 - th0, t1 - t0, t2 - t0)

                # the first iteration includes XLA compilation — never
                # under the straggler budget (docs/straggler.md).  An
                # injected wedge is the one exception: unguarded it
                # would stall the driver for the full stall instead of
                # exercising the watchdog it exists to test.
                if first_iteration and wedge is None:
                    loss, stage_times = one_iteration()
                else:
                    loss, stage_times = \
                        self._run_with_straggler_guard(one_iteration)
                h2d_s, dispatch_s, sync_s = stage_times
                if prefetcher is None:  # else the worker thread records it
                    self.metrics.add("host to device time", h2d_s)
                self.metrics.add("dispatch time", dispatch_s)
                self.metrics.add("compile + first iteration time" if
                                 first_iteration else "computing time",
                                 sync_s)
                first_iteration = False
                t_end = time.perf_counter()
                profile_ctl.poll_end()
                n = batch_n * record_scale  # global records this iteration
                self.state["neval"] += 1
                self.state["loss"] = loss
                if cluster_svc is not None:
                    # step COMPLETED: refresh the heartbeat and arm the
                    # watchdog (the first completed step ends the
                    # compile exemption)
                    cluster_svc.beat(self.state["neval"], done=True)
                if local_sync is not None:
                    # every H steps: average the islands under the
                    # bounded-staleness barrier — may SHED a peer stuck
                    # ≥ S rounds behind, or exit this process (43) if
                    # the survivors shed US (parallel/local_sync.py)
                    local_sync.on_step(self.state["neval"])
                records_this_epoch += n
                self.state["records"] = records_this_epoch
                self.metrics.add("data time", t_data - t_start)
                self._iteration_times.append(t_end - t_data)
                throughput = n / max(t_end - t_start, 1e-9)
                if tele:
                    tele.emit("step", step=self.state["neval"],
                              dur=t_end - t_start, loss=loss, records=n,
                              throughput=throughput,
                              epoch=self.state["epoch"])
                    self._emit_layer_counters(tele, step)
                if health is not None:
                    # may raise HealthError (never retried — see
                    # optimize()); the probe values are already
                    # materialized by the loss sync above, so this is a
                    # 5-float d2h copy, not a device round-trip
                    self._health_observe(health, step, loss)
                log.info(
                    f"[Epoch {self.state['epoch']} {records_this_epoch}/{dataset_size}]"
                    f"[Iteration {self.state['neval']}] Trained {n} records in "
                    f"{t_end - t_start:.4f} seconds. Throughput is {throughput:.1f} "
                    f"records/second. Loss is {loss:.5f}.")
                self.state["_epoch_boundary"] = False
                if records_this_epoch >= dataset_size:
                    self.state["epoch"] += 1
                    # expose the epoch to compiled schedules
                    step.opt_state = dict(step.opt_state)
                    epoch = jax.numpy.asarray(self.state["epoch"],
                                              jax.numpy.int32)
                    prev = step.opt_state["epoch"]
                    if mesh is not None and prev.ndim == 0:
                        # on a mesh, placed like the scalar it replaces:
                        # a plain asarray lands on the first device only,
                        # and the changed input sharding recompiles the
                        # step (local mode restacks its island axis, and
                        # off a mesh the state stays uncommitted)
                        epoch = jax.device_put(epoch, prev.sharding)
                    step.opt_state["epoch"] = epoch
                    records_this_epoch = 0
                    self.state["records"] = 0
                    self.state["_epoch_boundary"] = True
                    log.info(f"[Epoch {self.state['epoch'] - 1}] finished in "
                             f"{time.perf_counter() - epoch_start:.2f}s")
                    if tele:
                        tele.instant("epoch", epoch=self.state["epoch"] - 1,
                                     dur=time.perf_counter() - epoch_start)
                    epoch_start = time.perf_counter()
                if self._train_summary is not None:
                    ts = self._train_summary
                    # default: scalars on, Parameters histograms opt-in
                    # (TrainSummary.scala:64-88)
                    gate = getattr(ts, "should_write",
                                   lambda tag, st: tag != "Parameters")
                    if gate("Loss", self.state):
                        ts.add_scalar("Loss", loss, self.state["neval"])
                    if gate("Throughput", self.state):
                        ts.add_scalar("Throughput", throughput, self.state["neval"])
                    if gate("LearningRate", self.state):
                        lr = self.optim_method.get_learning_rate()
                        ts.add_scalar("LearningRate", lr, self.state["neval"])
                    if gate("Parameters", self.state) and hasattr(ts, "add_histogram"):
                        # fsdp/TP params are cross-process-sharded on a
                        # multi-host mesh: gather before np.asarray
                        gathered = step.gather_replicated(step.params)
                        for pname, arr in gathered.items():
                            ts.add_histogram(pname, np.asarray(arr),
                                             self.state["neval"])
                if self._val_trigger is not None and self._val_trigger(self.state):
                    with self.metrics.timer("validation time"), \
                            telemetry.span("validation"):
                        step.sync_to_model()
                        self._validate(eval_step)
                    if cluster_svc is not None:
                        # beat BETWEEN validation and checkpoint: the
                        # silent window peers must tolerate is one
                        # activity, never the two summed
                        cluster_svc.beat(self.state["neval"], done=True)
                ckpt_fired = self._ckpt_trigger is not None \
                    and self._ckpt_trigger(self.state)
                if ckpt_fired:
                    with self.metrics.timer("checkpoint time"), \
                            telemetry.span("checkpoint"):
                        self._save_checkpoint(step)
                if cluster_svc is not None:
                    # refresh after the (possibly slow) checkpoint too
                    cluster_svc.beat(self.state["neval"], done=True)
                preempt = getattr(self, "_preempt", None)
                if preempt is not None and preempt.requested.is_set():
                    # graceful preemption: the in-flight step finished
                    # above; commit a final checkpoint carrying the
                    # dataset/epoch position + RNG state (unless the
                    # trigger just saved this very step), mark the run,
                    # and return 0-exit clean — a fresh process resumes
                    # from here mid-epoch
                    if self._ckpt_path is not None and not ckpt_fired:
                        with self.metrics.timer("checkpoint time"), \
                                telemetry.span("checkpoint"):
                            self._save_checkpoint(step)
                    self._join_checkpoint_write()
                    self.preempted = True
                    telemetry.instant("run/preempted",
                                      step=self.state["neval"],
                                      epoch=self.state["epoch"],
                                      signum=preempt.signum or 0)
                    log.warning(
                        f"[Preempt] run preempted at iteration "
                        f"{self.state['neval']} (epoch "
                        f"{self.state['epoch']}); final checkpoint "
                        f"committed — a fresh optimize() resumes here")
                    if tele:
                        tele.end(it_sid)
                    break
                if tele:
                    tele.end(it_sid)
        except BaseException:
            if tele:
                # close the spans the exception left open in THIS scope
                # (marked abandoned) — begin/end pairing is an invariant
                # of the log, not of the happy path; spans the CALLER
                # opened around optimize() stay theirs to close
                tele.unwind(to_depth=tele_base)
            # the compiled step DONATES param/opt buffers, so the module
            # tree's original arrays are already deleted after the first
            # iteration — write the last-completed-iteration params back
            # before the retry loop rebuilds a TrainStep from the model
            # ("restart from current weights" must mean CURRENT)
            try:
                step.sync_to_model()
            except Exception:
                log.warning("could not sync params to model after failure")
            raise
        finally:
            if prefetcher is not None:
                prefetcher.close()
            # an in-flight capture is closed (valid trace), a merely
            # armed one cancelled — the control is reusable next run
            profile_ctl.abort()
        if local_sync is not None:
            # the run's final params are the ISLAND MEAN, not whatever
            # island this process happened to train last
            local_sync.finalize(self.state["neval"])
        step.sync_to_model()
        self._join_checkpoint_write()  # run ends with all writes landed
        log.info(self.metrics.summary())
        last = _CounterLines()
        self._emit_layer_counters(last, step)
        for line in last.lines():
            log.info(line)
        return self.model

    # -- training health (docs/observability.md) ----------------------------
    def _emit_layer_counters(self, tele, step):
        """Counters a layer keeps in its buffers (a routed layer's load
        per held expert), read where the loss has just reached the host:
        the step that wrote them is complete, so this is a copy of a
        few numbers and no wait.  ``tele`` is the open telemetry run
        (every step) or, once at the end of ``optimize()``, the
        :class:`_CounterLines` that puts the last step's into the log."""
        if not hasattr(self, "_counting_layers"):
            self._counting_layers = [
                (path, m) for path, m in self.model.named_modules()
                if hasattr(m, "step_counters")]
        for path, m in self._counting_layers:
            prefix = path + "." if path else ""
            own = {leaf: step.buffers[prefix + leaf]
                   for leaf in m.__dict__["_buffers"]}
            m.step_counters(own, tele, path)

    def _health_observe(self, policy: HealthPolicy, step: TrainStep,
                        loss: float) -> None:
        """Fold this iteration's in-graph probe into the policy: emit the
        typed ``health`` event + finding instants, mirror the probe into
        TrainSummary scalars, log warnings, and raise
        :class:`HealthError` when the halt predicate fires."""
        if step.last_health is None:
            return
        n = self.state["neval"]
        try:
            stats = probe_stats(np.asarray(step.last_health), loss)
        except Exception as e:  # noqa: BLE001 - a probe fetch must not
            # kill a healthy run; the step itself already succeeded
            log.warning(f"[Health] probe fetch failed at step {n} "
                        f"({type(e).__name__}: {e})")
            return
        telemetry.emit("health", step=n, **stats)
        action, findings = policy.observe(n, stats)
        for name, attrs in findings:
            telemetry.instant(name, **attrs)
        ts = self._train_summary
        if ts is not None:
            gate = getattr(ts, "should_write",
                           lambda tag, st: tag != "Parameters")
            if gate("Health", self.state):
                for key in ("grad_norm", "update_ratio",
                            "nonfinite_grads", "nonfinite_params"):
                    ts.add_scalar(f"health/{key}", stats[key], n)
        if action == "ok":
            return
        # BIGDL_PROFILE_ON_HEALTH=<dir>: the FIRST escalation arms a
        # one-shot profiler capture so the NEXT step — the divergence
        # itself, not a healthy step hours earlier — gets traced.
        # Latched per run attempt: later findings never re-arm.
        on_health = get_config().profile_on_health
        if on_health and action != "halt" \
                and not getattr(self, "_health_profile_armed", True):
            from bigdl_tpu.telemetry import profiler as _profiler

            ctl = _profiler.get()
            base = None if on_health.lower() in ("1", "true", "on", "yes") \
                else on_health
            if ctl.arm(1, ctl.default_dir(base), source="health"):
                self._health_profile_armed = True
        names = ", ".join(name for name, _ in findings)
        log.warning(f"[Health] step {n}: {names} "
                    f"(loss={stats['loss']:.4g}, "
                    f"grad_norm={stats['grad_norm']:.4g}, "
                    f"update_ratio={stats['update_ratio']:.4g})")
        if action == "halt":
            consec = policy.state["consecutive_nonfinite"]
            reason = (f"{consec} consecutive nonfinite step(s)" if consec
                      else "halt_when trigger fired")
            raise HealthError(n, reason, policy.evidence(n, stats))

    # -- straggler guard (docs/straggler.md) --------------------------------
    def _straggler_timeout(self) -> Optional[float]:
        """Current per-iteration budget in seconds, or None when disabled.
        ``BIGDL_ITERATION_TIMEOUT``: unset/"0" = off, a float = fixed
        budget, "auto" = 10x the median of recent iterations (min 60 s,
        armed after 5 samples) — the host-level analogue of the
        reference's kth-largest adaptive threshold
        (``DistriOptimizer.scala:339-367``, ``Util.kthLargest``)."""
        spec = get_config().iteration_timeout
        if not spec or spec == "0":
            return None
        if spec == "auto":
            if len(self._iteration_times) < 5:
                return None
            med = sorted(self._iteration_times)[len(self._iteration_times) // 2]
            return max(60.0, 10.0 * med)
        return float(spec)

    def _run_with_straggler_guard(self, fn):
        timeout = self._straggler_timeout()
        if timeout is None:
            return fn()
        import queue
        import threading

        results: "queue.Queue" = queue.Queue(maxsize=1)

        def runner():
            try:
                results.put(("ok", fn()))
            except BaseException as e:  # noqa: BLE001 — relayed to caller
                results.put(("err", e))

        # daemon: an abandoned thread blocked on a wedged device call must
        # not stall interpreter exit (concurrent.futures workers would)
        threading.Thread(target=runner, daemon=True,
                         name="bigdl-iteration").start()
        try:
            kind, value = results.get(timeout=timeout)
        except queue.Empty:
            # the dispatch thread stays blocked on the device; recovery
            # re-initializes from the last checkpoint (the only safe move
            # on a synchronous SPMD step — see docs/straggler.md).  The
            # firing lands in the telemetry timeline alongside the steps
            # it interrupted, not just in the logger stream.
            telemetry.instant("straggler/timeout", budget_s=timeout,
                              step=self.state["neval"] + 1)
            raise StragglerTimeout(
                f"iteration exceeded the straggler budget of {timeout:.1f}s "
                f"(BIGDL_ITERATION_TIMEOUT)") from None
        if kind == "err":
            raise value
        return value


class LocalOptimizer(Optimizer):
    """Single-chip training (``optim/LocalOptimizer.scala``)."""

    def __init__(self, model, dataset, criterion, batch_size: Optional[int] = None,
                 end_trigger: Optional[Trigger] = None, *,
                 optim_method: Optional[OptimMethod] = None):
        super().__init__(model, dataset, criterion, batch_size, end_trigger,
                         optim_method=optim_method)
        self._mesh = None


class DistriOptimizer(Optimizer):
    """Mesh-parallel training (``optim/DistriOptimizer.scala``): batch
    sharded over the data axis, gradient aggregation + (optionally ZeRO-1
    sharded) update inside the compiled step."""

    def __init__(self, model, dataset, criterion, batch_size: Optional[int] = None,
                 end_trigger: Optional[Trigger] = None, *, mesh=None,
                 optim_method: Optional[OptimMethod] = None):
        # mesh/optim_method keyword-only: positional slot 6 would differ
        # between the two interchangeable Optimizer classes
        super().__init__(model, dataset, criterion, batch_size, end_trigger,
                         optim_method=optim_method)
        self._mesh = mesh if mesh is not None else Engine.mesh
