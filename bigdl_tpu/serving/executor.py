"""Per-bucket AOT executables: the serving compile cache.

One :class:`BucketedExecutor` owns a model's inference executables —
one ``jax.jit(fwd).lower(state, spec).compile()`` per (batch-bucket,
seq-bucket) shape.  ``warmup()`` compiles the whole bucket set at
startup (``serve/warmup`` span, one ``compile`` event per bucket named
``ServeExecutor.warmup``), so first-request latency is a dispatch;
a compile that happens INSIDE the request path instead is emitted as
``ServeExecutor.compile`` — in a healthy server that name never appears
after startup, and ``telemetry diff`` gates on the compile count.

The executor is also the batch ``Predictor``'s compiled step
(``optim/predictor.py``): :func:`executor_for` keeps one executor per
live (model, mesh) pair, so offline scoring and online serving share
one compile cache — the fix for ``LocalPredictor.predict`` rebuilding
(and re-jitting) a fresh ``EvalStep`` on every call.

Retrace-detector integration mirrors TrainStep/EvalStep: every dispatch
reports through ``analysis.hooks`` under a per-bucket kind
(``ServeExecutor.run[b8]``), so within a bucket the signature is
constant by construction and ``trace_retraces`` stays clean over any
arrival-size mix — the test contract for "zero steady-state recompiles".
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from bigdl_tpu import telemetry as _telemetry
from bigdl_tpu.analysis import hooks as _hooks
from bigdl_tpu.serving.buckets import BucketPolicy

__all__ = ["BucketedExecutor", "executor_for", "default_policy"]


def _mesh_batch_div(mesh) -> int:
    """Rows every bucket must divide into on this mesh (1 off-mesh)."""
    if mesh is None:
        return 1
    from bigdl_tpu.parallel.mesh import DATA_AXIS

    return max(1, mesh.shape.get(DATA_AXIS, 1))


def default_policy(max_batch: int = 32, mesh=None) -> BucketPolicy:
    """The default bucket set, ALIGNED to the mesh batch axis: plain
    pow2 buckets off-mesh; on an N-way data mesh, multiples N, 2N, 4N
    ... (a bucket of 1 cannot shard over 2 devices)."""
    n = _mesh_batch_div(mesh)
    if n <= 1:
        return BucketPolicy(max_batch=max_batch)
    buckets, b = [], n
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max(max_batch, n))
    if buckets[-1] % n:
        buckets[-1] += n - buckets[-1] % n  # round up onto the mesh
    return BucketPolicy(max_batch=buckets[-1], batch_buckets=buckets)


class BucketedExecutor:
    """AOT-compiled, shape-bucketed inference over one model.

    ``seq_axis`` (models whose axis 1 is a padded time axis) enables
    sequence bucketing via ``policy.seq_buckets``; inputs longer than
    the largest bucket truncate.  ``compute_dtype`` mirrors EvalStep
    (e.g. ``jnp.bfloat16`` fwd with f32 params); quantized models pass
    None — the int8 path owns its dtypes.
    """

    def __init__(self, model, mesh=None, policy: Optional[BucketPolicy] = None,
                 compute_dtype=None, seq_axis: Optional[int] = None):
        from bigdl_tpu.nn.module import stamp_scope_names
        from bigdl_tpu.utils.config import get_config

        stamp_scope_names(model, enabled=get_config().module_scopes)
        self.model = model
        self.mesh = mesh
        self.policy = policy or default_policy(mesh=mesh)
        self.compute_dtype = compute_dtype
        self.seq_axis = seq_axis
        self.compile_count = 0
        self.warmup_s = 0.0
        self._fwd = self._make_fwd()
        self._exec: Dict[Tuple[int, Optional[int]], Any] = {}
        # per-bucket executable memory_analysis (recorded at compile
        # time): the resident-executable HBM the KV-cache budgeting
        # work (ROADMAP item 2) subtracts from the device budget
        self.bucket_memory: Dict[Tuple[int, Optional[int]],
                                 Dict[str, int]] = {}
        self._state = None        # device-placed {path: array}
        self._state_src = None    # host-side identity snapshot
        self._state_sig = None    # {path: (shape, dtype)} of the trace
        self._lock = threading.RLock()
        if mesh is not None:
            bad = [b for b in self.policy.batch_buckets
                   if not self._divisible(b)]
            if bad:
                raise ValueError(
                    f"batch buckets {bad} not divisible by the mesh "
                    f"batch axis — pick buckets that shard evenly")

    def _divisible(self, b: int) -> bool:
        from bigdl_tpu.parallel.mesh import DATA_AXIS

        n = self.mesh.shape.get(DATA_AXIS, 1)
        return b % n == 0

    def _make_fwd(self):
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.nn.module import functional_call

        model, cdt = self.model, self.compute_dtype

        def fwd(state, x):
            if cdt is not None:
                state = {k: (v.astype(cdt)
                             if jnp.issubdtype(v.dtype, jnp.floating) else v)
                         for k, v in state.items()}
            out, _ = functional_call(model, state, x, training=False)
            if cdt is not None:
                out = jax.tree.map(
                    lambda a: a.astype(jnp.float32)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, out)
            return out

        return fwd

    # -- state -------------------------------------------------------------
    def refresh_state(self) -> None:
        """Re-read the module tree's params/buffers onto the device.
        Identity-checked: unchanged arrays cost a dict walk, not a
        transfer.  A shape/dtype change (e.g. the model was re-built)
        drops the compiled executables — same-shape weight updates
        (training between predicts) keep every warm executable."""
        from bigdl_tpu.nn.module import state_dict

        host = state_dict(self.model)
        with self._lock:
            if self._state_src is not None \
                    and len(host) == len(self._state_src) \
                    and all(self._state_src.get(k) is v
                            for k, v in host.items()):
                return
            self._place_state(host)

    def _place_state(self, host) -> None:
        import jax
        import jax.numpy as jnp

        sig = {k: (tuple(np.shape(v)), str(getattr(v, "dtype", "?")))
               for k, v in host.items()}
        if self.mesh is not None:
            from bigdl_tpu.parallel.mesh import replicated

            state = {k: jax.device_put(jnp.asarray(v),
                                       replicated(self.mesh))
                     for k, v in host.items()}
        else:
            state = {k: jnp.asarray(v) for k, v in host.items()}
        if self._state_sig is not None and sig != self._state_sig:
            self._exec.clear()  # stale traces: the avals changed
        self._state_src = dict(host)
        self._state_sig = sig
        self._state = state

    # -- compiling ---------------------------------------------------------
    def _input_spec(self, key, sample_shape: Tuple[int, ...], dtype):
        import jax

        bb, sb = key
        shape = (bb,) + tuple(sample_shape)
        if sb is not None and len(shape) >= 2:
            shape = (bb, sb) + tuple(shape[2:])
        return jax.ShapeDtypeStruct(shape, dtype)

    def _compile(self, key, spec, name: str):
        import jax

        t0 = time.perf_counter()
        fn = jax.jit(self._fwd)
        if self.mesh is not None:
            from bigdl_tpu.parallel.mesh import data_sharding

            sharding = data_sharding(self.mesh, len(spec.shape))
            spec = jax.ShapeDtypeStruct(spec.shape, spec.dtype,
                                        sharding=sharding)
        try:
            compiled = fn.lower(self._state, spec).compile()
        except Exception as e:  # noqa: BLE001 - OOM forensics only
            self._maybe_raise_oom(e, f"ServeExecutor.compile{list(key)}")
            raise
        self._exec[key] = compiled
        self.compile_count += 1
        try:
            from bigdl_tpu.telemetry.device import memory_facts

            mf = memory_facts(compiled)
            if mf:
                self.bucket_memory[key] = mf
        except Exception:  # noqa: BLE001 - accounting is an observer
            pass
        dur = time.perf_counter() - t0
        tracer = _telemetry.get()
        if tracer is not None:
            tracer.emit("compile", name=name, dur=dur,
                        bucket=list(k for k in key if k is not None),
                        cache_size=len(self._exec))
        return compiled

    def _maybe_raise_oom(self, exc: Exception, context: str) -> None:
        """RESOURCE_EXHAUSTED from a serving compile or dispatch gets
        the same enriched postmortem the train path raises
        (telemetry/memory.py): largest resident buffers, categories,
        live-vs-limit, flight-dumped before the re-raise."""
        from bigdl_tpu.telemetry import memory as _tmem

        if not _tmem.is_oom(exc):
            return
        trees = {"state": self._state if self._state is not None else {}}
        summary = self.memory_summary()
        context = (f"{context} (resident executables: "
                   f"{len(self.bucket_memory)} buckets, "
                   f"{summary['resident_bytes']} bytes incl. state)")
        _tmem.raise_oom(exc, trees, context=context)

    def memory_summary(self) -> Dict[str, Any]:
        """Resident-executable HBM: per-device state (weights) bytes +
        the per-bucket executable breakdown.  ``resident_bytes`` =
        state + generated code + the LARGEST bucket temp (buckets run
        one at a time — their scratch is not additive; code is)."""
        from bigdl_tpu.telemetry.memory import _leaf_device_bytes

        with self._lock:
            state_bytes = sum(_leaf_device_bytes(v) for v in
                              (self._state or {}).values())
            buckets = {}
            peak_temp = code = 0
            for key, mf in sorted(self.bucket_memory.items(),
                                  key=lambda kv: (kv[0][0],
                                                  kv[0][1] or -1)):
                label = f"b{key[0]}" + (f"s{key[1]}"
                                        if key[1] is not None else "")
                buckets[label] = dict(mf)
                peak_temp = max(peak_temp, mf.get("temp_bytes", 0))
                code += mf.get("code_bytes", 0)
        return {"state_bytes": int(state_bytes),
                "code_bytes": int(code),
                "peak_temp_bytes": int(peak_temp),
                "resident_bytes": int(state_bytes + code + peak_temp),
                "buckets": buckets}

    def warmup(self, sample_shape: Tuple[int, ...], dtype) -> float:
        """AOT-compile every bucket in the policy for samples of
        ``sample_shape`` (feature shape, no batch axis).  Returns the
        wall seconds spent; idempotent per bucket."""
        # a warm RESTART'S warmup should load every bucket executable
        # from the persistent cache instead of recompiling the whole
        # set before the ready line (docs/compile.md: accelerator-only
        # unless BIGDL_COMPILE_CACHE opts plain CPU in, =0 opts out) —
        # the same managed cache the train step uses
        from bigdl_tpu.utils.engine import enable_compile_cache

        enable_compile_cache()
        t0 = time.perf_counter()
        self.refresh_state()
        with self._lock, _telemetry.span(
                "serve/warmup", buckets=len(self.policy.bucket_keys())):
            for key in self.policy.bucket_keys():
                if key not in self._exec:
                    spec = self._input_spec(key, sample_shape, dtype)
                    self._compile(key, spec, "ServeExecutor.warmup")
        self.warmup_s += time.perf_counter() - t0
        return self.warmup_s

    def warm_buckets(self):
        with self._lock:
            return sorted(self._exec,
                          key=lambda k: (k[0], k[1] if k[1] is not None
                                         else -1))

    def adopt_policy(self, policy: BucketPolicy,
                     seq_axis: Optional[int] = None) -> None:
        """Merge a caller's bucket requirements into the shared
        executor (the batch Predictor and a ModelServer over the same
        model keep ONE compile cache): batch buckets union, seq
        buckets/axis adopted when this executor had none.  Warm
        executables survive — the key set only grows."""
        with self._lock:
            self.policy.batch_buckets = tuple(sorted(
                set(self.policy.batch_buckets)
                | set(policy.batch_buckets)))
            self.policy.max_batch = self.policy.batch_buckets[-1]
            if policy.seq_buckets and not self.policy.seq_buckets:
                self.policy.seq_buckets = policy.seq_buckets
            if seq_axis is not None and self.seq_axis is None:
                self.seq_axis = seq_axis

    # -- dispatch ----------------------------------------------------------
    def bucket_of(self, x: np.ndarray) -> Tuple[int, Optional[int]]:
        x = np.asarray(x)
        n = x.shape[0]
        with self._lock:
            if n > self.policy.max_batch:
                # offline callers (Predictor at a larger batch_size)
                # grow the bucket set with the exact size — pow2 rounding
                # a steady full batch would waste real compute.  On a
                # mesh, round up onto the batch axis so the new bucket
                # still shards
                div = _mesh_batch_div(self.mesh)
                grown = n + (div - n % div) % div
                self.policy.batch_buckets = tuple(sorted(
                    set(self.policy.batch_buckets) | {grown}))
                self.policy.max_batch = grown
            bb = self.policy.batch_bucket(n)
        sb = None
        if self.seq_axis is not None and x.ndim >= 2:
            sb = self.policy.seq_bucket(x.shape[1])
        return bb, sb

    def run(self, x, record: Optional[Dict[str, Any]] = None) -> Any:
        """Pad ``[n, ...]`` onto its bucket, dispatch the warm
        executable (compiling it first if cold — emitted as the
        in-request-path ``ServeExecutor.compile``), slice the padding
        back off.  Returns the output pytree as numpy.

        ``record`` (request tracing, telemetry/request_trace.py): a dict
        the dispatch fills with its own split — bucket, padded rows,
        in-path ``compile_ms`` (zero on a warm bucket) and ``device_ms``
        — so the batcher can attribute each rider's wall time without
        re-deriving bucket selection."""
        import jax.numpy as jnp

        x = np.asarray(x)
        n = x.shape[0]
        key = self.bucket_of(x)
        padded = self.policy.pad(x, key[0], key[1])
        kind = f"ServeExecutor.run[b{key[0]}" \
               + (f"s{key[1]}]" if key[1] is not None else "]")
        if _hooks.hooks_active():
            _hooks.dispatch_event(self, kind, {"x": padded})
        compile_ms = 0.0
        with self._lock:
            if self._state is None:
                self.refresh_state()
            compiled = self._exec.get(key)
            if compiled is None:
                import jax

                t_c0 = time.perf_counter()
                spec = jax.ShapeDtypeStruct(padded.shape, padded.dtype)
                compiled = self._compile(key, spec, "ServeExecutor.compile")
                compile_ms = (time.perf_counter() - t_c0) * 1000.0
        xj = self._place_input(jnp.asarray(padded))
        t_d0 = time.perf_counter()
        try:
            out = compiled(self._state, xj)
        except Exception as e:  # noqa: BLE001 - OOM forensics only
            self._maybe_raise_oom(e, kind)
            raise
        if record is not None:
            import jax

            # dispatch is async: block before stamping device_ms so the
            # number is the compute, not the enqueue (the host-side
            # np.asarray conversion below would have blocked anyway)
            jax.block_until_ready(out)
            record.update(
                bucket=key[0], seq_bucket=key[1], rows=n,
                padded_rows=key[0] - n, compile_ms=round(compile_ms, 3),
                device_ms=round(
                    (time.perf_counter() - t_d0) * 1000.0, 3))
        if _hooks.hooks_active():
            # one executable per kind, forever — the detector sees a
            # constant signature AND a constant cache size per bucket
            _hooks.cache_event(self, kind, 1)
        import jax

        seq_in = x.shape[1] if (self.seq_axis is not None
                                and x.ndim >= 2) else None

        def host_rows(a):
            a = np.asarray(a)
            if key[0] == 1 and (a.ndim == 0 or a.shape[0] != 1):
                # Torch-legacy batch-1 ambiguity: Reshape's auto-detect
                # (Reshape.scala:61-63 semantics) treats a [1, ...]
                # input as UNBATCHED, so the bucket-1 executable's
                # output lost its batch axis — restore it so callers
                # always see [rows, ...]
                a = a[None]
            a = a[:n]
            if seq_in is not None and key[1] is not None \
                    and key[1] > seq_in and a.ndim >= 2 \
                    and a.shape[1] == key[1]:
                # seq-to-seq outputs carry the padded time axis: slice
                # back to the request's length.  Time-reducing heads
                # ([n, classes]) pass through untouched — their axis 1
                # doesn't match the bucket
                a = a[:, :seq_in]
            return a

        return jax.tree.map(host_rows, out)

    def _place_input(self, xj):
        if self.mesh is None:
            return xj
        import jax

        from bigdl_tpu.parallel.mesh import data_sharding

        return jax.device_put(xj, data_sharding(self.mesh, xj.ndim))


# -- the shared (model, mesh) -> executor cache ------------------------------
# LRU-capped: an executor strongly references its model (the fwd
# closure) and its compiled executables, so an UNBOUNDED registry would
# leak every model ever predicted for process lifetime (Module.predict
# routes through here).  The cap covers the real pattern — one or a few
# live served/scored models — and eviction merely costs the next
# predict of an evicted model a re-compile.
_REGISTRY_CAP = 8
_REGISTRY: "collections.OrderedDict[Tuple[int, Optional[int]], " \
           "Tuple[Any, BucketedExecutor]]" = collections.OrderedDict()
_REGISTRY_LOCK = threading.Lock()


def executor_for(model, mesh=None, max_batch: int = 32,
                 compute_dtype=None, seq_axis: Optional[int] = None,
                 policy: Optional[BucketPolicy] = None) -> BucketedExecutor:
    """One executor per live (model, mesh) pair — the process-wide
    compile cache shared by ``LocalPredictor`` and the serving layer.
    ``id()`` keys are revalidated against a weakref (CPython reuses
    addresses of collected objects); least-recently-used entries are
    evicted past the cap."""
    import weakref

    key = (id(model), id(mesh) if mesh is not None else None)
    with _REGISTRY_LOCK:
        hit = _REGISTRY.get(key)
        if hit is not None and hit[0]() is model:
            _REGISTRY.move_to_end(key)
            ex = hit[1]
            if policy is not None:
                ex.adopt_policy(policy, seq_axis=seq_axis)
            return ex
        if hit is not None:  # stale id reuse
            del _REGISTRY[key]
        ex = BucketedExecutor(
            model, mesh=mesh,
            policy=policy or default_policy(max_batch, mesh),
            compute_dtype=compute_dtype, seq_axis=seq_axis)
        try:
            ref = weakref.ref(model)
        except TypeError:  # unweakrefable model: no caching, still works
            return ex
        _REGISTRY[key] = (ref, ex)
        while len(_REGISTRY) > _REGISTRY_CAP:
            _REGISTRY.popitem(last=False)
        return ex
