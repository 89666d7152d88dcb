"""The compiled training step — the TPU-native collapse of the reference's
entire L4+L5 distributed machinery (SURVEY §2.7, §3.1).

Where the reference runs TWO Spark jobs per iteration (forward/backward +
putGradients, then aggregateGradientPartition + sharded update +
sendWeightPartition, ``optim/DistriOptimizer.scala:175-315``) with gradients
bounced through the BlockManager as bf16-truncated chunks, here ONE
jit/pjit-compiled function does it all inside XLA:

- batch sharded over the mesh ``data`` axis (the per-node minibatch split,
  ``DistriOptimizer.scala:184-202``),
- gradient averaging via the collective XLA inserts for the sharded batch
  (the getWeights/putGradients/aggregate round-trips,
  ``parameters/AllReduceParameter.scala:181-305``),
- optional **ZeRO-1 layout** (`parameter_sync='sharded'`): optimizer state
  sharded over ``data`` via sharding constraints so XLA lowers the gradient
  collective to reduce-scatter + all-gather around a 1/N-sized update —
  structurally identical to the reference's owner-node update
  (``DistriOptimizer.scala:294-315``),
- optional bf16 gradient compression matching the reference's
  top-16-bit truncation exactly (``parameters/FP16CompressedTensor.scala:272``),
- per-layer regularizers, gradient scales (setScaleW/B), and freeze masks
  applied functionally,
- BN running stats carried through the state pytree.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import telemetry as _telemetry
from bigdl_tpu.analysis import hooks as _hooks
from bigdl_tpu.nn.module import Module, functional_call, state_dict, _resolve
from bigdl_tpu.ops import dispatch as _kernel_dispatch
from bigdl_tpu.parallel.mesh import (DATA_AXIS, data_sharding,
                                     mesh_process_count, replicated,
                                     shard_local_batch)


def _jit_cache_size(compiled) -> Optional[int]:
    """Executable-cache entry count of a jit-wrapped callable (None when
    the jit internals don't expose it)."""
    try:
        return int(compiled._cache_size())
    except Exception:  # noqa: BLE001 - observability only, never fail
        return None


def _note_compile(tracer, owner, kind: str, before, t0: float,
                  compiled) -> bool:
    """Post-dispatch compile detection for the telemetry stream: the jit
    executable cache grew (or this is the owner's first dispatch and the
    cache size is unreadable) means the call just paid trace+compile —
    emit it with the wall time of the dispatch that carried it.  Returns
    whether a compile was recorded (the caller keys one-time facts off
    the first)."""
    after = _jit_cache_size(compiled)
    first = not getattr(owner, "_tele_dispatched", False)
    owner._tele_dispatched = True
    if before is not None and after is not None:
        grew = after > before
    else:
        grew = first
    if grew:
        fields = {"dur": time.perf_counter() - t0}
        if after is not None:
            fields["cache_size"] = after
        tracer.emit("compile", name=kind, **fields)
    return first

__all__ = ["TrainStep", "bf16_truncate", "EvalStep"]


def bf16_truncate(x: jax.Array) -> jax.Array:
    """Exact parity with the reference's FP16CompressedTensor: keep the top
    16 bits of the IEEE float32 (== bfloat16 round-toward-zero),
    ``FP16CompressedTensor.scala:272``."""
    if x.dtype != jnp.float32:
        return x
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)


def _param_meta(model: Module):
    """Per-parameter (scale, frozen, regularizer) from the module tree."""
    meta = {}
    for path, _ in model.named_parameters():
        mod, leaf = _resolve(model, path)
        scale = mod.__dict__.get("scale_b", 1.0) if leaf == "bias" \
            else mod.__dict__.get("scale_w", 1.0)
        reg = mod.__dict__.get("b_regularizer") if leaf == "bias" \
            else mod.__dict__.get("w_regularizer")
        if reg is not None and not getattr(reg, "is_enabled", True):
            reg = None
        meta[path] = (scale, mod.__dict__["_frozen"], reg)
    return meta


class TrainStep:
    """Build and run the compiled train step.

    ``parameter_sync``: 'allreduce' (plain DP), 'sharded' (ZeRO-1: shard
    optimizer state over the data axis), 'fsdp' (ZeRO-3: shard the
    PARAMETERS themselves over the data axis too — no device holds a
    whole replica; XLA all-gathers each weight at use and lowers the
    gradient collective to reduce-scatter.  Pure GSPMD: the sharding
    annotations change, the step math doesn't), or 'local' (local SGD,
    docs/fault_tolerance.md "Straggler tolerance": every device along
    the data axis trains its OWN island — params/opt-state/buffers gain
    a leading island axis sharded over ``data`` and the step runs under
    ``vmap``, so the compiled program carries ZERO cross-island
    collectives; islands re-converge only when the driver calls
    :meth:`average_islands` every H steps, parallel/local_sync.py).
    ``gradient_compression``: None or 'bf16' (reference truncation
    semantics).
    ``compute_dtype``: e.g. jnp.bfloat16 to run fwd/bwd in bf16 with f32
    master params.
    ``health_probe``: compute the fused numeric-health reduction per
    step (global grad/param/update norms + nonfinite counts,
    ``telemetry/health.py PROBE_FIELDS``) as an extra step output,
    stored on ``self.last_health`` — an async device array whose values
    are ready once the loss fetch the driver already performs has
    synced, so reading it is a d2h copy, not another device sync.
    ``skip_nonfinite``: additionally KEEP the previous
    params/opt-state/buffers (in-graph select) whenever the step's
    gradients, updated params, or loss are nonfinite — the poisoned
    update never lands (donation-safe: the select is part of the same
    compiled program).
    """

    def __init__(self, model: Module, criterion, optim_method, mesh=None,
                 parameter_sync: str = "allreduce",
                 gradient_compression: Optional[str] = None,
                 compute_dtype=None,
                 batch_axes=(DATA_AXIS,),
                 extra_sharding_rules: Optional[Callable] = None,
                 gradient_clipping: Optional[Tuple[float, float]] = None,
                 max_norm: Optional[float] = None,
                 remat: bool = False,
                 health_probe: bool = False,
                 skip_nonfinite: bool = False,
                 grad_fault: bool = False):
        self.model = model
        self.criterion = criterion
        self.optim = optim_method
        self.mesh = mesh
        if parameter_sync not in ("allreduce", "sharded", "fsdp", "local"):
            # validate where the mode is CONSUMED: a typo must not
            # silently degrade to replicated allreduce
            raise ValueError(f"unknown parameter_sync {parameter_sync!r} "
                             f"(allreduce | sharded | fsdp | local)")
        self.parameter_sync = parameter_sync
        self.gradient_compression = gradient_compression
        self.compute_dtype = compute_dtype
        self.batch_axes = tuple(batch_axes)
        self.extra_sharding_rules = extra_sharding_rules
        self.gradient_clipping = gradient_clipping
        self.max_norm = max_norm
        self.remat = remat
        self.health_probe = health_probe
        self.skip_nonfinite = skip_nonfinite
        # fault injection (bigdl_tpu/faults.py): the compiled step takes
        # one extra traced scalar multiplied into the RAW gradients —
        # 1.0 in healthy steps, NaN when a nan_grads fault fires, so the
        # poison enters through the same path a real divergence would
        # and the in-graph health probe judges it
        self.grad_fault = grad_fault
        self.last_health = None  # device [5] vector, see PROBE_FIELDS

        # module-path scopes (docs/observability.md): stamped before the
        # first trace so compiled-HLO op metadata carries the module tree
        # — the substrate of per-module cost attribution.  Trace-time
        # metadata only; jit cache keys are unchanged (zero retraces).
        from bigdl_tpu.nn.module import stamp_scope_names
        from bigdl_tpu.utils.config import get_config

        stamp_scope_names(model, enabled=get_config().module_scopes)
        self.params = state_dict(model, kind="param")
        self.buffers = state_dict(model, kind="buffer")
        self.opt_state = optim_method.init_state(self.params)
        self._meta = _param_meta(model)
        # sparse embedding-gradient sync (docs/sparse.md): the tables
        # whose gradient may arrive as unique-coalesced (indices, rows)
        # pairs instead of a dense [vocab, dim] scatter + all-reduce.
        # Exactness guardrails applied HERE (the layer owns the
        # per-trace density decision): a regularized table's reg
        # gradient is dense by definition, and value-clipping with a
        # bound that moves zeros (lo > 0 or hi < 0) would update every
        # untouched row on the dense path — both stay dense.
        from bigdl_tpu.nn.layers import embedding as _embed

        self._sparse_tables = {
            p: m for p, m in _embed.sparse_tables(model).items()
            if self._meta.get(p, (1.0, False, None))[2] is None}
        if self.gradient_clipping is not None and self._sparse_tables:
            lo, hi = self.gradient_clipping
            if not (lo <= 0.0 <= hi):
                self._sparse_tables = {}
        self._sparse_stats = None
        if parameter_sync == "local":
            # local-SGD islands (parallel/local_sync.py): every state
            # leaf gains a leading island axis and the step runs under
            # vmap with NO cross-island comms, so sharding rules and the
            # sparse row sync (both collective machinery) cannot apply
            if extra_sharding_rules is not None:
                raise ValueError("parameter_sync='local' does not "
                                 "compose with extra_sharding_rules")
            if len(self.batch_axes) != 1:
                raise ValueError("parameter_sync='local' needs exactly "
                                 "one batch axis")
            self._sparse_tables = {}
        self._avg_cache = None
        self._compiled = None
        self._place_initial()

    # -- sharding ----------------------------------------------------------
    def _param_sharding(self, path: str, arr):
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.mesh is None:
            return None
        if self.extra_sharding_rules is not None:
            spec = self.extra_sharding_rules(path, arr)
            if spec is not None:
                return NamedSharding(self.mesh, spec)
        if self.parameter_sync == "fsdp" and hasattr(arr, "ndim") \
                and arr.ndim >= 1:
            # ZeRO-3: each weight lives sharded over the batch axis
            # (axis 0 when divisible); XLA inserts the per-use
            # all-gather and the reduce-scatter on its gradient.
            # Explicit TP rules above take precedence; indivisible
            # leaves stay replicated.
            ax = self._zero_axis()
            n = self.mesh.shape.get(ax, 1)
            if n > 1 and arr.shape[0] % n == 0 and arr.shape[0] >= n:
                return NamedSharding(
                    self.mesh, P(*((ax,) + (None,) * (arr.ndim - 1))))
        return replicated(self.mesh)

    def _zero_axis(self):
        """The mesh axis ZeRO state shards over — the leading batch
        axis, not a hard-coded 'data' (a mesh may name it differently)."""
        return self.batch_axes[0] if self.batch_axes else DATA_AXIS

    def _opt_leaf_sharding(self, arr):
        """ZeRO-1/3: shard large optimizer-state leaves over the batch
        axis."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.mesh is None:
            return None
        if self.parameter_sync in ("sharded", "fsdp") \
                and hasattr(arr, "ndim") and arr.ndim >= 1:
            ax = self._zero_axis()
            n = self.mesh.shape.get(ax, 1)
            if n > 1 and arr.shape[0] % n == 0 and arr.shape[0] >= n:
                return NamedSharding(self.mesh, P(ax))
        return replicated(self.mesh)

    def _opt_state_shardings(self, opt_state):
        """Per-leaf opt-state shardings ALIGNED with the owning param's
        layout: a TP-ruled param's moment buffers follow the TP sharding
        (constraining them onto the ZeRO axis would force a per-step
        resharding collective); everything else gets the ZeRO layout."""
        rules = self.extra_sharding_rules

        def leaf(path, arr):
            if rules is not None and hasattr(arr, "ndim"):
                # the innermost dict key is the param name for the
                # per-param moment trees (velocity/m/v/...)
                key = None
                for part in reversed(path):
                    if hasattr(part, "key"):
                        key = part.key
                        break
                if key is not None:
                    spec = rules(str(key), arr)
                    if spec is not None:
                        from jax.sharding import NamedSharding

                        return NamedSharding(self.mesh, spec)
            return self._opt_leaf_sharding(arr)

        return jax.tree_util.tree_map_with_path(leaf, opt_state)

    def _place_initial(self):
        if self.parameter_sync == "local":
            self.params = {k: self._stack_island(v)
                           for k, v in self.params.items()}
            self.buffers = {k: self._stack_island(v)
                            for k, v in self.buffers.items()}
            self.opt_state = jax.tree.map(self._stack_island,
                                          self.opt_state)
            return
        if self.mesh is None:
            return
        self.params = {k: jax.device_put(v, self._param_sharding(k, v))
                       for k, v in self.params.items()}
        self.buffers = {k: jax.device_put(v, replicated(self.mesh))
                        for k, v in self.buffers.items()}
        self.opt_state = jax.tree.map(
            jax.device_put, self.opt_state,
            self._opt_state_shardings(self.opt_state))

    # -- local-SGD islands (parameter_sync='local') ------------------------
    def island_count(self) -> int:
        """Islands = devices along the batch axis (1 off-mesh)."""
        if self.mesh is None:
            return 1
        return int(self.mesh.shape.get(self._zero_axis(), 1))

    def _island_sharding(self, ndim: int):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(
            self.mesh, P(self._zero_axis(), *([None] * (ndim - 1))))

    def _stack_island(self, v):
        """Replicate one (unstacked) leaf into the stacked island layout:
        leading axis = island count, sharded over the batch axis so each
        device owns its own island's copy.  Multi-process: built from
        process-local rows — no collective, which is what lets the
        survivors rebuild state after a peer is shed."""
        a = np.asarray(v)
        n = self.island_count()
        if self.mesh is None:
            return jnp.broadcast_to(jnp.asarray(a), (n,) + a.shape)
        sharding = self._island_sharding(a.ndim + 1)
        nproc = mesh_process_count(self.mesh)
        if nproc > 1:
            local = np.ascontiguousarray(
                np.broadcast_to(a, (max(1, n // nproc),) + a.shape))
            return jax.make_array_from_process_local_data(
                sharding, local, (n,) + a.shape)
        return jax.device_put(
            np.ascontiguousarray(np.broadcast_to(a, (n,) + a.shape)),
            sharding)

    def _island_rows(self, stacked) -> np.ndarray:
        """This process's islands of one stacked leaf, as a host array
        with the island axis leading (all islands on a single host)."""
        shards = getattr(stacked, "addressable_shards", None)
        if not shards:
            return np.asarray(stacked)
        return np.concatenate([np.asarray(s.data) for s in shards],
                              axis=0)

    def island_mean_host(self, tree) -> Dict[str, np.ndarray]:
        """Host-side mean over this process's ADDRESSABLE islands — no
        collective, so it stays safe after peers desynchronize or are
        shed (the multi-process averaging path and the local-mode
        ``sync_to_model`` both build on it)."""
        out = {}
        for k, v in tree.items():
            rows = self._island_rows(v)
            if np.issubdtype(rows.dtype, np.floating):
                out[k] = rows.mean(axis=0).astype(rows.dtype)
            else:
                out[k] = rows[0]  # counters: islands agree by design
        return out

    def load_island_state(self, params: Dict[str, np.ndarray],
                          buffers: Optional[Dict[str, np.ndarray]] = None
                          ) -> None:
        """Overwrite every LOCAL island with the given (unstacked)
        state — the write-back half of a cross-process averaging round.
        Optimizer state intentionally stays per-island (local SGD
        averages parameters, not moments)."""
        self.params = {
            k: self._stack_island(np.asarray(params[k]).astype(
                self._island_rows(v).dtype))
            if k in params else v
            for k, v in self.params.items()}
        if buffers:
            self.buffers = {
                k: self._stack_island(np.asarray(buffers[k]).astype(
                    self._island_rows(v).dtype))
                if k in buffers else v
                for k, v in self.buffers.items()}

    def _fold_island_health(self, health) -> np.ndarray:
        """Aggregate the stacked (islands, 5) health probe into the one
        5-vector the policy reads: norms combine as sqrt-of-sum-of-
        squares, nonfinite counts sum.  Host-side over addressable
        islands — each process judges its own islands."""
        rows = self._island_rows(health).astype(np.float64)
        norms = np.sqrt(np.sum(rows[:, :3] ** 2, axis=0))
        bads = np.sum(rows[:, 3:], axis=0)
        return np.concatenate([norms, bads]).astype(np.float32)

    def _avg_fn(self):
        """The in-graph island averaging program (single-process path):
        mean over the island axis + broadcast back — the ONE collective
        local mode retains, paid every H steps instead of every step
        (its measured bytes are the ``sync/average`` event's payload and
        the bench leg's amortized comms_bytes)."""
        mesh = self.mesh

        def mean_bcast(a):
            if not jnp.issubdtype(a.dtype, jnp.floating):
                return a
            m = jnp.mean(a, axis=0, keepdims=True)
            out = jnp.broadcast_to(m, a.shape).astype(a.dtype)
            if mesh is not None:
                out = jax.lax.with_sharding_constraint(
                    out, self._island_sharding(a.ndim))
            return out

        def avg(params, buffers):
            return (jax.tree.map(mean_bcast, params),
                    jax.tree.map(mean_bcast, buffers))

        return avg

    def _avg_executable(self):
        if self._avg_cache is None:
            lowered = jax.jit(self._avg_fn(),
                              donate_argnums=(0, 1)).lower(
                self.params, self.buffers)
            self._avg_cache = lowered.compile()
        return self._avg_cache

    def average_islands(self) -> None:
        """One parameter-averaging round across THIS process's islands,
        in-graph (single-process local SGD; the multi-process barrier in
        parallel/local_sync.py composes :meth:`island_mean_host` +
        :meth:`load_island_state` over files instead — a jitted mean
        over a cross-process axis would be exactly the blocking
        collective the staleness barrier exists to avoid)."""
        if self.parameter_sync != "local":
            raise RuntimeError("average_islands needs "
                               "parameter_sync='local'")
        self.params, self.buffers = self._avg_executable()(
            self.params, self.buffers)

    # -- the pure step -----------------------------------------------------
    def _step_fn(self, with_health: bool = False, local: bool = False):
        """The pure (params, opt_state, buffers, x, y, key[, grad_scale])
        -> (params, opt_state, buffers, loss[, health]) function that
        :meth:`_build` jits.
        ``with_health`` appends the fused health 5-vector output.
        The optional trailing ``grad_scale`` scalar is the fault-plan
        input (``grad_fault=True`` dispatches pass it; omitted, the
        multiply never enters the trace).  ``local`` traces the step
        with NO mesh in scope — the single-island body the local-SGD
        wrapper vmaps over the island axis (every sharding constraint
        would otherwise re-introduce the collectives local mode
        removes)."""
        model, criterion, optim = self.model, self.criterion, self.optim
        meta = self._meta
        comp = self.gradient_compression
        cdt = self.compute_dtype
        mesh = None if local else self.mesh
        skip_nonfinite = self.skip_nonfinite

        from bigdl_tpu.nn.layers import embedding as _embed

        sparse_tables = self._sparse_tables
        cap_paths = {id(m): p for p, m in sparse_tables.items()}

        def loss_fn(params, buffers, x, y, key, proxies=None):
            call_params = params
            if cdt is not None:
                call_params = {k: v.astype(cdt) for k, v in params.items()}
                x = jax.tree.map(lambda a: a.astype(cdt) if jnp.issubdtype(a.dtype, jnp.floating) else a, x)
            if proxies is None:
                out, new_state = functional_call(
                    model, {**call_params, **buffers}, x, training=True,
                    rng=key)
                sparse_aux = {}
            else:
                # sparse capture: active embedding layers fetch their
                # cotangent proxies and record their coalesced unique
                # indices, returned as aux so the update can scatter-add
                with _embed.SparseCapture(cap_paths, proxies) as cap:
                    out, new_state = functional_call(
                        model, {**call_params, **buffers}, x,
                        training=True, rng=key)
                # arrays ONLY (jax.checkpoint rejects static leaves in
                # traced outputs): the static facts (path/slots/vocab)
                # come from the discovery pass's metas
                sparse_aux = {k: v["u"] for k, v in cap.aux.items()}
            loss = criterion.update_output(out, y)
            reg_loss = 0.0
            for path, (_, frozen, reg) in meta.items():
                if reg is not None and not frozen:
                    reg_loss = reg_loss + reg.loss(params[path])
            new_buffers = {k: new_state[k] for k in buffers}
            return loss + reg_loss, (loss, new_buffers, out, sparse_aux)

        if self.remat:
            # whole-model rematerialization: the backward recomputes the
            # forward instead of saving every activation — HBM for FLOPs
            # (finer-grained boundaries: wrap blocks in nn.Remat instead)
            loss_fn = jax.checkpoint(loss_fn, static_argnums=())

        def step(params, opt_state, buffers, x, y, key, grad_scale=None):
            # the WHOLE step: custom-VJP backward rules are traced after
            # the forward returns, and their kernels dispatch too.  (The
            # argument names label the HLO parameters that the memory
            # and comms walkers sort into categories.)
            with _kernel_dispatch.spmd_partitioned(mesh):
                return _step(params, opt_state, buffers, x, y, key,
                             grad_scale)

        def _step(params, opt_state, buffers, x, y, key, grad_scale=None):
            if mesh is not None:
                from jax.sharding import PartitionSpec as P

                ax = self.batch_axes[0] if len(self.batch_axes) == 1 else self.batch_axes
                x = jax.tree.map(
                    lambda a: jax.lax.with_sharding_constraint(
                        a, jax.sharding.NamedSharding(mesh, P(ax, *([None] * (a.ndim - 1))))), x)
            proxies, metas = {}, {}
            if sparse_tables and _embed.sparse_enabled():
                # discovery (one eval_shape, no FLOPs): which tables go
                # sparse for THIS batch shape, and their proxy shapes —
                # the layer's density rule decides per trace, so a
                # long-sequence batch over a small vocab stays dense
                # loss_fn is called WITHOUT proxies here: the discover
                # capture discover_proxies sets is ambient, so the
                # layers request shapes from it instead of binding
                shapes, metas = _embed.discover_proxies(
                    lambda: loss_fn(params, buffers, x, y, key),
                    cap_paths)
                proxies = {k: jnp.zeros(s.shape, s.dtype)
                           for k, s in shapes.items()}
            if proxies:
                active_tables = {m["path"] for m in metas.values()}
                dense_view = {k: v for k, v in params.items()
                              if k not in active_tables}

                def inner(dp, pr):
                    # active tables ride the closure (non-differentiated
                    # — their gradient IS the proxies'); everything else
                    # differentiates as before
                    full = dict(params)
                    full.update(dp)
                    return loss_fn(full, buffers, x, y, key, pr)

                (grads, prox_grads), (loss, new_buffers, _, aux) = \
                    jax.grad(inner, argnums=(0, 1), has_aux=True)(
                        dense_view, proxies)
            else:
                grads, (loss, new_buffers, _, aux) = jax.grad(
                    loss_fn, has_aux=True)(params, buffers, x, y, key)
                prox_grads = {}
            if grad_scale is not None:
                # fault injection BEFORE scaling/clipping/compression:
                # the probe must see nonfinite GRADS, exactly as a real
                # divergence would present
                grads = {k: g * grad_scale for k, g in grads.items()}
                prox_grads = {k: g * grad_scale
                              for k, g in prox_grads.items()}
            if cdt is not None:
                grads = {k: g.astype(jnp.float32) for k, g in grads.items()}
                prox_grads = {k: g.astype(jnp.float32)
                              for k, g in prox_grads.items()}
            def replicate_pair(u, g):
                # pin the sync collective onto the SMALL arrays: the
                # partitioner must replicate the coalesced rows (an
                # all-reduce over [slots, dim]) before any scatter —
                # never partial-scatter into [vocab, dim] and
                # all-reduce that
                if mesh is None:
                    return u, g
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                rep = NamedSharding(mesh, P())
                return (jax.lax.with_sharding_constraint(u, rep),
                        jax.lax.with_sharding_constraint(g, rep))

            # group the proxy cotangents by table; a table used MORE
            # THAN ONCE per forward densifies LOCALLY here, BEFORE the
            # nonlinear grad legs (bf16 truncate / value clip / global
            # norm): those must see the cross-call SUM exactly as the
            # dense path does, and the lazy Adagrad sum-then-square
            # also requires pre-summed rows.  Single-call tables (the
            # norm) stay row-sparse through every leg.
            by_path = {}
            for pkey, g in prox_grads.items():
                by_path.setdefault(metas[pkey]["path"], []).append(
                    (aux[pkey], g))
            sparse_entries = {}
            for path, entries in by_path.items():
                if len(entries) == 1:
                    sparse_entries[path] = entries[0]
                else:
                    dense = jnp.zeros_like(params[path])
                    for u, g in entries:
                        u, g = replicate_pair(u, g)
                        dense = dense.at[u].add(g.astype(dense.dtype),
                                                mode="drop")
                    grads[path] = dense  # rides the dense legs below
            # per-layer scales & freeze
            scaled = {}
            for k, g in grads.items():
                scale, frozen, _ = meta[k]
                if frozen:
                    g = jnp.zeros_like(g)
                elif scale != 1.0:
                    g = g * scale
                scaled[k] = g
            # sparse rows ride the same legs keyed by their table's path
            for path, (u, g) in list(sparse_entries.items()):
                scale, frozen, _ = meta[path]
                if frozen:
                    g = jnp.zeros_like(g)
                elif scale != 1.0:
                    g = g * scale
                sparse_entries[path] = (u, g)
            if comp == "bf16":
                scaled = {k: bf16_truncate(v) for k, v in scaled.items()}
                sparse_entries = {
                    k: (u, bf16_truncate(g))
                    for k, (u, g) in sparse_entries.items()}
            if self.gradient_clipping is not None:
                lo, hi = self.gradient_clipping
                scaled = {k: jnp.clip(v, lo, hi) for k, v in scaled.items()}
                # constructor guarantees lo <= 0 <= hi when sparse
                # tables are live, so untouched (zero) rows stay zero
                sparse_entries = {
                    k: (u, jnp.clip(g, lo, hi))
                    for k, (u, g) in sparse_entries.items()}
            if self.max_norm is not None:
                gn = jnp.sqrt(sum(jnp.sum(v * v) for v in scaled.values())
                              + sum(jnp.sum(g * g)
                                    for _, g in sparse_entries.values()))
                factor = jnp.minimum(1.0, self.max_norm / (gn + 1e-12))
                scaled = {k: v * factor for k, v in scaled.items()}
                sparse_entries = {
                    k: (u, g * factor)
                    for k, (u, g) in sparse_entries.items()}
            sparse_g = {path: replicate_pair(u, g)
                        for path, (u, g) in sparse_entries.items()}
            # ZeRO-1/3: constrain optimizer state onto the batch axis so
            # XLA lowers the gradient collective to reduce-scatter +
            # all-gather; TP-ruled params' moment buffers follow the TP
            # layout instead (per-leaf alignment, _opt_state_shardings)
            if mesh is not None and self.parameter_sync in ("sharded",
                                                            "fsdp"):
                opt_state = jax.tree.map(
                    lambda a, s: jax.lax.with_sharding_constraint(a, s)
                    if hasattr(a, "ndim") else a,
                    opt_state, self._opt_state_shardings(opt_state))
            # trace-time bookkeeping for the `train/sparse` instant:
            # static per-step sync accounting (what a dense all-reduce
            # of each table would move vs the coalesced rows)
            if sparse_g:
                self._sparse_stats = _embed.sparse_sync_stats(
                    {k: m for k, m in metas.items()
                     if m["path"] in sparse_g})
            if sparse_g and hasattr(optim, "update_mixed"):
                new_params, new_opt = optim.update_mixed(
                    scaled, sparse_g, params, opt_state,
                    scatter=self._row_scatter())
            else:
                # the pre-sparse contract: a duck-typed method needs
                # only update().  With sparse grads in hand, densify
                # them LOCALLY (zero collectives — the sync already
                # happened on the rows) so such a method still trains
                # exactly.
                for path, (u, g) in sparse_g.items():
                    scaled[path] = jnp.zeros_like(params[path]).at[u].add(
                        g.astype(params[path].dtype), mode="drop")
                new_params, new_opt = optim.update(scaled, params,
                                                   opt_state)
            if mesh is not None:
                new_params = {
                    k: jax.lax.with_sharding_constraint(v, self._param_sharding(k, v))
                    for k, v in new_params.items()}
            health = None
            if with_health or skip_nonfinite:
                # ONE fused reduction pass over the grad/param trees:
                # global grad/param/update norms + nonfinite counts.
                # XLA fuses the per-leaf partial sums into the step's
                # existing elementwise work; the scalars ride the step's
                # output fetch (no extra device->host sync).
                gsq = psq = usq = jnp.float32(0.0)
                gbad = pbad = jnp.int32(0)
                for k, g in scaled.items():
                    g32 = g.astype(jnp.float32)
                    p32 = params[k].astype(jnp.float32)
                    n32 = new_params[k].astype(jnp.float32)
                    d32 = n32 - p32
                    gsq += jnp.sum(g32 * g32)
                    psq += jnp.sum(p32 * p32)
                    usq += jnp.sum(d32 * d32)
                    gbad += jnp.sum((~jnp.isfinite(g32)).astype(jnp.int32))
                    pbad += jnp.sum((~jnp.isfinite(n32)).astype(jnp.int32))
                for k, (_u, g) in sparse_g.items():
                    # a row-sparse grad's norm IS the dense grad's norm
                    # (the zeros contribute nothing); param/update norms
                    # read the full table like any other param
                    g32 = g.astype(jnp.float32)
                    p32 = params[k].astype(jnp.float32)
                    n32 = new_params[k].astype(jnp.float32)
                    d32 = n32 - p32
                    gsq += jnp.sum(g32 * g32)
                    psq += jnp.sum(p32 * p32)
                    usq += jnp.sum(d32 * d32)
                    gbad += jnp.sum((~jnp.isfinite(g32)).astype(jnp.int32))
                    pbad += jnp.sum((~jnp.isfinite(n32)).astype(jnp.int32))
                health = jnp.stack(
                    [jnp.sqrt(gsq), jnp.sqrt(psq), jnp.sqrt(usq),
                     gbad.astype(jnp.float32), pbad.astype(jnp.float32)])
                if skip_nonfinite:
                    # poisoned step: keep the previous state wholesale
                    # (params, optimizer moments, BN buffers) — the
                    # in-graph analogue of drop-gradients-and-continue
                    ok = (gbad == 0) & (pbad == 0) & jnp.isfinite(loss)
                    keep = lambda n, o: jnp.where(ok, n, o)
                    new_params = {k: keep(v, params[k])
                                  for k, v in new_params.items()}
                    new_opt = jax.tree.map(keep, new_opt, opt_state)
                    new_buffers = {k: keep(v, buffers[k])
                                   for k, v in new_buffers.items()}
            if with_health:
                return new_params, new_opt, new_buffers, loss, health
            return new_params, new_opt, new_buffers, loss

        return step

    def _row_scatter(self):
        """The sparse update's row scatter, pinned against GSPMD's
        parallel-scatter lowering (docs/sparse.md).

        Left to itself the partitioner re-tiles the (replicated,
        free-to-slice) coalesced rows along the slots axis and lowers
        ``table.at[u].add(rows)`` as per-shard partial scatter + a dense
        ``[vocab, dim]`` all-reduce — re-creating the exact collective
        the sparse path removes, and sharding constraints on the
        operands alone do not dissuade it.  So: a REPLICATED target runs
        the scatter inside ``shard_map`` with fully-replicated specs
        (per-device identical local code — structurally no collective;
        the rows' own small all-reduce happens at the replication
        constraint, which IS the sync).  A dim0-SHARDED target (ZeRO
        moments, fsdp/row-sharded tables) keeps the GSPMD path with its
        layout pinned on both sides — each shard masks and applies the
        rows that land in its range.  Returns None off-mesh (the plain
        ``.at[]`` scatter is already local)."""
        mesh = self.mesh
        if mesh is None or mesh.devices.size <= 1:
            return None
        from functools import partial

        from jax.sharding import NamedSharding, PartitionSpec as P

        smap = partial(jax.shard_map, check_vma=False)
        rep = NamedSharding(mesh, P())

        def spec_of(kind, path, arr):
            if kind == "param":
                sh = self._param_sharding(path, arr)
                return sh.spec if sh is not None else P()
            if self.extra_sharding_rules is not None:
                s = self.extra_sharding_rules(path, arr)
                if s is not None:
                    return s
            sh = self._opt_leaf_sharding(arr)
            return sh.spec if sh is not None else P()

        def scatter(target, idx, updates, op, kind, path):
            idx = jax.lax.with_sharding_constraint(idx, rep)
            updates = jax.lax.with_sharding_constraint(updates, rep)
            spec = spec_of(kind, path, target)

            def body(t, i, u):
                if op == "set":
                    return t.at[i].set(u, mode="drop")
                return t.at[i].add(u, mode="drop")

            if tuple(spec) == ():
                return smap(body, mesh=mesh, in_specs=(P(), P(), P()),
                            out_specs=P())(target, idx, updates)
            sharding = NamedSharding(mesh, spec)
            target = jax.lax.with_sharding_constraint(target, sharding)
            return jax.lax.with_sharding_constraint(
                body(target, idx, updates), sharding)

        return scatter

    def _local_step_fn(self, with_health: bool = False):
        """The local-SGD island step: the mesh-free single-island body
        vmapped over the leading island axis.  Same external signature
        as :meth:`_step_fn`'s step — the driver cannot tell the modes
        apart — but every state leaf carries the island axis, the batch
        splits island-wise in-graph, and the per-island RNG key forks by
        island index so islands explore distinct stochastic paths.

        On a mesh the island axis is mapped with ``shard_map``, not a
        sharding-constrained vmap.  vmap's conv batching rule folds the
        island axis into the convolution batch/feature-group dims, and
        the SPMD partitioner answers the island sharding riding on those
        merged dims with per-step all-gathers of the full parameter set
        (measured at 33x the bytes of the allreduce this mode replaces);
        boundary sharding constraints cannot reach those interior ops.
        shard_map makes island-locality STRUCTURAL: each batch-axis
        shard runs the body on its own island block, so the compiled
        program contains ZERO cross-island collectives and a
        desynchronized (or shed) peer can never block a dispatch."""
        from functools import partial

        from jax.sharding import PartitionSpec as P

        inner = self._step_fn(with_health=with_health, local=True)
        n = self.island_count()
        mesh = self.mesh

        def islands(params, opt_state, buffers, xs, ys, keys, *rest):
            # leading axis = the islands of THIS shard (all of them
            # when mesh-free); the fault scalar broadcasts to each
            # rest is a Python tuple (the arity), not a traced value
            if rest:  # noqa: lint/tracer-branch
                one = lambda p, o, b, xi, yi, k: inner(p, o, b, xi, yi,
                                                       k, rest[0])
            else:
                one = lambda p, o, b, xi, yi, k: inner(p, o, b, xi, yi,
                                                       k)
            return jax.vmap(one)(params, opt_state, buffers, xs, ys,
                                 keys)

        def many(params, opt_state, buffers, x, y, key, grad_scale=None):
            def split(a):
                if a.shape[0] % n:
                    raise ValueError(
                        f"local-SGD batch axis {a.shape[0]} not "
                        f"divisible by {n} island(s)")
                return a.reshape((n, a.shape[0] // n) + a.shape[1:])

            xs = jax.tree.map(split, x)
            ys = jax.tree.map(split, y)
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
                jnp.arange(n))
            args = (params, opt_state, buffers, xs, ys, keys)
            if grad_scale is not None:
                args += (grad_scale,)
            if mesh is None:
                return islands(*args)
            isl = P(self._zero_axis())
            in_specs = (isl,) * 6
            if grad_scale is not None:
                in_specs += (P(),)  # the fault scalar is replicated
            return jax.shard_map(islands, mesh=mesh, in_specs=in_specs,
                                 out_specs=isl, check_vma=False)(*args)

        return many

    def _build(self):
        if self.parameter_sync == "local":
            return jax.jit(self._local_step_fn(
                with_health=self.health_probe), donate_argnums=(0, 1, 2))
        return jax.jit(self._step_fn(with_health=self.health_probe),
                       donate_argnums=(0, 1, 2))

    # -- host API ----------------------------------------------------------
    def run(self, x, y, key, grad_scale=None) -> float:
        """One training iteration; returns the loss.

        Single-host callers pass the GLOBAL batch; multi-host callers pass
        this process's LOCAL shard of it (per-process data sharding, the
        reference's per-node partition feeding)."""
        if _hooks.hooks_active():  # retrace detector sees the RAW args
            _hooks.dispatch_event(self, "TrainStep.run",
                                  {"x": x, "y": y, "key": key})
        x, y = self._shard_batch(x, y)
        # set only once run_sharded is definitely next — names both the
        # hooks cache event and the telemetry compile event after it
        self._dispatch_observed = "TrainStep.run"
        return self.run_sharded(x, y, key, grad_scale=grad_scale)

    def run_sharded(self, x, y, key, grad_scale=None):
        """One iteration over batch arrays already placed on the mesh
        (``_shard_batch``) — lets the host loop time the h2d transfer and
        the dispatch as separate Metrics stages."""
        # direct callers (the Optimizer's h2d/dispatch Metrics split)
        # bypass run(); the retrace detector still needs to see the args
        # or every recompile is misattributed as retrace/recompile.  A
        # DISTINCT event kind keeps the raw-args view from run() and the
        # mesh-placed view here from diffing against each other.
        kind = getattr(self, "_dispatch_observed", None)
        if kind is None:
            kind = "TrainStep.run_sharded"
            if _hooks.hooks_active():
                _hooks.dispatch_event(self, kind,
                                      {"x": x, "y": y, "key": key})
        self._dispatch_observed = None
        if self._compiled is None:
            # the per-step program is what `cli train` compiles: a
            # restart should load it from the same managed cache as
            # the serving warmup (docs/compile.md)
            from bigdl_tpu.utils.engine import enable_compile_cache

            enable_compile_cache()
            self._compiled = self._build()
        if self.parameter_sync == "local":
            # the driver may insert UNSTACKED scalars into opt_state
            # mid-run (the epoch counter at epoch boundaries); the
            # vmapped step needs every leaf to carry the island axis
            self.opt_state = jax.tree.map(
                lambda a: self._stack_island(a)
                if getattr(a, "ndim", 0) == 0 else a, self.opt_state)
        tracer = _telemetry.get()
        before = _jit_cache_size(self._compiled) if tracer else None
        t0 = time.perf_counter()
        args = (self.params, self.opt_state, self.buffers, x, y, key)
        if self.grad_fault:
            # always pass the scalar once armed — a consistent arity
            # keeps one executable (the scalar is a traced input, so
            # 1.0 vs NaN cannot retrace)
            args += (jnp.float32(1.0 if grad_scale is None
                                 else grad_scale),)
        try:
            out = self._compiled(*args)
        except Exception as e:  # noqa: BLE001 - OOM forensics only
            self._maybe_raise_oom(e, "TrainStep.run_sharded",
                                  x=x, y=y)
            raise
        if self.health_probe:
            (self.params, self.opt_state, self.buffers, loss,
             self.last_health) = out
        else:
            self.params, self.opt_state, self.buffers, loss = out
        if self.parameter_sync == "local":
            # stacked-island outputs: fold host-side over the
            # ADDRESSABLE islands only — an in-graph cross-island
            # reduce would be the collective local mode exists to
            # remove (and would block on a shed peer)
            if self.health_probe and self.last_health is not None:
                self.last_health = self._fold_island_health(
                    self.last_health)
            loss = self._island_rows(loss).mean()
        if tracer is not None:
            first = _note_compile(tracer, self, kind, before,
                                  t0, self._compiled)
            if first:
                self._emit_device_facts(tracer, x, y, key)
                self._emit_sparse_instant(tracer)
        if _hooks.hooks_active():
            _hooks.cache_event(self, kind,
                               _jit_cache_size(self._compiled))
        return loss

    def lower(self, x, y, key):
        """The program :meth:`run` dispatches, lowered for these
        arguments and not run (a ``jax.stages.Lowered``): the same
        :meth:`_build`, the batch placed as ``run`` places it, the fault
        scalar's slot filled where ``grad_fault`` is armed.  Lowering
        donates nothing, so the step's state stays as it was, and
        ``.compile()`` of the result is an executable in hand for a
        reader of its text, cost or memory: it is never installed."""
        if self._compiled is None:
            from bigdl_tpu.utils.engine import enable_compile_cache

            enable_compile_cache()
            self._compiled = self._build()
        if not any(isinstance(a, jax.Array) and not a.is_fully_addressable
                   for a in jax.tree.leaves((x, y))):
            # (a batch that spans processes was placed by run(): its
            # rows cannot be placed a second time from this process)
            x, y = self._shard_batch(x, y)
        args = (self.params, self.opt_state, self.buffers, x, y, key)
        if self.grad_fault:
            args += (jnp.float32(1.0),)
        return self._compiled.lower(*args)

    def _emit_device_facts(self, tracer, x, y, key) -> None:
        """Once per step object: pull the compiled program's cost/memory
        story (telemetry/device.py) so throughput numbers in the log come
        with an explanation.  ``auto`` re-lowers the already-traced step
        (no XLA compile); ``full`` additionally AOT-compiles for the HBM
        breakdown; ``off`` skips."""
        from bigdl_tpu.telemetry import device as _tdev
        from bigdl_tpu.utils.config import get_config

        cfg = get_config()
        level = cfg.telemetry_device
        comms_on = self._comms_enabled(cfg)
        memory_on = self._memory_enabled(cfg)
        if level == "off" and not comms_on and not memory_on:
            return

        lowered = None
        # the comms AND memory walkers both read the POST-SPMD-
        # partitioning HLO (collectives and the schedule don't exist in
        # the lowered StableHLO), so the one extra LOCAL XLA compile per
        # step object is SHARED: with both enabled, the second event is
        # a text parse.  Same class of cost as BIGDL_TELEMETRY_DEVICE=
        # full, and why both `auto` modes fire only on multi-device
        # meshes.
        compiled = None

        def recompile():
            nonlocal lowered, compiled
            if compiled is None:
                if lowered is None:
                    lowered = self.lower(x, y, key)
                compiled = lowered.compile()
            return compiled

        if level != "off":
            try:
                lowered = self.lower(x, y, key)
                facts = _tdev.collect_device_facts(
                    lowered, (self.params, self.opt_state, self.buffers),
                    level="auto" if level == "full" else level)
                if level == "full":
                    # the full-level HBM breakdown off the SAME compile
                    # the comms/memory walkers share
                    facts.update(_tdev.memory_facts(recompile()))
            except Exception:  # noqa: BLE001 - facts never fail the step
                facts = None
            if facts:
                tracer.emit("device_facts", facts=facts)
            if lowered is not None and cfg.telemetry_attribution \
                    and cfg.module_scopes:
                # per-module cost rows from the SAME lowered program — a
                # StableHLO text parse, no extra XLA compile
                try:
                    from bigdl_tpu.telemetry import attribution as _attr

                    payload = _attr.attribute_lowered(lowered, self.model)
                    payload["program"] = "train_step"
                    tracer.emit("attribution", **payload)
                except Exception:  # noqa: BLE001 - an observer
                    pass
        if comms_on:
            # Independent of the device-facts level: BIGDL_COMMS has its
            # own off switch, and TELEMETRY_DEVICE=off must not mute it.
            try:
                from bigdl_tpu.telemetry import comms as _comms

                payload = _comms.comms_facts(recompile(),
                                             mesh=self.mesh,
                                             model=self.model)
                payload["program"] = "train_step"
                tracer.emit("comms", **payload)
            except Exception:  # noqa: BLE001 - comms is an observer
                pass
        if memory_on:
            try:
                self._emit_memory_event(tracer, recompile(),
                                        program="train_step")
            except Exception:  # noqa: BLE001 - memory is an observer
                pass

    def _comms_enabled(self, cfg) -> bool:
        """Whether this step emits the per-collective ``comms`` event
        (docs/observability.md): ``BIGDL_COMMS`` on = always, off =
        never, auto = only when the mesh spans more than one device —
        the one case the compiled program contains collectives."""
        mode = (cfg.telemetry_comms or "auto").strip().lower()
        if mode in ("0", "off", "false", "no"):
            return False
        if mode in ("1", "on", "true", "yes"):
            return True
        return self.mesh is not None and self.mesh.devices.size > 1

    def _memory_enabled(self, cfg) -> bool:
        """Whether this step emits the per-step ``memory`` event
        (telemetry/memory.py): ``BIGDL_MEMORY`` on / off / auto, auto =
        multi-device meshes only — where per-device HBM is the scaling
        question and the comms event already pays the shared compile."""
        mode = (cfg.telemetry_memory or "auto").strip().lower()
        if mode in ("0", "off", "false", "no"):
            return False
        if mode in ("1", "on", "true", "yes"):
            return True
        return self.mesh is not None and self.mesh.devices.size > 1

    def _emit_memory_event(self, tracer, compiled, program: str) -> None:
        """One ``memory`` event off an in-hand executable: the walker's
        per-device peak + categories + per-module rows + live allocator
        stats; a ``memory/pressure`` instant when any device's live
        peak is within 5% of its limit."""
        from bigdl_tpu.telemetry import memory as _tmem

        payload = _tmem.memory_facts_compiled(compiled, model=self.model)
        # the event must stay a log line, not a log file: cap the row
        # and buffer tables (the CLI recomputes full tables on demand)
        payload["rows"] = sorted(payload.get("rows", []),
                                 key=lambda r: -r["total_bytes"])[:24]
        payload["largest"] = payload.get("largest", [])[:8]
        payload.pop("timeline", None)
        payload["program"] = program
        tracer.emit("memory", **payload)
        # judged per device against its OWN allocator bytes_limit (the
        # reservation-adjusted ceiling RESOURCE_EXHAUSTED fires
        # against), budget only as the fallback
        hit = _tmem.pressured_device(payload.get("live"),
                                     payload.get("hbm_limit_bytes"))
        if hit:
            tracer.instant("memory/pressure", device=hit["device"],
                           peak_bytes_in_use=hit["peak_bytes"],
                           hbm_limit_bytes=hit["limit_bytes"],
                           pct_of_limit=round(hit["peak_bytes"]
                                              / hit["limit_bytes"]
                                              * 100.0, 2))

    def _emit_sparse_instant(self, tracer) -> None:
        """Once per step object: the sparse-sync accounting recorded at
        trace time (docs/sparse.md) — per-table touched-row caps, the
        bytes the coalesced sync moves, and what the dense table
        all-reduce would have moved."""
        stats = self._sparse_stats
        if not stats:
            return
        st = dict(stats)
        st["rows"] = list(st.get("rows") or [])[:8]
        tracer.instant("train/sparse", **st)

    def _shard_batch(self, x, y):
        if self.mesh is None:
            return jax.tree.map(jnp.asarray, x), jax.tree.map(jnp.asarray, y)
        shard = lambda a: shard_local_batch(self.mesh, a, self.batch_axes)
        return jax.tree.map(shard, x), jax.tree.map(shard, y)

    def _maybe_raise_oom(self, exc: Exception, context: str,
                         x=None, y=None) -> None:
        """RESOURCE_EXHAUSTED from a dispatch (or an AOT compile)
        becomes a ``MemoryExhaustedError`` carrying the postmortem:
        largest known buffers, per-category totals, live-vs-limit —
        flight-dumped before the re-raise (docs/observability.md "my
        job OOMed — what was resident?").  Anything else returns and
        the caller re-raises the original."""
        from bigdl_tpu.telemetry import memory as _tmem

        if not _tmem.is_oom(exc):
            return
        trees = {"params": self.params, "opt_state": self.opt_state,
                 "buffers": self.buffers}
        if x is not None:
            trees["batch_x"] = x
        if y is not None:
            trees["batch_y"] = y
        _tmem.raise_oom(exc, trees, context=context)

    def gather_replicated(self, tree):
        """All-gather cross-process-sharded leaves to replicated (no-op on
        a single-host mesh).  Every process of a multi-host mesh must call
        this — it compiles to a collective; afterwards each leaf is
        addressable everywhere (the reference's getModel reassembly
        crossing the network, ``DistriOptimizer.scala:689-719``)."""
        if self.parameter_sync == "local":
            # local mode: the stacked leaves never replicate — the
            # jitted gather would be a cross-process collective that
            # hangs once a peer is shed.  The island mean over the
            # ADDRESSABLE islands is the local-SGD consensus view.
            return self.island_mean_host(tree)
        if self.mesh is not None and mesh_process_count(self.mesh) > 1:
            tree = jax.jit(lambda t: t,
                           out_shardings=replicated(self.mesh))(tree)
        return tree

    def sync_to_model(self):
        """Write the current params/buffers back into the module tree (the
        reference's getModel reassembly, ``DistriOptimizer.scala:689-719``)."""
        from bigdl_tpu.nn.module import load_state_dict

        if self.parameter_sync == "local":
            state = {**self.island_mean_host(self.params),
                     **self.island_mean_host(self.buffers)}
            load_state_dict(self.model, state, strict=False)
            return
        state = self.gather_replicated({**self.params, **self.buffers})
        load_state_dict(self.model, state, strict=False)


class EvalStep:
    """Compiled inference step sharing the TrainStep's sharding layout."""

    def __init__(self, model: Module, mesh=None, batch_axes=(DATA_AXIS,),
                 compute_dtype=None):
        from bigdl_tpu.nn.module import stamp_scope_names
        from bigdl_tpu.utils.config import get_config

        stamp_scope_names(model, enabled=get_config().module_scopes)
        self.model = model
        self.mesh = mesh
        self.batch_axes = tuple(batch_axes)
        self.compute_dtype = compute_dtype
        self._compiled = None

    def _build(self):
        model = self.model
        cdt = self.compute_dtype

        def fwd(state, x):
            if cdt is not None:
                state = {k: (v.astype(cdt) if jnp.issubdtype(v.dtype, jnp.floating) else v)
                         for k, v in state.items()}
            with _kernel_dispatch.spmd_partitioned(self.mesh):
                out, _ = functional_call(model, state, x, training=False)
            if cdt is not None:
                out = jax.tree.map(
                    lambda a: a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating) else a,
                    out)
            return out

        return jax.jit(fwd)

    def run(self, x):
        if _hooks.hooks_active():
            _hooks.dispatch_event(self, "EvalStep.run", {"x": x})
        if self._compiled is None:
            self._compiled = self._build()
        state = state_dict(self.model)
        if self.mesh is not None:
            x = jax.tree.map(
                lambda a: jax.device_put(
                    jnp.asarray(a), data_sharding(self.mesh, np.ndim(a), self.batch_axes)), x)
        else:
            x = jax.tree.map(jnp.asarray, x)
        tracer = _telemetry.get()
        before = _jit_cache_size(self._compiled) if tracer else None
        t0 = time.perf_counter()
        out = self._compiled(state, x)
        if tracer is not None:
            _note_compile(tracer, self, "EvalStep.run", before, t0,
                          self._compiled)
        if _hooks.hooks_active():
            _hooks.cache_event(self, "EvalStep.run",
                               _jit_cache_size(self._compiled))
        return out
