"""Kernel dispatch: one knob, per-op fallback, observable decisions.

Three ops keep TWO implementations, both live: flash attention (through
its router, ``ops/attention.py select_attention_backend``), the gated
delta rule (``ops/delta_rule.py``) and the state-space scan
(``ops/ssd.py``).  Each has a Pallas kernel (Mosaic-compiled on a TPU
off a mesh, ``interpret=True`` elsewhere so the CPU tier-1 suite
exercises the identical code path) and an XLA form of the same math (the
CPU, a partitioned step, a shape the kernel does not take), both UNDER
the op's ``jax.custom_vjp``; this module decides which runs.

Knob: ``BIGDL_KERNELS`` (read at trace time):

- ``auto`` (default) — Pallas on TPU hardware when the op's support
  predicate admits the shape/dtype and the step is not partitioned over
  a multi-device mesh; XLA everywhere else.  CPU runs keep
  their fused-XLA paths, so enabling telemetry or running the tier-1
  suite never silently drops onto the (slow) Pallas interpreter.
- ``pallas`` — Pallas whenever the shape is structurally supported;
  off-TPU this means interpret mode (the parity tests' setting).
- ``xla`` — the XLA form everywhere, a process-wide kill switch.

Every decision is emitted as a ``kernel/dispatch`` telemetry instant
(op, backend, reason) at TRACE time — one instant per compilation, not
per step — so PR 4's attribution can say which backend each module's
HLO actually contains.  The reasons: ``forced:BIGDL_KERNELS=<mode>``,
``unsupported-shape``, ``auto:tpu``, ``auto:off-tpu``,
``auto:spmd-partitioned`` from :func:`choose_backend`; from an op that
has one form and says so through :func:`note`, ``only-leg`` (the
short convolution, both LRNs, the Torch-legacy normalisations'
smoothing, the tie-split max pool and the average pool: the knob does
not reach them in any mode) and ``whole-plane`` (an average pool whose
window is the whole padded plane: a fused reduction, ``pool.avg_pool``).
A leg adds what it was launched with through :func:`launched` (``ssd``:
its chunking, ``head_block`` and ``grid``).  A small in-process ring
(:func:`decisions`) records the same for tests.

Caveat: the knob is read when a function is traced.  A jit-cached
executable does not re-dispatch when the env changes; tests flip the
env with fresh shapes (or eagerly) for exactly this reason.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
from collections import deque
from typing import Callable, Deque, Dict, List, Tuple

from bigdl_tpu import telemetry

__all__ = ["kernel_mode", "choose_backend", "dispatch", "use_interpret",
           "spmd_partitioned", "auto_pallas", "launched", "Decision",
           "decisions", "clear_decisions", "MODES"]

MODES = ("auto", "pallas", "xla")


class Decision(tuple):
    """One ``(op, backend, reason)`` triple — it unpacks, compares and
    sorts as that — which also carries, in ``launch``, what the leg
    said of itself (``ssd``: ``head_block`` and ``grid``; empty for a
    leg that reports nothing)."""

    launch: Dict[str, object]

    def __new__(cls, op: str, backend: str, reason: str, **launch):
        self = super().__new__(cls, (op, backend, reason))
        self.launch = launch
        return self


#: last N decisions, trace-time order
_DECISIONS: Deque[Decision] = deque(maxlen=256)

#: the launch facts of the leg :func:`dispatch` is running, while it runs
_LAUNCH = contextvars.ContextVar("bigdl_kernel_launch", default=None)

#: True while the current thread traces a step that XLA will partition
#: over a multi-device mesh (see :func:`spmd_partitioned`)
_SPMD = contextvars.ContextVar("bigdl_spmd_partitioned", default=False)


@contextlib.contextmanager
def spmd_partitioned(mesh):
    """Trace-time scope for a step whose jit XLA partitions over
    ``mesh`` (``TrainStep``/``EvalStep`` enter it around the model
    call).  The TPU compiler refuses a Mosaic kernel there — "Mosaic
    kernels cannot be automatically partitioned.  Please wrap the call
    in a shard_map" — so inside the scope, on more than one device,
    ``auto`` takes the XLA leg and records why.  Code already under a
    ``shard_map`` (local-SGD islands, ring attention) is per-device and
    does not enter it."""
    token = _SPMD.set(mesh is not None and mesh.size > 1)
    try:
        yield
    finally:
        _SPMD.reset(token)


def kernel_mode() -> str:
    """The process-wide kernel mode from ``BIGDL_KERNELS``.

    Raises on an unknown value instead of silently defaulting — a typo'd
    sweep leg comparing ``pallas`` against ``palas`` must fail loudly,
    not bench two identical XLA runs (same policy as
    ``flash_min_seq``)."""
    raw = os.environ.get("BIGDL_KERNELS", "auto")
    if raw not in MODES:
        raise ValueError(
            f"BIGDL_KERNELS={raw!r} is not one of {'|'.join(MODES)}")
    return raw


def use_interpret() -> bool:
    """Pallas interpret mode off-TPU."""
    from bigdl_tpu.ops.attention import is_tpu_device

    return not is_tpu_device()


def choose_backend(op: str, supported: bool) -> Tuple[str, str]:
    """(backend, reason) for one op instance; backend in {pallas, xla}."""
    mode = kernel_mode()
    if mode == "xla":
        return "xla", "forced:BIGDL_KERNELS=xla"
    if not supported:
        return "xla", "unsupported-shape"
    if mode == "pallas":
        return "pallas", "forced:BIGDL_KERNELS=pallas"
    ok, reason = auto_pallas()
    return ("pallas" if ok else "xla"), reason


def auto_pallas() -> Tuple[bool, str]:
    """The ``auto`` mode's rule, shared with the attention router: a
    Pallas kernel on TPU hardware, unless XLA is about to partition the
    program over a mesh (:func:`spmd_partitioned`)."""
    from bigdl_tpu.ops.attention import is_tpu_device

    if not is_tpu_device():
        return False, "auto:off-tpu"
    if _SPMD.get():
        return False, "auto:spmd-partitioned"
    return True, "auto:tpu"


def note(op: str, backend: str, reason: str, **launch) -> None:
    """Record + emit one dispatch decision (shared by :func:`dispatch`
    and call sites with one leg or selection logic of their own, e.g.
    the attention auto-backend)."""
    _DECISIONS.append(Decision(op, backend, reason, **launch))
    telemetry.instant("kernel/dispatch", op=op, backend=backend,
                      reason=reason, **launch)


def launched(**facts) -> None:
    """A leg's word on how it was launched, at trace time: ``ssd``
    reports its chunking, ``head_block`` and ``grid`` here, and they
    ride on that leg's decision.  Outside :func:`dispatch` (a kernel
    called bare) there is no decision to ride on."""
    holder = _LAUNCH.get()
    if holder is not None:
        holder.update(facts)


def dispatch(op: str, pallas_fn: Callable, xla_fn: Callable,
             supported: bool, *args, **kwargs):
    """Run ``pallas_fn`` or ``xla_fn`` per :func:`choose_backend`,
    recording the decision — once the leg is traced, so that it holds
    what the leg :func:`launched`.  Called at trace time
    inside the op's custom-vjp forward/backward rules."""
    backend, reason = choose_backend(op, supported)
    fn = pallas_fn if backend == "pallas" else xla_fn
    launch: Dict[str, object] = {}
    token = _LAUNCH.set(launch)
    try:
        return fn(*args, **kwargs)
    finally:
        _LAUNCH.reset(token)
        note(op, backend, reason, **launch)


def decisions() -> List[Decision]:
    """Recent decisions, each an (op, backend, reason) triple with its
    ``launch`` facts — test/bench introspection."""
    return list(_DECISIONS)


def clear_decisions() -> None:
    _DECISIONS.clear()
