"""Pass 3 — retrace/recompile detection for TrainStep/EvalStep.

``with trace_retraces() as mon:`` registers a monitor on the dispatch
hook points inside ``parallel/train_step.py``.  Every ``TrainStep.run``/
``EvalStep.run`` reports its raw host arguments; the monitor
computes each leaf's *effective abstract value* (shape, dtype, weak
typing — exactly the jit cache key ingredients) and, when a later
dispatch differs, emits a Diagnostic naming the argument and the cause:

- ``retrace/shape-change``   — new static shape (or pytree structure),
- ``retrace/dtype-change``   — new dtype,
- ``retrace/weak-type``      — weak/strong flip for the same dtype,
- ``retrace/python-scalar``  — the flip came from a Python scalar
  alternating with an array,
- ``retrace/recompile``      — the jit executable cache grew with no
  visible argument change (hyperparameter edit / structural re-trace).

This replaces staring at ``jax.log_compiles`` output with an answer to
the actual question: *which argument* caused the retrace.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

from bigdl_tpu.analysis import hooks
from bigdl_tpu.analysis.diagnostics import Report

__all__ = ["trace_retraces", "RetraceMonitor"]


class _LeafSig(NamedTuple):
    shape: Tuple[int, ...]
    dtype: str
    weak: bool
    py_scalar: bool


def _leaf_signature(x) -> _LeafSig:
    import jax.numpy as jnp

    if isinstance(x, (bool, int, float, complex)):
        # a Python scalar enters jit as a weak-typed 0-d constant
        return _LeafSig((), jnp.result_type(type(x)).name, True, True)
    if isinstance(x, np.ndarray) or np.isscalar(x):
        a = np.asarray(x)
        return _LeafSig(tuple(a.shape), a.dtype.name, False, False)
    shape = tuple(getattr(x, "shape", ()))
    dtype = getattr(x, "dtype", None)
    weak = bool(getattr(x, "weak_type", False))
    # str(), not jnp.dtype(): PRNG keys carry extended dtypes ('key<fry>')
    # that numpy's dtype constructor rejects
    return _LeafSig(shape, str(dtype) if dtype is not None else "object",
                    weak, False)


def _signature(args: Dict[str, Any]) -> Dict[str, _LeafSig]:
    import jax

    out: Dict[str, _LeafSig] = {}
    for name, tree in args.items():
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        for path, leaf in leaves:
            key = name + "".join(str(p) for p in path)
            out[key] = _leaf_signature(leaf)
    return out


class RetraceMonitor:
    """Collects retrace diagnostics; use via :func:`trace_retraces`."""

    def __init__(self, suppress=()):
        self.report = Report(suppress=suppress)
        self._seen: Dict[Tuple[int, str], Dict[str, _LeafSig]] = {}
        self._cache_sizes: Dict[Tuple[int, str], int] = {}
        self._dispatch_flagged: Dict[Tuple[int, str], bool] = {}
        self.dispatches = 0

    # -- hook callbacks ----------------------------------------------------
    def on_dispatch(self, owner, kind: str, args: Dict[str, Any]) -> None:
        self.dispatches += 1
        key = (id(owner), kind)
        sig = _signature(args)
        prev = self._seen.get(key)
        self._seen[key] = sig
        flagged = False
        if prev is not None:
            flagged = self._diff(kind, prev, sig)
        self._dispatch_flagged[key] = flagged

    def on_cache(self, owner, kind: str, size: int) -> None:
        key = (id(owner), kind)
        prev = self._cache_sizes.get(key)
        self._cache_sizes[key] = size
        if prev is not None and size > prev \
                and not self._dispatch_flagged.get(key, False):
            self.report.add(
                "retrace/recompile",
                f"{kind} recompiled (jit cache {prev} -> {size}) with no "
                f"argument shape/dtype change",
                where=kind,
                hint="a module hyperparameter or structure edit between "
                     "dispatches forces a re-trace")

    # -- diffing -----------------------------------------------------------
    def _diff(self, kind: str, prev: Dict[str, _LeafSig],
              cur: Dict[str, _LeafSig]) -> bool:
        flagged = False
        if set(prev) != set(cur):
            added = sorted(set(cur) - set(prev))
            gone = sorted(set(prev) - set(cur))
            self.report.add(
                "retrace/shape-change",
                f"argument pytree structure changed "
                f"(+{added or '[]'} -{gone or '[]'}) — every structure "
                f"recompiles",
                where=kind)
            return True
        for name in sorted(cur):
            p, c = prev[name], cur[name]
            if p == c:
                continue
            where = f"{kind}({name})"
            if p.dtype.startswith("static=") or \
                    c.dtype.startswith("static="):
                self.report.add(
                    "retrace/shape-change",
                    f"static argument changed {p.dtype[7:]} -> "
                    f"{c.dtype[7:]}; each distinct value compiles its "
                    f"own executable",
                    where=where,
                    hint="hold static/config arguments constant across "
                         "the hot loop")
                flagged = True
                continue
            if p.shape != c.shape:
                self.report.add(
                    "retrace/shape-change",
                    f"shape changed {list(p.shape)} -> {list(c.shape)}; "
                    f"each distinct shape compiles its own executable",
                    where=where,
                    hint="pad/bucket batches to a fixed set of shapes")
            elif p.dtype != c.dtype:
                self.report.add(
                    "retrace/dtype-change",
                    f"dtype changed {p.dtype} -> {c.dtype}",
                    where=where,
                    hint="convert once at the input pipeline boundary, "
                         "not per-step")
            elif p.weak != c.weak:
                if p.py_scalar or c.py_scalar:
                    self.report.add(
                        "retrace/python-scalar",
                        f"a Python scalar ({p.dtype}) alternates with an "
                        f"array here; the weak/strong type flip "
                        f"recompiles every flip",
                        where=where,
                        hint="pass jnp.asarray(value, dtype) consistently")
                else:
                    self.report.add(
                        "retrace/weak-type",
                        f"weak_type flipped {p.weak} -> {c.weak} for "
                        f"dtype {c.dtype}",
                        where=where,
                        hint="jnp.asarray with an explicit dtype makes "
                             "the type strong")
            else:
                continue
            flagged = True
        return flagged


class trace_retraces:
    """Context manager: ``with trace_retraces() as mon: ... mon.report``."""

    def __init__(self, suppress=()):
        self.monitor = RetraceMonitor(suppress=suppress)

    def __enter__(self) -> RetraceMonitor:
        hooks.register(self.monitor)
        return self.monitor

    def __exit__(self, *exc) -> Optional[bool]:
        hooks.unregister(self.monitor)
        return None
