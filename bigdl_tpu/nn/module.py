"""Core module system for bigdl_tpu.

Capability parity with the reference's ``AbstractModule``
(``nn/abstractnn/AbstractModule.scala:56``): forward/backward, parameter
access and flattening, train/eval modes, freeze/unFreeze, per-layer LR
scales (``setScaleW/B``), cloning, per-module timing, save/load and graph
node building — re-designed for JAX rather than translated:

- Modules are **host-side mutable objects** holding ``jax.Array`` parameters
  (Torch-style user API, like the reference), but every computation is
  expressed through a **pure functional core**: ``functional_call`` binds an
  explicit parameter/buffer pytree, runs ``forward`` under trace, and returns
  the updated state.  Training steps ``jit``/``pjit`` that pure function; the
  mutable API is a thin eager shell over it.
- ``backward`` is derived from ``jax.vjp`` of the pure forward instead of the
  reference's hand-written ``updateGradInput``/``accGradParameters`` chains
  (``AbstractModule.scala:260-297``).  Layers only define ``update_output``.
- Parameters are plain arrays; "shared flattened weight storage" across model
  clones (``DistriOptimizer.scala:566-571``) is unnecessary under SPMD — the
  pjit-sharded param pytree plays that role.
"""

from __future__ import annotations

import copy
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_UNSET = object()  # sentinel for __setattr__ hyper-version tracking

__all__ = [
    "Parameter",
    "Module",
    "Container",
    "Sequential",
    "Identity",
    "Echo",
    "LayerException",
    "functional_call",
    "state_dict",
    "load_state_dict",
    "stamp_scope_names",
    "capture_shapes",
    "summary",
]


class LayerException(RuntimeError):
    """Wraps errors raised inside a layer's forward/backward with the layer
    path, mirroring the reference's ``LayerException`` wrapping in
    ``AbstractModule.forward`` (``AbstractModule.scala:234``)."""

    def __init__(self, layer: str, error: BaseException):
        super().__init__(f"Layer info: {layer}\n{type(error).__name__}: {error}")
        self.layer = layer
        self.error = error


class Parameter:
    """Marker wrapper: assigning ``self.w = Parameter(arr)`` registers ``arr``
    as a trainable parameter of the module."""

    __slots__ = ("data",)

    def __init__(self, data):
        self.data = jnp.asarray(data)


def _is_array(x) -> bool:
    return isinstance(x, (jax.Array, np.ndarray))


class Module:
    """Base class of every layer and container."""

    def __init__(self):
        d = object.__getattribute__(self, "__dict__")
        d["_params"]: Dict[str, jax.Array] = {}
        d["_buffers"]: Dict[str, jax.Array] = {}
        d["_modules"]: Dict[str, "Module"] = {}
        d["_grads"]: Dict[str, jax.Array] = {}
        d["_frozen"] = False
        d["training"] = True
        d["_name"] = None
        d["scale_w"] = 1.0
        d["scale_b"] = 1.0
        d["forward_time"] = 0.0
        d["backward_time"] = 0.0
        d["output"] = None
        d["grad_input"] = None

    # -- attribute routing (torch-style registration) ----------------------
    def __setattr__(self, name, value):
        d = self.__dict__
        if isinstance(value, Parameter):
            d.setdefault("_params", {})[name] = value.data
            d["_modules"].pop(name, None)
            d.pop(name, None)
            return
        if "_params" in d and name in d["_params"]:
            if value is None:
                del d["_params"][name]
                d[name] = None
                return
            d["_params"][name] = jnp.asarray(value)
            return
        if "_buffers" in d and name in d["_buffers"]:
            if value is None:
                del d["_buffers"][name]
                d[name] = None
                return
            d["_buffers"][name] = jnp.asarray(value)
            return
        if isinstance(value, Module):
            d.setdefault("_modules", {})[name] = value
            d.pop(name, None)
            return
        if "_modules" in d and name in d["_modules"] and not isinstance(value, Module):
            del d["_modules"][name]
        # plain-attribute (hyperparameter) edits invalidate memoized
        # backward traces — the value may be baked into a cached jit.
        # Only SCALAR equality short-circuits the bump (container values
        # may hold arrays whose == is elementwise).
        old = d.get(name, _UNSET)
        if not (old is value or (
                isinstance(value, (int, float, str, bool, type(None)))
                and isinstance(old, type(value)) and old == value)):
            d["_hyper_version"] = d.get("_hyper_version", 0) + 1
        d[name] = value

    def __getattr__(self, name):
        # only called when normal lookup fails
        d = object.__getattribute__(self, "__dict__")
        for table in ("_params", "_buffers", "_modules"):
            t = d.get(table)
            if t is not None and name in t:
                return t[name]
        raise AttributeError(f"{type(self).__name__} has no attribute {name!r}")

    def register_buffer(self, name: str, value):
        self.__dict__["_buffers"][name] = jnp.asarray(value)

    def borrow(self, name: str, owner: "Module"):
        """Keep ``owner`` under ``self.<name>`` WITHOUT owning it: a
        module that reads a parameter another module of the same tree
        holds (a vocabulary head tied to the embedding).  ``owner`` is
        no child of this module, so its parameters are listed once,
        under the path the tree already gives them; whatever binds
        state to the tree (``functional_call``, a ``TrainStep``) binds
        it there, both readers see the one array, and its gradient is
        the sum over both uses."""
        self.__dict__[name] = owner

    # -- naming ------------------------------------------------------------
    def set_name(self, name: str) -> "Module":
        self.__dict__["_name"] = name
        return self

    def get_name(self) -> str:
        return self.__dict__["_name"] or f"{type(self).__name__}{abs(id(self)) % 100000}"

    def __repr__(self):
        return f"{type(self).__name__}"

    # -- forward / backward ------------------------------------------------
    def update_output(self, input):
        """Layer computation; subclasses override.  Default: identity."""
        return input

    def forward(self, input):
        from bigdl_tpu.utils.rng import RNG, current_rng_key, rng_context
        import jax as _jax

        t0 = time.perf_counter()
        # cost-attribution scope (docs/observability.md): once a model is
        # stamped (stamp_scope_names — TrainStep/EvalStep do it at build
        # time), every module runs its computation under
        # jax.named_scope(<registration key>), so compiled-HLO op metadata
        # carries the module-tree path.  Scopes are trace-time metadata
        # only: they never enter jit cache keys, so no retraces.
        scope = self.__dict__.get("_scope_name")
        run = self.update_output
        if scope:
            def run(inp, _run=self.update_output, _scope=scope):
                with _jax.named_scope(_scope):
                    return _run(inp)
        try:
            if current_rng_key() is None:
                # Eager call outside any training-step RNG context: install a
                # host-seeded key and remember it so backward() replays the
                # same random realization (dropout masks, RReLU slopes).
                key = _jax.random.key(int(RNG.randint(0, 2**31 - 1)))
                self.__dict__["_last_rng_key"] = key
                with rng_context(key):
                    out = run(input)
            else:
                out = run(input)
        except jax.errors.TracerArrayConversionError:
            raise
        except LayerException:
            raise
        except Exception as e:  # noqa: BLE001 - parity with LayerException wrap
            raise LayerException(self.get_name(), e) from e
        if _SHAPE_CAPTURE:
            # record ABSTRACT shapes only (never the tracers themselves):
            # the capture outlives the trace that produced it
            _SHAPE_CAPTURE[-1][id(self)] = jax.tree.map(
                lambda a: (tuple(jnp.shape(a)),
                           str(getattr(a, "dtype", type(a).__name__))), out)
        self.__dict__["output"] = out
        self.__dict__["forward_time"] += time.perf_counter() - t0
        return out

    __call__ = forward

    def backward(self, input, grad_output):
        """Compute ``gradInput`` and accumulate parameter gradients, via
        ``jax.vjp`` over the pure forward (replaces the reference's
        ``updateGradInput`` + ``accGradParameters``).

        The vjp is compiled and MEMOIZED per module: the trace is keyed on
        every submodule's identity, (training, frozen) flags, and
        hyperparameter version (bumped by ``__setattr__`` on plain-attr
        edits); buffers ride as traced arguments; ``jax.jit`` handles
        shape/dtype variation under each key — so a Torch-style eager loop
        pays tracing once, matching the reference's cheap repeated
        ``backward`` (``AbstractModule.scala:260-297``), while structural
        or hyperparameter edits re-trace automatically."""
        from bigdl_tpu.utils.rng import current_rng_key

        t0 = time.perf_counter()
        params = state_dict(self, kind="param")
        # Replay the key forward() used so the vjp recomputation sees the
        # same random realization the user observed.  An AMBIENT context
        # key must also ride as the traced argument — otherwise the
        # cached jit would bake the first call's key in as a constant and
        # replay stale dropout masks on every later step.
        replay_key = current_rng_key()
        if replay_key is None:
            replay_key = self.__dict__.get("_last_rng_key")

        # functional_call clears trace scratch (_last_rng_key, Recurrent
        # state, ...) — snapshot and restore so eager state survives
        # repeated backward calls and get_hidden_state() after backward
        # (only the TRACE touches python state; cached replays don't)
        scratch = []
        for m in self.modules():
            entry = {}
            if "_last_rng_key" in m.__dict__:
                entry["_last_rng_key"] = m.__dict__["_last_rng_key"]
            for attr in m.__dict__.get("_trace_attrs", ()):
                entry[attr] = m.__dict__.get(attr)
            scratch.append(entry)

        cache = self.__dict__.setdefault("_bwd_cache", {})
        # key: identity + mode + frozen + hyperparameter version of every
        # submodule (attr edits bump _hyper_version via __setattr__), so
        # stale traces cannot be replayed; buffers are traced ARGUMENTS so
        # e.g. BN running stats are always current
        flags = tuple((id(m), m.training, m.__dict__["_frozen"],
                       m.__dict__.get("_hyper_version", 0))
                      for m in self.modules())
        ckey = (replay_key is not None, flags)
        buffers = state_dict(self, kind="buffer")
        if ckey not in cache:
            def bwd_fn(p, bufs, inp, gout, key):
                def fn(p2, i2):
                    out, _ = functional_call(self, {**p2, **bufs}, i2,
                                             rng=key)
                    return out

                out, vjp = jax.vjp(fn, p, inp)
                tangent = jax.tree.map(
                    lambda o, g: jnp.asarray(g, o.dtype) if g is not None
                    else jnp.zeros_like(o), out, gout)
                return vjp(tangent)

            cache.clear()  # one live trace per module keeps memory bounded
            cache[ckey] = jax.jit(bwd_fn)
        p_grads, grad_input = cache[ckey](params, buffers, input,
                                          grad_output, replay_key)
        for m, entry in zip(self.modules(), scratch):
            for attr, val in entry.items():
                m.__dict__[attr] = val
        if not self.__dict__["_frozen"]:
            self._accumulate_grads(p_grads)
        self.__dict__["grad_input"] = grad_input
        self.__dict__["backward_time"] += time.perf_counter() - t0
        return grad_input

    def update_grad_input(self, input, grad_output):
        return self.backward(input, grad_output)

    def _accumulate_grads(self, path_grads: Dict[str, jax.Array]):
        for path, g in path_grads.items():
            mod, leaf = _resolve(self, path)
            if mod.__dict__["_frozen"]:
                continue
            scale = mod.scale_b if leaf == "bias" else mod.scale_w
            prev = mod.__dict__["_grads"].get(leaf)
            g = g * scale if scale != 1.0 else g
            mod.__dict__["_grads"][leaf] = g if prev is None else prev + g

    # -- parameters --------------------------------------------------------
    def named_parameters(self, prefix: str = "") -> Iterator[Tuple[str, jax.Array]]:
        for k, v in self.__dict__["_params"].items():
            yield prefix + k, v
        for name, m in self.__dict__["_modules"].items():
            yield from m.named_parameters(prefix + name + ".")

    def parameters(self) -> Tuple[List[jax.Array], List[jax.Array]]:
        """(weights, gradients) — mirrors ``AbstractModule.parameters``."""
        ws, gs = [], []
        for path, w in self.named_parameters():
            mod, leaf = _resolve(self, path)
            g = mod.__dict__["_grads"].get(leaf)
            ws.append(w)
            gs.append(g if g is not None else jnp.zeros_like(w))
        return ws, gs

    def get_parameters(self) -> Tuple[jax.Array, jax.Array]:
        """Flattened (weights, grads) 1-D views, mirroring
        ``AbstractModule.getParameters`` (``AbstractModule.scala:313``)."""
        ws, gs = self.parameters()
        if not ws:
            return jnp.zeros((0,)), jnp.zeros((0,))
        flat_w = jnp.concatenate([jnp.ravel(w) for w in ws])
        flat_g = jnp.concatenate([jnp.ravel(g) for g in gs])
        return flat_w, flat_g

    def set_flat_parameters(self, flat: jax.Array):
        offset = 0
        for path, w in list(self.named_parameters()):
            n = int(np.prod(w.shape)) if w.ndim else 1
            mod, leaf = _resolve(self, path)
            mod.__dict__["_params"][leaf] = flat[offset : offset + n].reshape(w.shape).astype(w.dtype)
            offset += n

    def zero_grad_parameters(self):
        for m in self.modules():
            m.__dict__["_grads"].clear()

    def update_parameters(self, lr: float):
        for path, w in list(self.named_parameters()):
            mod, leaf = _resolve(self, path)
            g = mod.__dict__["_grads"].get(leaf)
            if g is not None:
                mod.__dict__["_params"][leaf] = w - lr * g

    # -- modes / traversal -------------------------------------------------
    def modules(self) -> Iterator["Module"]:
        yield self
        for m in self.__dict__["_modules"].values():
            yield from m.modules()

    def named_modules(self, prefix: str = "") -> Iterator[Tuple[str, "Module"]]:
        yield prefix.rstrip("."), self
        for name, m in self.__dict__["_modules"].items():
            yield from m.named_modules(prefix + name + ".")

    def training_mode(self) -> "Module":
        for m in self.modules():
            m.__dict__["training"] = True
        return self

    # reference naming: model.training() / model.evaluate()
    def train(self) -> "Module":
        return self.training_mode()

    def evaluate(self) -> "Module":
        for m in self.modules():
            m.__dict__["training"] = False
        return self

    def is_training(self) -> bool:
        return self.__dict__["training"]

    def freeze(self) -> "Module":
        for m in self.modules():
            m.__dict__["_frozen"] = True
        return self

    def unfreeze(self) -> "Module":
        for m in self.modules():
            m.__dict__["_frozen"] = False
        return self

    def is_frozen(self) -> bool:
        return self.__dict__["_frozen"]

    def set_scale_w(self, s: float) -> "Module":
        self.__dict__["scale_w"] = s
        return self

    def set_scale_b(self, s: float) -> "Module":
        self.__dict__["scale_b"] = s
        return self

    # -- init --------------------------------------------------------------
    def reset(self):
        """Re-initialise parameters; layers with weights override."""
        for m in self.__dict__["_modules"].values():
            m.reset()

    def set_init_method(self, weight_init=None, bias_init=None) -> "Module":
        if weight_init is not None:
            self.__dict__["weight_init"] = weight_init
        if bias_init is not None:
            self.__dict__["bias_init"] = bias_init
        self.reset()
        return self

    # -- timing (getTimes parity) -----------------------------------------
    def get_times(self) -> List[Tuple["Module", float, float]]:
        return [(m, m.__dict__["forward_time"], m.__dict__["backward_time"]) for m in self.modules()]

    def reset_times(self):
        for m in self.modules():
            m.__dict__["forward_time"] = 0.0
            m.__dict__["backward_time"] = 0.0

    # -- cloning / persistence --------------------------------------------
    def clone_module(self) -> "Module":
        return copy.deepcopy(self)

    def save(self, path: str, overwrite: bool = False):
        from bigdl_tpu.utils.serializer import save_module

        save_module(self, path, overwrite=overwrite)
        return self

    # -- graph building ----------------------------------------------------
    def inputs(self, *nodes):
        """Build a graph ``Node`` from predecessor nodes — the functional-API
        builder mirroring ``AbstractModule.inputs`` (``AbstractModule.scala:607``)."""
        from bigdl_tpu.nn.graph import node_from_module

        return node_from_module(self, nodes)

    def __getitem__(self, name):
        for n, m in self.named_modules():
            if m.__dict__["_name"] == name or n == name:
                return m
        raise KeyError(name)

    def summary(self, input_spec=None) -> str:
        """Torch-style per-layer table (path, class, output shape via
        ``jax.eval_shape``, param count/bytes) — see
        :func:`bigdl_tpu.nn.module.summary`."""
        return summary(self, input_spec)

    # -- prediction / evaluation (single-process convenience) -------------
    def predict(self, dataset, batch_size: int = 32):
        from bigdl_tpu.optim.predictor import LocalPredictor

        return LocalPredictor(self, batch_size=batch_size).predict(dataset)

    def predict_class(self, dataset, batch_size: int = 32):
        from bigdl_tpu.optim.predictor import LocalPredictor

        return LocalPredictor(self, batch_size=batch_size).predict_class(dataset)

    def evaluate_on(self, dataset, methods, batch_size: int = 32):
        from bigdl_tpu.optim.evaluator import Evaluator

        return Evaluator(self, batch_size=batch_size).evaluate(dataset, methods)


# --------------------------------------------------------------------------
# Module paths: cost-attribution scopes + shape capture + summary
# --------------------------------------------------------------------------

#: stack of active shape-capture dicts (id(module) -> output shape pytree);
#: a plain module global so Module.forward pays one falsy check when off.
_SHAPE_CAPTURE: List[Dict[int, Any]] = []


def stamp_scope_names(root: Module, enabled: bool = True) -> Module:
    """Stamp every submodule with its registration key so
    :meth:`Module.forward` wraps its computation in
    ``jax.named_scope(<key>)`` — nesting reproduces the full module path
    (``features/0/conv1``) in compiled-HLO op metadata, the substrate of
    per-module cost attribution (``telemetry/attribution.py``).

    Labels are the ``_modules`` registration keys, so a scope path joined
    with ``.`` equals the ``named_parameters`` path of the same module.
    The root carries no scope (its children are the first frame).  A
    weight-shared module registered under several paths keeps the first
    label — its usages aggregate under one row.  ``enabled=False`` clears
    the stamps (``BIGDL_SCOPES=off``)."""
    seen = {id(root)}
    for name, m in root.named_modules():
        if not name:
            continue
        if not enabled:
            m.__dict__.pop("_scope_name", None)
            continue
        if id(m) in seen:  # weight sharing: first path wins
            continue
        seen.add(id(m))
        # __dict__ write, NOT __setattr__: stamping must not bump
        # _hyper_version (that would invalidate memoized backward traces)
        m.__dict__["_scope_name"] = name.rsplit(".", 1)[-1]
    return root


@contextmanager
def capture_shapes():
    """Collect each module's output shapes during the forwards run inside
    the block — yields ``{id(module): pytree of (shape, dtype)}``.  Safe
    under ``jax.eval_shape``: only abstract shapes are stored."""
    cap: Dict[int, Any] = {}
    _SHAPE_CAPTURE.append(cap)
    try:
        yield cap
    finally:
        # remove by IDENTITY: list.remove uses ==, and two empty capture
        # dicts compare equal — equality removal could strip another
        # active capture's dict under concurrency/nesting
        for i in range(len(_SHAPE_CAPTURE) - 1, -1, -1):
            if _SHAPE_CAPTURE[i] is cap:
                del _SHAPE_CAPTURE[i]
                break


def summary(module: Module, input_spec=None) -> str:
    """Torch-style per-layer table: module path, class, output shape,
    own-parameter count/bytes, trainable flag.

    ``input_spec``: a (pytree of) ``jax.ShapeDtypeStruct`` (or concrete
    arrays) fed through ``jax.eval_shape`` — no data, no compile.  When
    omitted the output-shape column is skipped (parameters only).

    The table needs no scope stamping (shape capture keys on module
    identity), so a ``BIGDL_SCOPES=off`` choice is left untouched."""
    shapes: Dict[int, Any] = {}
    if input_spec is not None:
        state = state_dict(module)

        def fwd(x):
            return functional_call(module, state, x, training=False)[0]

        with capture_shapes() as shapes:
            jax.eval_shape(fwd, input_spec)

    def _fmt_shape(tree) -> str:
        leaves = jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
            and isinstance(x[0], tuple) and isinstance(x[1], str))
        return ", ".join(f"{list(s)} {d}" for s, d in leaves) or "?"

    rows = []
    total_params = total_bytes = 0
    for name, m in module.named_modules():
        own = m.__dict__["_params"]
        n_params = sum(int(np.prod(p.shape)) if p.ndim else 1
                       for p in own.values())
        n_bytes = sum(int(getattr(p, "nbytes", 0)) for p in own.values())
        total_params += n_params
        total_bytes += n_bytes
        rows.append((name or "(root)", type(m).__name__,
                     _fmt_shape(shapes.get(id(m))) if shapes else "-",
                     n_params, n_bytes,
                     "frozen" if m.__dict__["_frozen"] else "train"))
    widths = [max(len(str(r[i])) for r in rows) for i in range(3)]
    lines = [f"{'module':<{widths[0]}}  {'class':<{widths[1]}}  "
             f"{'output shape':<{widths[2]}}  {'params':>10}  "
             f"{'bytes':>12}  mode"]
    lines.append("-" * len(lines[0]))
    for path, cls, shape, n, b, mode in rows:
        lines.append(f"{path:<{widths[0]}}  {cls:<{widths[1]}}  "
                     f"{shape:<{widths[2]}}  {n:>10}  {b:>12}  {mode}")
    lines.append("-" * len(lines[0]))
    lines.append(f"total parameters: {total_params:,}  "
                 f"({total_bytes:,} bytes)")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# Functional core
# --------------------------------------------------------------------------

def _resolve(root: Module, path: str) -> Tuple[Module, str]:
    parts = path.split(".")
    mod = root
    for p in parts[:-1]:
        mod = mod.__dict__["_modules"][p]
    return mod, parts[-1]


def state_dict(module: Module, kind: str = "all", prefix: str = "") -> Dict[str, jax.Array]:
    """Collect ``{path: array}`` for params and/or buffers."""
    out: Dict[str, jax.Array] = {}
    if kind in ("all", "param"):
        for k, v in module.__dict__["_params"].items():
            out[prefix + k] = v
    if kind in ("all", "buffer"):
        for k, v in module.__dict__["_buffers"].items():
            out[prefix + k] = v
    for name, m in module.__dict__["_modules"].items():
        out.update(state_dict(m, kind, prefix + name + "."))
    return out


def load_state_dict(module: Module, state: Dict[str, Any], strict: bool = True):
    """Load ``{path: array}`` into the module tree.

    Under ``strict=True`` ALL missing and unexpected keys are collected
    and reported in ONE ``KeyError`` (instead of failing on the first),
    so a checkpoint/analyzer mismatch is actionable in one shot."""
    own = state_dict(module)
    unexpected = [path for path in state if path not in own]
    for path, v in state.items():
        if path not in own:
            continue
        mod, leaf = _resolve(module, path)
        if leaf in mod.__dict__["_params"]:
            mod.__dict__["_params"][leaf] = v if isinstance(v, jax.Array) else jnp.asarray(v)
        elif leaf in mod.__dict__["_buffers"]:
            mod.__dict__["_buffers"][leaf] = v if isinstance(v, jax.Array) else jnp.asarray(v)
    if strict:
        missing = sorted(set(own) - set(state))
        if missing or unexpected:
            parts = []
            if missing:
                parts.append(f"missing keys in state: {missing}")
            if unexpected:
                parts.append(
                    f"no parameter/buffer in {type(module).__name__} for "
                    f"unexpected keys: {sorted(unexpected)}")
            raise KeyError("; ".join(parts))


def _clear_outputs(module: Module):
    for m in module.modules():
        m.__dict__["output"] = None
        m.__dict__["grad_input"] = None
        # forward() may have stored a replay key; under trace it is a tracer
        # (jax.random.key stages to the ambient trace) and must not survive
        m.__dict__.pop("_last_rng_key", None)
        # clear any module-specific trace-time scratch (e.g. Recurrent's
        # final scan state) so tracers never leak out of functional_call
        for attr in m.__dict__.get("_trace_attrs", ()):
            m.__dict__[attr] = None


def functional_call(
    module: Module,
    state: Dict[str, jax.Array],
    input,
    training: Optional[bool] = None,
    rng=None,
) -> Tuple[Any, Dict[str, jax.Array]]:
    """Pure-function view of ``module.forward``.

    Binds ``state`` (params and, optionally, buffers) onto the module tree,
    runs forward, collects the (possibly updated) buffer state, then restores
    the module's original concrete arrays.  Safe to trace under
    ``jit``/``pjit``/``grad``; this is the bridge from the Torch-style
    mutable API to the functional JAX core.

    Returns ``(output, new_state)`` where ``new_state`` covers the same keys
    as ``state`` with post-forward values (buffers may have advanced).
    """
    from bigdl_tpu.utils.rng import rng_context

    original = state_dict(module)
    unknown = set(state) - set(original)
    if unknown:
        raise KeyError(
            f"functional_call: state contains keys not present in "
            f"{type(module).__name__}: {sorted(unknown)}")
    modes = None
    if training is not None:
        modes = [m.__dict__["training"] for m in module.modules()]
        for m in module.modules():
            m.__dict__["training"] = training
    try:
        load_state_dict(module, state, strict=False)
        if rng is not None:
            with rng_context(rng):
                out = module.forward(input)
        else:
            out = module.forward(input)
        full = state_dict(module)
        new_state = {k: full[k] for k in state}
        return out, new_state
    finally:
        load_state_dict(module, original, strict=False)
        _clear_outputs(module)
        if modes is not None:
            for m, t in zip(module.modules(), modes):
                m.__dict__["training"] = t


# --------------------------------------------------------------------------
# Containers
# --------------------------------------------------------------------------

class Container(Module):
    """Base of composite modules (``nn/Container.scala:40``)."""

    def __init__(self, *modules: Module):
        super().__init__()
        for m in modules:
            self.add(m)

    def add(self, module: Module) -> "Container":
        idx = len(self.__dict__["_modules"])
        self.__dict__["_modules"][str(idx)] = module
        return self

    @property
    def layers(self) -> List[Module]:
        return list(self.__dict__["_modules"].values())

    def __len__(self):
        return len(self.__dict__["_modules"])

    def get(self, i: int) -> Module:
        return self.layers[i]


class Sequential(Container):
    """Chain container (``nn/Sequential.scala:30``)."""

    def update_output(self, input):
        out = input
        for m in self.layers:
            out = m.forward(out)
        return out


class Identity(Module):
    """Pass-through (``nn/Identity.scala``)."""


class Echo(Module):
    """Identity that prints its input's shape when eager (``nn/Echo.scala``)."""

    def update_output(self, input):
        try:
            print(f"Echo[{self.get_name()}]: shape={jnp.shape(input)}")
        except Exception:  # noqa: BLE001
            pass
        return input
