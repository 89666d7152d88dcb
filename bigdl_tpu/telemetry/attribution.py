"""Per-module cost attribution: where inside the model the FLOPs, bytes,
and ops of a compiled step go.

The nn layer stamps every module with its registration key
(``nn.stamp_scope_names``, done by TrainStep/EvalStep at build time), so
``Module.forward`` runs each layer under ``jax.named_scope(<key>)`` and
the lowered program's op locations carry the module-tree path::

    loc("jit(step)/jit(main)/jvp(4)/conv_general_dilated")          # fwd
    loc("jit(step)/jit(main)/transpose(jvp(4))/conv_general_dilated")  # bwd

This module parses the lowered StableHLO text (``Lowered.compiler_ir()``
printed with debug info — a re-lower of the already-traced step, NO XLA
compile), groups ops by their scope frames (autodiff wrappers
``jvp(...)``/``transpose(...)`` unwrap onto the same module, tagged
forward/backward; function frames like ``jit(log_softmax)`` fall out to
the unattributed bucket), and estimates per-op cost
HloCostAnalysis-style:

- ``dot_general``: ``2 * out_elems * prod(contracted dims)``;
- ``convolution``: ``2 * out_elems * prod(non-output kernel dims)``;
- elementwise arithmetic: ``out_elems`` flops; transcendentals
  (tanh/exp/...) are tracked in their own column, as XLA does;
- ``reduce``/``reduce_window``: one flop per folded element;
- data movement (reshape/broadcast/slice/...): bytes only.

Bytes are pre-fusion operand+output traffic — an upper bound on real
HBM movement (fusion keeps intermediates in registers), useful for
*ranking* modules, not billing.  The report always prints its FLOPs
total next to XLA's own ``cost_analysis()`` so the estimate's fidelity
is visible.

Scopes are trace-time metadata only — they never enter jit cache keys,
so enabling them causes zero retraces (``tests/test_attribution.py``
asserts this).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["lowered_text", "parse_lowered_text", "aggregate",
           "module_rows", "attribute_lowered", "attribute_train_step",
           "attribute_forward", "attribute_model", "format_attribution",
           "rows_from_events", "scope_of"]

_DTYPE_BYTES = {
    "i1": 1, "i4": 1, "ui4": 1, "i8": 1, "ui8": 1,
    "i16": 2, "ui16": 2, "f16": 2, "bf16": 2,
    "i32": 4, "ui32": 4, "f32": 4,
    "i64": 8, "ui64": 8, "f64": 8,
}

#: pure data movement / bookkeeping — no flops.
_NO_FLOPS = {
    "constant", "iota", "broadcast_in_dim", "broadcast", "reshape",
    "transpose", "slice", "concatenate", "pad", "gather", "convert",
    "bitcast_convert", "reverse", "dynamic_slice", "dynamic_update_slice",
    "rng_bit_generator", "optimization_barrier", "return", "call",
    "custom_call", "tuple", "get_tuple_element", "real", "imag",
    "all_gather", "all_reduce", "reduce_scatter", "collective_permute",
    "all_to_all", "partition_id", "replica_id", "create_token",
    "after_all", "composite", "while", "if", "case",
}

_TRANSCENDENTAL = {
    "exponential", "exponential_minus_one", "log", "log_plus_one",
    "logistic", "tanh", "sqrt", "rsqrt", "cbrt", "power", "sine",
    "cosine", "tan", "tanh_approx", "atan2", "erf", "erf_inv",
}

# "%12 = stablehlo.add ..." / '%12 = "stablehlo.reduce_window"(...' /
# "%12 = call @f(...)" (func.call at a function's top level, printed bare)
_OP_RE = re.compile(
    r"^\s*%[\w#]+(?::\d+)?\s*=\s*\"?"
    r"(?:(?:stablehlo|chlo|mhlo|func)\.|(?=call\s+@))([\w]+)\"?")
_FUNC_RE = re.compile(r"^\s*func\.func\s+(?:public\s+|private\s+)?@([\w.$\-]+)")
_CALLEE_RE = re.compile(r"call\s+@([\w.$\-]+)")
_LOC_REF_RE = re.compile(r"loc\((#loc\d*)\)\s*$")
_LOC_DEF_RE = re.compile(r"^(#loc\d*)\s*=\s*loc\((.*)\)\s*$")
_LOC_NAME_RE = re.compile(r'^"([^"]*)"')
_LOC_CHILD_RE = re.compile(r"(#loc\d*)")
_TENSOR_RE = re.compile(r"tensor<([^>]*)>")
_CONTRACT_RE = re.compile(r"contracting_dims\s*=\s*\[([0-9,\s]*)\]")
_DIMNUM_RE = re.compile(r"dim_numbers\s*=\s*\[([\w,\s]*)\]"
                        r"\s*x\s*\[([\w,\s]*)\]\s*->\s*\[([\w,\s]*)\]")
_STRIDE_RE = re.compile(r"stride\s*=\s*\[([0-9,\s]*)\]")
_PAD_RE = re.compile(r"pad\s*=\s*\[\[(.*?)\]\]")
_LHS_DIL_RE = re.compile(r"lhs_dilate\s*=\s*\[([0-9,\s]*)\]")
_RHS_DIL_RE = re.compile(r"rhs_dilate\s*=\s*\[([0-9,\s]*)\]")
_WINDOW_DIMS_RE = re.compile(r"window_dimensions\s*=\s*(?:array<i64:"
                             r"\s*([0-9,\s]*)>|\[([0-9,\s]*)\])")
# autodiff / transform wrappers that carry the scope through: unwrap and
# keep the payload.  transpose() marks the backward pass.
_UNWRAP_RE = re.compile(
    r"^(jvp|vjp|transpose|remat|rematted_computation|checkpoint|"
    r"custom_jvp|custom_vjp|vmap|pmap)\((.*)\)$")
# anything else of the form name(...) is a function-call frame
# (jit(log_softmax), ...), not a module scope.
_CALL_FRAME_RE = re.compile(r"^[\w.\-]+\(.*\)$")


def lowered_text(lowered) -> str:
    """StableHLO of a ``jax.stages.Lowered`` printed WITH location info
    (``Lowered.as_text()`` drops it); big constants elided."""
    return lowered.compiler_ir().operation.get_asm(
        enable_debug_info=True, large_elements_limit=16)


def scope_of(op_name: str) -> Tuple[str, str]:
    """(module path, direction) out of one op location name.

    Path frames join with ``.`` so they compare directly against
    ``named_parameters`` paths; direction is ``"fwd"`` or ``"bwd"``
    (``transpose(...)`` anywhere marks the backward pass).  An op with
    no module frame returns path ``""``."""
    frames = op_name.split("/")
    kept: List[str] = []
    bwd = False
    skip_region = 0
    for frame in frames[:-1] if len(frames) > 1 else []:
        while True:
            m = _UNWRAP_RE.match(frame)
            if m is None:
                break
            if m.group(1) == "transpose":
                bwd = True
            frame = m.group(2)
        if skip_region:
            # the region frame following a control-flow op ("body"/
            # "cond" after "while") is loop structure, not a module
            # scope — even when a module is ALSO registered as "body"
            # (ScanLayers), the structural frame is always the one
            # directly after "while"
            skip_region -= 1
            continue
        if frame == "while":
            # lax.scan/while_loop lower their body under "while/body"
            # (condition under "while/cond"): scan-over-layers scopes
            # must fold onto the module tree, not vanish into the loop
            skip_region = 1
            continue
        if not frame or _CALL_FRAME_RE.match(frame) or frame == "pjit":
            continue  # jit(...)/pjit function frames, not module scopes
        if frame in ("checkpoint", "rematted_computation", "remat",
                     "closed_call"):
            # jax.checkpoint's recompute-in-backward inserts these as
            # BARE frames (".../transpose(jvp(2))/checkpoint/
            # rematted_computation/0/fc1/..."), and a scan body traced
            # through core.closed_call gets one too (".../while/body/
            # closed_call/body/0/..."): transform structure, not module
            # scopes — the ops must fold onto the block's own tree path
            continue
        kept.append(frame)
    return ".".join(kept), ("bwd" if bwd else "fwd")


class OpCost:
    """One parsed op's attributed cost."""

    __slots__ = ("path", "direction", "opcode", "flops",
                 "transcendentals", "bytes")

    def __init__(self, path, direction, opcode, flops, transcendentals,
                 nbytes):
        self.path = path
        self.direction = direction
        self.opcode = opcode
        self.flops = flops
        self.transcendentals = transcendentals
        self.bytes = nbytes


def _type_cost(types_text: str) -> Tuple[int, int]:
    """(elements, bytes) summed over every ``tensor<...>`` in the text."""
    elems = total = 0
    for inner in _TENSOR_RE.findall(types_text):
        parts = inner.split("x")
        dtype = parts[-1]
        n = 1
        for d in parts[:-1]:
            if d.isdigit():
                n *= int(d)
        elems += n
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return elems, total


def _split_signature(sig_text: str) -> Tuple[str, str]:
    """Split an op's trailing type signature into (operand text, result
    text).  Handles both the function form ``: (A, B) -> C`` and the
    elementwise shorthand ``: C`` (operands share the result type)."""
    if "->" in sig_text:
        lhs, rhs = sig_text.rsplit("->", 1)
        return lhs, rhs
    return "", sig_text


def _dims(inner: str) -> List[int]:
    return [int(d) for d in inner.split("x")[:-1] if d.isdigit()]


def _ints(text: str) -> List[int]:
    return [int(t) for t in text.replace(" ", "").split(",") if t]


def _conv_flops(head: str, sig: str, out_elems: int) -> float:
    """XLA HloCostAnalysis convolution accounting: 2 FLOPs per VALID
    (output position, kernel position) pair — window positions that read
    only padding (or dilation holes) do not count, which is what makes a
    full-padded gradient conv cost the same as its forward conv."""
    m = _DIMNUM_RE.search(head)
    operand_text, result_text = _split_signature(sig)
    operand_types = _TENSOR_RE.findall(operand_text)
    if m is None or len(operand_types) < 2:
        return 0.0
    in_labels = [t.strip() for t in m.group(1).split(",")]
    k_labels = [t.strip() for t in m.group(2).split(",")]
    out_labels = [t.strip() for t in m.group(3).split(",")]
    in_dims = _dims(operand_types[0])
    k_dims = _dims(operand_types[1])
    out_types = _TENSOR_RE.findall(result_text)
    out_dims = _dims(out_types[0]) if out_types else []
    if len(in_dims) != len(in_labels) or len(k_dims) != len(k_labels) \
            or len(out_dims) != len(out_labels):
        return 0.0
    spatial = sorted(lbl for lbl in k_labels if lbl.isdigit())
    strides = _ints(_STRIDE_RE.search(head).group(1)) \
        if _STRIDE_RE.search(head) else []
    lhs_dil = _ints(_LHS_DIL_RE.search(head).group(1)) \
        if _LHS_DIL_RE.search(head) else []
    rhs_dil = _ints(_RHS_DIL_RE.search(head).group(1)) \
        if _RHS_DIL_RE.search(head) else []
    pad_m = _PAD_RE.search(head)
    pads = [_ints(p.strip(" []")) for p in pad_m.group(1).split("],")] \
        if pad_m else []

    valid = 1
    for d, lbl in enumerate(spatial):
        size_in = in_dims[in_labels.index(lbl)]
        size_k = k_dims[k_labels.index(lbl)]
        size_out = out_dims[out_labels.index(lbl)]
        stride = strides[d] if d < len(strides) else 1
        ld = lhs_dil[d] if d < len(lhs_dil) else 1
        rd = rhs_dil[d] if d < len(rhs_dil) else 1
        pad_lo = pads[d][0] if d < len(pads) and pads[d] else 0
        padded_in = (size_in - 1) * ld + 1 if size_in > 0 else 0
        count = 0
        for k in range(size_k):
            for o in range(size_out):
                i = o * stride + k * rd - pad_lo
                if 0 <= i < padded_in and i % ld == 0:
                    count += 1
        valid *= count
    k_in = 1
    for pos, lbl in enumerate(k_labels):
        if lbl == "i":
            k_in *= k_dims[pos]
    n_spatial_out = 1
    for lbl in spatial:
        n_spatial_out *= max(out_dims[out_labels.index(lbl)], 1)
    batch_feature = out_elems // max(n_spatial_out, 1)
    return 2.0 * batch_feature * k_in * valid


def _instr_flops(opcode: str, head: str, sig: str,
                 out_elems: int) -> Tuple[float, float]:
    """(flops, transcendentals), HloCostAnalysis conventions (fma = 2
    flops; transcendentals counted apart).  ``head`` is the op's first
    physical line (attributes live there), ``sig`` its type signature."""
    if opcode in _NO_FLOPS:
        return 0.0, 0.0
    if opcode in _TRANSCENDENTAL:
        return 0.0, float(out_elems)
    operand_text, _ = _split_signature(sig)
    operand_types = _TENSOR_RE.findall(operand_text)
    if opcode == "dot_general":
        m = _CONTRACT_RE.search(head)
        if m is None or not operand_types:
            return 0.0, 0.0
        lhs_dims = [d for d in operand_types[0].split("x")[:-1] if d.isdigit()]
        k = 1
        for idx in m.group(1).replace(" ", "").split(","):
            if idx and int(idx) < len(lhs_dims):
                k *= int(lhs_dims[int(idx)])
        return 2.0 * out_elems * k, 0.0
    if opcode == "convolution":
        return _conv_flops(head, sig, out_elems), 0.0
    if opcode == "reduce":
        if not operand_types:
            return 0.0, 0.0
        in_elems = _type_cost(f"tensor<{operand_types[0]}>")[0]
        return float(max(in_elems - out_elems, 0)), 0.0
    if opcode in ("reduce_window", "select_and_scatter"):
        m = _WINDOW_DIMS_RE.search(head)
        if m is not None:
            win = 1
            for d in (m.group(1) or m.group(2) or "").replace(
                    " ", "").split(","):
                if d:
                    win *= int(d)
            return float(out_elems * max(win - 1, 1)), 0.0
        return float(out_elems), 0.0
    if opcode == "clamp":
        return 2.0 * out_elems, 0.0
    # default: elementwise arithmetic / comparison / select
    return float(out_elems), 0.0


def _resolve_locs(loc_defs: Dict[str, str]) -> Dict[str, str]:
    """#locN -> op-name string.  A def is either a quoted name
    (possibly wrapping a child loc) or a callsite/file loc — those
    resolve through their first child reference."""
    memo: Dict[str, str] = {}

    def resolve(ref: str, depth: int = 0) -> str:
        if ref in memo:
            return memo[ref]
        if depth > 8:
            return ""
        body = loc_defs.get(ref, "")
        m = _LOC_NAME_RE.match(body)
        if m is not None:
            memo[ref] = m.group(1)
            return m.group(1)
        child = _LOC_CHILD_RE.search(body)
        out = resolve(child.group(1), depth + 1) if child else ""
        memo[ref] = out
        return out

    return {ref: resolve(ref) for ref in loc_defs}


def parse_lowered_text(text: str) -> List[OpCost]:
    """Parse debug-info StableHLO (:func:`lowered_text`) into
    per-op attributed costs.  Region ops (reduce_window, ...) keep their
    attribute head line; their types + loc arrive on the closing line."""
    lines = text.splitlines()
    loc_defs: Dict[str, str] = {}
    for line in lines:
        m = _LOC_DEF_RE.match(line.strip())
        if m is not None:
            loc_defs[m.group(1)] = m.group(2)
    locs = _resolve_locs(loc_defs)

    raw: List[Tuple[str, str, str, str, str]] = []  # + enclosing func
    pending: List[Tuple[str, str]] = []  # (opcode, head) of open region ops
    func = ""  # the func.func the current line sits in
    # private function -> (caller func, loc ref) of each of its call
    # sites: op names inside a private function (a scan body traced
    # through closed_call, a jitted helper) are relative to it, and the
    # scopes that lead to it stand on the call
    call_sites: Dict[str, List[Tuple[str, Optional[str]]]] = {}

    def sig_and_loc(line: str) -> Tuple[str, Optional[str]]:
        m = _LOC_REF_RE.search(line)
        ref = m.group(1) if m else None
        body = line[: m.start()] if m else line
        idx = body.rfind(" : ")
        return (body[idx + 3:] if idx >= 0 else ""), ref

    for line in lines:
        stripped = line.rstrip()
        m = _FUNC_RE.match(stripped)
        if m is not None:
            func = m.group(1)
            continue
        m = _OP_RE.match(stripped)
        if m is not None:
            opcode = m.group(1)
            if "loc(" in stripped and " : " in stripped:
                sig, ref = sig_and_loc(stripped)
                raw.append((opcode, stripped, sig, ref, func))
                callee = _CALLEE_RE.search(stripped) \
                    if opcode == "call" else None
                if callee is not None:
                    call_sites.setdefault(callee.group(1), []).append(
                        (func, ref))
            else:
                pending.append((opcode, stripped))  # region op opens here
        elif pending and stripped.lstrip().startswith("})") \
                and "loc(" in stripped:
            opcode, head = pending.pop()
            sig, ref = sig_and_loc(stripped)
            raw.append((opcode, head, sig, ref, func))

    def caller_scopes(func: str, depth: int = 0) -> List[str]:
        """The op-name prefixes that lead into ``func``, one for each
        time it runs: each call site's name under each of that caller's
        own prefixes (``main`` runs once, under none)."""
        if func not in call_sites or depth > 8:
            return [""]
        return ["/".join(p for p in (outer, locs.get(ref, "")) if p)
                for caller, ref in call_sites[func]
                for outer in caller_scopes(caller, depth + 1)]

    prefixes = {func: caller_scopes(func) for func in call_sites}
    ops: List[OpCost] = []
    for opcode, head, sig, ref, func in raw:
        if opcode in ("constant", "return", "func", "call"):
            continue
        _, result_text = _split_signature(sig)
        out_elems, out_bytes = _type_cost(result_text)
        operand_text, _ = _split_signature(sig)
        _, operand_bytes = _type_cost(operand_text)
        name = locs.get(ref, "") if ref else ""
        flops, trans = _instr_flops(opcode, head, sig, out_elems)
        # a function called from several sites is inlined at each: its
        # ops cost once a site, under that site's scopes
        for prefix in prefixes.get(func, ("",)):
            path, direction = scope_of(
                f"{prefix}/{name}" if prefix else name)
            ops.append(OpCost(path, direction, opcode, flops, trans,
                              out_bytes + operand_bytes))
    return ops


def aggregate(ops: List[OpCost]) -> Dict[str, Dict[str, Any]]:
    """Group parsed ops by scope path."""
    rows: Dict[str, Dict[str, Any]] = {}
    for op in ops:
        row = rows.setdefault(op.path, {
            "flops": 0.0, "flops_fwd": 0.0, "flops_bwd": 0.0,
            "transcendentals": 0.0, "bytes": 0.0, "ops": 0})
        row["flops"] += op.flops
        row[f"flops_{op.direction}"] += op.flops
        row["transcendentals"] += op.transcendentals
        row["bytes"] += op.bytes
        row["ops"] += 1
    return rows


def _module_info(model) -> Dict[str, Dict[str, Any]]:
    """path -> {class, params, param_bytes} for every module of a model
    (own params only — containers aggregate via the rollup)."""
    import numpy as np

    info: Dict[str, Dict[str, Any]] = {}
    for name, m in model.named_modules():
        own = m.__dict__["_params"]
        n = sum(int(np.prod(p.shape)) if getattr(p, "ndim", 0) else 1
                for p in own.values())
        b = sum(int(getattr(p, "nbytes", 0)) for p in own.values())
        info[name] = {"class": type(m).__name__, "params": n,
                      "param_bytes": b}
    return info


def module_rows(scope_rows: Dict[str, Dict[str, Any]],
                model=None) -> List[Dict[str, Any]]:
    """Fold scope rows onto the module tree.

    With a model: one row per module, in ``named_modules`` order, with
    CUMULATIVE cost (own scope + every scope underneath it) plus own
    param count/bytes; scope paths matching no module land in the
    ``(unattributed)`` row (loss/optimizer/collectives).  Without a
    model: one row per raw scope path."""
    def blank(path, cls=""):
        return {"path": path, "class": cls, "flops": 0.0,
                "flops_fwd": 0.0, "flops_bwd": 0.0,
                "transcendentals": 0.0, "bytes": 0.0, "ops": 0,
                "params": 0, "param_bytes": 0}

    if model is None:
        out = []
        for path in sorted(scope_rows):
            row = blank(path or "(unattributed)")
            row.update(scope_rows[path])
            out.append(row)
        return out

    info = _module_info(model)
    module_paths = [p for p in info if p]
    rows = {path: blank(path, info[path]["class"]) for path in info if path}
    unattributed = blank("(unattributed)")
    for spath, srow in scope_rows.items():
        # longest module path that prefixes the scope path on a dot
        # boundary (a module's internal named_scopes roll up to it)
        best = None
        for mp in module_paths:
            if spath == mp or spath.startswith(mp + "."):
                if best is None or len(mp) > len(best):
                    best = mp
        if best is None:
            targets = [unattributed]
        else:
            # cumulative: the owning module and every ancestor
            parts = best.split(".")
            targets = [rows[".".join(parts[:i + 1])]
                       for i in range(len(parts))]
        for row in targets:
            for key in ("flops", "flops_fwd", "flops_bwd",
                        "transcendentals", "bytes"):
                row[key] += srow.get(key, 0.0)
            row["ops"] += srow.get("ops", 0)
    for path, row in rows.items():
        row["params"] = info[path]["params"]
        row["param_bytes"] = info[path]["param_bytes"]
    ordered = [rows[name] for name, _ in model.named_modules() if name]
    if unattributed["ops"]:
        ordered.append(unattributed)
    return ordered


# -- building attribution from live objects ---------------------------------
def attribute_lowered(lowered, model=None) -> Dict[str, Any]:
    """Full attribution payload from a ``jax.stages.Lowered``:
    per-module rows + totals + XLA's own cost-analysis total for
    fidelity.  No XLA compile — text extraction and parsing only."""
    from bigdl_tpu.telemetry.device import normalize_cost_analysis

    ops = parse_lowered_text(lowered_text(lowered))
    rows = module_rows(aggregate(ops), model)
    out: Dict[str, Any] = {
        "rows": rows,
        "total_flops": sum(op.flops for op in ops),
        "total_transcendentals": sum(op.transcendentals for op in ops),
        "total_bytes": sum(op.bytes for op in ops),
    }
    try:
        cost = normalize_cost_analysis(lowered.cost_analysis())
        if cost.get("flops"):
            out["cost_flops"] = float(cost["flops"])
        if cost.get("bytes accessed"):
            out["cost_bytes"] = float(cost["bytes accessed"])
    except Exception:  # noqa: BLE001 - fidelity line is best-effort
        pass
    return out


def attribute_train_step(step, x, y, key=None) -> Dict[str, Any]:
    """Attribute a TrainStep's program.  ``x``/``y`` may be concrete
    arrays or ``jax.ShapeDtypeStruct`` specs — lowering needs only
    abstract values."""
    import jax

    from bigdl_tpu.nn.module import stamp_scope_names

    stamp_scope_names(step.model)
    if key is None:
        key = jax.random.key(0)
    lowered = step._build().lower(
        step.params, step.opt_state, step.buffers, x, y, key)
    out = attribute_lowered(lowered, step.model)
    out["program"] = "train_step"
    return out


def attribute_forward(model, input_spec) -> Dict[str, Any]:
    """Attribute the inference forward only (no criterion needed)."""
    import jax

    from bigdl_tpu.nn.module import (functional_call, stamp_scope_names,
                                     state_dict)

    stamp_scope_names(model)
    state = state_dict(model)

    def fwd(state, x):
        return functional_call(model, state, x, training=False)[0]

    lowered = jax.jit(fwd).lower(state, input_spec)
    out = attribute_lowered(lowered, model)
    out["program"] = "forward"
    return out


def attribute_model(name: str, batch: int = 8,
                    train: bool = True) -> Dict[str, Any]:
    """Registry-model attribution: build the model, a synthetic-spec
    TrainStep (when the registry knows the training pieces), and
    attribute it; ``train=False`` attributes the inference forward."""
    from bigdl_tpu.models import registry

    model = registry.build_model(name)
    spec = registry.input_spec(name, batch)
    pieces = registry.train_pieces(name, batch) if train else None
    if pieces is None:
        out = attribute_forward(model, spec)
    else:
        import bigdl_tpu.optim as optim
        from bigdl_tpu.parallel.train_step import TrainStep

        criterion, target_spec = pieces
        step = TrainStep(model, criterion,
                         optim.SGD(learning_rate=0.01, momentum=0.9))
        out = attribute_train_step(step, spec, target_spec)
    out["model"] = name
    out["batch"] = batch
    return out


def rows_from_events(events: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """The last ``attribution`` event of a run log (the CLI's
    read-from-artifact path), or None."""
    found = None
    for ev in events:
        if ev.get("kind") == "attribution":
            found = ev
    if found is None:
        return None
    return {k: v for k, v in found.items()
            if k not in ("v", "ts", "pid", "tid", "kind")}


# -- rendering ---------------------------------------------------------------
def _si(n: float) -> str:
    for div, unit in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(n) >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n:.0f} "


def format_attribution(result: Dict[str, Any]) -> str:
    """Human-readable per-module cost table."""
    rows = result.get("rows") or []
    lines: List[str] = []
    head = ["== per-module cost attribution =="]
    for key in ("model", "program", "batch"):
        if key in result:
            head.append(f"{key}={result[key]}")
    lines.append("  ".join(head))
    if not rows:
        lines.append("no attribution rows (model compiled without "
                     "module scopes? set BIGDL_SCOPES=on)")
        return "\n".join(lines)
    total = result.get("total_flops") or 1.0
    pw = max(len(r["path"]) for r in rows)
    cw = max((len(r.get("class", "")) for r in rows), default=5)
    lines.append(f"{'module':<{pw}}  {'class':<{cw}}  {'flops':>9}  "
                 f"{'fwd':>9}  {'bwd':>9}  {'%':>6}  {'bytes':>10}  "
                 f"{'params':>10}")
    lines.append("-" * len(lines[-1]))
    for r in rows:
        share = (r["flops"] / total * 100.0) if total else 0.0
        lines.append(
            f"{r['path']:<{pw}}  {r.get('class', ''):<{cw}}  "
            f"{_si(r['flops']):>9}  {_si(r['flops_fwd']):>9}  "
            f"{_si(r['flops_bwd']):>9}  {share:>5.1f}%  "
            f"{_si(r['bytes']):>9}B  {r.get('params', 0):>10}")
    lines.append("-" * len(lines[2]))
    lines.append(f"estimated total: {_si(result.get('total_flops', 0.0))}F"
                 f"  (+ {_si(result.get('total_transcendentals', 0.0))} "
                 f"transcendentals)")
    if result.get("cost_flops"):
        est = result.get("total_flops", 0.0)
        cost = result["cost_flops"]
        dev = (est - cost) / cost * 100.0 if cost else 0.0
        lines.append(f"XLA cost_analysis: {_si(cost)}F  "
                     f"(estimate {dev:+.1f}% vs XLA)")
    return "\n".join(lines)
