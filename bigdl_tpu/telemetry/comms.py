"""Per-collective communication attribution: which collectives a
compiled step runs, how many bytes each moves, over which mesh axes, and
on whose module's behalf.

Why a SECOND walker beside ``attribution.py``: the PR-4 walker parses
the lowered StableHLO, which is the program BEFORE SPMD partitioning —
shardings are still ``custom_call @Sharding`` annotations there, and the
collectives do not exist yet.  The all-reduce/all-gather/reduce-scatter
ops XLA inserts for a sharded step appear only in the **post-partitioning
optimized HLO** (``Compiled.as_text()``), so comms attribution parses
that text instead.  The partitioner carries each op's ``op_name``
metadata through, so the same :func:`attribution.scope_of` unwrapping
names the owning module (``transpose(jvp(x))`` = x's gradient
collective); partitioner-invented collectives with no metadata land in
``(unattributed)``.

Bytes convention (HloCostAnalysis-style "bytes accessed"): operand bytes
plus output bytes, with the output derived from the collective's
semantics —

- ``all-reduce`` / ``collective-permute`` / ``all-to-all``: out == in;
- ``all-gather``: out == in * group_size;
- ``reduce-scatter``: out == in / group_size;

so a 2-device gradient all-reduce of N parameter bytes accounts 2N.
``payload_bytes`` (operand side only) is what actually crosses the
interconnect boundary per device, the number to divide by link bandwidth.

Mesh axes: replica groups (both the explicit ``{{0,1},{2,3}}`` and the
iota ``[2,2]<=[4]`` forms) are matched against the groups each subset of
mesh axes would generate over the mesh's row-major device order — the
order ``jax.sharding.Mesh`` hands XLA as the device assignment — so an
all-reduce over ``replica_groups=[1,2]<=[2]`` on a ``("data",)`` mesh
reports ``axes=("data",)`` and a ZeRO reduce-scatter names the axis its
bytes cross.  Groups matching no axis subset report ``axes=()``.

Timing: the walker is static (bytes are exact at trace time, seconds are
not).  Per-collective wall time comes from an on-demand profiler capture
(``ProfilerControl.arm(..., perfetto=True)`` / ``POST
/profile?steps=N&perfetto=1``): :func:`collective_times_from_trace`
reads the capture's Chrome/Perfetto JSON and sums collective event
durations, and the CLI (``telemetry attribute --comms run.jsonl``)
divides expected bytes by measured seconds to report achieved bytes/s
against ``BIGDL_PEAK_BW`` (``device.peak_bw_per_device``).
"""

from __future__ import annotations

import gzip
import json
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from bigdl_tpu.telemetry.attribution import scope_of

__all__ = ["Collective", "parse_hlo_collectives", "infer_axes",
           "comms_facts", "attribute_comms_train_step",
           "attribute_comms_model", "comms_from_events", "format_comms",
           "collective_times_from_trace", "COLLECTIVE_OPS"]

#: canonical collective opcodes (HLO spelling); ``-start`` async halves
#: count as the op, ``-done`` halves are skipped (same bytes twice).
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all",
                  "collective-broadcast")

_HLO_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
    "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_OPS_ALT = "|".join(COLLECTIVE_OPS)
#: one collective op line of optimized HLO text; group(1) = the result
#: type, group(2) = opcode, group(3) = "-start" on the async form,
#: group(4) = the operand list inside the parens
_COLL_RE = re.compile(
    rf"=\s*(\([^=]*?\)|\S+)\s+({_OPS_ALT})(-start)?\((.*?)\)(?:,|\s*$)")
#: typed operand, e.g. ``f32[100,192]{{1,0}} %dot.5``
_SHAPE_RE = re.compile(r"\b(" + "|".join(_HLO_DTYPE_BYTES) +
                       r")\[([0-9,]*)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{(\{[\d,\s]*\}"
                              r"(?:\s*,\s*\{[\d,\s]*\})*)\}")
#: iota form: [groups,size]<=[d0,d1,...] with an optional T(perm)
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_COMMENT_RE = re.compile(r"/\*.*?\*/")


class Collective:
    """One parsed collective op."""

    __slots__ = ("opcode", "path", "direction", "payload_bytes", "bytes",
                 "group_size", "groups", "axes", "channel_id", "op_name")

    def __init__(self, opcode, path, direction, payload_bytes, nbytes,
                 group_size, groups, axes, channel_id, op_name):
        self.opcode = opcode
        self.path = path
        self.direction = direction
        self.payload_bytes = payload_bytes
        self.bytes = nbytes
        self.group_size = group_size
        self.groups = groups
        self.axes = axes
        self.channel_id = channel_id
        self.op_name = op_name


def _operand_bytes(operand_text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(operand_text):
        n = 1
        for d in dims.split(","):
            if d.strip().isdigit():
                n *= int(d)
        total += n * _HLO_DTYPE_BYTES[dtype]
    return total


def _parse_groups(line: str) -> Optional[List[Tuple[int, ...]]]:
    """Replica groups out of one HLO line, both spellings, or the
    source/target pairs of a collective-permute (as 2-groups)."""
    m = _GROUPS_IOTA_RE.search(line)
    if m is not None:
        import numpy as np

        n_groups, size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(p) for p in m.group(4).split(",")])
        ids = ids.reshape(n_groups, size)
        return [tuple(int(x) for x in row) for row in ids]
    m = _GROUPS_BRACE_RE.search(line)
    if m is not None:
        groups = []
        for part in re.findall(r"\{([\d,\s]*)\}", m.group(1)):
            ids = [int(t) for t in part.replace(" ", "").split(",") if t]
            if ids:
                groups.append(tuple(ids))
        return groups or None
    m = _PAIRS_RE.search(line)
    if m is not None:
        return [tuple(int(t) for t in p.split(","))
                for p in re.findall(r"\{(\d+,\d+)\}", m.group(1))]
    return None


def infer_axes(groups: Optional[List[Tuple[int, ...]]],
               axis_names: Sequence[str],
               axis_sizes: Sequence[int]) -> Tuple[str, ...]:
    """The mesh axes a replica-group set spans, or ``()`` when it maps
    onto no axis subset.

    Device ids are positions in the mesh's row-major device order (the
    device assignment ``jax.sharding.Mesh`` hands XLA).  For every
    non-empty subset S of axes, the groups S would generate are "vary
    the S coordinates, fix the rest"; the parsed set is matched against
    each (smallest subset first, so a single-axis collective never
    reports a superset).  ``collective-permute`` pairs match via the
    same rule — a ring over one axis yields pairs whose coordinates
    differ only on that axis."""
    import itertools

    import numpy as np

    if not groups or not axis_names:
        return ()
    sizes = tuple(int(s) for s in axis_sizes)
    n = int(np.prod(sizes)) if sizes else 0
    if n == 0 or any(i >= n for g in groups for i in g):
        return ()
    parsed = {frozenset(g) for g in groups}
    coords = {i: np.unravel_index(i, sizes) for i in range(n)}
    # permute pairs (collective-permute source/target): when every pair
    # connects devices differing on exactly one axis, that axis (or
    # those axes, for several rings) is the answer — pairs are not a
    # partition, so the subset matching below can never name them
    if all(len(g) == 2 for g in groups) and all(
            sum(ca != cb for ca, cb in zip(coords[a], coords[b])) == 1
            for a, b in (tuple(g) for g in groups)):
        differing = set()
        for a, b in (tuple(g) for g in groups):
            differing |= {axis_names[d] for d in range(len(sizes))
                          if coords[a][d] != coords[b][d]}
        if differing:
            return tuple(ax for ax in axis_names if ax in differing)
    ids = np.arange(n).reshape(sizes)
    for r in range(1, len(sizes) + 1):
        for subset in itertools.combinations(range(len(sizes)), r):
            rest = [d for d in range(len(sizes)) if d not in subset]
            moved = ids.transpose(rest + list(subset)).reshape(
                -1, int(np.prod([sizes[d] for d in subset])))
            generated = {frozenset(int(x) for x in row) for row in moved}
            if generated == parsed:
                return tuple(axis_names[d] for d in subset)
    return ()


def parse_hlo_collectives(hlo_text: str,
                          axis_names: Sequence[str] = (),
                          axis_sizes: Sequence[int] = ()
                          ) -> List[Collective]:
    """All collective ops of one optimized-HLO module text."""
    out: List[Collective] = []
    for line in hlo_text.splitlines():
        if "/*" in line:  # long tuples carry /*index=5*/ markers
            line = _COMMENT_RE.sub("", line)
        m = _COLL_RE.search(line)
        if m is None:
            continue
        opcode = m.group(2)
        groups = _parse_groups(line)
        group_size = max((len(g) for g in groups), default=1) \
            if groups else 1
        payload = _operand_bytes(m.group(4))
        if payload == 0 and not (m.group(3) and opcode != "all-reduce"):
            # the XLA under jax 0.9.0 prints operands by name only
            # (``all-reduce(%fusion.3)``): size the operands from the
            # RESULT type.  Not for an async -start form whose result
            # tuple mixes operands, results and context (all-reduce's
            # is its operands' shapes alone)
            result = _operand_bytes(m.group(1))
            if opcode == "all-gather":
                payload = result // max(group_size, 1)
            elif opcode == "reduce-scatter":
                payload = result * group_size
            else:
                payload = result
        if payload == 0:
            continue
        if opcode == "all-gather":
            nbytes = payload + payload * group_size
        elif opcode == "reduce-scatter":
            nbytes = payload + payload // max(group_size, 1)
        else:
            nbytes = 2 * payload
        name_m = _OPNAME_RE.search(line)
        op_name = name_m.group(1) if name_m else ""
        path, direction = scope_of(op_name) if op_name else ("", "fwd")
        ch = _CHANNEL_RE.search(line)
        axes = infer_axes(groups, axis_names, axis_sizes)
        out.append(Collective(opcode, path, direction, payload, nbytes,
                              group_size, groups, axes,
                              int(ch.group(1)) if ch else None, op_name))
    return out


def _mesh_axes(mesh) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    if mesh is None:
        return (), ()
    names = tuple(mesh.axis_names)
    return names, tuple(int(mesh.shape[a]) for a in names)


def _module_fold(colls: List[Collective], model=None
                 ) -> List[Dict[str, Any]]:
    """Per-module rows (owning module = longest module-path prefix of
    the op's scope path; no model = raw scope paths)."""
    module_paths: List[str] = []
    if model is not None:
        module_paths = [p for p, _ in model.named_modules() if p]
    rows: Dict[str, Dict[str, Any]] = {}
    for c in colls:
        owner = None
        if module_paths and c.path:
            for mp in module_paths:
                if (c.path == mp or c.path.startswith(mp + ".")) and \
                        (owner is None or len(mp) > len(owner)):
                    owner = mp
        key = owner if owner is not None else (
            c.path if (c.path and model is None) else "(unattributed)")
        row = rows.setdefault(key, {"path": key, "bytes": 0,
                                    "payload_bytes": 0, "count": 0,
                                    "ops": {}})
        row["bytes"] += c.bytes
        row["payload_bytes"] += c.payload_bytes
        row["count"] += 1
        row["ops"][c.opcode] = row["ops"].get(c.opcode, 0) + 1
    return sorted(rows.values(), key=lambda r: -r["bytes"])


def comms_facts(compiled_or_text, mesh=None, model=None) -> Dict[str, Any]:
    """The full comms payload from a compiled executable (or its HLO
    text): totals, per-axis and per-op breakdowns, per-module rows, and
    the expected per-step seconds when a peak-bandwidth figure is known
    (``BIGDL_PEAK_BW`` / the device table)."""
    text = compiled_or_text if isinstance(compiled_or_text, str) \
        else compiled_or_text.as_text()
    names, sizes = _mesh_axes(mesh)
    colls = parse_hlo_collectives(text, names, sizes)
    by_axis: Dict[str, float] = {}
    by_op: Dict[str, Dict[str, Any]] = {}
    for c in colls:
        axis_key = "+".join(c.axes) if c.axes else "(unknown)"
        by_axis[axis_key] = by_axis.get(axis_key, 0) + c.bytes
        row = by_op.setdefault(c.opcode, {"count": 0, "bytes": 0,
                                          "payload_bytes": 0})
        row["count"] += 1
        row["bytes"] += c.bytes
        row["payload_bytes"] += c.payload_bytes
    out: Dict[str, Any] = {
        "count": len(colls),
        "bytes": int(sum(c.bytes for c in colls)),
        "payload_bytes": int(sum(c.payload_bytes for c in colls)),
        "by_axis": by_axis,
        "by_op": by_op,
        "rows": _module_fold(colls, model),
    }
    try:
        import jax

        from bigdl_tpu.telemetry.device import peak_bw_per_device

        peak = peak_bw_per_device(jax.devices()[0].device_kind)
        if peak:
            out["peak_bw_per_device"] = peak
            out["expected_s"] = out["payload_bytes"] / peak
    except Exception:  # noqa: BLE001 - the bandwidth line is best-effort
        pass
    return out


def attribute_comms_train_step(step, x, y, key=None) -> Dict[str, Any]:
    """Comms attribution of a TrainStep's program: lower + XLA-compile
    (the partitioner must run for the collectives to exist), parse.
    ``x``/``y`` may be ShapeDtypeStructs — only the compile needs to
    happen, never a dispatch."""
    import jax

    from bigdl_tpu.nn.module import stamp_scope_names

    stamp_scope_names(step.model)
    if key is None:
        key = jax.random.key(0)
    compiled = step._build().lower(
        step.params, step.opt_state, step.buffers, x, y, key).compile()
    out = comms_facts(compiled, mesh=step.mesh, model=step.model)
    out["program"] = "train_step"
    return out


def attribute_comms_model(name: str, batch: int = 8, devices: int = 0,
                          sync: str = "allreduce",
                          sparse: Optional[str] = None) -> Dict[str, Any]:
    """Registry-model comms attribution over a fresh ``data``-axis mesh
    spanning ``devices`` devices (0 = all local devices) — CPU-friendly:
    one local XLA compile, no run needed.  ``sparse`` overrides the
    ``BIGDL_SPARSE`` mode for this compile (off | auto | on) — the A/B
    that shows an embedding table's sync bytes collapsing to the
    touched-rows fraction (docs/sparse.md)."""
    import dataclasses

    import jax

    import bigdl_tpu.optim as optim
    from bigdl_tpu.models import registry
    from bigdl_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from bigdl_tpu.parallel.train_step import TrainStep
    from bigdl_tpu.utils.config import get_config, set_config

    prev = get_config()
    if sparse is not None:
        set_config(dataclasses.replace(prev, sparse_sync=sparse))
    try:
        n = devices or len(jax.devices())
        mesh = make_mesh((n,), (DATA_AXIS,), devices=jax.devices()[:n])
        model = registry.build_model(name)
        spec = registry.input_spec(name, batch)
        pieces = registry.train_pieces(name, batch)
        if pieces is None:
            raise ValueError(f"registry model {name!r} has no training "
                             f"pieces — comms attribution needs a train "
                             f"step")
        criterion, target_spec = pieces
        step = TrainStep(model, criterion,
                         optim.SGD(learning_rate=0.01, momentum=0.9),
                         mesh=mesh, parameter_sync=sync)
        out = attribute_comms_train_step(step, spec, target_spec)
    finally:
        if sparse is not None:
            set_config(prev)
    out["model"] = name
    out["batch"] = batch
    out["mesh"] = {"devices": n, "sync": sync}
    if sparse is not None:
        out["sparse"] = sparse
    return out


def comms_from_events(events: List[Dict[str, Any]]
                      ) -> Optional[Dict[str, Any]]:
    """The last ``comms`` event of a run log (the read-from-artifact CLI
    path), or None."""
    found = None
    for ev in events:
        if ev.get("kind") == "comms":
            found = ev
    if found is None:
        return None
    return {k: v for k, v in found.items()
            if k not in ("v", "ts", "pid", "tid", "kind")}


# -- measured wall time from a profiler capture ------------------------------
_TRACE_TOKENS = {
    "all-reduce": ("all-reduce", "allreduce", "all_reduce"),
    "all-gather": ("all-gather", "allgather", "all_gather"),
    "reduce-scatter": ("reduce-scatter", "reducescatter", "reduce_scatter"),
    "collective-permute": ("collective-permute", "collectivepermute",
                           "collective_permute"),
    "all-to-all": ("all-to-all", "alltoall", "all_to_all"),
}


def collective_times_from_trace(trace_dir: str) -> Dict[str, float]:
    """Summed collective wall seconds per opcode out of a profiler
    capture's Chrome/Perfetto JSON (``ProfilerControl.arm(...,
    perfetto=True)`` writes one).  Returns ``{}`` when the capture holds
    no parseable trace — TPU captures carry device lanes with the
    collective ops named; plain CPU captures may not."""
    out: Dict[str, float] = {}
    paths: List[str] = []
    perfetto: List[str] = []
    for root, _dirs, files in os.walk(trace_dir):
        for f in files:
            if f in ("perfetto_trace.json.gz", "perfetto_trace.json"):
                perfetto.append(os.path.join(root, f))
            elif f.endswith((".trace.json.gz", ".trace.json")):
                paths.append(os.path.join(root, f))
    # a perfetto-enabled capture may leave BOTH spellings describing the
    # SAME events — summing across them would double every duration, so
    # the perfetto file wins outright when present
    if perfetto:
        paths = perfetto
    for path in paths:
        try:
            opener = gzip.open if path.endswith(".gz") else open
            with opener(path, "rt", encoding="utf-8",
                        errors="replace") as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        for ev in doc.get("traceEvents", []):
            if ev.get("ph") != "X" or not ev.get("dur"):
                continue
            name = str(ev.get("name", "")).lower()
            for op, tokens in _TRACE_TOKENS.items():
                if any(t in name for t in tokens):
                    out[op] = out.get(op, 0.0) + float(ev["dur"]) / 1e6
                    break
    return out


# -- rendering ---------------------------------------------------------------
def _fmt_bytes(n: float) -> str:
    for div, unit in ((1 << 30, "GiB"), (1 << 20, "MiB"), (1 << 10, "KiB")):
        if abs(n) >= div:
            return f"{n / div:.2f} {unit}"
    return f"{int(n)} B"


def format_comms(result: Dict[str, Any]) -> str:
    """Human-readable comms attribution report."""
    lines: List[str] = []
    head = ["== per-collective comms attribution =="]
    for key in ("model", "program", "batch"):
        if key in result:
            head.append(f"{key}={result[key]}")
    lines.append("  ".join(head))
    if not result.get("count"):
        lines.append("no collectives in this program (single device, or "
                     "nothing sharded)")
        return "\n".join(lines)
    lines.append(f"collectives: {result['count']}   bytes accessed "
                 f"{_fmt_bytes(result['bytes'])}   payload "
                 f"{_fmt_bytes(result['payload_bytes'])}")
    by_op = result.get("by_op") or {}
    if by_op:
        lines.append("")
        lines.append("-- by collective --")
        width = max(len(op) for op in by_op)
        for op, row in sorted(by_op.items(), key=lambda kv: -kv[1]["bytes"]):
            lines.append(f"{op:<{width}}  x{row['count']:<3} "
                         f"{_fmt_bytes(row['bytes']):>11}  "
                         f"(payload {_fmt_bytes(row['payload_bytes'])})")
    by_axis = result.get("by_axis") or {}
    if by_axis:
        lines.append("")
        lines.append("-- by mesh axis --")
        width = max(len(a) for a in by_axis)
        for axis, nbytes in sorted(by_axis.items(), key=lambda kv: -kv[1]):
            lines.append(f"{axis:<{width}}  {_fmt_bytes(nbytes):>11}")
    rows = result.get("rows") or []
    if rows:
        lines.append("")
        lines.append("-- by module --")
        width = max(len(r["path"]) for r in rows)
        total = result.get("bytes") or 1
        for r in rows:
            ops = ",".join(f"{op}x{n}" for op, n in
                           sorted(r.get("ops", {}).items()))
            lines.append(f"{r['path']:<{width}}  "
                         f"{_fmt_bytes(r['bytes']):>11}  "
                         f"{r['bytes'] / total * 100:5.1f}%  {ops}")
    measured = result.get("measured_s")
    expected = result.get("expected_s")
    peak = result.get("peak_bw_per_device")
    if measured:
        achieved = result.get("payload_bytes", 0) / measured
        line = (f"measured collective time {measured * 1e3:.3f} ms/step  "
                f"-> achieved {_fmt_bytes(achieved)}/s")
        if peak:
            line += f"  ({achieved / peak * 100:.1f}% of peak " \
                    f"{_fmt_bytes(peak)}/s)"
        lines.append("")
        lines.append(line)
    elif expected is not None and peak:
        lines.append("")
        lines.append(f"expected {expected * 1e3:.3f} ms/step at peak "
                     f"{_fmt_bytes(peak)}/s (BIGDL_PEAK_BW; no measured "
                     f"capture — arm one with POST /profile?steps=N"
                     f"&perfetto=1)")
    return "\n".join(lines)
