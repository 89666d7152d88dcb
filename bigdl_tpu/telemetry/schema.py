"""The event-log schema: one catalog of event kinds + a validator.

Every line of a run log is one JSON object.  Base fields (all kinds):

| field | type  | meaning                          |
|-------|-------|----------------------------------|
| v     | int   | schema version (currently 1)     |
| ts    | float | epoch seconds at emission        |
| pid   | int   | OS process id                    |
| tid   | int   | thread id (one Chrome lane each) |
| kind  | str   | one of :data:`KINDS`             |

Kind-specific required fields are listed in :data:`KINDS`; extra fields
are always allowed (attrs travel with their event).  ``validate_run``
additionally checks the *structural* invariants the Chrome exporter and
the summary reader rely on: every ``span_begin`` has a matching
``span_end`` on the same thread, pairs close LIFO (proper nesting), and
span ids are unique.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Tuple

__all__ = ["KINDS", "STREAM_NAMES", "validate_event", "validate_events",
           "validate_run", "read_events"]

_NUM = (int, float)

#: kind -> {required field: type tuple}
KINDS: Dict[str, Dict[str, tuple]] = {
    "run_start": {"meta": (dict,)},
    "run_end": {"dur": _NUM},
    "span_begin": {"name": (str,), "span": (int,), "parent": (int,),
                   "depth": (int,)},
    "span_end": {"name": (str,), "span": (int,), "dur": _NUM},
    "stage": {"name": (str,), "dur": _NUM},
    "counter": {"name": (str,), "value": _NUM},
    "gauge": {"name": (str,), "value": _NUM},
    "event": {"name": (str,)},
    "step": {"step": (int,), "dur": _NUM},
    "compile": {"name": (str,), "dur": _NUM},
    "retrace": {"rule": (str,), "message": (str,)},
    "device_facts": {"facts": (dict,)},
    # one per probed training step: grad/param/update norms + nonfinite
    # counts (telemetry/health.py PROBE_FIELDS travel as extra fields)
    "health": {"step": (int,)},
    # per-module cost attribution (telemetry/attribution.py): rows is a
    # list of {path, class, flops, flops_fwd, flops_bwd, bytes, params}
    "attribution": {"rows": (list,)},
    # one per executed serving batch (bigdl_tpu/serving/batcher.py):
    # size = rows carried, dur = assemble+infer seconds; queue_ms /
    # infer_ms / fill / requests travel as extra fields — the raw
    # material for `telemetry diff`'s serve_p50/p99/qps metrics
    "serve": {"size": (int,), "dur": _NUM},
    # one per COMPLETED generation (serving/generate/batcher.py):
    # tokens = emitted count, dur = submit-to-last-token seconds;
    # ttft_ms / itl_p99_ms / finish / queue_ms travel as extra fields —
    # the raw material for the bigdl_gen_* metrics and the fleet view's
    # decode-replica columns
    "generate": {"tokens": (int,), "dur": _NUM},
    # one per serving request (telemetry/request_trace.py): the span
    # timeline + component tally + blame verdict of one request's trip
    # through the server.  trace_id = the X-Request-Id echoed to the
    # client, endpoint = predict|generate, ms = ingress-to-done wall,
    # status = ok|rejected|error|cancelled; spans / components / blame /
    # reason / ttft_ms / slo_violated travel as extra fields — the raw
    # material for `telemetry trace`, the chrome request lanes, and the
    # fleet SLO columns
    "request": {"trace_id": (str,), "endpoint": (str,), "ms": _NUM,
                "status": (str,)},
    # per-collective comms attribution (telemetry/comms.py): count =
    # collective ops in the compiled step, bytes = HloCostAnalysis-style
    # bytes accessed; payload_bytes / by_axis / by_op / rows /
    # expected_s / measured_s travel as extra fields — the raw material
    # for `telemetry diff`'s comms_bytes/comms_s and fleet skew blame
    "comms": {"count": (int,), "bytes": _NUM},
    # per-run goodput/badput ledger (telemetry/ledger.py): emitted once
    # at end_run — goodput_pct = 100*compute/wall, wall_s = run wall
    # seconds; compute_s / badput_s / badput (per-category seconds) /
    # counts / blame / conservation_err_pct travel as extra fields — the
    # raw material for `telemetry diff`'s goodput gate and the bench
    # rows' goodput columns
    "goodput": {"goodput_pct": _NUM, "wall_s": _NUM},
    # per-step memory attribution (telemetry/memory.py): peak_bytes =
    # predicted per-device peak HBM (args + live-buffer-timeline temp
    # peak off the scheduled post-opt HLO); categories / rows / largest
    # / live (allocator stats per device) / hbm_limit_bytes travel as
    # extra fields — the raw material for `telemetry diff`'s
    # peak_hbm_bytes gate and the fleet memory-pressure note
    "memory": {"peak_bytes": _NUM},
}

_BASE: Dict[str, tuple] = {"v": (int,), "ts": _NUM, "pid": (int,),
                           "tid": (int,), "kind": (str,)}

#: every span/stage/counter/gauge/instant name the framework emits,
#: plus the compile-event names.  ``tests/test_schema_registry.py``
#: greps the sources for emitted literals and asserts membership here,
#: so a new event stream cannot silently bypass ``--validate`` and the
#: readers (report/diff/metrics_http) that key off names.
STREAM_NAMES = frozenset({
    # spans
    "train/iteration", "data_wait", "validation", "checkpoint",
    "perf/warmup", "perf/timed",
    # serving (bigdl_tpu/serving/, docs/serving.md): startup AOT warmup
    # span, server lifecycle instants, queue gauge, admission counters
    "serve/warmup", "serve/started", "serve/drain", "serve/load",
    "serve/queue_depth", "serve/requests", "serve/rejected",
    # the LLM decode subsystem (serving/generate/, docs/serving.md
    # "Autoregressive generation"): tokens-emitted counter per coalesced
    # decode iteration, live active-sequence + KV-cache-occupancy gauges
    "serve/generate", "serve/active_seqs", "serve/cache_occupancy",
    # SLO burn accounting (telemetry/request_trace.py SLOTracker):
    # observed windowed p99 / declared budget, published rate-limited
    # into the run log so the FleetWatcher and `telemetry diff` see the
    # burn without scraping /metrics
    "serve/slo_p99_burn", "serve/slo_ttft_burn",
    # instants
    "epoch", "checkpoint/saved", "straggler/timeout", "run/retry",
    "metrics/serving", "profile/armed", "profile/captured",
    "flight/dump",
    # managed persistent compile cache (utils/compile_cache.py,
    # docs/compile.md): one instant per persistent-cache hit/miss (the
    # per-run counts `telemetry diff` and /metrics key off), plus the
    # once-per-run cache-key ingredients announcement
    "compile/cache_hit", "compile/cache_miss", "compile/cache",
    # kernel dispatch (bigdl_tpu/ops/dispatch.py): one instant per
    # TRACE-time backend decision — op, backend (pallas|xla), reason —
    # so attribution can name which backend each module compiled to;
    # a leg adds how it was launched (op=gated_delta_rule: leg, chunk,
    # chunks, heads, key_dim, value_dim and, on its Pallas leg,
    # chunks_per_block, grid;
    # op=attention: window, q_heads, kv_heads, head_dim, scale (null: 1
    # over the root of head_dim) and the flash leg's blocks;
    # op=gated_short_conv: taps, channels, tokens; op=ssd: chunk, chunks,
    # heads, head_dim, state, groups and, on its Pallas leg, head_block,
    # grid; op=lrn_cross_map.fwd|.bwd: channels, size, layout;
    # op=latent_attention: heads, qk_dim, rope_dim, value_dim, q_rank,
    # kv_rank, scale and the flash leg's blocks)
    "kernel/dispatch",
    # routed experts (bigdl_tpu/nn/layers/moe.py RoutedExperts): one
    # instant per TRACE of a layer (experts, held, top_k, capacity
    # rows, worst = the most rows that can land here, combine = how the
    # sorted rows return to their tokens: "fold" where capacity ==
    # tokens x top_k, so the order is a whole permutation and its
    # inverse gathers tokens x top_k rows in both passes; "scatter_add"
    # where a prefix of capacity rows is scatter-added; the router's
    # score function, whether a bias enters the choice, whether there
    # is a shared expert, the latent width the routed rows have or null,
    # the experts' activation and whether they are gated), and per step
    # the rows
    # each held expert received, their sum, largest and mean (max over
    # mean: the imbalance the grouped product sees) and the rows that
    # took the exact path (counters, emitted by the Optimizer where it
    # has the loss on the host)
    "moe/route", "moe/load", "moe/exact_rows", "moe/held_rows",
    "moe/held_rows_max", "moe/held_rows_mean",
    # rematerialization (bigdl_tpu/nn/layers/container_ext.py Remat):
    # one instant per value a block keeps for its backward pass in place
    # of recomputing it, as the TRACE of that pass decides it: the name
    # a kernel gave the value (kept: flash_attention/out,
    # flash_attention/lse), its shape, dtype and bytes; a block that
    # names nothing, or is given a policy of its own, says nothing
    "remat/keep",
    # short convolution (bigdl_tpu/nn/layers/short_conv.py
    # GatedShortConv): per step and layer the root mean square of what
    # enters the convolution (B * u) and of the layer's output
    # (counters, as above; the layer's trace-time kernel/dispatch
    # instant is op=gated_short_conv with taps, channels, tokens)
    "short_conv/gate_in_rms", "short_conv/out_rms",
    # linear attention (bigdl_tpu/nn/layers/linear_attention.py
    # GatedDeltaNet): per step and layer the mean decay exp(g), the mean
    # beta and the largest state norm over the heads after the last
    # token (counters, emitted by the Optimizer where it has the loss on
    # the host; the rule's own trace-time decision is a kernel/dispatch
    # instant with op=gated_delta_rule)
    "linear_attn/decay_mean", "linear_attn/beta_mean",
    "linear_attn/state_norm_max",
    # state-space mixer (bigdl_tpu/nn/layers/ssm.py Mamba2Mixer): per
    # step and layer the mean decay a token exp(dt A), the mean step dt
    # and the largest state norm over the heads after the last token
    # (counters, as above; the scan's own trace-time decision is a
    # kernel/dispatch instant with op=ssd: chunk, chunks, heads,
    # head_dim, state, groups and, on its Pallas leg, head_block, grid)
    "ssm/decay_mean", "ssm/dt_mean", "ssm/state_norm_max",
    # multi-stream residual path (bigdl_tpu/nn/layers/hyper_connection.py
    # HyperConnection): one instant per TRACE of a path (streams,
    # sinkhorn_iters, clamp, eps, embed_dim, dtype of the streams), and
    # per step and path the largest distance of a column sum of the
    # mixing matrix from 1 (Sinkhorn's own error after its iterations),
    # the mean off-diagonal entry of that matrix, and the means of the
    # read and the write weights (counters, as above; a block under
    # nn.Remat says the streams it keeps as its input on a remat/keep
    # instant, kept=residual_streams)
    "residual/mhc", "mhc/col_err_max", "mhc/res_offdiag_mean",
    "mhc/pre_mean", "mhc/post_mean",
    # fault tolerance (bigdl_tpu/faults.py + docs/fault_tolerance.md):
    # injected faults, quarantined torn checkpoints, graceful
    # preemption, and checkpoint auto-resume
    "fault/injected", "checkpoint/quarantined", "run/preempted",
    "run/resumed",
    # cluster fault tolerance (bigdl_tpu/parallel/cluster.py): peer
    # declared lost by the collective watchdog, a checkpoint step
    # certified cluster-consistent by the commit barrier, and a
    # supervised full-cluster restart
    "cluster/peer_lost", "cluster/commit", "cluster/restart",
    # elastic resharding (docs/fault_tolerance.md "Elastic recovery"):
    # a topology change — a restore onto a different mesh than wrote
    # the checkpoint (source=restore, old→new process/device counts)
    # or a supervised capacity-aware width change (source=supervisor,
    # from_n/to_n/declared_n).  The fleet view folds it so hosts of a
    # legitimately-shrunk cluster are marked departed, not stalled.
    "cluster/reshard",
    # straggler-tolerant local-SGD (bigdl_tpu/parallel/local_sync.py,
    # docs/fault_tolerance.md "Straggler tolerance"): one instant per
    # parameter averaging (round, step, h, bytes, dur), one per
    # bounded-staleness barrier pass (round, waited_s, lag, stale), and
    # the shed verdict — a peer S averaging rounds behind excused from
    # the fleet, which continues averaging at reduced width
    "sync/average", "sync/staleness", "cluster/shed",
    # goodput ledger inputs (telemetry/ledger.py): checkpoint-restore
    # wall (stage), preempt-resume fast-forward replay (stage), and the
    # supervisor's drain interval (instant with dur) — the measured
    # out-of-step intervals the run-level conservation check needs
    "checkpoint/restore", "resume/fast_forward", "cluster/drain",
    # fleet aggregation (telemetry/fleet.py): the coordinator's live
    # watcher publishes the completed-step gap and the blamed per-step
    # excess as gauges, and a rate-limited skew-blame instant whenever
    # the fleet diverges — the PR-7 watchdog's flight dump carries them
    "cluster/skew", "fleet/lag_steps", "fleet/skew_s",
    # memory observability (telemetry/memory.py): one rate-limited
    # instant when a device's live allocator peak crosses 95% of its
    # HBM limit — the step before RESOURCE_EXHAUSTED, surfaced so the
    # fleet blame and tpu_watch can call it BEFORE the crash
    "memory/pressure",
    # sparse embedding-gradient sync (nn/layers/embedding.py +
    # parallel/train_step.py, docs/sparse.md): once per step object,
    # the static per-step sync accounting — touched-row caps per table,
    # bytes the coalesced (indices, rows) sync moves, and the dense
    # table all-reduce bytes it replaced (saved_bytes = the win
    # tpu_watch prints and the comms walker confirms)
    "train/sparse",
    # health findings (telemetry/health.py detectors + policy)
    "health/nonfinite", "health/skip", "health/loss_spike",
    "health/plateau", "health/grad_explosion", "health/halt",
    # counters / gauges
    "perf/records_per_sec", "prefetch/queue_depth", "prefetch/in_flight",
    "prefetch/staging_reuse",
    # pipeline stages (optim.Metrics forwarding)
    "host to device time", "host to device time (overlapped)",
    "batch stack time (overlapped)",
    "dispatch time", "computing time",
    "compile + first iteration time", "data time", "validation time",
    "checkpoint time", "checkpoint wait time",
    # compile-event names (TrainStep/EvalStep dispatch kinds; the
    # serving executor splits startup warmup compiles from the
    # in-request-path compiles a healthy server never emits)
    "TrainStep.run", "TrainStep.run_sharded", "EvalStep.run",
    "ServeExecutor.warmup", "ServeExecutor.compile",
    # the generation executor's prefill/decode compiles split the same
    # way: warmup names are paid once at startup, the in-request-path
    # name never appears in a healthy server
    "GenerateExecutor.warmup", "GenerateExecutor.compile",
})


def validate_event(event: Dict[str, Any]) -> List[str]:
    """Field-level check of one event; returns human-readable problems
    (empty when valid)."""
    errors: List[str] = []
    if not isinstance(event, dict):
        return [f"event is {type(event).__name__}, not an object"]
    for field, types in _BASE.items():
        if field not in event:
            errors.append(f"missing base field {field!r}")
        elif not isinstance(event[field], types) \
                or isinstance(event[field], bool):
            errors.append(f"base field {field!r} has type "
                          f"{type(event[field]).__name__}")
    kind = event.get("kind")
    if kind not in KINDS:
        errors.append(f"unknown kind {kind!r}")
        return errors
    for field, types in KINDS[kind].items():
        if field not in event:
            errors.append(f"{kind}: missing field {field!r}")
        elif not isinstance(event[field], types) \
                or isinstance(event[field], bool):
            errors.append(f"{kind}: field {field!r} has type "
                          f"{type(event[field]).__name__}")
    return errors


def validate_events(events: Iterable[Dict[str, Any]]) -> List[str]:
    """Per-event checks plus the structural span invariants: matched
    begin/end per id, LIFO close order per thread, unique span ids."""
    errors: List[str] = []
    stacks: Dict[int, List[Tuple[int, str]]] = {}
    seen_ids: set = set()
    for i, ev in enumerate(events):
        for problem in validate_event(ev):
            errors.append(f"event {i}: {problem}")
        kind = ev.get("kind")
        tid = ev.get("tid")
        if kind == "span_begin" and isinstance(ev.get("span"), int):
            sid = ev["span"]
            if sid in seen_ids:
                errors.append(f"event {i}: span id {sid} reused")
            seen_ids.add(sid)
            stack = stacks.setdefault(tid, [])
            if ev.get("depth") != len(stack):
                errors.append(f"event {i}: span {sid} depth "
                              f"{ev.get('depth')} != stack depth "
                              f"{len(stack)}")
            parent = stack[-1][0] if stack else 0
            if ev.get("parent") != parent:
                errors.append(f"event {i}: span {sid} parent "
                              f"{ev.get('parent')} != open span {parent}")
            stack.append((sid, ev.get("name", "")))
        elif kind == "span_end" and isinstance(ev.get("span"), int):
            sid = ev["span"]
            stack = stacks.setdefault(tid, [])
            if not stack:
                errors.append(f"event {i}: span_end {sid} with no open "
                              f"span on tid {tid}")
            else:
                top_sid, top_name = stack.pop()
                if top_sid != sid:
                    errors.append(f"event {i}: span_end {sid} closes out "
                                  f"of order (open span is {top_sid} "
                                  f"{top_name!r})")
                elif ev.get("name") != top_name:
                    errors.append(f"event {i}: span_end {sid} name "
                                  f"{ev.get('name')!r} != begin name "
                                  f"{top_name!r}")
    for tid, stack in stacks.items():
        for sid, name in stack:
            errors.append(f"span {sid} {name!r} never closed "
                          f"(tid {tid})")
    return errors


def read_events(path: str) -> Tuple[List[Dict[str, Any]], List[str]]:
    """Parse a JSONL run log; returns (events, parse errors).  Malformed
    lines are reported, not fatal — a crashed run may truncate its final
    line."""
    events: List[Dict[str, Any]] = []
    errors: List[str] = []
    with open(path, "r", encoding="utf-8") as fh:
        for lineno, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except ValueError as e:
                errors.append(f"line {lineno}: not valid JSON ({e})")
    return events, errors


def validate_run(path: str) -> Tuple[int, List[str]]:
    """Full-file validation: parse + per-event + structural checks.
    Returns (event count, problems)."""
    events, errors = read_events(path)
    errors.extend(validate_events(events))
    return len(events), errors
