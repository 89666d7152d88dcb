"""CLI: ``python -m bigdl_tpu.telemetry ...`` — inspect and compare runs.

Default output: the summary report (per-stage time table, step-time
p50/p95, compile/retrace/event timeline, device facts + MFU estimate,
training-health section).

Options::

    python -m bigdl_tpu.telemetry run.jsonl                  # summary
    python -m bigdl_tpu.telemetry run.jsonl --json           # machine view
    python -m bigdl_tpu.telemetry run.jsonl --chrome t.json  # chrome://tracing
    python -m bigdl_tpu.telemetry run.jsonl --validate       # schema check
    python -m bigdl_tpu.telemetry p0.jsonl p1.jsonl ...      # fleet view
    python -m bigdl_tpu.telemetry p0.jsonl p1.jsonl --chrome fleet.json
    python -m bigdl_tpu.telemetry fleet <dir> [--watch]      # live fleet table
    python -m bigdl_tpu.telemetry trace run.jsonl --slowest 3  # request
    python -m bigdl_tpu.telemetry trace run.jsonl --id abc123  # waterfalls
    python -m bigdl_tpu.telemetry diff old.jsonl new.jsonl   # regression
    python -m bigdl_tpu.telemetry diff old_bench.json new_bench.json
    python -m bigdl_tpu.telemetry goodput run.jsonl ...      # wall-time
    python -m bigdl_tpu.telemetry goodput --supervise-dir d  # ledger
    python -m bigdl_tpu.telemetry attribute --model lenet    # per-module cost
    python -m bigdl_tpu.telemetry attribute run.jsonl        # from a run log
    python -m bigdl_tpu.telemetry attribute --comms --model lenet --mesh 2
    python -m bigdl_tpu.telemetry attribute --comms run.jsonl  # comms view
    python -m bigdl_tpu.telemetry attribute --memory --model lenet --mesh 2
    python -m bigdl_tpu.telemetry attribute --memory run.jsonl # HBM view
    python -m bigdl_tpu.telemetry memory --model transformer --mesh 4 \
        --zero1 --remat                                  # fit estimator

Passing several run logs merges them into the multi-host fleet view
(per-process step progress + step-skew + blame); ``--chrome`` then
writes ONE trace with a pid lane per process, viewable as a fleet
timeline in Perfetto.  ``fleet`` tails/aggregates a telemetry DIRECTORY
(one-shot or ``--watch``) — the offline twin of the coordinator's live
``/status`` fleet block.  ``diff`` compares two runs (JSONL logs or
bench_serving.py JSON, mixed freely) and exits nonzero when the candidate
regressed beyond the thresholds — the CI gate.  ``attribute`` prints
the per-module FLOPs/bytes table — computed fresh for a registry model
(``--model``, CPU-friendly: lower + parse, no run needed) or read back
from a run log's ``attribution`` event; ``--comms`` switches to the
per-collective view (bytes moved, mesh axes, owning modules, bandwidth
vs ``BIGDL_PEAK_BW``), enriched with measured per-collective wall time
when the log names a perfetto profiler capture that still exists.
``trace`` renders per-request serving waterfalls offline from a run
log's ``request`` events (telemetry/request_trace.py) — the slowest N
by default, one exact id with ``--id``, request-lane Chrome output with
``--chrome``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from bigdl_tpu.telemetry import schema
from bigdl_tpu.telemetry.chrome_trace import write_chrome_trace
from bigdl_tpu.telemetry.report import (fleet_summarize, format_fleet,
                                        format_summary, summarize)


def attribute_main(argv) -> int:
    """``python -m bigdl_tpu.telemetry attribute`` entry (also backs the
    ``models/cli.py attribute`` subcommand)."""
    import argparse

    from bigdl_tpu.telemetry import attribution

    p = argparse.ArgumentParser(
        prog="bigdl_tpu.telemetry attribute",
        description="per-module FLOPs/bytes attribution table "
                    "(--comms: per-collective bytes/axes/bandwidth)")
    p.add_argument("run", nargs="?", default=None, metavar="run.jsonl",
                   help="read the attribution event back from a run log "
                        "(recorded with BIGDL_ATTRIBUTION=1; comms "
                        "events are on by default for sharded steps)")
    p.add_argument("--model", default=None,
                   help="compute fresh for a registry model instead")
    p.add_argument("-b", "--batch", type=int, default=8)
    p.add_argument("--forward", action="store_true",
                   help="attribute the inference forward instead of the "
                        "full train step")
    p.add_argument("--comms", action="store_true",
                   help="per-collective comms view: bytes moved, mesh "
                        "axes, owning modules, bandwidth vs "
                        "BIGDL_PEAK_BW")
    p.add_argument("--memory", action="store_true",
                   help="per-module HBM view: params / optimizer state "
                        "/ activations-at-peak / workspace per device "
                        "(telemetry/memory.py)")
    p.add_argument("--mesh", type=int, default=0, metavar="N",
                   help="(--comms/--memory --model) data-axis mesh size "
                        "to shard over (default: all local devices for "
                        "--comms, single device for --memory)")
    p.add_argument("--sync", default="allreduce",
                   choices=("allreduce", "sharded", "fsdp"),
                   help="(--comms/--memory --model) parameter_sync "
                        "mode to compile with")
    p.add_argument("--sparse", default=None,
                   choices=("off", "auto", "on"),
                   help="(--comms --model) override BIGDL_SPARSE for "
                        "this compile — A/B the sparse embedding sync "
                        "vs the dense table all-reduce "
                        "(docs/sparse.md)")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)
    if (args.run is None) == (args.model is None):
        p.error("pass exactly one of run.jsonl or --model NAME")
    if args.comms and args.memory:
        p.error("--comms and --memory are different views — pass one")
    if args.memory:
        from bigdl_tpu.telemetry import memory as memory_mod

        if args.model is not None:
            result = memory_mod.attribute_memory_model(
                args.model, batch=args.batch, devices=args.mesh,
                sync=args.sync)
        else:
            events, parse_errors = schema.read_events(args.run)
            for e in parse_errors:
                print(f"warning: {args.run}: {e}", file=sys.stderr)
            result = memory_mod.memory_from_events(events)
            if result is None:
                print(f"error: {args.run} has no memory event (sharded "
                      f"steps emit one by default; BIGDL_MEMORY=on "
                      f"forces it, or use --model)", file=sys.stderr)
                return 2
        if args.json:
            print(json.dumps(result, indent=2, default=str))
        else:
            print(memory_mod.format_memory(result))
        return 0
    if args.comms:
        from bigdl_tpu.telemetry import comms as comms_mod

        if args.model is not None:
            result = comms_mod.attribute_comms_model(
                args.model, batch=args.batch, devices=args.mesh,
                sync=args.sync, sparse=args.sparse)
        else:
            events, parse_errors = schema.read_events(args.run)
            for e in parse_errors:
                print(f"warning: {args.run}: {e}", file=sys.stderr)
            result = comms_mod.comms_from_events(events)
            if result is None:
                print(f"error: {args.run} has no comms event (sharded "
                      f"steps emit one by default; BIGDL_COMMS=on "
                      f"forces it, or use --model)", file=sys.stderr)
                return 2
            _enrich_measured(result, events)
        if args.json:
            print(json.dumps(result, indent=2, default=str))
        else:
            print(comms_mod.format_comms(result))
        return 0
    if args.model is not None:
        result = attribution.attribute_model(
            args.model, batch=args.batch, train=not args.forward)
    else:
        events, parse_errors = schema.read_events(args.run)
        for e in parse_errors:
            print(f"warning: {args.run}: {e}", file=sys.stderr)
        result = attribution.rows_from_events(events)
        if result is None:
            print(f"error: {args.run} has no attribution event (record "
                  f"with BIGDL_ATTRIBUTION=1, or use --model)",
                  file=sys.stderr)
            return 2
    if args.json:
        print(json.dumps(result, indent=2, default=str))
    else:
        print(attribution.format_attribution(result))
    return 0


def _enrich_measured(result, events) -> None:
    """Fold measured per-collective wall time into a comms result when
    the log records a perfetto profiler capture whose trace dir still
    exists (``POST /profile?steps=N&perfetto=1`` wrote it)."""
    import os

    from bigdl_tpu.telemetry import comms as comms_mod

    captures = [e for e in events
                if e.get("kind") == "event"
                and e.get("name") == "profile/captured"
                and e.get("perfetto") and e.get("dir")]
    armed_steps = {e.get("dir"): e.get("steps")
                   for e in events
                   if e.get("kind") == "event"
                   and e.get("name") == "profile/armed"}
    for cap in reversed(captures):  # newest capture wins
        trace_dir = cap["dir"]
        if not os.path.isdir(trace_dir):
            continue
        times = comms_mod.collective_times_from_trace(trace_dir)
        if not times:
            continue
        steps = max(int(armed_steps.get(trace_dir) or 1), 1)
        # one unit everywhere: per-STEP seconds (the capture spans
        # `steps` iterations), for the total and the per-op split alike
        result["measured_by_op"] = {op: t / steps
                                    for op, t in times.items()}
        result["measured_s"] = sum(times.values()) / steps
        result["measured_from"] = trace_dir
        return


def memory_main(argv) -> int:
    """``python -m bigdl_tpu.telemetry memory`` — the device-free fit
    estimator: lower a registry TrainStep on CPU with the requested
    mesh/sharding, predict per-device peak HBM, compare against the
    budget (``BIGDL_HBM_GB`` / the per-chip table), and rank blocks by
    remat payoff.  Exit 0 = fits (or no budget known), 1 = predicted
    peak exceeds the budget, 2 = nothing to estimate."""
    import argparse

    from bigdl_tpu.telemetry import memory as memory_mod

    p = argparse.ArgumentParser(
        prog="bigdl_tpu.telemetry memory",
        description="device-free fit estimator: will this model fit on "
                    "N chips? (predicted per-device peak HBM vs "
                    "BIGDL_HBM_GB, with a remat advisor)")
    p.add_argument("--model", required=True,
                   help="registry model name")
    p.add_argument("-b", "--batch", type=int, default=8,
                   help="GLOBAL batch size (default %(default)s)")
    p.add_argument("--mesh", type=int, default=1, metavar="N",
                   help="data-axis mesh size to predict for (CPU "
                        "emulation needs XLA_FLAGS=--xla_force_host_"
                        "platform_device_count=N)")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1 layout: optimizer state sharded over "
                        "the data axis (parameter_sync='sharded')")
    p.add_argument("--fsdp", action="store_true",
                   help="ZeRO-3 layout: params + optimizer state "
                        "sharded (parameter_sync='fsdp')")
    p.add_argument("--remat", action="store_true",
                   help="estimate WITH whole-model rematerialization "
                        "(activations recomputed, not stored)")
    p.add_argument("--no-advice", action="store_true",
                   help="skip the remat advisor (one fewer re-lower)")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)
    sync = "fsdp" if args.fsdp else ("sharded" if args.zero1
                                     else "allreduce")
    try:
        result = memory_mod.fit_estimate(
            args.model, batch=args.batch, devices=args.mesh, sync=sync,
            remat=args.remat, advise=not args.no_advice)
    except (KeyError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(result, indent=2, default=str))
    else:
        print(memory_mod.format_memory(result))
    if result.get("fits") is False:
        return 1
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "diff":
        from bigdl_tpu.telemetry import diff as diff_mod

        return diff_mod.main(argv[1:])
    if argv and argv[0] == "attribute":
        return attribute_main(argv[1:])
    if argv and argv[0] == "memory":
        return memory_main(argv[1:])
    if argv and argv[0] == "fleet":
        from bigdl_tpu.telemetry import fleet as fleet_mod

        return fleet_mod.main(argv[1:])
    if argv and argv[0] == "trace":
        from bigdl_tpu.telemetry import request_trace

        return request_trace.trace_main(argv[1:])
    if argv and argv[0] == "goodput":
        from bigdl_tpu.telemetry import ledger

        return ledger.goodput_main(argv[1:])

    p = argparse.ArgumentParser(
        prog="bigdl_tpu.telemetry",
        description="summarize / compare / export telemetry run logs "
                    "(subcommands: diff <runA> <runB>, fleet <dir> "
                    "[--watch], trace run.jsonl [--slowest N|--id ID], "
                    "goodput <run.jsonl...|--supervise-dir DIR>, "
                    "attribute [run.jsonl | --model NAME] "
                    "[--comms|--memory], memory --model NAME --mesh N)")
    p.add_argument("runs", nargs="+", metavar="run.jsonl",
                   help="path(s) to run-*.jsonl event logs; several "
                        "merge into the fleet view")
    p.add_argument("--json", action="store_true",
                   help="emit the summary as JSON instead of text")
    p.add_argument("--chrome", metavar="OUT.json", default=None,
                   help="also write a Chrome trace_event JSON for "
                        "chrome://tracing / Perfetto (several runs "
                        "merge into one trace with a pid lane per "
                        "process)")
    p.add_argument("--validate", action="store_true",
                   help="only validate the log(s) against the schema; "
                        "exit 1 on any violation")
    args = p.parse_args(argv)

    if args.validate:
        total_events = 0
        errors = []
        for path in args.runs:
            events, parse_errors = schema.read_events(path)
            total_events += len(events)
            errors += [f"{path}: {e}" for e in
                       parse_errors + schema.validate_events(events)]
        if errors:
            for e in errors:
                print(e, file=sys.stderr)
            print(f"{total_events} events, {len(errors)} problems")
            return 1
        print(f"{total_events} events, schema ok")
        return 0

    loaded = []
    for path in args.runs:
        events, parse_errors = schema.read_events(path)
        for e in parse_errors:  # non-fatal: a crashed run truncates a line
            print(f"warning: {path}: {e}", file=sys.stderr)
        loaded.append((path, events))

    if len(loaded) > 1:
        fleet = fleet_summarize(loaded)
        if args.json:
            print(json.dumps(fleet, indent=2, default=str))
        else:
            print(format_fleet(fleet))
        if args.chrome:
            # one merged trace, a pid lane per process — the fleet
            # timeline view (each log keeps its own OS pid; the lane
            # label names the process_index and file)
            merged = []
            names = {}
            for path, events in loaded:
                merged.extend(events)
                pidx = next((e.get("meta", {}).get("process_index")
                             for e in events
                             if e.get("kind") == "run_start"), None)
                for e in events:
                    if isinstance(e.get("pid"), int):
                        label = f"p{pidx}" if pidx is not None else "p?"
                        names[e["pid"]] = \
                            f"{label} ({os.path.basename(path)})"
                        break
            merged.sort(key=lambda e: e.get("ts", 0.0))
            n = write_chrome_trace(merged, args.chrome,
                                   process_names=names)
            print(f"\nchrome trace: {args.chrome} ({n} trace events, "
                  f"{len(loaded)} process lanes) — open in "
                  f"chrome://tracing or https://ui.perfetto.dev",
                  file=sys.stderr if args.json else sys.stdout)
        return 0

    path, events = loaded[0]
    summary = summarize(events)
    if args.json:
        print(json.dumps(summary, indent=2, default=str))
    else:
        print(format_summary(summary, events))
    if args.chrome:
        n = write_chrome_trace(events, args.chrome)
        print(f"\nchrome trace: {args.chrome} ({n} trace events) — open "
              f"in chrome://tracing or https://ui.perfetto.dev",
              file=sys.stderr if args.json else sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
