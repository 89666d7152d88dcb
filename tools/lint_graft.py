#!/usr/bin/env python
"""CI gate: tracer-leak AST lint over the repo's Python sources.

Thin wrapper over ``bigdl_tpu.analysis.lint_sources`` (pass 4 of the
static analyzer) pinned to the repo's source roots; exits nonzero when
any error-severity finding fires, so CI fails on a freshly introduced
tracer leak.  The same check runs inside the tier-1 pytest run via
``tests/test_lint_clean.py``.

Usage::

    python tools/lint_graft.py                 # bigdl_tpu/ tools/ examples/
    python tools/lint_graft.py mypkg/ file.py  # explicit targets
    python tools/lint_graft.py --warnings-ok   # ignore warnings
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bigdl_tpu.analysis.ast_lint import DEFAULT_LINT_DIRS, lint_paths  # noqa: E402

#: modules the CI gate PINS: reliability-critical subsystems whose
#: accidental deletion/rename must fail the build, not pass it silently
#: (the default-dir lint would simply stop seeing a removed file)
PINNED_MODULES = [
    "bigdl_tpu/faults.py",
    "bigdl_tpu/utils/ckpt_digest.py",
    "bigdl_tpu/utils/sharded_ckpt.py",
    # elastic resharding (ISSUE 12): losing this silently reverts
    # checkpoints to same-shape-only restore — a shrunk slice can no
    # longer resume, and ZeRO restores onto the wrong width would
    # silently replicate every moment shard
    "bigdl_tpu/utils/ckpt_topology.py",
    # cluster fault tolerance (ISSUE 7): losing this silently reverts
    # peer loss to an indefinite collective hang and restores to
    # per-host (possibly mixed-step) discovery
    "bigdl_tpu/parallel/cluster.py",
    "bigdl_tpu/telemetry/schema.py",
    "bigdl_tpu/telemetry/flight.py",
    "bigdl_tpu/telemetry/metrics_http.py",
    # fleet-wide comms observability (ISSUE 10): losing comms.py blinds
    # the bytes-moved gate the ZeRO/pipeline work lands against; losing
    # fleet.py silently reverts cross-host visibility to after-the-fact
    # log merges with no skew blame
    "bigdl_tpu/telemetry/comms.py",
    "bigdl_tpu/telemetry/fleet.py",
    # request-level serving traces (ISSUE 14): losing this blinds the
    # per-request waterfalls, the slow-request blame verdict, and the
    # SLO burn gate — "one user's request was slow" reverts to an
    # unanswerable aggregate p99
    "bigdl_tpu/telemetry/request_trace.py",
    # memory observability (ISSUE 11): losing memory.py blinds the
    # peak_hbm_bytes gate (the ZeRO "optimizer HBM dropped" proof), the
    # fit estimator, and OOM forensics — device OOMs revert to a bare
    # RESOURCE_EXHAUSTED with no resident-buffer evidence
    "bigdl_tpu/telemetry/memory.py",
    # the ops library (PR 6): losing any of these silently reverts
    # hot paths to unfused XLA chains and wrong-by-autodiff VJPs
    "bigdl_tpu/ops/dispatch.py",
    "bigdl_tpu/ops/lrn.py",
    "bigdl_tpu/ops/norm.py",
    "bigdl_tpu/ops/pool.py",
    "bigdl_tpu/ops/attention.py",
    # the serving layer (ISSUE 8): losing any of these silently reverts
    # online inference to per-call EvalStep rebuilds (a compile per
    # predict) and drops the continuous-batching HTTP frontend
    "bigdl_tpu/serving/buckets.py",
    "bigdl_tpu/serving/executor.py",
    "bigdl_tpu/serving/batcher.py",
    "bigdl_tpu/serving/server.py",
    # the LLM decode subsystem (ISSUE 13): losing kv_cache.py breaks
    # the trace-order cache contract silently (decode would recompute
    # full context); losing decode.py/batcher.py drops /v1/generate and
    # reverts generation to one full forward per token
    "bigdl_tpu/serving/generate/kv_cache.py",
    "bigdl_tpu/serving/generate/decode.py",
    "bigdl_tpu/serving/generate/batcher.py",
    # compile-time war (ISSUE 9): losing scan.py silently reverts the
    # registry models to N-times-unrolled lowering; losing
    # compile_cache.py blinds the persistent cache (hits/misses/compile
    # budget become unmeasured again)
    "bigdl_tpu/nn/layers/scan.py",
    "bigdl_tpu/utils/compile_cache.py",
    # sparse embedding fast path (ISSUE 15): losing embedding.py
    # silently reverts every table gradient to the dense [vocab, dim]
    # all-reduce (and drops LookupTable/EmbeddingBag outright); losing
    # dlrm.py drops the recsys scenario both bench harnesses gate
    "bigdl_tpu/nn/layers/embedding.py",
    "bigdl_tpu/models/dlrm.py",
    # goodput ledger (ISSUE 18): losing ledger.py silently drops the
    # run-level wall-time accounting every surface folds (goodput
    # event, /status.goodput, fleet columns, diff/bench gates)
    "bigdl_tpu/telemetry/ledger.py",
    # straggler-tolerant local SGD (ISSUE 20): losing local_sync.py
    # silently drops the bounded-staleness barrier + shed protocol —
    # parameter_sync=local would average islands but never exchange
    # across processes, and a slow host would stall the fleet forever
    "bigdl_tpu/parallel/local_sync.py",
]


def check_pins(repo: str) -> list:
    """Missing pinned modules (empty = all present)."""
    return [m for m in PINNED_MODULES
            if not os.path.isfile(os.path.join(repo, m))]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="tracer-leak lint (python -m bigdl_tpu.analysis --lint)")
    p.add_argument("paths", nargs="*",
                   help=f"files/dirs to lint (default: "
                        f"{' '.join(DEFAULT_LINT_DIRS)})")
    p.add_argument("--suppress", action="append", default=[],
                   metavar="RULE")
    p.add_argument("--warnings-ok", action="store_true",
                   help="exit 0 even when warnings fire (errors still "
                        "fail)")
    args = p.parse_args(argv)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    missing = check_pins(repo)
    if missing:
        print(f"pinned modules missing: {', '.join(missing)}")
        return 1
    paths = args.paths or [os.path.join(repo, d) for d in DEFAULT_LINT_DIRS]
    report = lint_paths(paths, suppress=args.suppress)
    print(report.format())
    if report.errors:
        return 1
    if report.warnings and not args.warnings_ok:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
