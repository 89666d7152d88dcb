"""Schema-drift guard: every event kind and stream name the sources
emit must be registered in ``telemetry/schema.py`` — a new event can't
silently bypass ``--validate`` and the readers (report, diff,
metrics_http) that key off names.

The scan is purely lexical (literal first arguments of the emit
helpers), so adding an event stream means adding its name to
``schema.KINDS`` / ``schema.STREAM_NAMES`` in the same change — which
is exactly the point."""

import glob
import os
import re

from bigdl_tpu.telemetry import schema

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: reliability-critical modules the registry pins alongside the CI lint
#: (tools/lint_graft.py PINNED_MODULES) — a rename/removal must fail
#: tests, not silently drop the subsystem from the lexical scan
PINNED = ["bigdl_tpu/faults.py", "bigdl_tpu/utils/ckpt_digest.py",
          "bigdl_tpu/utils/sharded_ckpt.py",
          # elastic resharding (ISSUE 12): the topology record both
          # checkpoint backends write and the pre-load reshard
          # validation — a silent drop reverts checkpoints to
          # same-shape-only restore
          "bigdl_tpu/utils/ckpt_topology.py",
          "bigdl_tpu/parallel/cluster.py",
          # the serving layer (ISSUE 8): the bucketed compile cache the
          # batch Predictor ALSO routes through — a silent drop reverts
          # every predict() to a fresh-EvalStep compile
          "bigdl_tpu/serving/buckets.py",
          "bigdl_tpu/serving/executor.py",
          "bigdl_tpu/serving/batcher.py",
          "bigdl_tpu/serving/server.py",
          # the LLM decode subsystem (ISSUE 13): KV cache + prefill/
          # decode executables + generation batching — a silent drop
          # reverts generation to one full-context forward per token
          # and loses the /v1/generate streaming surface
          "bigdl_tpu/serving/generate/kv_cache.py",
          "bigdl_tpu/serving/generate/decode.py",
          "bigdl_tpu/serving/generate/batcher.py",
          # compile-time war (ISSUE 9): scan-over-layers + the managed
          # persistent compile cache — a silent drop reverts models to
          # N-times-unrolled lowering and unmeasured cache traffic
          "bigdl_tpu/nn/layers/scan.py",
          "bigdl_tpu/utils/compile_cache.py",
          # fleet-wide comms observability (ISSUE 10): the collective
          # walker the bytes-moved diff gate reads, and the live
          # cross-host aggregator behind /status.fleet + skew blame
          "bigdl_tpu/telemetry/comms.py",
          "bigdl_tpu/telemetry/fleet.py",
          # request-level serving traces (ISSUE 14): the span-timeline
          # store behind /v1/trace/<id>, the per-request blame verdict,
          # and the SLO burn gates — a silent drop reverts serving
          # observability to aggregate percentiles with no evidence
          "bigdl_tpu/telemetry/request_trace.py",
          # memory observability (ISSUE 11): the HBM walker behind the
          # peak_hbm_bytes diff gate, the fit estimator, and the
          # OOM-forensics evidence — a silent drop reverts device OOMs
          # to a bare RESOURCE_EXHAUSTED
          "bigdl_tpu/telemetry/memory.py",
          # sparse embedding fast path (ISSUE 15): the row-sparse
          # cotangent capture + the recsys scenario — a silent drop
          # reverts every embedding gradient to the dense table
          # all-reduce and loses the dlrm bench/serving tenant
          "bigdl_tpu/nn/layers/embedding.py",
          "bigdl_tpu/models/dlrm.py",
          # goodput ledger (ISSUE 18): a silent drop loses the
          # wall-time conservation contract and every goodput surface
          # (end-of-run event, CLI fold, diff/bench gates)
          "bigdl_tpu/telemetry/ledger.py",
          # straggler-tolerant local SGD (ISSUE 20): the bounded-
          # staleness barrier + shed protocol — a silent drop leaves
          # parameter_sync=local with no cross-process exchange and no
          # way to stop waiting for a slow host
          "bigdl_tpu/parallel/local_sync.py"]


def test_pinned_fault_tolerance_modules_present():
    missing = [m for m in PINNED
               if not os.path.isfile(os.path.join(REPO, m))]
    assert missing == [], (
        f"pinned modules missing: {missing} — fault injection and "
        f"crash-consistent restore are load-bearing (ISSUE 5); update "
        f"the pins if these moved")
    from tools.lint_graft import check_pins

    assert check_pins(REPO) == []

#: literal emit kinds: tracer.emit("<kind>", ...)
_KIND_RE = re.compile(r'\.emit\(\s*"(\w+)"')
#: literal stream names through the typed helpers
_NAME_RE = re.compile(
    r'\.(?:instant|gauge|counter|stage|span|begin)\(\s*"([^"]+)"')
#: instants spelled as emit("event", name="...")
_EVENT_NAME_RE = re.compile(r'\.emit\(\s*"event",\s*name="([^"]+)"')
#: compile events carry a literal dispatch-kind name
_COMPILE_NAME_RE = re.compile(r'\.emit\(\s*"compile",\s*name="([^"]+)"')
#: Metrics pipeline stages (forwarded into stage events by the bridge)
_STAGE_RE = re.compile(r'(?:metrics\.add|self\.metrics\.add|\.timer)'
                       r'\(\s*"([^"]+)"')
#: health findings are built as ("health/<x>", attrs) tuples
_FINDING_RE = re.compile(r'\(\s*"(health/[\w]+)"')


def _sources():
    paths = glob.glob(os.path.join(REPO, "bigdl_tpu", "**", "*.py"),
                      recursive=True)
    paths += glob.glob(os.path.join(REPO, "tools", "*.py"))
    paths += [os.path.join(REPO, "bench_serving.py")]
    # the registry itself and this test don't count as emitters
    skip = os.path.join("telemetry", "schema.py")
    return [p for p in paths if os.path.exists(p) and skip not in p]


def _scan():
    kinds, names = set(), set()
    for path in _sources():
        with open(path, "r", encoding="utf-8") as fh:
            src = fh.read()
        kinds.update(_KIND_RE.findall(src))
        names.update(_NAME_RE.findall(src))
        names.update(_EVENT_NAME_RE.findall(src))
        names.update(_COMPILE_NAME_RE.findall(src))
        names.update(_STAGE_RE.findall(src))
        if path.endswith(os.path.join("telemetry", "health.py")):
            names.update(_FINDING_RE.findall(src))
    return kinds, names


def test_every_emitted_kind_is_registered():
    kinds, _ = _scan()
    # pattern-rot tripwire: the scan must keep seeing the core kinds
    assert {"step", "compile", "device_facts", "health",
            "attribution"} <= kinds
    unregistered = sorted(kinds - set(schema.KINDS))
    assert unregistered == [], (
        f"event kinds emitted but not in schema.KINDS: {unregistered} — "
        f"register them (with their required fields) in "
        f"telemetry/schema.py")


def test_every_emitted_stream_name_is_registered():
    _, names = _scan()
    assert {"train/iteration", "data_wait", "straggler/timeout",
            "prefetch/queue_depth", "prefetch/in_flight",
            "prefetch/staging_reuse", "profile/armed",
            "flight/dump",
            "fault/injected", "checkpoint/quarantined",
            "run/preempted", "run/resumed"} <= names, \
        "name scan lost its anchors"
    unregistered = sorted(names - set(schema.STREAM_NAMES))
    assert unregistered == [], (
        f"stream names emitted but not in schema.STREAM_NAMES: "
        f"{unregistered} — register them in telemetry/schema.py so "
        f"--validate and the readers know about them")


def test_registry_names_are_not_stale():
    """The reverse direction, advisory-strength: names in the registry
    should still have an emitter somewhere (catches renames that forget
    the registry).  'computing time' is emitted via a ternary the
    lexical scan can't see; dispatch kinds are built dynamically."""
    _, names = _scan()
    allowed_unseen = {"computing time", "TrainStep.run",
                      "TrainStep.run_sharded", "EvalStep.run",
                      # serving compile events carry their name through
                      # a variable (warmup vs in-request-path), so the
                      # lexical scan can't see the literals
                      "ServeExecutor.warmup", "ServeExecutor.compile",
                      "GenerateExecutor.warmup",
                      "GenerateExecutor.compile"}
    stale = sorted(set(schema.STREAM_NAMES) - names - allowed_unseen)
    assert stale == [], (
        f"STREAM_NAMES entries with no emitter found: {stale} — "
        f"remove them or fix the rename")
