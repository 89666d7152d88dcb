"""Run-regression diff: compare two telemetry artifacts and say whether
the second one got worse.

``python -m bigdl_tpu.telemetry diff <runA> <runB>`` accepts either
JSONL run logs (anything ``schema.read_events`` parses) or a bench
artifact (``bench_serving.py``'s output: one JSON object with a
``configs`` table) — in any combination,
as long as both sides expose comparable metrics.  Compared, when
present on both sides:

- step p50 / p95 / mean seconds        (lower is better, pct threshold)
- throughput (records/s, images/s)     (higher is better, pct threshold)
- data-wait share of iteration time    (lower is better, pct threshold)
- MFU                                  (higher is better, pct threshold)
- compile / retrace counts             (count slack, default 0)
- health-event counts (nonfinite steps, spikes, ...) (count slack)
- goodput_pct / badput_s (run ledger)  (dedicated goodput threshold)

Exit code contract (CI-ready): 0 = no regression, 1 = at least one
metric regressed beyond its threshold, 2 = inputs not comparable.
``bench_serving.py --diff-against <baseline.json>`` delegates here.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["load_metrics", "run_log_metrics", "bench_metrics",
           "diff_metrics", "format_diff", "DEFAULT_THRESHOLD_PCT",
           "DEFAULT_COMPILE_THRESHOLD_PCT",
           "DEFAULT_MEMORY_THRESHOLD_PCT",
           "DEFAULT_GOODPUT_THRESHOLD_PCT", "MIN_GOODPUT_WALL_S"]

DEFAULT_THRESHOLD_PCT = 10.0

#: peak_hbm_bytes regression threshold (the memory budget,
#: docs/observability.md): its own knob because HBM regressions are
#: STEP-function failures — a model that grew 10% past the headroom
#: OOMs outright, so CI legs near the budget tighten this to ~2-5%
#: (``telemetry diff --memory-threshold-pct``) while roomy legs leave
#: the default.
DEFAULT_MEMORY_THRESHOLD_PCT = 10.0

#: compile_s regression threshold (the compile budget, docs/compile.md):
#: looser than the runtime threshold by design — compile wall time is
#: noisier run-to-run than step time, and the class of outlier this
#: gate exists for (lenet 445 s vs a 2.7 s sibling, BASELINE.md round
#: 5) is an order of magnitude, not ten percent.  ``telemetry diff
#: --compile-threshold-pct`` tightens it per CI leg.
DEFAULT_COMPILE_THRESHOLD_PCT = 50.0

#: goodput regression threshold (telemetry/ledger.py,
#: docs/observability.md "Goodput"): its own knob because goodput is the
#: run-level roll-up the wall-time-reclaiming PRs (overlap, local SGD,
#: autoscaling) gate against — a 5% drop in the fraction of wall time
#: that trained the model is a real loss even when every step-level
#: metric still passes the looser 10% default.  Applied to
#: ``goodput_pct`` (higher is better) and total ``badput_s`` (lower).
DEFAULT_GOODPUT_THRESHOLD_PCT = 5.0

#: run logs with less wall time than this carry no goodput metrics —
#: a run-level wall-time roll-up over a sub-second smoke run is noise,
#: and gating on it would fail CI on scheduler jitter
MIN_GOODPUT_WALL_S = 1.0

#: metric name -> (direction, kind); direction "lower"/"higher" is the
#: GOOD direction, kind "pct" uses the relative threshold, "count" the
#: absolute slack.  Per-config bench metrics are matched by suffix.
_RULES: List[Tuple[str, str, str]] = [
    ("step_p50_s", "lower", "pct"),
    ("step_p95_s", "lower", "pct"),
    ("step_mean_s", "lower", "pct"),
    ("throughput", "higher", "pct"),
    ("data_wait_share", "lower", "pct"),
    ("mfu", "higher", "pct"),
    ("compiles", "lower", "count"),
    # cumulative compile seconds — per run log and per bench config —
    # gate on the dedicated compile threshold ("pct_compile"), not the
    # runtime threshold: the compile budget (docs/compile.md)
    ("compile_s", "lower", "pct_compile"),
    (".compile_s", "lower", "pct_compile"),
    ("retraces", "lower", "count"),
    ("health_events", "lower", "count"),
    ("nonfinite_steps", "lower", "count"),
    # comms metrics (telemetry/comms.py): collective bytes per step and
    # collective seconds per step — the ZeRO/pipeline bytes-moved gate
    # ("did this sharding change move more data than it saved?"),
    # pct-thresholded like MFU
    ("comms_bytes", "lower", "pct"),
    ("comms_s", "lower", "pct"),
    (".comms_bytes", "lower", "pct"),
    (".comms_s", "lower", "pct"),
    # achieved training loss on bench rows: the
    # convergence side of the local-SGD trade — the comms_bytes gate
    # alone would bless H=10^6 (zero comms, junk model)
    ("final_loss", "lower", "pct"),
    (".final_loss", "lower", "pct"),
    # memory metrics (telemetry/memory.py): predicted per-device peak
    # HBM per run log (last memory event) and per bench row — the
    # "ZeRO-1 drops per-device optimizer HBM" gate, on the dedicated
    # memory threshold ("pct_memory")
    ("peak_hbm_bytes", "lower", "pct_memory"),
    (".peak_hbm_bytes", "lower", "pct_memory"),
    (".images_per_sec", "higher", "pct"),
    (".mfu", "higher", "pct"),
    # serving metrics (bigdl_tpu/serving + bench_serving.py): latency
    # percentiles regress UP, sustained rate regresses DOWN; steady-
    # state recompiles and shed load are zero-slack counts — ONE
    # in-request-path compile is a p99 spike worth failing CI over
    ("serve_p50_ms", "lower", "pct"),
    ("serve_p99_ms", "lower", "pct"),
    ("serve_qps", "higher", "pct"),
    (".p50_ms", "lower", "pct"),
    (".p99_ms", "lower", "pct"),
    (".qps", "higher", "pct"),
    (".rejected", "lower", "count"),
    (".steady_compiles", "lower", "count"),
    (".retrace_diagnostics", "lower", "count"),
    # generation serving (bench_serving.py --generate): sustained token
    # rate regresses DOWN; time-to-first-token and the inter-token tail
    # regress UP — the decode-path p99 gate for the next TPU round
    (".tokens_s", "higher", "pct"),
    (".ttft_p50_ms", "lower", "pct"),
    (".ttft_p99_ms", "lower", "pct"),
    (".itl_p99_ms", "lower", "pct"),
    # request-level tracing (telemetry/request_trace.py): end-to-end
    # per-request latency from `request` events (ingress to done —
    # includes queue, padding, respond; the batch-level serve_p99_ms
    # above sees only queue+infer), and SLO violations as a zero-slack
    # count — a candidate that starts blowing a declared budget fails
    # even when the percentile drift stays under the pct threshold
    ("request_p50_ms", "lower", "pct"),
    ("request_p99_ms", "lower", "pct"),
    ("slo_violations", "lower", "count"),
    (".slo_violations", "lower", "count"),
    # goodput ledger (telemetry/ledger.py): the run-level roll-up —
    # fraction of wall time that trained the model, and the badput
    # seconds it lost — on the dedicated tighter threshold
    # ("pct_goodput"); per run log (last goodput event, else folded
    # fresh) and per bench row
    ("goodput_pct", "higher", "pct_goodput"),
    ("badput_s", "lower", "pct_goodput"),
    (".goodput_pct", "higher", "pct_goodput"),
    (".badput_s", "lower", "pct_goodput"),
]


def _rule_for(name: str) -> Optional[Tuple[str, str]]:
    for key, direction, kind in _RULES:
        if name == key or (key.startswith(".") and name.endswith(key)):
            return direction, kind
    return None


# -- loading -----------------------------------------------------------------
def run_log_metrics(path: str) -> Dict[str, Any]:
    """Comparable metrics out of one JSONL run log (via the report
    summarizer)."""
    from bigdl_tpu.telemetry import schema
    from bigdl_tpu.telemetry.report import summarize

    events, _ = schema.read_events(path)
    summary = summarize(events)
    st = summary["steps"]
    stages = summary["stages"]
    out: Dict[str, Any] = {"kind": "run_log", "path": path,
                           "steps": st["count"]}
    if st["count"]:
        out["step_p50_s"] = st["p50_s"]
        out["step_p95_s"] = st["p95_s"]
        out["step_mean_s"] = st["mean_s"]
        if "throughput_mean" in st:
            out["throughput"] = st["throughput_mean"]
    # data-wait share: driver stall waiting for input, over the total
    # iteration time.  The Optimizer records the SAME interval twice —
    # as the data_wait span and as the Metrics-forwarded "data time"
    # stage — so take one (the span when present), never their sum
    if "data_wait" in stages:
        wait = stages["data_wait"]["total_s"]
    else:
        wait = stages.get("data time", {}).get("total_s", 0.0)
    iter_total = stages.get("train/iteration", {}).get("total_s", 0.0) \
        or st.get("total_s", 0.0)
    if iter_total:
        out["data_wait_share"] = wait / iter_total
    if summary.get("mfu") is not None:
        out["mfu"] = summary["mfu"]
    out["compiles"] = len(summary["compiles"])
    out["compile_s"] = sum(float(c.get("dur", 0.0))
                           for c in summary["compiles"])
    out["retraces"] = len(summary["retraces"])
    # comms snapshot (telemetry/comms.py, kind "comms"): the LAST event
    # describes the step program that ran — bytes are exact at trace
    # time; seconds prefer a measured profiler capture over the
    # peak-bandwidth expectation
    comms_events = [e for e in events if e.get("kind") == "comms"]
    if comms_events:
        last = comms_events[-1]
        if last.get("bytes") is not None:
            out["comms_bytes"] = float(last["bytes"])
        measured = [e for e in comms_events
                    if e.get("measured_s") is not None]
        if measured:
            out["comms_s"] = float(measured[-1]["measured_s"])
        elif last.get("expected_s") is not None:
            out["comms_s"] = float(last["expected_s"])
    # memory snapshot (telemetry/memory.py, kind "memory"): the LAST
    # event describes the step program that ran — peak is exact at
    # compile time, the number the HBM budget gates
    memory_events = [e for e in events if e.get("kind") == "memory"]
    if memory_events and memory_events[-1].get("peak_bytes") is not None:
        out["peak_hbm_bytes"] = float(memory_events[-1]["peak_bytes"])
    # goodput roll-up (telemetry/ledger.py): the run's goodput event
    # when end_run wrote one, else summarize() folded the raw events.
    # Sub-second walls are all noise (a smoke run's goodput is whatever
    # the interpreter was doing that millisecond) — don't offer them to
    # the gate
    gp = summary.get("goodput")
    if gp and gp.get("wall_s", 0.0) >= MIN_GOODPUT_WALL_S:
        out["goodput_pct"] = float(gp["goodput_pct"])
        out["badput_s"] = float(gp.get("badput_s", 0.0))
    health = summary.get("health", {})
    out["health_events"] = sum(health.get("events", {}).values())
    out["nonfinite_steps"] = health.get("nonfinite_steps", 0)
    # serving runs: fold per-batch `serve` events into the same
    # latency/rate metrics bench_serving.py emits, so a serve run log
    # diffs against another run log OR a bench_serving JSON
    serves = [e for e in events if e.get("kind") == "serve"]
    if serves:
        lats = sorted(float(e.get("queue_ms", 0.0))
                      + float(e.get("infer_ms", 0.0)) for e in serves)
        out["serve_p50_ms"] = lats[int(0.50 * (len(lats) - 1))]
        out["serve_p99_ms"] = lats[int(round(0.99 * (len(lats) - 1)))]
        rows = sum(int(e.get("size", 0)) for e in serves)
        span = max(e["ts"] for e in serves) - min(e["ts"] for e in serves)
        if span > 0:
            out["serve_qps"] = rows / span
    # request traces (telemetry/request_trace.py, kind "request"): the
    # TRUE end-to-end per-request percentiles (the serve fold above is
    # per batch and sees only queue+infer), plus the SLO violation count
    reqs = [e for e in events if e.get("kind") == "request"]
    if reqs:
        from bigdl_tpu.telemetry.report import _percentile

        # latency percentiles: completed requests PLUS dispatch
        # timeouts — a 504's wall is real waiting the client did and
        # the live histograms include it; instant 429/503 rejections
        # stay out (their ~0ms walls would dilute the percentiles)
        timed = [e for e in reqs if e.get("status") != "rejected"
                 or e.get("reason") == "dispatch_timeout"]
        if timed:
            lats = [float(e.get("ms", 0.0) or 0.0) for e in timed]
            out["request_p50_ms"] = _percentile(lats, 50.0)
            out["request_p99_ms"] = _percentile(lats, 99.0)
        # violations count over EVERY event: a rejected-504 that blew
        # the budget is precisely the violation the zero-slack gate
        # must see (the RequestFold counts it the same way)
        out["slo_violations"] = sum(1 for e in reqs
                                    if e.get("slo_violated"))
    return out


def bench_metrics(doc: Dict[str, Any], path: str = "?") -> Dict[str, Any]:
    """Comparable metrics out of one bench artifact (the JSON object
    with the per-config ``configs`` table)."""
    out: Dict[str, Any] = {"kind": "bench", "path": path}
    for name, row in (doc.get("configs") or {}).items():
        if not isinstance(row, dict) or "error" in row:
            continue
        if row.get("images_per_sec") is not None:
            out[f"{name}.images_per_sec"] = float(row["images_per_sec"])
        if row.get("mfu") is not None:
            out[f"{name}.mfu"] = float(row["mfu"])
        # per-leg compile seconds: the explicit field on new rows, the
        # stages_s breakdown on banked pre-budget artifacts
        compile_s = row.get("compile_s")
        if compile_s is None:
            compile_s = (row.get("stages_s") or {}).get("compile")
        if compile_s is not None:
            out[f"{name}.compile_s"] = float(compile_s)
        # serving rows (bench_serving.py): latency/rate + the zero-
        # slack steady-state counters; generation rows (--generate)
        # add sustained tokens/s, TTFT percentiles, and the
        # inter-token tail
        for key in ("p50_ms", "p99_ms", "qps", "rejected",
                    "steady_compiles", "retrace_diagnostics",
                    "tokens_s", "ttft_p50_ms", "ttft_p99_ms",
                    "itl_p99_ms", "slo_violations"):
            if row.get(key) is not None:
                out[f"{name}.{key}"] = float(row[key])
        # comms snapshot on bench rows — lets ZeRO/pipeline PRs gate
        # on bytes moved
        for key in ("comms_bytes", "comms_s", "final_loss"):
            if row.get(key) is not None:
                out[f"{name}.{key}"] = float(row[key])
        # memory snapshot on bench rows (bench_serving.py off the warm
        # bucket set) — the memory threshold's input
        if row.get("peak_hbm_bytes") is not None:
            out[f"{name}.peak_hbm_bytes"] = float(row["peak_hbm_bytes"])
        # goodput roll-up on bench rows (telemetry/ledger.py via the
        # live telemetry.goodput() accessor at artifact time)
        for key in ("goodput_pct", "badput_s"):
            if row.get(key) is not None:
                out[f"{name}.{key}"] = float(row[key])
    if doc.get("value") is not None and not doc.get("configs"):
        out["throughput"] = float(doc["value"])
    if doc.get("mfu") is not None:
        out["mfu"] = float(doc["mfu"])
    # whole-artifact goodput (stamped off the run that produced the
    # artifact)
    for key in ("goodput_pct", "badput_s"):
        if doc.get(key) is not None:
            out[key] = float(doc[key])
    return out


def load_metrics(path: str) -> Dict[str, Any]:
    """Sniff ``path`` (bench JSON object vs JSONL run log) and load the
    comparable metrics."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    with open(path, "r", encoding="utf-8") as fh:
        head = fh.read(1 << 20)
    try:
        doc = json.loads(head)
        if isinstance(doc, dict) and "kind" not in doc:
            return bench_metrics(doc, path)
    except ValueError:
        pass
    return run_log_metrics(path)


# -- comparing ---------------------------------------------------------------
def diff_metrics(a: Dict[str, Any], b: Dict[str, Any],
                 threshold_pct: float = DEFAULT_THRESHOLD_PCT,
                 count_slack: int = 0,
                 compile_threshold_pct: Optional[float] = None,
                 memory_threshold_pct: Optional[float] = None,
                 goodput_threshold_pct: Optional[float] = None
                 ) -> List[Dict[str, Any]]:
    """Compare metric dicts (A = baseline, B = candidate).  Returns one
    row per comparable metric: ``{name, a, b, delta_pct, better,
    regressed}``, regressions first.  ``compile_threshold_pct`` is the
    compile budget applied to ``compile_s`` metrics (None = the default
    :data:`DEFAULT_COMPILE_THRESHOLD_PCT`); ``memory_threshold_pct``
    the memory budget applied to ``peak_hbm_bytes`` metrics (None =
    :data:`DEFAULT_MEMORY_THRESHOLD_PCT`); ``goodput_threshold_pct``
    the goodput gate applied to ``goodput_pct``/``badput_s`` metrics
    (None = :data:`DEFAULT_GOODPUT_THRESHOLD_PCT`)."""
    if compile_threshold_pct is None:
        compile_threshold_pct = DEFAULT_COMPILE_THRESHOLD_PCT
    if memory_threshold_pct is None:
        memory_threshold_pct = DEFAULT_MEMORY_THRESHOLD_PCT
    if goodput_threshold_pct is None:
        goodput_threshold_pct = DEFAULT_GOODPUT_THRESHOLD_PCT
    rows: List[Dict[str, Any]] = []
    for name in sorted(set(a) & set(b)):
        rule = _rule_for(name)
        if rule is None:
            continue
        direction, kind = rule
        va, vb = a[name], b[name]
        if not isinstance(va, (int, float)) \
                or not isinstance(vb, (int, float)):
            continue
        delta = vb - va
        delta_pct = (delta / abs(va) * 100.0) if va else None
        worse = delta > 0 if direction == "lower" else delta < 0
        if kind == "count":
            regressed = worse and abs(delta) > count_slack
        elif delta_pct is None:
            # zero baseline: any move in the bad direction IS the
            # regression (0 -> anything is an infinite pct change)
            regressed = worse and abs(delta) > 1e-9
        elif kind == "pct_compile":
            regressed = worse and abs(delta_pct) > compile_threshold_pct
        elif kind == "pct_memory":
            regressed = worse and abs(delta_pct) > memory_threshold_pct
        elif kind == "pct_goodput":
            if name.endswith("goodput_pct"):
                # already a percentage: compare in percentage POINTS —
                # relative change would make a 10%->9.4% drop regress
                # while 90%->85% (nine times the lost wall) passed
                regressed = worse and abs(va - vb) > goodput_threshold_pct
            else:
                regressed = worse and abs(delta_pct) > goodput_threshold_pct
        else:
            regressed = worse and abs(delta_pct) > threshold_pct
        rows.append({"name": name, "a": va, "b": vb,
                     "delta_pct": delta_pct, "better": direction,
                     "regressed": bool(regressed)})
    rows.sort(key=lambda r: (not r["regressed"], r["name"]))
    return rows


def format_diff(rows: List[Dict[str, Any]], a: Dict[str, Any],
                b: Dict[str, Any]) -> str:
    lines = [f"== telemetry diff ==",
             f"A (baseline):  {a.get('path', '?')} [{a.get('kind')}]",
             f"B (candidate): {b.get('path', '?')} [{b.get('kind')}]"]
    if not rows:
        lines.append("no comparable metrics on both sides")
        return "\n".join(lines)
    width = max(len(r["name"]) for r in rows)
    for r in rows:
        pct = (f"{r['delta_pct']:+8.2f}%" if r["delta_pct"] is not None
               else f"{r['b'] - r['a']:+9.3g}")  # 0-baseline: abs delta
        flag = "REGRESSED" if r["regressed"] else "ok"
        lines.append(f"{r['name']:<{width}}  {r['a']:>12.6g} -> "
                     f"{r['b']:>12.6g}  {pct}  "
                     f"({r['better']} is better)  {flag}")
    n_reg = sum(r["regressed"] for r in rows)
    lines.append(f"{n_reg} regression(s) out of {len(rows)} compared "
                 f"metric(s)")
    return "\n".join(lines)


def main(argv=None) -> int:
    """``python -m bigdl_tpu.telemetry diff`` entry (also callable from
    ``bench_serving.py``)."""
    import argparse
    import sys

    p = argparse.ArgumentParser(
        prog="bigdl_tpu.telemetry diff",
        description="compare two runs (JSONL run logs or bench JSON) "
                    "and exit nonzero on a regression")
    p.add_argument("run_a", help="baseline artifact")
    p.add_argument("run_b", help="candidate artifact")
    p.add_argument("--threshold-pct", type=float,
                   default=DEFAULT_THRESHOLD_PCT,
                   help="relative regression threshold for timing/"
                        "throughput/MFU metrics (default %(default)s)")
    p.add_argument("--count-slack", type=int, default=0,
                   help="allowed increase for compile/retrace/health "
                        "counts (default 0)")
    p.add_argument("--compile-threshold-pct", type=float, default=None,
                   help="compile budget: relative regression threshold "
                        "for compile_s metrics (default "
                        f"{DEFAULT_COMPILE_THRESHOLD_PCT})")
    p.add_argument("--memory-threshold-pct", type=float, default=None,
                   help="memory budget: relative regression threshold "
                        "for peak_hbm_bytes metrics (default "
                        f"{DEFAULT_MEMORY_THRESHOLD_PCT})")
    p.add_argument("--goodput-threshold-pct", type=float, default=None,
                   help="goodput gate: relative regression threshold "
                        "for goodput_pct/badput_s metrics (default "
                        f"{DEFAULT_GOODPUT_THRESHOLD_PCT})")
    p.add_argument("--json", action="store_true",
                   help="emit rows as JSON instead of the table")
    args = p.parse_args(argv)

    try:
        a = load_metrics(args.run_a)
        b = load_metrics(args.run_b)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    rows = diff_metrics(a, b, threshold_pct=args.threshold_pct,
                        count_slack=args.count_slack,
                        compile_threshold_pct=args.compile_threshold_pct,
                        memory_threshold_pct=args.memory_threshold_pct,
                        goodput_threshold_pct=args.goodput_threshold_pct)
    n_regressed = sum(r["regressed"] for r in rows)
    exit_code = 2 if not rows else (1 if n_regressed else 0)
    if args.json:
        # CI-consumable: the verdict and exit code travel IN the
        # payload, so a pipeline can archive one artifact and decide
        # later without re-running (exit-code contract unchanged)
        verdict = {0: "ok", 1: "regressed", 2: "not_comparable"}[exit_code]
        print(json.dumps({"a": a, "b": b, "rows": rows,
                          "verdict": verdict, "regressions": n_regressed,
                          "compared": len(rows),
                          "threshold_pct": args.threshold_pct,
                          "compile_threshold_pct":
                              (args.compile_threshold_pct
                               if args.compile_threshold_pct is not None
                               else DEFAULT_COMPILE_THRESHOLD_PCT),
                          "memory_threshold_pct":
                              (args.memory_threshold_pct
                               if args.memory_threshold_pct is not None
                               else DEFAULT_MEMORY_THRESHOLD_PCT),
                          "goodput_threshold_pct":
                              (args.goodput_threshold_pct
                               if args.goodput_threshold_pct is not None
                               else DEFAULT_GOODPUT_THRESHOLD_PCT),
                          "count_slack": args.count_slack,
                          "exit_code": exit_code}, indent=2))
    else:
        print(format_diff(rows, a, b))
    if not rows:
        print("error: nothing comparable", file=sys.stderr)
    return exit_code
