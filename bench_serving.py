#!/usr/bin/env python
"""Open-loop serving load harness — the diff-gateable check on the
serving layer (docs/serving.md; ROADMAP item 2).

Starts a :class:`bigdl_tpu.serving.ModelServer` in-process on an
ephemeral port, AOT-warms every bucket, then drives it with an
**open-loop** arrival schedule: request send times are fixed up front
at the offered rate (``--qps``), independent of completions — the load
a server actually faces, where a slow dispatch makes the queue grow
instead of politely slowing the clients down (closed-loop harnesses
hide exactly the p99 failures this one exists to catch).

Request sizes cycle through ``--mix`` (rows per request), so the
steady-state traffic exercises MIXED bucket selection; the retrace
detector is armed for the whole timed window and any in-request-path
compile after warmup is counted separately (``steady_compiles``).

Emits one JSON line with a per-config row::

    {"metric": "serving_lenet_qps", "value": 118.3, "unit": "qps",
     "configs": {"serve_lenet": {"qps": ..., "p50_ms": ..., "p99_ms":
     ..., "rejected": 0, "steady_compiles": 0,
     "retrace_diagnostics": 0, ...}}}

which ``python -m bigdl_tpu.telemetry diff A B`` and
``--diff-against BASELINE.json`` (exit 4 on regression)
compare: p50/p99 regress up, qps regresses down, and
``steady_compiles``/``retrace_diagnostics``/``rejected`` are
zero-slack counters — ONE production recompile fails the gate.

``--generate`` switches the harness to the LLM decode path
(docs/serving.md "Autoregressive generation"): the same open-loop
schedule drives ``POST /v1/generate`` with a mixed prompt-length cycle
(``--gen-mix``), reads each token off the chunked stream as it lands,
and banks the generation row — ``tokens_s`` (sustained emitted
tokens/s), ``ttft_p50_ms``/``ttft_p99_ms`` (time to first token — the
prefill + queue cost a user feels), and ``itl_p99_ms`` (p99 inter-token
latency — the decode-step tail).  All four are diff-gated:
``tokens_s`` regresses down, the latencies regress up, and the same
zero-slack ``steady_compiles``/``retrace_diagnostics`` counters hold —
a decode executable compiling mid-stream is a frozen token stream.

``--slo-p99-ms`` / ``--slo-ttft-ms`` declare latency budgets
(telemetry/request_trace.py SLOTracker): the server tracks its windowed
p99 (and TTFT p99) against them live, the worst 32 violators by
budget overshoot keep their trace ids (``VIOLATING_KEEP`` — worst-first,
not newest, so a sustained burn cannot evict its own catastrophic
evidence), and the harness **exits 4 when a budget is burned**
(observed p99 > budget) — the same exit code as ``--diff-against``, so
CI treats a blown SLO exactly like a regression.  The bench JSON row
carries the full SLO ledger (burn rates + the violating requests' trace
ids), so the failing artifact names its own evidence: feed any id to
``GET /v1/trace/<id>`` on a live server or ``python -m
bigdl_tpu.telemetry trace run.jsonl --id <id>`` offline.

Usage::

    python bench_serving.py --model lenet --qps 100 --duration 10
    python bench_serving.py --model lenet --diff-against BENCH_serving.json
    python bench_serving.py --model dlrm --qps 100 --duration 12 \
        --diff-against BENCH_SERVING_cpu_r15.json   # the recsys tenant
    python bench_serving.py --model lenet --qps 100 --slo-p99-ms 50
    python bench_serving.py --model transformer --generate --qps 5 \
        --duration 10 --gen-mix 8,24,64 --max-new-tokens 16
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import urllib.request

import numpy as np

__all__ = ["run_load", "main"]


def _pct(sorted_vals, p):
    """Nearest-rank percentile over a pre-sorted list; ``None`` when
    empty (client-side stats distinguish "no samples" from 0 ms)."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              int(round(p / 100.0 * (len(sorted_vals) - 1))))
    return round(sorted_vals[idx], 3)


def _synth_rows(spec, rng, rows: int, seq_len=None) -> np.ndarray:
    """Synthetic request payload: ``rows`` samples at the model's
    canonical feature shape (optionally a shorter seq for token
    models — the mixed-size part of the protocol)."""
    shape = (rows,) + tuple(spec.shape[1:])
    if seq_len is not None and len(shape) >= 2:
        shape = (rows, seq_len) + tuple(shape[2:])
    dt = np.dtype(spec.dtype)
    if np.issubdtype(dt, np.integer):
        return rng.integers(1, 200, shape).astype(dt)
    return rng.normal(size=shape).astype(dt)


def run_load(server, spec, qps: float, duration_s: float, mix,
             seq_mix=None, senders: int = 8, timeout_s: float = 30.0):
    """Drive ``server`` open-loop; returns client-side stats.

    ``mix`` cycles request row counts; ``seq_mix`` (token models)
    cycles sequence lengths.  Arrival times are scheduled before the
    first send and never adjusted — a stalled server meets the full
    backlog, exactly like production."""
    n = max(1, int(qps * duration_s))
    rng = np.random.default_rng(0)
    url = f"http://127.0.0.1:{server.port}/v1/predict"
    plan = []
    for i in range(n):
        rows = mix[i % len(mix)]
        seq = seq_mix[i % len(seq_mix)] if seq_mix else None
        body = json.dumps(
            {"inputs": _synth_rows(spec, rng, rows, seq).tolist()}
        ).encode("utf-8")
        plan.append((i / qps, rows, body))
    lat_ms, codes = [], []
    lock = threading.Lock()
    idx = [0]
    start = time.perf_counter()

    def sender():
        while True:
            with lock:
                if idx[0] >= len(plan):
                    return
                at, rows, body = plan[idx[0]]
                idx[0] += 1
            delay = start + at - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t0 = time.perf_counter()
            try:
                req = urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"},
                    method="POST")
                with urllib.request.urlopen(req, timeout=timeout_s) as r:
                    r.read()
                    code = r.status
            except urllib.error.HTTPError as e:
                code = e.code
            except Exception:  # noqa: BLE001 - connection-level failure
                code = -1
            with lock:
                codes.append(code)
                if code == 200:
                    lat_ms.append((time.perf_counter() - t0) * 1000.0)

    threads = [threading.Thread(target=sender, daemon=True)
               for _ in range(senders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 3 * timeout_s)
    wall = time.perf_counter() - start
    lat = sorted(lat_ms)
    return {"offered_qps": round(qps, 2),
            "qps": round(len(lat) / wall, 2) if wall > 0 else None,
            "requests": len(codes), "ok": len(lat),
            "rejected": sum(1 for c in codes if c == 429),
            "failed": sum(1 for c in codes if c not in (200, 429)),
            "p50_ms": _pct(lat, 50), "p99_ms": _pct(lat, 99),
            "wall_s": round(wall, 3)}


def run_generate_load(server, qps: float, duration_s: float, gen_mix,
                      max_new_tokens: int, vocab: int, senders: int = 8,
                      temperature: float = 0.0,
                      timeout_s: float = 60.0):
    """Drive ``POST /v1/generate`` open-loop; returns client-side
    generation stats.  ``gen_mix`` cycles prompt lengths (mixed-length
    prefill is the scheduling case worth measuring); every request
    streams and the client clocks each token as its chunk lands —
    TTFT and inter-token latency are measured where the user sits,
    queue wait included."""
    n = max(1, int(qps * duration_s))
    rng = np.random.default_rng(0)
    url = f"http://127.0.0.1:{server.port}/v1/generate"
    plan = []
    for i in range(n):
        plen = gen_mix[i % len(gen_mix)]
        body = json.dumps(
            {"prompt": rng.integers(1, vocab, plen).tolist(),
             "max_new_tokens": max_new_tokens,
             "temperature": temperature, "seed": i}).encode("utf-8")
        plan.append((i / qps, body))
    ttft_ms, itl_ms, codes, tokens = [], [], [], [0]
    lock = threading.Lock()
    idx = [0]
    start = time.perf_counter()

    def sender():
        while True:
            with lock:
                if idx[0] >= len(plan):
                    return
                at, body = plan[idx[0]]
                idx[0] += 1
            delay = start + at - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t0 = time.perf_counter()
            got, stamps = 0, []
            try:
                req = urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"},
                    method="POST")
                with urllib.request.urlopen(req, timeout=timeout_s) as r:
                    code = r.status
                    for line in r:
                        if not line.strip():
                            continue
                        ev = json.loads(line)
                        if "token" in ev:
                            stamps.append(time.perf_counter())
                            got += 1
                        elif "error" in ev:
                            code = -2
            except urllib.error.HTTPError as e:
                code = e.code
            except Exception:  # noqa: BLE001 - connection-level failure
                code = -1
            with lock:
                codes.append(code)
                tokens[0] += got
                if code == 200 and stamps:
                    ttft_ms.append((stamps[0] - t0) * 1000.0)
                    itl_ms.extend((b - a) * 1000.0 for a, b in
                                  zip(stamps, stamps[1:]))

    threads = [threading.Thread(target=sender, daemon=True)
               for _ in range(senders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 3 * timeout_s)
    wall = time.perf_counter() - start
    ttft = sorted(ttft_ms)
    itl = sorted(itl_ms)
    return {"offered_qps": round(qps, 2),
            "requests": len(codes),
            "ok": sum(1 for c in codes if c == 200),
            "rejected": sum(1 for c in codes if c == 429),
            "failed": sum(1 for c in codes if c not in (200, 429)),
            "gen_tokens": tokens[0],
            "tokens_s": round(tokens[0] / wall, 2) if wall > 0 else None,
            "ttft_p50_ms": _pct(ttft, 50), "ttft_p99_ms": _pct(ttft, 99),
            "itl_p50_ms": _pct(itl, 50), "itl_p99_ms": _pct(itl, 99),
            "max_new_tokens": max_new_tokens,
            "wall_s": round(wall, 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", default="lenet")
    ap.add_argument("--num-classes", type=int, default=0)
    ap.add_argument("--qps", type=float, default=50.0,
                    help="offered (open-loop) request rate")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="timed window seconds")
    ap.add_argument("--mix", default="1,1,2,4", metavar="R,R,...",
                    help="request row-count cycle (mixed sizes "
                         "exercise bucket selection)")
    ap.add_argument("--seq-mix", default=None, metavar="T,T,...",
                    help="token models: request sequence-length cycle")
    ap.add_argument("-b", "--max-batch", type=int, default=16)
    ap.add_argument("--buckets", default=None, metavar="N,N,...")
    ap.add_argument("--seq-buckets", default=None, metavar="T,T,...")
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--queue-limit", type=int, default=256)
    ap.add_argument("--senders", type=int, default=8)
    ap.add_argument("--int8", action="store_true",
                    help="serve quantized with calibrated static "
                         "activation scales")
    ap.add_argument("--generate", action="store_true",
                    help="bench the LLM decode path: POST /v1/generate "
                         "streamed token mix (tokens/s, TTFT, "
                         "inter-token p99)")
    ap.add_argument("--gen-mix", default="8,24,64", metavar="L,L,...",
                    help="--generate: prompt-length cycle (mixed "
                         "prefill shapes)")
    ap.add_argument("--max-new-tokens", type=int, default=16,
                    help="--generate: tokens emitted per request")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="--generate: 0 = greedy (default), >0 samples")
    ap.add_argument("--decode-buckets", default=None, metavar="B,B,...",
                    help="--generate: decode batch buckets (default "
                         "1,2,4,8)")
    ap.add_argument("--cache-buckets", default=None, metavar="C,C,...",
                    help="--generate: KV cache-length buckets")
    ap.add_argument("--vocab", type=int, default=0,
                    help="--generate: vocab size for synthetic prompts "
                         "(default: the model's)")
    ap.add_argument("--diff-against", default=None,
                    metavar="BASELINE.json",
                    help="compare against a prior bench_serving JSON "
                         "(telemetry diff); exit 4 on regression")
    ap.add_argument("--diff-threshold-pct", type=float, default=None)
    ap.add_argument("--slo-p99-ms", type=float, default=None,
                    metavar="MS",
                    help="declared request-latency p99 budget: exit 4 "
                         "when the observed p99 exceeds it; violating "
                         "requests' trace ids land in the bench JSON")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    metavar="MS",
                    help="--generate: declared time-to-first-token p99 "
                         "budget (same exit-4 gate)")
    args = ap.parse_args(argv)

    from bigdl_tpu import telemetry
    from bigdl_tpu.analysis.retrace import trace_retraces
    from bigdl_tpu.models import registry
    from bigdl_tpu.serving import serve_model

    if args.generate:
        # the shared build rule (unrolled transformer etc.) lives
        # beside the decode subsystem — same path as cli serve
        from bigdl_tpu.serving.generate import generation_model

        model = generation_model(args.model, args.num_classes)
    else:
        model = registry.build_model(args.model, args.num_classes)
    spec = registry.input_spec(args.model, 1)
    if args.int8:
        from bigdl_tpu.nn.quantized import calibrate, quantize

        model = quantize(model)
        calibrate(model, [_synth_rows(spec, np.random.default_rng(1),
                                      max(2, args.max_batch // 2))])

    def buckets(text):
        return [int(b) for b in text.split(",")] if text else None

    seq_buckets = buckets(args.seq_buckets)
    if args.generate and not seq_buckets:
        from bigdl_tpu.serving.generate import default_seq_buckets

        seq_buckets = default_seq_buckets(spec)
    with telemetry.maybe_run(meta={"cmd": "bench_serving",
                                   "model": args.model}) as owned_log:
        server = serve_model(
            model, spec, name=args.model, host="127.0.0.1", port=0,
            max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
            queue_limit=args.queue_limit,
            batch_buckets=buckets(args.buckets),
            seq_buckets=seq_buckets,
            generate=args.generate,
            decode_buckets=buckets(args.decode_buckets),
            cache_buckets=buckets(args.cache_buckets),
            slo_p99_ms=args.slo_p99_ms, slo_ttft_ms=args.slo_ttft_ms)
        print(f"# serving {args.model} on :{server.port}, "
              f"{server.executor.compile_count} buckets warm "
              f"({server.executor.warmup_s:.1f}s)",
              file=sys.stderr, flush=True)
        warm_compiles = server.executor.compile_count
        mix = [int(r) for r in args.mix.split(",")]
        seq_mix = [int(t) for t in args.seq_mix.split(",")] \
            if args.seq_mix else None
        try:
            with telemetry.span("serve/load", qps=args.qps,
                                duration=args.duration):
                with trace_retraces() as mon:
                    if args.generate:
                        stats = run_generate_load(
                            server, args.qps, args.duration,
                            [int(p) for p in args.gen_mix.split(",")],
                            args.max_new_tokens,
                            vocab=args.vocab or args.num_classes or 256,
                            senders=args.senders,
                            temperature=args.temperature)
                    else:
                        stats = run_load(server, spec, args.qps,
                                         args.duration, mix,
                                         seq_mix=seq_mix,
                                         senders=args.senders)
            steady = server.executor.compile_count - warm_compiles
            row = dict(stats)
            try:
                # resident-executable HBM (weights + generated code +
                # largest bucket scratch): the serving-side
                # peak_hbm_bytes the diff gate compares, and the number
                # the KV-cache budgeting work subtracts from the device
                mem = server.executor.memory_summary()
                row["peak_hbm_bytes"] = mem["resident_bytes"]
                row["executable_memory"] = {
                    k: mem[k] for k in ("state_bytes", "code_bytes",
                                        "peak_temp_bytes")}
            except Exception:  # noqa: BLE001 - accounting only
                pass
            row.update(
                steady_compiles=steady,
                retrace_diagnostics=len(mon.report.diagnostics),
                warm_buckets=len(server.executor.warm_buckets()),
                warmup_s=round(server.executor.warmup_s, 3),
                max_batch=args.max_batch,
                max_wait_ms=args.max_wait_ms, int8=bool(args.int8),
                server=server.status())
            if server.slo.active():
                # the SLO ledger travels IN the bench artifact: burn
                # rates plus the worst violators' trace ids — the
                # failing JSON names its own evidence
                row["slo"] = server.slo.status()
                row["slo_violations"] = server.slo.violations
        finally:
            server.stop(drain=True)
        # read the live goodput ledger while the run is still open —
        # end_run (the `with` exit) detaches it
        gp = telemetry.goodput()
    if owned_log:
        print(f"# telemetry run log: {owned_log}", file=sys.stderr)

    if args.generate:
        name = f"generate_{args.model}"
        line = {"metric": f"serving_{args.model}_gen_tokens_s",
                "value": row.get("tokens_s"), "unit": "tokens/s",
                "vs_baseline": None, "configs": {name: row}}
    else:
        name = f"serve_{args.model}"
        line = {"metric": f"serving_{args.model}_qps",
                "value": row.get("qps"), "unit": "qps",
                "vs_baseline": None, "configs": {name: row}}
    if gp and gp.get("wall_s"):
        line["goodput_pct"] = gp["goodput_pct"]
        line["badput_s"] = gp["badput_s"]
    print(json.dumps(line))
    sys.stdout.flush()

    slo_burned = []
    if args.slo_p99_ms is not None or args.slo_ttft_ms is not None:
        burn = (row.get("slo") or {}).get("burn") or {}
        slo_burned = [
            which for which, b in sorted(burn.items())
            if (b or {}).get("burn") is not None and b["burn"] > 1.0]
        if slo_burned:
            violating = (row.get("slo") or {}).get("violating") or []
            ids = [v.get("trace_id") for v in violating]
            print(f"SLO VIOLATED ({', '.join(slo_burned)}): "
                  + "  ".join(
                      f"{w} {burn[w]['observed_ms']}ms observed vs "
                      f"{burn[w]['budget_ms']}ms budget "
                      f"(burn {burn[w]['burn']}x)" for w in slo_burned)
                  + f"; violating trace ids: {ids}", file=sys.stderr)

    if args.diff_against:
        from bigdl_tpu.telemetry import diff as tdiff

        base = tdiff.load_metrics(args.diff_against)
        cur = tdiff.bench_metrics(line, path="<this run>")
        kwargs = {}
        if args.diff_threshold_pct is not None:
            kwargs["threshold_pct"] = args.diff_threshold_pct
        rows = tdiff.diff_metrics(base, cur, **kwargs)
        print(tdiff.format_diff(rows, base, cur), file=sys.stderr)
        if not rows:
            print("error: --diff-against found nothing comparable",
                  file=sys.stderr)
            return 2
        if any(r["regressed"] for r in rows):
            return 4  # the sweep ran; it's just slower
    if slo_burned:
        return 4  # the sweep ran; it blew its declared budget
    return 0


if __name__ == "__main__":
    sys.exit(main())
