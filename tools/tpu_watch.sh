#!/bin/bash
# Live /status printer for a running bigdl_tpu process.
#
# Polls the JSON /status endpoint that a run serves when
# BIGDL_METRICS_PORT is set (telemetry/metrics_http.py) and prints one
# line per poll: step/loss/throughput, fleet, memory, serving, goodput.
# Pure HTTP: it never imports jax and never touches the device, so it is
# safe beside the one process that holds the chip.
#
# Usage: BIGDL_METRICS_PORT=9100 bash tools/tpu_watch.sh [interval_s]
set -u

status_line() {
  [ -z "${BIGDL_METRICS_PORT:-}" ] && return 1
  python - "$BIGDL_METRICS_PORT" 2>/dev/null <<'PY'
import json, sys, urllib.request
port = sys.argv[1]
st = json.load(urllib.request.urlopen(
    f"http://127.0.0.1:{port}/status", timeout=2))
step = st.get("step") or {}
line = (f"status: step={step.get('step', '?')} loss={step.get('loss', '?')} "
        f"throughput={step.get('throughput', '?')} "
        f"nonfinite={st.get('nonfinite_steps', 0)} "
        f"compiles={st.get('compiles', 0)}")
# managed compile cache (docs/compile.md): cumulative compile seconds
# + persistent-cache hit/miss — a babysitter sees at a glance whether a
# restart's compile bill is being paid in cash or from the cache
if st.get("compile_s"):
    line += f" compile_s={st['compile_s']}"
cache = st.get("compile_cache") or {}
proc_cache = st.get("compile_cache_process") or {}
# fall back to the process-lifetime pair only as a PAIR — mixing one
# scope's hits with the other's misses prints a ratio belonging to
# neither run
if not (cache.get("hits") or cache.get("misses")):
    cache = proc_cache
hits, misses = cache.get("hits", 0), cache.get("misses", 0)
if hits or misses:
    line += f" cache={hits}h/{misses}m"
# goodput ledger (telemetry/ledger.py): live share of wall time spent
# training plus the dominant badput category — a babysitter sees "the
# job holds the slice but only 60% of it trains" without waiting for
# the post-run `telemetry goodput` fold
gp = st.get("goodput") or {}
if gp.get("wall_s"):
    line += f" goodput={gp.get('goodput_pct', 0):.0f}%"
    bad = gp.get("badput") or {}
    worst = max(bad.items(), key=lambda kv: kv[1], default=None)
    if worst and worst[1] > 0:
        line += f" badput={worst[0]}:{worst[1]:.0f}s"
# on-demand profiler + flight recorder (telemetry/profiler.py,
# telemetry/flight.py): show a capture in flight / the last artifacts so
# a sweep babysitter knows a POST /profile actually landed
prof = st.get("profiler") or {}
if prof.get("state", "idle") != "idle":
    line += (f" profiler={prof['state']}:{prof.get('steps_left', '?')}"
             f"->{prof.get('trace_dir', '?')}")
elif prof.get("last_trace_dir"):
    line += f" last_trace={prof['last_trace_dir']}"
flight = st.get("flight") or {}
if flight.get("last_dump_path"):
    line += f" flight_dump={flight['last_dump_path']}"
# fault tolerance (docs/fault_tolerance.md): checkpoint freshness and
# the last injected fault — a babysitter sees at a glance whether the
# run is checkpointing on cadence and whether a fault plan has fired
ckpt = st.get("checkpoint") or {}
if ckpt.get("saved_at"):
    line += f" ckpt=step{ckpt.get('step', '?')}@{ckpt.get('age_s', '?')}s"
fault = st.get("last_fault") or {}
if fault.get("fault"):
    line += f" last_fault={fault['fault']}@{fault.get('step', '?')}"
if st.get("quarantined_checkpoints"):
    line += f" quarantined={st['quarantined_checkpoints']}"
if st.get("preempted"):
    line += " PREEMPTED"
# inference serving (bigdl_tpu/serving/): live qps + latency
# percentiles + queue pressure — a babysitter sees a p99 spike or
# shed load (429s) without curling the serve port itself; STEADY-
# STATE compiles above the warm bucket count mean the server is
# recompiling in production (docs/serving.md runbook entry)
srv = st.get("serving") or {}
if srv:
    line += (f" serve[{srv.get('model', '?')}]:"
             f"qps={srv.get('qps', 0)}"
             f" p50={srv.get('p50_ms', '?')}ms"
             f" p99={srv.get('p99_ms', '?')}ms"
             f" q={srv.get('queue_depth', 0)}/{srv.get('queue_limit', '?')}"
             f" fill={srv.get('batch_fill', '?')}"
             f" compiles={srv.get('compiles', '?')}")
    if srv.get("rejected"):
        line += f" rejected={srv['rejected']}"
    # the LLM decode path (serving/generate/): live token rate, TTFT,
    # and decode-slot pressure — a babysitter sees a TTFT spike or a
    # full decode batch (admissions queueing behind max_active) without
    # curling /v1/generate (docs/serving.md runbook entry)
    gen = srv.get("generate") or {}
    if gen:
        line += (f" gen={gen.get('tokens_s', 0)}tok/s"
                 f" ttft={gen.get('ttft_p50_ms', '?')}ms"
                 f" active={gen.get('active_seqs', 0)}"
                 f"/{gen.get('max_active', '?')}"
                 f" cache={gen.get('cache_occupancy', 0)}")
    # SLO burn + tail evidence (telemetry/request_trace.py): burn is
    # observed windowed p99 / declared budget (1.0x = budget exactly
    # spent); the slowest retained trace id is the exemplar a
    # babysitter feeds to GET /v1/trace/<id> for the waterfall + blame
    slo = srv.get("slo") or {}
    burn = slo.get("burn") or {}
    cells = []
    for which in ("p99", "ttft"):
        b = (burn.get(which) or {}).get("burn")
        if b is not None:
            cells.append(f"{which} {b}x")
    if cells:
        line += " slo=" + "/".join(cells)
        if slo.get("violations"):
            line += f"!viol{slo['violations']}"
    slowest = []
    for ep, rows in ((srv.get("traces") or {}).get("slowest")
                     or {}).items():
        if rows:
            slowest.append((rows[0].get("ms", 0), rows[0], ep))
    if slowest:
        ms, row, ep = max(slowest, key=lambda t: t[0])
        line += f" slowest={row.get('trace_id', '?')}@{ms:.0f}ms"
        if (row.get("blame") or {}).get("cause"):
            line += f":{row['blame']['cause']}"
    if srv.get("draining"):
        line += " DRAINING"
# cluster fault tolerance (parallel/cluster.py): the per-peer heartbeat
# table — a babysitter sees which host stalled BEFORE the watchdog
# aborts the collective, and DEGRADED the instant a peer is presumed
# lost (the same signal /healthz turns 503 on)
cl = st.get("cluster") or {}
if cl:
    if cl.get("state") == "degraded":
        line += " cluster=DEGRADED"
    peers = cl.get("peers") or {}
    cells = []
    for name in sorted(peers):
        p = peers[name]
        cell = f"{name}:s{p.get('step', '?')}@{p.get('age_s', '?')}s"
        if p.get("lost"):
            cell += "!LOST"
        elif p.get("status") not in ("running", None):
            cell += f":{p['status']}"
        cells.append(cell)
    if cells:
        line += " peers=" + ",".join(cells)
# comms attribution (telemetry/comms.py): collective bytes per compiled
# step — a babysitter sees whether a sharding change blew up the
# all-reduce bill without waiting for the post-run diff
comms = st.get("comms") or {}
if comms.get("bytes"):
    line += (f" comms={comms['bytes'] / 1e6:.1f}MB/step"
             f"@{comms.get('count', '?')}coll")
# sparse embedding sync (train/sparse instant, docs/sparse.md): the
# bytes-per-step the row-sparse sync saves vs a dense table all-reduce
# — a babysitter sees whether the fast path is actually engaged
sp = st.get("sparse") or {}
if sp.get("saved_bytes"):
    line += (f" sparse={sp['saved_bytes'] / 1e6:.1f}MB-saved/step"
             f"@{sp.get('tables', '?')}tbl")
# memory attribution (telemetry/memory.py): live allocator vs limit +
# the compiled step's predicted per-device peak — the babysitter sees a
# run creeping toward RESOURCE_EXHAUSTED before it dies
mem = st.get("memory") or {}
if mem.get("peak_bytes"):
    g = 1 << 30
    live = mem.get("live_bytes")
    limit = mem.get("limit_bytes") or mem.get("hbm_limit_bytes")
    if live is not None and limit:
        line += (f" hbm={live / g:.1f}G/{limit / g:.1f}G"
                 f" peak={mem['peak_bytes'] / g:.1f}G")
        # 0.95 == telemetry.memory.PRESSURE_FRACTION (stdlib-only
        # snippet; limit_bytes here is already the allocator's own)
        if live >= 0.95 * limit:
            line += "!PRESSURE"
    else:
        line += f" hbm_peak={mem['peak_bytes'] / g:.2f}G"
# straggler-tolerant local SGD (parallel/local_sync.py): averaging
# period, worst peer lag vs the staleness bound, cumulative barrier
# wait, and any shed hosts — the babysitter sees "p1 is 2/3 rounds
# behind" before the shed verdict lands
ls_ = st.get("local_sync") or {}
if ls_.get("h"):
    line += f" sync=local H={ls_['h']} stale={ls_.get('lag', 0)}/{ls_.get('stale', '?')}"
    if ls_.get("waited_s"):
        line += f" held={ls_['waited_s']:.1f}s"
    if ls_.get("shed"):
        line += " shed=" + ",".join(f"p{p}" for p in ls_["shed"]) + "!"
# fleet watcher (telemetry/fleet.py, coordinator only): host count,
# completed-step lag, and the skew-blame verdict — "one host is slow,
# whose fault?" answered on one line
fl = st.get("fleet") or {}
if fl.get("hosts"):
    line += f" fleet={len(fl['hosts'])}h/lag{fl.get('lag_steps', 0)}"
    # elastic recovery (docs/fault_tolerance.md): current/declared
    # width when the cluster runs DEGRADED after a capacity-aware
    # reshard — the babysitter sees "2/4" instead of guessing why half
    # the hosts went quiet
    w = fl.get("width") or {}
    if w.get("current") and w.get("declared") \
            and w["current"] != w["declared"]:
        line += f" width={w['current']}/{w['declared']}!DEGRADED"
    bl = fl.get("blame") or {}
    if bl.get("cause"):
        line += (f" blame=p{bl.get('laggard', '?')}:{bl['cause']}"
                 f"+{bl.get('excess_s', 0) * 1e3:.0f}ms")
print(line)
PY
}

if [ -z "${BIGDL_METRICS_PORT:-}" ]; then
  echo "tpu_watch: set BIGDL_METRICS_PORT to the run's metrics port" >&2
  exit 2
fi
# until the endpoint goes away (the run ended)
while line=$(status_line); do
  echo "$(date -u +%H:%M:%S) $line"
  sleep "${1:-60}"
done
