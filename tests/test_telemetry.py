"""Unified runtime telemetry (docs/observability.md): span
nesting/ordering guarantees, schema + Chrome-trace invariants, Metrics
concurrency + event forwarding, straggler/prefetch/retrace visibility,
and the tier-1 end-to-end check — a registry-model CLI training run with
telemetry on must yield a schema-valid JSONL log from which the
inspection CLI reconstructs the stage table, step percentiles,
compile/retrace timeline, and an MFU estimate."""

import glob
import json
import os
import threading

import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu import telemetry
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.telemetry import schema
from bigdl_tpu.telemetry.chrome_trace import chrome_trace
from bigdl_tpu.telemetry.report import format_summary, summarize
from bigdl_tpu.utils.config import set_config


def teardown_function(_fn):
    telemetry.end_run()  # no run leaks across tests
    set_config(None)


def _events(sink, kind):
    return [e for e in sink.events if e["kind"] == kind]


# -- tracer core -------------------------------------------------------------
def test_span_nesting_and_pairing():
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        with telemetry.span("outer", tag="a"):
            with telemetry.span("inner1"):
                pass
            with telemetry.span("inner2"):
                pass
    assert schema.validate_events(sink.events) == []
    begins = _events(sink, "span_begin")
    ends = _events(sink, "span_end")
    assert [b["name"] for b in begins] == ["outer", "inner1", "inner2"]
    outer, inner1, inner2 = begins
    assert outer["depth"] == 0 and outer["parent"] == 0
    assert inner1["parent"] == outer["span"] and inner1["depth"] == 1
    assert inner2["parent"] == outer["span"] and inner2["depth"] == 1
    # LIFO close order: children end before the parent
    assert [e["name"] for e in ends] == ["inner1", "inner2", "outer"]
    assert all(e["dur"] >= 0 for e in ends)
    assert outer["tag"] == "a"  # attrs travel with the event


def test_span_unwind_closes_abandoned_spans():
    sink = telemetry.MemorySink()
    tracer = telemetry.Tracer(sinks=[sink])
    a = tracer.begin("a")
    tracer.begin("b")  # never explicitly ended
    tracer.end(a)  # must close b first, marked abandoned
    assert schema.validate_events(sink.events) == []
    ends = _events(sink, "span_end")
    assert [e["name"] for e in ends] == ["b", "a"]
    assert ends[0].get("abandoned") is True
    assert "abandoned" not in ends[1]
    tracer.end(12345)  # unknown id: no-op, still balanced
    assert schema.validate_events(sink.events) == []


def test_span_stacks_are_per_thread():
    sink = telemetry.MemorySink()
    tracer = telemetry.Tracer(sinks=[sink])
    barrier = threading.Barrier(2)

    def worker(name):
        barrier.wait()
        with tracer.span(name):
            with tracer.span(name + "/child"):
                pass

    threads = [threading.Thread(target=worker, args=(f"t{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert schema.validate_events(sink.events) == []
    for b in _events(sink, "span_begin"):
        # each thread's root span parents to 0, never to the other thread
        if not b["name"].endswith("/child"):
            assert b["parent"] == 0 and b["depth"] == 0


def test_module_helpers_are_noops_when_disabled():
    assert not telemetry.enabled()
    telemetry.stage("x", 0.1)
    telemetry.counter("x", 1)
    telemetry.gauge("x", 1)
    telemetry.instant("x")
    with telemetry.span("x"):
        pass  # nullcontext


def test_close_unwinds_spans_left_open_on_other_threads():
    sink = telemetry.MemorySink()
    tracer = telemetry.Tracer(sinks=[sink])
    opened = threading.Event()

    def worker():
        tracer.begin("worker/stuck")  # thread exits without ending it
        opened.set()

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert opened.wait(5)
    tracer.close()
    assert schema.validate_events(sink.events) == []
    end = next(e for e in _events(sink, "span_end")
               if e["name"] == "worker/stuck")
    assert end.get("abandoned") is True
    begin = next(e for e in _events(sink, "span_begin")
                 if e["name"] == "worker/stuck")
    assert end["tid"] == begin["tid"] != threading.get_ident()


def test_maybe_run_ownership(tmp_path, monkeypatch):
    # telemetry off: no run started, yields None
    with telemetry.maybe_run() as owned:
        assert owned is None and not telemetry.enabled()
    # configured + no active run: owns it, ends it even on exceptions
    monkeypatch.setenv("BIGDL_TELEMETRY", str(tmp_path))
    with pytest.raises(RuntimeError, match="boom"):
        with telemetry.maybe_run(meta={"cmd": "t"}) as owned:
            assert owned and telemetry.enabled()
            raise RuntimeError("boom")
    assert not telemetry.enabled(), "owned run must end on exception"
    n, errors = schema.validate_run(owned)
    assert errors == [] and n >= 2  # run_start + run_end flushed
    # an OUTER run is never ended (and never re-pointed at a new file)
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]) as outer:
        with telemetry.maybe_run() as owned:
            assert owned is None
            assert telemetry.get() is outer
        assert telemetry.enabled(), "outer run must survive maybe_run"
        telemetry.instant("after")  # still recorded by the outer run
    assert any(e["name"] == "after" for e in _events(sink, "event"))


def test_nested_start_run_rejected(tmp_path):
    telemetry.start_run(str(tmp_path))
    with pytest.raises(RuntimeError, match="already active"):
        telemetry.start_run(str(tmp_path))
    telemetry.end_run()
    telemetry.end_run()  # idempotent


# -- schema ------------------------------------------------------------------
def test_schema_rejects_malformed_events():
    base = {"v": 1, "ts": 1.0, "pid": 1, "tid": 1}
    assert schema.validate_event({**base, "kind": "nope"})
    assert schema.validate_event({**base, "kind": "stage", "name": "x"})
    assert schema.validate_event(
        {**base, "kind": "stage", "name": 3, "dur": 0.1})
    assert not schema.validate_event(
        {**base, "kind": "stage", "name": "x", "dur": 0.1})
    # structural: unclosed + out-of-order spans
    ev = [dict(base, kind="span_begin", name="a", span=1, parent=0,
               depth=0),
          dict(base, kind="span_begin", name="b", span=2, parent=1,
               depth=1),
          dict(base, kind="span_end", name="a", span=1, dur=0.1)]
    problems = schema.validate_events(ev)
    assert any("out of order" in p for p in problems)
    ev = [dict(base, kind="span_begin", name="a", span=1, parent=0,
               depth=0)]
    assert any("never closed" in p for p in schema.validate_events(ev))


def test_jsonl_roundtrip_and_validate_run(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with telemetry.run(path):
        telemetry.counter("records", 32)
        with telemetry.span("stage_a"):
            telemetry.instant("marker", detail="hello")
    n, errors = schema.validate_run(path)
    assert errors == []
    assert n == 7  # run_start, counter, begin, event, end, goodput, run_end
    events, parse_errors = schema.read_events(path)
    assert parse_errors == []
    assert events[0]["kind"] == "run_start"
    assert events[-1]["kind"] == "run_end"


# -- chrome export -----------------------------------------------------------
def _assert_chrome_nesting(trace):
    stacks = {}
    for ev in trace["traceEvents"]:
        key = (ev.get("pid"), ev.get("tid"))
        if ev["ph"] == "B":
            stacks.setdefault(key, []).append(ev["name"])
        elif ev["ph"] == "E":
            stack = stacks.setdefault(key, [])
            assert stack, f"E without B on lane {key}: {ev['name']}"
            assert stack.pop() == ev["name"], "unbalanced span nesting"
    for key, stack in stacks.items():
        assert not stack, f"unclosed chrome spans on lane {key}: {stack}"


def test_chrome_trace_export_nests_and_types():
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        with telemetry.span("outer"):
            with telemetry.span("inner"):
                telemetry.gauge("depth", 2)
        telemetry.stage("h2d", 0.01)
        telemetry.instant("fired")
        telemetry.emit("step", step=1, dur=0.5, loss=1.0)
    trace = chrome_trace(sink.events)
    _assert_chrome_nesting(trace)
    phases = {e["ph"] for e in trace["traceEvents"]}
    assert {"B", "E", "X", "C", "i"} <= phases
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert any(e["name"] == "step 1" and e["dur"] == 0.5e6 for e in xs)
    # X events start dur before their emission timestamp
    h2d = next(e for e in xs if e["name"] == "h2d")
    assert h2d["dur"] == pytest.approx(0.01e6)


# -- Metrics: concurrency + forwarding (satellite) ---------------------------
def test_metrics_concurrent_writers_lose_nothing():
    m = Metrics()
    n_threads, n_adds = 8, 400
    barrier = threading.Barrier(n_threads + 1)
    stop = threading.Event()

    def writer(i):
        barrier.wait()
        for _ in range(n_adds):
            m.add("shared stage", 1.0)
            m.add(f"own {i}", 2.0)

    def reader():
        barrier.wait()
        while not stop.is_set():
            m.summary()
            m.get("shared stage")
            m.stages()

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    rt = threading.Thread(target=reader)
    for t in threads + [rt]:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rt.join()
    assert m.count("shared stage") == n_threads * n_adds
    assert m.total("shared stage") == pytest.approx(n_threads * n_adds)
    for i in range(n_threads):
        assert m.count(f"own {i}") == n_adds
        assert m.get(f"own {i}") == 2.0


def test_metrics_stages_report_in_stable_pipeline_order():
    """ISSUE-3 satellite: summaries of the same run must be comparable
    line-by-line — canonical pipeline stages first (execution order, not
    alphabetical), unknown stages in first-recorded order."""
    m = Metrics()
    for name in ("computing time", "zeta custom", "data time",
                 "alpha custom", "dispatch time"):
        m.add(name, 1.0)
    assert m.stages() == ["data time", "dispatch time", "computing time",
                          "zeta custom", "alpha custom"]
    lines = m.summary().splitlines()[1:-1]
    assert [ln.split(" : ")[0] for ln in lines] == m.stages()


def test_metrics_forward_into_event_log_under_concurrency():
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        m = Metrics()
        threads = [threading.Thread(
            target=lambda: [m.add("stage", 0.5) for _ in range(100)])
            for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        with m.timer("timed stage"):
            pass
    stages = _events(sink, "stage")
    assert len([e for e in stages if e["name"] == "stage"]) == 400
    assert any(e["name"] == "timed stage" for e in stages)
    assert schema.validate_events(sink.events) == []


# -- runtime visibility: straggler, prefetch, retrace ------------------------
def _make_samples(n=64, dim=4):
    rng = np.random.default_rng(0)
    return [Sample(rng.normal(size=dim).astype(np.float32),
                   np.int64(rng.integers(0, 2))) for _ in range(n)]


def test_straggler_firing_lands_in_event_log(monkeypatch):
    import time as _time

    from bigdl_tpu.optim.optimizer import StragglerTimeout

    sink = telemetry.MemorySink()
    monkeypatch.setenv("BIGDL_ITERATION_TIMEOUT", "0.3")
    o = optim.LocalOptimizer(
        nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax()), _make_samples(),
        nn.ClassNLLCriterion(), batch_size=16,
        end_trigger=Trigger.max_iteration(1))
    with telemetry.run(sinks=[sink]):
        with pytest.raises(StragglerTimeout):
            o._run_with_straggler_guard(lambda: _time.sleep(5))
    fired = [e for e in _events(sink, "event")
             if e["name"] == "straggler/timeout"]
    assert fired and fired[0]["budget_s"] == pytest.approx(0.3)


def test_training_emits_steps_prefetch_depth_and_compiles():
    sink = telemetry.MemorySink()
    model = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
    o = optim.LocalOptimizer(model, _make_samples(),
                             nn.ClassNLLCriterion(), batch_size=16,
                             end_trigger=Trigger.max_iteration(5))
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    with telemetry.run(sinks=[sink]):
        o.optimize()
    assert schema.validate_events(sink.events) == []
    steps = _events(sink, "step")
    assert [e["step"] for e in steps] == [1, 2, 3, 4, 5]
    assert all(e["records"] == 16 and e["dur"] > 0 for e in steps)
    # prefetch (default depth 2) samples its queue fill level
    depths = [e for e in sink.events
              if e["kind"] == "gauge" and e["name"] == "prefetch/queue_depth"]
    assert depths
    # the first dispatch compiled (the Optimizer dispatches via
    # run_sharded), and the facts explain it
    compiles = _events(sink, "compile")
    assert any(c["name"] == "TrainStep.run_sharded" for c in compiles)
    facts = _events(sink, "device_facts")
    assert facts and facts[0]["facts"].get("flops_per_step", 0) > 0
    # iteration spans wrap data_wait spans (nesting in the live log)
    begins = _events(sink, "span_begin")
    it_ids = {b["span"] for b in begins if b["name"] == "train/iteration"}
    dw = [b for b in begins if b["name"] == "data_wait"]
    assert dw and all(b["parent"] in it_ids for b in dw)


def test_unwritable_telemetry_dir_never_kills_training(tmp_path,
                                                       monkeypatch):
    """Telemetry is an observer: a misconfigured BIGDL_TELEMETRY (here a
    plain file where a directory is needed) must log a warning and train
    anyway, not raise out of optimize()."""
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("occupied")
    monkeypatch.setenv("BIGDL_TELEMETRY", str(blocker / "sub"))
    model = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
    o = optim.LocalOptimizer(model, _make_samples(),
                             nn.ClassNLLCriterion(), batch_size=16,
                             end_trigger=Trigger.max_iteration(1))
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    o.optimize()  # must complete
    assert not telemetry.enabled(), "no half-started run may leak"


def test_optimize_preserves_caller_spans():
    """The documented embedding pattern: a span the CALLER opened around
    optimize() must survive it — the loop's exception unwind stops at
    its own scope's depth."""
    sink = telemetry.MemorySink()
    model = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
    o = optim.LocalOptimizer(model, _make_samples(),
                             nn.ClassNLLCriterion(), batch_size=16,
                             end_trigger=Trigger.max_iteration(2))
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    with telemetry.run(sinks=[sink]):
        with telemetry.span("job"):
            o.optimize()
            telemetry.instant("still_inside_job")
    assert schema.validate_events(sink.events) == []
    job_ends = [e for e in _events(sink, "span_end")
                if e["name"] == "job"]
    assert len(job_ends) == 1 and "abandoned" not in job_ends[0]


def test_retrace_bridge_attributes_shape_change():
    import jax

    from bigdl_tpu.parallel.train_step import TrainStep
    from bigdl_tpu.telemetry.bridge import RetraceBridge

    sink = telemetry.MemorySink()
    rng = np.random.default_rng(0)
    with telemetry.run(sinks=[sink]):
        bridge = RetraceBridge(telemetry.get()).install()
        try:
            step = TrainStep(nn.Sequential(nn.Linear(4, 2)),
                             nn.MSECriterion(),
                             optim.SGD(learning_rate=0.1))
            for n in (8, 16):  # batch shape change => retrace
                x = rng.normal(size=(n, 4)).astype(np.float32)
                y = rng.normal(size=(n, 2)).astype(np.float32)
                step.run(x, y, jax.random.key(0))
        finally:
            bridge.remove()
    retraces = _events(sink, "retrace")
    assert any(e["rule"] == "retrace/shape-change" for e in retraces)
    assert len(_events(sink, "compile")) >= 2  # both shapes compiled


def test_step_respects_device_facts_off(monkeypatch):
    import jax

    from bigdl_tpu.parallel.train_step import TrainStep

    monkeypatch.setenv("BIGDL_TELEMETRY_DEVICE", "off")
    sink = telemetry.MemorySink()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 4)).astype(np.float32)
    y = rng.normal(size=(8, 2)).astype(np.float32)
    with telemetry.run(sinks=[sink]):
        step = TrainStep(nn.Sequential(nn.Linear(4, 2)),
                         nn.MSECriterion(), optim.SGD(learning_rate=0.1))
        step.run(x, y, jax.random.key(1))
    # "off" silences the device-facts emitter; compiles still land
    assert not _events(sink, "device_facts")
    assert any(c["name"] == "TrainStep.run"
               for c in _events(sink, "compile"))


def test_summary_bridge_feeds_tensorboard(tmp_path):
    from bigdl_tpu.visualization import TrainSummary

    ts = TrainSummary(str(tmp_path), "app")
    sink = telemetry.MemorySink()
    model = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
    o = optim.LocalOptimizer(model, _make_samples(),
                             nn.ClassNLLCriterion(), batch_size=16,
                             end_trigger=Trigger.max_iteration(4))
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    o.set_train_summary(ts)
    with telemetry.run(sinks=[sink]):
        o.optimize()
    rows = ts.read_scalar("telemetry/prefetch/queue_depth")
    assert rows, "telemetry gauges bridged into the TrainSummary writer"
    assert ts.read_scalar("Loss")  # the existing scalars still flow
    ts.close()


# -- device facts / MFU ------------------------------------------------------
def test_peak_flops_table_and_override(monkeypatch):
    from bigdl_tpu.telemetry import device

    assert device.peak_flops_per_device("TPU v4") == 275e12
    assert device.peak_flops_per_device("TPU v5 lite") == 197e12
    assert device.peak_flops_per_device("TPU v5p") == 459e12
    assert device.peak_flops_per_device("cpu") is None
    monkeypatch.setenv("BIGDL_PEAK_FLOPS", "2e12")
    assert device.peak_flops_per_device("cpu") == 2e12


def test_mfu_estimate():
    from bigdl_tpu.telemetry.device import mfu_estimate

    assert mfu_estimate(1e12, 0.01, 275e12, 1) == \
        pytest.approx(1e14 / 275e12)
    assert mfu_estimate(1e12, 0.01, 275e12, 4) == \
        pytest.approx(1e14 / (4 * 275e12))
    assert mfu_estimate(0, 0.01, 275e12) is None
    assert mfu_estimate(1e12, 0.01, None) is None


# -- the tier-1 end-to-end acceptance ----------------------------------------
def test_cli_train_with_telemetry_end_to_end(tmp_path, monkeypatch,
                                             capsys):
    """models/cli train (registry model, synthetic data) with telemetry
    on -> schema-valid JSONL -> the inspection CLI reconstructs the
    per-stage table, step p50/p95, compile timeline, and an MFU
    estimate; the Chrome export nests correctly."""
    from bigdl_tpu.models import cli as models_cli
    from bigdl_tpu.telemetry import __main__ as tele_cli

    tele_dir = str(tmp_path / "tele")
    monkeypatch.setenv("BIGDL_TELEMETRY", tele_dir)
    # CPU has no peak-FLOPs table entry; pin one so MFU is computable
    monkeypatch.setenv("BIGDL_PEAK_FLOPS", "1e12")
    models_cli.main(["train", "--model", "lenet", "-b", "256",
                     "--max-epoch", "1", "--telemetry", tele_dir])
    capsys.readouterr()  # drop the training output
    runs = glob.glob(os.path.join(tele_dir, "run-*.jsonl"))
    assert len(runs) == 1
    n, errors = schema.validate_run(runs[0])
    assert errors == [], errors[:5]
    assert n > 20

    events, _ = schema.read_events(runs[0])
    summary = summarize(events)
    # 1024 synthetic records / batch 256 = 4 steps
    assert summary["steps"]["count"] == 4
    assert summary["steps"]["records"] == 1024
    assert summary["steps"]["p95_s"] >= summary["steps"]["p50_s"] > 0
    for stage_name in ("data time", "dispatch time", "validation time",
                       "train/iteration", "data_wait"):
        assert stage_name in summary["stages"], stage_name
    assert any(c["name"] == "TrainStep.run_sharded"
               for c in summary["compiles"])
    facts = summary["device_facts"]
    assert facts["flops_per_step"] > 0
    assert facts["peak_flops_per_device"] == 1e12
    assert summary["mfu"] is not None and summary["mfu"] > 0

    chrome_path = str(tmp_path / "trace.json")
    rc = tele_cli.main([runs[0], "--chrome", chrome_path])
    assert rc == 0
    out = capsys.readouterr().out
    assert "-- stage time --" in out
    assert "p50" in out and "p95" in out
    assert "compile" in out
    assert "MFU" in out
    with open(chrome_path) as fh:
        trace = json.load(fh)
    assert trace["traceEvents"]
    _assert_chrome_nesting(trace)
    rc = tele_cli.main([runs[0], "--validate"])
    assert rc == 0


def test_cli_json_summary_roundtrips(tmp_path, capsys):
    from bigdl_tpu.telemetry import __main__ as tele_cli

    path = str(tmp_path / "run.jsonl")
    with telemetry.run(path):
        telemetry.emit("step", step=1, dur=0.01, records=8,
                       throughput=800.0)
        telemetry.stage("data time", 0.002)
    assert tele_cli.main([path, "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["steps"]["count"] == 1
    assert summary["stages"]["data time"]["n"] == 1
