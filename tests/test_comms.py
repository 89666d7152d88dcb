"""Per-collective comms attribution (telemetry/comms.py, ISSUE 10).

Covers the HLO collective parser (both replica-groups spellings,
variadic operands, async -start forms), mesh-axis inference, the
byte-accounting acceptance criterion (within 10% of the analytic
parameter-payload expectation on 2-device sharded lenet/transformer
steps — the XLA cost_analysis bytes-accessed convention: operand +
output), module attribution of gradient collectives, the per-step
``comms`` event and its knob, the CLI views, the trace-time parser, and
the diff gate."""

import gzip
import json

import jax
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu import telemetry
from bigdl_tpu.parallel.mesh import make_mesh
from bigdl_tpu.parallel.train_step import TrainStep
from bigdl_tpu.telemetry import comms, schema
from bigdl_tpu.utils.config import BigDLConfig, set_config


@pytest.fixture(autouse=True)
def _fresh_config():
    set_config(None)
    yield
    set_config(None)


# -- the HLO parser ----------------------------------------------------------
def test_parse_hlo_collectives_brace_and_iota_groups():
    hlo = """
  %all-reduce.1 = f32[4,8]{1,0} all-reduce(f32[4,8]{1,0} %p0), channel_id=1, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(step)/jit(main)/transpose(jvp(fc1))/dot_general"}
  %all-gather = f32[16,8]{1,0} all-gather(f32[8,8]{1,0} %p1), channel_id=2, replica_groups=[2,2]<=[4], dimensions={0}, metadata={op_name="jit(step)/jit(main)/jvp(fc2)/dot_general"}
  %reduce-scatter = f32[4]{0} reduce-scatter(f32[8]{0} %p2), channel_id=3, replica_groups=[1,2]<=[2], dimensions={0}, to_apply=%add
  %all-reduce-done = f32[4,8]{1,0} all-reduce-done(f32[4,8]{1,0} %ar)
"""
    colls = comms.parse_hlo_collectives(hlo, ("data", "model"), (2, 2))
    assert [c.opcode for c in colls] == ["all-reduce", "all-gather",
                                        "reduce-scatter"]
    ar, ag, rs = colls
    assert ar.payload_bytes == 4 * 8 * 4 and ar.bytes == 2 * 4 * 8 * 4
    assert ar.path == "fc1" and ar.direction == "bwd"
    assert ar.groups == [(0, 1), (2, 3)]
    # all-gather: out = in * group_size
    assert ag.payload_bytes == 8 * 8 * 4
    assert ag.bytes == 8 * 8 * 4 * (1 + 2)
    assert ag.direction == "fwd"
    # reduce-scatter: out = in / group_size
    assert rs.payload_bytes == 8 * 4 and rs.bytes == 8 * 4 + 4 * 4
    # the -done half of an async pair is never double-counted
    assert len(colls) == 3


def test_parse_hlo_variadic_and_start_forms():
    hlo = """
  %all-reduce = (f32[4]{0}, f32[2,2]{1,0}) all-reduce(f32[4]{0} %a, f32[2,2]{1,0} %b), channel_id=5, replica_groups={{0,1}}, to_apply=%add
  %all-reduce-start = f32[8]{0} all-reduce-start(f32[8]{0} %c), channel_id=6, replica_groups={{0,1}}, to_apply=%add
"""
    colls = comms.parse_hlo_collectives(hlo, ("data",), (2,))
    assert len(colls) == 2
    # the combiner's variadic all-reduce sums every operand
    assert colls[0].payload_bytes == (4 + 4) * 4
    assert colls[1].payload_bytes == 8 * 4
    assert all(c.axes == ("data",) for c in colls)


def test_infer_axes_subsets_and_permute_pairs():
    names, sizes = ("data", "model"), (2, 4)
    # model-axis groups on a (2,4) mesh: {0..3} and {4..7}
    assert comms.infer_axes([(0, 1, 2, 3), (4, 5, 6, 7)], names, sizes) \
        == ("model",)
    # data-axis groups pair positions 4 apart
    assert comms.infer_axes([(0, 4), (1, 5), (2, 6), (3, 7)],
                            names, sizes) == ("data",)
    # everything at once
    assert comms.infer_axes([tuple(range(8))], names, sizes) \
        == ("data", "model")
    # a permute ring along the model axis (not a partition)
    ring = [(0, 1), (1, 2), (2, 3), (3, 0)]
    assert comms.infer_axes(ring, names, sizes) == ("model",)
    # a pair crossing BOTH axes at once names nothing
    assert comms.infer_axes([(0, 7)], names, sizes) == ()
    assert comms.infer_axes(None, names, sizes) == ()


# -- acceptance: bytes within 10% of the analytic expectation ---------------
def _param_bytes(step):
    return sum(int(np.prod(np.shape(v))) * 4 for v in step.params.values())


@pytest.mark.parametrize("name,batch", [("lenet", 8), ("transformer", 2)])
def test_comms_bytes_match_cost_accounting(name, batch):
    """The acceptance criterion: on the 2-device batch-sharded lenet and
    transformer train steps, the walker's collective bytes-accessed
    must land within 10% of the analytic expectation — every f32
    gradient is all-reduced, and the bytes-accessed convention (operand
    + output, as XLA's cost analysis counts an op) makes that 2x the
    parameter bytes, modulo the scalar loss psum."""
    from bigdl_tpu.models import registry

    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    model = registry.build_model(name)
    spec = registry.input_spec(name, batch)
    criterion, tspec = registry.train_pieces(name, batch)
    step = TrainStep(model, criterion,
                     optim.SGD(learning_rate=0.01, momentum=0.9),
                     mesh=mesh, parameter_sync="allreduce")
    out = comms.attribute_comms_train_step(step, spec, tspec)
    assert out["count"] > 0
    expected = 2 * _param_bytes(step)
    assert abs(out["bytes"] - expected) / expected < 0.10, \
        (out["bytes"], expected)
    # every byte crosses the data axis — the replica groups resolved
    assert out["by_axis"].get("data", 0) == out["bytes"]
    # gradient collectives attribute onto real modules, backward pass
    named = [r for r in out["rows"] if r["path"] != "(unattributed)"]
    assert named, out["rows"]
    assert sum(r["bytes"] for r in named) / out["bytes"] > 0.9
    text = comms.format_comms(out)
    assert "all-reduce" in text and "data" in text


def test_comms_zero1_moves_more_bytes_than_allreduce():
    """ZeRO-1 ('sharded') trades the plain gradient all-reduce for a
    reduce-scatter + sharded update + param all-gather — exactly the
    bytes-moved-per-axis accounting question of arXiv 2004.13336, and
    the walker must expose the difference so `diff` can gate it: more
    collective ops, more bytes accessed than the dense all-reduce, all
    still crossing the data axis."""
    from bigdl_tpu.models import registry

    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    criterion, tspec = registry.train_pieces("lenet", 8)
    spec = registry.input_spec("lenet", 8)
    outs = {}
    for sync in ("allreduce", "sharded"):
        step = TrainStep(registry.build_model("lenet"), criterion,
                         optim.SGD(learning_rate=0.01, momentum=0.9),
                         mesh=mesh, parameter_sync=sync)
        outs[sync] = comms.attribute_comms_train_step(step, spec, tspec)
    dense, zero = outs["allreduce"], outs["sharded"]
    assert zero["bytes"] > dense["bytes"]
    assert zero["by_axis"].get("data", 0) == zero["bytes"]
    # the ZeRO layout introduces gather/scatter traffic beside (or
    # instead of) the plain all-reduce
    assert set(zero["by_op"]) != {"all-reduce"} or \
        zero["count"] > dense["count"], zero["by_op"]


def test_single_device_step_has_no_collectives():
    model = nn.Sequential(nn.Linear(6, 4), nn.LogSoftMax())
    step = TrainStep(model, nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1))
    x = jax.ShapeDtypeStruct((4, 6), np.float32)
    y = jax.ShapeDtypeStruct((4,), np.int32)
    out = comms.attribute_comms_train_step(step, x, y)
    assert out["count"] == 0 and out["bytes"] == 0
    assert "no collectives" in comms.format_comms(out)


# -- the comms event + knob --------------------------------------------------
def _sharded_step_run(sink):
    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    model = nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 4),
                          nn.LogSoftMax())
    step = TrainStep(model, nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1), mesh=mesh)
    x = np.ones((8, 6), np.float32)
    y = np.zeros((8,), np.int64)
    with telemetry.run(sinks=[sink]):
        step.run(x, y, jax.random.key(0))


def test_comms_event_emitted_for_sharded_step_by_default():
    sink = telemetry.MemorySink()
    _sharded_step_run(sink)
    events = [e for e in sink.events if e.get("kind") == "comms"]
    assert len(events) == 1
    ev = events[0]
    assert schema.validate_event(ev) == []
    assert ev["count"] > 0 and ev["bytes"] > 0
    assert ev["by_axis"].get("data") == ev["bytes"]
    assert ev["program"] == "train_step"


def test_comms_on_knob_survives_device_facts_off():
    """BIGDL_COMMS=on must emit even with BIGDL_TELEMETRY_DEVICE=off —
    the two knobs are independent (review finding: the device-level
    early return used to mute comms too)."""
    set_config(BigDLConfig(telemetry_device="off", telemetry_comms="on"))
    sink = telemetry.MemorySink()
    _sharded_step_run(sink)
    kinds = [e.get("kind") for e in sink.events]
    assert "comms" in kinds
    assert "device_facts" not in kinds  # the device level still holds


def test_comms_event_off_knob_and_single_device_auto():
    set_config(BigDLConfig(telemetry_comms="off"))
    sink = telemetry.MemorySink()
    _sharded_step_run(sink)
    assert not [e for e in sink.events if e.get("kind") == "comms"]
    # auto + no mesh: nothing emitted either
    set_config(None)
    sink2 = telemetry.MemorySink()
    model = nn.Sequential(nn.Linear(6, 4), nn.LogSoftMax())
    step = TrainStep(model, nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1))
    with telemetry.run(sinks=[sink2]):
        step.run(np.ones((4, 6), np.float32), np.zeros((4,), np.int64),
                 jax.random.key(0))
    assert not [e for e in sink2.events if e.get("kind") == "comms"]


def test_comms_event_rides_the_step_once_and_reads_what_lower_gives():
    """One ``comms`` event a step object, named for the one program
    there is, and its numbers are those of the executable
    ``TrainStep.lower`` hands out (the observers' own source)."""
    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    model = nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 4),
                          nn.LogSoftMax())
    step = TrainStep(model, nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1), mesh=mesh)
    x = np.ones((8, 6), np.float32)
    y = np.zeros((8,), np.int64)
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        step.run(x, y, jax.random.key(0))
        step.run(x, y, jax.random.key(1))
    events = [e for e in sink.events if e.get("kind") == "comms"]
    assert len(events) == 1
    assert events[0]["program"] == "train_step"
    assert events[0]["bytes"] > 0
    facts = comms.comms_facts(
        step.lower(x, y, jax.random.key(0)).compile(), mesh=mesh)
    assert (facts["count"], facts["bytes"]) \
        == (events[0]["count"], events[0]["bytes"])


# -- CLI ---------------------------------------------------------------------
def test_cli_attribute_comms_model_and_json(capsys):
    from bigdl_tpu.telemetry import __main__ as cli

    rc = cli.main(["attribute", "--comms", "--model", "lenet",
                   "--mesh", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "comms attribution" in out and "all-reduce" in out
    rc = cli.main(["attribute", "--comms", "--model", "lenet",
                   "--mesh", "2", "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["count"] > 0
    assert doc["by_axis"]["data"] == doc["bytes"]


def test_cli_attribute_comms_from_run_log(tmp_path, capsys):
    from bigdl_tpu.telemetry import __main__ as cli

    log = tmp_path / "run.jsonl"
    _sharded_step_run(telemetry.JsonlSink(str(log)))
    rc = cli.main(["attribute", "--comms", str(log)])
    out = capsys.readouterr().out
    assert rc == 0 and "all-reduce" in out
    # a log without comms events exits 2 with a hint
    empty = tmp_path / "empty.jsonl"
    with telemetry.run(str(empty)):
        telemetry.instant("epoch", epoch=1)
    assert cli.main(["attribute", "--comms", str(empty)]) == 2


# -- measured wall time from a capture ---------------------------------------
def test_collective_times_from_trace_and_cli_enrichment(tmp_path, capsys):
    trace_dir = tmp_path / "profile-x"
    (trace_dir / "plugins").mkdir(parents=True)
    doc = {"traceEvents": [
        {"ph": "X", "name": "all-reduce.3", "dur": 1500.0, "ts": 0},
        {"ph": "X", "name": "fusion.allreduce_wrapper", "dur": 500.0,
         "ts": 10},
        {"ph": "X", "name": "reduce-scatter.1", "dur": 250.0, "ts": 20},
        {"ph": "X", "name": "dot_general", "dur": 9999.0, "ts": 30},
        {"ph": "i", "name": "all-reduce-instant-ignored", "ts": 40},
    ]}
    with gzip.open(trace_dir / "plugins" / "host.trace.json.gz", "wt",
                   encoding="utf-8") as fh:
        json.dump(doc, fh)
    times = comms.collective_times_from_trace(str(trace_dir))
    assert times["all-reduce"] == pytest.approx(2000.0 / 1e6)
    assert times["reduce-scatter"] == pytest.approx(250.0 / 1e6)
    assert "all-to-all" not in times
    # a perfetto-enabled capture may write BOTH spellings for the SAME
    # events: the perfetto file must win outright, never sum with the
    # chrome one (review finding: durations used to double)
    with gzip.open(trace_dir / "perfetto_trace.json.gz", "wt",
                   encoding="utf-8") as fh:
        json.dump(doc, fh)
    times = comms.collective_times_from_trace(str(trace_dir))
    assert times["all-reduce"] == pytest.approx(2000.0 / 1e6)

    # a run log naming the capture gets measured_s + achieved bandwidth
    from bigdl_tpu.telemetry import __main__ as cli

    log = tmp_path / "run.jsonl"
    with telemetry.run(str(log)):
        telemetry.emit("comms", count=2, bytes=4_000_000,
                       payload_bytes=2_000_000,
                       by_axis={"data": 4_000_000}, program="train_step")
        telemetry.instant("profile/armed", steps=2, dir=str(trace_dir),
                          source="http", perfetto=True)
        telemetry.instant("profile/captured", dir=str(trace_dir),
                          source="http", perfetto=True)
    rc = cli.main(["attribute", "--comms", str(log), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    # 2.25 ms of collectives over 2 captured steps — and the per-op
    # split carries the SAME per-step unit as the total
    assert doc["measured_s"] == pytest.approx(2250.0 / 1e6 / 2)
    assert doc["measured_by_op"]["all-reduce"] == \
        pytest.approx(2000.0 / 1e6 / 2)
    assert sum(doc["measured_by_op"].values()) == \
        pytest.approx(doc["measured_s"])
    assert doc["measured_from"] == str(trace_dir)


def test_profiler_arm_perfetto_flag_roundtrip():
    from bigdl_tpu.telemetry import profiler

    ctl = profiler.ProfilerControl()
    assert ctl.arm(2, "/tmp/nowhere", perfetto=True)
    assert ctl.perfetto is True
    ctl.abort()
    assert ctl.state == profiler.IDLE


# -- diff gate ---------------------------------------------------------------
def _comms_log(path, nbytes, expected_s=None):
    with telemetry.run(str(path)):
        tr = telemetry.get()
        for i in range(1, 4):
            tr.emit("step", step=i, dur=0.01, records=8)
        fields = {"count": 4, "bytes": nbytes,
                  "payload_bytes": nbytes // 2}
        if expected_s is not None:
            fields["expected_s"] = expected_s
        tr.emit("comms", **fields)


def test_diff_flags_comms_bytes_regression(tmp_path, capsys):
    from bigdl_tpu.telemetry import __main__ as cli

    lean, fat = tmp_path / "lean.jsonl", tmp_path / "fat.jsonl"
    _comms_log(lean, 1_000_000, expected_s=0.001)
    _comms_log(fat, 1_500_000, expected_s=0.0015)
    rc = cli.main(["diff", str(lean), str(fat)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "comms_bytes" in out and "REGRESSED" in out
    assert "comms_s" in out
    # fewer bytes moved is an improvement, not a regression
    assert cli.main(["diff", str(fat), str(lean)]) == 0


def test_bench_row_comms_fields_diff_by_suffix():
    from bigdl_tpu.telemetry.diff import bench_metrics, diff_metrics

    a = bench_metrics({"configs": {"x": {"images_per_sec": 10.0,
                                         "comms_bytes": 100.0,
                                         "comms_s": 0.01}}})
    b = bench_metrics({"configs": {"x": {"images_per_sec": 10.0,
                                         "comms_bytes": 200.0,
                                         "comms_s": 0.02}}})
    rows = {r["name"]: r for r in diff_metrics(a, b)}
    assert rows["x.comms_bytes"]["regressed"]
    assert rows["x.comms_s"]["regressed"]


# -- device table ------------------------------------------------------------
def test_peak_bw_override_and_table(monkeypatch):
    from bigdl_tpu.telemetry.device import peak_bw_per_device

    monkeypatch.delenv("BIGDL_PEAK_BW", raising=False)
    assert peak_bw_per_device("TPU v5 lite") == 2.0e11
    assert peak_bw_per_device("TPU v5p chip") == 6.0e11  # longest prefix
    assert peak_bw_per_device("cpu") is None
    monkeypatch.setenv("BIGDL_PEAK_BW", "1e9")
    assert peak_bw_per_device("anything") == 1e9
