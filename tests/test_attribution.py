"""Per-module cost attribution (docs/observability.md): module-path
scopes in lowered HLO, the StableHLO cost parser, FLOPs fidelity vs
XLA's own cost_analysis, zero-retrace guarantee, Module.summary, and the
CLI surfaces."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu import telemetry
from bigdl_tpu.analysis.retrace import trace_retraces
from bigdl_tpu.models.registry import input_spec, train_pieces
from bigdl_tpu.nn.module import stamp_scope_names
from bigdl_tpu.parallel.train_step import TrainStep, _jit_cache_size
from bigdl_tpu.telemetry import attribution, schema
from bigdl_tpu.telemetry.attribution import (attribute_model, format_attribution,
                                             scope_of)
from bigdl_tpu.utils.config import BigDLConfig, set_config


def teardown_function(_fn):
    telemetry.end_run()
    set_config(None)


def _mlp():
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(3)
    return nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 2),
                         nn.LogSoftMax())


# -- scope plumbing ----------------------------------------------------------
def test_scope_of_unwraps_autodiff_frames():
    assert scope_of("jit(step)/jit(main)/jvp(4)/conv_general_dilated") \
        == ("4", "fwd")
    assert scope_of(
        "jit(step)/jit(main)/transpose(jvp(2))/jvp(attn)/dot_general") \
        == ("2.attn", "bwd")
    # function frames (jit(log_softmax)) are not module scopes
    assert scope_of(
        "jit(step)/jit(main)/jvp(jit(take_along_axis))/gather") \
        == ("", "fwd")
    assert scope_of("w") == ("", "fwd")


def test_private_function_ops_cost_once_per_call_site():
    """A jitted helper lowers to ONE private function whose op names are
    relative to it; the scopes stand on each ``call @helper``.  Its ops
    are attributed to every call site, not to '' or to the first."""
    @jax.jit
    def helper(x, w):
        return jnp.tanh(x @ w)

    def f(x, w):
        with jax.named_scope("a"):
            y = helper(x, w)
        with jax.named_scope("b"):
            return helper(y, w)

    text = attribution.lowered_text(
        jax.jit(f).lower(jnp.ones((4, 8)), jnp.ones((8, 8))))
    assert text.count("call @helper") == 2  # shared, not inlined
    dots = {op.path: op.flops
            for op in attribution.parse_lowered_text(text)
            if op.opcode == "dot_general"}
    assert dots == {"a": 2 * 4 * 8 * 8, "b": 2 * 4 * 8 * 8}


def test_stamp_scope_names_and_off_switch():
    m = _mlp()
    stamp_scope_names(m)
    labels = {name: mod.__dict__.get("_scope_name")
              for name, mod in m.named_modules()}
    assert labels[""] is None  # root carries no scope
    assert labels["0"] == "0" and labels["3"] == "3"
    stamp_scope_names(m, enabled=False)
    assert all(mod.__dict__.get("_scope_name") is None
               for _, mod in m.named_modules())


def test_scopes_add_zero_retraces():
    """The acceptance invariant: scopes are trace-time metadata, never
    jit cache-key material — N steady-state steps stay at one compiled
    executable with no retrace diagnostics."""
    step = TrainStep(_mlp(), nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1))
    assert any(mod.__dict__.get("_scope_name")
               for _, mod in step.model.named_modules()), \
        "TrainStep must stamp scopes by default"
    x = jnp.ones((4, 6))
    y = jnp.zeros((4,), jnp.int32)
    with trace_retraces() as mon:
        for i in range(3):
            step.run(x, y, jax.random.key(i))
    assert mon.report.rules_fired() == []
    assert _jit_cache_size(step._compiled) == 1


def test_scopes_off_knob_respected_by_train_step():
    set_config(BigDLConfig(module_scopes=False))
    step = TrainStep(_mlp(), nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1))
    assert all(mod.__dict__.get("_scope_name") is None
               for _, mod in step.model.named_modules())


# -- attribution fidelity (the acceptance criterion) -------------------------
@pytest.mark.parametrize("name,batch", [("lenet", 8), ("transformer", 2)])
def test_attribution_covers_layers_and_matches_cost_analysis(name, batch):
    """Every parameterized layer appears in the table, conv/linear/
    attention modules carry real FLOPs, and the estimate's total is
    within 10% of XLA's cost_analysis for the same lowered program."""
    result = attribute_model(name, batch=batch)
    rows = {r["path"]: r for r in result["rows"]}
    # every parameterized module has a row
    from bigdl_tpu.models.registry import build_model

    model = build_model(name)
    for path, mod in model.named_modules():
        if path and mod.__dict__["_params"]:
            assert path in rows, f"no attribution row for {path}"
    # compute-bearing layers are individually attributed
    hot_classes = ("SpatialConvolution", "Linear", "MultiHeadAttention")
    hot = [r for r in result["rows"] if r.get("class") in hot_classes]
    assert hot, "expected conv/linear/attention rows"
    # the self-attention QKV GEMM is fused into the attention module
    # (deliberate, see nn/layers/attention.py) — its projection rows
    # may read 0, but every OTHER hot row must carry flops, and the
    # attention row must absorb the fused cost
    for r in hot:
        if r["path"].endswith(("q_proj", "k_proj", "v_proj")):
            continue
        assert r["flops"] > 0, f"{r['path']} has no flops"
        assert r["flops_fwd"] > 0, f"{r['path']} missing forward flops"
        assert r["flops_bwd"] > 0, f"{r['path']} missing backward flops"
    # fidelity: within 10% of XLA's own counting
    assert result.get("cost_flops"), "cost_analysis total missing"
    est, cost = result["total_flops"], result["cost_flops"]
    assert abs(est - cost) / cost < 0.10, \
        f"estimate {est:.3g} vs cost_analysis {cost:.3g}"
    # the unattributed bucket stays a sliver, not the story
    un = rows.get("(unattributed)")
    if un is not None:
        assert un["flops"] / max(est, 1.0) < 0.10
    # table renders
    text = format_attribution(result)
    assert "cost_analysis" in text and name == result["model"]


def test_attribution_event_emitted_when_enabled():
    set_config(BigDLConfig(telemetry_attribution=True))
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        step = TrainStep(_mlp(), nn.ClassNLLCriterion(),
                         optim.SGD(learning_rate=0.1))
        step.run(jnp.ones((4, 6)), jnp.zeros((4,), jnp.int32),
                 jax.random.key(0))
    assert schema.validate_events(sink.events) == []
    events = [e for e in sink.events if e["kind"] == "attribution"]
    assert len(events) == 1
    rows = {r["path"]: r for r in events[0]["rows"]}
    assert rows["0"]["flops"] > 0 and rows["0"]["class"] == "Linear"
    assert rows["0"]["params"] == 6 * 8 + 8


def test_attribution_not_emitted_by_default():
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        step = TrainStep(_mlp(), nn.ClassNLLCriterion(),
                         optim.SGD(learning_rate=0.1))
        step.run(jnp.ones((4, 6)), jnp.zeros((4,), jnp.int32),
                 jax.random.key(0))
    assert [e for e in sink.events if e["kind"] == "attribution"] == []


def test_rows_from_events_reads_back_the_last_attribution():
    events = [{"kind": "attribution", "rows": [{"path": "0"}], "v": 1,
               "ts": 0.0, "pid": 1, "tid": 1, "total_flops": 5.0}]
    out = attribution.rows_from_events(events)
    assert out == {"rows": [{"path": "0"}], "total_flops": 5.0}
    assert attribution.rows_from_events([]) is None


# -- Module.summary ----------------------------------------------------------
def test_module_summary_table_shapes_and_params():
    model = _mlp()
    text = model.summary(jax.ShapeDtypeStruct((4, 6), jnp.float32))
    assert "Linear" in text and "LogSoftMax" in text
    assert "[4, 8] float32" in text      # hidden layer output shape
    assert "[4, 2] float32" in text      # head output shape
    total = 6 * 8 + 8 + 8 * 2 + 2
    assert f"total parameters: {total}" in text


def test_module_summary_without_input_spec_lists_params_only():
    text = _mlp().summary()
    assert "Linear" in text and "-" in text
    assert "total parameters" in text


def test_registry_summary_cli(capsys):
    from bigdl_tpu.models import cli

    cli.main(["summary", "--model", "lenet", "-b", "4"])
    out = capsys.readouterr().out
    assert "SpatialConvolution" in out
    assert "total parameters: 22,278" in out


# -- CLI surfaces ------------------------------------------------------------
def test_telemetry_attribute_cli_model_json(capsys):
    from bigdl_tpu.telemetry.__main__ import main

    rc = main(["attribute", "--model", "lenet", "-b", "4", "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["model"] == "lenet"
    paths = [r["path"] for r in doc["rows"]]
    assert "1" in paths and "8" in paths
    assert abs(doc["total_flops"] - doc["cost_flops"]) \
        / doc["cost_flops"] < 0.10


def test_telemetry_attribute_cli_from_run_log(tmp_path, capsys):
    log = tmp_path / "run.jsonl"
    set_config(BigDLConfig(telemetry_attribution=True))
    with telemetry.run(str(log)):
        step = TrainStep(_mlp(), nn.ClassNLLCriterion(),
                         optim.SGD(learning_rate=0.1))
        step.run(jnp.ones((4, 6)), jnp.zeros((4,), jnp.int32),
                 jax.random.key(0))
    from bigdl_tpu.telemetry.__main__ import main

    rc = main(["attribute", str(log)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "per-module cost attribution" in out and "Linear" in out
    # summary report shows the top-modules section for the same log
    rc = main([str(log)])
    assert rc == 0
    assert "per-module cost" in capsys.readouterr().out


def test_telemetry_attribute_cli_log_without_event(tmp_path, capsys):
    log = tmp_path / "run.jsonl"
    with telemetry.run(str(log)):
        telemetry.instant("epoch", epoch=1)
    from bigdl_tpu.telemetry.__main__ import main

    assert main(["attribute", str(log)]) == 2


def test_models_cli_attribute_forward(capsys):
    from bigdl_tpu.models import cli

    cli.main(["attribute", "--model", "lenet", "-b", "4", "--forward",
              "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert doc["program"] == "forward"
    rows = {r["path"]: r for r in doc["rows"]}
    assert rows["1"]["flops"] > 0
    assert rows["1"]["flops_bwd"] == 0  # forward-only program
