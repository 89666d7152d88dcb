"""Static-analyzer suite (``bigdl_tpu/analysis``): one intentionally
broken model per rule class asserting the EXACT rule id fires, plus a
clean run over every model in the zoo registry asserting zero errors —
so no pass can degrade into a stub that always returns clean."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.analysis import (check_model, check_partition_specs,
                                check_shapes, trace_retraces)
from bigdl_tpu.analysis.ast_lint import lint_source
from bigdl_tpu.analysis.shape_pass import infer_input_spec, output_spec
from bigdl_tpu.analysis.sharding_pass import check_train_step
from bigdl_tpu.models import registry
from bigdl_tpu.nn.graph import Graph, GraphBuildError, Input, Node
from bigdl_tpu.nn.module import load_state_dict, state_dict
from bigdl_tpu.parallel.mesh import make_mesh
from bigdl_tpu.parallel.train_step import EvalStep, TrainStep


# --------------------------------------------------------------------------
# seeded defects: every rule class must fire with its exact rule id
# --------------------------------------------------------------------------

def test_seeded_shape_mismatch():
    # 8-dim output feeds a 4-dim input: dot contraction mismatch
    broken = nn.Sequential(nn.Linear(4, 8), nn.Linear(4, 2))
    res = check_shapes(broken, jax.ShapeDtypeStruct((2, 4), jnp.float32))
    assert "shape/mismatch" in res.report.rules_fired()
    assert res.out is None
    # the finding is pinned to the offending layer, not the whole model
    assert res.report.errors[0].where == "1"


def test_seeded_f64_promotion():
    class PromoteF64(nn.Module):
        def update_output(self, input):
            return jnp.asarray(input, jnp.float64)


    m = nn.Sequential(nn.Linear(4, 4), PromoteF64(), nn.Linear(4, 2))
    with jax.enable_x64():
        res = check_shapes(m, jax.ShapeDtypeStruct((2, 4), jnp.float32))
    assert "shape/f64" in res.report.rules_fired()
    # only the promoting layer is flagged, not every downstream consumer
    assert [d.where for d in res.report
            if d.rule == "shape/f64"] == ["1"]


def test_seeded_dead_node():
    inp = Input()
    live = nn.Linear(4, 4).set_name("live").inputs(inp)
    nn.Linear(4, 4).set_name("deadbranch").inputs(inp)  # feeds nothing
    g = Graph(inp, live)
    res = check_shapes(g, jax.ShapeDtypeStruct((2, 4), jnp.float32))
    assert "shape/dead-node" in res.report.rules_fired()
    assert any("deadbranch" in d.message for d in res.report)
    assert not res.report.errors  # dead node is a warning, model still runs


def test_seeded_bad_partition_spec_axis():
    mesh = make_mesh((jax.device_count(),), ("data",))
    report = check_partition_specs(
        mesh,
        {"w": P("model"), "v": P("data")},
        {"w": np.zeros((8, 8)), "v": np.zeros((6, 2))})
    rules = report.rules_fired()
    assert "shard/unknown-axis" in rules        # 'model' not on this mesh
    if jax.device_count() > 1 and 6 % jax.device_count():
        assert "shard/indivisible" in rules     # 6 rows over 8 devices


def test_seeded_bad_train_step_sharding_rule():
    # a bad axis in extra_sharding_rules would explode inside
    # TrainStep.__init__'s device_put — the pre-flight check names the
    # parameter and the bad axis BEFORE construction
    from bigdl_tpu.analysis.sharding_pass import check_sharding_rules

    mesh = make_mesh((jax.device_count(),), ("data",))
    m = nn.Sequential(nn.Linear(4, 4), nn.LogSoftMax())
    report = check_sharding_rules(
        mesh, state_dict(m, kind="param"),
        lambda path, arr: P("model") if path.endswith("weight") else None)
    assert "shard/unknown-axis" in report.rules_fired()
    assert any("0.weight" in d.where for d in report)


def test_seeded_duplicate_axis_and_rule_error():
    mesh = make_mesh((jax.device_count(),), ("data",))
    report = check_partition_specs(
        mesh, {"w": P("data", "data")}, {"w": np.zeros((8, 8))})
    assert "shard/duplicate-axis" in report.rules_fired()

    from bigdl_tpu.analysis.sharding_pass import check_sharding_rules

    def crashing_rules(path, arr):
        raise RuntimeError("boom")

    report = check_sharding_rules(
        mesh, {"0.weight": np.zeros((4, 4))}, crashing_rules)
    assert report.rules_fired() == ["shard/rule-error"]


def test_hooks_never_kill_the_step():
    class Exploding:
        def on_dispatch(self, *a):
            raise RuntimeError("observer bug")

        def on_cache(self, *a):
            raise RuntimeError("observer bug")

    from bigdl_tpu.analysis import hooks as hooks_mod

    m = nn.Sequential(nn.Linear(4, 3), nn.LogSoftMax())
    step = TrainStep(m, nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1))
    bad = Exploding()
    hooks_mod.register(bad)
    try:
        loss = step.run(jnp.ones((4, 4)), jnp.zeros((4,), jnp.int32),
                        jax.random.key(0))
    finally:
        hooks_mod.unregister(bad)
    assert np.isfinite(float(loss))


def test_replicated_large_param_warning():
    if jax.device_count() < 2:
        pytest.skip("needs a multi-device mesh")
    mesh = make_mesh((jax.device_count(),), ("data",))
    m = nn.Sequential(nn.Linear(512, 2048))  # 1M+ elements, replicated
    step = TrainStep(m, nn.MSECriterion(), optim.SGD(learning_rate=0.1),
                     mesh=mesh)
    report = check_train_step(step)
    assert "shard/replicated-large" in report.rules_fired()
    assert not report.errors  # advisory, not an error


def test_seeded_tracer_leak_fixture():
    fixture = """
import time
import numpy as np
import jax

@jax.jit
def step(x):
    if x > 0:              # lint/tracer-branch
        x = -x
    t = time.time()        # lint/host-call
    return np.abs(x) + t   # lint/tracer-numpy
"""
    report = lint_source(fixture, "fixture.py")
    rules = report.rules_fired()
    assert "lint/tracer-branch" in rules
    assert "lint/host-call" in rules
    assert "lint/tracer-numpy" in rules


def test_lint_static_idioms_stay_clean():
    clean = """
import jax
import jax.numpy as jnp

@jax.jit
def f(x, y):
    if x.ndim == 3:        # static: fine
        x = x[None]
    n = x.shape[0]
    if n > 2:              # static-derived: fine
        y = y + 1
    if y is None:          # identity: fine
        return x
    return jnp.where(x > 0, x, -x)   # traced select: fine
"""
    assert not lint_source(clean, "clean.py").rules_fired()


def test_lint_name_resolution_respects_scope():
    # a module-level host helper sharing its name with a locally-jitted
    # def must NOT be linted as traced code (Python scoping: the local
    # def wins at the jit(...) reference)
    src = """
import jax


def fwd(x, t):
    if x > t:          # host-side: fine
        return x
    return t


def build():
    def fwd(y):
        return -y
    return jax.jit(fwd)
"""
    assert not lint_source(src, "scoped.py").rules_fired()


def test_lint_match_statement_bodies_scanned():
    src = """
import jax
import numpy as np

@jax.jit
def f(x, mode):
    match mode:
        case "neg":
            if x > 0:           # leak inside a case body
                x = -x
        case _:
            x = np.abs(x)       # np on tracer inside a case body
    return x
"""
    rules = lint_source(src, "m.py").rules_fired()
    assert "lint/tracer-branch" in rules
    assert "lint/tracer-numpy" in rules


def test_lint_paths_accepts_extensionless_file(tmp_path):
    from bigdl_tpu.analysis.ast_lint import lint_paths

    script = tmp_path / "train"  # explicit target, no .py suffix
    script.write_text("import jax\n\n@jax.jit\ndef f(x):\n"
                      "    if x > 0:\n        return -x\n    return x\n")
    assert "lint/tracer-branch" in \
        lint_paths([str(script)]).rules_fired()


def test_lint_noqa_suppression():
    src = """
import jax

@jax.jit
def f(x):
    if x > 0:  # noqa: lint/tracer-branch
        return -x
    return x
"""
    assert not lint_source(src, "x.py").rules_fired()


def test_seeded_graph_duplicate_name():
    inp = Input()
    a = nn.Linear(4, 4).set_name("fc").inputs(inp)
    b = nn.Linear(4, 4).set_name("fc").inputs(a)  # distinct module, same name
    with pytest.raises(GraphBuildError) as exc:
        Graph(inp, b)
    assert exc.value.rule == "graph/duplicate-name"
    assert "fc" in str(exc.value)


def test_graph_weight_sharing_names_ok():
    # the SAME module object on two nodes (Siamese) is not a collision
    shared = nn.Linear(4, 4).set_name("tied")
    inp = Input()
    a = shared.inputs(inp)
    b = shared.inputs(a)
    g = Graph(inp, b)
    out = g.forward(jnp.ones((2, 4)))
    assert out.shape == (2, 4)


def test_seeded_graph_cycle():
    n1 = Node(nn.Linear(4, 4).set_name("a"))
    n2 = Node(nn.Linear(4, 4).set_name("b"))
    n1.add_prev(n2)
    n2.add_prev(n1)
    with pytest.raises(GraphBuildError) as exc:
        Graph([], n1)
    assert exc.value.rule == "graph/cycle"
    # the message names the actual cycle members
    assert "a" in str(exc.value) and "b" in str(exc.value)


def test_seeded_retrace_shape_change():
    m = nn.Sequential(nn.Linear(4, 3), nn.LogSoftMax())
    step = TrainStep(m, nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1))
    y4 = jnp.zeros((4,), jnp.int32)
    y6 = jnp.zeros((6,), jnp.int32)
    with trace_retraces() as mon:
        step.run(jnp.ones((4, 4)), y4, jax.random.key(0))
        step.run(jnp.ones((4, 4)), y4, jax.random.key(1))  # steady: no diag
        step.run(jnp.ones((6, 4)), y6, jax.random.key(2))  # retrace
    rules = mon.report.rules_fired()
    assert rules.count("retrace/shape-change") == 2  # x and y both changed
    assert any("x" in d.where for d in mon.report)


def test_retrace_sees_direct_run_sharded():
    # the Optimizer's hot loop calls run_sharded directly (its h2d vs
    # dispatch Metrics split) — the detector must still attribute the
    # retrace to the argument instead of a false retrace/recompile
    m = nn.Sequential(nn.Linear(4, 3), nn.LogSoftMax())
    step = TrainStep(m, nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1))
    with trace_retraces() as mon:
        for n in (4, 4, 6):  # last batch shrinks: legitimate retrace
            x, y = step._shard_batch(jnp.ones((n, 4)),
                                     jnp.zeros((n,), jnp.int32))
            step.run_sharded(x, y, jax.random.key(n))
    rules = mon.report.rules_fired()
    assert mon.dispatches == 3
    assert "retrace/shape-change" in rules
    assert "retrace/recompile" not in rules


def test_cli_json_output_is_pure_json(capsys):
    import json

    from bigdl_tpu.analysis.__main__ import main

    assert main(["lenet", "--json"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out) == []  # clean model, no findings, valid JSON


def test_seeded_retrace_python_scalar():
    es = EvalStep(nn.Sequential(nn.Identity()))
    with trace_retraces() as mon:
        es.run(jnp.float32(1.0))   # strong f32 scalar
        es.run(2.0)                # Python float: weak — flip recompiles
    assert "retrace/python-scalar" in mon.report.rules_fired()


# --------------------------------------------------------------------------
# satellite: load_state_dict aggregates ALL key problems in one error
# --------------------------------------------------------------------------

def test_load_state_dict_reports_all_keys_at_once():
    m = nn.Sequential(nn.Linear(2, 2), nn.Linear(2, 2))
    st = state_dict(m)
    bad = dict(st)
    del bad["0.weight"], bad["1.bias"]          # two missing
    bad["ghost.weight"] = jnp.zeros((2, 2))     # two unexpected
    bad["phantom.bias"] = jnp.zeros((2,))
    with pytest.raises(KeyError) as exc:
        load_state_dict(m, bad, strict=True)
    msg = str(exc.value)
    for key in ("0.weight", "1.bias", "ghost.weight", "phantom.bias"):
        assert key in msg, f"{key} not reported in: {msg}"


def test_load_state_dict_nonstrict_ignores_unknown():
    m = nn.Sequential(nn.Linear(2, 2))
    load_state_dict(m, {"nope.weight": jnp.zeros((2, 2))}, strict=False)


# --------------------------------------------------------------------------
# clean runs: every zoo model must pass every static check with 0 errors
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", registry.model_names())
def test_zoo_model_checks_clean(name):
    model = registry.build_model(name)
    spec = registry.input_spec(name)
    res = check_model(model, spec)
    assert not res.report.errors, res.report.format()
    assert res.out is not None
    assert res.layers, "per-layer walk produced no rows"


def test_infer_input_spec_matches_registry():
    # optimize_for_tpu's fallback inference agrees with the canonical
    # spec for the conv models it exists for
    for name in ("resnet", "vgg_cifar", "lenet"):
        model = registry.build_model(name)
        inferred = infer_input_spec(model)
        assert inferred is not None, name
        assert output_spec(model, inferred) is not None, name


# --------------------------------------------------------------------------
# CLI plumbing
# --------------------------------------------------------------------------

def test_cli_model_check_exit_codes(capsys):
    from bigdl_tpu.analysis.__main__ import main

    assert main(["lenet", "resnet"]) == 0
    out = capsys.readouterr().out
    assert "0 error(s)" in out


def test_cli_list_rules(capsys):
    from bigdl_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("shape/mismatch", "shard/unknown-axis",
                 "retrace/shape-change", "lint/tracer-branch"):
        assert rule in out


def test_cli_lint_path_fails_on_leak(tmp_path, capsys):
    from bigdl_tpu.analysis.__main__ import main

    bad = tmp_path / "leaky.py"
    bad.write_text("import jax\n\n@jax.jit\ndef f(x):\n"
                   "    if x > 0:\n        return -x\n    return x\n")
    assert main([str(bad)]) == 1
    assert main([str(bad), "--suppress", "lint/tracer-branch"]) == 0


def test_lint_graft_tool_exit_codes(tmp_path):
    # the wrapper's argparse/exit plumbing on explicit targets; the
    # repo-wide clean run is tests/test_lint_clean.py (no need to lint
    # the whole tree twice per tier-1 run)
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import lint_graft
    finally:
        sys.path.pop(0)
    clean = tmp_path / "clean.py"
    clean.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return -x\n")
    leaky = tmp_path / "leaky.py"
    leaky.write_text("import jax\n\n@jax.jit\ndef f(x):\n"
                     "    if x > 0:\n        return -x\n    return x\n")
    assert lint_graft.main([str(clean)]) == 0
    assert lint_graft.main([str(tmp_path)]) == 1
    assert lint_graft.main([str(leaky),
                            "--suppress", "lint/tracer-branch"]) == 0
