"""End-to-end training-loop tests on the virtual 8-device CPU mesh — the
analogue of the reference's DistriOptimizerSpec strategy (SURVEY §4):
distributed path exercised locally, correctness vs a naive reference
optimizer (RefDistriOptimizer/RefLocalOptimizer), fault-injection for the
retry path (ExceptionTest)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu import telemetry
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.nn.module import Module, functional_call, state_dict
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.parallel.mesh import make_mesh
from bigdl_tpu.parallel.train_step import TrainStep, bf16_truncate
from bigdl_tpu.utils.module_format import register


def _make_data(n=64, dim=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    w = rng.normal(size=(dim,)).astype(np.float32)
    y = (x @ w > 0).astype(np.int64)
    return [Sample(x[i], np.int64(y[i])) for i in range(n)], x, y


def _mlp(dim=4, seed=42):
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(seed)
    return nn.Sequential(nn.Linear(dim, 16), nn.Tanh(), nn.Linear(16, 2), nn.LogSoftMax())


def test_local_optimizer_trains():
    samples, x, y = _make_data()
    model = _mlp()
    o = optim.LocalOptimizer(model, samples, nn.ClassNLLCriterion(), batch_size=16,
                             end_trigger=Trigger.max_epoch(8))
    o.set_optim_method(optim.SGD(learning_rate=0.5))
    trained = o.optimize()
    res = optim.Evaluator(trained).evaluate(samples, [optim.Top1Accuracy()])
    acc = res[0][0].result()[0]
    assert acc > 0.9, acc


def test_distri_optimizer_on_8dev_mesh_matches_local():
    """RefDistriOptimizer-style equivalence: mesh-sharded training must
    follow the same trajectory as single-device training."""
    samples, x, y = _make_data()
    crit = nn.ClassNLLCriterion()
    mesh = make_mesh()

    m1 = _mlp(seed=7)
    o1 = optim.DistriOptimizer(m1, samples, crit, batch_size=16,
                               end_trigger=Trigger.max_iteration(12), mesh=mesh)
    o1.set_optim_method(optim.SGD(learning_rate=0.5))
    o1.optimize()

    from bigdl_tpu.utils.rng import RNG

    m2 = _mlp(seed=7)
    o2 = optim.LocalOptimizer(m2, samples, crit, batch_size=16,
                              end_trigger=Trigger.max_iteration(12))
    o2.set_optim_method(optim.SGD(learning_rate=0.5))
    o2.optimize()

    p1, p2 = state_dict(m1), state_dict(m2)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=1e-4, atol=1e-5)
    # 12 iterations cross three epoch boundaries, where the driver
    # writes the epoch scalar into the step's state: placed anywhere but
    # where the old one lived (on a mesh: the first device only), it
    # recompiles the whole step
    for o in (o1, o2):
        assert o.last_train_step._compiled._cache_size() == 1


def test_zero1_sharded_matches_allreduce():
    """Sharded-optimizer (ZeRO-1) layout must be numerically equivalent to
    plain allreduce (the reference's RefDistriOptimizer check for its
    owner-node sharded update)."""
    samples, _, _ = _make_data(n=64, dim=8)
    crit = nn.ClassNLLCriterion()
    mesh = make_mesh()
    results = {}
    for mode in ("allreduce", "sharded"):
        m = _mlp(dim=8, seed=3)
        o = optim.DistriOptimizer(m, samples, crit, batch_size=32,
                                  end_trigger=Trigger.max_iteration(8), mesh=mesh)
        o.set_optim_method(optim.Adam(learning_rate=0.05))
        o.set_parameter_sync(mode)
        o.optimize()
        results[mode] = state_dict(m)
    for k in results["allreduce"]:
        np.testing.assert_allclose(np.asarray(results["allreduce"][k]),
                                   np.asarray(results["sharded"][k]),
                                   rtol=1e-4, atol=1e-5)


def test_tensor_parallel_trajectory_matches_replicated():
    """Ref-optimizer discipline for the model axis: a megatron-sharded
    (column-parallel fc1 / row-parallel fc2) training run on a
    ``data x model`` mesh must follow the SAME weight trajectory as the
    fully-replicated run — wrong TP math (a missing psum, a transposed
    shard) diverges within a step and fails the allclose
    (``RefDistriOptimizer.scala:30`` applied to tensor parallelism)."""
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.utils.rng import RNG

    def build():
        RNG.set_seed(21)
        return nn.Sequential(
            nn.Linear(8, 32).set_name("tp_fc1"), nn.Tanh(),
            nn.Linear(32, 16).set_name("tp_fc2"), nn.Tanh(),
            nn.Linear(16, 2), nn.LogSoftMax())

    def tp_rules(path, arr):
        if path.startswith("0.weight"):
            return P("model", None)   # column-parallel: split out-features
        if path.startswith("0.bias"):
            return P("model")
        if path.startswith("2.weight"):
            return P(None, "model")   # row-parallel: split in-features
        return None

    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(9)
    batches = [(rng.normal(size=(16, 8)).astype(np.float32),
                rng.integers(0, 2, 16)) for _ in range(10)]

    final = {}
    for tag, rules in (("tp", tp_rules), ("replicated", None)):
        step = TrainStep(build(), nn.ClassNLLCriterion(),
                         optim.SGD(learning_rate=0.3, momentum=0.9),
                         mesh=mesh, extra_sharding_rules=rules)
        for i, (x, y) in enumerate(batches):
            loss = step.run(x, y, jax.random.key(i))
        assert np.isfinite(float(loss))
        final[tag] = {k: np.asarray(v) for k, v in step.params.items()}

    # the TP run really sharded the weights over the model axis
    assert final["tp"]["0.weight"].shape == (32, 8)
    for k in final["replicated"]:
        np.testing.assert_allclose(final["tp"][k], final["replicated"][k],
                                   rtol=2e-4, atol=2e-5, err_msg=k)


def test_tensor_parallel_wrong_sharding_detected():
    """Negative control for the trajectory test: a WRONG megatron layout
    (row-parallel applied to the first linear's out-features while its
    bias stays replicated-summed... i.e. a transposed column split) must
    NOT silently reproduce the replicated trajectory.  Guards the guard:
    if pjit somehow ignored extra_sharding_rules, both this and the
    positive test would pass and we'd know the gate is vacuous."""
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.utils.rng import RNG

    def build():
        RNG.set_seed(21)
        return nn.Sequential(
            nn.Linear(8, 32).set_name("tp_fc1"), nn.Tanh(),
            nn.Linear(32, 16).set_name("tp_fc2"), nn.Tanh(),
            nn.Linear(16, 2), nn.LogSoftMax())

    mesh = make_mesh((2, 4), ("data", "model"))
    x = np.random.default_rng(9).normal(size=(16, 8)).astype(np.float32)
    y = np.random.default_rng(9).integers(0, 2, 16)

    step = TrainStep(build(), nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.3), mesh=mesh,
                     extra_sharding_rules=lambda p, a: (
                         P(None, "model") if p.startswith("0.weight") else None))
    # GSPMD treats the spec as a LAYOUT, not math: dims that don't divide
    # the axis raise at placement; a divisible-but-transposed layout still
    # computes the same math (resharding inserted automatically), so the
    # correct outcome for this wrong-layout case is an error OR identical
    # trajectory — what must never happen is a silently DIFFERENT result.
    try:
        loss = float(step.run(x, y, jax.random.key(0)))
    except Exception:
        return  # rejected outright: acceptable
    ref = TrainStep(build(), nn.ClassNLLCriterion(),
                    optim.SGD(learning_rate=0.3), mesh=mesh)
    ref.run(x, y, jax.random.key(0))
    for k in ref.params:
        np.testing.assert_allclose(np.asarray(step.params[k]),
                                   np.asarray(ref.params[k]),
                                   rtol=2e-4, atol=2e-5, err_msg=k)


def test_bf16_truncation_exact_semantics():
    x = jnp.asarray(np.random.randn(100).astype(np.float32))
    t = np.asarray(bf16_truncate(x))
    bits = t.view(np.uint32)
    assert (bits & 0x0000FFFF).max() == 0  # low 16 bits cleared
    assert np.abs(t - np.asarray(x)).max() < 0.01 * np.abs(np.asarray(x)).max() + 1e-6


def test_bf16_compressed_training_still_converges():
    samples, _, _ = _make_data()
    m = _mlp(seed=5)
    o = optim.LocalOptimizer(m, samples, nn.ClassNLLCriterion(), batch_size=16,
                             end_trigger=Trigger.max_epoch(8))
    o.set_optim_method(optim.SGD(learning_rate=0.5))
    o.set_gradient_compression("bf16")
    o.optimize()
    res = optim.Evaluator(m).evaluate(samples, [optim.Top1Accuracy()])
    assert res[0][0].result()[0] > 0.9


def test_regularizer_and_freeze_in_train_step():
    model = nn.Sequential(
        nn.Linear(4, 8, w_regularizer=optim.L2Regularizer(0.1)), nn.Tanh(),
        nn.Linear(8, 2))
    model.get(2).freeze()
    frozen_before = np.asarray(model.get(2).weight).copy()
    step = TrainStep(model, nn.MSECriterion(), optim.SGD(learning_rate=0.1))
    x = np.random.randn(8, 4).astype(np.float32)
    y = np.random.randn(8, 2).astype(np.float32)
    for i in range(3):
        step.run(x, y, jax.random.key(i))
    step.sync_to_model()
    np.testing.assert_array_equal(np.asarray(model.get(2).weight), frozen_before)
    assert not np.allclose(np.asarray(model.get(0).weight), 0)


@register  # persistable: the retry restores it from the checkpoint
class ExceptionLayer(Module):
    """Fault injection (``utils/TestUtils.scala:103`` ExceptionTest): throws
    on the Nth forward — counted on the host each time the compiled step
    RUNS (a count at trace time would see one forward, the trace)."""

    count = 0

    def __init__(self, fail_at: int):
        super().__init__()
        self.fail_at = fail_at

    def _tick(self):
        ExceptionLayer.count += 1
        if ExceptionLayer.count == self.fail_at:
            raise RuntimeError("injected failure")

    def update_output(self, input):
        jax.debug.callback(self._tick)
        return input


def test_retry_recovers_from_checkpoint(tmp_path, monkeypatch):
    monkeypatch.setenv("BIGDL_RETRY_BACKOFF", "0.05")  # the retry, not the wait
    samples, _, _ = _make_data(n=32)
    ExceptionLayer.count = 0
    model = nn.Sequential(nn.Linear(4, 8), ExceptionLayer(fail_at=6), nn.Tanh(),
                          nn.Linear(8, 2), nn.LogSoftMax())
    o = optim.LocalOptimizer(model, samples, nn.ClassNLLCriterion(), batch_size=16,
                             end_trigger=Trigger.max_iteration(8))
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    o.set_checkpoint(str(tmp_path), Trigger.several_iteration(2)).overwrite_checkpoint()
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        o.optimize()
    assert len([e for e in sink.events if e.get("name") == "run/retry"]) == 1
    # 8 iterations, the one that failed, and iteration 5 a second time:
    # the retry resumed from model.4, the newest checkpoint at the
    # failure, and not from the weights it held (9) or from scratch (14)
    assert ExceptionLayer.count == 10
    assert o.model is not model  # the restored module took its place
    assert o.state["neval"] >= 8  # completed despite the injected failure
    assert os.path.exists(str(tmp_path))


def test_checkpoint_resume_roundtrip(tmp_path):
    samples, _, _ = _make_data()
    m = _mlp(seed=11)
    o = optim.LocalOptimizer(m, samples, nn.ClassNLLCriterion(), batch_size=16,
                             end_trigger=Trigger.max_iteration(4))
    o.set_optim_method(optim.Adam(learning_rate=0.01))
    o.set_checkpoint(str(tmp_path), Trigger.several_iteration(2)).overwrite_checkpoint()
    o.optimize()
    from bigdl_tpu.utils.serializer import load_module, load_optim_method

    mfile = optim.Optimizer.get_latest_file(str(tmp_path), "model")
    ofile = optim.Optimizer.get_latest_file(str(tmp_path), "optimMethod")
    assert mfile and mfile.endswith("model.4")
    m2 = load_module(mfile)
    om2 = load_optim_method(ofile)
    p1, p2 = state_dict(m), state_dict(m2)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]), rtol=1e-6)
    assert om2.state["driver_state"]["neval"] == 4
    # resume continues the iteration count
    o2 = optim.LocalOptimizer(m2, samples, nn.ClassNLLCriterion(), batch_size=16,
                              end_trigger=Trigger.max_iteration(6))
    o2.set_optim_method(om2)
    o2.set_state(om2.state["driver_state"])
    o2.optimize()
    assert o2.state["neval"] == 6


def test_validation_and_summary_hooks():
    samples, _, _ = _make_data()
    m = _mlp(seed=13)

    class FakeSummary:
        def __init__(self):
            self.tags = []

        def add_scalar(self, tag, value, step):
            self.tags.append(tag)

    ts, vs = FakeSummary(), FakeSummary()
    o = optim.LocalOptimizer(m, samples, nn.ClassNLLCriterion(), batch_size=32,
                             end_trigger=Trigger.max_iteration(4))
    o.set_optim_method(optim.SGD(learning_rate=0.5))
    o.set_validation(Trigger.several_iteration(2), samples,
                     [optim.Top1Accuracy(), optim.Loss(nn.ClassNLLCriterion())], 32)
    o.set_train_summary(ts).set_validation_summary(vs)
    o.optimize()
    assert "Loss" in ts.tags and "Throughput" in ts.tags and "LearningRate" in ts.tags
    assert "Top1Accuracy" in vs.tags and "Loss" in vs.tags
    assert "score" in o.state


def test_predictor_and_evaluator():
    samples, x, y = _make_data()
    m = _mlp()
    optim.LocalOptimizer(m, samples, nn.ClassNLLCriterion(), batch_size=16,
                         end_trigger=Trigger.max_epoch(6)
                         ).set_optim_method(optim.SGD(learning_rate=0.5)).optimize()
    pred = optim.LocalPredictor(m).predict_class(samples)
    assert (pred == y).mean() > 0.9
    out = optim.LocalPredictor(m).predict(x)
    assert out.shape == (64, 2)


def test_per_stage_metrics_recorded():
    """The host loop must record every SPMD-observable stage
    (docs/straggler.md + Metrics.scala:31-130 re-scope)."""
    samples, _, _ = _make_data()
    o = optim.LocalOptimizer(_mlp(), samples, nn.ClassNLLCriterion(),
                             batch_size=16,
                             end_trigger=Trigger.max_iteration(6))
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    o.set_validation(Trigger.several_iteration(3), samples,
                     [optim.Top1Accuracy()], batch_size=32)
    o.optimize()
    stages = o.metrics.stages()
    for want in ("data time", "host to device time (overlapped)",
                 "dispatch time", "computing time",
                 "compile + first iteration time", "validation time"):
        assert want in stages, (want, stages)
    assert o.metrics.count("compile + first iteration time") == 1
    assert o.metrics.count("computing time") == 5
    assert o.metrics.total("computing time") > 0
    assert "mean" in o.metrics.summary()


def test_straggler_watchdog_times_out_and_retry_budget_ends_run(monkeypatch):
    """A hung iteration triggers StragglerTimeout; with no checkpoint and
    an exhausted retry budget the run surfaces the failure
    (docs/straggler.md policy)."""
    import time as _time

    from bigdl_tpu.optim.optimizer import StragglerTimeout

    samples, _, _ = _make_data()
    o = optim.LocalOptimizer(_mlp(), samples, nn.ClassNLLCriterion(),
                             batch_size=16,
                             end_trigger=Trigger.max_iteration(3))
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    calls = {"n": 0}

    def hang():
        calls["n"] += 1
        _time.sleep(5)

    monkeypatch.setenv("BIGDL_ITERATION_TIMEOUT", "0.5")
    monkeypatch.setenv("BIGDL_FAILURE_RETRY_TIMES", "1")
    # make the iteration hang without a device in the loop
    o._run_with_straggler_guard(lambda: None)  # guard path exercised
    with pytest.raises(StragglerTimeout):
        o._run_with_straggler_guard(hang)
    assert calls["n"] == 1


def test_straggler_auto_budget_arms_after_samples(monkeypatch):
    samples, _, _ = _make_data()
    o = optim.LocalOptimizer(_mlp(), samples, nn.ClassNLLCriterion(),
                             batch_size=16,
                             end_trigger=Trigger.max_iteration(1))
    monkeypatch.setenv("BIGDL_ITERATION_TIMEOUT", "auto")
    assert o._straggler_timeout() is None  # not armed yet
    for t in (0.1, 0.2, 0.1, 0.3, 0.2):
        o._iteration_times.append(t)
    assert o._straggler_timeout() == 60.0  # 10x median, floored at 60s
    o._iteration_times.extend([30.0] * 20)
    assert o._straggler_timeout() == pytest.approx(300.0)
    monkeypatch.setenv("BIGDL_ITERATION_TIMEOUT", "0")
    assert o._straggler_timeout() is None


def test_async_checkpoint_overlaps_and_lands(tmp_path, monkeypatch):
    """Checkpoint byte-writes overlap training (BIGDL_ASYNC_CHECKPOINT
    default); a slow writer must not lose or tear the file set — the run
    joins in-flight writes before restores and at the end."""
    import time as _time

    from bigdl_tpu.utils import file as File
    from bigdl_tpu.utils.serializer import load_module, load_optim_method

    real_save = File.save

    def slow_save(data, path, overwrite=False):
        _time.sleep(0.05)
        return real_save(data, path, overwrite)

    monkeypatch.setattr(File, "save", slow_save)
    # optimizer.py binds the module, not the function — patch its ref too
    import bigdl_tpu.optim.optimizer as opt_mod

    monkeypatch.setattr(opt_mod.File, "save", slow_save)

    samples, _, _ = _make_data()
    m = _mlp(seed=17)
    o = optim.LocalOptimizer(m, samples, nn.ClassNLLCriterion(),
                             batch_size=16,
                             end_trigger=Trigger.max_iteration(6))
    o.set_optim_method(optim.Adam(learning_rate=0.01))
    o.set_checkpoint(str(tmp_path), Trigger.several_iteration(1))
    o.overwrite_checkpoint()
    o.optimize()

    mfile = optim.Optimizer.get_latest_file(str(tmp_path), "model")
    ofile = optim.Optimizer.get_latest_file(str(tmp_path), "optimMethod")
    assert mfile and mfile.endswith("model.6")
    m2 = load_module(mfile)  # loads => the write fully landed
    om2 = load_optim_method(ofile)
    assert om2.state["driver_state"]["neval"] == 6
    p1, p2 = state_dict(m), state_dict(m2)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=1e-6)
    assert "checkpoint wait time" in o.metrics.stages()


def test_remat_trajectory_identical():
    """Rematerialization (jax.checkpoint) must change only the memory /
    recompute schedule, never the math: TrainStep(remat=True) and the
    nn.Remat block wrapper both reproduce the plain trajectory."""
    from bigdl_tpu.utils.rng import RNG

    rng = np.random.default_rng(4)
    batches = [(rng.normal(size=(16, 8)).astype(np.float32),
                rng.integers(0, 2, 16)) for _ in range(6)]

    def run(remat_flag, wrap):
        RNG.set_seed(77)
        block = nn.Sequential(nn.Linear(8, 32), nn.Tanh(),
                              nn.Linear(32, 8), nn.Tanh())
        m = nn.Sequential(nn.Remat(block) if wrap else block,
                          nn.Linear(8, 2), nn.LogSoftMax())
        step = TrainStep(m, nn.ClassNLLCriterion(),
                         optim.SGD(learning_rate=0.3, momentum=0.9),
                         remat=remat_flag)
        for i, (x, y) in enumerate(batches):
            step.run(x, y, jax.random.key(i))
        return {k: np.asarray(v) for k, v in step.params.items()}

    plain = run(False, False)
    step_remat = run(True, False)
    block_remat = run(False, True)
    for k in plain:
        np.testing.assert_allclose(step_remat[k], plain[k],
                                   rtol=1e-6, atol=1e-7, err_msg=k)
    # the wrapped model nests the block's params one level deeper; match
    # by sorted value shapes + norms instead of keys
    a = sorted((v.shape, round(float(np.linalg.norm(v)), 4))
               for v in plain.values())
    b = sorted((v.shape, round(float(np.linalg.norm(v)), 4))
               for v in block_remat.values())
    assert a == b


def test_remat_with_dropout_deterministic():
    """Dropout inside a Remat block: the recompute must reproduce the
    SAME mask (keys derive from the same fold_in chain), so grads equal
    the unwrapped module's."""
    from bigdl_tpu.nn.module import functional_call, state_dict
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(5)
    inner = nn.Sequential(nn.Linear(8, 16), nn.Dropout(0.5), nn.Tanh())
    wrapped = nn.Remat(inner)  # SAME instance: same per-module rng ids
    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=(4, 8)).astype(np.float32))
    p1 = state_dict(inner, kind="param")
    p2 = state_dict(wrapped, kind="param")

    def loss(m, p, key):
        out, _ = functional_call(m, p, x, training=True, rng=key)
        return jnp.sum(out ** 2)

    key = jax.random.key(3)
    g1 = jax.grad(lambda p: loss(inner, p, key))(p1)
    g2 = jax.grad(lambda p: loss(wrapped, p, key))(p2)
    n1 = sorted(round(float(jnp.linalg.norm(v)), 5) for v in g1.values())
    n2 = sorted(round(float(jnp.linalg.norm(v)), 5) for v in g2.values())
    assert n1 == n2


def test_constructor_optim_method_kwarg():
    """Reference python-API parity: Optimizer(..., optim_method=...) in
    the constructor, equivalent to set_optim_method."""
    samples, _, _ = _make_data()
    o = optim.LocalOptimizer(_mlp(), samples, nn.ClassNLLCriterion(),
                             batch_size=16,
                             end_trigger=Trigger.max_iteration(2),
                             optim_method=optim.Adam(learning_rate=0.01))
    assert isinstance(o.optim_method, optim.Adam)
    o.optimize()
    assert o.state["neval"] >= 2


def test_fsdp_matches_allreduce_and_shards_params():
    """ZeRO-3 ('fsdp'): the parameters themselves live sharded over the
    data axis — trajectory identical to plain allreduce (pure GSPMD
    re-annotation, same math) AND the layout is verifiably sharded, so
    no device holds a whole replica."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    samples, _, _ = _make_data(n=64, dim=8)
    crit = nn.ClassNLLCriterion()
    mesh = make_mesh()
    results = {}
    for mode in ("allreduce", "fsdp"):
        m = _mlp(dim=8, seed=3)
        o = optim.DistriOptimizer(m, samples, crit, batch_size=32,
                                  end_trigger=Trigger.max_iteration(8),
                                  mesh=mesh)
        o.set_optim_method(optim.Adam(learning_rate=0.05))
        o.set_parameter_sync(mode)
        o.optimize()
        results[mode] = state_dict(m)
    for k in results["allreduce"]:
        np.testing.assert_allclose(np.asarray(results["allreduce"][k]),
                                   np.asarray(results["fsdp"][k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)
    # layout: every divisible leaf of an fsdp TrainStep is sharded over
    # data (the Optimizer run above used the same TrainStep config)
    step = TrainStep(_mlp(dim=8, seed=3), crit,
                     optim.Adam(learning_rate=0.05), mesh=mesh,
                     parameter_sync="fsdp")
    step.run(np.zeros((32, 8), np.float32), np.zeros(32, np.int64),
             jax.random.key(0))
    n = mesh.shape["data"]
    checked = 0
    for k, v in step.params.items():
        if v.ndim >= 1 and v.shape[0] % n == 0 and v.shape[0] >= n:
            want = NamedSharding(mesh, P(*(("data",) + (None,) * (v.ndim - 1))))
            assert v.sharding.is_equivalent_to(want, v.ndim), (k, v.sharding)
            checked += 1
    assert checked >= 2, "no parameter was actually fsdp-sharded"


def test_fsdp_composes_with_tensor_parallel():
    """fsdp + explicit TP rules on a data x model mesh: TP rules win on
    their leaves, everything else shards over data; trajectory equals
    the replicated run."""
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.utils.rng import RNG

    def build():
        RNG.set_seed(21)
        return nn.Sequential(
            nn.Linear(8, 32).set_name("tp_fc1"), nn.Tanh(),
            nn.Linear(32, 16).set_name("tp_fc2"), nn.Tanh(),
            nn.Linear(16, 2), nn.LogSoftMax())

    def tp_rules(path, arr):
        if path.startswith("0.weight"):
            return P("model", None)
        if path.startswith("0.bias"):
            return P("model")
        return None

    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(9)
    batches = [(rng.normal(size=(16, 8)).astype(np.float32),
                rng.integers(0, 2, 16)) for _ in range(8)]
    final = {}
    for tag, sync, rules in (("fsdp_tp", "fsdp", tp_rules),
                             ("plain", "allreduce", None)):
        step = TrainStep(build(), nn.ClassNLLCriterion(),
                         optim.SGD(learning_rate=0.3, momentum=0.9),
                         mesh=mesh, parameter_sync=sync,
                         extra_sharding_rules=rules)
        for i, (x, y) in enumerate(batches):
            loss = step.run(x, y, jax.random.key(i))
        assert np.isfinite(float(loss))
        final[tag] = {k: np.asarray(v) for k, v in step.params.items()}
    for k in final["plain"]:
        np.testing.assert_allclose(final["fsdp_tp"][k], final["plain"][k],
                                   rtol=2e-4, atol=2e-5, err_msg=k)
