"""``TrainStep.lower``: the program ``run`` dispatches, in hand without
running a step.  It is what the compile-time observers read
(``_emit_device_facts``) and what a test of the compiled text calls, so
it has to be THAT program under every option that changes the program:
the four ``parameter_sync`` layouts, the fault scalar's extra argument,
the health probe's extra output.  And it has to be free: no state
consumed, no executable installed, no event, no retrace finding."""

import jax
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu import telemetry
from bigdl_tpu.analysis.retrace import trace_retraces
from bigdl_tpu.parallel.mesh import make_mesh
from bigdl_tpu.parallel.train_step import TrainStep, _jit_cache_size
from bigdl_tpu.telemetry.comms import comms_facts
from bigdl_tpu.utils.rng import RNG


def _step(mesh=None, **kw):
    RNG.set_seed(0)
    model = nn.Sequential(nn.Linear(6, 16), nn.Tanh(), nn.Linear(16, 4),
                          nn.LogSoftMax())
    return TrainStep(model, nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1, momentum=0.9),
                     mesh=mesh, **kw)


def _data(batch=8):
    rng = np.random.RandomState(0)
    return (rng.randn(batch, 6).astype(np.float32),
            rng.randint(0, 4, batch))


def _mesh(n):
    return make_mesh((n,), ("data",), devices=jax.devices()[:n])


#: the collective kinds of the compiled step on two devices, by layout:
#: which must be there, and which must not (ROADMAP R3 quotes the same
#: kinds for Inception-v1 on four chips: `sharded` adds all-gathers to
#: the all-reduce, `local` has no collective at all)
_KINDS = {
    "allreduce": ({"all-reduce"}, {"all-gather", "reduce-scatter",
                                   "all-to-all"}),
    "sharded": ({"all-reduce", "all-gather"}, set()),
    "fsdp": ({"all-gather"}, set()),
    "local": (set(), {"all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute"}),
}


@pytest.mark.parametrize("sync", sorted(_KINDS))
def test_lower_is_the_layouts_program_and_consumes_no_state(sync):
    mesh = _mesh(2)
    x, y = _data()
    step = _step(mesh, parameter_sync=sync)
    lowered = step.lower(x, y, jax.random.key(0))
    facts = comms_facts(lowered.compile(), mesh=mesh)
    there, absent = _KINDS[sync]
    assert there <= set(facts["by_op"]), facts["by_op"]
    assert not absent & set(facts["by_op"]), facts["by_op"]
    if sync == "local":
        assert facts["count"] == 0 and facts["bytes"] == 0, facts
    # lowering donated nothing: the state is alive, and the step it
    # then runs is the step a fresh object runs
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves((step.params,
                                                step.opt_state)))
    loss = step.run(x, y, jax.random.key(0))
    fresh = _step(mesh, parameter_sync=sync).run(x, y, jax.random.key(0))
    assert float(loss) == float(fresh)
    # and one executable serves both: lower() installed nothing
    assert _jit_cache_size(step._compiled) == 1


@pytest.mark.parametrize("option,n_args,n_outs", [
    ({}, 6, 4),
    ({"grad_fault": True}, 7, 4),
    ({"health_probe": True}, 6, 5),
])
def test_lower_has_the_arity_run_dispatches(option, n_args, n_outs):
    """``grad_fault`` adds the fault scalar's argument, ``health_probe``
    the 5-vector output: ``lower`` gives the same signature, so a
    ``run`` after it (poisoned or not) finds its executable's shape."""
    x, y = _data()
    step = _step(**option)
    lowered = step.lower(x, y, jax.random.key(0))
    args, kwargs = lowered.args_info
    assert len(args) == n_args and not kwargs
    outs = lowered.out_info
    assert len(outs) == n_outs
    if option.get("health_probe"):
        assert tuple(outs[-1].shape) == (5,)
    scale = {"grad_scale": 1.0} if option.get("grad_fault") else {}
    assert np.isfinite(float(step.run(x, y, jax.random.key(0), **scale)))
    assert _jit_cache_size(step._compiled) == 1


def test_lower_is_silent_and_leaves_the_jit_cache_alone():
    """No telemetry event, no retrace finding, no new cache entry: not
    on a fresh step, not on a warm one, not for a batch shape the step
    has never seen."""
    x, y = _data()
    x2, y2 = _data(batch=4)
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]), trace_retraces() as mon:
        fresh = _step()
        before = len(sink.events)
        fresh.lower(x, y, jax.random.key(0))
        assert len(sink.events) == before
        assert _jit_cache_size(fresh._compiled) == 0
        warm = _step()
        warm.run(x, y, jax.random.key(0))
        assert _jit_cache_size(warm._compiled) == 1
        during = len(sink.events)
        warm.lower(x, y, jax.random.key(1))
        warm.lower(x2, y2, jax.random.key(1))
        assert len(sink.events) == during
        assert _jit_cache_size(warm._compiled) == 1
        warm.run(x, y, jax.random.key(2))  # same shape: no finding
    assert mon.report.rules_fired() == [], mon.report.format()


def test_runs_on_a_mesh_follow_the_runs_off_it():
    """n ``run`` calls over the 8-device data mesh give the losses n
    calls on one device give: the sharded batch and the all-reduce
    change where the step runs, not what it computes."""
    x, y = _data(batch=16)
    on, off = _step(_mesh(8)), _step()
    keys = [jax.random.key(i) for i in range(4)]
    got = [float(on.run(x, y, k)) for k in keys]
    want = [float(off.run(x, y, k)) for k in keys]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for k in off.params:
        np.testing.assert_allclose(np.asarray(on.params[k]),
                                   np.asarray(off.params[k]),
                                   rtol=1e-5, atol=1e-6)
