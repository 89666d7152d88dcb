"""Async input prefetch (VERDICT r3 item 4; reference capability
``dataset/image/MTLabeledBGRImgToBatch.scala:31``): the Optimizer loop
must overlap host transform + h2d with the device step, without changing
training semantics."""

import time

import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.dataset.transformer import Transformer
from bigdl_tpu.nn.module import state_dict
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.utils.config import BigDLConfig, set_config


def teardown_function(_fn):
    set_config(None)


def _make_data(n=64, dim=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)
    return [Sample(x[i], np.int64(y[i])) for i in range(n)]


def _mlp(dim=4, width=16, seed=42):
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(seed)
    return nn.Sequential(nn.Linear(dim, width), nn.Tanh(),
                         nn.Linear(width, 2), nn.LogSoftMax())


def _train(prefetch: int, seed=7, iters=12):
    set_config(BigDLConfig(prefetch_batches=prefetch))
    from bigdl_tpu.utils.rng import RNG

    samples = _make_data()
    m = _mlp(seed=seed)
    RNG.set_seed(99)  # data shuffling + dropout keys identical per run
    o = optim.LocalOptimizer(m, samples, nn.ClassNLLCriterion(),
                             batch_size=16,
                             end_trigger=Trigger.max_iteration(iters))
    o.set_optim_method(optim.SGD(learning_rate=0.5, momentum=0.9))
    o.optimize()
    return {k: np.asarray(v) for k, v in state_dict(m).items()}, o.metrics


def test_prefetch_matches_sync_trajectory():
    """Double-buffered input must reproduce the synchronous trajectory
    bit-for-bit in expectation (same batches, same keys, same updates)."""
    p_params, p_metrics = _train(prefetch=2)
    s_params, s_metrics = _train(prefetch=0)
    for k in s_params:
        np.testing.assert_allclose(p_params[k], s_params[k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    # both paths record the full stage set; h2d is driver-side stall
    # when sync, explicitly-overlapped producer time when prefetching
    for m, h2d in ((p_metrics, "host to device time (overlapped)"),
                   (s_metrics, "host to device time")):
        for want in ("data time", h2d, "dispatch time", "computing time"):
            assert want in m.stages(), (want, m.stages())


class SlowTransform(Transformer):
    """Host-side transform with a fixed per-batch cost (stands in for
    JPEG decode + augmentation)."""

    def __init__(self, delay_s: float):
        self.delay_s = delay_s

    def apply(self, it):
        for batch in it:
            time.sleep(self.delay_s)
            yield batch


def test_prefetch_hides_slow_input():
    """Deflaked (ISSUE 3 satellite): the old version asserted a
    wall-clock ratio (overlapped wait < 60% of sync wait), which a
    loaded machine blew ~1 run in 4 by descheduling the producer
    thread.  The property that makes the overlap real is scheduling-
    independent: the producer demonstrably runs AHEAD of the driver
    (queue depth reaches >= 1 while the driver is busy — the first step
    alone holds the driver in XLA compile for ~100ms while the producer
    only pays the ~20ms transform), every batch flows through the queue
    (producer-side h2d samples, zero driver-side ones), and the queue
    keeps being refilled DURING training, not just in the warmup fill."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch

    delay, iters = 0.02, 6
    rng = np.random.default_rng(3)
    samples = [Sample(rng.normal(size=(16,)).astype(np.float32),
                      np.int64(i % 2)) for i in range(64)]
    set_config(BigDLConfig(prefetch_batches=2))
    ds = DataSet.array(samples).transform(
        SampleToMiniBatch(16)).transform(SlowTransform(delay))
    o = optim.LocalOptimizer(_mlp(dim=16, seed=5), ds,
                             nn.ClassNLLCriterion(), batch_size=16,
                             end_trigger=Trigger.max_iteration(iters))
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        o.optimize()

    # 1) the producer ran ahead: some put sampled a non-empty queue
    depths = [e["value"] for e in sink.events
              if e["kind"] == "gauge"
              and e["name"] == "prefetch/queue_depth"]
    assert depths, "producer never enqueued a batch"
    assert max(depths) >= 1, f"producer never got ahead: {depths}"
    # 2) every consumed batch came through the queue: h2d happened on
    # the producer thread, never as a driver-side stall
    m = o.metrics
    assert m.count("host to device time (overlapped)") >= iters
    assert m.count("host to device time") == 0
    assert m.count("data time") == iters  # the driver's queue-pop waits
    # 3) sustained overlap: the queue was refilled after the first step
    # completed, not only during the pre-training pipe fill
    first_step = next(i for i, e in enumerate(sink.events)
                      if e["kind"] == "step")
    assert any(e["kind"] == "gauge"
               and e["name"] == "prefetch/queue_depth"
               for e in sink.events[first_step + 1:]), \
        "no queue activity after the first step"


def test_prefetch_surfaces_producer_errors():
    """A failure inside the input pipeline must reach the retry loop like
    a compute failure, not hang the driver."""
    class Boom(Transformer):
        def apply(self, it):
            for i, batch in enumerate(it):
                if i == 2:
                    raise RuntimeError("injected input failure")
                yield batch

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch

    set_config(BigDLConfig(prefetch_batches=2, failure_retry_times=1,
                           failure_retry_interval=60.0))
    ds = DataSet.array(_make_data()).transform(
        SampleToMiniBatch(16)).transform(Boom())
    o = optim.LocalOptimizer(_mlp(), ds, nn.ClassNLLCriterion(),
                             batch_size=16,
                             end_trigger=Trigger.max_iteration(10))
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    with pytest.raises(RuntimeError, match="injected input failure"):
        o.optimize()


# -- the ordered pool (PR 29): W workers stack and place, next() keeps the
# iterator's order --------------------------------------------------------

def _numbered(n):
    """Ready batches whose every value is their place in the sequence."""
    from bigdl_tpu.dataset.minibatch import MiniBatch

    for i in range(n):
        yield MiniBatch(np.full((2, 3), i, np.float32),
                        np.full((2,), i, np.int64))


def _pool(data_iter, place=lambda x, y: (x, y), depth=2):
    from bigdl_tpu.optim.metrics import Metrics
    from bigdl_tpu.optim.optimizer import _BatchPrefetcher

    return _BatchPrefetcher(data_iter, place, depth, Metrics())


def _uneven_place(x, y):
    """Even batches take far longer to place than odd ones, so every odd
    batch is ready before the even one in front of it."""
    time.sleep(0.03 if int(x[0, 0]) % 2 == 0 else 0.001)
    return x, y


def _drain(pf):
    out = []
    try:
        while True:
            item = pf.next()
            if item is None:
                return out
            out.append(item)
    finally:
        pf.close()


@pytest.fixture
def several_workers(monkeypatch):
    """WORKERS follows the machine's cores; these cases need W > 1."""
    from bigdl_tpu.optim.optimizer import _BatchPrefetcher

    monkeypatch.setattr(_BatchPrefetcher, "WORKERS",
                        max(_BatchPrefetcher.WORKERS, 4))


def _live_workers():
    import threading

    return [t for t in threading.enumerate()
            if t.name == "bigdl-prefetch" and t.is_alive()]


def test_pool_hands_out_in_iterator_order(several_workers):
    got = _drain(_pool(_numbered(12), _uneven_place))
    assert [n for n, _ in got] == [2] * 12
    assert [int(x[0, 0]) for _, (x, _) in got] == list(range(12))
    assert [int(y[0]) for _, (_, y) in got] == list(range(12))


def test_pool_matches_sync_trajectory_under_uneven_placement(
        monkeypatch, several_workers):
    import itertools

    from bigdl_tpu.parallel.train_step import TrainStep

    real, calls = TrainStep._shard_batch, itertools.count()

    def uneven(self, x, y):
        time.sleep(0.03 if next(calls) % 2 == 0 else 0.001)
        return real(self, x, y)

    monkeypatch.setattr(TrainStep, "_shard_batch", uneven)
    p_params, _ = _train(prefetch=2)
    s_params, _ = _train(prefetch=0)
    for k in s_params:
        np.testing.assert_allclose(p_params[k], s_params[k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_stacking_error_surfaces_in_its_place_in_the_sequence(
        several_workers):
    """The 3rd batch cannot be stacked: batches 1 and 2 arrive, the 3rd
    ``next()`` raises, though batches 4 and 5 were stacked long before."""
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch

    samples = _make_data(n=20)
    samples[9] = Sample(np.zeros((2, 2), np.float32), np.int64(0))
    pf = _pool(SampleToMiniBatch(4).apply(iter(samples)), _uneven_place)
    try:
        assert pf.next()[0] == 4
        assert pf.next()[0] == 4
        with pytest.raises(ValueError, match="different rank"):
            pf.next()
    finally:
        pf.close()
    assert _live_workers() == []


def test_close_with_workers_blocked_on_a_full_queue():
    pf = _pool(_numbered(10 ** 9))
    deadline = time.monotonic() + 10.0
    while pf._pulled - pf._handed < pf._limit and \
            time.monotonic() < deadline:
        time.sleep(0.005)
    assert pf._pulled - pf._handed == pf._limit  # full: every worker waits
    assert len(_live_workers()) >= pf.WORKERS
    t0 = time.monotonic()
    pf.close()
    assert time.monotonic() - t0 < 4.0
    assert _live_workers() == []
    assert pf._pulled == pf._limit  # nothing was pulled past the bound


class _Observer(Transformer):
    def __init__(self):
        self.seen = []

    def apply(self, it):
        for s in it:
            self.seen.append(float(s.feature[0]))
            yield s


def _observed(prefetch, iters=9):
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch
    from bigdl_tpu.utils.rng import RNG

    set_config(BigDLConfig(prefetch_batches=prefetch))
    obs = _Observer()
    RNG.set_seed(99)  # the epoch permutations
    ds = DataSet.array(_make_data()).transform(obs).transform(
        SampleToMiniBatch(16))
    o = optim.LocalOptimizer(_mlp(), ds, nn.ClassNLLCriterion(),
                             batch_size=16,
                             end_trigger=Trigger.max_iteration(iters))
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    o.optimize()
    return obs.seen


def test_observer_in_the_chain_sees_the_synchronous_order():
    """Nine steps of 16 over 64 records cross two epoch boundaries; the
    pool pulls further ahead than the loop consumes, never differently."""
    sync, pooled = _observed(0), _observed(2)
    assert len(sync) >= 9 * 16 and len(pooled) >= len(sync)
    assert pooled[:len(sync)] == sync


def test_in_flight_gauge_exceeds_one_when_placing_is_slow(several_workers):
    from bigdl_tpu import telemetry

    def slow(x, y):
        time.sleep(0.02)
        return x, y

    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        pf = _pool(_numbered(8), slow)
        got = _drain(pf)
    assert len(got) == 8
    in_flight = [e["value"] for e in sink.events if e["kind"] == "gauge"
                 and e["name"] == "prefetch/in_flight"]
    assert max(in_flight) > 1, in_flight
    assert max(in_flight) <= pf._limit
    for stage in ("batch stack time (overlapped)",
                  "host to device time (overlapped)"):
        assert pf._metrics.count(stage) == 8


def test_pulls_are_paced_apart_not_in_bursts(several_workers):
    """Workers that start together would finish together for ever, and
    the loop would get WORKERS batches at once, then none for a cycle.
    A pull waits until a WORKERS-th of a batch's cycle has passed since
    the last one: a lower bound, so no load on the machine can fail it."""
    from bigdl_tpu.optim.optimizer import _BatchPrefetcher

    pulls, w = [], _BatchPrefetcher.WORKERS

    def timed():
        for batch in _numbered(5 * w):
            pulls.append(time.monotonic())
            yield batch

    def place(x, y):
        time.sleep(0.04)
        return x, y

    got = _drain(_pool(timed(), place))
    assert len(got) == 5 * w
    gaps = np.diff(pulls[2 * w:])  # the first round starts together
    assert gaps.min() >= 0.5 * 0.04 / w, gaps  # unpaced: microseconds


def test_pool_keeps_order_under_more_workers_than_cores(monkeypatch):
    """Stress: 32 workers, a thread switch every 10 us, 400 batches whose
    placing takes an uneven sliver of time.  A lost update in the
    sequence numbers or the reorder buffer breaks the order, drops a
    batch or lets more than the bound in flight."""
    import sys

    from bigdl_tpu.optim.optimizer import _BatchPrefetcher

    monkeypatch.setattr(_BatchPrefetcher, "WORKERS", 32)
    worst = [0]

    def place(x, y):
        time.sleep((int(x[0, 0]) * 7 % 5) * 1e-4)
        return x, y

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pf = _pool(_numbered(400), place)
        out = []
        deadline = time.monotonic() + 60.0
        try:
            while time.monotonic() < deadline:
                worst[0] = max(worst[0], pf._pulled - pf._handed)
                item = pf.next()
                if item is None:
                    break
                out.append(int(item[1][0][0, 0]))
        finally:
            pf.close()
    finally:
        sys.setswitchinterval(old)
    assert out == list(range(400))
    assert worst[0] <= pf._limit == 34
    assert _live_workers() == []


# -- placement (PR 29): a mesh's rows go from the host to the chips that
# own them, not through device 0 ------------------------------------------

def _mesh4():
    import jax

    from bigdl_tpu.parallel.mesh import make_mesh

    return make_mesh(devices=jax.devices()[:4])


def _puts(monkeypatch):
    """Records what reaches ``jax.device_put``, and lets it through."""
    import jax

    seen, real = [], jax.device_put

    def device_put(x, *a, **kw):
        seen.append(x)
        return real(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", device_put)
    return seen


def _check_rows(arr, host):
    """Every addressable shard holds its own rows of ``host`` only."""
    import jax

    rows = host.shape[0] // 4
    assert len(arr.addressable_shards) == 4
    for k, dev in enumerate(jax.devices()[:4]):
        shard, = [s for s in arr.addressable_shards if s.device == dev]
        want = host[k * rows:(k + 1) * rows]
        assert shard.data.shape == want.shape
        assert shard.data.devices() == {dev}
        np.testing.assert_array_equal(np.asarray(shard.data), want)


def test_shard_local_batch_puts_host_rows_on_their_own_devices(monkeypatch):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.parallel.mesh import data_sharding, shard_local_batch

    mesh = _mesh4()
    x = np.arange(8 * 3 * 5, dtype=np.float32).reshape(8, 3, 5)
    y = np.arange(8, dtype=np.int64)
    before = [jax.device_put(jnp.asarray(a), data_sharding(mesh, a.ndim))
              for a in (x, y)]  # the parent's placement
    seen = _puts(monkeypatch)
    after = [shard_local_batch(mesh, a) for a in (x, y)]
    # the HOST array reached device_put: nothing was committed whole to
    # one device on the way (jnp.asarray would hand over a jax.Array)
    assert [type(a) for a in seen] == [np.ndarray, np.ndarray]
    for old, new, host in zip(before, after, (x, y)):
        assert new.sharding == old.sharding
        assert new.dtype == old.dtype and new.shape == old.shape
        assert new.committed
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
        _check_rows(new, host)
    # an array that is on a device already goes as it is
    assert shard_local_batch(mesh, before[0]) is before[0]
