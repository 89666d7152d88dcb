"""Async input prefetch (VERDICT r3 item 4; reference capability
``dataset/image/MTLabeledBGRImgToBatch.scala:31``): the Optimizer loop
must overlap host transform + h2d with the device step, without changing
training semantics."""

import itertools
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


# -- staging buffers (PR 31): a worker stacks into arrays it keeps, and
# fills them again only when what was placed from them is ready and
# nothing placed is backed by them -----------------------------------------

def _to_stack(seqs, rows=4, lengths=None):
    """Batches that are still to be stacked; every value of batch ``i``
    is ``i``.  ``lengths`` makes the records ragged (padded with -1)."""
    from bigdl_tpu.dataset.minibatch import MiniBatch
    from bigdl_tpu.dataset.sample import PaddingParam

    for i in seqs:
        if lengths is None:
            yield MiniBatch.from_samples(
                [Sample(np.full((3,), i, np.float32), np.int64(i))
                 for _ in range(rows)])
        else:
            yield MiniBatch.from_samples(
                [Sample(np.full((n,), i, np.float32), np.int64(i))
                 for n in lengths], PaddingParam(-1.0))


def _copying(seen=None):
    """A placement that copies, as a device with memory of its own does:
    reuse engages on the CPU.  ``seen`` gets the host arrays it was
    handed, which are the worker's staging arrays themselves."""
    def place(x, y):
        if seen is not None:
            seen.append(x)
        return np.array(x), np.array(y)
    return place


@pytest.fixture
def one_worker(monkeypatch):
    """One worker makes which array a batch is stacked into a fact."""
    from bigdl_tpu.optim.optimizer import _BatchPrefetcher

    monkeypatch.setattr(_BatchPrefetcher, "WORKERS", 1)


def _reuse_gauge(sink):
    return [e["value"] for e in sink.events if e["kind"] == "gauge"
            and e["name"] == "prefetch/staging_reuse"]


def test_reuse_engages_under_a_copying_placement_and_keeps_the_trajectory(
        monkeypatch, several_workers):
    from bigdl_tpu import telemetry
    from bigdl_tpu.optim.optimizer import _BatchPrefetcher
    from bigdl_tpu.parallel.train_step import TrainStep

    # NumPy copies, which the compiled step takes as they are: a
    # jax.Array of the CPU client would be taken to hold the buffer
    monkeypatch.setattr(TrainStep, "_shard_batch",
                        lambda self, x, y: (np.array(x), np.array(y)))
    iters = 3 * _BatchPrefetcher.WORKERS + 4
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        p_params, _ = _train(prefetch=2, iters=iters)
    s_params, _ = _train(prefetch=0, iters=iters)
    for k in s_params:
        np.testing.assert_allclose(p_params[k], s_params[k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    share = _reuse_gauge(sink)
    assert len(share) >= iters
    # every worker's first batch is fresh, every later one is reused
    assert share[-1] >= 1 - _BatchPrefetcher.WORKERS / len(share) > 0.5


class _Late(np.ndarray):
    """A placed array that is ready when the test says so."""
    gate = None

    def block_until_ready(self):
        assert self.gate.wait(10.0)
        return self


def test_a_buffer_is_not_filled_again_before_its_placement_is_ready(
        one_worker):
    import threading

    seen, gates = [], []

    def place(x, y):
        seen.append(x)
        late = np.array(x).view(_Late)
        late.gate = threading.Event()
        gates.append(late.gate)
        return late, np.array(y)

    pf = _pool(_to_stack(range(6)), place)
    try:
        deadline = time.monotonic() + 10.0
        while not gates and time.monotonic() < deadline:
            time.sleep(0.002)
        time.sleep(0.1)  # without the wait, batch 1 is in the buffer by now
        assert len(seen) == 1 and (seen[0] == 0).all()
        assert pf._handed == 0 and not pf._ready  # not handed out either
        gates[0].set()
        n, (x0, _) = pf.next()
        assert n == 4 and (np.asarray(x0) == 0).all()
        while len(gates) < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert seen[1] is seen[0] and (seen[0] == 1).all()  # filled again
        assert (np.asarray(x0) == 0).all()  # the placed copy is its own
        # close() with the worker in that wait: it ends once ready
        threading.Timer(0.2, gates[1].set).start()
    finally:
        pf.close()
    assert _live_workers() == []


def _np_passthrough(x, y):
    return np.asarray(x), np.asarray(y)


def _jnp_asarray(x, y):
    import jax.numpy as jnp

    return jnp.asarray(x), jnp.asarray(y)


def _mesh_rows(x, y):
    from bigdl_tpu.parallel.mesh import shard_local_batch

    mesh = _mesh4()
    return shard_local_batch(mesh, x), shard_local_batch(mesh, y)


@pytest.mark.parametrize("place", [_np_passthrough, _jnp_asarray,
                                   _mesh_rows],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_placement_backed_by_the_host_array_keeps_it(
        place, several_workers):
    """The CPU client takes an aligned host array without a copy, and a
    test's placement may hand the array through: such a batch keeps its
    array for good.  Every batch handed out still holds its rows after
    ``2 x WORKERS`` later ones were stacked, and nothing was reused."""
    from bigdl_tpu.optim.optimizer import _BatchPrefetcher

    n = 4 * _BatchPrefetcher.WORKERS
    pf = _pool(_to_stack(range(n)), place)
    got = _drain(pf)  # all of them alive to the end
    assert len(got) == n
    for i, (_, (x, y)) in enumerate(got):
        assert (np.asarray(x) == i).all() and (np.asarray(y) == i).all(), i
    assert (pf._stacked, pf._reused) == (n, 0)


def test_another_shape_replaces_the_set_and_the_full_shape_is_reused_again(
        one_worker):
    """Full, full, a short last batch, full, full, then padded batches
    of length 5, 5, 7, 5, 5 with their padding in other places."""
    seen = []
    batches = itertools.chain(
        _to_stack([0, 1]), _to_stack([2], rows=3), _to_stack([3, 4]),
        _to_stack([5], lengths=(5, 2, 1)), _to_stack([6], lengths=(1, 5, 3)),
        _to_stack([7], lengths=(7, 7, 2)), _to_stack([8], lengths=(2, 5, 5)),
        _to_stack([9], lengths=(5, 1, 1)))
    pf = _pool(batches, _copying(seen))
    got = _drain(pf)
    assert [n for n, _ in got] == [4, 4, 3, 4, 4, 3, 3, 3, 3, 3]
    for i, (_, (x, y)) in enumerate(got):
        assert (y == i).all()
        assert ((x == i) | (x == -1)).all()
    assert [x.shape for _, (x, _) in got] == \
        [(4, 3)] * 2 + [(3, 3)] + [(4, 3)] * 2 + [(3, 5)] * 2 + \
        [(3, 7)] + [(3, 5)] * 2
    np.testing.assert_array_equal(
        got[6][1][0], [[6, -1, -1, -1, -1], [6] * 5, [6, 6, 6, -1, -1]])
    np.testing.assert_array_equal(
        got[9][1][0], [[9] * 5, [9, -1, -1, -1, -1], [9, -1, -1, -1, -1]])
    same = [b is a for a, b in zip(seen, seen[1:])]
    assert same == [True, False, False, True, False, True, False, False,
                    True]
    assert (pf._stacked, pf._reused) == (10, 4)


def test_an_error_while_stacking_into_a_kept_buffer_surfaces_in_place(
        one_worker):
    from bigdl_tpu.dataset.minibatch import MiniBatch

    def batches():
        yield from _to_stack([0, 1])
        yield MiniBatch.from_samples(
            [Sample(np.zeros((3,), np.float32), np.int64(2)),
             Sample(np.zeros((2, 2), np.float32), np.int64(2))])
        yield from _to_stack([3, 4])

    seen = []
    pf = _pool(batches(), _copying(seen))
    try:
        assert (pf.next()[1][0] == 0).all()
        assert (pf.next()[1][0] == 1).all()
        assert seen[1] is seen[0]  # the kept buffer was in use
        with pytest.raises(ValueError, match="different rank"):
            pf.next()
    finally:
        pf.close()
    assert _live_workers() == []
    # the next attempt's prefetcher knows nothing of the last one's
    again = []
    pf = _pool(_to_stack(range(3)), _copying(again))
    got = _drain(pf)
    assert [int(x[0, 0]) for _, (x, _) in got] == [0, 1, 2]
    assert all(a is not s for a in again for s in seen)
    assert (pf._stacked, pf._reused) == (3, 2)


def test_a_batch_that_came_stacked_is_never_kept_or_written(one_worker):
    """Its arrays are the dataset's: a feeder that stacked the next batch
    into them would rewrite the data."""
    from bigdl_tpu import telemetry

    ready = list(_numbered(5))
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        got = _drain(_pool(itertools.chain(ready, _to_stack([5, 6])),
                           _copying()))
    assert [int(x[0, 0]) for _, (x, _) in got] == list(range(7))
    for i, batch in enumerate(ready):
        assert (batch.get_input() == i).all()
        assert (batch.get_target() == i).all()
    # one reading a batch, and one more with the end of the data
    assert _reuse_gauge(sink) == [0, 0, 0, 0, 0, 0, 1 / 7, 1 / 7]


@pytest.mark.parametrize("workers", ["one_worker", "several_workers"])
def test_deferred_batches_a_dataset_keeps_read_the_same_every_epoch(
        workers, request):
    """``DataSet.array(list(SampleToMiniBatch(b)(...)))`` hands the same
    deferred batches out every epoch.  A staging array lent to one of
    them must not become that batch's own: it is filled again, and the
    batch would read the last batch's rows the next time round."""
    request.getfixturevalue(workers)
    kept = list(_to_stack(range(5))) + list(_to_stack([5], rows=3)) + \
        list(_to_stack([6, 7], lengths=(5, 2, 1)))
    for _epoch in range(2):
        pf = _pool(iter(kept), _copying())
        got = _drain(pf)
        assert [n for n, _ in got] == [4] * 5 + [3] * 3
        for i, (_, (x, y)) in enumerate(got):
            assert (y == i).all() and ((x == i) | (x == -1)).all(), i
            assert (x[:, 0] == i).all()
        if workers == "one_worker":  # the array WAS filled again meanwhile
            assert (pf._stacked, pf._reused) == (8, 5)
    # nothing of a lent array stayed with a batch: stacked on its own
    # now, each still gives its rows
    for i, batch in enumerate(kept):
        assert batch._samples is not None
        assert (batch.get_target() == i).all()
        assert (batch.get_input()[:, 0] == i).all()
