"""Straggler-tolerant local SGD (ISSUE 20,
``bigdl_tpu/parallel/local_sync.py`` + ``parameter_sync='local'`` in
``parallel/train_step.py``; docs/fault_tolerance.md "Straggler
tolerance").

Three layers:

* the pure :class:`StalenessBarrier` state machine — behind-by-<S
  continues on stale contributions, behind-by-S sheds, inactive
  statuses and excused peers never delay anyone;
* the :class:`LocalSyncDriver` protocol against a fake cluster — the
  averaging cadence, the grace window charged to ``straggler`` badput,
  the hard-shed marker + excuse, the p0 soft-shed carve-out, and the
  victim's status-then-exit ordering;
* the compiled-program claims — the local-mode step contains ZERO
  cross-island collectives, the amortized averaging traffic beats the
  synchronous all-reduce by >= 0.8·H, and the synchronous path is
  byte-identical whether or not the local-SGD knobs are set.

The live multi-process shed e2e rides tests/test_multihost.py
(``test_two_process_local_sgd_sheds_straggler``).
"""

import json
import threading
import time

import jax
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu import telemetry
from bigdl_tpu.parallel import local_sync
from bigdl_tpu.parallel.local_sync import (BarrierDecision, LocalSyncDriver,
                                           StalenessBarrier, _weighted_mean)
from bigdl_tpu.parallel.mesh import make_mesh
from bigdl_tpu.parallel.train_step import TrainStep
from bigdl_tpu.utils.config import BigDLConfig, set_config


@pytest.fixture(autouse=True)
def _fresh():
    set_config(None)
    yield
    telemetry.end_run()
    set_config(None)


def _instants(sink, name):
    return [e for e in sink.events
            if e.get("kind") == "event" and e.get("name") == name]


# -- the pure staleness barrier ----------------------------------------------
def test_barrier_under_bound_continues():
    """Peers behind by < S never delay the round: survivors average
    their latest (stale) contribution — the SSP contract."""
    b = StalenessBarrier(0, 4, stale=3)
    d = b.decide(5, {1: 3, 2: 5, 3: 4})
    assert isinstance(d, BarrierDecision)
    assert d.ready and d.laggards == [] and d.max_lag == 2


def test_barrier_at_bound_sheds():
    b = StalenessBarrier(0, 3, stale=2)
    d = b.decide(6, {1: 5, 2: 3})
    assert not d.ready and d.laggards == [2] and d.max_lag == 3
    # a peer that never published counts from round 0
    d2 = b.decide(2, {})
    assert sorted(d2.laggards) == [1, 2] and d2.max_lag == 2


def test_barrier_skips_inactive_and_excused():
    """done/preempted/shed/failed peers left on purpose (or are the
    watchdog's problem); excused peers were already shed by US.
    Neither is waited for, neither is shed again."""
    b = StalenessBarrier(0, 5, stale=1)
    statuses = {1: "done", 2: "shed", 3: "preempted"}
    assert b.decide(9, {4: 9}, statuses=statuses).ready
    d = b.decide(9, {}, statuses=statuses, excused=(4,))
    assert d.ready and d.max_lag == 0
    d = b.decide(9, {}, statuses={1: "failed", 2: "running"},
                 excused=(3, 4))
    assert d.laggards == [2]


def test_barrier_rejects_bad_bound():
    with pytest.raises(ValueError, match="staleness bound"):
        StalenessBarrier(0, 2, stale=0)


# -- the weighted merge ------------------------------------------------------
def test_weighted_mean_by_island_count_and_skips_mismatch():
    own = (2.0, {"w": np.array([0.0, 0.0], np.float32),
                 "step": np.array(7, np.int64)}, {})
    peer = (1.0, {"w": np.array([3.0, 3.0], np.float32),
                  "step": np.array(9, np.int64)}, {})
    odd = (4.0, {"w": np.array([1.0, 2.0, 3.0], np.float32)}, {})
    params, buffers = _weighted_mean([own, peer, odd])
    # 2 islands at 0.0 + 1 island at 3.0 -> 1.0; the mis-shaped (and
    # the key-missing) contribution never pollutes the fold
    np.testing.assert_allclose(params["w"], [1.0, 1.0])
    # integer leaves (step counters) keep this process's own value
    assert params["step"] == 7
    assert buffers == {}


# -- the driver against a fake cluster ---------------------------------------
class _FakeHeartbeat:
    def __init__(self):
        self.beats = []

    def beat(self, neval, status=None):
        self.beats.append((neval, status))


class _FakeCluster:
    """The slice of ClusterService the driver touches, minus the
    processes: a directory, a peer table, and the excuse book."""

    def __init__(self, directory, pidx, count, statuses=None):
        self.directory = str(directory)
        self.process_index = pidx
        self.process_count = count
        self.statuses = dict(statuses or {})
        self.beats = []
        self.excused = []
        self.heartbeat = _FakeHeartbeat()
        self.monitor = self

    def peer_table(self):
        return {f"p{p}": {"process_index": p, "status": s}
                for p, s in self.statuses.items()}

    def beat(self, neval):
        self.beats.append(neval)

    def excuse_peer(self, peer, reason):
        self.excused.append((peer, reason))


def _tiny_local_step():
    model = nn.Sequential(nn.Linear(2, 2), nn.LogSoftMax())
    return TrainStep(model, nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1), mesh=None,
                     parameter_sync="local")


def _peer_payload(path, params, islands=1.0):
    payload = {"__islands__": np.asarray(islands)}
    payload.update({f"p::{k}": np.asarray(v) for k, v in params.items()})
    np.savez(str(path), **payload)


def test_driver_sheds_laggard_after_grace(tmp_path, monkeypatch):
    """p1 never publishes: after one grace window the survivor writes
    the ``shed.p1.json`` marker, excuses p1 everywhere, emits
    ``cluster/shed`` (hard), arms its own teardown bypass — and the
    wait lands in ``sync/staleness`` ``waited_s`` for the ledger."""
    armed = []
    monkeypatch.setattr(local_sync, "_arm_survivor_exit",
                        lambda w=None: armed.append(w))
    fake = _FakeCluster(tmp_path, 0, 2, statuses={1: "running"})
    drv = LocalSyncDriver(_tiny_local_step(), cluster=fake, h=1,
                          stale=1, grace=0.15, poll=0.02)
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        drv.on_step(1)
        drv.on_step(2)  # excused: the gone peer never delays again
    marker = json.loads((tmp_path / "shed.p1.json").read_text())
    assert marker["peer"] == 1 and marker["by"] == 0
    assert marker["lag"] >= 1 and marker["stale"] == 1
    assert [p for p, _ in fake.excused] == [1]
    assert fake.beats, "survivor must keep beating while it waits"
    assert armed, "shed must arm the survivor's os._exit teardown"
    sheds = _instants(sink, "cluster/shed")
    assert len(sheds) == 1
    assert sheds[0]["role"] == "survivor" and sheds[0]["mode"] == "hard"
    stale = _instants(sink, "sync/staleness")
    assert stale[0]["waited_s"] >= 0.15   # the grace window, charged
    assert stale[1]["waited_s"] < 0.1     # round 2: nobody to wait for
    avgs = _instants(sink, "sync/average")
    assert [e["peers"] for e in avgs] == [1, 1]


def test_driver_merges_peer_within_bound_no_shed(tmp_path, monkeypatch):
    """A peer that HAS published within the bound is merged (weighted
    by island count) and nothing is shed — including its contribution
    being up to S rounds stale."""
    monkeypatch.setattr(local_sync, "_arm_survivor_exit",
                        lambda w=None: pytest.fail("must not shed"))
    fake = _FakeCluster(tmp_path, 0, 2, statuses={1: "running"})
    step = _tiny_local_step()
    drv = LocalSyncDriver(step, cluster=fake, h=1, stale=2,
                          grace=0.2, poll=0.02)
    own = step.island_mean_host(step.params)
    _peer_payload(tmp_path / "sync.p1.r1.npz",
                  {k: np.asarray(v) + 2.0 for k, v in own.items()})
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        drv.on_step(1)  # round 1: peer current
        merged = step.island_mean_host(step.params)
        for k in own:
            np.testing.assert_allclose(
                np.asarray(merged[k]), np.asarray(own[k]) + 1.0,
                rtol=1e-6, atol=1e-6,
                err_msg=f"merge of {k} is not the equal-weight mean")
        drv.on_step(2)  # round 2: peer stale by 1 < S=2 -> still merged
    assert not fake.excused and not list(tmp_path.glob("shed.*"))
    avgs = _instants(sink, "sync/average")
    assert [e["peers"] for e in avgs] == [2, 2]


def test_driver_soft_sheds_process_zero(tmp_path, monkeypatch):
    """p0 hosts the jax.distributed coordination service: making it
    exit would fatally abort every survivor's runtime client.  A slow
    p0 is excused (survivors stop waiting and stop merging it) but gets
    NO marker — it keeps running."""
    monkeypatch.setattr(local_sync, "_arm_survivor_exit",
                        lambda w=None: None)
    fake = _FakeCluster(tmp_path, 1, 2, statuses={0: "running"})
    drv = LocalSyncDriver(_tiny_local_step(), cluster=fake, h=1,
                          stale=1, grace=0.1, poll=0.02)
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        drv.on_step(1)
    assert not (tmp_path / "shed.p0.json").exists()
    assert [p for p, _ in fake.excused] == [0]
    sheds = _instants(sink, "cluster/shed")
    assert len(sheds) == 1 and sheds[0]["mode"] == "soft"


def test_driver_grace_window_lets_peer_catch_up(tmp_path, monkeypatch):
    """A peer AT the bound that publishes before the window closes is
    NOT shed — the barrier re-decides while it holds the door."""
    monkeypatch.setattr(local_sync, "_arm_survivor_exit",
                        lambda w=None: pytest.fail("must not shed"))
    fake = _FakeCluster(tmp_path, 0, 2, statuses={1: "running"})
    step = _tiny_local_step()
    drv = LocalSyncDriver(step, cluster=fake, h=1, stale=1,
                          grace=2.0, poll=0.02)
    own = step.island_mean_host(step.params)

    def late_publish():
        time.sleep(0.15)
        _peer_payload(tmp_path / "sync.p1.r1.npz", own)

    t = threading.Thread(target=late_publish)
    t.start()
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        drv.on_step(1)
    t.join()
    assert not fake.excused
    st = _instants(sink, "sync/staleness")[0]
    assert 0.1 <= st["waited_s"] < 1.5  # waited, but far short of grace


def test_victim_beats_shed_status_then_exits(tmp_path, monkeypatch):
    """The victim's side of the protocol: finding our own marker means
    publish heartbeat status ``shed`` as the LAST act (survivors hold
    their service-killing teardown until they see it), then exit 43
    into the supervisor."""
    codes = []

    def fake_exit(code):
        codes.append(code)
        raise RuntimeError("exited")

    monkeypatch.setattr(local_sync.os, "_exit", fake_exit)
    fake = _FakeCluster(tmp_path, 1, 2, statuses={0: "running"})
    drv = LocalSyncDriver(_tiny_local_step(), cluster=fake, h=4,
                          stale=1, grace=0.1)
    (tmp_path / "shed.p1.json").write_text(json.dumps(
        {"peer": 1, "by": 0, "round": 3, "lag": 1, "stale": 1}))
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        with pytest.raises(RuntimeError, match="exited"):
            drv.on_step(1)
    from bigdl_tpu.parallel.cluster import EXIT_PEER_LOST

    assert codes == [EXIT_PEER_LOST]
    assert fake.heartbeat.beats == [(1, "shed")]
    sheds = _instants(sink, "cluster/shed")
    assert len(sheds) == 1 and sheds[0]["role"] == "victim"
    assert sheds[0]["by"] == 0


def test_driver_grace_defaults_derive_from_heartbeat_interval():
    set_config(BigDLConfig(heartbeat_interval=3.0))
    drv = LocalSyncDriver(_tiny_local_step(), cluster=None)
    assert drv.grace == pytest.approx(6.0)
    set_config(BigDLConfig(heartbeat_interval=0.1,
                           local_sync_grace=0.25))
    assert LocalSyncDriver(_tiny_local_step(), cluster=None).grace \
        == pytest.approx(0.25)


# -- single-process cadence over a real mesh ---------------------------------
def test_single_process_rounds_collapse_islands():
    """H local steps, then the in-graph average: ``sync/average`` fires
    exactly at round boundaries (plus the finalize round), and after
    the final average every island holds the same parameters."""
    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    model = nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 4),
                          nn.LogSoftMax())
    step = TrainStep(model, nn.ClassNLLCriterion(),
                     optim.SGD(learning_rate=0.1), mesh=mesh,
                     parameter_sync="local")
    assert step.island_count() == 2
    drv = LocalSyncDriver(step, cluster=None, h=2, stale=1)
    rng = np.random.RandomState(0)
    x = rng.randn(8, 6).astype(np.float32)
    y = rng.randint(0, 4, 8)
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        for i in range(1, 6):
            loss = step.run(x, y, jax.random.key(i))
            assert np.isfinite(loss)
            drv.on_step(i)
        drv.finalize(5)
    avgs = _instants(sink, "sync/average")
    assert [e["step"] for e in avgs] == [2, 4, 5]
    assert all(e["islands"] == 2 and e["peers"] == 1 for e in avgs)
    assert all(e["waited_s"] == 0 for e in
               _instants(sink, "sync/staleness"))
    for k, v in step.params.items():
        rows = step._island_rows(v)
        np.testing.assert_allclose(
            rows[0], rows[1], rtol=1e-6, atol=1e-6,
            err_msg=f"islands of {k} did not collapse to their mean")


def test_metrics_sink_folds_local_sync_status():
    """The live surface: sync/average + sync/staleness + cluster/shed
    fold into /status.local_sync — the block tpu_watch prints as
    ``sync=local H=8 stale=1/3``."""
    from bigdl_tpu.telemetry.metrics_http import MetricsSink

    sink = MetricsSink()
    base = {"v": 1, "ts": 1.0, "pid": 1, "tid": 1, "kind": "event"}
    sink.emit({**base, "name": "sync/average", "round": 2, "step": 16,
               "h": 8, "bytes": 1024, "dur": 0.01, "peers": 2,
               "islands": 2})
    sink.emit({**base, "name": "sync/staleness", "round": 2,
               "waited_s": 0.4, "lag": 1, "stale": 3, "step": 16})
    sink.emit({**base, "name": "sync/staleness", "round": 3,
               "waited_s": 0.1, "lag": 0, "stale": 3, "step": 24})
    sink.emit({**base, "name": "cluster/shed", "peer": 1, "round": 3,
               "lag": 3, "stale": 3, "process_index": 0,
               "role": "survivor", "mode": "hard"})
    # the victim's own instant (and a duplicate verdict) never
    # double-counts the shed list
    sink.emit({**base, "name": "cluster/shed", "peer": 1, "round": 3,
               "lag": 3, "stale": 3, "process_index": 1,
               "role": "victim"})
    st = sink.status()["local_sync"]
    assert st["h"] == 8 and st["round"] == 2 and st["peers"] == 2
    assert st["islands"] == 2 and st["bytes"] == 1024
    assert st["lag"] == 0 and st["stale"] == 3  # latest verdict wins
    assert st["waited_s"] == pytest.approx(0.5)  # ...but waits sum
    assert st["shed"] == [1]


# -- compiled-program claims -------------------------------------------------
def _registry_pieces(batch=8):
    model = nn.Sequential(nn.Linear(6, 16), nn.Tanh(), nn.Linear(16, 4),
                          nn.LogSoftMax())
    rng = np.random.RandomState(0)
    x = rng.randn(batch, 6).astype(np.float32)
    y = rng.randint(0, 4, batch)
    return model, x, y


def test_local_step_has_zero_collectives_and_beats_sync_comms():
    """The tentpole's comms claim, off the EXACT compiled programs: the
    local-mode step contains no collective at all (island locality
    is structural under shard_map), and the one averaging program paid
    every H steps keeps the reduction at >= 0.8·H of the synchronous
    per-step all-reduce."""
    from bigdl_tpu.telemetry.comms import comms_facts

    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    crit = nn.ClassNLLCriterion()
    h = 8

    model, x, y = _registry_pieces()
    sync_step = TrainStep(model, crit, optim.SGD(learning_rate=0.1),
                          mesh=mesh, parameter_sync="allreduce")
    sync_bytes = comms_facts(
        sync_step.lower(x, y, jax.random.key(0)).compile(),
        mesh=mesh)["bytes"]
    assert sync_bytes > 0

    model2, _, _ = _registry_pieces()
    local_step = TrainStep(model2, crit, optim.SGD(learning_rate=0.1),
                           mesh=mesh, parameter_sync="local")
    lf = comms_facts(local_step.lower(x, y, jax.random.key(0)).compile(),
                     mesh=mesh)
    assert lf["count"] == 0 and lf["bytes"] == 0, lf
    local_step.average_islands()
    avg_bytes = comms_facts(local_step._avg_cache, mesh=mesh)["bytes"]
    assert avg_bytes > 0
    reduction = sync_bytes / (avg_bytes / h)
    assert reduction >= 0.8 * h, (sync_bytes, avg_bytes, reduction)


def test_sync_path_byte_identical_when_local_mode_off():
    """The do-no-harm acceptance: with ``parameter_sync != local`` the
    compiled program must be BYTE-IDENTICAL whether or not the
    local-SGD knobs are set — the mode leaves zero residue on the
    synchronous path."""
    from bigdl_tpu.telemetry.comms import comms_facts

    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])

    def compile_sync():
        model, x, y = _registry_pieces()
        step = TrainStep(model, nn.ClassNLLCriterion(),
                         optim.SGD(learning_rate=0.1), mesh=mesh,
                         parameter_sync="allreduce")
        return step.lower(x, y, jax.random.key(0)).compile()

    # both from ONE call site: the program text carries the source
    # lines of its callers, and two call sites differ in nothing else
    programs = []
    for knobs in (None, BigDLConfig(local_sync_h=4, local_sync_stale=1,
                                    local_sync_grace=0.25)):
        set_config(knobs)
        programs.append(compile_sync())
    plain, knobbed = programs
    a = comms_facts(plain, mesh=mesh)
    b = comms_facts(knobbed, mesh=mesh)
    assert (a["bytes"], a["count"]) == (b["bytes"], b["count"])
    assert plain.as_text() == knobbed.as_text()
