"""Straggler-tolerant local SGD: H local steps between parameter
averagings, a bounded-staleness barrier over the PR 7 heartbeat mesh,
and blame-driven SHEDDING of hosts that fall too far behind
(docs/fault_tolerance.md "Straggler tolerance").

``parameter_sync=local`` (train_step.py) gives every device along the
data axis its own parameter ISLAND: the compiled step is the inner
single-replica program vmapped over a leading island axis, so it
contains ZERO cross-island collectives and a dispatch never blocks on
a peer.  What synchronous data-parallel pays per step — one
all-reduce over every gradient byte — local SGD pays once per
``BIGDL_LOCAL_SYNC_H`` steps as a parameter average (DeepSpark, arXiv
1602.08191; post-local SGD, arXiv 1808.07217), an ≈ H× reduction in
comms bytes, which the comms walker measures
(``tests/test_local_sync.py`` holds it to >= 0.8 x H).

This module is the driver the Optimizer runs at iteration
boundaries.  Two layers:

* :class:`StalenessBarrier` — the pure decision core, fed a peer →
  latest-published-round table.  A peer whose lag is under the
  staleness bound S (``BIGDL_LOCAL_SYNC_STALE``) never delays anyone:
  survivors average whatever that peer last published (stale by < S
  rounds — the SSP contract, arXiv 1312.7651's bounded-staleness
  reading).  A peer AT the bound gets one grace window to catch up,
  then the survivors SHED it: emit ``cluster/shed``, write the
  ``shed.p<idx>.json`` marker, and excuse it from the watchdog + the
  commit barrier (parallel/cluster.py).  Unit tests drive this class
  with synthetic tables — no processes needed.

* :class:`LocalSyncDriver` — the filesystem transport.  Every H
  steps each process collapses its local islands in-graph
  (``TrainStep.average_islands``), publishes its island-mean as
  ``sync.p<idx>.r<round>.npz`` in the cluster dir, merges the latest
  contribution of every active peer host-side (weighted by island
  count), and loads the result back.  No jax collective carries the
  exchange, so membership can shrink mid-run without recompiling —
  the property that makes shedding safe.  A shed host finds its own
  marker at the next round boundary, publishes heartbeat status
  ``shed``, and exits :data:`~bigdl_tpu.parallel.cluster.EXIT_PEER_LOST`
  (43) into the supervisor, which treats survivor-completion as clean
  and relaunches degraded per ``--min-n`` otherwise.

The wall time survivors spend inside the grace window is charged to
``straggler`` badput by the goodput ledger (``sync/staleness``
``waited_s`` — telemetry/ledger.py), so "we waited on a slow host"
shows up in the same blame column whether the straggler guard or the
staleness barrier caught it.
"""

from __future__ import annotations

import io
import json
import logging
import os
import re
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bigdl_tpu.utils import file as File
from bigdl_tpu.utils.config import get_config

__all__ = ["StalenessBarrier", "BarrierDecision", "LocalSyncDriver"]

log = logging.getLogger("bigdl_tpu.local_sync")

_SYNC_RE = re.compile(r"^sync\.p(\d+)\.r(\d+)\.npz$")

#: heartbeat statuses that make a peer INACTIVE for the barrier — it
#: left (or is leaving) on purpose and must be neither waited for nor
#: shed.  ``failed`` is the watchdog's jurisdiction, not ours.
_INACTIVE = ("done", "preempted", "shed", "failed")


@dataclass
class BarrierDecision:
    """What the staleness bound says about one averaging round."""

    ready: bool                       #: no active peer is at the bound
    laggards: List[int] = field(default_factory=list)  #: peers at/over S
    max_lag: int = 0                  #: worst active-peer lag, rounds


class StalenessBarrier:
    """The pure bounded-staleness decision: given this process's
    averaging round and every peer's latest PUBLISHED round, which
    peers are within the bound (average with their latest
    contribution), and which are at it (wait one grace window, then
    shed)?  Stateless and filesystem-free — the unit tests feed it
    synthetic tables."""

    def __init__(self, process_index: int, process_count: int,
                 stale: int):
        if stale < 1:
            raise ValueError("staleness bound must be >= 1 round")
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        self.stale = int(stale)

    def decide(self, own_round: int,
               peer_rounds: Dict[int, int],
               statuses: Optional[Dict[int, str]] = None,
               excused: Any = ()) -> BarrierDecision:
        """``peer_rounds`` maps peer index → latest round it published
        (absent = 0: nothing yet).  Peers whose heartbeat status is in
        :data:`_INACTIVE`, and excused peers, are skipped entirely."""
        statuses = statuses or {}
        excused = set(excused)
        laggards: List[int] = []
        max_lag = 0
        for p in range(self.process_count):
            if p == self.process_index or p in excused:
                continue
            if statuses.get(p) in _INACTIVE:
                continue
            lag = own_round - int(peer_rounds.get(p, 0))
            max_lag = max(max_lag, lag)
            if lag >= self.stale:
                laggards.append(p)
        return BarrierDecision(ready=not laggards, laggards=laggards,
                               max_lag=max_lag)


class LocalSyncDriver:
    """Runs the local-SGD rounds for one training process: in-graph
    island averaging, the cross-process filesystem exchange, the
    bounded-staleness barrier, and both sides of the shed protocol."""

    def __init__(self, train_step, cluster=None,
                 h: Optional[int] = None, stale: Optional[int] = None,
                 grace: Optional[float] = None,
                 poll: float = 0.05):
        cfg = get_config()
        self.step = train_step
        self.cluster = cluster
        self.h = max(1, int(h if h is not None else cfg.local_sync_h))
        self.stale = max(1, int(stale if stale is not None
                                else cfg.local_sync_stale))
        #: how long survivors hold the door for a peer AT the bound
        #: before shedding it — the window the ledger charges to
        #: ``straggler`` badput.  BIGDL_LOCAL_SYNC_GRACE overrides;
        #: unset (0) derives from the heartbeat interval.
        if grace is None:
            grace = cfg.local_sync_grace or \
                max(2.0 * cfg.heartbeat_interval, 1.0)
        self.grace = float(grace)
        self.poll = float(poll)
        self.round = 0
        self._last_avg_step = 0
        self._excused: set = set()
        self._avg_bytes: Optional[int] = None
        if cluster is not None:
            self.barrier = StalenessBarrier(cluster.process_index,
                                            cluster.process_count,
                                            self.stale)
        else:
            self.barrier = None

    # -- driver entry points (Optimizer loop) --------------------------------
    def on_step(self, neval: int) -> None:
        """Called after every COMPLETED iteration ``neval``."""
        if self._multiproc():
            self._maybe_exit_shed(neval)
        if neval <= 0 or neval % self.h:
            return
        self._average(neval // self.h, neval)

    def finalize(self, neval: int) -> None:
        """One last averaging before the run's params become the
        model's: the result of local SGD is the island MEAN, not the
        island this process happened to train."""
        if self._multiproc():
            self._maybe_exit_shed(neval)
        if neval <= 0 or neval == self._last_avg_step:
            return
        # final rounds never wait and never shed: peers may legitimately
        # be finishing at different steps
        self._average(self.round + 1, neval, final=True)

    # -- the averaging round -------------------------------------------------
    def _multiproc(self) -> bool:
        return self.cluster is not None and self.cluster.process_count > 1

    def _average(self, rnd: int, neval: int, final: bool = False) -> None:
        from bigdl_tpu import telemetry

        t0 = time.perf_counter()
        self.round = rnd
        self._last_avg_step = neval
        waited, lag, peers = 0.0, 0, 1
        if self._multiproc():
            # the island axis spans processes here, so the jitted mean
            # would BE the blocking cross-process collective this
            # barrier exists to avoid: publish the host-side mean of
            # our addressable islands instead, and merge peers' files
            nbytes = self._publish(rnd)
            if not final:
                waited, lag = self._hold_the_door(rnd, neval)
            peers = self._merge_peers(rnd)
        else:
            # single process: collapse the islands in-graph (the
            # AOT-compiled mean the comms walker measures)
            self.step.average_islands()
            nbytes = self._in_graph_bytes()
        dur = time.perf_counter() - t0
        telemetry.instant("sync/average", round=rnd, step=neval,
                          h=self.h, bytes=nbytes, dur=dur, peers=peers,
                          islands=self.step.island_count())
        telemetry.instant("sync/staleness", round=rnd,
                          waited_s=round(waited, 6), lag=lag,
                          stale=self.stale, step=neval)

    def _in_graph_bytes(self) -> int:
        """Collective bytes of ONE in-graph averaging dispatch (0 on a
        single device) — measured once from the compiled program."""
        if self._avg_bytes is None:
            self._avg_bytes = 0
            try:
                from bigdl_tpu.telemetry import comms as _comms

                if self.step._avg_cache is not None:
                    facts = _comms.comms_facts(self.step._avg_cache,
                                               mesh=self.step.mesh)
                    self._avg_bytes = int(facts.get("bytes", 0))
            except Exception:  # noqa: BLE001 - telemetry never fails a round
                pass
        return self._avg_bytes

    # -- filesystem exchange -------------------------------------------------
    def _dir(self) -> str:
        return self.cluster.directory

    def _pidx(self) -> int:
        return self.cluster.process_index

    def _sync_path(self, p: int, rnd: int) -> str:
        return File.join(self._dir(), f"sync.p{p}.r{rnd}.npz")

    def _publish(self, rnd: int) -> int:
        """Write this process's island-mean contribution for ``rnd``
        (atomically, via the File layer) and prune rounds older than
        the staleness window.  Returns the bytes shipped."""
        payload = {"__islands__": np.asarray(self.step.island_count())}
        for name, arr in self.step.island_mean_host(
                self.step.params).items():
            payload[f"p::{name}"] = np.asarray(arr)
        for name, arr in self.step.island_mean_host(
                self.step.buffers).items():
            payload[f"b::{name}"] = np.asarray(arr)
        buf = io.BytesIO()
        np.savez(buf, **payload)
        blob = buf.getvalue()
        File.save(blob, self._sync_path(self._pidx(), rnd),
                  overwrite=True)
        self._prune(rnd)
        return len(blob)

    def _prune(self, rnd: int) -> None:
        cutoff = rnd - self.stale - 1
        try:
            for name in File.listdir(self._dir()):
                m = _SYNC_RE.match(name)
                if m and int(m.group(1)) == self._pidx() \
                        and int(m.group(2)) < cutoff:
                    File.remove(File.join(self._dir(), name))
        except OSError:
            pass

    def _scan_rounds(self) -> Dict[int, int]:
        """Peer → latest published round, from the sync files."""
        latest: Dict[int, int] = {}
        try:
            for name in File.listdir(self._dir()):
                m = _SYNC_RE.match(name)
                if m:
                    p, r = int(m.group(1)), int(m.group(2))
                    latest[p] = max(latest.get(p, 0), r)
        except OSError:
            pass
        return latest

    def _statuses(self) -> Dict[int, str]:
        table = self.cluster.monitor.peer_table()
        return {row["process_index"]: row.get("status", "?")
                for row in table.values()}

    # -- the bounded-staleness barrier + shed --------------------------------
    def _hold_the_door(self, rnd: int, neval: int) -> Tuple[float, int]:
        """Give peers AT the staleness bound one grace window to catch
        up; shed whoever is still at it when the window closes.
        Returns (seconds waited, worst active-peer lag) — the wait is
        what the ledger charges to ``straggler`` badput."""
        t0 = time.perf_counter()
        decision = self.barrier.decide(rnd, self._scan_rounds(),
                                       self._statuses(), self._excused)
        deadline = t0 + self.grace
        while decision.laggards and time.perf_counter() < deadline:
            time.sleep(self.poll)
            # keep our own heartbeat fresh while we hold the door — a
            # fast host waiting on a slow one must not LOOK wedged
            self.cluster.beat(neval)
            self._maybe_exit_shed(neval)
            decision = self.barrier.decide(rnd, self._scan_rounds(),
                                           self._statuses(),
                                           self._excused)
        for p in decision.laggards:
            self._shed(p, rnd, rnd - self._scan_rounds().get(p, 0))
        return time.perf_counter() - t0, decision.max_lag

    def _shed(self, peer: int, rnd: int, lag: int) -> None:
        """The survivors' verdict: peer ``peer`` fell S rounds behind
        and did not recover within the grace window.  Announce it,
        write the marker the victim (and the supervisor) will read,
        and excuse the peer from every barrier this process runs.

        Process 0 is special: it hosts the jax.distributed coordination
        service, so making it EXIT would fatally abort every survivor's
        runtime client mid-run.  A slow p0 is soft-shed instead — the
        survivors stop waiting for it (and stop merging its stale
        rounds), but it keeps running."""
        from bigdl_tpu import telemetry

        hard = peer != 0
        if hard:
            marker = File.join(self._dir(), f"shed.p{peer}.json")
            if not File.exists(marker):
                try:
                    File.save(json.dumps(
                        {"peer": peer, "by": self._pidx(), "round": rnd,
                         "lag": lag, "stale": self.stale,
                         "ts": time.time()}).encode(), marker,
                        overwrite=True)
                except OSError as e:
                    log.warning(
                        f"[LocalSync] shed marker write failed: {e}")
        self._excused.add(peer)
        self.cluster.excuse_peer(
            peer, f"shed at round {rnd} ({lag} rounds behind, "
                  f"bound {self.stale})")
        telemetry.instant("cluster/shed", peer=peer, round=rnd,
                          lag=lag, stale=self.stale,
                          process_index=self._pidx(), role="survivor",
                          mode="hard" if hard else "soft")
        # once a peer is gone it can never join jax.distributed's
        # shutdown barrier: our otherwise-clean exit would block on it
        # and the XLA client destructor turns the failed barrier into a
        # fatal abort.  Leave via os._exit instead, like the watchdog.
        _arm_survivor_exit(self._await_victims)
        log.warning(
            f"[LocalSync] SHED p{peer} at round {rnd}: {lag} averaging "
            f"rounds behind (bound {self.stale}); survivors continue "
            f"without it — the supervisor treats its exit as planned")

    def _maybe_exit_shed(self, neval: int) -> None:
        """The victim's side: the survivors voted us out.  Publish the
        ``shed`` heartbeat status (peers read the exit as planned, like
        done/preempted), flush telemetry, and exit 43 into the
        supervisor."""
        from bigdl_tpu import telemetry
        from bigdl_tpu.parallel.cluster import EXIT_PEER_LOST

        marker = _read_marker(File.join(
            self._dir(), f"shed.p{self._pidx()}.json"))
        if marker is None:
            return
        log.error(
            f"[LocalSync] this process (p{self._pidx()}) was SHED by "
            f"p{marker.get('by')} at round {marker.get('round')} "
            f"({marker.get('lag')} rounds behind, bound "
            f"{marker.get('stale')}); exiting {EXIT_PEER_LOST} — the "
            f"survivors finish without us")
        telemetry.instant("cluster/shed", peer=self._pidx(),
                          by=marker.get("by"), round=marker.get("round"),
                          lag=marker.get("lag"), stale=self.stale,
                          process_index=self._pidx(), role="victim")
        try:
            telemetry.end_run()
        except Exception:  # noqa: BLE001 - dying process
            pass
        # the ``shed`` status is the LAST act before the exit: the
        # survivors hold their own (service-killing) teardown until
        # they see it, so it must mean "os._exit is imminent", not
        # "still flushing telemetry"
        try:
            self.cluster.heartbeat.beat(neval, status="shed")
        except Exception:  # noqa: BLE001
            pass
        os._exit(EXIT_PEER_LOST)

    def _await_victims(self, timeout: float = 30.0) -> None:
        """Exit-time courtesy from the survivor: hold our own teardown
        until every hard-shed victim has published heartbeat status
        ``shed`` (meaning its own ``os._exit`` is imminent).  If this
        process hosts the coordination service (p0 usually does),
        exiting first would fatally abort a victim that is still
        draining its last slow iteration — turning its clean 43 into a
        SIGABRT casualty the supervisor would relaunch over."""
        deadline = time.time() + timeout
        victims = [p for p in sorted(self._excused)
                   if File.exists(File.join(self._dir(),
                                            f"shed.p{p}.json"))]
        while victims and time.time() < deadline:
            for p in list(victims):
                hb = _read_marker(File.join(self._dir(),
                                            f"heartbeat.p{p}.json"))
                if hb is not None and hb.get("status") == "shed":
                    victims.remove(p)
            if victims:
                time.sleep(0.1)
        if victims:
            log.warning(f"[LocalSync] shed peer(s) {victims} never "
                        f"confirmed exit within {timeout:.0f}s — "
                        f"tearing down anyway")

    # -- merging peer contributions ------------------------------------------
    def _merge_peers(self, rnd: int) -> int:
        """Average this process's island mean with the LATEST
        contribution of every active peer (weighted by island count;
        a peer's contribution may be stale by up to S rounds — the
        bounded-staleness contract) and load the result back into the
        stacked device state.  Returns how many processes the merge
        folded."""
        statuses = self._statuses()
        latest = self._scan_rounds()
        contribs: List[Tuple[float, Dict[str, np.ndarray],
                             Dict[str, np.ndarray]]] = []
        own_params = self.step.island_mean_host(self.step.params)
        own_buffers = self.step.island_mean_host(self.step.buffers)
        contribs.append((float(self.step.island_count()),
                         own_params, own_buffers))
        for p in range(self.cluster.process_count):
            if p == self._pidx() or p in self._excused:
                continue
            if statuses.get(p) == "shed":
                continue
            r = latest.get(p, 0)
            if r <= 0 or r < rnd - self.stale:
                continue  # nothing published, or beyond the bound
            loaded = self._load(p, r)
            if loaded is not None:
                contribs.append(loaded)
        # ALWAYS load the fold back: even with no peer contribution the
        # local islands must still collapse to their mean (the in-graph
        # average never ran on the multi-process path)
        params, buffers = _weighted_mean(contribs)
        self.step.load_island_state(params, buffers)
        return len(contribs)

    def _load(self, p: int, rnd: int) -> Optional[
            Tuple[float, Dict[str, np.ndarray], Dict[str, np.ndarray]]]:
        try:
            blob = File.load(self._sync_path(p, rnd))
            with np.load(io.BytesIO(blob), allow_pickle=False) as z:
                count = float(z["__islands__"]) if "__islands__" in z \
                    else 1.0
                params = {k[len("p::"):]: z[k] for k in z.files
                          if k.startswith("p::")}
                buffers = {k[len("b::"):]: z[k] for k in z.files
                           if k.startswith("b::")}
            return count, params, buffers
        except (OSError, ValueError, KeyError) as e:
            log.warning(f"[LocalSync] could not read p{p} round {rnd} "
                        f"contribution: {e}")
            return None


def _weighted_mean(contribs) -> Tuple[Dict[str, np.ndarray],
                                      Dict[str, np.ndarray]]:
    """Island-count-weighted mean of the float leaves; non-float
    leaves (step counters, integer buffers) keep this process's own
    value.  A peer missing a key (or shipping a different shape —
    mid-upgrade mixed fleets) simply doesn't contribute to it."""
    _, own_params, own_buffers = contribs[0]

    def fold(own: Dict[str, np.ndarray], which: int) -> Dict[str, np.ndarray]:
        out: Dict[str, np.ndarray] = {}
        for name, arr in own.items():
            arr = np.asarray(arr)
            if not np.issubdtype(arr.dtype, np.floating):
                out[name] = arr
                continue
            acc = np.zeros(arr.shape, dtype=np.float64)
            weight = 0.0
            for contrib in contribs:
                count, tree = contrib[0], contrib[which]
                peer = tree.get(name)
                if peer is None or np.shape(peer) != arr.shape:
                    continue
                acc += count * np.asarray(peer, dtype=np.float64)
                weight += count
            out[name] = (acc / max(weight, 1e-12)).astype(arr.dtype)
        return out

    return fold(own_params, 1), fold(own_buffers, 2)


def _read_marker(path: str) -> Optional[Dict[str, Any]]:
    try:
        return json.loads(File.load(path).decode())
    except (OSError, ValueError):
        return None


_survivor_exit_armed = False


def _arm_survivor_exit(waiter=None) -> None:
    """After shedding a peer, this process can no longer tear down
    jax.distributed cleanly: the dead peer never joins the shutdown
    barrier, and the XLA client destructor escalates the failed barrier
    into a fatal abort (SIGABRT) ~100 s after an otherwise-successful
    exit.  So the survivor leaves the way the cluster watchdog does —
    ``os._exit`` at interpreter exit, skipping the C++ teardown.
    ``waiter`` runs first (the hold-for-victims courtesy).  An
    excepthook keeps a crashed survivor reporting failure instead of
    being laundered into exit 0."""
    global _survivor_exit_armed
    if _survivor_exit_armed:
        return
    _survivor_exit_armed = True
    import atexit
    import sys

    state = {"code": 0}
    prev_hook = sys.excepthook

    def hook(tp, val, tb):
        state["code"] = 1
        prev_hook(tp, val, tb)

    sys.excepthook = hook

    def bail():
        if waiter is not None:
            try:
                waiter()
            except Exception:  # noqa: BLE001 - exiting regardless
                pass
        try:
            from bigdl_tpu import telemetry
            telemetry.end_run()
        except Exception:  # noqa: BLE001
            pass
        try:
            sys.stdout.flush()
            sys.stderr.flush()
        except Exception:  # noqa: BLE001
            pass
        os._exit(state["code"])

    atexit.register(bail)
