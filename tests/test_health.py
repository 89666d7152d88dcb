"""Training-health monitor + live metrics export + run-regression diff
(ISSUE 3): seeded-divergence runs must produce ``health/*`` events, trip
the policy (warn / skip-step / halt with ``HealthError``), and leave a
schema-valid run log; the OpenMetrics endpoint must serve parseable text
during a live run; ``telemetry diff`` must flag a slowed run and exit
nonzero."""

import glob
import json
import os
import re
import urllib.request

import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu import telemetry
from bigdl_tpu.dataset.minibatch import MiniBatch
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.dataset.transformer import Transformer
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.telemetry import schema
from bigdl_tpu.telemetry.health import (HealthError, HealthPolicy,
                                        LossEwma, probe_stats)
from bigdl_tpu.utils.config import BigDLConfig, set_config


def teardown_function(_fn):
    telemetry.end_run()
    set_config(None)


def _samples(n=64, dim=4, seed=0):
    rng = np.random.default_rng(seed)
    return [Sample(rng.normal(size=dim).astype(np.float32),
                   np.int64(i % 2)) for i in range(n)]


def _mlp(dim=4):
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(7)
    return nn.Sequential(nn.Linear(dim, 8), nn.Tanh(), nn.Linear(8, 2),
                         nn.LogSoftMax())


class PoisonAt(Transformer):
    """Replace every batch input with NaN from batch index ``at`` on —
    the seeded divergence (a corrupt shard, a bad augmentation)."""

    def __init__(self, at: int):
        self.at = at

    def apply(self, it):
        for i, batch in enumerate(it):
            if i >= self.at:
                batch = MiniBatch(
                    [np.full_like(a, np.nan) for a in batch.inputs],
                    list(batch.targets) or None)
            yield batch


def _poisoned_optimizer(at=2, iters=20, **policy_kw):
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch

    ds = DataSet.array(_samples()).transform(
        SampleToMiniBatch(16)).transform(PoisonAt(at))
    o = optim.LocalOptimizer(_mlp(), ds, nn.ClassNLLCriterion(),
                             batch_size=16,
                             end_trigger=Trigger.max_iteration(iters))
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    if policy_kw:
        o.set_health_policy(HealthPolicy(**policy_kw))
    return o


# -- probe + policy units ----------------------------------------------------
def test_probe_stats_decodes_vector():
    stats = probe_stats([3.0, 4.0, 2.0, 0.0, 0.0], 0.5)
    assert stats["grad_norm"] == 3.0
    assert stats["update_ratio"] == pytest.approx(0.5)
    assert stats["nonfinite_grads"] == 0 and stats["loss"] == 0.5
    bad = probe_stats([float("nan"), 1.0, 0.0, 5.0, 2.0], float("nan"))
    assert bad["nonfinite_grads"] == 5 and bad["nonfinite_params"] == 2


def test_loss_ewma_detects_spike_not_noise():
    det = LossEwma(alpha=0.1, spike_factor=4.0, warmup=5)
    rng = np.random.default_rng(0)
    for i in range(30):  # gentle noise: no findings
        assert det.update(i, 1.0 + 0.01 * rng.normal()) == []
    findings = det.update(30, 50.0)
    assert [n for n, _ in findings] == ["health/loss_spike"]
    assert findings[0][1]["step"] == 30
    # nonfinite losses bypass the EWMA entirely
    assert det.update(31, float("nan")) == []


def test_loss_ewma_detects_plateau_once():
    det = LossEwma(alpha=0.5, warmup=2, plateau_patience=4,
                   plateau_rtol=1e-3)
    names = []
    for i in range(20):
        names += [n for n, _ in det.update(i, 1.0)]
    assert names.count("health/plateau") == 1  # once per plateau


def test_policy_escalation_and_halt_trigger():
    pol = HealthPolicy(on_nonfinite="halt", halt_after=2)
    finite = probe_stats([1.0, 1.0, 0.1, 0, 0], 0.5)
    nonfinite = probe_stats([float("inf"), 1.0, 0.1, 3, 0], float("nan"))
    assert pol.observe(1, finite)[0] == "ok"
    action, findings = pol.observe(2, nonfinite)
    assert action == "warn"
    assert any(n == "health/nonfinite" for n, _ in findings)
    action, findings = pol.observe(3, nonfinite)
    assert action == "halt"
    assert any(n == "health/halt" for n, _ in findings)
    # a finite step resets the consecutive counter
    pol2 = HealthPolicy(on_nonfinite="halt", halt_after=2)
    pol2.observe(1, nonfinite)
    pol2.observe(2, finite)
    assert pol2.observe(3, nonfinite)[0] == "warn"
    # custom Trigger-style predicate: halt on TOTAL nonfinite steps
    pol3 = HealthPolicy(
        on_nonfinite="warn",
        halt_when=Trigger(lambda s: s["nonfinite_steps"] >= 2))
    pol3.observe(1, nonfinite)
    assert pol3.observe(5, nonfinite)[0] == "halt"


def test_policy_rejects_bad_config():
    with pytest.raises(ValueError, match="on_nonfinite"):
        HealthPolicy(on_nonfinite="explode")
    with pytest.raises(ValueError, match="halt_after"):
        HealthPolicy(halt_after=0)


def test_user_policy_state_is_fresh_per_run_attempt():
    """A user-installed policy is config; its running counters/EWMA must
    start pristine on every run attempt (checkpoint-restore retries,
    repeated optimize() calls) — and the user's object is never
    mutated."""
    pol = HealthPolicy(on_nonfinite="halt", halt_after=2)
    for _ in range(2):  # second optimize() halts at the same step
        o = _poisoned_optimizer(at=0, iters=10)
        o.set_health_policy(pol)
        with pytest.raises(HealthError) as exc:
            o.optimize()
        assert exc.value.step == 2
    assert pol.state["consecutive_nonfinite"] == 0
    assert pol.state["nonfinite_steps"] == 0


def test_invalid_health_env_fails_fast_not_retried():
    """A BIGDL_HEALTH typo is a config error: it must raise before the
    checkpoint-restore retry loop, not burn the retry budget on it."""
    import time as _time

    set_config(BigDLConfig(health_action="hal",  # typo
                           failure_retry_times=5,
                           failure_retry_interval=60.0))
    o = _poisoned_optimizer(at=100, iters=1)
    t0 = _time.perf_counter()
    with pytest.raises(ValueError, match="on_nonfinite"):
        o.optimize()
    assert _time.perf_counter() - t0 < 5.0  # no retries, no training


# -- seeded divergence end-to-end --------------------------------------------
def test_nan_run_halts_with_health_error_and_valid_log(tmp_path):
    """The acceptance path: a run that NaNs at a known step must emit
    ``health/nonfinite`` events, halt with HealthError carrying the
    evidence, never burn the retry budget, and leave a schema-valid
    run log."""
    tele_dir = str(tmp_path / "tele")
    set_config(BigDLConfig(telemetry_dir=tele_dir, health_action="halt",
                           health_halt_after=2, failure_retry_times=3,
                           failure_retry_interval=60.0))
    o = _poisoned_optimizer(at=2)  # first NaN batch -> step 3
    with pytest.raises(HealthError) as exc:
        o.optimize()
    err = exc.value
    assert err.step == 4  # halt_after=2 consecutive nonfinite steps
    assert err.evidence["nonfinite_grads"] > 0
    assert err.evidence["consecutive_nonfinite"] == 2
    assert not telemetry.enabled(), "owned run must end on halt"

    runs = glob.glob(os.path.join(tele_dir, "run-*.jsonl"))
    assert len(runs) == 1, "halt must not be retried (one run, one log)"
    n, errors = schema.validate_run(runs[0])
    assert errors == [] and n > 10
    events, _ = schema.read_events(runs[0])
    probes = [e for e in events if e["kind"] == "health"]
    assert len(probes) == 4 and probes[0]["step"] == 1
    assert all(k in probes[0] for k in
               ("grad_norm", "update_ratio", "nonfinite_grads"))
    names = [e["name"] for e in events if e["kind"] == "event"]
    assert names.count("health/nonfinite") == 2
    assert names.count("health/halt") == 1
    assert "run/retry" not in names, "HealthError must bypass the retry loop"


def test_skip_policy_keeps_params_finite_and_completes():
    from bigdl_tpu.nn.module import state_dict

    sink = telemetry.MemorySink()
    o = _poisoned_optimizer(at=3, iters=8, on_nonfinite="skip",
                            halt_after=100)
    with telemetry.run(sinks=[sink]):
        model = o.optimize()  # completes: poisoned updates never land
    for k, v in state_dict(model).items():
        assert np.isfinite(np.asarray(v)).all(), k
    names = [e["name"] for e in sink.events if e["kind"] == "event"]
    assert names.count("health/skip") == 5  # steps 4..8 all skipped
    assert schema.validate_events(sink.events) == []


def test_warn_policy_never_halts():
    o = _poisoned_optimizer(at=2, iters=6, on_nonfinite="warn")
    o.optimize()  # diverged, warned, completed


def test_health_off_disables_probes():
    set_config(BigDLConfig(health_action="off"))
    o = _poisoned_optimizer(at=2, iters=4)
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        o.optimize()
    assert not [e for e in sink.events if e["kind"] == "health"]


def test_health_scalars_reach_train_summary(tmp_path):
    from bigdl_tpu.visualization import TrainSummary

    ts = TrainSummary(str(tmp_path), "app")
    o = _poisoned_optimizer(at=100, iters=4, on_nonfinite="warn")
    o.set_train_summary(ts)
    o.optimize()
    rows = ts.read_scalar("health/grad_norm")
    assert [int(r[0]) for r in rows] == [1, 2, 3, 4]
    ts.close()


# -- live metrics endpoint ---------------------------------------------------
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (NaN|[+-]Inf|[-+0-9.eE]+)$")


def test_metrics_endpoint_serves_openmetrics_during_run():
    set_config(BigDLConfig(metrics_port=0))
    sink = telemetry.MemorySink()
    with telemetry.run(sinks=[sink]):
        server = telemetry.metrics_server()
        assert server is not None and server.port > 0
        telemetry.emit("step", step=3, dur=0.01, loss=0.5, records=16,
                       throughput=1600.0, epoch=1)
        telemetry.emit("health", step=3, grad_norm=1.5, param_norm=2.0,
                       update_norm=0.1, update_ratio=0.05,
                       nonfinite_grads=0, nonfinite_params=0, loss=0.5)
        telemetry.counter("records", 16)
        telemetry.gauge("prefetch/queue_depth", 2)
        base = f"http://127.0.0.1:{server.port}"
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=5).read().decode()
        lines = [ln for ln in text.splitlines() if ln]
        assert lines[-1] == "# EOF"
        samples = [ln for ln in lines if not ln.startswith("#")]
        assert samples, text
        for ln in samples:  # every sample line is exposition-parseable
            assert _SAMPLE_RE.match(ln), ln
        by_name = {ln.split("{")[0]: ln for ln in samples}
        assert 'process_index="0"' in by_name["bigdl_step"]
        assert by_name["bigdl_step"].endswith(" 3")
        assert by_name["bigdl_loss"].endswith(" 0.5")
        assert "bigdl_health_grad_norm" in by_name
        assert "bigdl_prefetch_queue_depth" in by_name
        assert by_name["bigdl_records_total"].endswith(" 16")

        status = json.loads(urllib.request.urlopen(
            base + "/status", timeout=5).read())
        assert status["step"]["step"] == 3
        assert status["health"]["grad_norm"] == 1.5
        ok = json.loads(urllib.request.urlopen(
            base + "/healthz", timeout=5).read())
        assert ok == {"ok": True}
        assert urllib.request.urlopen(base + "/metrics",
                                      timeout=5).status == 200
    # run ended -> endpoint torn down
    assert telemetry.metrics_server() is None
    with pytest.raises(Exception):
        urllib.request.urlopen(f"http://127.0.0.1:{server.port}/healthz",
                               timeout=1)


def test_metrics_endpoint_off_by_default():
    with telemetry.run(sinks=[telemetry.MemorySink()]):
        assert telemetry.metrics_server() is None


# -- cli end-to-end (acceptance shape) ---------------------------------------
def test_cli_train_divergence_halts_with_metrics_port(tmp_path,
                                                      monkeypatch,
                                                      capsys):
    """``cli train lenet --telemetry <dir> --metrics-port 0`` on a
    diverging run (lr so large the first update overflows float32)
    halts with HealthError and leaves a schema-valid log containing
    health events."""
    from bigdl_tpu.models import cli as models_cli

    tele_dir = str(tmp_path / "tele")
    monkeypatch.setenv("BIGDL_HEALTH_HALT_AFTER", "2")
    # the cli writes --telemetry/--metrics-port into os.environ; seed
    # them via monkeypatch so the mutation is UNDONE after this test
    monkeypatch.setenv("BIGDL_TELEMETRY", tele_dir)
    monkeypatch.setenv("BIGDL_METRICS_PORT", "0")
    with pytest.raises(HealthError) as exc:
        models_cli.main(["train", "--model", "lenet", "-b", "256",
                         "--max-epoch", "1", "--learning-rate", "1e40",
                         "--telemetry", tele_dir, "--metrics-port", "0"])
    capsys.readouterr()
    assert exc.value.evidence["nonfinite_params"] > 0
    runs = glob.glob(os.path.join(tele_dir, "run-*.jsonl"))
    assert len(runs) == 1
    n, errors = schema.validate_run(runs[0])
    assert errors == [], errors[:5]
    events, _ = schema.read_events(runs[0])
    names = [e["name"] for e in events if e["kind"] == "event"]
    assert "health/halt" in names
    assert any(e["kind"] == "health" for e in events)
    # the endpoint came up on an ephemeral port and announced itself
    serving = [e for e in events if e.get("name") == "metrics/serving"]
    assert serving and serving[0]["port"] > 0


# -- regression diff ---------------------------------------------------------
def _write_run(path, dur, steps=10, pidx=0, health_events=0):
    with telemetry.run(str(path), meta={"process_index": pidx}):
        tr = telemetry.get()
        for i in range(1, steps + 1):
            sid = tr.begin("train/iteration", step=i)
            tr.emit("step", step=i, dur=dur, loss=1.0 / i, records=16,
                    throughput=16.0 / dur)
            tr.end(sid)
        for _ in range(health_events):
            telemetry.instant("health/nonfinite", step=1)


def test_diff_flags_slowed_run_nonzero(tmp_path, capsys):
    from bigdl_tpu.telemetry import __main__ as cli

    fast, slow = tmp_path / "fast.jsonl", tmp_path / "slow.jsonl"
    _write_run(fast, 0.010)
    _write_run(slow, 0.016)
    rc = cli.main(["diff", str(fast), str(slow)])
    out = capsys.readouterr().out
    assert rc == 1, out
    assert "REGRESSED" in out and "step_p50_s" in out
    # same run against itself: clean
    assert cli.main(["diff", str(fast), str(fast)]) == 0
    # improvements never flag
    assert cli.main(["diff", str(slow), str(fast)]) == 0
    # fresh health events are a regression regardless of speed
    sick = tmp_path / "sick.jsonl"
    _write_run(sick, 0.010, health_events=2)
    assert cli.main(["diff", str(fast), str(sick)]) == 1
    out = capsys.readouterr().out
    assert "health_events" in out


def test_diff_threshold_and_json(tmp_path, capsys):
    from bigdl_tpu.telemetry import __main__ as cli

    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_run(a, 0.010)
    _write_run(b, 0.011)  # +10%: inside a 25% threshold
    assert cli.main(["diff", str(a), str(b),
                     "--threshold-pct", "25"]) == 0
    capsys.readouterr()  # drop the table view
    rc = cli.main(["diff", str(a), str(b), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert {"a", "b", "rows"} <= set(doc)
    assert any(r["name"] == "step_p50_s" for r in doc["rows"])
    assert rc in (0, 1)
    # machine-readable verdict (CI consumes the payload, not the table):
    # the verdict/exit code travel IN the JSON and agree with the rc
    assert doc["verdict"] == ("regressed" if rc == 1 else "ok")
    assert doc["exit_code"] == rc
    assert doc["regressions"] == sum(r["regressed"] for r in doc["rows"])
    assert doc["compared"] == len(doc["rows"])
    assert doc["threshold_pct"] == 10.0 and doc["count_slack"] == 0


def test_diff_json_verdict_covers_all_exit_codes(tmp_path, capsys):
    from bigdl_tpu.telemetry import __main__ as cli

    fast, slow = tmp_path / "fast.jsonl", tmp_path / "slow.jsonl"
    _write_run(fast, 0.010)
    _write_run(slow, 0.016)
    assert cli.main(["diff", str(fast), str(slow), "--json"]) == 1
    assert json.loads(capsys.readouterr().out)["verdict"] == "regressed"
    assert cli.main(["diff", str(fast), str(fast), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["verdict"] == "ok"
    bare = tmp_path / "bare.json"  # bench doc with no metrics at all
    bare.write_text("{}")
    assert cli.main(["diff", str(fast), str(bare), "--json"]) == 2
    out = capsys.readouterr().out
    assert json.loads(out)["verdict"] == "not_comparable"


def test_diff_zero_baseline_still_regresses():
    """0 -> worse is an infinite pct change: it must flag, not slip
    through the pct threshold as 'no delta_pct computable'."""
    from bigdl_tpu.telemetry.diff import diff_metrics

    rows = diff_metrics({"data_wait_share": 0.0},
                        {"data_wait_share": 0.5})
    assert rows[0]["regressed"]
    rows = diff_metrics({"data_wait_share": 0.0},
                        {"data_wait_share": 0.0})
    assert not rows[0]["regressed"]


def test_diff_bench_json_and_missing_file(tmp_path, capsys):
    from bigdl_tpu.telemetry import __main__ as cli

    base = tmp_path / "base.json"
    cand = tmp_path / "cand.json"
    base.write_text(json.dumps({"metric": "m", "configs": {
        "lenet_mnist": {"images_per_sec": 1000.0, "mfu": 0.5}}}))
    cand.write_text(json.dumps({"metric": "m", "configs": {
        "lenet_mnist": {"images_per_sec": 850.0, "mfu": 0.42},
        "broken": {"error": "X"}}}))
    assert cli.main(["diff", str(base), str(cand)]) == 1
    assert "lenet_mnist.images_per_sec" in capsys.readouterr().out
    assert cli.main(["diff", str(base), str(tmp_path / "nope.json")]) == 2


# -- fleet view --------------------------------------------------------------
def test_fleet_view_reports_skew_and_lag(tmp_path, capsys):
    from bigdl_tpu.telemetry import __main__ as cli
    from bigdl_tpu.telemetry.report import fleet_summarize

    p0, p1 = tmp_path / "p0.jsonl", tmp_path / "p1.jsonl"
    _write_run(p0, 0.010, steps=10, pidx=0)
    _write_run(p1, 0.010, steps=8, pidx=1)
    loaded = [(str(p), schema.read_events(str(p))[0]) for p in (p0, p1)]
    fleet = fleet_summarize(loaded)
    assert fleet["step_lag"] == 2
    assert {p["process_index"] for p in fleet["processes"]} == {0, 1}
    assert fleet["skew"]["at_step"] is not None
    rc = cli.main([str(p0), str(p1)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "fleet view (2 processes)" in out
    assert "step lag" in out and "step skew" in out
    # --validate accepts multiple logs too
    assert cli.main([str(p0), str(p1), "--validate"]) == 0


def test_fleet_duplicate_process_index_merges_latest_incarnation(
        tmp_path, capsys):
    """Two logs claiming one process_index are what a supervisor
    restart produces (two incarnations of the same rank): the fleet
    view must keep the LATEST run per rank instead of double-counting
    skew across incarnations — the superseded log is reported, never
    silently dropped."""
    import time as _time

    from bigdl_tpu.telemetry import __main__ as cli
    from bigdl_tpu.telemetry.report import fleet_summarize

    paths = [tmp_path / n for n in ("old_p0.jsonl", "new_p0.jsonl",
                                    "p1.jsonl")]
    _write_run(paths[0], 0.010, steps=3, pidx=0)  # dead incarnation
    _time.sleep(0.05)  # run_start ts orders the incarnations
    _write_run(paths[1], 0.010, steps=5, pidx=0)
    _write_run(paths[2], 0.010, steps=5, pidx=1)
    loaded = [(str(p), schema.read_events(str(p))[0]) for p in paths]
    fleet = fleet_summarize(loaded)
    # one row per RANK, and rank 0's row is the newest incarnation
    assert len(fleet["processes"]) == 2
    by_pidx = {p["process_index"]: p for p in fleet["processes"]}
    assert by_pidx[0]["path"].endswith("new_p0.jsonl")
    assert by_pidx[0]["last_step"] == 5
    assert fleet["step_lag"] == 0  # the dead incarnation's 3 steps
    # don't fake a lag
    assert fleet["superseded"] == [str(paths[0])]
    assert fleet["notes"] and "kept latest" in fleet["notes"][0]
    assert cli.main([str(p) for p in paths]) == 0
    out = capsys.readouterr().out
    assert "note:" in out and "superseded" in out
    assert "WARNING" not in out


def test_schema_accepts_health_kind():
    base = {"v": 1, "ts": 1.0, "pid": 1, "tid": 1}
    assert not schema.validate_event(
        {**base, "kind": "health", "step": 3, "grad_norm": 1.0})
    assert schema.validate_event({**base, "kind": "health"})  # no step
