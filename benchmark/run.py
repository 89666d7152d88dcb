#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it makes the records and the weights from ``--seed``, builds
the program's model and Optimizer as ``cli train`` does, lets ONE
``optimize()`` call drive the compiled step through the three steps that
``correct`` is decided on, through the warm-up and through the measured
window, then follows the same three steps with the plain reference and
prints the contract's result line last.

Everything that belongs to one cell, configuration or per-layer metric is
found by its name in ``BENCHMARK.json`` and read from a file of its own
(``workloads/``, ``configs/``, ``layer_metrics/``, ``readers/``,
``models/``, ``optimizers/``); adding one edits no file that is here.
What a record is comes from the configuration's family, and what the
update rule is from the recipe it names: this file holds neither.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # before any heavy import: set-up counts them

import argparse
import gc
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (compare, models, optimizers, reference,  # noqa: E402
                       stats, trace as tracelib)


def read_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_cell(name: str) -> Dict:
    """The cell's data, found by name: its entry in ``BENCHMARK.json``,
    its workload and configuration files, and the metrics it reports."""
    bench = read_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name,
        "chips": entry["chips"],
        "workload": read_json(HERE, "workloads", name + ".json"),
        "config": read_json(ROOT, conf["file"]),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def setup_compile_cache() -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at the checkout's fixed ``.jax_cache`` (which is also where the
    program would put it): the path is part of the cache's key."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def find_devices(chips: int):
    """The accelerator the cell asks for, or no result at all."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no accelerator: JAX reports "
                         f"{devices[0].platform!r}; a time taken there "
                         f"would mean nothing")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices, log) -> int:
    """The peak on the fullest chip.  On the TPU ``peak_bytes_in_use``
    counts live buffers only; what compiled programs hold for their
    temporaries is ``peak_bytes_reserved``, apart from it (the free block
    the runtime reports is the limit less both)."""
    stats = [d.memory_stats() or {} for d in devices]
    log(f"memory: {json.dumps(stats[0])}")
    return max(m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0)
               for m in stats)


class CompileCounter:
    """Counts traces and backend compilations through ``jax.monitoring``;
    the window must see none."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax.monitoring

        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        kind = self.EVENTS.get(event)
        if kind:
            self.counts[kind] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


class Hooks:
    """The two places the Optimizer loop calls back into the harness:
    ``end`` (its end trigger, at every loop top) and ``add_scalar`` (its
    train summary, after ``float(loss)``).  They stamp the harness's own
    clock, read the Optimizer's per-stage Metrics, keep the state the
    comparison needs from steps 1 and 3, open and close the window."""

    STAGES = ("data time", "dispatch time")

    def __init__(self, optimizer, first_gradient, param_keys: List[str],
                 seconds: float, check_steps: int, warmup_steps: int,
                 compiles: CompileCounter, trace_dir: Optional[str]):
        self.o = optimizer
        self.first_gradient = first_gradient  # of opt_state, by the recipe
        self.param_keys = param_keys      # the model's own order of leaves
        self.seconds = seconds
        self.check_steps = check_steps
        self.warmup_steps = max(warmup_steps, check_steps)
        self.compiles = compiles
        self.trace_dir = trace_dir
        self.tops: List[float] = []       # loop-top stamp of step i+1
        self.ends: List[float] = []       # loss-on-host stamp of step i+1
        self.losses: List[float] = []
        self.stage_totals: List[Dict[str, float]] = []
        self.grad1 = None                 # first gradient, from step 1's state
        self.params_after_check = None
        self.t_open = self.t_close = None
        self.open_step = None             # steps completed at t_open
        self.compiles_at_open = None
        self.compiles_at_close = None
        self.stamp_perf = None

    # -- the end trigger: loop top -----------------------------------------
    def end(self, state) -> bool:
        done = state.get("neval", 0)
        now = time.perf_counter()
        if self.t_open is None and done >= self.warmup_steps:
            if self.trace_dir:
                self._start_trace()
            now = self.t_open = time.perf_counter()
            self.open_step = done
            self.compiles_at_open = self.compiles.snapshot()
        elif self.t_open is not None and now - self.t_open >= self.seconds:
            self.t_close = now
            self.compiles_at_close = self.compiles.snapshot()
            if self.trace_dir:
                import jax

                jax.profiler.stop_trace()
            return True
        if len(self.tops) == done:
            self.tops.append(now)
        return False

    def _start_trace(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(tracelib.STAMP):
            self.stamp_perf = time.perf_counter()

    # -- the train summary: step end ----------------------------------------
    def add_scalar(self, tag: str, value, step: int):
        if tag != "Loss":
            return
        self.ends.append(time.perf_counter())
        self.losses.append(float(value))
        m = self.o.metrics
        self.stage_totals.append({s: m.total(s) for s in self.STAGES})
        if step == 1:
            self.grad1 = self._host(
                self.first_gradient(self._step().opt_state))
        if step == self.check_steps:
            self.params_after_check = self._host(self._step().params)

    def _step(self):
        return self.o.last_train_step

    def _host(self, tree: Dict):
        import numpy as np

        return [np.asarray(tree[k]) for k in self.param_keys]


def build_optimizer(cell: Dict, family, recipe, model, samples, devices):
    """The ``cli train`` recipe: registry model, ``Sample`` records, the
    configuration's update rule and compute type over f32 master
    weights; the class and the sync mode are the cell's."""
    import jax.numpy as jnp

    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch, Transformer

    w, conf = cell["workload"], cell["config"]

    class Tap(Transformer):
        """Passes every record through and notes which ones the first
        batches were made of: the harness observing its own input."""

        def __init__(self, index_of, keep):
            self.index_of, self.keep, self.seen = index_of, keep, []

        def apply(self, it):
            for s in it:
                if len(self.seen) < self.keep:
                    self.seen.append(self.index_of[id(s)])
                yield s

    tap = Tap({id(s): i for i, s in enumerate(samples)},
              w["check_steps"] * w["batch"])
    dataset = DataSet.array(samples).transform(tap).transform(
        SampleToMiniBatch(w["batch"]))
    kw = {}
    if w["optimizer"] == "DistriOptimizer":
        from bigdl_tpu.utils.engine import Engine

        Engine.init(devices=devices)
        kw["mesh"] = Engine.mesh
    o = getattr(optim, w["optimizer"])(
        model, dataset, family.criterion(), **kw)
    o.set_optim_method(recipe.build(conf))
    o.set_compute_dtype(jnp.dtype(conf["compute_dtype"]))
    if w.get("parameter_sync"):
        o.set_parameter_sync(w["parameter_sync"])
    return o, tap


def run_cell(cell: Dict, seed: int, seconds: float, traced: bool,
             devices, log=print) -> Dict:
    """Everything after the look for a chip.  Returns the result line as
    a dict; ``log`` gets the lines printed before it."""
    import jax
    import numpy as np

    w, conf = cell["workload"], cell["config"]
    family, recipe = models.load(conf), optimizers.load(conf)
    compiles = CompileCounter()

    # -- set-up: records, weights, model, optimizer -------------------------
    phases = [("imports, backend", time.perf_counter())]
    n_records = w["epoch_batches"] * w["batch"]
    x, y = models.make_records(family, seed, n_records, conf)
    phases.append(("records", time.perf_counter()))
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn.module import load_state_dict, state_dict
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(reference.seed_words(seed, 4)[0] & 0x7FFFFFFF)
    samples = [Sample(x[i], y[i]) for i in range(n_records)]
    specs = family.param_specs(conf)
    model = family.build(conf)
    own = state_dict(model, kind="param")
    if [tuple(v.shape) for v in own.values()] != \
            [tuple(s["shape"]) for s in specs]:
        raise SystemExit("the program's parameters no longer line up with "
                         "the reference's, in order and shape")
    phases.append(("program imports, model", time.perf_counter()))
    weights = reference.make_weights(specs, seed, conf["init_gain"])
    load_state_dict(model, dict(zip(own, weights)), strict=False)
    param_keys = list(own)
    del weights, own
    o, tap = build_optimizer(cell, family, recipe, model, samples, devices)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        seconds = min(seconds, w["trace_seconds"])
    hooks = Hooks(o, lambda st: recipe.first_gradient(st, conf), param_keys,
                  seconds, w["check_steps"], w["warmup_steps"], compiles,
                  trace_dir)
    o.set_train_summary(hooks)
    o.set_end_when(Trigger(hooks.end))
    phases.append(("weights, optimizer", time.perf_counter()))

    # -- ONE optimize(): check steps, warm-up, window ------------------------
    try:
        o.optimize()
        setup_s = hooks.t_open - _T_PROCESS
        phases += [("first step", hooks.ends[0]),
                   ("check and warm-up steps", hooks.t_open)]
        log("setup: " + ", ".join(
            f"{name} {b - a:.1f} s" for (name, b), a in zip(
                phases, [_T_PROCESS] + [t for _, t in phases[:-1]])))
        peak = memory_peak(devices, log)
        result = reduce_window(cell, hooks, o, devices, trace_dir, log)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    result["metrics_all"]["setup_s"] = setup_s

    # -- the reference, once the program's state is freed --------------------
    seen = np.array(tap.seen).reshape(w["check_steps"], w["batch"])
    got = {"losses": hooks.losses[:w["check_steps"]],
           "grad1_norms": reference.leaf_norms(hooks.grad1),
           "w_after": hooks.params_after_check}
    o.set_train_summary(None)
    del o, hooks, model, samples, tap
    gc.collect()
    t_ref = time.perf_counter()
    w0 = reference.host_weights(specs, seed, conf["init_gain"])
    got["delta_norms"] = reference.leaf_norms(
        [a - b for a, b in zip(got.pop("w_after"), w0)])
    want = reference.follow(
        family, w0, [(x[rows], y[rows]) for rows in seen], recipe, conf,
        devices=devices)
    numbers = compare.numbers(got, want)
    verdict = compare.judge(numbers, w["limits"])
    for line in compare.lines(numbers, w["limits"]):
        log(line)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s for "
        f"{w['check_steps']} steps of {w['batch']} records, peak "
        f"{want['device_peak_bytes'] / 1e9:.1f} GB")

    window_ok = (result["failed"] == 0 and result["window_compiles"] == 0
                 and result["window_traces"] == 0)
    log(f"window: {result['attempted']} steps started, "
        f"{result['failed']} failed, {result['window_compiles']} compiles "
        f"and {result['window_traces']} traces inside it (limit 0 each)")
    wanted = cell["per_layer"] if traced else cell["end_to_end"]
    units = {m["name"]: m["unit"] for m in wanted}
    metrics = {k: {"value": v, "unit": units[k]}
               for k, v in result["metrics_all"].items()
               if k in units and v is not None and math.isfinite(v)}
    d0 = devices[0]
    out = {
        "correct": bool(verdict and window_ok),
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices), "memory_peak_bytes": int(peak)},
        "compared": numbers,
    }
    if traced:
        out["device"].update(result["device"])
        out["breakdown"] = result["breakdown"]
    return out


def reduce_window(cell: Dict, hooks: Hooks, o, devices, trace_dir, log
                  ) -> Dict:
    """From the stamps (and the trace) to every metric the cell has."""
    w, conf = cell["workload"], cell["config"]
    k0 = hooks.open_step
    tops, ends = hooks.tops[k0:], hooks.ends[k0:]
    losses = hooks.losses[k0:]
    e2e = stats.window_metrics(tops, ends, hooks.t_open, hooks.t_close,
                               w["batch"])
    log(f"steps: {e2e['steps_completed']} in {e2e['window_s']:.3f} s, "
        f"median {e2e.get('step_p50_ms', float('nan')):.3f} ms, "
        f"p90 {e2e.get('step_p90_ms', float('nan')):.3f} ms, "
        f"max {e2e.get('step_max_ms', float('nan')):.3f} ms; "
        f"loss {hooks.losses[0]:.4f} -> {hooks.losses[-1]:.4f}")
    failed = sum(1 for v in losses if not math.isfinite(v))
    before, after = hooks.compiles_at_open, hooks.compiles_at_close
    out = {
        "attempted": len(tops),
        "failed": failed + (len(tops) - len(ends)),
        "window_compiles": after["compiles"] - before["compiles"],
        "window_traces": after["traces"] - before["traces"],
        "metrics_all": {"records_per_s": e2e["records_per_s"],
                        "step_p90_ms": e2e.get("step_p90_ms")},
    }
    if not trace_dir:
        return out

    # -- the traced run: per-layer metrics, each by a reader of its own -------
    totals = hooks.stage_totals[k0 - 1:]  # one before the window, to diff
    ctx = {
        "cell": cell, "batch": w["batch"], "chips": len(devices),
        "device_kind": devices[0].device_kind,
        "steps": e2e["steps_completed"], "window_s": e2e["window_s"],
        "stage_per_step": {s: stats.diffs([t[s] for t in totals])
                           for s in Hooks.STAGES},
        "peaks": read_json(HERE, "peaks.json"),
    }
    tr = tracelib.load(trace_dir, devices[0].platform)
    if tr.stamp_s is None:
        raise SystemExit("the trace holds no " + tracelib.STAMP)
    shift = tr.stamp_s - hooks.stamp_perf  # harness clock -> profiler's
    lo, hi = hooks.t_open + shift, hooks.t_close + shift
    ctx.update(trace=tr, lo=lo, hi=hi)
    for m in cell["per_layer"]:
        spec = read_json(HERE, "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        out["metrics_all"][m["name"]] = reader.read(
            ctx, **spec.get("args", {}))
    busy = tracelib.busy_seconds(tr, lo, hi)
    phases = []
    data = ctx["stage_per_step"]["data time"]
    for i, (a, b) in enumerate(zip(tops, ends)):
        wait = data[i] if i < len(data) else 0.0
        phases.append(("data_wait", a + shift, a + wait + shift))
        phases.append(("in-step host", a + wait + shift, b + shift))
        nxt = tops[i + 1] if i + 1 < len(tops) else hooks.t_close
        phases.append(("between steps", b + shift, nxt + shift))
    out["device"] = {"busy_s": busy, "window_s": hi - lo}
    by_name = tracelib.seconds_by_name(tr, lo, hi)
    for name, secs in tracelib.top(by_name, 25):
        log(f"top device op: {1e3 * secs / max(e2e['steps_completed'], 1):9.3f} ms/step  {name}")
    out["breakdown"] = {
        "device_ops": tracelib.top(by_name),
        "idle_gaps": tracelib.top(
            tracelib.idle_gaps_by_phase(tr, lo, hi, phases)),
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    setup_compile_cache()
    devices = find_devices(cell["chips"])
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
