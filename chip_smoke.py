#!/usr/bin/env python
"""The quickest proof that bigdl_tpu still starts on the chip.

``python chip_smoke.py`` drives the main path ONCE on one TPU chip,
through the entry points a user calls, at the full width and depth of
Inception-v1 / ImageNet (``models.build_inception_v1(1000)``, input
``3x224x224``, batch 256, bf16 compute — the configuration of the
benchmark's first cell), with random weights and synthetic data made from
``--seed``:

- **train**  — what ``python -m bigdl_tpu.models.cli train`` does: an
  ``optim.LocalOptimizer`` over ``Sample`` records with
  ``SGD(momentum=0.9)``, a validation trigger and a checkpoint trigger;
- **serve**  — what ``cli serve --bf16`` does: ``serving.serve_model``
  with AOT-warmed buckets, then ``POST /v1/predict`` over real HTTP on
  an ephemeral port, ``/status`` read back, a drained stop;
- **kernels** — ``flash_attention`` forward and backward on the chip
  against its XLA leg, ``cross_map_lrn`` (one leg, the banded product)
  against its definition in float32, and the ``kernel/dispatch``
  decisions of the train phase.

``python chip_smoke.py --chips 4`` runs ONLY the data-parallel path:
``optim.DistriOptimizer`` (``cli train --distributed``) over the
4-device ``data`` mesh with ``allreduce`` and with ``sharded`` (ZeRO-1)
parameter sync, compared with the same steps on one device of the same
process.

One process, because a chip belongs to one process at a time; the
server runs in threads of it.  Every phase prints one JSON line; the
LAST line of stdout is ``{"ok": true, "device": {...}}`` with the
device as JAX reports it.  Nothing here catches a phase's failure: an
exception, a wrong result or a backend that is not a TPU ends the run
with a non-zero exit code and no ``"ok": true``.
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CLASSES = 1000
IMAGE = (3, 224, 224)

#: |got - want| <= TOL * max|want|, per dtype: bf16 keeps 8 bits of
#: mantissa (eps 2^-8) and two forms round intermediates differently;
#: f32 differs by the transcendental and MXU pass order only
KERNEL_TOL = {"bfloat16": 4e-2, "float32": 2e-3}

#: served bf16 log-probs against an f32 forward of the same weights
SERVE_ATOL = 0.1

#: mesh loss against the one-device loss, relative, per iteration
MESH_LOSS_RTOL = 0.02


class Phase:
    """Times one phase and prints its JSON line when it ENDS WELL.  An
    exception passes through untouched: no line, no exit code 0."""

    def __init__(self, name: str):
        self.name = name
        self.fields = {}

    def __enter__(self):
        from bigdl_tpu.utils import compile_cache

        self._cache0 = compile_cache.monitor().snapshot()
        self._t0 = time.perf_counter()
        return self.fields

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        from bigdl_tpu.utils import compile_cache

        now = compile_cache.monitor().snapshot()
        line = {"phase": self.name,
                "seconds": round(time.perf_counter() - self._t0, 3),
                "compile_s": round(now["compile_s"]
                                   - self._cache0["compile_s"], 3),
                "cache_hits": now["hits"] - self._cache0["hits"],
                "cache_misses": now["misses"] - self._cache0["misses"]}
        line.update(self.fields)
        print(json.dumps(line), flush=True)
        return False


def check(cond, message: str):
    if not cond:
        raise AssertionError(message)


def on_platform(tree, platform: str, what: str) -> int:
    """Every array of ``tree`` lives on ``platform``; returns how many
    distinct devices hold them."""
    import jax

    devices = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        devices |= set(leaf.devices())
    check(devices, f"{what}: no device arrays")
    wrong = sorted({d.platform for d in devices} - {platform})
    check(not wrong, f"{what}: arrays live on {wrong}, not {platform}")
    return len(devices)


def inception_v1():
    from bigdl_tpu import models

    return models.build_inception_v1(CLASSES)


def make_samples(seed: int, n: int, labels_used: int = 10):
    """``n`` ImageNet-shaped records from ``seed``.  Only ``labels_used``
    of the 1000 classes occur, each with its own mean, so that a few
    SGD steps move the loss by more than bf16 resolves near ln(1000)."""
    import numpy as np

    from bigdl_tpu.dataset.sample import Sample

    rng = np.random.default_rng(seed)
    labels = rng.integers(0, labels_used, n)
    x = rng.standard_normal((n,) + IMAGE, dtype=np.float32)
    x += (labels / labels_used - 0.5).astype(np.float32)[:, None, None,
                                                         None]
    # ClassNLLCriterion takes 0-based integer targets
    return [Sample(x[i], np.int32(labels[i])) for i in range(n)]


def build_optimizer(cls, model, samples, batch, iters, sync=None, **kw):
    """The ``cli train`` recipe (models/cli.py cmd_train: SGD, learning
    rate 0.05, momentum 0.9) in the benchmark's step configuration
    (bf16 compute, f32 master weights)."""
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim

    o = cls(model, samples, nn.ClassNLLCriterion(), batch_size=batch,
            end_trigger=optim.Trigger.max_iteration(iters), **kw)
    o.set_optim_method(optim.SGD(learning_rate=0.05, momentum=0.9))
    o.set_compute_dtype(jnp.bfloat16)
    if sync is not None:
        o.set_parameter_sync(sync)
    return o


def read_summary(summary, batch):
    """(losses, seconds per iteration) off the run's TrainSummary; the
    first iteration's seconds include the step's compilation."""
    import numpy as np

    losses = [v for _, v, _ in summary.read_scalar("Loss")]
    check(losses and np.isfinite(losses).all(),
          f"losses not finite: {losses}")
    step_s = [batch / v for _, v, _ in summary.read_scalar("Throughput")]
    return ([round(float(v), 5) for v in losses],
            [round(float(t), 4) for t in step_s])


# -- phase: train -----------------------------------------------------------
def train_phase(seed: int, platform: str, batch: int = 256,
                iters: int = 6):
    import numpy as np

    import bigdl_tpu.optim as optim
    from bigdl_tpu import native
    from bigdl_tpu.nn.module import state_dict
    from bigdl_tpu.ops import dispatch
    from bigdl_tpu.utils import serializer
    from bigdl_tpu.utils.rng import RNG
    from bigdl_tpu.visualization import TrainSummary

    with Phase("train") as out, tempfile.TemporaryDirectory() as tmp:
        RNG.set_seed(seed)
        samples = make_samples(seed, 2 * batch)
        val = make_samples(seed + 1, batch)
        model = inception_v1()
        dispatch.clear_decisions()
        o = build_optimizer(optim.LocalOptimizer, model, samples, batch,
                            iters)
        every = optim.Trigger.several_iteration(max(1, iters // 2))
        o.set_validation(every, val,
                         [optim.Top1Accuracy(), optim.Top5Accuracy()],
                         batch_size=batch)
        o.set_checkpoint(os.path.join(tmp, "ckpt"), every)
        summary = TrainSummary(os.path.join(tmp, "tb"), "chip_smoke")
        o.set_train_summary(summary)
        t0 = time.perf_counter()
        trained = o.optimize()
        out["optimize_s"] = round(time.perf_counter() - t0, 3)

        losses, step_s = read_summary(summary, batch)
        check(len(losses) == iters, f"{len(losses)} losses for {iters} "
              f"iterations")
        check(len(set(losses)) > 1, f"losses all equal: {losses}")
        step = o.last_train_step
        on_platform((step.params, step.opt_state, step.buffers),
                    platform, "train step state")
        # what `cli test --checkpoint` does: newest model.* reloads
        snaps = sorted(glob.glob(os.path.join(tmp, "ckpt", "**",
                                              "model.*"), recursive=True),
                       key=os.path.getmtime)
        check(snaps, "no model.* checkpoint written")
        restored = state_dict(serializer.load_module(snaps[-1]))
        final = state_dict(trained)
        check(restored.keys() == final.keys(), "checkpoint keys differ")
        for k in final:  # the last trigger fired on the last iteration
            np.testing.assert_array_equal(np.asarray(restored[k]),
                                          np.asarray(final[k]), err_msg=k)
        decisions = sorted(set(dispatch.decisions()))
        out.update(batch=batch, iterations=iters, losses=losses,
                   checkpoint=os.path.basename(snaps[-1]),
                   restored_arrays=len(restored),
                   step_s=step_s, state_platform=platform,
                   native_loaded=native.is_native_loaded(),
                   kernel_dispatch=decisions)
    return decisions


# -- phase: serve -----------------------------------------------------------
def _post(port, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/predict",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=120) as resp:
        body = json.loads(resp.read())
    return body, (time.perf_counter() - t0) * 1000.0


def serve_phase(seed: int, platform: str, buckets=(1, 8)):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.parallel.train_step import EvalStep
    from bigdl_tpu.serving import serve_model
    from bigdl_tpu.utils.rng import RNG

    with Phase("serve") as out:
        RNG.set_seed(seed)
        model = inception_v1().evaluate()
        rng = np.random.default_rng(seed)
        n_burst = max(buckets)
        xs = rng.standard_normal((1 + n_burst,) + IMAGE, dtype=np.float32)
        spec = jax.ShapeDtypeStruct((1,) + IMAGE, jnp.float32)
        server = serve_model(
            model, spec, name="inception_v1", host="127.0.0.1", port=0,
            max_batch=n_burst, max_wait_ms=20.0,
            batch_buckets=list(buckets), compute_dtype=jnp.bfloat16)
        try:
            warm = server.executor.compile_count
            # the served weights are among the process's live arrays
            on_platform(jax.live_arrays(), platform, "live arrays")
            # one lone request (bucket 1), then a burst the batcher
            # coalesces toward the largest bucket
            first, first_ms = _post(server.port,
                                    {"inputs": xs[0].tolist()})
            results = [None] * n_burst

            def send(i):
                results[i] = _post(server.port,
                                   {"inputs": xs[1 + i].tolist()})

            threads = [threading.Thread(target=send, args=(i,))
                       for i in range(n_burst)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            check(all(r is not None for r in results),
                  "a burst request did not come back")
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/status",
                    timeout=30) as resp:
                status = json.loads(resp.read())["serving"]
            steady = server.executor.compile_count - warm
        finally:
            server.stop(drain=True)
        outs = np.asarray([first["outputs"]]
                          + [r[0]["outputs"] for r in results], np.float32)
        check(outs.shape == (1 + n_burst, CLASSES), f"shape {outs.shape}")
        check(np.isfinite(outs).all(), "non-finite served output")
        # log-probabilities: each row's exp sums to 1 (bf16 forward)
        np.testing.assert_allclose(np.exp(outs).sum(-1), 1.0, atol=2e-2)
        ref = np.asarray(EvalStep(model).run(jnp.asarray(xs)))
        err = float(np.abs(outs - ref).max())
        check(err <= SERVE_ATOL, f"served vs f32 forward: {err}")
        check(steady == 0, f"{steady} compiles after warm-up")
        check(status["compiles"] == warm, "status disagrees on compiles")
        st = server.batcher
        check(st.requests == 1 + n_burst and st.rejected == 0,
              f"drained {st.requests} requests, {st.rejected} rejected")
        out.update(buckets=list(buckets), warm_buckets=warm,
                   warmup_s=round(server.executor.warmup_s, 3),
                   steady_compiles=steady, requests=st.requests,
                   batches=st.batches, rejected=st.rejected,
                   first_request_ms=round(first_ms, 2),
                   burst_ms=[round(r[1], 2) for r in results],
                   status_p50_ms=status.get("p50_ms"),
                   status_p99_ms=status.get("p99_ms"),
                   max_abs_err_vs_f32=round(err, 5),
                   state_platform=platform)


# -- phase: kernels ---------------------------------------------------------
def _max_err(got, want) -> float:
    """max|got - want| / max|want| over a pytree, in f32."""
    import jax
    import numpy as np

    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        check(np.isfinite(g).all(), "non-finite kernel output")
        worst = max(worst, float(np.abs(g - w).max() / np.abs(w).max()))
    return worst


def _value_and_vjp(op):
    import jax

    def run(*args):
        *xs, g = args
        y, vjp = jax.vjp(op, *xs)
        return y, vjp(g)

    return jax.jit(run)


def kernels_phase(seed: int, platform: str, train_decisions,
                  attn_shapes=((8, 8, 512, 64), (2, 8, 4096, 64)),
                  lrn_shapes=((32, 64, 56, 56), (32, 192, 56, 56))):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.ops import dispatch
    from bigdl_tpu.ops.attention import (dot_product_attention,
                                         flash_attention)
    from bigdl_tpu.ops.lrn import cross_map_lrn

    with Phase("kernels") as out:
        rng = np.random.default_rng(seed)
        rows = []

        def draw(shape, dtype):
            return jnp.asarray(rng.standard_normal(shape, np.float32),
                               dtype)

        for shape in attn_shapes:
            q, k, v, g = (draw(shape, jnp.bfloat16) for _ in range(4))
            got = _value_and_vjp(
                lambda q, k, v: flash_attention(q, k, v, causal=True))(
                    q, k, v, g)
            want = _value_and_vjp(
                lambda q, k, v: dot_product_attention(
                    q, k, v, causal=True))(q, k, v, g)
            on_platform(got, platform, "flash_attention")
            err = _max_err(got, want)
            check(err <= KERNEL_TOL["bfloat16"],
                  f"flash_attention {shape}: {err}")
            rows.append({"op": "flash_attention", "shape": list(shape),
                         "dtype": "bfloat16", "max_err": round(err, 5)})

        lrn = lambda x: cross_map_lrn(x, 5, 1e-4, 0.75, 1.0)  # noqa: E731
        only_leg = {("lrn_cross_map.fwd", "xla", "only-leg"),
                    ("lrn_cross_map.bwd", "xla", "only-leg")}

        def lrn_definition(x):
            """The layer's rank-5 path on ``[N, C, 1, H, W]``: a
            ``reduce_window`` sum over the channel window, backward by
            autodiff; given and returned in float32."""
            return nn.SpatialCrossMapLRN(5, 1e-4, 0.75, 1.0).update_output(
                x[:, :, None])[:, :, 0]

        for shape in lrn_shapes:
            for dtype in (jnp.bfloat16, jnp.float32):
                x, g = draw(shape, dtype), draw(shape, dtype)
                dispatch.clear_decisions()
                got = _value_and_vjp(lrn)(x, g)
                took = set(dispatch.decisions())
                check(took == only_leg, f"cross_map_lrn took {took}")
                on_platform(got, platform, "cross_map_lrn")
                want = _value_and_vjp(lrn_definition)(
                    x.astype(jnp.float32), g.astype(jnp.float32))
                name = jnp.dtype(dtype).name
                err = _max_err(got, want)
                check(err <= KERNEL_TOL[name],
                      f"cross_map_lrn {shape} {name}: {err}")
                rows.append({"op": "cross_map_lrn", "shape": list(shape),
                             "dtype": name, "max_err": round(err, 5)})

        # Inception-v1 holds no op with a kernel: every site of the
        # train step announces its one form
        chose = [d for d in train_decisions
                 if tuple(d[1:]) not in (("xla", "only-leg"),
                                         ("xla", "whole-plane"))]
        check(not chose, f"the train step chose a leg: {chose}")
        took = {d for d in train_decisions
                if d[0].startswith("lrn_cross_map")}
        check(took == only_leg, f"the train step's LRN sites took {took}")
        out.update(tolerance=KERNEL_TOL, parity=rows,
                   train_dispatch=[list(d) for d in train_decisions],
                   default_device=str(jax.devices()[0]))


# -- the four-chip path -----------------------------------------------------
def mesh_phase(seed: int, platform: str, chips: int = 4, batch: int = 256,
               iters: int = 4):
    import jax
    import numpy as np

    import bigdl_tpu.optim as optim
    from bigdl_tpu.ops import dispatch
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.rng import RNG
    from bigdl_tpu.visualization import TrainSummary

    samples = make_samples(seed, 2 * batch)
    Engine.init()
    check(Engine.mesh.devices.size == chips and Engine.mesh.axis_names
          == ("data",), f"Engine mesh is {Engine.mesh}")

    def run(name, cls, sync=None):
        with Phase(name) as out, tempfile.TemporaryDirectory() as tmp:
            RNG.set_seed(seed)  # same weights, order and dropout keys
            dispatch.clear_decisions()
            o = build_optimizer(cls, inception_v1(), samples, batch, iters,
                                sync)
            summary = TrainSummary(tmp, name)
            o.set_train_summary(summary)
            o.optimize()
            losses, step_s = read_summary(summary, batch)
            step = o.last_train_step
            out.update(losses=losses, sync=sync, step_s=step_s,
                       kernel_dispatch=sorted(set(dispatch.decisions())))
            if sync is None:
                check(on_platform(step.params, platform, name) == 1,
                      "the reference run spread over several devices")
                return losses
            # the work is really spread: batch shards, and under ZeRO-1
            # the optimizer moments, on `chips` distinct devices
            xs, ys = step._shard_batch(
                np.stack([s.feature for s in samples[:batch]]),
                np.stack([s.label for s in samples[:batch]]))
            batch_devs = {sh.device for sh in xs.addressable_shards}
            check(len(batch_devs) == chips
                  and xs.addressable_shards[0].data.shape[0]
                  == batch // chips,
                  f"batch shards on {len(batch_devs)} devices")
            on_platform((step.params, step.opt_state), platform, name)
            moments = [a for a in jax.tree_util.tree_leaves(step.opt_state)
                       if getattr(a, "ndim", 0) >= 1]
            split = [a for a in moments
                     if a.addressable_shards[0].data.shape != a.shape]
            if sync == "sharded":
                check(split, "no optimizer moment is sharded")
                for a in split:
                    check(len({sh.device for sh in a.addressable_shards})
                          == chips, "a moment shard set misses a device")
            else:
                check(not split, "allreduce mode sharded a moment")
            text = step._compiled.lower(
                step.params, step.opt_state, step.buffers, xs, ys,
                jax.random.key(0)).compile().as_text()
            counts = {c: text.count(c) for c in
                      ("all-reduce", "reduce-scatter", "all-gather")}
            check(counts["all-reduce"] > 0, f"no all-reduce: {counts}")
            if sync == "sharded":
                check(counts["reduce-scatter"] + counts["all-gather"] > 0,
                      f"ZeRO-1 step without scatter/gather: {counts}")
            out.update(batch_shard_devices=len(batch_devs),
                       per_device_batch=batch // chips,
                       sharded_moments=len(split),
                       moments=len(moments), collectives=counts)
            return losses

    ref = run("one_device", optim.LocalOptimizer)
    for sync in ("allreduce", "sharded"):
        got = run(f"mesh_{sync}", optim.DistriOptimizer, sync)
        np.testing.assert_allclose(
            got, ref, rtol=MESH_LOSS_RTOL,
            err_msg=f"{sync} loss trajectory left the one-device one")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel mesh path and "
                         "its one-device comparison")
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args(argv)

    import jax

    from bigdl_tpu.utils.engine import enable_compile_cache

    dev = jax.devices()[0]  # a backend that does not come up raises here
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {device})",
              file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {device['count']}", file=sys.stderr)
        return 1
    print(json.dumps({"phase": "start", "device": device,
                      "compile_cache": enable_compile_cache(),
                      "jax": jax.__version__}), flush=True)
    if args.chips == 4:
        mesh_phase(args.seed, "tpu")
    else:
        decisions = train_phase(args.seed, "tpu")
        serve_phase(args.seed, "tpu")
        kernels_phase(args.seed, "tpu", decisions)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
