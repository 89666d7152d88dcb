#!/usr/bin/env python
"""Benchmark driver: the five BASELINE.md configs (LeNet-5/MNIST,
VGG-16/CIFAR-10, Inception-v1/ImageNet, LSTM text classifier,
ResNet-50/ImageNet) under the reference's synthetic-data protocol
(``models/utils/DistriOptimizerPerf.scala:33-124`` / LocalOptimizerPerf:
device-resident synthetic data, fixed batch, records/sec after warmup),
plus an efficiency account: per-step FLOPs from XLA's cost analysis,
achieved TFLOP/s, and MFU against the chip's peak.

Prints ONE JSON line: the headline metric (Inception-v1 ImageNet
throughput, the BASELINE.json north star) with a ``configs`` field
carrying every config's images/sec + FLOPs + TFLOP/s + MFU.
The reference publishes no numeric baselines (BASELINE.json
``"published": {}``), so vs_baseline is null.

Env knobs: BENCH_CONFIGS=comma,list  BENCH_ITERS.  Warmup is one full
(untimed) scan dispatch — there is no separate warmup knob.  A backend
that does not come up, or a config that fails, is a non-zero exit; no
number is ever printed that this run did not measure.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

#: the north-star config (BASELINE.json)
HEADLINE = "inception_v1_imagenet"

#: best round-3 measured headline throughput (BASELINE.md) — the
#: progress denominator for ``vs_round3_best``
ROUND3_BEST = 4853.0


def zipf_indices(rng, shape, vocab: int, a: float = 1.05) -> np.ndarray:
    """Zipfian ids over ``[0, vocab)``: rank r drawn with P(r) ~ r^-a —
    the hot-row skew real token/id traffic actually has.  The uniform
    sampler the bench used before is the BEST case for an embedding
    (every row equally warm, no hot-row cache/contention behaviour and
    maximal unique rows per batch); embedding legs sample zipfian so the
    sparse-sync win and hot-row behaviour are measured under realistic
    skew (docs/sparse.md)."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -a
    p /= p.sum()
    return rng.choice(vocab, size=shape, p=p).astype(np.int32)


def _configs():
    """name -> (build_model, build_batch, criterion, batch).
    ``build_batch(batch, seq=None)``: token configs honor a sequence
    override (the bucketed lstm protocol); image configs ignore it."""
    from bigdl_tpu import models
    import bigdl_tpu.nn as nn

    rng = np.random.default_rng(0)

    def img(batch, c, h, w, classes):
        x = jnp.asarray(rng.normal(size=(batch, c, h, w)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, classes, batch))
        return x, y

    def tokens(batch, seq, vocab, classes, seq_targets=False, zipf=None):
        if zipf is not None:
            x = jnp.asarray(zipf_indices(rng, (batch, seq), vocab, zipf))
        else:
            x = jnp.asarray(rng.integers(0, vocab, (batch, seq),
                                         dtype=np.int32))
        if seq_targets:  # LM: a target token per position
            y = jnp.asarray(rng.integers(0, classes, (batch, seq), dtype=np.int32))
        else:
            y = jnp.asarray(rng.integers(0, classes, batch))
        return x, y

    def dlrm_batch(batch):
        # Criteo-style: 13 integer count features + 8 zipfian
        # categorical ids, one per 50000-row table (models/dlrm.py)
        dense = rng.integers(0, 100, (batch, 13), dtype=np.int32)
        cat = zipf_indices(rng, (batch, 8), 50000, 1.05)
        x = jnp.asarray(np.concatenate([dense, cat], axis=1))
        y = jnp.asarray(rng.integers(0, 2, batch))
        return x, y

    return {
        "lenet_mnist": (
            lambda: models.build_lenet5(10),
            lambda b: img(b, 1, 28, 28, 10), nn.ClassNLLCriterion(), 1024),
        "vgg16_cifar10": (
            lambda: models.build_vgg_for_cifar10(10),
            lambda b: img(b, 3, 32, 32, 10), nn.ClassNLLCriterion(), 512),
        "inception_v1_imagenet": (
            lambda: models.build_inception_v1(1000),
            lambda b: img(b, 3, 224, 224, 1000), nn.ClassNLLCriterion(), 256),
        # zipfian ids since r15 (realistic hot-row skew; uniform was the
        # embedding's best case) and the BUCKETED variable-length
        # protocol (LSTM_BUCKETS below; BENCH_LSTM_BUCKETS=0 restores
        # the fixed-200 leg for old-round comparisons)
        "lstm_text": (
            lambda: models.build_lstm_classifier(5000, class_num=20),
            lambda b, s=None: tokens(b, s or 200, 5000, 20, zipf=1.05),
            nn.ClassNLLCriterion(), 256),
        # representative large recurrent shape: the tiny config above is
        # latency-bound (see BASELINE.md roofline note); this one feeds
        # the MXU a 1536x4096 fused-gate matmul per scan step.  Its
        # 102400-lookup batch touches the whole 20000-row table, so the
        # sparse auto rule keeps its sync DENSE (docs/sparse.md "when
        # dense wins") — the sparse-sync proof shape is `dlrm`
        "lstm_text_large": (
            lambda: models.build_lstm_classifier(
                20000, embed_dim=512, hidden_size=1024, num_layers=2,
                class_num=20),
            lambda b, s=None: tokens(b, s or 200, 20000, 20, zipf=1.05),
            nn.ClassNLLCriterion(), 512),
        # recsys ranking (models/dlrm.py, docs/sparse.md): 8 x 50000-row
        # embedding bags + MLPs + pairwise interaction; a batch touches
        # <= 512 of each table's 50000 rows, so the sparse sync moves
        # ~2% of the dense table all-reduce — the measured sparse win
        "dlrm": (
            lambda: models.build_dlrm(),
            lambda b, s=None: dlrm_batch(b), nn.ClassNLLCriterion(), 512),
        "resnet50_imagenet": (
            lambda: models.build_resnet(50, 1000),
            lambda b: img(b, 3, 224, 224, 1000), nn.ClassNLLCriterion(), 128),
        # decoder-only LM through the Pallas flash-attention path:
        # [batch, seq] tokens -> per-position next-token NLL
        "transformer_lm": (
            lambda: models.build_transformer_lm(
                32000, num_layers=6, embed_dim=512, num_heads=8, max_len=512),
            lambda b: tokens(b, 512, 32000, 32000, seq_targets=True),
            nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), size_average=True),
            32),
        # long-context single-chip: flash attention (O(S) memory) +
        # per-block rematerialization at seq 4096
        "transformer_lm_long": (
            lambda: models.build_transformer_lm(
                32000, num_layers=6, embed_dim=512, num_heads=8,
                max_len=4096, remat=True),
            lambda b: tokens(b, 4096, 32000, 32000, seq_targets=True),
            nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), size_average=True),
            4),
    }


def peak_flops_per_sec(int8: bool = False):
    """The MFU / utilization denominator from the ONE peak table
    (``telemetry/device.py CHIP_PEAKS``): None on the CPU, which has no
    meaningful peak; an accelerator the table does not know raises."""
    from bigdl_tpu.telemetry import device as _device

    kind = jax.devices()[0].device_kind
    if int8:
        return _device.peak_int8_ops_per_device(kind)
    return _device.peak_flops_per_device(kind)


def make_step(name: str, batch: int = None, seq: int = None):
    """Build the exact train step a config benches — the shared setup
    recipe (seed, graph passes, SGD 0.9-momentum, bf16 compute) for
    bench.run_config, tools/profile_bench.py, and tools/hlo_dump.py so
    their runtime and compiler views stay views of the SAME program.
    ``seq`` overrides the sequence length on token configs that honor it
    (the bucketed lstm protocol).  Returns (step, x, y)."""
    import bigdl_tpu.optim as optim
    from bigdl_tpu.nn.fuse import optimize_for_tpu
    from bigdl_tpu.parallel.train_step import TrainStep
    from bigdl_tpu.utils.rng import RNG

    build_model, build_batch, criterion, default_batch = _configs()[name]
    RNG.set_seed(0)
    model = optimize_for_tpu(build_model())
    step = TrainStep(model, criterion,
                     optim.SGD(learning_rate=0.01, momentum=0.9),
                     compute_dtype=jnp.bfloat16)
    if seq is None:
        x, y = build_batch(batch or default_batch)
    else:
        x, y = build_batch(batch or default_batch, seq)
    return step, x, y


def make_drain(step):
    """Value-fetch sync: a params-derived scalar forces every queued
    dispatch INCLUDING its optimizer updates (the loss alone only depends
    on params from the previous iteration).  Shared with
    ``tools/scaling_bench.py`` so the timing protocol stays in one place."""
    def drain():
        float(jnp.sum(jax.tree_util.tree_leaves(step.params)[0]))
    return drain


#: attention geometry per transformer config: (layers, heads, head_dim,
#: seq).  XLA's cost analysis cannot see inside the Pallas flash custom
#: call, so when the auto backend routes a config to flash its S^2
#: matmul FLOPs vanish from the count and MFU is UNDERSTATED (measured:
#: dense seq-512 counted 3,816 GF, flash 3,492 GF for the same model).
#: The correction adds the DENSE-equivalent algorithmic FLOPs
#: (12*L*B*H*S^2*D: 4 fwd + 8 bwd matmul terms — flash's extra
#: recompute is deliberately NOT counted, matching standard MFU
#: practice of counting model FLOPs, not rematerialization).
ATTN_GEOM = {
    "transformer_lm": (6, 8, 64, 512),
    "transformer_lm_long": (6, 8, 64, 4096),
}


def _flash_attn_flops(name, batch):
    geom = ATTN_GEOM.get(name)
    if not geom:
        return 0.0
    # THE routing predicate, shared with MultiHeadAttention (round-5
    # advisor: re-deriving it here silently drifted when the rule or
    # the BIGDL_KERNELS knob changed it)
    from bigdl_tpu.ops.attention import flash_auto

    layers, heads, d, s = geom
    if not flash_auto(s, s):
        return 0.0  # dense path: cost analysis already counts it
    return 12.0 * layers * batch * heads * float(s) * s * d


#: configs riding the bucketed variable-length protocol (dataset/
#: text.py BucketedPadding boundaries): batches are drawn per length
#: bucket instead of always padding to max seq, and MFU stops crediting
#: pad positions.  BENCH_LSTM_BUCKETS=0 restores the fixed-length leg
#: (comparisons against pre-r15 banked rounds).
LSTM_BUCKETS = {"lstm_text": (32, 64, 128, 200)}


def run_config(name, batch, iters):
    from bigdl_tpu import telemetry

    with telemetry.span(f"bench/{name}", batch=batch, iters=iters):
        if name in LSTM_BUCKETS \
                and os.environ.get("BENCH_LSTM_BUCKETS", "1") != "0":
            return _run_config_bucketed(name, batch, iters,
                                        LSTM_BUCKETS[name])
        return _run_config_timed(name, batch, iters)


def _time_leg(name, step, x, y, iters):
    """The shared timing core: one AOT scan compile, cost analysis, an
    untimed warmup dispatch, then the timed window.
    Returns ``(wall_s, compile_s, stages, flops_per_iter)`` —
    ``flops_per_iter`` is the raw XLA count (pad masking is the
    caller's accounting)."""
    flops = None
    t_c0 = time.perf_counter()
    cost = step.aot_scan(x, y, jax.random.key(0), iters)
    from bigdl_tpu.telemetry.device import normalize_cost_analysis

    cost = normalize_cost_analysis(cost)
    compile_s = time.perf_counter() - t_c0
    if cost and cost.get("flops"):
        flops = float(cost["flops"])

    drain = make_drain(step)

    losses = step.run_scan(x, y, jax.random.key(1), iters)  # warmup
    if not bool(jnp.isfinite(losses).all()):
        raise FloatingPointError("non-finite loss during warmup")
    drain()  # the warmup scan's LAST param update must not leak into t0

    t0 = time.perf_counter()
    xs, ys = step._shard_batch(x, y)
    t_h2d = time.perf_counter()
    step.run_scan_sharded(xs, ys, jax.random.key(2))
    t_dispatch = time.perf_counter()
    drain()
    wall = time.perf_counter() - t0
    stages = {"compile": round(compile_s, 3),
              "h2d": round(t_h2d - t0, 4),
              "dispatch": round(t_dispatch - t_h2d, 4),
              "device": round(wall - (t_dispatch - t0), 4)}
    return wall, compile_s, stages, flops


def _bucket_lengths(rng, n, max_len):
    """Realistic sentence lengths for the bucketed lstm leg: lognormal
    (median ~45 tokens, long tail clipped at the model's max seq) — the
    shape short-text classification corpora actually have, instead of
    every row exactly max_len."""
    ln = np.round(rng.lognormal(np.log(45.0), 0.8, size=n))
    return np.clip(ln, 4, max_len).astype(int)


def _run_config_bucketed(name, batch, iters, boundaries):
    """The variable-length protocol (dataset/text.py BucketedPadding):
    sample realistic lengths, assign each row to its bucket, run the
    timed scan once per bucket holding >= 5% of rows (iterations split
    by share), aggregate.  MFU accounting multiplies each bucket's XLA
    FLOPs by its valid-token fraction — pad positions compute but no
    longer count as useful work."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.dataset.text import BucketedPadding

    bp = BucketedPadding(boundaries)
    rng = np.random.default_rng(7)
    lengths = _bucket_lengths(rng, 4096, boundaries[-1])
    by_bucket = {}
    for ln in lengths:
        by_bucket.setdefault(bp.bucket_of(int(ln)), []).append(int(ln))
    shares = {b: len(v) / len(lengths) for b, v in by_bucket.items()}
    legs = {b: v for b, v in by_bucket.items() if shares[b] >= 0.05}
    scale = sum(shares[b] for b in legs)  # renormalize dropped tails
    total_rows = 0
    total_wall = 0.0
    useful_flops = 0.0
    compile_s_total = 0.0
    stages_total = {"compile": 0.0, "h2d": 0.0, "dispatch": 0.0,
                    "device": 0.0}
    buckets_out = {}
    peak_hbm = None
    for b_seq in sorted(legs):
        iters_b = max(2, int(round(iters * shares[b_seq] / scale)))
        step, x, y = make_step(name, batch, seq=b_seq)
        # end-pad each row past its sampled valid length with index 0
        # (the dataset convention) so the content matches what a
        # bucketed input pipeline would feed
        row_lens = rng.choice(np.asarray(legs[b_seq]), size=batch)
        row_lens = np.minimum(row_lens, b_seq)
        xm = np.asarray(x)
        mask = np.arange(b_seq)[None, :] < row_lens[:, None]
        xm = np.where(mask, xm, 0).astype(xm.dtype)
        x = jnp.asarray(xm)
        valid_frac = float(row_lens.sum()) / float(batch * b_seq)
        wall, compile_s, stages, flops = _time_leg(
            f"{name}[s{b_seq}]", step, x, y, iters_b)
        total_rows += batch * iters_b
        total_wall += wall
        compile_s_total += compile_s
        for k in stages_total:
            stages_total[k] += stages[k]
        if flops:
            useful_flops += flops * valid_frac * iters_b
        try:
            from bigdl_tpu.telemetry import memory as _tmem

            mrow = _tmem.analyze_hlo_memory(step._scan_cache[1].as_text())
            peak_hbm = max(peak_hbm or 0, int(mrow["peak_bytes"]))
        except Exception:  # noqa: BLE001 - the snapshot is an observer
            pass
        buckets_out[str(b_seq)] = {
            "share": round(shares[b_seq] / scale, 3), "iters": iters_b,
            "images_per_sec": round(batch * iters_b / wall, 2),
            "valid_token_frac": round(valid_frac, 3),
            "compile_s": round(compile_s, 3),
        }
    rate = total_rows / total_wall
    telemetry.counter(f"bench/{name}/images_per_sec", rate)
    out = {"images_per_sec": round(rate, 2), "batch": batch,
           "compile_s": round(compile_s_total, 3),
           "stages_s": {k: round(v, 4) for k, v in stages_total.items()},
           "buckets": buckets_out,
           "valid_token_frac": round(
               sum(r["valid_token_frac"] * r["share"]
                   for r in buckets_out.values()), 3)}
    if useful_flops:
        achieved = useful_flops / total_wall
        out["step_gflops"] = round(useful_flops / max(1, total_rows
                                                      // batch) / 1e9, 2)
        out["achieved_tflops"] = round(achieved / 1e12, 2)
        peak = peak_flops_per_sec()
        if peak:
            # pad positions excluded: this MFU counts USEFUL tokens only
            out["mfu"] = round(achieved / peak, 4)
    if peak_hbm:
        out["peak_hbm_bytes"] = peak_hbm
    return out


def _run_config_timed(name, batch, iters):
    from bigdl_tpu import telemetry

    step, x, y = make_step(name, batch)

    # ALL timed iterations run inside ONE dispatch (lax.scan over the
    # step) — per-dispatch latency is a property of the host link, not of
    # the training program, and a real TPU deployment amortizes it the
    # same way.  The AOT compile also yields XLA's cost analysis (scan
    # body counted once).
    wall, compile_s, stages, flops = _time_leg(name, step, x, y, iters)
    t_h2d_s = stages["h2d"]
    t_dispatch_s = stages["dispatch"]
    flash_flops = 0.0
    if flops:
        flash_flops = _flash_attn_flops(name, batch)
        flops += flash_flops

    rate = batch * iters / wall
    # same numbers, second consumer: the telemetry event log (when a run
    # is active) carries the stage split + throughput next to the
    # aot_scan compile/device_facts events TrainStep already emitted
    telemetry.stage("h2d", t_h2d_s)
    telemetry.stage("dispatch", t_dispatch_s)
    telemetry.stage("device", stages["device"])
    telemetry.counter(f"bench/{name}/images_per_sec", rate)
    out = {"images_per_sec": round(rate, 2), "batch": batch,
           # the compile budget's input (docs/compile.md): per-leg
           # compile seconds as a first-class field so
           # `--diff-against --compile-budget` gates the lenet-445s
           # class of outlier instead of it hiding inside stages_s
           "compile_s": round(compile_s, 3),
           # host-loop stage breakdown (optim/Metrics.scala:31-130
           # re-scope; see docs/straggler.md): compile / h2d / dispatch /
           # device-sync seconds for the timed window
           "stages_s": stages}
    if flops:
        achieved = flops * iters / wall
        out["step_gflops"] = round(flops / 1e9, 2)
        out["achieved_tflops"] = round(achieved / 1e12, 2)
        if flash_flops:
            out["flash_gflops_added"] = round(flash_flops / 1e9, 2)
        peak = peak_flops_per_sec()
        if peak:
            out["mfu"] = round(achieved / peak, 4)
    # comms snapshot off the scan executable (telemetry/comms.py): the
    # scan body holds each collective once, so these are per-iteration
    # numbers — `--diff-against` then gates bytes-moved regressions
    # (.comms_bytes/.comms_s) exactly like MFU, which is what the
    # ZeRO/pipeline PRs need to prove "the reduce-scatter is hidden"
    try:
        from bigdl_tpu.telemetry import comms as _comms

        cf = _comms.comms_facts(step._scan_cache[1], mesh=step.mesh,
                                model=step.model)
        if cf["count"] or step.mesh is not None:
            out["comms_bytes"] = cf["bytes"]
            out["comms_collectives"] = cf["count"]
            if cf.get("by_axis"):
                out["comms_by_axis"] = cf["by_axis"]
            if cf.get("expected_s") is not None:
                out["comms_s"] = round(cf["expected_s"], 6)
    except Exception:  # noqa: BLE001 - the snapshot is an observer
        pass
    # memory snapshot off the SAME scan executable (telemetry/memory.py
    # while-body recursion reports the peak INSIDE the scanned step):
    # `--diff-against --memory-budget` gates per-device HBM exactly
    # like MFU — the "ZeRO-1 drops optimizer HBM" CI claim
    try:
        from bigdl_tpu.telemetry import memory as _tmem

        mrow = _tmem.analyze_hlo_memory(step._scan_cache[1].as_text())
        out["peak_hbm_bytes"] = int(mrow["peak_bytes"])
        out["hbm_categories"] = {
            k: int(v) for k, v in mrow["categories"].items() if v}
    except Exception:  # noqa: BLE001 - the snapshot is an observer
        pass
    return out


def _local_sgd_leg(mode, h, iters, mesh, batch=128):
    """One side of the local-SGD pair: train the registry LeNet on a
    data-axis mesh for ``iters`` steps under ``mode``, measure the
    effective per-step collective bytes off the EXACT compiled programs
    that ran (the scan executable; plus the averaging executable,
    amortized over H, for the local leg), and record the achieved
    loss."""
    import bigdl_tpu.optim as optim
    from bigdl_tpu.nn.fuse import optimize_for_tpu
    from bigdl_tpu.parallel.train_step import TrainStep
    from bigdl_tpu.telemetry import comms as _comms
    from bigdl_tpu.utils.rng import RNG

    build_model, build_batch, criterion, _ = _configs()["lenet_mnist"]
    RNG.set_seed(0)
    model = optimize_for_tpu(build_model())
    step = TrainStep(model, criterion,
                     optim.SGD(learning_rate=0.05, momentum=0.9),
                     mesh=mesh, parameter_sync=mode,
                     compute_dtype=jnp.bfloat16)
    x, y = build_batch(batch)
    key = jax.random.key(0)
    # AOT first: installs the scan EXECUTABLE (not just the jit) so the
    # comms walker below reads the exact program that ran
    step.aot_scan(x, y, key, h if mode == "local" else iters)
    t0 = time.perf_counter()
    losses = []
    if mode == "local":
        # scan in H-step chunks with a parameter averaging between
        # chunks — the local-SGD schedule itself (parallel/local_sync.py
        # drives the same rhythm in the training loop)
        for r in range(max(1, iters // h)):
            chunk = step.run_scan(x, y, jax.random.fold_in(key, r), h)
            losses.append(np.asarray(chunk))
            step.average_islands()
    else:
        losses.append(np.asarray(step.run_scan(x, y, key, iters)))
    wall = time.perf_counter() - t0
    if not all(np.isfinite(c).all() for c in losses):
        raise FloatingPointError(f"non-finite loss in local-SGD "
                                 f"{mode} leg")
    row = {"batch": batch, "h": h if mode == "local" else 1,
           "sync": mode,
           "final_loss": round(float(np.mean(losses[-1])), 6),
           "images_per_sec": round(batch * iters / wall, 2)}
    nbytes = float(_comms.comms_facts(step._scan_cache[1],
                                      mesh=mesh)["bytes"])
    if mode == "local" and step._avg_cache is not None:
        nbytes += float(_comms.comms_facts(step._avg_cache,
                                           mesh=mesh)["bytes"]) / h
    row["comms_bytes"] = nbytes
    return row


def run_local_sgd_pair(iters, h=None):
    """The local-SGD evidence pair (docs/fault_tolerance.md "Straggler
    tolerance"): the same registry model trained synchronously and with
    H local steps between averagings on a 2-device data mesh.  The
    ``local_sgd_sync`` / ``local_sgd_local`` rows ride the artifact's
    ``configs`` table, so ``--diff-against`` gates BOTH sides of the
    trade: ``.comms_bytes`` (the ≈H× reduction must not erode) and
    ``.final_loss`` (H=10^6 would zero the comms and junk the model)."""
    from bigdl_tpu.parallel.mesh import make_mesh

    h = int(h or os.environ.get("BENCH_LOCAL_SGD_H", "8"))
    if len(jax.devices()) < 2:
        raise RuntimeError("local-SGD pair needs >= 2 devices")
    mesh = make_mesh((2,), ("data",))
    iters = max(iters, 2 * h)
    return {
        "local_sgd_sync": _local_sgd_leg("allreduce", h, iters, mesh),
        "local_sgd_local": _local_sgd_leg("local", h, iters, mesh),
    }


#: inference configs for the int8-vs-bf16 comparison (the bigquant
#: capability's headline claim: int8 doubles MXU throughput on v5e —
#: 394 TOPS int8 vs 197 TFLOP/s bf16; nn/quantized.py)
INFER_CONFIGS = {"inception_v1_imagenet": 256, "vgg16_cifar10": 512}


def run_infer_config(name, batch, iters, quantized):
    """Inference img/s + op-throughput accounting for one config, bf16
    or int8-quantized — the measured check on nn/quantized.py's
    throughput claim (VERDICT r4 Weak #4: 'the throughput feature is
    currently a comment').  ``utilization`` divides achieved op/s by
    the matching peak of the chip table: the bf16 peak for the float
    leg, the int8 peak for the int8 leg (v5e: 393 TOP/s vs 197
    TFLOP/s) — so an int8 leg that merely MATCHES bf16 img/s shows
    half the utilization, making a non-win visible."""
    from bigdl_tpu.nn.module import state_dict
    from bigdl_tpu.nn.quantized import quantize
    from bigdl_tpu.parallel.train_step import EvalStep
    from bigdl_tpu.utils.rng import RNG

    build_model, build_batch, _, _ = _configs()[name]
    RNG.set_seed(0)
    model = build_model().evaluate()
    x, _ = build_batch(batch)
    if quantized:
        from bigdl_tpu.nn.quantized import calibrate

        model = quantize(model)
        # calibrated static activation scales (BASELINE.md round-6 fix):
        # the dynamic per-conv amax reduce was the int8 regression —
        # production serving calibrates, so the bench leg measures the
        # calibrated path (one eager forward on the measurement batch)
        calibrate(model, [np.asarray(x)])
        es = EvalStep(model)  # int8 path owns its own dtypes
    else:
        es = EvalStep(model, compute_dtype=jnp.bfloat16)
    # ONE AOT compile serves the cost analysis AND the timed loop (the
    # run_config aot_scan pattern) — es.run would jit the same program
    # a second time
    state = state_dict(model)
    xj = jnp.asarray(x)
    compiled = es._build().lower(state, xj).compile()
    ops = None
    try:
        from bigdl_tpu.telemetry.device import normalize_cost_analysis

        cost = normalize_cost_analysis(compiled.cost_analysis())
        ops = float(cost.get("flops") or 0) or None
    except Exception:  # noqa: BLE001 — accounting must not sink the leg
        pass
    jax.block_until_ready(compiled(state, xj))  # warmup
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = compiled(state, xj)
    jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    row = {"img_s": round(batch * iters / wall, 2)}
    if ops:
        achieved = ops * iters / wall
        row["achieved_tops"] = round(achieved / 1e12, 2)
        peak = peak_flops_per_sec(int8=quantized)
        if peak:
            row["utilization"] = round(achieved / peak, 4)
    return row


def run_infer_table(iters):
    """{config: {bf16_*, int8_*, int8_speedup}} — one table per config.
    A leg that raises is recorded under ``<leg>_error`` so the other
    legs still run; the sweep's exit code turns non-zero for it."""
    table = {}
    for name, batch in INFER_CONFIGS.items():
        row = {}
        for tag, q in (("bf16", False), ("int8", True)):
            try:
                leg = run_infer_config(name, batch, iters, q)
                row.update({f"{tag}_{k}": v for k, v in leg.items()})
            except Exception as e:  # noqa: BLE001
                row[f"{tag}_error"] = f"{type(e).__name__}: {e}"
        if "bf16_img_s" in row and "int8_img_s" in row:
            row["int8_speedup"] = round(row["int8_img_s"] / row["bf16_img_s"], 3)
        table[name] = row
        print(f"# infer {name}: {row}", file=sys.stderr, flush=True)
    return table


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        description="bigdl_tpu benchmark driver (env knobs: BENCH_CONFIGS,"
                    " BENCH_ITERS, ... — see module docstring)")
    ap.add_argument("--diff-against", default=None, metavar="BASELINE.json",
                    help="after the sweep, compare this run's line against"
                         " a prior bench JSON (or a telemetry run log) via"
                         " python -m bigdl_tpu.telemetry diff; exit 4 on a"
                         " regression — the CI perf gate")
    ap.add_argument("--diff-threshold-pct", type=float, default=None,
                    help="regression threshold for --diff-against "
                         "(default: the diff engine's)")
    ap.add_argument("--compile-budget", type=float, default=None,
                    metavar="PCT",
                    help="compile budget for --diff-against: a config "
                         "whose compile_s grew more than PCT%% over the "
                         "baseline exits 4 like any other regression "
                         "(default: the diff engine's compile threshold,"
                         " 50%%)")
    ap.add_argument("--memory-budget", type=float, default=None,
                    metavar="PCT",
                    help="memory budget for --diff-against: a config "
                         "whose peak_hbm_bytes grew more than PCT%% "
                         "over the baseline exits 4 like any other "
                         "regression (default: the diff engine's "
                         "memory threshold, 10%%)")
    args = ap.parse_args(argv)
    from bigdl_tpu.utils.engine import Engine, enable_compile_cache

    # a second driver on the same chip is diagnosed as such; a backend
    # that does not come up raises out of jax.devices() — either way a
    # non-zero exit and no number
    Engine.check_singleton(raise_on_conflict=True)
    jax.devices()
    enable_compile_cache()  # the same directory as the CLI and chip_smoke
    # BIGDL_TELEMETRY routes the sweep's per-config stage timings,
    # compiles, and device facts into one JSONL run log (the instrumented
    # path replacing this file's former ad-hoc-only timing story)
    from bigdl_tpu import telemetry

    with telemetry.maybe_run(meta={"cmd": "bench"}) as owned_log:
        line = _sweep()
    if owned_log:
        print(f"# telemetry run log: {owned_log}", file=sys.stderr)
    if args.diff_against:
        from bigdl_tpu.telemetry import diff as tdiff

        base = tdiff.load_metrics(args.diff_against)
        cur = tdiff.bench_metrics(line, path="<this sweep>")
        kwargs = {}
        if args.diff_threshold_pct is not None:
            kwargs["threshold_pct"] = args.diff_threshold_pct
        if args.compile_budget is not None:
            kwargs["compile_threshold_pct"] = args.compile_budget
        if args.memory_budget is not None:
            kwargs["memory_threshold_pct"] = args.memory_budget
        rows = tdiff.diff_metrics(base, cur, **kwargs)
        print(tdiff.format_diff(rows, base, cur), file=sys.stderr)
        if not rows:
            # nothing comparable (every config errored, or a disjoint
            # baseline) must FAIL the gate, not silently pass it — the
            # same contract as `telemetry diff` exit 2
            print("error: --diff-against found nothing comparable",
                  file=sys.stderr)
            sys.exit(2)
        if any(r["regressed"] for r in rows):
            sys.exit(4)  # this sweep RAN, it just got slower
    failed = _failed(line)
    if failed:
        print(f"error: failed: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


def _failed(line) -> list:
    """Names of the configs / inference legs of a sweep line that
    raised — the sweep goes on past them, the exit code does not."""
    bad = [n for n, r in line["configs"].items() if "error" in r]
    bad += [f"infer:{n}" for n, r in
            (line.get("infer_int8_vs_bf16") or {}).items()
            if any(k.endswith("_error") for k in r)]
    return bad


def _sweep():
    iters = int(os.environ.get("BENCH_ITERS", "24"))
    cfgs = _configs()
    only = os.environ.get("BENCH_CONFIGS")
    names = [n.strip() for n in only.split(",")] if only else list(cfgs)

    results = {}
    for name in names:
        try:
            *_, batch = cfgs[name]
            results[name] = run_config(name, batch, iters)
        except Exception as e:  # noqa: BLE001 — recorded; main() exits non-zero
            results[name] = {"error": f"{type(e).__name__}: {e}"}
        print(f"# {name}: {results[name]}", file=sys.stderr, flush=True)

    # local-SGD comms/convergence pair: on for the full sweep whenever
    # a 2-device data mesh is possible, opt-in/out via BENCH_LOCAL_SGD
    want_ls = os.environ.get("BENCH_LOCAL_SGD")
    if want_ls == "1" or (want_ls != "0" and not only
                          and len(jax.devices()) >= 2):
        try:
            results.update(run_local_sgd_pair(iters))
        except Exception as e:  # noqa: BLE001 — recorded; main() exits non-zero
            results["local_sgd_local"] = {
                "error": f"{type(e).__name__}: {e}"}
        for n in ("local_sgd_sync", "local_sgd_local"):
            if n in results:
                print(f"# {n}: {results[n]}", file=sys.stderr, flush=True)

    # int8-vs-bf16 inference table: on for the full sweep (the driver's
    # default invocation), opt-in/out via BENCH_INFER=1/0
    infer = None
    want_infer = os.environ.get("BENCH_INFER")
    if want_infer == "1" or (want_infer != "0" and not only):
        infer = run_infer_table(max(8, iters // 2))

    # the metric name must say what was actually measured: the north-star
    # Inception config when it ran, else the first selected config
    head_name = HEADLINE if HEADLINE in results else next(iter(results))
    head = results[head_name]
    line = {
        "metric": f"{head_name}_train_throughput",
        "value": head.get("images_per_sec"),
        "unit": "images/sec",
        "vs_baseline": None,
        "mfu": head.get("mfu"),
        "device": jax.devices()[0].device_kind,
        "source": _source_state(),
        # the reference publishes no numbers (BASELINE.md) so vs_baseline
        # stays None; track progress against our own best measured round
        # number instead (round 3: 4,853 img/s Inception-v1, BASELINE.md)
        "vs_round3_best": (round(head["images_per_sec"] / ROUND3_BEST, 3)
                           if head_name == HEADLINE
                           and head.get("images_per_sec") else None),
        "configs": results,
    }
    try:
        from bigdl_tpu.utils import compile_cache as _cc

        # the sweep's persistent-cache story rides the artifact: a warm
        # round shows hits ~= requests, and the ingredients explain any
        # surprise cold round (docs/compile.md)
        line["compile_cache"] = _cc.monitor().snapshot()
        line["compile_cache_ingredients"] = _cc.cache_key_ingredients()
    except Exception:  # noqa: BLE001 — accounting must not sink the sweep
        pass
    if infer is not None:
        line["infer_int8_vs_bf16"] = infer
    try:
        from bigdl_tpu import telemetry as _tel

        # the run is still open here, so read the live ledger rather
        # than the (unwritten) run log — diff gates compare goodput_pct
        # / badput_s across rounds like any other metric
        gp = _tel.goodput()
        if gp and gp.get("wall_s"):
            line["goodput_pct"] = gp["goodput_pct"]
            line["badput_s"] = gp["badput_s"]
            line["badput"] = gp["badput"]
    except Exception:  # noqa: BLE001 — accounting must not sink the sweep
        pass
    print(json.dumps(line))
    return line


def _source_state():
    """Commit + dirty flag of the tree that produced the number — a bench
    artifact certifies nothing unless it names the exact source state (the
    round-2 maxpool regression hid for a full round because the committed
    tree diverged from the benched tree)."""
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    try:
        rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=here, capture_output=True, text=True,
                             timeout=10).stdout.strip()
        dirty = subprocess.run(["git", "status", "--porcelain"],
                               cwd=here, capture_output=True, text=True,
                               timeout=10).stdout.strip()
        return {"commit": rev or None, "dirty": bool(dirty)}
    except Exception:
        return {"commit": None, "dirty": None}


if __name__ == "__main__":
    main()
