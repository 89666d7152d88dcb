"""Model-zoo command-line entry points (SURVEY §2.13: each reference
model ships scopt-based ``Train``/``Test`` mains, e.g.
``models/lenet/Train.scala``, plus the synthetic-data perf harnesses
``models/utils/{Local,Distri}OptimizerPerf.scala``).

Usage::

    python -m bigdl_tpu.models.cli train  --model lenet  -f ./mnist -b 64
    python -m bigdl_tpu.models.cli test   --model lenet  -f ./mnist \
        --checkpoint ./ckpt
    python -m bigdl_tpu.models.cli perf   --model inception_v1 -b 64 -i 10
    python -m bigdl_tpu.models.cli serve  --model lenet --port 8000 -b 32
    python -m bigdl_tpu.models.cli summary   --model lenet
    python -m bigdl_tpu.models.cli attribute --model transformer
    python -m bigdl_tpu.models.cli supervise -n 4 -- \
        python -m bigdl_tpu.models.cli train --model lenet --distributed \
        --checkpoint ./ckpt

``train`` runs the full Optimizer loop (validation every epoch, optional
checkpointing and TensorBoard summaries, resume from snapshot);
``test`` reloads a checkpoint and evaluates Top1/Top5; ``perf`` is the
LocalOptimizerPerf protocol (synthetic data, records/sec after warmup);
``summary`` prints the Torch-style per-layer table (path, output shape
via eval_shape, params); ``attribute`` prints the per-module FLOPs/bytes
cost table (docs/observability.md).  Missing dataset folders fall back
to synthetic data so every command is runnable anywhere.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time
from typing import Optional, Tuple

import numpy as np


def _build_model(name: str, num_classes: int):
    # one shared name->builder table with the static analyzer
    # (python -m bigdl_tpu.analysis), see models/registry.py
    from bigdl_tpu.models import registry

    if name not in registry.MODELS:
        raise SystemExit(f"unknown --model {name!r}; choose from "
                         f"{registry.model_names()}")
    return registry.build_model(name, num_classes)


#: sequence models take [batch, time] int token ids, not images.
# shared with the analyzer's canonical input specs (models/registry.py)
from bigdl_tpu.models.registry import (  # noqa: E402
    LANGUAGE_MODELS, LM_SEQ_LEN, LSTM_SEQ_LEN, LSTM_VOCAB)

SEQ_MODELS = ("lstm",) + LANGUAGE_MODELS


@functools.lru_cache(maxsize=2)
def _news20_corpus(folder: Optional[str], vocab_size: int):
    """(dictionary, [per-doc token lists], [labels]) for news20 — cached so
    cmd_train's two _load_data calls read/tokenize the corpus once.

    The vocabulary always comes from the TRAIN split so train/test token
    ids agree.  Documents are tokenized one-by-one (a doc that tokenizes
    to nothing yields an empty list, NOT a dropped row) so tokens stay
    aligned index-for-index with labels."""
    from bigdl_tpu.dataset import datasets
    from bigdl_tpu.dataset.text import Dictionary, SentenceTokenizer

    all_pairs = datasets.load_news20(folder)
    tok = SentenceTokenizer()

    def tokens_of(text):
        out = list(tok.apply(iter([text])))
        return out[0] if out else []

    docs = [tokens_of(t) for t, _ in all_pairs]
    labels = [lab for _, lab in all_pairs]
    # Dictionary keeps vocab_size words + an UNK row, and ids are shifted
    # by 1 to reserve 0 for padding, so cap at vocab_size - 2 to keep
    # every id (UNK included) < vocab_size
    dic = Dictionary((d for i, d in enumerate(docs) if i % 5 != 4),
                     vocab_size=max(1, vocab_size - 2))
    return dic, docs, labels


def _load_token_data(model_name: str, folder: Optional[str], split: str,
                     vocab_size: int) -> Tuple[np.ndarray, np.ndarray]:
    """Token-shaped data for the sequence models: news20 text run through
    the text pipeline (tokenize -> dictionary -> fixed-length ids).

    ``lstm``  -> (tokens [N,LSTM_SEQ_LEN] int, class labels [N]);
    ``transformer`` -> (tokens [N,T] int, next-token targets [N,T])."""
    dic, docs, labels = _news20_corpus(folder, vocab_size)
    # deterministic split: every 5th doc is test, the rest train
    keep = [i for i in range(len(docs))
            if (i % 5 == 4) == (split == "test")]
    ids = [np.asarray([dic.index(w) + 1 for w in docs[i]], np.int32)
           for i in keep]  # reserve 0 for padding
    if model_name == "lstm":
        seq_len = LSTM_SEQ_LEN
        x = np.zeros((len(ids), seq_len), np.int32)
        for i, t in enumerate(ids):
            x[i, :min(len(t), seq_len)] = t[:seq_len]
        y = np.asarray([labels[i] for i in keep], np.int64)
        return x, y
    # transformer LM: one long stream chunked into next-token windows
    stream = np.concatenate(ids) if ids else np.zeros(0, np.int32)
    n = max(1, len(stream) // (LM_SEQ_LEN + 1))
    stream = np.resize(stream, n * (LM_SEQ_LEN + 1))
    chunks = stream.reshape(n, LM_SEQ_LEN + 1)
    return chunks[:, :-1].astype(np.int32), chunks[:, 1:].astype(np.int64)


def _load_data(model_name: str, folder: Optional[str], split: str,
               num_classes: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    from bigdl_tpu.dataset import datasets

    if model_name in SEQ_MODELS:
        vocab = (LSTM_VOCAB if model_name == "lstm"
                 else (num_classes or 256))
        return _load_token_data(model_name, folder, split, vocab)
    if model_name in ("lenet", "autoencoder"):
        imgs, labels = datasets.load_mnist(folder, split)
        x = ((imgs.astype(np.float32) / 255.0) - 0.1307) / 0.3081
        x = x.reshape(-1, 1, 28, 28)
    else:
        imgs, labels = datasets.load_cifar10(folder, split)
        x = imgs.astype(np.float32) / 255.0
        x = (x - x.mean((0, 1, 2))) / (x.std((0, 1, 2)) + 1e-7)
        x = x.transpose(0, 3, 1, 2)
    return x, labels


def cmd_train(args) -> None:
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.rng import RNG

    if getattr(args, "distributed", False):
        # join the cluster FIRST: jax.distributed.initialize must run
        # before any jax computation, and building the model below
        # already executes some — without this, a multi-process
        # `train --distributed` (e.g. under `supervise`) dies at
        # DistriOptimizer construction
        Engine.init()
    RNG.set_seed(args.seed)
    x, y = _load_data(args.model, args.folder, "train", args.num_classes)
    xt, yt = _load_data(args.model, args.folder, "test", args.num_classes)
    num_classes = args.num_classes
    if args.model == "lstm" and not num_classes:
        num_classes = int(max(y.max(), yt.max())) + 1
    model = _build_model(args.model, num_classes)
    if args.model_snapshot:
        from bigdl_tpu.utils import serializer

        model = serializer.load_module(args.model_snapshot)

    if args.model == "autoencoder":
        flat = x.reshape(len(x), -1)
        samples = [Sample(flat[i], flat[i]) for i in range(len(flat))]
        criterion = nn.MSECriterion()
        val_methods = [optim.Loss(nn.MSECriterion())]
        val_samples = samples[:256]
    elif args.model in LANGUAGE_MODELS:
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        criterion = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), size_average=True)
        val_methods = [optim.Loss(
            nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), size_average=True))]
        val_samples = [Sample(xt[i], yt[i]) for i in range(len(xt))]
    else:
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        criterion = nn.ClassNLLCriterion()
        val_methods = [optim.Top1Accuracy(), optim.Top5Accuracy()]
        val_samples = [Sample(xt[i], yt[i]) for i in range(len(xt))]

    method = optim.SGD(learning_rate=args.learning_rate,
                       momentum=args.momentum,
                       weight_decay=args.weight_decay)
    if args.state_snapshot:
        from bigdl_tpu.utils import serializer

        method = serializer.load_optim_method(args.state_snapshot)

    if getattr(args, "distributed", False):
        # the reference's Train mains are the DISTRIBUTED entry points
        # (spark-submit + Engine.init); here: Engine mesh over every
        # addressable device, same loop
        o = optim.DistriOptimizer(
            model, samples, criterion, batch_size=args.batch_size,
            end_trigger=optim.Trigger.max_epoch(args.max_epoch))
    else:
        o = optim.LocalOptimizer(
            model, samples, criterion, batch_size=args.batch_size,
            end_trigger=optim.Trigger.max_epoch(args.max_epoch))
    o.set_optim_method(method)
    o.set_validation(optim.Trigger.every_epoch(), val_samples, val_methods,
                     batch_size=args.batch_size)
    if args.checkpoint:
        o.set_checkpoint(args.checkpoint, optim.Trigger.every_epoch())
    if args.summary_dir:
        from bigdl_tpu.visualization import TrainSummary, ValidationSummary

        o.set_train_summary(TrainSummary(args.summary_dir, args.app_name))
        o.set_validation_summary(
            ValidationSummary(args.summary_dir, args.app_name))
    trained = o.optimize()
    if getattr(o, "preempted", False):
        # graceful SIGTERM/SIGINT: the final checkpoint is committed;
        # exit 0 — rerunning this exact command resumes mid-epoch
        # (docs/fault_tolerance.md).  The hint names the topology the
        # checkpoint can restore onto (it is topology-PORTABLE — a
        # shrunk slice resumes on fewer chips) and the capacity-aware
        # supervise recipe, not just "re-run me".
        print(f"preempted at iteration {o.state['neval']} "
              f"(epoch {o.state['epoch']}); checkpoint committed"
              + (f" under {args.checkpoint}" if args.checkpoint else "")
              + " — rerun to resume")
        hint = o.resume_hint()
        if hint:
            print(hint)
        return
    res = optim.Evaluator(trained, batch_size=args.batch_size).evaluate(
        val_samples, val_methods)
    for r, m in res:
        print(f"final {m}: {r}")


def cmd_test(args) -> None:
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.utils import serializer

    if args.model_snapshot:
        model = serializer.load_module(args.model_snapshot)
    elif args.checkpoint:
        import glob

        cands = sorted(glob.glob(os.path.join(args.checkpoint, "**",
                                              "model.*"), recursive=True),
                       key=os.path.getmtime)
        if not cands:
            raise SystemExit(f"no model.* snapshot under {args.checkpoint}")
        model = serializer.load_module(cands[-1])
    else:
        raise SystemExit("test needs --model-snapshot or --checkpoint")
    x, y = _load_data(args.model, args.folder, "test", args.num_classes)
    samples = [Sample(x[i], y[i]) for i in range(len(x))]
    if args.model in LANGUAGE_MODELS:
        methods = [optim.Loss(
            nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), size_average=True))]
    else:
        methods = [optim.Top1Accuracy(), optim.Top5Accuracy()]
    from bigdl_tpu import telemetry

    with telemetry.maybe_run(meta={"cmd": "test",
                                   "model": args.model}) as owned_log:
        res = optim.Evaluator(model, batch_size=args.batch_size).evaluate(
            samples, methods)
    if owned_log:
        print(f"telemetry run log: {owned_log}")
    for r, m in res:
        print(f"{m}: {r}")


def cmd_perf(args) -> None:
    """LocalOptimizerPerf protocol: synthetic data, records/sec."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.parallel.train_step import TrainStep
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(0)
    num_classes = args.num_classes or (
        256 if args.model in LANGUAGE_MODELS
        else {"lstm": 2}.get(args.model, 1000))
    model = _build_model(args.model, num_classes)
    rng = np.random.default_rng(0)
    criterion = nn.ClassNLLCriterion()
    if args.model in SEQ_MODELS:
        if args.model == "lstm":
            x = rng.integers(0, LSTM_VOCAB,
                             (args.batch_size, LSTM_SEQ_LEN),
                             dtype=np.int32)
            y = rng.integers(0, num_classes, args.batch_size)
        else:
            # num_classes doubles as the LM vocab, matching _build_model
            x = rng.integers(0, num_classes,
                             (args.batch_size, LM_SEQ_LEN), dtype=np.int32)
            y = rng.integers(0, num_classes, (args.batch_size, LM_SEQ_LEN))
            criterion = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), size_average=True)
        x, y = jnp.asarray(x), jnp.asarray(y)
    else:
        shape = {"lenet": (1, 28, 28), "autoencoder": (1, 28, 28)}.get(
            args.model, (3, 224, 224))
        if args.model in ("vgg_cifar", "resnet"):
            shape = (3, 32, 32)
        x = jnp.asarray(rng.normal(size=(args.batch_size,) + shape)
                        .astype(np.float32))
        if args.model == "autoencoder":
            criterion = nn.MSECriterion()
            y = x.reshape(args.batch_size, -1)
        else:
            y = jnp.asarray(rng.integers(0, num_classes, args.batch_size))
    from bigdl_tpu import telemetry

    with telemetry.maybe_run(meta={"cmd": "perf", "model": args.model,
                                   "batch": args.batch_size}) as owned_log:
        step = TrainStep(model, criterion,
                         optim.SGD(learning_rate=0.01, momentum=0.9),
                         compute_dtype=jnp.bfloat16 if args.bf16 else None)
        with telemetry.span("perf/warmup", iters=args.warmup):
            for i in range(args.warmup):
                step.run(x, y, jax.random.key(i))
            if args.warmup:
                # drain the queue incl. the last warmup optimizer update
                float(jnp.sum(jax.tree_util.tree_leaves(step.params)[0]))
        with telemetry.span("perf/timed", iters=args.iteration):
            t0 = time.perf_counter()
            for i in range(args.iteration):
                step.run(x, y, jax.random.key(100 + i))
            # params-derived fetch forces the LAST iteration's optimizer
            # update inside the timed window (loss_i only depends on
            # params_{i-1})
            float(jnp.sum(jax.tree_util.tree_leaves(step.params)[0]))
            wall = time.perf_counter() - t0
        rate = args.batch_size * args.iteration / wall
        telemetry.counter("perf/records_per_sec", rate)
    if owned_log:
        print(f"telemetry run log: {owned_log}")
    print(f"{args.model}: {rate:.1f} records/sec "
          f"(batch {args.batch_size}, {args.iteration} iters, "
          f"{wall:.2f}s)")


def cmd_serve(args) -> None:
    """Production inference serving (docs/serving.md): HTTP frontend ->
    bounded queue -> continuous batcher -> bucketed AOT executables,
    warmed before the ready line prints.  SIGTERM drains gracefully."""
    import jax.numpy as jnp

    from bigdl_tpu import telemetry
    from bigdl_tpu.models import registry
    from bigdl_tpu.serving import serve_model
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(args.seed)  # fresh-registry weights reproducible
    if args.model_snapshot:
        from bigdl_tpu.utils import serializer

        model = serializer.load_module(args.model_snapshot)
    elif args.generate:
        # scan stacks cannot be cache-addressed; the shared build rule
        # (unrolled transformer etc.) lives beside the decode subsystem
        from bigdl_tpu.serving.generate import generation_model

        try:
            model = generation_model(args.model, args.num_classes)
        except ValueError as e:
            raise SystemExit(str(e)) from None
    else:
        model = _build_model(args.model, args.num_classes)
    spec = registry.input_spec(args.model, 1)
    if args.int8:
        from bigdl_tpu.nn.quantized import calibrate, quantize

        model = quantize(model)
        # calibrated static activation scales: the serve path must
        # never pay the dynamic per-layer amax reduce (BASELINE.md
        # round-6) — one synthetic batch at the canonical input spec
        rng = np.random.default_rng(0)
        shape = (min(8, args.batch_size),) + tuple(spec.shape[1:])
        if np.issubdtype(np.dtype(spec.dtype), np.integer):
            calib = rng.integers(0, 256, shape).astype(spec.dtype)
        else:
            calib = rng.normal(size=shape).astype(spec.dtype)
        calibrate(model, [calib])

    def _buckets(text):
        return [int(b) for b in text.split(",")] if text else None

    seq_buckets = _buckets(args.seq_buckets)
    if args.generate and not seq_buckets:
        from bigdl_tpu.serving.generate import default_seq_buckets

        seq_buckets = default_seq_buckets(spec)
    with telemetry.maybe_run(meta={"cmd": "serve", "model": args.model,
                                   "batch": args.batch_size}):
        server = serve_model(
            model, spec, name=args.model, port=args.port,
            max_batch=args.batch_size, max_wait_ms=args.max_wait_ms,
            queue_limit=args.queue_limit,
            batch_buckets=_buckets(args.buckets),
            seq_buckets=seq_buckets,
            compute_dtype=jnp.bfloat16 if args.bf16 and not args.int8
            else None,
            request_timeout_s=args.request_timeout,
            generate=args.generate,
            decode_buckets=_buckets(args.decode_buckets),
            cache_buckets=_buckets(args.cache_buckets),
            max_new_tokens_limit=args.max_new_tokens_limit,
            slo_p99_ms=args.slo_p99_ms, slo_ttft_ms=args.slo_ttft_ms)
        # readiness line AFTER warmup: every bucket is compiled once
        # this prints — tests and load balancers key off it
        gen = ""
        if args.generate:
            gen = (f", generate decode={list(server.executor.decode_buckets)}"
                   f" cache={list(server.executor.cache_buckets)}")
        print(f"serving {args.model} on port {server.port} "
              f"(buckets {list(server.executor.policy.batch_buckets)}, "
              f"warmup {server.executor.warmup_s:.1f}s{gen})", flush=True)
        server.install_signal_handlers()
        server.wait()
        server.stop(drain=True)
        st = server.batcher
        print(f"drained: {st.requests} requests, {st.rejected} rejected, "
              f"{st.batches} batches", flush=True)


def cmd_supervise(args) -> None:
    """Supervised elastic cluster launch (parallel/cluster.py): run N
    copies of a worker command as a jax.distributed cluster, let the
    collective watchdog turn peer loss into clean aborts instead of
    hung all-reduces, and restart the full cluster from the last
    cluster-consistent checkpoint when an incarnation dies."""
    import logging

    logging.basicConfig(level=logging.INFO)
    from bigdl_tpu.parallel.cluster import Supervisor

    command = list(args.command or [])
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        raise SystemExit(
            "supervise needs a worker command, e.g.:\n"
            "  python -m bigdl_tpu.models.cli supervise -n 4 -- "
            "python -m bigdl_tpu.models.cli train --model lenet "
            "--distributed --checkpoint ./ckpt")
    sup = Supervisor(nprocs=args.nprocs, command=command,
                     max_restarts=args.max_restarts,
                     cluster_dir=args.cluster_dir,
                     keep_faults=args.keep_faults,
                     log_dir=args.log_dir,
                     min_nprocs=args.min_n)
    from bigdl_tpu import telemetry

    # the supervisor's own run log is the incarnation-chain spine the
    # goodput ledger stitches against: cluster/restart (with backoff_s),
    # cluster/reshard and cluster/drain instants land here instead of
    # being dropped on the floor (BIGDL_TELEMETRY gates it, as for the
    # workers — which inherit the same dir through the environment)
    # device_facts=False: the supervisor never initializes a jax
    # backend — a parent that holds the chip starves its own workers
    with telemetry.maybe_run(meta={"cmd": "supervise",
                                   "role": "supervisor",
                                   "declared_n": args.nprocs},
                             device_facts=False):
        rc = sup.run()
    raise SystemExit(rc)


def cmd_summary(args) -> None:
    """Torch-style per-layer table over a registry model — reuses the
    module-path machinery the cost attribution is built on."""
    from bigdl_tpu.models.registry import input_spec

    model = _build_model(args.model, args.num_classes)
    print(model.summary(input_spec(args.model, args.batch_size)))


def cmd_attribute(args) -> None:
    """Per-module FLOPs/bytes table (telemetry/attribution.py), or the
    per-collective comms view (telemetry/comms.py) with ``--comms``."""
    import json

    from bigdl_tpu.telemetry import attribution

    if args.comms and args.memory:
        raise SystemExit("--comms and --memory are different views — "
                         "pass one")
    if args.comms:
        from bigdl_tpu.telemetry import comms

        result = comms.attribute_comms_model(
            args.model, batch=args.batch_size, devices=args.mesh,
            sync=args.sync, sparse=args.sparse)
        print(json.dumps(result, indent=2, default=str) if args.json
              else comms.format_comms(result))
        return
    if args.memory:
        from bigdl_tpu.telemetry import memory as tmem

        result = tmem.attribute_memory_model(
            args.model, batch=args.batch_size, devices=args.mesh,
            sync=args.sync)
        print(json.dumps(result, indent=2, default=str) if args.json
              else tmem.format_memory(result))
        return
    result = attribution.attribute_model(
        args.model, batch=args.batch_size, train=not args.forward)
    if args.json:
        print(json.dumps(result, indent=2, default=str))
    else:
        print(attribution.format_attribution(result))


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="bigdl_tpu.models.cli",
                                description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp):
        sp.add_argument("--model", default="lenet")
        sp.add_argument("-f", "--folder", default=None,
                        help="dataset folder (synthetic data when absent)")
        sp.add_argument("-b", "--batch-size", type=int, default=64)
        sp.add_argument("--num-classes", type=int, default=0)
        sp.add_argument("--telemetry", default=None, metavar="DIR",
                        help="write a JSONL telemetry run log under DIR "
                             "(same as BIGDL_TELEMETRY; inspect with "
                             "python -m bigdl_tpu.telemetry)")
        sp.add_argument("--metrics-port", type=int, default=None,
                        metavar="PORT",
                        help="serve live OpenMetrics (/metrics) + JSON "
                             "status (/status) on PORT while the run is "
                             "alive (0 = ephemeral; same as "
                             "BIGDL_METRICS_PORT; needs --telemetry or "
                             "BIGDL_TELEMETRY)")

    t = sub.add_parser("train", help="train a zoo model")
    common(t)
    t.add_argument("--learning-rate", type=float, default=0.05)
    t.add_argument("--momentum", type=float, default=0.9)
    t.add_argument("--weight-decay", type=float, default=0.0)
    t.add_argument("--max-epoch", type=int, default=2)
    t.add_argument("--checkpoint", default=None)
    t.add_argument("--summary-dir", default=None)
    t.add_argument("--app-name", default="bigdl_tpu")
    t.add_argument("--model-snapshot", default=None,
                   help="resume model from snapshot")
    t.add_argument("--state-snapshot", default=None,
                   help="resume optim method from snapshot")
    t.add_argument("--seed", type=int, default=42)
    t.add_argument("--distributed", action="store_true",
                   help="train on the Engine mesh over every addressable "
                        "device (the reference's spark-submit Train mode)")
    t.set_defaults(fn=cmd_train)

    te = sub.add_parser("test", help="evaluate a checkpointed model")
    common(te)
    te.add_argument("--checkpoint", default=None)
    te.add_argument("--model-snapshot", default=None)
    te.set_defaults(fn=cmd_test)

    pf = sub.add_parser("perf", help="synthetic-data throughput harness")
    common(pf)
    pf.add_argument("-i", "--iteration", type=int, default=10)
    pf.add_argument("--warmup", type=int, default=3)
    pf.add_argument("--bf16", action="store_true", default=True)
    pf.add_argument("--no-bf16", dest="bf16", action="store_false")
    pf.set_defaults(fn=cmd_perf)

    se = sub.add_parser("serve", help="serve a zoo model over HTTP: "
                                      "continuous batching, shape "
                                      "buckets, AOT-warmed executables "
                                      "(docs/serving.md)")
    common(se)
    se.add_argument("--port", type=int, default=8000,
                    help="HTTP port (0 = ephemeral, printed on the "
                         "ready line)")
    se.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="batcher coalescing deadline from the oldest "
                         "queued request (default %(default)s)")
    se.add_argument("--queue-limit", type=int, default=256,
                    help="bounded request queue; past it requests get "
                         "429 (default %(default)s)")
    se.add_argument("--buckets", default=None, metavar="N,N,...",
                    help="batch buckets (default: powers of two up to "
                         "--batch-size)")
    se.add_argument("--seq-buckets", default=None, metavar="T,T,...",
                    help="sequence buckets for token models (default: "
                         "the model's fixed sequence length)")
    se.add_argument("--int8", action="store_true",
                    help="serve the quantized model with calibrated "
                         "static activation scales")
    se.add_argument("--bf16", action="store_true",
                    help="bf16 forward with f32 params (ignored with "
                         "--int8)")
    se.add_argument("--request-timeout", type=float, default=30.0,
                    help="per-request dispatch timeout seconds")
    se.add_argument("--model-snapshot", default=None,
                    help="serve a .btpu snapshot instead of fresh "
                         "registry weights")
    se.add_argument("--seed", type=int, default=42,
                    help="weight-init seed for fresh registry weights")
    se.add_argument("--generate", action="store_true",
                    help="causal token models: enable POST /v1/generate"
                         " — KV-cached decode, continuous batching, "
                         "token streaming (docs/serving.md)")
    se.add_argument("--decode-buckets", default=None, metavar="B,B,...",
                    help="--generate: decode batch buckets; the largest"
                         " is the max concurrent sequences (default "
                         "1,2,4,8)")
    se.add_argument("--cache-buckets", default=None, metavar="C,C,...",
                    help="--generate: KV cache-length buckets (default:"
                         " doubling from the smallest seq bucket to the"
                         " model's max_len)")
    se.add_argument("--max-new-tokens-limit", type=int, default=1024,
                    help="--generate: per-request max_new_tokens cap")
    se.add_argument("--slo-p99-ms", type=float, default=None,
                    metavar="MS",
                    help="declared request-latency p99 budget: live "
                         "burn-rate gauges on /metrics + /status.slo, "
                         "violating requests keep their trace ids "
                         "(docs/observability.md)")
    se.add_argument("--slo-ttft-ms", type=float, default=None,
                    metavar="MS",
                    help="--generate: declared time-to-first-token "
                         "p99 budget (same burn accounting)")
    se.set_defaults(fn=cmd_serve)

    sv = sub.add_parser("supervise",
                        help="launch + babysit an N-process cluster: "
                             "watchdog-clean peer-loss aborts, bounded "
                             "restarts from the last cluster-consistent "
                             "checkpoint (docs/fault_tolerance.md)")
    sv.add_argument("-n", "--nprocs", type=int, required=True,
                    help="cluster size (one jax process per slot)")
    sv.add_argument("--max-restarts", type=int, default=5,
                    help="full-cluster restarts before giving up")
    sv.add_argument("--min-n", type=int, default=None, metavar="M",
                    help="capacity-aware floor: when restart attempts "
                         "at -n keep dying on the same missing peer, "
                         "relaunch DEGRADED at M processes instead of "
                         "burning the restart budget (the topology-"
                         "portable checkpoint reshards on load; grows "
                         "back to -n on the next full-capacity restart)")
    sv.add_argument("--cluster-dir", default=None,
                    help="shared heartbeat/commit dir (default: a fresh "
                         "temp dir; must be shared storage on real "
                         "multi-host fleets)")
    sv.add_argument("--log-dir", default=None,
                    help="capture each worker's stdout+stderr to "
                         "<dir>/inc<k>.p<i>.log (a SIGKILLed worker "
                         "leaves no flight dump — this is the "
                         "supervisor-side postmortem record)")
    sv.add_argument("--keep-faults", action="store_true",
                    help="keep BIGDL_FAULTS for restart incarnations "
                         "(default: cleared — an injected fault plan "
                         "describes one scenario, not every restart)")
    sv.add_argument("command", nargs=argparse.REMAINDER, metavar="-- cmd",
                    help="worker command to run n times with the "
                         "cluster env injected")
    sv.set_defaults(fn=cmd_supervise)

    sm = sub.add_parser("summary", help="Torch-style per-layer table "
                                        "(shapes via eval_shape)")
    common(sm)
    sm.set_defaults(fn=cmd_summary)

    at = sub.add_parser("attribute", help="per-module FLOPs/bytes cost "
                                          "attribution table (--comms: "
                                          "per-collective bytes/axes)")
    common(at)
    at.add_argument("--forward", action="store_true",
                    help="attribute the inference forward instead of "
                         "the full train step")
    at.add_argument("--comms", action="store_true",
                    help="per-collective comms view: bytes moved, mesh "
                         "axes, owning modules (telemetry/comms.py)")
    at.add_argument("--memory", action="store_true",
                    help="per-module HBM view: params / optimizer "
                         "state / activations-at-peak per device "
                         "(telemetry/memory.py)")
    at.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="(--comms/--memory) data-axis mesh size to "
                         "shard over (default: all local devices for "
                         "--comms, single device for --memory)")
    at.add_argument("--sync", default="allreduce",
                    choices=("allreduce", "sharded", "fsdp", "local"),
                    help="(--comms/--memory) parameter_sync mode to "
                         "compile with (local = local-SGD islands, "
                         "parallel/local_sync.py)")
    at.add_argument("--sparse", default=None,
                    choices=("off", "auto", "on"),
                    help="(--comms) override BIGDL_SPARSE for this "
                         "compile — A/B the sparse embedding sync "
                         "(docs/sparse.md)")
    at.add_argument("--json", action="store_true")
    # same default batch as `python -m bigdl_tpu.telemetry attribute`:
    # the two front-ends of one table must print the same numbers
    at.set_defaults(fn=cmd_attribute, batch_size=8)

    args = p.parse_args(argv)
    if getattr(args, "telemetry", None):
        # the env route keeps one resolution path (utils/config.py);
        # the Optimizer / perf harness start the run from config
        os.environ["BIGDL_TELEMETRY"] = args.telemetry
    if getattr(args, "metrics_port", None) is not None:
        os.environ["BIGDL_METRICS_PORT"] = str(args.metrics_port)
    args.fn(args)


if __name__ == "__main__":
    main()
