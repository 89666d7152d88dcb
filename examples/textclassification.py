#!/usr/bin/env python
"""Text classification example — CNN over word embeddings on the
20-newsgroups layout (reference ``example/textclassification/`` +
``example/utils/TextClassifier.scala``, SURVEY §2.13).

Pipeline (mirroring ``TextClassifier.scala``): tokenize -> build the
vocabulary -> embed each document into a ``[embed_dim, 1, seq_len]`` map
(GloVe vectors when ``--glove`` points at ``glove.6B.<dim>d.txt``;
deterministic random vectors otherwise, this image has no egress) ->
the 5x1 conv/pool stack (``TextClassifier.scala:171-194``) -> Optimizer
with ClassNLLCriterion -> Top1Accuracy validation.

Run: ``python examples/textclassification.py --max-epoch 2``
"""

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def tokenize(text):
    """Lowercase word split (SimpleTokenizer.scala equivalent)."""
    return re.findall(r"[a-z']+", text.lower())


def build_word_index(texts, max_words):
    """Most-frequent-first vocabulary; index 0 is the padding slot."""
    from collections import Counter

    counts = Counter(w for t in texts for w in tokenize(t))
    return {w: i + 1 for i, (w, _) in
            enumerate(counts.most_common(max_words))}


def load_embeddings(word_index, embed_dim, glove_path=None):
    """[vocab+1, embed_dim] embedding matrix: GloVe rows when available,
    seeded random otherwise; row 0 (padding) stays zero."""
    rng = np.random.default_rng(42)
    table = rng.normal(0, 0.4, (len(word_index) + 1, embed_dim)) \
        .astype(np.float32)
    table[0] = 0.0
    if glove_path and os.path.exists(glove_path):
        with open(glove_path, errors="ignore") as f:
            for line in f:
                parts = line.rstrip().split(" ")
                if parts[0] in word_index and len(parts) == embed_dim + 1:
                    table[word_index[parts[0]]] = np.asarray(
                        parts[1:], np.float32)
    return table


def vectorize(text, word_index, table, seq_len):
    """One document -> [embed_dim, 1, seq_len] (the reference's
    Reshape(embeddingDim, 1, maxSequenceLength) input layout)."""
    ids = [word_index.get(w, 0) for w in tokenize(text)][:seq_len]
    ids = ids + [0] * (seq_len - len(ids))
    return table[np.asarray(ids)].T[:, None, :]  # (D, 1, S)


def build_model(class_num, embed_dim, seq_len):
    """The conv stack of ``TextClassifier.scala:171-194`` (pool sizes
    scaled to the configured sequence length)."""
    import bigdl_tpu.nn as nn

    # spatial extent left before the last (global) pool: conv5 -> pool5
    # -> conv5 -> pool5 -> conv5 (the reference's 35 for seq_len 1000)
    final = ((seq_len - 4) // 5 - 4) // 5 - 4
    if final < 1:
        raise ValueError(f"seq_len {seq_len} too short for the conv stack")
    return nn.Sequential(
        nn.SpatialConvolution(embed_dim, 128, 5, 1),
        nn.ReLU(),
        nn.SpatialMaxPooling(5, 1, 5, 1),
        nn.SpatialConvolution(128, 128, 5, 1),
        nn.ReLU(),
        nn.SpatialMaxPooling(5, 1, 5, 1),
        nn.SpatialConvolution(128, 128, 5, 1),
        nn.ReLU(),
        nn.SpatialMaxPooling(final, 1, final, 1),
        nn.Reshape([128]),
        nn.Linear(128, 100),
        nn.Linear(100, class_num),
        nn.LogSoftMax(),
    )


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--data-dir", help="20-newsgroups directory "
                   "(one subdir per group); synthetic when absent")
    p.add_argument("--glove", help="path to glove.6B.<dim>d.txt")
    p.add_argument("--embed-dim", type=int, default=50)
    p.add_argument("--seq-len", type=int, default=500)
    p.add_argument("--max-words", type=int, default=5000)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--max-epoch", type=int, default=3)
    p.add_argument("--learning-rate", type=float, default=0.05)
    p.add_argument("--synthetic-size", type=int, default=400)
    args = p.parse_args(argv)

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.datasets import load_news20
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(1)

    pairs = load_news20(args.data_dir, synthetic_size=args.synthetic_size)
    texts = [t for t, _ in pairs]
    labels = [l for _, l in pairs]
    class_num = max(labels) + 1
    word_index = build_word_index(texts, args.max_words)
    table = load_embeddings(word_index, args.embed_dim, args.glove)

    samples = [Sample(vectorize(t, word_index, table, args.seq_len),
                      np.int64(l)) for t, l in pairs]
    # real 20-newsgroups data arrives grouped by class directory — a
    # seeded shuffle keeps every class on both sides of the split
    order = np.random.default_rng(7).permutation(len(samples))
    samples = [samples[i] for i in order]
    split = int(0.8 * len(samples))
    train, val = samples[:split], samples[split:]

    model = build_model(class_num, args.embed_dim, args.seq_len)
    o = optim.Optimizer(model=model, dataset=train,
                        criterion=nn.ClassNLLCriterion(),
                        batch_size=args.batch_size,
                        end_trigger=optim.Trigger.max_epoch(args.max_epoch))
    o.set_optim_method(optim.SGD(learning_rate=args.learning_rate,
                                 momentum=0.9))
    o.set_validation(optim.Trigger.every_epoch(), val,
                     [optim.Top1Accuracy()], batch_size=args.batch_size)
    trained = o.optimize()

    res = optim.Evaluator(trained).evaluate(val, [optim.Top1Accuracy()])
    acc = res[0][0].result()[0]
    print(f"[textclassification] validation accuracy: {acc:.4f}")
    return trained, word_index, table, acc


if __name__ == "__main__":
    main()
