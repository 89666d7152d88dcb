#!/usr/bin/env python
"""TreeLSTM sentiment example — constituency-tree sentiment classification
(reference ``example/treeLSTMSentiment/Train.scala:33`` +
``TreeSentiment.scala:25``: GloVe embeddings -> BinaryTreeLSTM ->
per-node TimeDistributed classifier, trained with Adagrad under
TimeDistributedCriterion).

Data: each sample is (token ids [L], tree [N, 3]) with a sentiment class
per tree node (SST labels every constituent).  Tree rows are
(left, right, leaf) 1-based node indices, children before parents — the
repo's ``BinaryTreeLSTM`` scan order.  Real SST data (prepared per the
reference's ``fetch_and_preprocess.py``) can be dropped in; without it
the example synthesizes a word-polarity corpus so it always runs.

Run: ``python examples/treelstm_sentiment.py [-b 16] [-e 4]``
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

PAD, OOV, INDEX_FROM = 1, 2, 3  # the reference's paddingValue/oovChar


def build_model(vocab_size, embed_dim, hidden, classes, p=0.5,
                embeddings=None):
    """``TreeSentiment.scala:25`` re-built on the repo's layer family."""
    import bigdl_tpu.nn as nn

    embedding = nn.LookupTable(vocab_size, embed_dim)
    if embeddings is not None:
        embedding.weight = np.asarray(embeddings, np.float32)
    embedding.set_scale_w(2.0)

    return nn.Sequential(
        nn.ParallelTable().add(embedding).add(nn.Identity()),
        nn.BinaryTreeLSTM(embed_dim, hidden),
        nn.TimeDistributed(nn.Sequential(
            nn.Dropout(p), nn.Linear(hidden, classes), nn.LogSoftMax())),
    )


def synthetic_corpus(n=256, vocab=50, leaves=8, seed=0):
    """Word-polarity sentences under random binary trees: each word
    INDEX_FROM.. is positive (even id) or negative (odd id); every node is
    labeled by its subtree's majority polarity — the SST per-constituent
    labeling scheme at toy scale."""
    rng = np.random.default_rng(seed)
    samples = []
    n_nodes = 2 * leaves - 1
    for _ in range(n):
        tokens = rng.integers(INDEX_FROM, vocab, leaves)
        polarity = np.where(tokens % 2 == 0, 1, -1)
        # random binary tree: combine two subtree roots until one remains
        tree = np.zeros((n_nodes, 3), np.int32)
        score = {}
        for i in range(leaves):
            tree[i] = (0, 0, i + 1)
            score[i + 1] = int(polarity[i])
        roots = list(range(1, leaves + 1))
        nxt = leaves + 1
        while len(roots) > 1:
            i = rng.integers(0, len(roots) - 1)
            l, r = roots.pop(i), roots.pop(i)
            tree[nxt - 1] = (l, r, 0)
            score[nxt] = score[l] + score[r]
            roots.append(nxt)
            nxt += 1
        labels = np.array([0 if score[i + 1] <= 0 else 1
                           for i in range(n_nodes)], np.int64)
        samples.append((tokens.astype(np.int64), tree, labels))
    return samples


def root_accuracy(model, samples, batch_size=32):
    """Root-node accuracy (TreeNNAccuracy's job; the repo's trees list the
    root LAST, so index -1)."""
    import jax.numpy as jnp

    model.evaluate()
    hits = total = 0
    for i in range(0, len(samples), batch_size):
        chunk = samples[i:i + batch_size]
        toks = jnp.asarray(np.stack([s[0] for s in chunk]))
        trees = jnp.asarray(np.stack([s[1] for s in chunk]))
        out = np.asarray(model.forward([toks, trees]))
        pred = out[:, -1, :].argmax(-1)
        hits += int((pred == np.stack([s[2] for s in chunk])[:, -1]).sum())
        total += len(chunk)
    model.training_mode()
    return hits / total


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-b", "--batchSize", type=int, default=16)
    p.add_argument("-e", "--maxEpoch", type=int, default=4)
    p.add_argument("--hiddenSize", type=int, default=32)
    p.add_argument("--embedDim", type=int, default=16)
    p.add_argument("--learningRate", type=float, default=0.1)
    p.add_argument("--dropout", type=float, default=0.2)
    args = p.parse_args(argv)

    from bigdl_tpu.utils.logging import redirect_thirdparty_logs

    redirect_thirdparty_logs()

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(5)
    vocab, classes = 50, 2
    data = synthetic_corpus(n=256, vocab=vocab)
    train = [Sample([t, tr], lb) for t, tr, lb in data[:192]]
    dev = data[192:]

    model = build_model(vocab, args.embedDim, args.hiddenSize, classes,
                        p=args.dropout)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)
    before = root_accuracy(model, dev)
    o = optim.LocalOptimizer(model, train, crit,
                             batch_size=args.batchSize,
                             end_trigger=optim.Trigger.max_epoch(args.maxEpoch))
    o.set_optim_method(optim.Adagrad(learning_rate=args.learningRate))
    o.optimize()
    after = root_accuracy(model, dev)
    print(f"dev root accuracy: {before:.3f} -> {after:.3f} "
          f"({len(train)} train / {len(dev)} dev trees)")
    return before, after


if __name__ == "__main__":
    main()
