"""Composable data transformers (``dataset/Transformer.scala:44-86``).

A Transformer maps ``Iterator[A] -> Iterator[B]`` and composes with ``>>``
(the reference's ``->``) into a ChainedTransformer.  Transformers are
host-side (numpy) — the device only ever sees finished MiniBatches.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Iterator, List, Optional

import numpy as np

__all__ = ["Transformer", "ChainedTransformer", "SampleToMiniBatch", "Identity"]


class Transformer:
    def apply(self, it: Iterator) -> Iterator:
        raise NotImplementedError

    def __call__(self, it: Iterable) -> Iterator:
        return self.apply(iter(it))

    def __rshift__(self, other: "Transformer") -> "ChainedTransformer":
        return ChainedTransformer(self, other)

    def clone_transformer(self) -> "Transformer":
        import copy

        return copy.deepcopy(self)


class ChainedTransformer(Transformer):
    def __init__(self, first: Transformer, second: Transformer):
        self.first, self.second = first, second

    def apply(self, it):
        return self.second(self.first(it))


class Identity(Transformer):
    def apply(self, it):
        return it


class SampleToMiniBatch(Transformer):
    """Batch Samples into MiniBatches with optional padding
    (``dataset/Transformer.scala:309`` SampleToMiniBatch + the padding
    strategies of ``dataset/MiniBatch.scala:333-452``).

    ``feature_padding_param``/``label_padding_param`` pad variable-length
    samples to a common shape; ``fixed_length`` pads every batch to the same
    length — essential on TPU to avoid per-batch recompilation.

    The batches it yields are deferred (``MiniBatch.from_samples``):
    whoever first reads one stacks it, not this generator."""

    def __init__(self, batch_size: int, feature_padding_param=None,
                 label_padding_param=None, partition_num: Optional[int] = None,
                 drop_last: bool = False):
        self.batch_size = batch_size
        self.feature_padding = feature_padding_param
        self.label_padding = label_padding_param
        self.drop_last = drop_last

    def apply(self, it):
        from bigdl_tpu.dataset.minibatch import MiniBatch

        buf: List = []
        for sample in it:
            buf.append(sample)
            if len(buf) == self.batch_size:
                yield MiniBatch.from_samples(buf, self.feature_padding, self.label_padding)
                buf = []
        if buf and not self.drop_last:
            yield MiniBatch.from_samples(buf, self.feature_padding, self.label_padding)
