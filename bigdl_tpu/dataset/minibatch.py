"""MiniBatch (``dataset/MiniBatch.scala:33``): stacked batch of Samples
with ``size/slice/get_input/get_target`` and the padding strategies."""

from __future__ import annotations

import itertools
from typing import List, Optional, Sequence, Union

import numpy as np

from bigdl_tpu.dataset.sample import PaddingParam, Sample

__all__ = ["MiniBatch"]


def _fits(out: Optional[np.ndarray], shape, arrays: List[np.ndarray]) -> bool:
    """Whether filling ``out`` gives what a fresh allocation would hold:
    the result's shape, and the dtype of every array (so nothing is cast
    that ``np.stack`` would have promoted)."""
    return out is not None and out.shape == tuple(shape) and \
        all(a.dtype == out.dtype for a in arrays)


def _pad_stack(arrays: List[np.ndarray], param: Optional[PaddingParam],
               out: Optional[np.ndarray] = None) -> np.ndarray:
    """Stack arrays, padding the leading axis (and any ragged trailing axes)
    to a common shape.  ``out`` is an array to fill in place of a fresh
    allocation; one whose shape or dtype is not the result's is left
    untouched, and the caller gets a fresh array as without it."""
    shapes = [a.shape for a in arrays]
    if len(set(shapes)) == 1 and (param is None or param.fixed_length is None):
        if _fits(out, (len(arrays), *shapes[0]), arrays):
            return np.stack(arrays, out=out)
        return np.stack(arrays)
    pad_value = param.padding_value if param else 0.0
    ndim = arrays[0].ndim
    if any(len(s) != ndim for s in shapes):
        raise ValueError(f"samples of different rank cannot be padded to "
                         f"one shape: {sorted(set(shapes))}")
    target = [max(s[d] for s in shapes) for d in range(ndim)]
    if param is not None and param.fixed_length is not None:
        if param.fixed_length < target[0]:
            raise ValueError(
                f"fixed_length {param.fixed_length} < longest sample {target[0]}")
        target[0] = param.fixed_length
    if _fits(out, (len(arrays), *target), arrays):
        out.fill(pad_value)
    else:
        out = np.full((len(arrays), *target), pad_value, dtype=arrays[0].dtype)
    for i, a in enumerate(arrays):
        sl = (i,) + tuple(slice(0, d) for d in a.shape)
        out[sl] = a
    return out


class MiniBatch:
    """``inputs``/``targets`` are lists of stacked arrays.  A batch made
    by :meth:`from_samples` is DEFERRED: it keeps the samples and the
    padding parameters, answers :meth:`size` from their count, and
    stacks on first use of anything else, on whichever thread asks — so
    the Optimizer's feeder stacks off the thread that pulls the dataset
    iterator (into a batch of its own, :meth:`_stacked`: this one stays
    deferred), and a resume skips batches without stacking them.  One
    thread owns a batch at a time; stacking is not locked."""

    def __init__(self, inputs, targets=None):
        self._samples: Optional[Sequence[Sample]] = None
        self._inputs: List[np.ndarray] = inputs if isinstance(inputs, list) else [inputs]
        self._targets: List[np.ndarray] = (targets if isinstance(targets, list) else [targets]) \
            if targets is not None else []

    @classmethod
    def from_samples(cls, samples: Sequence[Sample],
                     feature_padding: Optional[PaddingParam] = None,
                     label_padding: Optional[PaddingParam] = None) -> "MiniBatch":
        batch = cls([])
        batch._samples = samples
        batch._padding = (feature_padding, label_padding)
        return batch

    def _stacked(self, out: Sequence[np.ndarray] = ()) -> "MiniBatch":
        """A batch of this one's values that is stacked: this one if it
        is; for a deferred one a NEW batch, while this one stays deferred
        and keeps nothing of what was made.  ``out`` holds arrays to fill
        instead of allocating, one a leaf, the inputs and then the
        targets; a leaf takes its own where it fits (:func:`_pad_stack`)
        and a fresh array where not, so the values never depend on
        ``out``.  Only a caller that owns ``out`` and will fill it again
        passes it, the Optimizer's feeder (``_StagingBuffers``): what it
        gets is its own to rewrite because no batch a dataset may hold
        (and hand out again next epoch) points at it."""
        if self._samples is None:
            return self
        samples, (feature_padding, label_padding) = self._samples, self._padding
        n_feat = len(samples[0].features)
        n_lab = len(samples[0].labels)
        spare = itertools.chain(out, itertools.repeat(None))
        return MiniBatch(
            [_pad_stack([s.features[i] for s in samples], feature_padding,
                        next(spare)) for i in range(n_feat)],
            [_pad_stack([s.labels[i] for s in samples], label_padding,
                        next(spare)) for i in range(n_lab)])

    def _stack(self) -> "MiniBatch":
        """Build ``_inputs``/``_targets`` if they are still owed, into
        fresh arrays that are this batch's for good."""
        if self._samples is not None:
            done = self._stacked()
            self._inputs, self._targets = done._inputs, done._targets
            self._samples = None
        return self

    @property
    def inputs(self) -> List[np.ndarray]:
        return self._stack()._inputs

    @property
    def targets(self) -> List[np.ndarray]:
        return self._stack()._targets

    def size(self) -> int:
        if self._samples is not None:
            return len(self._samples)
        return self._inputs[0].shape[0]

    def get_input(self):
        return self.inputs[0] if len(self.inputs) == 1 else self.inputs

    def get_target(self):
        if not self.targets:
            return None
        return self.targets[0] if len(self.targets) == 1 else self.targets

    def slice(self, offset: int, length: int) -> "MiniBatch":
        return MiniBatch([a[offset:offset + length] for a in self.inputs],
                         [a[offset:offset + length] for a in self.targets] or None)

    def __repr__(self):
        return f"MiniBatch(inputs={[a.shape for a in self.inputs]}, " \
               f"targets={[a.shape for a in self.targets]})"
