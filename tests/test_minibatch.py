"""``MiniBatch`` on its own: a batch made from samples is stacked on
first use, not where it was made, and then equals one stacked at once."""

import numpy as np
import pytest

from bigdl_tpu.dataset import minibatch as mb
from bigdl_tpu.dataset.minibatch import MiniBatch
from bigdl_tpu.dataset.sample import PaddingParam, Sample
from bigdl_tpu.dataset.transformer import SampleToMiniBatch


def _equal_shape(rng):
    return [Sample(rng.normal(size=(3, 4)).astype(np.float32),
                   np.int64(i % 3)) for i in range(5)], None, None


def _ragged_padded(rng):
    return [Sample(rng.normal(size=(n, 2)).astype(np.float32),
                   np.arange(n, dtype=np.int64)) for n in (3, 1, 4)], \
        PaddingParam(-1.0), PaddingParam(7)


def _fixed_length(rng):
    return [Sample(rng.normal(size=(n,)).astype(np.float32), np.int64(n))
            for n in (2, 5, 3)], PaddingParam(0.5, fixed_length=8), None


def _multi_feature(rng):
    return [Sample([rng.normal(size=(4,)).astype(np.float32),
                    np.full((2, 2), i, np.int32)],
                   [np.int64(i), np.float32(i) / 2]) for i in range(4)], \
        None, None


def _label_less(rng):
    return [Sample(rng.normal(size=(6,)).astype(np.float32))
            for _ in range(3)], None, None


CASES = [_equal_shape, _ragged_padded, _fixed_length, _multi_feature,
         _label_less]


def _eager(samples, fpad, lpad):
    """The batch as it was built before stacking was deferred."""
    inputs = [mb._pad_stack([s.features[i] for s in samples], fpad)
              for i in range(len(samples[0].features))]
    targets = [mb._pad_stack([s.labels[i] for s in samples], lpad)
               for i in range(len(samples[0].labels))]
    return MiniBatch(inputs, targets or None)


def _same(a, b):
    if isinstance(a, list):
        assert isinstance(b, list) and len(a) == len(b)
        for u, v in zip(a, b):
            _same(u, v)
    elif a is None:
        assert b is None
    else:
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__.strip("_"))
def test_deferred_batch_equals_eager(case):
    samples, fpad, lpad = case(np.random.default_rng(0))
    lazy = MiniBatch.from_samples(samples, fpad, lpad)
    eager = _eager(samples, fpad, lpad)
    assert lazy.size() == eager.size() == len(samples)
    _same(lazy.get_input(), eager.get_input())
    _same(lazy.get_target(), eager.get_target())
    _same(lazy.inputs, eager.inputs)
    _same(lazy.targets, eager.targets)
    assert repr(lazy) == repr(eager)
    assert lazy.size() == len(samples)  # from the arrays now, unchanged


def test_fixed_length_pads_to_the_stated_length():
    samples, fpad, _ = _fixed_length(np.random.default_rng(1))
    x = MiniBatch.from_samples(samples, fpad).get_input()
    assert x.shape == (3, 8)
    assert x[0, 2:].tolist() == [0.5] * 6


@pytest.fixture
def stack_calls(monkeypatch):
    calls = []
    real = mb._pad_stack

    def counting(arrays, param):
        calls.append(len(arrays))
        return real(arrays, param)

    monkeypatch.setattr(mb, "_pad_stack", counting)
    return calls


def test_size_does_not_stack(stack_calls):
    samples, _, _ = _equal_shape(np.random.default_rng(2))
    it = SampleToMiniBatch(2).apply(iter(samples))
    sizes = [b.size() for b in it]
    assert sizes == [2, 2, 1]
    assert stack_calls == []


def test_slice_stacks_once(stack_calls):
    samples, _, _ = _equal_shape(np.random.default_rng(3))
    batch = MiniBatch.from_samples(samples)
    a, b = batch.slice(0, 2), batch.slice(2, 3)
    assert stack_calls == [5, 5]  # one feature, one label: once each
    assert a.size() == 2 and b.size() == 3
    np.testing.assert_array_equal(
        np.concatenate([a.get_input(), b.get_input()]), batch.get_input())
    np.testing.assert_array_equal(b.get_target(), batch.get_target()[2:])
    assert stack_calls == [5, 5]


@pytest.mark.parametrize("samples,fpad", [
    ([Sample(np.zeros((3,), np.float32)),
      Sample(np.zeros((2, 2), np.float32))], None),
    ([Sample(np.zeros((3,), np.float32)),
      Sample(np.zeros((9,), np.float32))], PaddingParam(fixed_length=4)),
], ids=["ranks_differ_no_padding", "longer_than_fixed_length"])
def test_unstackable_batch_raises_at_first_use(samples, fpad):
    batch = MiniBatch.from_samples(samples, fpad)  # nothing raised yet
    assert batch.size() == 2
    with pytest.raises(ValueError):
        batch.get_input()
    with pytest.raises(ValueError):  # and again: it is not half built
        batch.slice(0, 1)
