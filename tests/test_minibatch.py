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

    def counting(arrays, param, out=None):
        calls.append(len(arrays))
        return real(arrays, param, out)

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


# -- stacking into arrays the caller passes (PR 31): the feeder's staging
# buffers.  The values never depend on what was passed -------------------

def _junk_like(arrays):
    return [np.full_like(a, 113) for a in arrays]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__.strip("_"))
def test_stacking_into_passed_buffers_equals_a_fresh_stack(case):
    """Two batches in a row into the same buffers, as a feeder worker
    stacks them: each equals the batch stacked alone, and is the buffer
    itself.  The second batch's samples come in the opposite order, so a
    padded leg has to lay its padding anew over the first batch's rows."""
    samples, fpad, lpad = case(np.random.default_rng(4))
    first = _eager(samples, fpad, lpad)
    buffers = _junk_like(first.inputs + first.targets)
    for batch_samples in (samples, samples[::-1]):
        want = _eager(batch_samples, fpad, lpad)
        lazy = MiniBatch.from_samples(batch_samples, fpad, lpad)
        made = lazy._stacked(out=buffers)
        assert all(m is b for m, b in
                   zip(made.inputs + made.targets, buffers, strict=True))
        _same(made.inputs, want.inputs)
        _same(made.targets, want.targets)
        # the batch itself stays deferred and holds none of the buffers:
        # stacked on its own it gets arrays that are its for good
        assert lazy._samples is not None
        _same(lazy.inputs, want.inputs)
        _same(lazy.targets, want.targets)
        assert not any(a is b for a in lazy.inputs + lazy.targets
                       for b in buffers)
        assert lazy._stacked(out=buffers) is lazy  # stacked: nothing to make
        assert made._stacked(out=buffers) is made


def _wrong_shape(a):
    return np.full((a.shape[0] + 1, *a.shape[1:]), 113, a.dtype)


def _wrong_dtype(a):
    return np.full(a.shape, 113, np.float64 if a.dtype != np.float64
                   else np.float32)


@pytest.mark.parametrize("spoil", [_wrong_shape, _wrong_dtype],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("case", [_equal_shape, _ragged_padded,
                                  _fixed_length],
                         ids=lambda c: c.__name__.strip("_"))
def test_a_buffer_that_does_not_fit_is_left_untouched(case, spoil):
    samples, fpad, _ = case(np.random.default_rng(5))
    arrays = [s.features[0] for s in samples]
    want = mb._pad_stack(arrays, fpad)
    bad = spoil(want)
    got = mb._pad_stack(arrays, fpad, out=bad)
    assert got is not bad and (bad == 113).all()
    _same(got, want)
    # through a batch: the misfit leaf is fresh, the one that fits is used
    _, _, lpad = case(np.random.default_rng(5))
    eager = _eager(samples, fpad, lpad)
    good = _junk_like(eager.targets)
    lazy = MiniBatch.from_samples(samples, fpad, lpad)
    made = lazy._stacked(out=[bad] + good)
    assert made.inputs[0] is not bad and (bad == 113).all()
    assert all(m is g for m, g in zip(made.targets, good, strict=True))
    _same(made.inputs, eager.inputs)
    _same(made.targets, eager.targets)


def test_samples_of_mixed_dtype_promote_as_before_and_take_no_buffer():
    arrays = [np.ones((2,), np.float32), np.ones((2,), np.float64)]
    buf = np.full((2, 2), 113, np.float64)
    got = mb._pad_stack(arrays, None, out=buf)
    assert got is not buf and (buf == 113).all()
    _same(got, np.stack(arrays))


def test_fewer_buffers_than_leaves_leaves_the_rest_fresh():
    samples, _, _ = _multi_feature(np.random.default_rng(6))
    eager = _eager(samples, None, None)
    buffers = _junk_like(eager.inputs[:1])
    lazy = MiniBatch.from_samples(samples)
    made = lazy._stacked(out=buffers)
    assert made.inputs[0] is buffers[0]
    _same(made.inputs, eager.inputs)
    _same(made.targets, eager.targets)
