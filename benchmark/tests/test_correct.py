"""``correct`` has to be able to come out false: the control (the
reference in int8, in the program's place) fails the real cell's limits
at a size a test can hold, and a run whose timed step returns its state
unchanged is judged not correct by the harness itself.  The same for a
family whose records are token ids, under either update rule."""

import hashlib
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchmark import compare, control, models, reference, run  # noqa: E402
from benchmark.optimizers import adam  # noqa: E402
from rehearse import tiny_cell  # noqa: E402

TOKEN_CELLS = ["tiny_lm.c1", "tiny_lm_adam.c1"]


def test_the_control_fails_the_cells_own_limits():
    cell = tiny_cell("tiny_inception.c1")
    cell["workload"]["batch"] = 32      # the control's gaps grow with the
    cell["config"]["classes"] = 1000    # tensors: 0.18 here, 0.2-0.4 at 256
    limits = run.load_cell("inception_v1.train.b256.c1")["workload"]["limits"]
    nums = control.control_numbers(cell, seed=2 ** 31 + 5)
    assert not compare.judge(nums, limits), nums
    over = [k for k, v in nums.items() if v > compare.limit_of(k, limits)]
    assert over, "the lower precision has to fail one of the numbers"


@pytest.mark.parametrize("name", TOKEN_CELLS)
def test_the_control_fails_the_token_fixtures_limits(name):
    cell = tiny_cell(name)
    limits = cell["workload"]["limits"]
    nums = control.control_numbers(cell, seed=2 ** 31 + 5)
    assert not compare.judge(nums, limits), nums
    assert nums["grad1_worst_leaf_gap"] > 3 * limits["grad1_worst_leaf_gap"]


@pytest.mark.parametrize("name", ["tiny_inception.c1"] + TOKEN_CELLS)
def test_a_sound_tiny_run_is_correct_and_a_broken_step_is_not(
        name, monkeypatch):
    import jax

    from bigdl_tpu.parallel.train_step import TrainStep

    cell = tiny_cell(name)
    out = run.run_cell(cell, 2 ** 31 + 9, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1

    sound = TrainStep.run_sharded

    def state_unchanged(self, x, y, key, grad_scale=None):
        """The timed path broken underneath: the step computes its loss
        and hands back the state it was given."""
        keep = jax.tree.map(lambda a: a.copy(),
                            (self.params, self.opt_state, self.buffers))
        loss = sound(self, x, y, key)
        self.params, self.opt_state, self.buffers = keep
        return loss

    monkeypatch.setattr(TrainStep, "run_sharded", state_unchanged)
    out = run.run_cell(cell, 2 ** 31 + 9, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is False
    assert out["compared"]["delta_worst_leaf_gap"] == pytest.approx(1.0)


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(str((a.dtype, a.shape)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def test_a_family_without_make_records_gets_the_image_records_unchanged():
    """Same seed, same bytes, as before a record could be anything else:
    the digest was taken from ``reference.make_records`` at PR 26."""
    conf = {"family": "resnet", "image": [3, 8, 8], "classes": 10}
    family = models.load(conf)
    assert not hasattr(family, "make_records")
    x, y = models.make_records(family, 2 ** 31 + 77, 24, conf)
    want = reference.make_records(2 ** 31 + 77, 24, [3, 8, 8], 10)
    assert x.dtype == np.float32 and x.shape == (24, 3, 8, 8)
    assert y.dtype == np.int32 and y.shape == (24,)
    assert _digest(x, y) == _digest(*want) == "fbee2acc850d0bbc"


def test_token_records_are_the_seeds_and_zipf_ranks_are_not_ids():
    x, y = reference.make_token_records(2 ** 31 + 77, 512, 16, 64, zipf=1.0)
    assert x.dtype == y.dtype == np.int32 and x.shape == y.shape == (512, 16)
    assert (x[:, 1:] == y[:, :-1]).all()          # y is the next token
    assert 0 <= x.min() and max(x.max(), y.max()) < 64
    assert _digest(x, y) == "f17f10bb5339d7cc"
    counts = np.bincount(np.concatenate([x[:, 0], y.ravel()]), minlength=64)
    order = np.argsort(-counts)
    assert counts[order[0]] > 8 * counts[order[-1]]  # Zipf, exponent 1
    assert list(order[:8]) != sorted(order[:8])      # rank is not id
    flat, _ = reference.make_token_records(2 ** 31 + 77, 512, 16, 64)
    uniform = np.bincount(flat.ravel(), minlength=64)
    assert uniform.max() < 2 * uniform.min()


def test_follow_under_adam_is_three_hand_written_steps():
    """A two-leaf quadratic, ``loss = sum_rows 0.5 |a - x|^2 + |b|^2 y``,
    against Kingma & Ba's Algorithm 1 written out in numpy float64."""
    import jax.numpy as jnp

    family = types.SimpleNamespace(
        BLOCK_ROWS=2,
        loss_sum=lambda p, x, y, quant=None: jnp.sum(
            0.5 * jnp.sum((p[0][None, :] - x) ** 2, axis=1)
            + jnp.sum(p[1] ** 2) * y))
    conf = {"learning_rate": 0.05, "beta1": 0.9, "beta2": 0.999,
            "epsilon": 1e-8}
    rng = np.random.default_rng(5)
    a0 = rng.standard_normal(3).astype(np.float32)
    b0 = rng.standard_normal(2).astype(np.float32)
    batches = [(rng.standard_normal((4, 3)).astype(np.float32),
                rng.random(4).astype(np.float32)) for _ in range(3)]
    got = reference.follow(family, [a0, b0], batches, adam, conf)

    w = [a0.astype(np.float64), b0.astype(np.float64)]
    m, v = [0 * w[0], 0 * w[1]], [0 * w[0], 0 * w[1]]
    losses, g1 = [], None
    for t, (x, y) in enumerate(batches, 1):
        losses.append(np.mean(0.5 * np.sum((w[0] - x) ** 2, axis=1)
                              + np.sum(w[1] ** 2) * y))
        g = [np.mean(w[0] - x, axis=0), 2 * w[1] * np.mean(y)]
        g1 = g1 or [np.linalg.norm(a) for a in g]
        for i in range(2):
            m[i] = 0.9 * m[i] + 0.1 * g[i]
            v[i] = 0.999 * v[i] + 0.001 * g[i] ** 2
            w[i] = w[i] - 0.05 * (m[i] / (1 - 0.9 ** t)) / (
                np.sqrt(v[i] / (1 - 0.999 ** t)) + 1e-8)
    delta = [np.linalg.norm(w[0] - a0), np.linalg.norm(w[1] - b0)]
    np.testing.assert_allclose(got["losses"], losses, rtol=1e-5)
    np.testing.assert_allclose(got["grad1_norms"], g1, rtol=1e-5)
    np.testing.assert_allclose(got["delta_norms"], delta, rtol=1e-4)
