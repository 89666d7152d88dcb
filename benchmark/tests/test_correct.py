"""``correct`` has to be able to come out false: the control (the
reference in int8, in the program's place) fails the real cell's limits
at a size a test can hold, and a run whose timed step returns its state
unchanged is judged not correct by the harness itself."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchmark import compare, control, run  # noqa: E402
from rehearse import tiny_cell  # noqa: E402


def test_the_control_fails_the_cells_own_limits():
    cell = tiny_cell("tiny_inception.c1")
    cell["workload"]["batch"] = 32      # the control's gaps grow with the
    cell["config"]["classes"] = 1000    # tensors: 0.18 here, 0.2-0.4 at 256
    limits = run.load_cell("inception_v1.train.b256.c1")["workload"]["limits"]
    nums = control.control_numbers(cell, seed=2 ** 31 + 5)
    assert not compare.judge(nums, limits), nums
    over = [k for k, v in nums.items() if v > compare.limit_of(k, limits)]
    assert over, "the lower precision has to fail one of the numbers"


def test_a_sound_tiny_run_is_correct_and_a_broken_step_is_not(monkeypatch):
    import jax

    from bigdl_tpu.parallel.train_step import TrainStep

    cell = tiny_cell("tiny_inception.c1")
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
    assert out["compared"]["delta_worst_leaf_gap"] > \
        cell["workload"]["limits"]["delta_worst_leaf_gap"]
