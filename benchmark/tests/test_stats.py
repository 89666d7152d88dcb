"""The end-to-end arithmetic on made-up step stamps."""

import numpy as np
import pytest

from benchmark import stats


@pytest.mark.parametrize("q", [0, 50, 90, 95, 100])
def test_percentile_is_numpys(q):
    v = [0.25, 0.251, 0.249, 0.31, 0.25, 0.252, 0.26, 0.2505, 0.4, 0.25]
    assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))
    assert stats.percentile([3.0], q) == 3.0


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_rate_is_completed_work_over_the_whole_window():
    # 4 steps of 0.25 s start in a window that closes at 1.1 s: the last
    # one's loss reaches the host after the close and does not count
    tops = [0.0, 0.25, 0.5, 0.9]
    ends = [0.25, 0.5, 0.75, 1.15]
    m = stats.window_metrics(tops, ends, 0.0, 1.1, batch=256)
    assert m["steps_started"] == 4 and m["steps_completed"] == 3
    assert m["records_per_s"] == pytest.approx(3 * 256 / 1.1)
    assert m["step_p50_ms"] == pytest.approx(250.0)
    assert m["step_p90_ms"] == pytest.approx(250.0)


def test_a_stall_shows_in_the_tail_and_in_the_rate():
    tops = [i * 0.1 for i in range(20)]
    ends = [t + 0.1 for t in tops]
    # a 0.5 s stall inside step 8: everything after it shifts
    tops = tops[:8] + [t + 0.5 for t in tops[8:]]
    ends = ends[:7] + [ends[7] + 0.5] + [e + 0.5 for e in ends[8:]]
    m = stats.window_metrics(tops, ends, 0.0, ends[-1], batch=10)
    assert m["step_max_ms"] == pytest.approx(600.0)
    assert m["records_per_s"] == pytest.approx(200 / 2.5)
    assert m["step_p50_ms"] == pytest.approx(100.0)


def test_window_metrics_refuses_nonsense():
    with pytest.raises(ValueError):
        stats.window_metrics([0.0], [], 0.0, 1.0, 1)
    with pytest.raises(ValueError):
        stats.window_metrics([0.0], [0.5], 1.0, 1.0, 1)


def test_diffs_of_a_running_total():
    assert stats.diffs([1.0, 1.5, 1.5, 3.0]) == [0.5, 0.0, 1.5]
