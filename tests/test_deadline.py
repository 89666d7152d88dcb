"""The per-test wall-clock limit of ``tests/conftest.py``: every test
runs under one, its own ``deadline`` mark or the default."""

import signal
import time

import pytest

import conftest


def test_unmarked_test_runs_under_the_default_deadline(request, monkeypatch):
    assert request.node.get_closest_marker("deadline") is None
    armed, _interval = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < armed <= conftest.DEFAULT_DEADLINE_S
    # the same arming with the default cut to a fraction of a second: a
    # test that sleeps past it fails, it does not take the run with it
    monkeypatch.setattr(conftest, "DEFAULT_DEADLINE_S", 0.2)
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError, match="exceeded its 0.2s deadline"):
        with conftest.hard_deadline(request.node):
            time.sleep(30)
    assert time.perf_counter() - t0 < 5
    # and the limit this test itself runs under is armed again
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 0


@pytest.mark.deadline(7)
def test_explicit_deadline_mark_wins_over_the_default():
    armed, _interval = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < armed <= 7
