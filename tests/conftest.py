"""Test configuration: run everything on a virtual 8-device CPU mesh so
multi-chip sharding paths are exercised without TPU hardware (the analogue
of the reference's `local[N]` + Engine-override distributed tests,
``optim/DistriOptimizerSpec.scala:40-41``)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "deadline(seconds): hard per-test wall-clock cap enforced with "
        "SIGALRM — every multihost/cluster test carries one so a "
        "deadlocked collective can never eat the tier-1 time budget")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run")


@pytest.fixture(autouse=True)
def _seed_rng():
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(42)
    yield


@pytest.fixture(autouse=True)
def _hard_deadline(request):
    """Enforce ``@pytest.mark.deadline(seconds)``: SIGALRM interrupts
    whatever the test is blocked in (including a subprocess wait on a
    hung cluster) and fails it with TimeoutError instead of letting it
    run to the suite-level timeout.  Main-thread only by construction
    (pytest runs tests on the main thread)."""
    import signal as _signal

    marker = request.node.get_closest_marker("deadline")
    if marker is None:
        yield
        return
    limit = float(marker.args[0])

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{request.node.nodeid} exceeded its {limit:.0f}s deadline "
            f"(deadlocked collective / hung subprocess?)")

    old = _signal.signal(_signal.SIGALRM, _on_alarm)
    _signal.setitimer(_signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        _signal.setitimer(_signal.ITIMER_REAL, 0.0)
        _signal.signal(_signal.SIGALRM, old)
