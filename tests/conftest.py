"""Test configuration: run everything on a virtual 8-device CPU mesh so
multi-chip sharding paths are exercised without TPU hardware (the analogue
of the reference's `local[N]` + Engine-override distributed tests,
``optim/DistriOptimizerSpec.scala:40-41``)."""

import atexit
import contextlib
import gc
import os
import shutil
import signal
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if sys.dont_write_bytecode:
    # Where the interpreter is told to write no bytecode (the image sets
    # PYTHONDONTWRITEBYTECODE), each of the ~100 interpreters the suite
    # starts — cluster workers, the CLI, bench children — compiles jax
    # and this package from source again, ~1.6 s every time.  One
    # bytecode cache for the session, in a temporary directory, for this
    # process and every child.
    _pycache = tempfile.mkdtemp(prefix="bigdl_tpu_pycache_")
    atexit.register(shutil.rmtree, _pycache, ignore_errors=True)
    sys.dont_write_bytecode, sys.pycache_prefix = False, _pycache
    os.environ.pop("PYTHONDONTWRITEBYTECODE", None)
    os.environ["PYTHONPYCACHEPREFIX"] = _pycache

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "deadline(seconds): hard per-test wall-clock cap enforced with "
        "SIGALRM in place of the default one — every multihost/cluster "
        "test carries one so a deadlocked collective can never eat the "
        "tier-1 time budget")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run")


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_programs():
    """A module's compiled programs go when the module is done.  Kept,
    they are ~200 MB a module and 3 GB after ten, and every later trace
    and compile in the process slows with them: ``test_windowed_fuzz``
    takes 40 s alone, 50 s after four other modules and 89 s at the end
    of the whole suite, and 39 s after those four with this in place."""
    yield
    import jax

    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _seed_rng():
    from bigdl_tpu.utils.rng import RNG

    RNG.set_seed(42)
    yield


#: the limit of every test that carries no ``deadline`` mark: a test that
#: blocks costs two minutes and one failure, not the rest of the run
DEFAULT_DEADLINE_S = 120.0


@contextlib.contextmanager
def hard_deadline(node):
    """Run the body under ``node``'s wall-clock limit — its
    ``@pytest.mark.deadline(seconds)`` or, without one,
    ``DEFAULT_DEADLINE_S``: SIGALRM interrupts whatever the test is
    blocked in (including a subprocess wait on a hung cluster) and fails
    it with TimeoutError instead of letting it run to the suite-level
    timeout.  Main-thread only by construction (pytest runs tests on the
    main thread)."""
    marker = node.get_closest_marker("deadline")
    limit = float(marker.args[0]) if marker else DEFAULT_DEADLINE_S

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{node.nodeid} exceeded its {limit:g}s deadline "
            f"(deadlocked collective / hung subprocess?)")

    old_handler = signal.signal(signal.SIGALRM, _on_alarm)
    old_timer = signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *old_timer)
        signal.signal(signal.SIGALRM, old_handler)


@pytest.fixture(autouse=True)
def _hard_deadline(request):
    with hard_deadline(request.node):
        yield
