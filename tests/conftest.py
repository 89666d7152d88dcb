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


# Tracing allocates containers by the million.  At the interpreter's
# default a young collection (with jax's own callback on it) starts every
# 700 allocations, 1,400 times in a minute of tests, and every tenth
# promotes: ``test_kernels``, ``test_numeric_grads`` and ``test_analysis``
# took 131 s of the suite and take 97 s at these thresholds (PR 37).
gc.set_threshold(50_000, 20, 100)


def _collect_and_freeze():
    """What is alive now stays: it goes to the collector's permanent
    generation.  Collection imports jax, torch and every test module,
    millions of objects that every later collection of an older
    generation walked again: the three modules named above take 72 s
    with this."""
    gc.collect()
    gc.freeze()


def pytest_collection_finish(session):
    _collect_and_freeze()


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_programs():
    """A module's compiled programs go when the module is done.  Kept,
    they are ~200 MB a module and 3 GB after ten, and every later trace
    and compile in the process slows with them: ``test_windowed_fuzz``
    takes 40 s alone, 50 s after four other modules and 89 s at the end
    of the whole suite, and 39 s after those four with this in place.
    What the module imported late (tensorflow, a zoo) is frozen too."""
    yield
    import jax

    jax.clear_caches()
    _collect_and_freeze()


@pytest.fixture(scope="session")
def topo():
    """A ``v5e:2x2`` topology that is described, not present: the installed
    TPU compiler lowers for its devices.  Described once a session (3 s),
    inside a fixture and never at import: libtpu belongs to one process
    at a time."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="session")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Dispatch as it decides on a TPU, in the default kernel mode
    (``is_tpu_device()`` sees the CPU here)."""
    from bigdl_tpu.ops import attention, dispatch

    monkeypatch.setattr(attention, "is_tpu_device", lambda: True)
    monkeypatch.delenv("BIGDL_KERNELS", raising=False)
    dispatch.clear_decisions()


@pytest.fixture(scope="module")
def described_compiles_stay_out_of_the_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: the cache is off for a
    module that compiles so."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


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
