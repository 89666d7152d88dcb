"""Engine.check_singleton — the reference's two-drivers-on-one-device
guard (``Engine.scala:165``, ``DistriOptimizer.scala:543-554``), rebuilt
as an advisory per-platform flock because the TPU failure mode (two host
processes contending for one chip's PJRT client) presents as an
indefinite claim hang.  The guard must never touch jax itself."""

import os
import subprocess
import sys
import textwrap

import pytest

from bigdl_tpu.utils.config import BigDLConfig, set_config
from bigdl_tpu.utils.engine import Engine


HOLDER = textwrap.dedent("""
    import os, sys, fcntl, time
    fd = os.open(sys.argv[1], os.O_CREAT | os.O_RDWR, 0o600)
    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
    print("held", flush=True)
    time.sleep(30)
""")


@pytest.fixture
def fresh_lock():
    if Engine._singleton_fd is not None:
        os.close(Engine._singleton_fd)
        Engine._singleton_fd = None
    yield
    if Engine._singleton_fd is not None:
        os.close(Engine._singleton_fd)
        Engine._singleton_fd = None


def test_first_process_acquires(fresh_lock):
    assert Engine.check_singleton(force=True) is True
    assert Engine.check_singleton(force=True) is True  # idempotent while held
    # pid recorded for conflict diagnosis
    with open(Engine._singleton_lock_path()) as f:
        assert f.read().strip() == str(os.getpid())


def test_path_derivation_touches_no_jax(fresh_lock, monkeypatch):
    """The lock identity must come from env/config only — initializing a
    backend IS the claim the guard protects against."""
    monkeypatch.delenv("TPU_VISIBLE_DEVICES", raising=False)
    path = Engine._singleton_lock_path()
    assert "bigdl_tpu_" in path
    monkeypatch.setenv("TPU_VISIBLE_DEVICES", "sentinel-0,1")
    assert Engine._singleton_lock_path() != path  # visibility splits the lock


def test_conflict_warns_and_raises(fresh_lock):
    holder = subprocess.Popen(
        [sys.executable, "-c", HOLDER, Engine._singleton_lock_path()],
        stdout=subprocess.PIPE, text=True)
    try:
        assert holder.stdout.readline().strip() == "held"
        assert Engine.check_singleton(force=True) is False  # default: warn
        with pytest.raises(RuntimeError, match="another process"):
            Engine.check_singleton(raise_on_conflict=True, force=True)
        try:
            set_config(BigDLConfig(check_singleton_strict=True))
            with pytest.raises(RuntimeError):
                Engine.check_singleton(force=True)
        finally:
            set_config(None)
    finally:
        holder.kill()
        holder.wait()


def test_unusable_lockfile_is_advisory(fresh_lock, monkeypatch):
    monkeypatch.setattr(Engine, "_singleton_lock_path",
                        lambda: "/nonexistent-dir/x.lock")
    assert Engine.check_singleton(force=True) is True  # skipped, not a failure


def test_lock_released_on_reset(fresh_lock):
    assert Engine.check_singleton(force=True) is True
    Engine.reset()
    assert Engine._singleton_fd is None
    assert Engine.check_singleton(force=True) is True  # reacquirable


def test_cpu_platform_short_circuits(fresh_lock, monkeypatch):
    """Concurrent CPU-only processes are legitimate — no lock taken."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert Engine.check_singleton() is True
    assert Engine._singleton_fd is None
