"""bench.py and chip_smoke.py as programs: what they must never do.

- a backend that does not come up, or a config that fails, is a
  non-zero exit — there is no replay of an old number and no exit 0
  around an ``"error"`` row;
- importing ``bench`` (every tool under ``tools/`` does) initializes no
  jax backend: a parent that touches the backend holds the chip;
- ``chip_smoke.py`` refuses to pass on anything that is not a TPU.

Reference protocol being protected: the per-iteration throughput record
of ``models/utils/DistriOptimizerPerf.scala:33-124``."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, env_extra, timeout=180):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    env.pop("XLA_FLAGS", None)  # single-device is fine and faster here
    return subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=REPO)


def test_import_of_bench_initializes_no_backend():
    proc = _run(["-c", "import bench\n"
                 "from bigdl_tpu.utils.compile_cache import "
                 "initialized_platform\n"
                 "assert initialized_platform() is None\n"], {})
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_failed_config_makes_the_sweep_exit_nonzero():
    """A config that raises is recorded as an ``error`` row — and the
    exit code says so: 1, never 0, and never a replayed old number."""
    proc = _run([os.path.join(REPO, "bench.py")],
                {"BENCH_CONFIGS": "no_such_config", "BENCH_INFER": "0"})
    assert proc.returncode == 1, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "error" in line["configs"]["no_such_config"]
    assert line["value"] is None and "replayed" not in line


def test_backend_that_does_not_come_up_is_an_error_not_a_number():
    proc = _run([os.path.join(REPO, "bench.py")],
                {"JAX_PLATFORMS": "no_such_platform"})
    assert proc.returncode != 0
    assert not proc.stdout.strip(), proc.stdout[-500:]


def test_chip_smoke_fails_off_the_chip():
    """The contract's first half: where JAX finds no accelerator the
    smoke exits non-zero and prints no result."""
    proc = _run([os.path.join(REPO, "chip_smoke.py")], {})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout, proc.stdout[-500:]


def test_flash_attn_flop_correction(monkeypatch):
    """The dense-equivalent attention FLOPs (12*L*B*H*S^2*D) are added
    only when the auto backend would route the config to flash — off-TPU
    (dense) the correction must be zero so MFU accounting matches what
    XLA already counted."""
    import bench
    from bigdl_tpu.ops import attention

    assert bench._flash_attn_flops("transformer_lm", 32) == 0.0  # cpu

    monkeypatch.setattr(attention, "is_tpu_device", lambda: True)
    got = bench._flash_attn_flops("transformer_lm", 32)
    assert got == 12.0 * 6 * 32 * 8 * 512 * 512 * 64
    # below the flash threshold: dense path, already counted
    monkeypatch.setenv("BIGDL_FLASH_MIN_SEQ", "1024")
    assert bench._flash_attn_flops("transformer_lm", 32) == 0.0
    # non-transformer configs have no correction
    assert bench._flash_attn_flops("inception_v1_imagenet", 256) == 0.0
