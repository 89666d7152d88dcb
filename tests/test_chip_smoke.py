"""chip_smoke.py as a program: it refuses to pass on anything that is
not a TPU (PR 21's bring-up proof; the benchmark's own refusal is held
by ``benchmark/tests``)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_off_the_chip():
    """The contract's first half: where JAX finds no accelerator the
    smoke exits non-zero and prints no result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # single-device is fine and faster here
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout, proc.stdout[-500:]
