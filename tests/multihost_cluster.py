"""Launching ``tests/multihost_worker.py`` on live CPU clusters: the one
copy of the environment, launch and wait code that ``test_multihost.py``,
``test_cluster.py`` and ``test_faults.py`` share.

A cluster is ``nproc`` worker processes joined through
``jax.distributed`` (each with 2 virtual devices); ``nproc=1`` is the
single-process control, which gets no coordinator at all."""

import os
import subprocess
import sys

import numpy as np

# the flock-serialized allocator with the recent-port ledger: two tests
# grabbing ports back-to-back can otherwise race the same ephemeral port
# into both clusters (deflake, ISSUE 20)
from bigdl_tpu.parallel.cluster import _free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multihost_worker.py")


def worker_env(**extra) -> dict:
    # the worker sets its own XLA_FLAGS/platform before importing jax,
    # and a fault plan reaches it only when the test passes one
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BIGDL_FAULTS")}
    env["BIGDL_REPO"] = REPO
    env.update({k: str(v) for k, v in extra.items()})
    return env


def launch_cluster(nproc: int, **extra) -> list:
    """Start the worker ``nproc`` times; the processes, for
    :func:`wait_all`."""
    if nproc == 1:
        envs = [worker_env(**extra)]
    else:
        port = _free_port()
        envs = [worker_env(BIGDL_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                           BIGDL_NUM_PROCESSES=nproc, BIGDL_PROCESS_ID=pid,
                           **extra) for pid in range(nproc)]
    return [subprocess.Popen([sys.executable, WORKER], env=env,
                             stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT) for env in envs]


def wait_all(procs, timeout: int = 420):
    """``(returncodes, outputs)`` once every process has exited.  The
    generous default ``timeout`` is deliberate: these are real
    jax.distributed clusters and must stay green on loaded CI machines
    (deflake budget, ISSUE 5)."""
    outs = []
    try:
        for p in procs:
            stdout, _ = p.communicate(timeout=timeout)
            outs.append(stdout.decode(errors="replace"))
    finally:
        for p in procs:  # a hung collective must not leak live workers
            if p.poll() is None:
                p.kill()
                p.wait()
    return [p.returncode for p in procs], outs


def start_cluster(out, nproc: int = 2, **extra):
    """Launch the worker on an ``nproc``-process cluster and return the
    call that waits for it, holds it to its exit codes and gives back
    ``out``, the path the coordinator saves its params to — so that a
    test can start two runs that do not depend on each other (a cluster
    and its control, the uninterrupted and the injured job) and then
    wait for both."""
    out = str(out)
    procs = launch_cluster(nproc, BIGDL_TEST_OUT=out, **extra)

    def finish(expect_out: bool = True, timeout: int = 420,
               codes=None) -> str:
        """``codes`` maps process index -> expected returncode where a
        nonzero exit IS the asserted behavior (a shed straggler exits
        43), 0 otherwise.  ``expect_out=False`` for runs that
        legitimately end without publishing params (graceful
        preemption)."""
        got, outputs = wait_all(procs, timeout)
        for pid, (code, text) in enumerate(zip(got, outputs)):
            want = (codes or {}).get(pid, 0)
            assert code == want, (
                f"worker p{pid} of {nproc} exited {code} "
                f"(expected {want}):\n{text[-4000:]}")
        if expect_out:
            assert os.path.exists(out), "coordinator did not write params"
        return out

    return finish


def run_cluster(out, nproc: int = 2, expect_out: bool = True,
                timeout: int = 420, codes=None, **extra) -> str:
    """:func:`start_cluster` and wait: the run's params path."""
    return start_cluster(out, nproc, **extra)(expect_out, timeout, codes)


def assert_same_params(path_a, path_b, rtol: float, atol: float):
    a, b = np.load(path_a), np.load(path_b)
    assert set(a.files) == set(b.files) and len(a.files) > 0
    for k in a.files:
        np.testing.assert_allclose(a[k], b[k], rtol=rtol, atol=atol,
                                   err_msg=f"param {k} diverged")
