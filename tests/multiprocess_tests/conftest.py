"""Harness that spawns real multi-process JAX CPU clusters per scenario —
the TPU-native ``mpiexec -n 2`` (SURVEY.md §4: the reference ran its whole
suite under mpiexec; here each worker is an OS process with one CPU device
joined via ``jax.distributed.initialize``)."""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "_mp_worker.py")
_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(devices_per_proc: int = 1) -> dict:
    env = dict(os.environ)
    # plain CPU devices; scrub TPU and parent-test mesh settings
    # so each worker builds its own world
    for k in list(env):
        if k.startswith(("TPU_", "LIBTPU", "PJRT_", "JAX_", "XLA_")):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    if devices_per_proc > 1:
        # multi-device processes: global device ids interleave as
        # (proc 0: 0..d-1), (proc 1: d..2d-1), ... so mesh-minor axes
        # stay process-local and mesh-major axes span the boundary
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices_per_proc}")
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _launch(scenario, nprocs, timeout, devices_per_proc):
    """Run ``scenario`` (``"a+b"``: several, one after the other) across
    ``nprocs`` real processes.  Returns each worker's output and what
    went wrong with the launch, ``None`` if every worker exited 0."""
    addr = f"localhost:{_free_port()}"
    env = _worker_env(devices_per_proc)
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, addr, str(nprocs), str(i),
             scenario],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env, cwd=_REPO_ROOT)
        for i in range(nprocs)
    ]
    outputs, codes = [], []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outputs.append(out)
            codes.append(p.returncode)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        outputs = [p.communicate()[0] for p in procs]
        return outputs, (
            f"scenario {scenario!r} timed out after {timeout}s "
            "(likely a cross-process collective deadlock)\n"
            + "\n---\n".join(outputs))
    if any(codes):
        report = "\n".join(
            f"--- worker {i} rc={codes[i]} ---\n{outputs[i]}"
            for i in range(nprocs))
        return outputs, f"scenario {scenario!r} failed:\n{report}"
    return outputs, None


def _assert_ran(name, outputs, wrong):
    for i, out in enumerate(outputs):
        assert f"WORKER_OK {i} {name}" in out, (
            wrong or f"worker {i} exited 0 without {name}'s OK marker:"
            f"\n{out}")


@pytest.fixture(scope="session")
def mp_run():
    """Run ``scenario`` across ``nprocs`` real processes; fail the test on
    any non-zero worker exit, with both workers' output in the report."""

    def run(scenario: str, nprocs: int = 2, timeout: int = 180,
            devices_per_proc: int = 1):
        outputs, wrong = _launch(scenario, nprocs, timeout,
                                 devices_per_proc)
        if wrong:
            pytest.fail(wrong)
        _assert_ran(scenario, outputs, None)

    return run


@pytest.fixture(scope="session")
def mp_run_shared():
    """One launch of two processes with a device each for several
    scenarios, each with a test of its own: returns ``ran(name)``, which fails the test that calls it unless
    ``name`` ran to its marker on every worker.  The workers stop at the
    first scenario that fails (a rank that went on alone would wait for
    its peer), so the tests of those before it pass, its own fails with
    the workers' output, and those after it fail as not reached."""

    def run(names):
        outputs, wrong = _launch("+".join(names), 2, 280, 1)
        return lambda name: _assert_ran(name, outputs, wrong)

    return run
