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


@pytest.fixture(scope="session")
def mp_run():
    """Run ``scenario`` across ``nprocs`` real processes; fail the test on
    any non-zero worker exit, with both workers' output in the report."""

    def run(scenario: str, nprocs: int = 2, timeout: int = 180,
            devices_per_proc: int = 1):
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
            for p in procs:
                out, _ = p.communicate()
                outputs.append(out)
            pytest.fail(
                f"scenario {scenario!r} timed out after {timeout}s "
                "(likely a cross-process collective deadlock)\n"
                + "\n---\n".join(outputs))
        if any(codes):
            report = "\n".join(
                f"--- worker {i} rc={codes[i]} ---\n{outputs[i]}"
                for i in range(nprocs))
            pytest.fail(f"scenario {scenario!r} failed:\n{report}")
        for i, out in enumerate(outputs):
            for name in scenario.split("+"):
                assert f"WORKER_OK {i} {name}" in out, (
                    f"worker {i} exited 0 without {name}'s OK marker:\n"
                    f"{out}")

    return run
