"""Test harness: run everything on an 8-device virtual CPU mesh.

This is the SURVEY.md §4 "lesson for the TPU build": the reference could
only test multi-node behaviour under a real ``mpiexec -n 2``; JAX lets us
fake an 8-chip world on CPU with ``--xla_force_host_platform_device_count``,
so every collective, sharding, and pipeline schedule is exercised in a
plain single-process pytest run.
"""

import os

# XLA_FLAGS is read at backend-init time (first jax.devices()); the platform
# is pinned through both the env var and jax.config so the tests run on the
# CPU whatever the shell exported.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.device_count() == 8, (
    "test harness expects the 8-device virtual CPU mesh; got "
    f"{jax.devices()}"
)


@pytest.fixture(scope="session")
def world_size():
    return jax.device_count()


@pytest.fixture()
def comm():
    from chainermn_tpu import create_communicator

    return create_communicator("tpu_xla")


@pytest.fixture()
def loopback_comm():
    from chainermn_tpu import create_communicator

    return create_communicator("loopback")
