"""Test harness: run everything on an 8-device virtual CPU mesh.

This is the SURVEY.md §4 "lesson for the TPU build": the reference could
only test multi-node behaviour under a real ``mpiexec -n 2``; JAX lets us
fake an 8-chip world on CPU with ``--xla_force_host_platform_device_count``,
so every collective, sharding, and pipeline schedule is exercised in a
plain single-process pytest run.
"""

import contextlib
import faulthandler
import os
import signal
import tempfile
import threading

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


# No test takes 100 s under the driver's six workers; one that passes this
# has hung.  The driver's command is cut at 1,470 s and then reports a
# short count and no name, so each phase of each test (its set-up, which
# builds the module's and the session's fixtures it is first to ask for,
# its body, its teardown) carries its own limit.
TEST_LIMIT_S = 300


class HungTest(BaseException):
    """Not an ``Exception``: a retry or a poll loop under test that
    catches those does not swallow its own limit."""


def pytest_configure(config):
    # the process's own stderr: pytest's capture has let go of
    # descriptor 2 here and takes it again around every test
    global _stderr
    _stderr = os.fdopen(os.dup(2), "w")


@contextlib.contextmanager
def time_limit(seconds, what):
    """Raise ``HungTest`` in the main thread, with every thread's
    stack in its message, once the body has run ``seconds``, and again
    every tenth of that while it goes on (something caught the last
    one); put back the SIGALRM handler and the real-time timer that
    stood before.

    The handler runs when the main thread is next in Python.  A thread
    stuck inside XLA (a collective that waits for a peer, a
    ``block_until_ready``) is not: for it, and for a body that caught
    every raise, a watching thread writes the name and the stacks to the
    process's stderr a twentieth past the limit, and the run goes on
    hanging with its name in the log.  A hang that holds the GIL is
    past both."""
    if threading.current_thread() is not threading.main_thread():
        yield       # signals are delivered to the main thread only
        return
    said = f"{what} ran past its {seconds} s limit"

    def on_alarm(signum, frame):
        with tempfile.TemporaryFile(mode="w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            raise HungTest(f"{said}\n{f.read()}")

    def on_watch():
        print(f"\n{said} and has not come back:", file=_stderr, flush=True)
        faulthandler.dump_traceback(file=_stderr, all_threads=True)

    watch = threading.Timer(seconds * 1.05, on_watch)
    watch.daemon = True
    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    old_timer = signal.setitimer(signal.ITIMER_REAL, seconds, seconds / 10)
    watch.start()
    try:
        yield
    finally:
        watch.cancel()
        signal.setitimer(signal.ITIMER_REAL, *old_timer)
        signal.signal(signal.SIGALRM, old_handler)


# wrappers of the three phases, inside pytest's own catch of each: the
# raise is reported as that test's failure or error and the run goes on
@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with time_limit(TEST_LIMIT_S, f"{item.nodeid} (set-up)"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with time_limit(TEST_LIMIT_S, item.nodeid):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    with time_limit(TEST_LIMIT_S, f"{item.nodeid} (teardown)"):
        return (yield)


@pytest.fixture(scope="session")
def jitted():
    """``jitted(fn)``: ``fn`` as ONE compiled program, traced as its
    module stands at the call (a chunk length a test has monkeypatched
    is read at the trace, so a new ``jax.jit`` a call): run eagerly,
    each of its ops is a program of its own to compile and a
    token-by-token form dispatches every token's."""
    return lambda fn: jax.jit(lambda *a: fn(*a))


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
