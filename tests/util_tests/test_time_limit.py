"""A hung test fails by name (tests/conftest.py): the limit every test
runs under, driven here at a sub-second limit on a sleeping body."""

import os
import signal
import time

import pytest


@pytest.fixture()
def limit(request):
    """``tests/conftest.py`` as pytest loaded it (several directories
    hold a ``conftest.py`` and no ``__init__.py``, so the bare module
    name is whichever of them was imported first)."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "conftest.py")
    return next(p for p in request.config.pluginmanager.get_plugins()
                if getattr(p, "__file__", None) == path)


def test_a_sleeping_body_fails_by_name_and_the_timer_is_put_back(
        limit, monkeypatch, tmp_path):
    stderr = open(tmp_path / "stderr", "w+")
    monkeypatch.setattr(limit, "_stderr", stderr)

    def before(signum, frame):
        raise AssertionError("the outer handler fired")

    # this phase's own timer is running: this test's node id is what a
    # hang here would be reported under
    outer_handler = signal.getsignal(signal.SIGALRM)
    outer_left, outer_again = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < outer_left <= limit.TEST_LIMIT_S
    assert outer_again == limit.TEST_LIMIT_S / 10
    assert callable(outer_handler)
    assert not issubclass(limit.HungTest, Exception)

    signal.signal(signal.SIGALRM, before)
    try:
        with pytest.raises(limit.HungTest) as hung:
            with limit.time_limit(0.2, "tests/x.py::test_sleeps[case]"):
                time.sleep(5)
        said = str(hung.value)
        assert "tests/x.py::test_sleeps[case] ran past its 0.2 s" in said
        # every thread's stack, this one's down to the sleeping line
        assert "most recent call first" in said
        assert __file__ in said
        # handler and timer as they stood: the outer one still counts
        # down from where it was, and nothing fires after the body
        assert signal.getsignal(signal.SIGALRM) is before
        left, again = signal.getitimer(signal.ITIMER_REAL)
        assert outer_left - 6 < left < outer_left and again == outer_again
        # a body that catches the raise gets it again, a tenth of the
        # limit later each time, and the watching thread has meanwhile
        # named it on the process's stderr
        caught = []
        with pytest.raises(limit.HungTest):
            with limit.time_limit(0.5, "tests/x.py::test_swallows"):
                while len(caught) < 5:
                    try:
                        time.sleep(5)
                    except BaseException as e:
                        caught.append(e)
                time.sleep(5)
        assert all(isinstance(e, limit.HungTest) for e in caught)
        assert "tests/x.py::test_swallows ran past its 0.5 s limit and " \
            "has not come back:\n" in _read(stderr)
        assert "most recent call first" in _read(stderr)
        # a body inside its limit passes through and disarms as well
        with limit.time_limit(0.2, "quick"):
            pass
        time.sleep(0.3)
        assert signal.getsignal(signal.SIGALRM) is before
        assert "quick" not in _read(stderr)
    finally:
        signal.signal(signal.SIGALRM, outer_handler)


def _read(f):
    f.seek(0)
    return f.read()
