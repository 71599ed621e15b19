"""``chip_smoke.py`` without a chip, and where the compile cache lives.

The smoke is the driver's proof that the system starts on the TPU, so
what must hold in THIS sandbox is the other half of its contract: with
no accelerator it exits non-zero and never prints the result line — at
once when run as the driver runs it, and after rehearsing every phase
at toy sizes under ``--tiny``.
"""

import os
import subprocess
import sys
import tempfile

import jax
import pytest

from chainermn_tpu.utils import compile_cache, enable_compile_cache

_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


def _smoke(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # one CPU device, as without a chip
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=280, cwd=_ROOT, env=env)


def test_no_chip_fails_before_any_phase():
    proc = _smoke()
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "[train-" not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_tiny_rehearsal_runs_every_phase_and_never_prints_ok():
    proc = _smoke("--tiny")
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert '"ok"' not in proc.stdout
    # every phase ran to its end, at toy sizes, kernels interpreted
    for mark in ("[train-transformer] compile_s=", "kernel=False",
                 "[train-resnet] world=1", "tokens_equal_static=True",
                 "[done]"):
        assert mark in proc.stdout, (mark, proc.stdout[-2000:],
                                     proc.stderr[-2000:])
    assert "not a chip check" in proc.stderr


@pytest.fixture()
def on_a_chip(monkeypatch):
    """Let ``enable_compile_cache`` believe it is on a TPU, and put the
    cache configuration back afterwards (no test may leave the
    persistent cache on for the rest of its worker)."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_traceback_in_locations_limit",
             "jax_compilation_cache_include_metadata_in_key")
    before = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_cache_dir_from_the_environment_is_left_to_jax(
        on_a_chip, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    # no directory was set in code: JAX's own reading of the variable
    # (made when jax was imported) is untouched
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(
        on_a_chip, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == os.path.join(_ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path           # the same every time
    tmp = os.path.realpath(tempfile.gettempdir())
    assert not os.path.realpath(path).startswith(tmp + os.sep)
    # and git ignores it
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_key_takes_in_the_name_stacks_and_nothing_of_the_path(
        on_a_chip, monkeypatch, tmp_path):
    """The per-layer metrics are read off op names in a profile, so an
    executable served from the cache must carry this program's names:
    the metadata goes into the key, and with no traceback in a location
    the metadata holds name stacks alone (two checkouts still hit each
    other)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    enable_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    assert jax.config.jax_traceback_in_locations_limit == 0


def test_cache_stays_off_on_the_cpu(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert compile_cache._CHECKOUT == _ROOT
