"""``parallel/_compat.py`` — the one place the package's shard_map
vocabulary is resolved.  The failure mode it guards: an import chain
(`models.transformer` → `_compat`) raising ImportError on the installed
jax takes every test file that imports the models down *at collection*
(``all_gather_invariant`` is not public in jax 0.9.0).  These tests pin
that every symbol resolves and behaves on the installed jax."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from chainermn_tpu.parallel import _compat

AX = "world"


def test_models_transformer_imports_cleanly():
    """THE regression: this exact import is the one 35 test files once
    died on.  Run in a fresh interpreter so a warm ``sys.modules``
    can't mask an import-time failure."""
    import os
    import subprocess
    import sys

    root = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", ".."))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=root + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", "import chainermn_tpu.models.transformer"],
        capture_output=True, text=True, timeout=240, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_compat_exports_resolve():
    for name in _compat.__all__:
        assert getattr(_compat, name) is not None


def test_jax_namespace_has_the_spellings_the_package_uses():
    """Call sites across the package use these spellings directly."""
    assert callable(jax.shard_map)
    assert callable(jax.typeof)
    assert callable(jax.lax.axis_size)
    assert callable(jax.lax.pcast)


def test_all_gather_invariant_gathers(comm):
    """The primitive gathers a varying value into the
    identical full array on every member — and the result types as
    replicated (out_specs P() must be accepted)."""
    n = comm.size
    x = np.random.RandomState(0).randn(n, 3).astype(np.float32)

    f = jax.jit(jax.shard_map(
        lambda s: _compat.all_gather_invariant(
            s[:, 0], comm.axis_name, tiled=True),
        mesh=comm.mesh, in_specs=P(comm.axis_name), out_specs=P()))
    np.testing.assert_allclose(np.asarray(f(x)), x[:, 0], rtol=1e-6)


def test_axis_size_is_static(comm):
    """axis_size must fold to a python int under tracing — shapes
    (zero1 shard widths, pipeline stages) are built from it."""
    sizes = []

    def body(s):
        k = _compat.axis_size(comm.axis_name)
        sizes.append(k)
        return jnp.zeros((k,))[None]  # a SHAPE built from it

    out = jax.jit(jax.shard_map(
        body, mesh=comm.mesh, in_specs=P(comm.axis_name),
        out_specs=P(comm.axis_name)))(
            np.zeros((comm.size, 1), np.float32))
    assert sizes[0] == comm.size
    assert out.shape == (comm.size, comm.size)


def test_pcast_and_typeof_roundtrip(comm):
    """pcast retypes without changing values — also a value that is
    ALREADY varying over the axis, which ``lax.pcast`` on jax 0.9
    refuses (callers cannot know how their operand arrives); typeof
    exposes the ``vma`` set."""
    x = np.random.RandomState(1).randn(comm.size, 4).astype(np.float32)

    def body(s):
        assert comm.axis_name in _compat.typeof(s).vma  # arrives varying
        v = _compat.pcast(s, (comm.axis_name,), to="varying")
        inv = jnp.zeros(s.shape, s.dtype)               # arrives invariant
        assert comm.axis_name not in _compat.typeof(inv).vma
        inv = _compat.pcast(inv, comm.axis_name, to="varying")
        assert comm.axis_name in _compat.typeof(inv).vma
        return v + inv

    out = jax.jit(jax.shard_map(
        body, mesh=comm.mesh, in_specs=P(comm.axis_name),
        out_specs=P(comm.axis_name)))(x)
    np.testing.assert_allclose(np.asarray(out), x, rtol=1e-6)
