"""Where compiled programs are kept between processes.

Every chip call starts on a fresh machine, and a cold 300M train step,
ResNet-50 and the decode programs take minutes to compile.  JAX's
persistent compilation cache keys entries on (among other things) the
cache directory, so the directory must not move between runs.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; call before the first
    jit.  Returns the directory in use, or None on the CPU backend,
    where nothing is cached: the tests and the rehearsals compile
    afresh (and XLA:CPU logs an error for every entry it reads back).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of it
    is left alone and no directory is set in code.  Where it is not, the
    cache goes to the fixed ``<checkout>/.jax_cache`` (git-ignored) —
    never a temp dir, a pid or a timestamp, which would never hit.

    The key of an entry takes in the ops' metadata (their name stacks:
    file paths and line numbers are switched off, see below), because
    the per-layer metrics are read off the op names in a profile and a
    stale name is a wrong number.

    Asking which backend this is initialises it: in a multi-host
    program call ``init_distributed`` first.
    """
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # keep the sub-second programs too: a warm run should compile
    # nothing it compiled before
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # a Pallas kernel's payload carries the Python call stack of every
    # op (file paths, line numbers), and that payload is part of the
    # entry's key: with it, a checkout in another directory — or an
    # edit that shifts a line above the kernel — never hits
    jax.config.update("jax_traceback_in_locations_limit", 0)
    # ...and with the limit at 0 a location holds the op's name stack
    # and nothing else, so it can go back INTO the key.  JAX leaves it
    # out by default and says what that costs: "executables loaded from
    # the cache may have stale metadata, which may show up in profiles".
    # This repo reads its per-layer metrics off those op names
    # (``utils.telemetry.classify_op_name``): a step that differs from
    # a cached one only by a scope's name would otherwise be served the
    # old executable, whose ops lack the scope: a wrong measurement,
    # not a cosmetic fault.  Two checkouts in different directories
    # still hit each other (no path or line is left in a location)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path
