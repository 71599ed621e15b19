"""Native host runtime — C++ batch loader and pack/unpack (see
``loader.cpp`` for the design; the reference's native host layer was
pinned-memory arenas + CuPy pack kernels in ``_memory_utility.py``,
unverified — mount empty, see SURVEY.md).

The shared library is built lazily with ``g++`` on first use and kept
next to the source under a name that carries the source's hash, so the
library that runs is always the one built from ``loader.cpp`` as it
stands (whatever the files' mtimes say); everything degrades to a documented pure-Python
fallback when no compiler is available (``native_available()``).
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "NativeBatchIterator",
    "native_available",
    "pack_arrays",
    "unpack_arrays",
]

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "loader.cpp")
_LIB_GLOB = os.path.join(_DIR, "_libcmn_native.*.so")
_lock = threading.Lock()
_lib = None
_build_error: Optional[str] = None


def _lib_path() -> str:
    """``_libcmn_native.<hash of loader.cpp>.so`` (git-ignored)."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_libcmn_native.{digest}.so")


def _build(lib_path: str) -> Optional[str]:
    # build beside the target and rename: concurrent builders (xdist
    # workers) each publish a complete library or nothing
    fd, tmp = tempfile.mkstemp(dir=_DIR, prefix="_libcmn_native.",
                               suffix=".tmp")
    os.close(fd)
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
           _SRC, "-o", tmp]
    try:
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=300)
        except (FileNotFoundError, subprocess.TimeoutExpired) as e:
            return f"{type(e).__name__}: {e}"
        if proc.returncode != 0:
            return proc.stderr[-2000:]
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    for stale in glob.glob(_LIB_GLOB):      # built from an older source
        if stale != lib_path:
            with contextlib.suppress(FileNotFoundError):
                os.remove(stale)
    return None


def _load():
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        lib_path = _lib_path()
        if not os.path.exists(lib_path):
            _build_error = _build(lib_path)
            if _build_error is not None:
                return None
        lib = ctypes.CDLL(lib_path)
        lib.cmn_loader_create.restype = ctypes.c_void_p
        lib.cmn_loader_create.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int, ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
            ctypes.c_int, ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
        ]
        lib.cmn_loader_next.restype = ctypes.c_int
        lib.cmn_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.cmn_loader_release.restype = None
        lib.cmn_loader_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.cmn_loader_destroy.restype = None
        lib.cmn_loader_destroy.argtypes = [ctypes.c_void_p]
        for name in ("cmn_pack", "cmn_unpack"):
            fn = getattr(lib, name)
            fn.restype = None
        lib.cmn_pack.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int, ctypes.c_void_p, ctypes.c_int,
        ]
        lib.cmn_unpack.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    """True when the C++ runtime is (or can be) built and loaded."""
    return _load() is not None


def _native_perm(n: int, seed: int, epoch: int) -> np.ndarray:
    """EXACTLY the permutation loader.cpp builds (std::mt19937_64 +
    top-down Fisher-Yates with ``rng() % (i+1)``), so a seeded run
    yields identical batch order whether or not the native library is
    available."""
    state = np.empty(312, np.uint64)
    mask = 0xFFFFFFFFFFFFFFFF
    s = (seed + 0x9E3779B97F4A7C15 * (epoch + 1)) & mask
    state[0] = s
    for i in range(1, 312):
        # python-int arithmetic: intended mod-2^64 wraparound without
        # numpy's overflow warnings
        s = (6364136223846793005 * (s ^ (s >> 62)) + i) & mask
        state[i] = s
    idx = 312

    def gen():
        nonlocal state, idx
        if idx >= 312:
            # mt19937_64 twist — sequential, because entries past the
            # wrap point read values already twisted this round
            upper = np.uint64(0xFFFFFFFF80000000)
            lower = np.uint64(0x7FFFFFFF)
            for i in range(312):
                x = ((state[i] & upper)
                     | (state[(i + 1) % 312] & lower))
                xa = x >> np.uint64(1)
                if x & np.uint64(1):
                    xa ^= np.uint64(0xB5026F5AA96619E9)
                state[i] = state[(i + 156) % 312] ^ xa
            idx = 0
        y = state[idx]
        idx += 1
        y ^= (y >> np.uint64(29)) & np.uint64(0x5555555555555555)
        y ^= (y << np.uint64(17)) & np.uint64(0x71D67FFFEDA60000)
        y ^= (y << np.uint64(37)) & np.uint64(0xFFF7EEE000000000)
        y ^= y >> np.uint64(43)
        return int(y)

    perm = np.arange(n, dtype=np.int64)
    for i in range(n - 1, 0, -1):
        j = gen() % (i + 1)
        perm[i], perm[j] = perm[j], perm[i]
    return perm


# --------------------------------------------------------------------- #
# batch loader
# --------------------------------------------------------------------- #


class NativeBatchIterator:
    """Prefetching batch iterator over memory-resident field arrays.

    API-compatible with :class:`chainermn_tpu.SerialIterator` where the
    trainer touches it (``epoch``, ``epoch_detail``, ``reset``,
    ``__next__`` → tuple of per-field batch arrays), but batch assembly
    happens in C++ worker threads *ahead* of the training step.

    The returned arrays are **views into a recycled slot**: consume them
    (``jax.device_put`` / copy) before the next ``__next__`` call.  This
    is the single-consumer ring-buffer contract of the native loader.
    In particular, a ``StandardUpdater`` converter that will HOLD more
    than one batch (``steps_per_execution`` windows) must copy —
    ``lambda b: tuple(np.array(a) for a in b)`` — or earlier views in
    the window will be overwritten by the prefetch threads.

    Falls back to equivalent in-process numpy assembly when the native
    library is unavailable (``native_available()`` False).
    """

    def __init__(self, arrays: Sequence[np.ndarray], batch_size: int,
                 repeat: bool = True, shuffle: bool = False,
                 seed: int = 0, n_slots: int = 3, n_threads: int = 2,
                 drop_last: bool = True):
        if not arrays:
            raise ValueError("need at least one field array")
        n = len(arrays[0])
        if any(len(a) != n for a in arrays):
            raise ValueError("field arrays must share their leading dim")
        if drop_last and n < batch_size:
            raise ValueError(
                f"dataset of {n} examples smaller than one batch "
                f"({batch_size}) with drop_last")
        self._arrays = [np.ascontiguousarray(a) for a in arrays]
        self.batch_size = batch_size
        self._repeat = repeat
        self._shuffle = shuffle
        self._seed = seed
        self._drop_last = drop_last
        self._n = n
        self._bpe = (n // batch_size if drop_last
                     else (n + batch_size - 1) // batch_size)
        self._n_slots = n_slots
        self._n_threads = n_threads
        self.epoch = 0
        self._popped = 0
        self._pending_release = -1
        self._handle = None
        self._lib = _load()
        if self._lib is not None:
            self._create()

    def _create(self):
        fields = (ctypes.c_void_p * len(self._arrays))(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in self._arrays])
        itemsizes = (ctypes.c_int64 * len(self._arrays))(
            *[a.dtype.itemsize * int(np.prod(a.shape[1:], dtype=np.int64))
              for a in self._arrays])
        handle = self._lib.cmn_loader_create(
            fields, itemsizes, len(self._arrays), self._n,
            self.batch_size, self._n_slots, self._n_threads,
            self._seed, int(self._shuffle), int(self._drop_last))
        if not handle:
            raise RuntimeError("cmn_loader_create failed")
        self._handle = handle

    # ------------------------------------------------------------------ #
    # iterator protocol (trainer-compatible surface)
    # ------------------------------------------------------------------ #

    @property
    def repeat(self) -> bool:
        return self._repeat

    def owns_buffers(self, arrays) -> bool:
        """True in native mode: returned batches are views into recycled
        slots, so a consumer that defers the host→device copy (sharded
        ``jax.device_put`` — see ``iterators.prefetch.put_window``) must
        copy them first.  The numpy fallback returns fresh fancy-index
        copies, which nobody rewrites."""
        return self._handle is not None

    @property
    def epoch_detail(self) -> float:
        return self._popped / self._bpe

    def reset(self):
        # rebuild the native pipeline so batch order restarts at epoch 0
        if self._handle is not None:
            self._lib.cmn_loader_destroy(self._handle)
            self._handle = None
            self._create()
        self.epoch = 0
        self._popped = 0
        self._pending_release = -1

    def __iter__(self):
        return self

    def __next__(self) -> Tuple[np.ndarray, ...]:
        if not self._repeat and self._popped >= self._bpe:
            raise StopIteration
        if self._handle is not None:
            return self._next_native()
        return self._next_fallback()

    def _next_native(self):
        lib = self._lib
        if self._pending_release >= 0:
            lib.cmn_loader_release(self._handle, self._pending_release)
        ptrs = (ctypes.c_void_p * len(self._arrays))()
        rows = ctypes.c_int64()
        epoch = ctypes.c_int64()
        slot = lib.cmn_loader_next(
            self._handle, ptrs, ctypes.byref(rows), ctypes.byref(epoch))
        self._pending_release = slot
        out = []
        for a, p in zip(self._arrays, ptrs):
            shape = (int(rows.value),) + a.shape[1:]
            buf = (ctypes.c_char * (
                int(rows.value) * a.dtype.itemsize
                * int(np.prod(a.shape[1:], dtype=np.int64)))
            ).from_address(p)
            out.append(np.frombuffer(buf, dtype=a.dtype).reshape(shape))
        self._popped += 1
        self.epoch = self._popped // self._bpe
        return tuple(out)

    def _next_fallback(self):
        ep, in_ep = divmod(self._popped, self._bpe)
        if self._shuffle:
            perm = _native_perm(self._n, self._seed, ep)
        else:
            perm = np.arange(self._n)
        idx = perm[in_ep * self.batch_size:
                   in_ep * self.batch_size + self.batch_size]
        self._popped += 1
        self.epoch = self._popped // self._bpe
        return tuple(a[idx] for a in self._arrays)

    def __del__(self):  # pragma: no cover
        if getattr(self, "_handle", None) is not None:
            self._lib.cmn_loader_destroy(self._handle)
            self._handle = None


# --------------------------------------------------------------------- #
# pack / unpack
# --------------------------------------------------------------------- #


def pack_arrays(arrays: Sequence[np.ndarray],
                n_threads: int = 4) -> np.ndarray:
    """Concatenate array bytes into one contiguous uint8 buffer using the
    C++ thread pool (falls back to numpy when unavailable)."""
    arrays = [np.ascontiguousarray(a) for a in arrays]
    sizes = [a.nbytes for a in arrays]
    out = np.empty(sum(sizes), np.uint8)
    lib = _load()
    if lib is None or not arrays:
        off = 0
        for a, s in zip(arrays, sizes):
            out[off:off + s] = a.view(np.uint8).reshape(-1)
            off += s
        return out
    srcs = (ctypes.c_void_p * len(arrays))(
        *[a.ctypes.data_as(ctypes.c_void_p) for a in arrays])
    csizes = (ctypes.c_int64 * len(arrays))(*sizes)
    lib.cmn_pack(srcs, csizes, len(arrays),
                 out.ctypes.data_as(ctypes.c_void_p), n_threads)
    return out


def unpack_arrays(packed: np.ndarray, templates: Sequence[np.ndarray],
                  n_threads: int = 4):
    """Inverse of :func:`pack_arrays`: split ``packed`` into arrays with
    the shapes/dtypes of ``templates``."""
    packed = np.ascontiguousarray(packed.view(np.uint8).reshape(-1))
    outs = [np.empty(t.shape, t.dtype) for t in templates]
    sizes = [o.nbytes for o in outs]
    if sum(sizes) != packed.nbytes:
        raise ValueError(
            f"packed buffer of {packed.nbytes} bytes does not match "
            f"templates totalling {sum(sizes)}")
    lib = _load()
    if lib is None or not outs:
        off = 0
        for o, s in zip(outs, sizes):
            o.view(np.uint8).reshape(-1)[:] = packed[off:off + s]
            off += s
        return outs
    dsts = (ctypes.c_void_p * len(outs))(
        *[o.ctypes.data_as(ctypes.c_void_p) for o in outs])
    csizes = (ctypes.c_int64 * len(outs))(*sizes)
    lib.cmn_unpack(packed.ctypes.data_as(ctypes.c_void_p), csizes,
                   len(outs), dsts, n_threads)
    return outs
