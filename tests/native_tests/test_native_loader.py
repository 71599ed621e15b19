"""Native C++ loader: build, coverage/determinism, ring-buffer reuse,
pack/unpack round-trip, and fallback parity."""

import numpy as np
import pytest

from chainermn_tpu import native
from chainermn_tpu.native import (
    NativeBatchIterator,
    native_available,
    pack_arrays,
    unpack_arrays,
)

N, BS = 64, 16


def fields(n=N, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 7, 3).astype(np.float32)
    y = rng.randint(0, 10, size=n).astype(np.int32)
    return x, y


def test_native_builds():
    assert native_available(), native._build_error


def collect_epoch(it):
    xs, ys = [], []
    start = it.epoch
    while it.epoch == start:
        x, y = next(it)
        xs.append(x.copy())   # views are recycled — copy to keep
        ys.append(y.copy())
    return np.concatenate(xs), np.concatenate(ys)


def test_library_follows_the_source_not_mtimes(tmp_path, monkeypatch):
    """The library that runs is the one built from loader.cpp as it
    stands: its file name carries the source's hash, so an edited
    source is rebuilt even when a stale binary is NEWER than it (a
    copied tree, a checkout) — and an untouched source is not."""
    import os
    import shutil

    src = tmp_path / "loader.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_SRC", str(src))
    same = native._lib_path()
    assert same == native._lib_path()
    with open(src, "a") as f:
        f.write("\n// edited\n")
    os.utime(src, (0, 0))           # older than any binary on disk
    assert native._lib_path() != same


def test_sequential_coverage_and_order():
    x, y = fields()
    it = NativeBatchIterator([x, y], BS, shuffle=False)
    gx, gy = collect_epoch(it)
    np.testing.assert_array_equal(gx, x)
    np.testing.assert_array_equal(gy, y)
    # second epoch repeats identically when not shuffling
    gx2, _ = collect_epoch(it)
    np.testing.assert_array_equal(gx2, x)


def test_shuffle_covers_and_differs_by_epoch():
    x, y = fields()
    it = NativeBatchIterator([x, y], BS, shuffle=True, seed=7)
    gx1, gy1 = collect_epoch(it)
    gx2, _ = collect_epoch(it)
    # same multiset of labels, different order across epochs
    np.testing.assert_array_equal(np.sort(gy1), np.sort(y))
    assert not np.array_equal(gx1, gx2)
    # label/image pairing preserved through the gather
    lookup = {xx.tobytes(): yy for xx, yy in zip(x, y)}
    for row, lab in zip(gx1, gy1):
        assert lookup[row.tobytes()] == lab


def test_shuffle_deterministic_given_seed():
    x, y = fields()
    a = NativeBatchIterator([x, y], BS, shuffle=True, seed=3)
    b = NativeBatchIterator([x, y], BS, shuffle=True, seed=3)
    for _ in range(8):
        xa, ya = next(a)
        xb, yb = next(b)
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)


def test_ring_reuse_many_epochs():
    """More pops than slots — exercises release/recycle and ordering."""
    x, y = fields()
    it = NativeBatchIterator([x, y], BS, shuffle=True, seed=1,
                             n_slots=2, n_threads=3)
    seen = 0
    for _ in range(20):
        xb, yb = next(it)
        assert xb.shape == (BS, 7, 3)
        seen += len(yb)
    assert seen == 20 * BS
    assert it.epoch == 20 * BS // N


def test_non_repeating_stops():
    x, y = fields()
    it = NativeBatchIterator([x, y], BS, repeat=False)
    batches = list(it)
    assert len(batches) == N // BS
    it.reset()
    assert len(list(it)) == N // BS


def test_fallback_matches_native_sequential():
    x, y = fields()
    nat = NativeBatchIterator([x, y], BS, shuffle=False)
    fb = NativeBatchIterator([x, y], BS, shuffle=False)
    fb._handle, fb._lib = None, None   # force the numpy path
    for _ in range(6):
        xa, ya = next(nat)
        xb, yb = next(fb)
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)


def test_pack_unpack_roundtrip():
    rng = np.random.RandomState(0)
    arrays = [rng.randn(13, 5).astype(np.float32),
              rng.randint(0, 100, size=(7,)).astype(np.int64),
              rng.randn(2, 3, 4).astype(np.float16)]
    packed = pack_arrays(arrays)
    assert packed.nbytes == sum(a.nbytes for a in arrays)
    outs = unpack_arrays(packed, arrays)
    for a, b in zip(arrays, outs):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError):
        unpack_arrays(packed[:-1], arrays)


def test_validation_errors():
    x, y = fields()
    with pytest.raises(ValueError):
        NativeBatchIterator([], BS)
    with pytest.raises(ValueError):
        NativeBatchIterator([x, y[:10]], BS)
    with pytest.raises(ValueError):
        NativeBatchIterator([x[:8]], BS)


def test_fallback_shuffle_matches_native():
    """Seeded shuffle order must not depend on whether the C++ library
    is available — the fallback replicates mt19937_64 Fisher-Yates."""
    x, y = fields()
    nat = NativeBatchIterator([x, y], BS, shuffle=True, seed=11)
    fb = NativeBatchIterator([x, y], BS, shuffle=True, seed=11)
    fb._handle, fb._lib = None, None
    for _ in range(2 * (N // BS) + 1):   # crosses an epoch boundary
        xa, ya = next(nat)
        xb, yb = next(fb)
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)


def test_matches_serial_iterator_batch_for_batch():
    """The trainer-facing contract: NativeBatchIterator + identity
    converter must hand StandardUpdater the SAME batch arrays as
    SerialIterator + default_converter (sequential order — the two
    shuffles are different algorithms by design)."""
    from chainermn_tpu import SerialIterator
    from chainermn_tpu.training import default_converter

    x, y = fields()
    data = list(zip(x, y))
    serial = SerialIterator(data, BS, shuffle=False)
    nat = NativeBatchIterator([x, y], BS, shuffle=False)
    for _ in range(2 * (N // BS) + 1):      # spans an epoch boundary
        sx, sy = default_converter(next(serial))
        nx, ny = next(nat)
        np.testing.assert_array_equal(nx, sx)
        np.testing.assert_array_equal(ny, sy)
