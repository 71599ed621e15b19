"""Host-side batch-assembly throughput: C++ NativeBatchIterator vs the
pure-Python fallback (the same gather numpy would do in-process).

The loader is HOST work — no TPU involved — so this runs anywhere and
directly: value = native/python assembly-throughput ratio on an
ImageNet-shaped shard (images/sec each recorded as extras).  The win
comes from assembling batches in C++ worker threads AHEAD of the
consumer (prefetch into a slot ring), so the training step never waits
on host gather — on the 1-core container the visible ratio also folds
in thread-scheduling overhead, making it a conservative lower bound.

Prints ONE JSON line (bench contract); records to the run history (BENCH_HISTORY.json).
"""

import argparse
import json
import sys
import time

import numpy as np

from _bench_common import record_measurement

METRIC = "native_loader_assembly_speedup_vs_python"
UNIT = "x"


def _consume(it, n_batches):
    t0 = time.perf_counter()
    rows = 0
    for _ in range(n_batches):
        out = next(it)
        # touch one byte per field so lazily-materialised views count
        rows += out[0].shape[0]
        _ = out[0].ravel()[0], out[-1].ravel()[0]
    return rows / (time.perf_counter() - t0)


def run(n=2048, image=64, batch=256, batches=64, shuffle=True):
    from chainermn_tpu.native import NativeBatchIterator, native_available

    rng = np.random.RandomState(0)
    x = rng.randn(n, image, image, 3).astype(np.float32)
    y = rng.randint(0, 1000, size=n).astype(np.int32)

    nat = NativeBatchIterator([x, y], batch, shuffle=shuffle, seed=3,
                              n_threads=2)
    native_used = nat._handle is not None
    # warm the prefetch ring, then measure steady-state
    _consume(nat, 4)
    nat_rate = _consume(nat, batches)

    py = NativeBatchIterator([x, y], batch, shuffle=shuffle, seed=3)
    py._handle, keep = None, py._handle   # force the python fallback
    try:
        _consume(py, 4)
        py_rate = _consume(py, batches)
    finally:
        py._handle = keep

    return {
        "metric": METRIC,
        "value": round(nat_rate / py_rate, 3),
        "unit": UNIT,
        "vs_baseline": round(nat_rate / py_rate, 3),
        "native_images_per_sec": round(nat_rate, 1),
        "python_images_per_sec": round(py_rate, 1),
        "native_backend": bool(native_used and native_available()),
        "batch": batch, "image": image, "n": n,
    }


def main(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--n", type=int, default=2048)
    p.add_argument("--image", type=int, default=64)
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--batches", type=int, default=64)
    args = p.parse_args(argv)
    result = run(n=args.n, image=args.image, batch=args.batch,
                 batches=args.batches)
    try:
        record_measurement(result)
    except Exception:
        pass
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
