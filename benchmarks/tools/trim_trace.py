"""Cut a recorded trace down to what the reduction reads and write it
as gzipped JSON: small enough to keep beside the tests.

    python benchmarks/tools/trim_trace.py <trace_dir> <out.json.gz> [--structure]
"""

import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import trace    # noqa: E402


def main():
    trace_dir, out = sys.argv[1], sys.argv[2]
    if "--structure" in sys.argv:
        for row in trace.structure(trace_dir):
            print(*row)
    planes = trace.load(trace_dir)
    with gzip.open(out, "wt") as f:
        json.dump(planes, f)
    print(out, os.path.getsize(out), "bytes;",
          {p: {ln: len(ev) for ln, ev in lines.items()}
           for p, lines in planes.items()})


if __name__ == "__main__":
    main()
