"""The benchmark's tests import it as the ``benchmarks`` package from
the root of the checkout, as ``benchmarks/run.py`` itself does."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
