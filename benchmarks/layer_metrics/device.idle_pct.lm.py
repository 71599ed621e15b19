"""Device: 1 - union of op intervals over the traced window, on the
worst device."""

from benchmarks.lib.readings import idle_pct as read  # noqa: F401
