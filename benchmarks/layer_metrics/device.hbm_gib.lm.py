"""Device: arguments + results + temporaries of the compiled step, from
its ``memory_analysis()``, per device."""

from benchmarks.lib.readings import hbm_gib as read  # noqa: F401
