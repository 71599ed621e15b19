"""Expert layer: device time a step, device 0, of what moves the rows
and not of what multiplies them: ``moe/route`` (router, sort, the rows'
gather out) and ``moe/combine`` (the gather home, the gated sum).  The
sorted buffer has all ``N x k`` rows whatever share of them is routed
to the experts held here, so at a small held share this is the larger
part of ``moe.ms_per_step``."""

from benchmarks.lib.readings import per_step_ms
from benchmarks.lib.scopes import scope_seconds


def read(ctx):
    parts = [scope_seconds(ctx, part)
             for part in ("moe/route", "moe/combine")]
    if all(part is None for part in parts):
        return None
    return per_step_ms(ctx, sum(part or 0 for part in parts))
