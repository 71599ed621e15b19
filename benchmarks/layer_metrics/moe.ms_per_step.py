"""Expert layer: device time a step of everything the dropless expert
layer runs, device 0: the router, the sort and the rows' moves
(``moe/route``), the grouped products (``moe/experts``) and the gated
sum home (``moe/combine``), forward, recomputed forward and backward.
By the scope in each instruction's ``op_name`` (``lib/scopes.py``); an
earlier line of the run gives the parts."""

from benchmarks.lib.harness import log
from benchmarks.lib.readings import per_step_ms
from benchmarks.lib.scopes import scope_seconds

PARTS = ("moe/route", "moe/experts", "moe/combine")


def read(ctx):
    seconds = scope_seconds(ctx, "moe/")
    if seconds is None:
        return None
    log("moe.ms_per_step", **{
        part: f"{per_step_ms(ctx, scope_seconds(ctx, part) or 0):.3f}"
        for part in PARTS},
        attn_all_ops=f"{per_step_ms(ctx, scope_seconds(ctx, 'attn/') or 0):.3f}")
    return per_step_ms(ctx, seconds)
