"""Kernels: the least time the chip could take for the MLA layers'
attention cores in a step (causal pairs at 2 x (192 + 128) FLOPs a pair
a head forward and 2.5 times that backward; K at 192 and V at 128 for
every head: ``lib/counts_hybrid.py``) over the time of the flash
kernels under ``attn/mla``."""

from benchmarks.lib.scopes import scope_seconds
from benchmarks.lib.scopes_hybrid import roofline_pct


def read(ctx):
    return roofline_pct(
        ctx, scope_seconds(ctx, "attn/mla",
                           among=set(ctx["facts"].get("kernels", ()))),
        ctx["facts"].get("flash_mla_flops_bytes"), "flash.mla_roofline")
