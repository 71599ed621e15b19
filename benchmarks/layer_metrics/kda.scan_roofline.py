"""Kernels: the least time the chip could take for the RECURRENCE of
the KDA layers in a step, whatever the chunking (a token a head three
products of 2 x 128 x 128 forward and twice that backward; q, k, v, g,
beta in and o out once a pass: ``lib/counts_hybrid.py``), over the time
under ``kda/scan``."""

from benchmarks.lib.scopes_hybrid import roofline_pct, scope_seconds


def read(ctx):
    return roofline_pct(ctx, scope_seconds(ctx, "kda/scan"),
                        ctx["facts"].get("kda_scan_flops_bytes"),
                        "kda.scan_roofline")
