"""Step program: device self time a step, device 0, of the two ends of
the model: the embedding's rows and, backward, the scatter-add into its
gradient (``step/embed``); the final norm, the logits, the loss and
their backward (``step/head``).  An earlier line gives the two apart."""

from benchmarks.lib.harness import log
from benchmarks.lib.scopes_step import path_ms


def read(ctx):
    embed, head = path_ms(ctx, "step/embed") or 0.0, \
        path_ms(ctx, "step/head")
    if head is None:
        return None
    log("step.head_ms", embed=f"{embed:.3f}", head=f"{head:.3f}")
    return embed + head
