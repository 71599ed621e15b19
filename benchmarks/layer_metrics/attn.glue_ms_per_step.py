"""Step program: device self time a step, device 0, of what an
attention layer does besides its projections and its core: rotary
(``attn.rope``), the copy of K/V out to the query heads
(``attn.kv_repeat``), the gate (``attn.gate``) and what wears no child
of ``attn/<kind>`` (the relayouts to the kernel's layout and back, the
backward's ``delta``, the residual add).  An earlier line gives each
part, the core and the whole: the parts sum to it."""

from benchmarks.lib.harness import log
from benchmarks.lib.scopes_step import attention_parts

GLUE = ("attn.rope", "attn.kv_repeat", "attn.gate", "rest")


def read(ctx):
    parts = attention_parts(ctx)
    if parts is None or not parts["whole"]:
        return None
    log("attn.glue_ms_per_step",
        **{k: f"{v:.3f}" for k, v in sorted(parts.items())})
    return sum(parts.get(part, 0.0) for part in GLUE)
