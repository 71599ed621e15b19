"""Step program: device self time a step, device 0, of the attention
layers' projections: the input norm and the fused q/k/v (and gate)
product (``attn.qkv``) and the output product (``attn.out``), forward,
recomputed and backward, every kind of layer together."""

from benchmarks.lib.harness import log
from benchmarks.lib.scopes_step import attention_parts


def read(ctx):
    parts = attention_parts(ctx)
    if parts is None or not parts["whole"]:
        return None
    qkv, out = parts.get("attn.qkv", 0.0), parts.get("attn.out", 0.0)
    log("attn.proj_ms_per_step", qkv=f"{qkv:.3f}", out=f"{out:.3f}")
    return qkv + out
