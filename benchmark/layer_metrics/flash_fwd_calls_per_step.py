"""Calls of the `flash_attention_fwd` kernel a step makes, over devices:
a count, which repeats exactly. (18 attentions a step in the 6+6-layer
model; the backward pass runs the forward again where it recomputes.)"""
from . import _named


def read(ctx):
    ops = _named.named_kernel_events(ctx, "flash_attention",
                                     "flash_attention_fwd")
    if ops is None or not ctx["steps"]:
        return None
    return len(ops) / ctx["trace"]["n_devices"] / ctx["steps"]
