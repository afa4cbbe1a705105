"""The attention kernels' share of the MXU roofline under a learned
sparse attention: the attention FLOPs over the (query, key) pairs the
program's counter says the selection KEPT (forward 2 * H * kept * (d + d),
backward twice that) over the chip's peak, over the summed device time of
the flash-attention events. The needed work is the same whatever
implements it: a dense causal kernel that masks reads low, a kernel that
gathers the kept keys can read high."""
from . import _dsa


def read(ctx):
    seconds = _dsa.kernel_seconds_per_step(ctx, "flash_attention")
    if not seconds or ctx["peaks"] is None or _dsa.kept_pairs(ctx) is None:
        return None
    flops = ctx["family"].flops_per_step(
        ctx["sizes"], ctx["traffic"])["attention_step"]
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["flops_per_s"]) \
        / seconds
