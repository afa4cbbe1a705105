"""The grouped-matmul kernels' share of the MXU roofline: the routed
experts' FLOPs of a step (2 * rows * hidden * expert width * 3 matrices *
3 passes — forward, dx, dw — with `rows` the program's own count of rows
routed to held experts, padding not counted) over the chip's peak, over
the kernels' device time a step."""
from . import _moe


def read(ctx):
    seconds = _moe.gmm_seconds_per_step(ctx)
    if not seconds or ctx["peaks"] is None or _moe.expert_load(ctx) is None:
        return None
    flops = ctx["family"].flops_per_step(
        ctx["sizes"], ctx["traffic"])["routed_step"]
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["flops_per_s"]) \
        / seconds
