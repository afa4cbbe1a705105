"""The flash-attention kernels' share of the MXU roofline where grouped
queries pack two 64-wide heads into a lane block and both read one key
head: causal attention's FLOPs of the cell's shapes (forward 2 * B * H *
causal pairs * (64 + 64) a layer, a step three forwards, nothing
recomputed counted: the family's `attention_step`) over the chip's peak,
over the summed device time of the flash-attention events. A family
without an `attention_step`, or a trace without flash events, gives
nothing to read."""
from . import _dsa


def read(ctx):
    seconds = _dsa.kernel_seconds_per_step(ctx, "flash_attention")
    if not seconds or ctx["peaks"] is None:
        return None
    flops = ctx["family"].flops_per_step(
        ctx["sizes"], ctx["traffic"]).get("attention_step")
    if not flops:
        return None
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["flops_per_s"]) \
        / seconds
