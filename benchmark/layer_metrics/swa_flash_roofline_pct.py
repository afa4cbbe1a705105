"""The sliding-window flash kernels' share of the MXU roofline: the FLOPs
the window layers need — forward 2 * H * pairs * (d + d) over the (query,
key) pairs the program's `window_attn_pairs` counter says the bands
admitted, a step three forwards, nothing recomputed counted
(`_swa.window_flops_per_step`) — over the chip's peak, over the summed
device time of the `flash_attention_window_*` events. A kernel that
worked the whole causal square would read at most the band's share of it
(23.4% at S = 8,192 and a window of 1,024). None without the kernels'
events or the counter."""
from . import _swa


def read(ctx):
    seconds = _swa.kernel_seconds_per_step(ctx)
    pairs = _swa.admitted_pairs(ctx)
    if not seconds or ctx["peaks"] is None or pairs is None:
        return None
    flops = _swa.window_flops_per_step(ctx["sizes"], pairs)
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["flops_per_s"]) \
        / seconds
