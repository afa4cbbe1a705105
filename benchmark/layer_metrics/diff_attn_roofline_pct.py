"""Differential attention's share of the MXU roofline: the FLOPs its
flash kernels need — every query head (two maps a differential head)
over the (query, key) pairs admitted, q k^T over the key width and p v
over the value width twice as wide, forward 2 * H * pairs * 3 d, a step
three forwards, nothing recomputed counted; the pairs those the
program's `window_attn_pairs` counter says the window layers took, the
full and cross layers' causal — over the chip's
peak, over the summed device time of every flash event of the step
(`flash_attention_*` and `flash_attention_window_*`). None without the
events or the counter."""
from . import _dsa, _selective_scan


def read(ctx):
    seconds = sum(_dsa.kernel_seconds_per_step(ctx, k) or 0.0
                  for k in ("flash_attention", "flash_attention_window"))
    if not seconds or ctx["peaks"] is None \
            or _selective_scan.window_pairs(ctx) is None:
        return None
    fam, sz, tr = ctx["family"], ctx["sizes"], ctx["traffic"]
    flops = 3 * fam.attention_flops_forward(
        sz, fam.attention_pairs_per_step(sz, tr))
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["flops_per_s"]) \
        / seconds
