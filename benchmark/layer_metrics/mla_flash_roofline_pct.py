"""The three flash-attention kernels' share of the MXU roofline where q/k
and v differ in width (latent attention): attention FLOPs of the cell's
shapes counted at the two widths (forward 2*B*H*Sq*Sk*(d_qk + d_v) an
attention, causal half; backward twice that; nothing recomputed) over the
chip's peak, over the summed device time of the events named
`flash_attention_fwd` / `_dq` / `_dkv`."""
from . import _named

_KERNELS = ("flash_attention_fwd", "flash_attention_dq",
            "flash_attention_dkv")


def read(ctx):
    ms = [m for m in (_named.kernel_ms_per_step(ctx, "flash_attention", name)
                      for name in _KERNELS) if m]
    if not ms or ctx["peaks"] is None:
        return None
    flops = ctx["family"].flops_per_step(
        ctx["sizes"], ctx["traffic"])["attention_step"]
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["flops_per_s"]) \
        / (sum(ms) / 1e3)
