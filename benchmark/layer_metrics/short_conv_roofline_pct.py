"""The gated short convolution kernels' share of their roofline, which
HBM sets: the bytes a step's operators cannot avoid — forward the
in-projection's output X [3 D] read and the result [D] written, backward
X and d out read and dX written, in the compute type, the family's
functions of the shapes, at the tokens the program's counter says were
convolved — over the chip's bandwidth, over the summed device time of
the `gated_short_conv_fwd` / `_bwd` events. The needed traffic is the
same whatever implements it."""
from . import _short_conv


def read(ctx):
    seconds = _short_conv.kernel_seconds_per_step(ctx)
    if not seconds or ctx["peaks"] is None \
            or _short_conv.convolved_tokens(ctx) is None:
        return None
    least = ctx["family"].short_conv_roofline_seconds_per_step(
        ctx["sizes"], ctx["traffic"], ctx["peaks"])
    return 100.0 * least / ctx["chips"] / seconds
