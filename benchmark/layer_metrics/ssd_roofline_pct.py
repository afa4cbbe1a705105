"""The scan kernels' share of their roofline: the least time the chip
could take for the scans of a step — forward and backward each the
larger of their matrix products' FLOPs over the chip's peak and the HBM
bytes of x, B, C, dt (and dy) read and y (dx, dB, dC, d dt) written over
its bandwidth, the family's functions of the shapes, at the tokens the
program's counter says were scanned — over the summed device time of the
`mamba2_ssd_fwd` / `_bwd` events. The needed work is the same whatever
implements it."""
from . import _ssd


def read(ctx):
    seconds = _ssd.kernel_seconds_per_step(ctx)
    if not seconds or ctx["peaks"] is None \
            or _ssd.scanned_tokens(ctx) is None:
        return None
    least = ctx["family"].ssd_roofline_seconds_per_step(
        ctx["sizes"], ctx["traffic"], ctx["peaks"])
    return 100.0 * least / ctx["chips"] / seconds
