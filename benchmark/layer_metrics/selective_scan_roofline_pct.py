"""The selective-scan kernels' share of their roofline: the least time the
chip could take for the scans of a step — the HBM bytes their operators
cannot avoid (forward: x, dt, B, C read, y written; backward: those and
dy read, dx, d dt, dB, dC written; the family's function of the shapes)
at the tokens the program's `selective_scan_tokens` counter says were
scanned, over the chip's bandwidth (the recurrence has no matrix
product) — over the summed device time of the `selective_scan_fwd` /
`_bwd` events. The bytes are the same whatever implements the scan."""
from . import _selective_scan


def read(ctx):
    seconds = _selective_scan.kernel_seconds_per_step(ctx)
    if not seconds or ctx["peaks"] is None \
            or _selective_scan.scanned_tokens(ctx) is None:
        return None
    least = ctx["family"].selective_scan_roofline_seconds_per_step(
        ctx["sizes"], ctx["traffic"], ctx["peaks"])
    return 100.0 * least / ctx["chips"] / seconds
