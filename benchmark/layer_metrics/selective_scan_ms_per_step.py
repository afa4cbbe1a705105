"""Device milliseconds a step spends in the two selective-scan kernels of
the Mamba-1 mixers (`selective_scan_fwd`, `selective_scan_bwd`): the
summed device time of the events so named, over devices and steps."""
from . import _selective_scan


def read(ctx):
    seconds = _selective_scan.kernel_seconds_per_step(ctx)
    return None if seconds is None else seconds * 1e3
