"""Device milliseconds a step spends in the two state-space scan kernels
of the Mamba-2 mixers (`mamba2_ssd_fwd`, `mamba2_ssd_bwd`): the summed
device time of the events so named, over devices and steps."""
from . import _ssd


def read(ctx):
    seconds = _ssd.kernel_seconds_per_step(ctx)
    return None if seconds is None else seconds * 1e3
