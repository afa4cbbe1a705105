"""Device milliseconds a step spends in the two kernels of the gated
short convolution layers (`gated_short_conv_fwd`, `gated_short_conv_bwd`):
the summed device time of the events so named, over devices and steps."""
from . import _short_conv


def read(ctx):
    seconds = _short_conv.kernel_seconds_per_step(ctx)
    return None if seconds is None else seconds * 1e3
