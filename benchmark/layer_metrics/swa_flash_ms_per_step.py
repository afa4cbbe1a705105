"""Device milliseconds a step spends in the sliding-window layers' flash
kernels (`flash_attention_window_fwd`, `flash_attention_window_bwd`): the
summed device time of the events so named, over devices and steps."""
from . import _swa


def read(ctx):
    seconds = _swa.kernel_seconds_per_step(ctx)
    return None if seconds is None else seconds * 1e3
