"""Device milliseconds a step spends in the `flash_attention_fwd`
kernel: the summed device time of the events so named, over devices and
steps."""
from . import _named


def read(ctx):
    return _named.kernel_ms_per_step(ctx, "flash_attention",
                                     "flash_attention_fwd")
