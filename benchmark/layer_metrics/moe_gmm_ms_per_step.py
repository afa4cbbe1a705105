"""Device milliseconds a step spends in the three grouped-matmul kernels
of the expert layers (`moe_grouped_matmul_fwd`, `_dx`, `_dw`): the summed
device time of the events so named, over devices and steps."""
from . import _moe


def read(ctx):
    seconds = _moe.gmm_seconds_per_step(ctx)
    return None if seconds is None else seconds * 1e3
