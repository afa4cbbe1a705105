"""What the readers of the gated short convolution share. The program
names its two kernels `gated_short_conv_fwd` and `gated_short_conv_bwd`
(the family classifies both as `gated_short_conv`) and keeps a
persistable `short_conv_tokens` counter that every step overwrites with
the tokens each conv layer's operator went over; the family reads it
after the proof steps. A program without them gives these readers
nothing to read: they return None, never 0."""
from . import _dsa


def kernel_seconds_per_step(ctx):
    """Summed device time of the two kernels' events over devices and
    steps, in seconds; None where the trace has none."""
    return _dsa.kernel_seconds_per_step(ctx, "gated_short_conv")


def convolved_tokens(ctx):
    """The counter [conv layers] as the family read it, or None where
    the family reads none or it never counted."""
    read = getattr(ctx["family"], "convolved_tokens", None)
    tokens = read(ctx["sizes"]) if read is not None else None
    if tokens is None or not tokens.size or tokens.sum() <= 0:
        return None
    return tokens
