"""What the readers of the state-space scan share. The program names its
two scan kernels `mamba2_ssd_fwd` and `mamba2_ssd_bwd` (the family
classifies both as `mamba2_ssd`) and keeps a persistable
`mamba_ssd_tokens` counter that every step overwrites with the tokens
each mixer scanned; the family reads it after the proof steps. A program
without them gives these readers nothing to read: they return None,
never 0."""
from . import _dsa


def kernel_seconds_per_step(ctx):
    """Summed device time of the scan kernels' events over devices and
    steps, in seconds; None where the trace has none."""
    return _dsa.kernel_seconds_per_step(ctx, "mamba2_ssd")


def scanned_tokens(ctx):
    """The counter [mixer layers] as the family read it, or None where
    the family reads none or it never counted."""
    read = getattr(ctx["family"], "scanned_tokens", None)
    scanned = read(ctx["sizes"]) if read is not None else None
    if scanned is None or not scanned.size or scanned.sum() <= 0:
        return None
    return scanned
