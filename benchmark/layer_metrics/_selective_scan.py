"""What the readers of the selective scan and of differential attention
share. The program names its two scan kernels `selective_scan_fwd` and
`selective_scan_bwd` (the family classifies both as `selective_scan`)
and keeps persistable `selective_scan_tokens` and `window_attn_pairs`
counters that every step overwrites; the family reads them after the
proof steps. A program without them gives these readers
nothing to read: they return None, never 0."""
from . import _dsa


def kernel_seconds_per_step(ctx):
    """Summed device time of the scan kernels' events over devices and
    steps, in seconds; None where the trace has none."""
    return _dsa.kernel_seconds_per_step(ctx, "selective_scan")


def _counter(ctx, reader):
    read = getattr(ctx["family"], reader, None)
    value = read(ctx["sizes"]) if read is not None else None
    if value is None or not value.size or value.sum() <= 0:
        return None
    return value


def scanned_tokens(ctx):
    """The `selective_scan_tokens` counter [Mamba layers] as the family
    read it, or None where the family reads none or it never counted."""
    return _counter(ctx, "scanned_tokens")


def window_pairs(ctx):
    """The `window_attn_pairs` counter [window layers] as the family read
    it, or None."""
    return _counter(ctx, "window_pairs")
