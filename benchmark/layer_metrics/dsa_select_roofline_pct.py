"""The selection kernel's share of the HBM roofline: the float32 scores
read once and the int8 mask written once (5 bytes a (query, key) pair of
the whole [S, S] square) over the chip's bandwidth, over the device time
of the `sparse_index_select` events."""
from . import _dsa


def read(ctx):
    seconds = _dsa.kernel_seconds_per_step(ctx, "sparse_index_select")
    if not seconds or ctx["peaks"] is None:
        return None
    moved = ctx["family"].index_select_bytes(ctx["sizes"], ctx["traffic"])
    return 100.0 * moved / (ctx["chips"] * ctx["peaks"]["bytes_per_s"]) \
        / seconds
