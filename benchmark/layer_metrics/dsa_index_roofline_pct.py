"""The index-score kernel's share of the MXU roofline: 2 * index heads *
index head width a causal (query, key) pair, forward only (the indexer
takes no gradient), over the chip's peak, over the device time of the
`sparse_index_scores` events."""
from . import _dsa


def read(ctx):
    seconds = _dsa.kernel_seconds_per_step(ctx, "sparse_index_scores")
    if not seconds or ctx["peaks"] is None:
        return None
    flops = ctx["family"].flops_per_step(
        ctx["sizes"], ctx["traffic"])["index_step"]
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["flops_per_s"]) \
        / seconds
