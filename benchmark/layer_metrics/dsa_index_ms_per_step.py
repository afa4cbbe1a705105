"""Device milliseconds a step spends choosing the keys of a learned
sparse attention: the `sparse_index_scores` events plus the
`sparse_index_select` events (the program takes both kernels or
neither: one routing decision an op)."""
from . import _dsa


def read(ctx):
    scores = _dsa.kernel_seconds_per_step(ctx, "sparse_index_scores")
    select = _dsa.kernel_seconds_per_step(ctx, "sparse_index_select")
    if scores is None or select is None:
        return None
    return (scores + select) * 1e3
