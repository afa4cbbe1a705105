"""Share of the causal (query, key) pairs the selection kept, mean over
layers, from the program's `sparse_attn_kept` counter as the last proof
step left it: sum_t min(t + 1, top_k) over S (S + 1) / 2 a sequence, 43.75
at S = 8,192 and top_k = 2,048 (a little more where scores tie at a
threshold)."""
from . import _dsa


def read(ctx):
    kept = _dsa.kept_pairs(ctx)
    if kept is None:
        return None
    return 100.0 * float(kept.mean()) / ctx["family"].causal_pairs(
        ctx["traffic"])
