"""Reader of the kept-pairs counter a learned-sparse-attention program
keeps.

`models.decoder_lm` builds a persistable int32 `sparse_attn_kept`
[layers with an indexer]; every step OVERWRITES it, inside the compiled
step, with the number of (query, key) pairs each layer's selection kept
(the `sparse_attention_index` op's Kept): the last step's count, not a
running sum, which an int32 could not hold for long. No fetch and no host
work a step: the counter is read when somebody asks.

    kept = observability.sparse_attention.kept_pairs(scope)   # numpy int64
    share = observability.sparse_attention.kept_share(kept, batch, seq_len)

A program without the counter gives None.
"""
from __future__ import annotations

import numpy as np

KEPT_PAIRS_VAR = "sparse_attn_kept"

__all__ = ["KEPT_PAIRS_VAR", "kept_pairs", "causal_pairs", "kept_share"]


def kept_pairs(scope, name=KEPT_PAIRS_VAR):
    """The counter as a numpy int64 array [layers], or None where the
    scope holds no such variable."""
    var = scope.find_var(name)
    if var is None or not var.is_initialized():
        return None
    value = var.get_value()
    return np.asarray(getattr(value, "array", value)).astype(np.int64)


def causal_pairs(batch, seq_len):
    """(query, key) pairs with key <= query in `batch` sequences."""
    return batch * seq_len * (seq_len + 1) // 2


def kept_share(kept, batch, seq_len):
    """Mean over layers of pairs kept / causal pairs of the last step
    (sum_t min(t + 1, top_k) / (S (S + 1) / 2) a sequence: 0.4375 at
    S = 8,192 and top_k = 2,048); None for a counter that never
    counted."""
    kept = np.asarray(kept, np.float64)
    if kept.size == 0 or kept.sum() <= 0:
        return None
    return float(kept.mean() / causal_pairs(batch, seq_len))
