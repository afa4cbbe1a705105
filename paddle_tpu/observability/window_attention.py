"""Reader of the admitted-pairs counter a program with sliding-window
attention layers keeps.

`models.decoder_lm` builds a persistable int32 `window_attn_pairs`
[window layers]; every step OVERWRITES it, inside the compiled step, with
the (query, key) pairs each window layer's band admitted (the
`fused_attention` op's WindowPairs: B x the pairs of one head, row r
keeping min(r + 1, window) keys): the last step's count, not a running
sum. No fetch and no host work a step: the counter is read when somebody
asks.

    pairs = observability.window_attention.admitted_pairs(scope)  # int64

A program without the counter gives None.
"""
from __future__ import annotations

from .moe import _counter

WINDOW_PAIRS_VAR = "window_attn_pairs"

__all__ = ["WINDOW_PAIRS_VAR", "admitted_pairs"]


def admitted_pairs(scope, name=WINDOW_PAIRS_VAR):
    """The counter as a numpy int64 array [window layers], or None where
    the scope holds no such variable."""
    return _counter(scope, name)
