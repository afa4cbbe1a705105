"""Reader of the scanned-tokens counter a program with Mamba-1 mixers
keeps.

`models.decoder_lm` builds a persistable int32 `selective_scan_tokens`
[Mamba layers]; every step OVERWRITES it, inside the compiled step, with
the tokens each mixer's selective scan went over (the `selective_scan`
op's Tokens): the last step's count, not a running sum. No fetch and no
host work a step: the counter is read when somebody asks.

    scanned = observability.selective_scan.scanned_tokens(scope)  # int64

A program without the counter gives None.
"""
from __future__ import annotations

from .moe import _counter

SELECTIVE_SCAN_TOKENS_VAR = "selective_scan_tokens"

__all__ = ["SELECTIVE_SCAN_TOKENS_VAR", "scanned_tokens"]


def scanned_tokens(scope, name=SELECTIVE_SCAN_TOKENS_VAR):
    """The counter as a numpy int64 array [Mamba layers], or None where
    the scope holds no such variable."""
    return _counter(scope, name)
