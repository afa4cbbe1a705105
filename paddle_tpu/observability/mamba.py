"""Reader of the scanned-tokens counter a program with Mamba-2 mixers
keeps.

`models.decoder_lm` builds a persistable int32 `mamba_ssd_tokens` [mixer
layers]; every step OVERWRITES it, inside the compiled step, with the
tokens each mixer's state-space scan went over (the `mamba2_ssd` op's
Tokens): the last step's count, not a running sum. No fetch and no host
work a step: the counter is read when somebody asks.

    scanned = observability.mamba.scanned_tokens(scope)    # numpy int64

A program without the counter gives None.
"""
from __future__ import annotations

from .moe import _counter

SSD_TOKENS_VAR = "mamba_ssd_tokens"

__all__ = ["SSD_TOKENS_VAR", "scanned_tokens"]


def scanned_tokens(scope, name=SSD_TOKENS_VAR):
    """The counter as a numpy int64 array [mixer layers], or None where
    the scope holds no such variable."""
    return _counter(scope, name)
