"""Reader of the convolved-tokens counter a program with gated short
convolution layers keeps.

`models.decoder_lm` builds a persistable int32 `short_conv_tokens` [conv
layers]; every step OVERWRITES it, inside the compiled step, with the
tokens each layer's `gated_short_conv` op went over (the op's Tokens):
the last step's count, not a running sum. No fetch and no host work a
step: the counter is read when somebody asks.

    tokens = observability.short_conv.convolved_tokens(scope)  # int64

A program without the counter gives None.
"""
from __future__ import annotations

from .moe import _counter

SHORT_CONV_TOKENS_VAR = "short_conv_tokens"

__all__ = ["SHORT_CONV_TOKENS_VAR", "convolved_tokens"]


def convolved_tokens(scope, name=SHORT_CONV_TOKENS_VAR):
    """The counter as a numpy int64 array [conv layers], or None where
    the scope holds no such variable."""
    return _counter(scope, name)
