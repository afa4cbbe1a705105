"""Seconds of set-up this process spent in the first call of each
executable: the sum of its `first_dispatch` set-up spans (jit lowering
plus XLA compile, or the load from the persistent cache)."""
from . import _named


def read(ctx):
    return _named.setup_span_seconds("first_dispatch")
