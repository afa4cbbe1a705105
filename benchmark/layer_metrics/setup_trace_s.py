"""Seconds of set-up this process spent tracing programs: the sum of its
`trace_step` set-up spans (one per program traced: the abstract walk
over every op's lowering and building the jitted callable)."""
from . import _named


def read(ctx):
    return _named.setup_span_seconds("trace_step")
