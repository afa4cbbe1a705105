"""Seconds of first dispatches JAX spent tracing the step function to
a jaxpr (the ops' lowerings walked again): the sum of the process's
`first_dispatch.jit_trace` set-up spans."""
from . import _setup


def read(ctx):
    return _setup.span_seconds("first_dispatch.jit_trace")
