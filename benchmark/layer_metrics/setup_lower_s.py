"""Seconds of first dispatches JAX spent lowering the jaxpr to an MLIR
module: the sum of the process's `first_dispatch.lower` set-up
spans."""
from . import _setup


def read(ctx):
    return _setup.span_seconds("first_dispatch.lower")
