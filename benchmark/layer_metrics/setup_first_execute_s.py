"""Seconds of first dispatches left after JAX's trace, lowering and
compile or cache load: the first execution of each executable (its
program loaded onto the device, its arguments handled, the call
enqueued). `first_dispatch` spans less their children."""
from . import _setup


def read(ctx):
    return _setup.first_execute_seconds()
