"""Seconds of first dispatches inside JAX's backend-compile interval:
XLA compiling (`first_dispatch.compile`, a miss of the persistent cache
or a request that did not use it) or the cache handing the executable
back (`first_dispatch.cache_load`, a hit). `setup_cache_hit_pct` says
which a run paid."""
from . import _setup


def read(ctx):
    return _setup.span_seconds("first_dispatch.compile",
                               "first_dispatch.cache_load")
