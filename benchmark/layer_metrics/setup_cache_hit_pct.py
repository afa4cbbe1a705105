"""Share of the first dispatches' compile requests that the persistent
compile cache answered: hits over hits and misses, as `jax.monitoring`
counted them inside `first_dispatch` spans (the program's
`tracing.compile_totals()`)."""
from . import _setup


def read(ctx):
    return _setup.cache_hit_pct()
