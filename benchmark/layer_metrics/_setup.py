"""What the readers of a job's set-up share. The program keeps a set-up
span for every phase it pays before its first steady step
(`paddle_tpu.observability.tracing.setup_spans()`: `first_dispatch` with
the children `first_dispatch.jit_trace` / `.lower` / `.compile` or
`.cache_load` that `jax.monitoring` timed inside it) and two process
counters (`build_totals()`: build-time shape inference by op type;
`compile_totals()`: what JAX compiled inside first dispatches and
outside them). A program without a span or counter (the commits before
them) gives its reader nothing to read: None, never 0."""
from . import _named

FIRST_DISPATCH = "first_dispatch"


def _tracing():
    try:
        from paddle_tpu.observability import tracing
    except ImportError:
        return None
    return tracing


def span_seconds(*names):
    """Summed seconds of the process's set-up spans with one of
    `names`; None where it holds none of them."""
    found = [s for s in map(_named.setup_span_seconds, names)
             if s is not None]
    return sum(found) if found else None


def first_execute_seconds():
    """What is left of the `first_dispatch` spans after their children:
    the first execution (and the call's own argument handling). None on
    a program whose first dispatches have no children."""
    tracing = _tracing()
    spans = getattr(tracing, "setup_spans", None)
    if spans is None:
        return None
    spans = spans()
    parents = {s["span"]: s["dur_ms"] for s in spans
               if s.get("name") == FIRST_DISPATCH}
    children = [s for s in spans if s.get("parent") in parents
                and s.get("name", "").startswith(FIRST_DISPATCH + ".")]
    if not children:
        return None
    return (sum(parents.values())
            - sum(s["dur_ms"] for s in children)) / 1e3


def infer_shapes_seconds():
    """Seconds of build-time shape inference over all op types; None
    where the program keeps no such counter or appended no op."""
    totals = getattr(_tracing(), "build_totals", None)
    if totals is None:
        return None
    totals = totals()
    return totals["seconds"] if totals["calls"] else None


def cache_hit_pct():
    """Hits over hits and misses of the persistent compile cache inside
    first dispatches, in %; None where the program keeps no such
    counter or no request of a first dispatch used the cache."""
    totals = getattr(_tracing(), "compile_totals", None)
    if totals is None:
        return None
    inside = totals()[FIRST_DISPATCH]
    asked = inside["cache_hits"] + inside["cache_misses"]
    return 100.0 * inside["cache_hits"] / asked if asked else None
