"""Host milliseconds a step leaves the device idle inside the program's
own Python: the seconds of device-idle gaps whose innermost host span is
one of the program's (`pt.step`, `pt.executor.*`, `pt.engine.*`), over
steps. The gaps under JAX's own spans (dispatch, transfers) and under
the harness's `bench.step` are not counted."""
from . import _named


def read(ctx):
    gaps = [v for k, v in ctx["trace"]["idle_gaps_s"].items()
            if k.startswith(_named.PROGRAM_SPANS)]
    if not gaps or not ctx["steps"]:
        return None
    return sum(gaps) / ctx["steps"] * 1e3
