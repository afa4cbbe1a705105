"""Host milliseconds a step costs outside device work: the traced window
less the union of device-busy intervals, per step."""


def read(ctx):
    if not ctx["steps"]:
        return None
    return (ctx["window_s"] - ctx["trace"]["busy_s"]) / ctx["steps"] * 1e3
