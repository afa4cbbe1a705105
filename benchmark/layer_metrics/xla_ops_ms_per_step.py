"""Device milliseconds a step spends in XLA-lowered operations: every
"XLA Ops" event that is not a Pallas custom call."""


def read(ctx):
    if not ctx["steps"]:
        return None
    seconds = sum(v for k, v in ctx["trace"]["by_category_s"].items()
                  if k.startswith("xla:"))
    return seconds / ctx["steps"] * 1e3
