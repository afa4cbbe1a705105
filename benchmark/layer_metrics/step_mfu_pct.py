"""The whole step's share of the chips' peak: FLOPs the forward and
backward passes need (the family's function of the shapes, no recomputed
work) times steps, over the traced window, over chips times peak."""


def read(ctx):
    if ctx["peaks"] is None or not ctx["steps"]:
        return None
    flops = ctx["family"].flops_per_step(ctx["sizes"], ctx["traffic"])["step"]
    return 100.0 * flops * ctx["steps"] / ctx["window_s"] / (
        ctx["chips"] * ctx["peaks"]["flops_per_s"])
