"""Rows routed to the experts this chip holds, per token and MoE layer,
from the program's `moe_expert_load` counter after the proof steps:
top_k * experts held / experts (0.75 = 6 * 16 / 128) when the router
spreads tokens evenly."""
from . import _moe


def read(ctx):
    got = _moe.expert_load(ctx)
    if got is None:
        return None
    load, tokens = got
    return float(load.sum(axis=1).mean() / tokens)
