"""Imbalance over the held experts: the busiest expert's rows over the
mean held expert's, in the worst MoE layer, from the program's
`moe_expert_load` counter after the proof steps. 1.0 is even."""
from . import _moe


def read(ctx):
    got = _moe.expert_load(ctx)
    if got is None:
        return None
    load, _ = got
    mean = load.mean(axis=1)
    return float((load.max(axis=1) / mean.clip(1e-30)).max())
