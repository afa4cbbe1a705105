"""Reader of the expert-load counter a mixture-of-experts program keeps.

`models.decoder_lm` builds a persistable int32 `moe_expert_load`
[MoE layers, experts held]; every step adds, inside the compiled step,
the number of tokens the router sent to each expert this chip holds (the
`moe_router` op's Counts). No fetch and no host work a step: the counter
is read when somebody asks.

    load = observability.moe.expert_load(scope)        # numpy int64
    stats = observability.moe.load_stats(load, tokens)

`tokens` is the number of tokens routed since the counter was last zero
(steps x tokens a step). A program without the counter gives None.
"""
from __future__ import annotations

import numpy as np

EXPERT_LOAD_VAR = "moe_expert_load"

__all__ = ["EXPERT_LOAD_VAR", "expert_load", "load_stats"]


def expert_load(scope, name=EXPERT_LOAD_VAR):
    """The counter as a numpy int64 array [MoE layers, experts held], or
    None where the scope holds no such variable."""
    var = scope.find_var(name)
    if var is None or not var.is_initialized():
        return None
    value = var.get_value()
    return np.asarray(getattr(value, "array", value)).astype(np.int64)


def load_stats(load, tokens):
    """{"held_rows_per_token": rows routed to held experts per token and
    layer (top_k x experts held / experts when routing is uniform),
    "load_max_over_mean": the busiest held expert's rows over the mean
    held expert's, worst layer}; None for an empty counter."""
    load = np.asarray(load, np.float64)
    if load.size == 0 or tokens <= 0 or load.sum() <= 0:
        return None
    per_layer = load.sum(axis=1)
    mean = np.maximum(load.mean(axis=1), 1e-30)
    return {"held_rows_per_token": float(per_layer.mean() / tokens),
            "load_max_over_mean": float((load.max(axis=1) / mean).max())}
