"""Readers of the two counters a mixture-of-experts program keeps.

`models.decoder_lm` builds two persistable int32 variables that every
step adds to inside the compiled step; no fetch and no host work a step,
a counter is read when somebody asks:

`moe_expert_load` [MoE layers, experts held]: the number of tokens the
router sent to each expert this chip holds (the `moe_router` op's
Counts).

    load = observability.moe.expert_load(scope)        # numpy int64
    stats = observability.moe.load_stats(load, tokens)

`tokens` is the number of tokens routed since the counter was last zero
(steps x tokens a step).

`moe_rows_worked` [MoE layers, 2]: the rows of its worst-case row buffer
each expert layer worked over (the prefix `moe_experts` took) and the
rows of it in use (the `moe_experts` op's RowsWorked). It wraps after
2**31 rows a layer: 32,000 steps of 8,192 tokens, top-8, 16 of 128
experts held, were every step the worst case, 210,000 at the first
prefix; read it and set it to zero before that.

    worked = observability.moe.rows_worked(scope)      # numpy int64
    stats = observability.moe.rows_worked_stats(worked, steps, worst)

`worst` is the buffer's worst-case length
(`kernels.grouped_matmul.buffer_rows`). A program without a counter
gives None.
"""
from __future__ import annotations

import numpy as np

EXPERT_LOAD_VAR = "moe_expert_load"
ROWS_WORKED_VAR = "moe_rows_worked"

__all__ = ["EXPERT_LOAD_VAR", "ROWS_WORKED_VAR", "expert_load",
           "load_stats", "rows_worked", "rows_worked_stats"]


def _counter(scope, name):
    var = scope.find_var(name)
    if var is None or not var.is_initialized():
        return None
    value = var.get_value()
    return np.asarray(getattr(value, "array", value)).astype(np.int64)


def expert_load(scope, name=EXPERT_LOAD_VAR):
    """The counter as a numpy int64 array [MoE layers, experts held], or
    None where the scope holds no such variable."""
    return _counter(scope, name)


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


def rows_worked(scope, name=ROWS_WORKED_VAR):
    """The counter as a numpy int64 array [MoE layers, 2] (rows worked
    over, rows in use), or None where the scope holds no such
    variable."""
    return _counter(scope, name)


def rows_worked_stats(worked, steps, worst_rows):
    """{"worked_share_of_worst": per layer, rows worked over a step over
    the worst-case buffer's `worst_rows` (1.0: every step took the worst
    case), "worked_over_in_use": per layer, rows worked over the rows in
    use (1.0: nothing but tiles in use)}; None for an empty counter."""
    worked = np.asarray(worked, np.float64)
    if worked.size == 0 or steps <= 0 or worst_rows <= 0 \
            or (worked[:, 1] <= 0).any():
        return None
    return {"worked_share_of_worst":
            (worked[:, 0] / (steps * worst_rows)).tolist(),
            "worked_over_in_use": (worked[:, 0] / worked[:, 1]).tolist()}
