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

The prefix a layer took in a step also says which combine it ran (the
rule is static: `kernels.grouped_matmul.combine_by_rows`), so readings a
step apart give how often the row-driven combine engages:

    steps = np.diff(np.stack(readings), axis=0)        # [steps, layers, 2]
    stats = observability.moe.combine_stats(steps, ladder, n_choices)

`ladder` is `kernels.grouped_matmul.prefix_rows(...)`, `n_choices`
tokens x top_k.
"""
from __future__ import annotations

import numpy as np

EXPERT_LOAD_VAR = "moe_expert_load"
ROWS_WORKED_VAR = "moe_rows_worked"

__all__ = ["EXPERT_LOAD_VAR", "ROWS_WORKED_VAR", "expert_load",
           "load_stats", "rows_worked", "rows_worked_stats",
           "combine_stats"]


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


def combine_stats(worked, ladder, n_choices):
    """Which combine the expert layers ran, from the counter's increase
    over single steps: `worked` int [steps, layers, 2] (or [layers, 2],
    one step), each increase's first column the prefix that layer took
    in that step, a rung of `ladder`. Where the layer's kernels run, a
    rung under `COMBINE_MAX_SHARE` of the `n_choices` choices combines
    over its rows (kernel `moe_combine`) and a longer one over every
    choice. {"by_rows_share": the share of layer-steps that combined
    over rows, "by_rows_share_per_layer": the same a layer,
    "rows_over_choices": rows the combines went over (the rung's, or
    `n_choices` where every choice was gathered) over layer-steps x
    n_choices}; None for no step. An increase that is no rung (readings
    more than a step apart) is a ValueError."""
    from ..kernels.grouped_matmul import combine_by_rows
    took = np.asarray(worked, np.int64)[..., 0]
    took = took.reshape(-1, took.shape[-1])
    if took.size == 0 or n_choices <= 0:
        return None
    if not np.isin(took, ladder).all():
        raise ValueError(
            f"rows worked {sorted(set(took.ravel()) - set(ladder))} are no "
            f"rung of {list(ladder)}: readings one step apart?")
    by_rows = np.isin(took, [rows for rows in ladder
                             if combine_by_rows(rows, n_choices)])
    went_over = np.where(by_rows, took, n_choices)
    return {"by_rows_share": float(by_rows.mean()),
            "by_rows_share_per_layer": by_rows.mean(axis=0).tolist(),
            "rows_over_choices": float(went_over.mean() / n_choices)}
