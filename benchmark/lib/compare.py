"""The comparison that decides `correct` for a training cell.

Both sides give, for the first three steps from the seed's weights on
the seed's first three batches: each step's loss, the norm of the first
gradient per leaf as the optimizer got it, and the norm of each leaf's
change after the three steps. Compared are

  loss_gap   the worst step's |loss - reference| / |reference|;
  grad_gap   the worst leaf's | |g| - |g_ref| | / max(|g_ref|, median
             leaf's |g_ref|) — the gap between the norms, not the norm of
             the difference, against the leaf's or the median leaf's
             reference norm, whichever is larger, since some gradients
             are all but zero;
  delta_gap  the same of |p_3 - p_0|, over the leaves whose reference
             gradient is at least a thousandth of the median leaf's:
             under Adam a leaf whose gradient is nought to rounding (a
             key projection's bias under softmax) moves by round-off
             alone. The rule is on the reference's gradient, not on a
             name.

  grad_dir_gap  the median leaf's |g - g_ref| / max(|g_ref|, median leaf's
             |g_ref|) over the seed's sample of 2,048 elements a leaf (the
             whole leaf where it is smaller). The three numbers above
             are gaps between NORMS, and a norm moves only with the
             square of a rounding error that has no preferred sign; on
             the chip they read within a factor of two for bfloat16 and
             for float8 (PERF.md). This one is of first order in the
             rounding, and it is what the float8 control fails. The
             median leaf, not the worst: the worst is an all-but-zero
             gradient (a key projection's bias), which is noise on both
             sides.

Each number has a limit of its own in benchmark/limits/<cell>.json, set
from readings on the chip (PERF.md gives them).
"""
from __future__ import annotations

import math
import statistics

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "delta_gap", "grad_dir_gap")
_QUIET_GRADIENT = 1e-3       # of the median leaf's reference gradient


def _worst_leaf(got, ref, leaves):
    med = statistics.median(ref[n] for n in leaves)
    worst, where = 0.0, None
    for n in leaves:
        gap = abs(got[n] - ref[n]) / max(ref[n], med, 1e-30)
        if not math.isfinite(gap):
            gap = math.inf
        if where is None or gap > worst:
            worst, where = gap, n
    return worst, where


def gaps(got, ref):
    """{number: value}, {number: where it was read} from two readings of
    the shape families' `run_reference` returns."""
    if set(got["grad_norms"]) != set(ref["grad_norms"]):
        raise ValueError("the two sides name different leaves: "
                         f"{sorted(set(got['grad_norms']) ^ set(ref['grad_norms']))[:6]}")
    values, where = {}, {}
    loss = [abs(a - b) / abs(b) if math.isfinite(a) else math.inf
            for a, b in zip(got["losses"], ref["losses"])]
    values["loss_gap"] = max(loss)
    where["loss_gap"] = f"step {loss.index(max(loss)) + 1}"
    leaves = sorted(ref["grad_norms"])
    values["grad_gap"], where["grad_gap"] = _worst_leaf(
        got["grad_norms"], ref["grad_norms"], leaves)
    med_g = statistics.median(ref["grad_norms"].values())
    moving = [n for n in leaves
              if ref["grad_norms"][n] >= _QUIET_GRADIENT * med_g]
    values["delta_gap"], where["delta_gap"] = _worst_leaf(
        got["delta_norms"], ref["delta_norms"], moving)
    if "grad_sample" in got and "grad_sample" in ref:
        norm = {n: float(np.linalg.norm(ref["grad_sample"][n]))
                for n in leaves}
        med = statistics.median(norm.values())
        per_leaf = {n: float(np.linalg.norm(
            np.asarray(got["grad_sample"][n], np.float64)
            - ref["grad_sample"][n])) / max(norm[n], med, 1e-30)
            for n in leaves}
        # one non-finite leaf makes the side's reading no number at all
        values["grad_dir_gap"] = statistics.median(per_leaf.values()) \
            if all(math.isfinite(v) for v in per_leaf.values()) else math.inf
        worst = max(per_leaf, key=per_leaf.get)
        where["grad_dir_gap"] = (f"median of {len(leaves)} leaves; worst "
                                 f"{worst} {per_leaf[worst]:.3g}")
    return values, where


def judge(values, limits):
    """({number: {"value", "limit"}}, correct): every number the limits
    file names is held to its limit; a number it does not name is not
    compared."""
    compared = {k: {"value": values[k], "limit": float(limits[k])}
                for k in NUMBERS if k in limits and k in values}
    if not compared:
        raise ValueError("the limits file holds no limit")
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in compared.values())
    return compared, ok
