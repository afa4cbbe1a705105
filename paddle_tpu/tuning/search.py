"""Deterministic seeded search driver: coordinate descent with
successive-halving trial budgets (docs/TUNING.md).

The knob space is small and axis-structured (a handful of knobs, 2-4
candidates each), so the driver is coordinate descent — optimize one
knob at a time against the measured objective, holding the rest at the
incumbent — with successive halving inside each coordinate: every
candidate gets a cheap low-budget measurement first, the better half
gets re-measured at double budget, until one survives. That spends the
expensive high-budget steps only on configurations that already looked
good, the classic successive-halving argument.

Determinism contract (tests/test_tuning.py): same space + objective +
seed => the identical trial sequence and winner. Coordinate order is a
seeded shuffle, survivors sort by (score, candidate index) so ties
break by catalog order, and repeated (config, budget) evaluations are
memoized — a deterministic objective is measured exactly once per
budget.

The objective is "lower is better", typically measured step
milliseconds (driver.py adds per-knob penalties from the engine's
counters under PT_TUNE_OBJECTIVE=attribution).
"""
from __future__ import annotations

import random
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["Trial", "coordinate_descent"]


class Trial:
    """One objective evaluation."""

    __slots__ = ("index", "knob", "value", "config", "budget", "score")

    def __init__(self, index: int, knob: Optional[str], value,
                 config: Dict[str, Any], budget: int, score: float):
        self.index = index
        self.knob = knob          # None for the incumbent baseline
        self.value = value
        self.config = dict(config)
        self.budget = budget
        self.score = score

    def as_dict(self) -> Dict[str, Any]:
        return {"index": self.index, "knob": self.knob,
                "value": self.value, "config": self.config,
                "budget": self.budget, "score": self.score}


def _cfg_key(config: Dict[str, Any], budget: int) -> Tuple:
    return (tuple(sorted((k, repr(v)) for k, v in config.items())),
            budget)


def coordinate_descent(
        space: Sequence[Tuple[str, Sequence]],
        objective: Callable[[Dict[str, Any], int], float],
        start: Dict[str, Any],
        *,
        seed: int = 0,
        budgets: Sequence[int] = (2, 6),
        rounds: int = 2,
        on_trial: Optional[Callable[[Trial], None]] = None,
) -> Tuple[Dict[str, Any], List[Trial]]:
    """Minimize ``objective(config, budget)`` over ``space``.

    space: [(knob name, candidate values)]; start: full initial config
    (every knob in space must be present — usually the safe defaults).
    budgets: successive-halving measurement budgets, ascending; the
    LAST budget is the deciding one. Returns (best config, trials).
    """
    budgets = [int(b) for b in budgets]
    assert budgets and all(b > 0 for b in budgets), budgets
    rng = random.Random(seed)
    incumbent = dict(start)
    memo: Dict[Tuple, float] = {}
    trials: List[Trial] = []

    def measure(knob, val, config, budget) -> float:
        k = _cfg_key(config, budget)
        if k in memo:
            return memo[k]
        score = float(objective(dict(config), budget))
        memo[k] = score
        t = Trial(len(trials), knob, val, config, budget, score)
        trials.append(t)
        if on_trial is not None:
            on_trial(t)
        return score

    for _ in range(max(1, rounds)):
        order = list(range(len(space)))
        rng.shuffle(order)
        changed = False
        for si in order:
            name, cands = space[si]
            cands = list(cands)
            if len(cands) < 2:
                continue
            # successive halving over this coordinate's candidates;
            # every survivor reaches the deciding (last) budget, so
            # the final comparison never mixes budgets
            alive = list(range(len(cands)))
            scores: Dict[int, float] = {}
            for bi, budget in enumerate(budgets):
                for ci in alive:
                    cfg = dict(incumbent)
                    cfg[name] = cands[ci]
                    scores[ci] = measure(name, cands[ci], cfg, budget)
                if bi < len(budgets) - 1:
                    alive.sort(key=lambda ci: (scores[ci], ci))
                    alive = alive[:max(1, (len(alive) + 1) // 2)]
            alive.sort(key=lambda ci: (scores[ci], ci))
            best_ci = alive[0]
            # adopt only a STRICT improvement over the incumbent at the
            # deciding budget — ties keep the current (safer) value
            inc_score = measure(None, incumbent[name], dict(incumbent),
                                budgets[-1])
            if cands[best_ci] != incumbent[name] \
                    and scores[best_ci] < inc_score:
                incumbent[name] = cands[best_ci]
                changed = True
        if not changed:
            break
    return incumbent, trials
