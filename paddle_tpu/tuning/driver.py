"""Engine-facing autotune orchestration (docs/TUNING.md).

One entry point, :func:`autotune_for_run`, called by ``Engine.run``
at the first step of a program when ``FLAGS_autotune`` is on:

* cache HIT: the persisted winner is applied before the first trace —
  zero trials, the step pays only one JSON read;
* cache MISS: a scope-snapshotted search runs real engine steps under
  candidate configs (coordinate descent + successive halving,
  search.py), the winner is persisted atomically (cache.py), then
  applied.

Feedback-directed: the objective is the framework's own telemetry —
fetch-fenced wall milliseconds per step, the same number
``pt_step_total_seconds`` observes — measured on the live program +
feed, not a proxy model.

Safety invariants the tests pin down (tests/test_tuning.py):

* trials run against a SNAPSHOT of the scope (np copies — donation
  invalidates jax buffers) and the scope (params + RNG state, which
  lives in scope vars) is restored before every trial and after the
  search, so searching never perturbs the training trajectory;
* knob state is snapshot/restored around the whole search even when a
  trial raises (knobs.apply is all-or-nothing, knobs.applied restores
  in ``finally``);
* reentry is impossible: trials run through ``Engine.run`` which
  consults :func:`state.search_in_progress` before autotuning;
* with lossy knobs excluded (the default) the applied winner is
  value-preserving, so the tuned trajectory is bit-identical where the
  winner keeps kernels off the hot ops (docs/TUNING.md caveats).
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import cache, knobs, search, state

__all__ = ["autotune_for_run", "snapshot_scope", "restore_scope",
           "search_config"]


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _budgets() -> Sequence[int]:
    raw = os.environ.get("PT_TUNE_BUDGETS", "").strip()
    if raw:
        try:
            bs = [int(x) for x in raw.split(",") if x.strip()]
            if bs and all(b > 0 for b in bs):
                return bs
        except ValueError:
            pass
    return (2, 5)


def _variants_enabled() -> bool:
    return os.environ.get("PT_TUNE_VARIANTS", "").strip() in (
        "1", "true", "yes", "on")


def _objective_mode() -> str:
    """``wall`` (default) or ``attribution`` (PT_TUNE_OBJECTIVE): wall
    scores trials by fetch-fenced step ms alone; attribution adds
    bounded per-knob waste penalties from the PR 10/12/14 telemetry so
    credit lands on the knob that owns the waste (docs/TUNING.md)."""
    mode = os.environ.get("PT_TUNE_OBJECTIVE", "").strip().lower()
    return mode if mode in ("wall", "attribution") else "wall"


def _attr_signals(engine, c0: Dict[str, float], steps: int
                  ) -> Dict[str, float]:
    """Per-knob credit signals measured over one trial: engine-counter
    deltas (vs the pre-trial snapshot ``c0``) normalized per step. A
    knob with no live signal contributes nothing — the attribution
    objective then degrades to pure wall time."""
    c = engine.counters
    steps = max(1, int(steps))
    sig: Dict[str, float] = {}
    # sched_lanes <- pt_step_lane_idle_seconds: lanes idling inside the
    # scheduler's phase windows
    lane = float(c.get("lane_idle_ms", 0.0)) - \
        float(c0.get("lane_idle_ms", 0.0))
    if lane > 0:
        sig["lane_idle_ms"] = lane / steps
    # allreduce_bucket_mb <- comm-overlap fraction (only meaningful
    # when the trial actually moved collective bytes)
    if float(c.get("collective_bytes", 0.0)) > \
            float(c0.get("collective_bytes", 0.0)):
        sig["comm_overlap_frac"] = float(
            c.get("comm_overlap_frac", 0.0))
    # multi_step_k <- host-phase share: the fraction of substeps that
    # paid a host dispatch round-trip (1.0 at K=1, 1/K in slab mode)
    sub = float(c.get("multistep_substeps", 0.0)) - \
        float(c0.get("multistep_substeps", 0.0))
    disp = float(c.get("multistep_dispatches", 0.0)) - \
        float(c0.get("multistep_dispatches", 0.0))
    if sub > 0:
        sig["host_share"] = max(0.0, min(1.0, disp / sub))
    return sig


def _attr_score(wall_ms: float, sig: Dict[str, float]) -> float:
    """wall ms + bounded per-knob waste penalties (>= 0 each, capped
    at half the wall so no single signal can dominate the measured
    time). With every signal absent this IS the wall objective."""
    cap = wall_ms * 0.5
    s = wall_ms
    s += min(sig.get("lane_idle_ms", 0.0), cap)
    if "comm_overlap_frac" in sig:
        s += min((1.0 - sig["comm_overlap_frac"]) * wall_ms * 0.25,
                 cap)
    s += min(sig.get("host_share", 0.0) * wall_ms * 0.25, cap)
    return s


# ---------------------------------------------------------------------------
# scope snapshot / restore
# ---------------------------------------------------------------------------

def snapshot_scope(scope) -> Dict[str, np.ndarray]:
    """np copies of every array-valued scope var. Copies, not views:
    donated buffers are invalidated by the very steps the trials run."""
    snap = {}
    for name in scope.local_var_names():
        v = scope.find_var(name)
        if v is None:
            continue
        val = v.get_value()
        if val is None:
            continue
        try:
            snap[name] = np.array(val, copy=True)
        except Exception:
            continue  # non-array var (reader handle etc.) — not step state
    return snap


def restore_scope(scope, snap: Dict[str, np.ndarray]) -> None:
    for name, arr in snap.items():
        scope.var(name).set_value(np.array(arr, copy=True))


# ---------------------------------------------------------------------------
# the measured objective
# ---------------------------------------------------------------------------

def _step_ms(engine, program, scope, place, feed, fetch_names,
             steps: int) -> float:
    """Median fetch-fenced wall ms over ``steps`` timed steps (one
    untimed warmup first — it carries the trace+compile)."""
    fetches = list(fetch_names)

    def one():
        out = engine.run(program, scope, place, feed, fetches)
        if out:
            np.asarray(out[0])  # fence: wait for the device

    one()
    ts = []
    for _ in range(max(1, steps)):
        t0 = time.perf_counter()
        one()
        ts.append((time.perf_counter() - t0) * 1e3)
    return sorted(ts)[len(ts) // 2]


def search_config(engine, program, scope, place, feed, fetch_names,
                  *, seed: Optional[int] = None,
                  include_lossy: Optional[bool] = None,
                  on_trial=None):
    """Scope-snapshotted knob search on the live program.

    Returns (best_config, trials, start_config, deciding_budget,
    wall_record). The scope and all knob state are exactly as before
    the call, whatever happened inside. Under
    ``PT_TUNE_OBJECTIVE=attribution`` trial SCORES carry per-knob
    waste penalties while ``wall_record`` keeps the raw fetch-fenced
    wall ms per (config digest, budget).
    """
    from ..observability import metrics, tracing
    space = knobs.search_space(include_lossy)
    only = os.environ.get("PT_TUNE_KNOBS", "").strip()
    if only:
        # restrict the searched axes (comma-separated knob names):
        # cheap CI runs and targeted experiments search a subspace,
        # everything else stays at its ambient value
        names = {n.strip() for n in only.split(",") if n.strip()}
        space = [(n, c) for n, c in space if n in names]
    start = {name: knobs.value(name) for name, _ in space}
    if seed is None:
        seed = _env_int("PT_TUNE_SEED", 0)
    budgets = _budgets()
    rounds = _env_int("PT_TUNE_ROUNDS", 2)
    scope_snap = snapshot_scope(scope)
    knob_snap = knobs.snapshot()
    try:
        from ..observability import memory as _obs_memory
        _obs_memory.note_host_bytes(
            "tuning_snapshot",
            sum(int(a.nbytes) for a in scope_snap.values()))
    except Exception:
        _obs_memory = None
    trials_c = metrics.counter("pt_tuning_trials_total")
    trial_h = metrics.histogram("pt_tuning_trial_seconds")
    mode = _objective_mode()
    # pure fetch-fenced wall ms per (config digest, budget) — under
    # the attribution objective the SCORE carries penalties, so the
    # adoption fall-back in autotune_for_run needs the raw wall too
    wall_rec: Dict[Any, float] = {}

    def objective(config: Dict[str, Any], budget: int) -> float:
        t0 = time.time()
        tp0 = time.perf_counter()
        # identical starting state for every trial: params + RNG live
        # in the scope, so this restore makes trials comparable AND
        # keeps the search off the training trajectory
        restore_scope(scope, scope_snap)
        c0 = {k: float(engine.counters.get(k, 0.0))
              for k in ("lane_idle_ms", "collective_bytes",
                        "multistep_substeps", "multistep_dispatches")}
        with knobs.applied(config):
            ms = _step_ms(engine, program, scope, place, feed,
                          fetch_names, budget)
        wall_rec[(knobs.config_digest(config), budget)] = ms
        score = ms
        if mode == "attribution":
            score = _attr_score(
                ms, _attr_signals(engine, c0, budget + 1))
        dur_ms = (time.perf_counter() - tp0) * 1e3
        trials_c.inc()
        trial_h.observe(dur_ms / 1e3)
        tracing.record_span(
            "tuning.trial", t0, dur_ms, kind="tuning",
            ann={"budget": budget, "step_ms": round(ms, 3),
                 "score": round(score, 3), "objective": mode,
                 "config": knobs.config_digest(config)})
        return score

    state.set_search_in_progress(True)
    try:
        best, trials = search.coordinate_descent(
            space, objective, start, seed=seed, budgets=budgets,
            rounds=rounds, on_trial=on_trial)
    finally:
        state.set_search_in_progress(False)
        knobs.restore(knob_snap)
        restore_scope(scope, scope_snap)
        if _obs_memory is not None:
            _obs_memory.note_host_bytes("tuning_snapshot", 0)
    return best, trials, start, budgets[-1], wall_rec


# ---------------------------------------------------------------------------
# the engine hook
# ---------------------------------------------------------------------------

def _apply_entry(config: Dict[str, Any], source: str) -> None:
    knobs.apply(config)
    state.set_applied(knobs.config_digest(config), config, source)


def _register_variants(entry_variants: Optional[Dict[str, Any]]) -> None:
    if not entry_variants:
        return
    try:
        from . import variants
        variants.register_winner(entry_variants.get("winners") or {})
    except Exception:
        # a stale variant record must never break training startup
        pass


def autotune_for_run(engine, program, scope, place, feed,
                     fetch_names) -> Dict[str, Any]:
    """Cache-or-search for one program; applies the winner. Called by
    ``Engine.run`` once per program fingerprint when FLAGS_autotune is
    on (and never from inside a search trial)."""
    from ..observability import metrics, tracing
    # key from the AMBIENT knob baseline — computed before any apply,
    # so search runs and cache-hit runs agree on the key; the
    # fingerprint is the CONTENT hash, so tomorrow's identical model
    # hits today's entry (cache.content_fingerprint)
    key = cache.cache_key(cache.content_fingerprint(program))
    entry = cache.lookup(key)
    if entry is not None:
        _apply_entry(dict(entry["config"]), "cache")
        _register_variants(entry.get("kernel_variants"))
        metrics.counter("pt_tuning_cache_hits_total").inc()
        engine.counters["tuning_cache_hits"] += 1
        if entry.get("objective_ms") is not None:
            metrics.gauge("pt_tuning_best_ms").set(
                float(entry["objective_ms"]))
        return {"source": "cache", "config": dict(entry["config"]),
                "trials": 0, "objective_ms": entry.get("objective_ms"),
                "default_ms": entry.get("default_ms"),
                "delta_ms": entry.get("delta_ms"),
                "path": cache.path_for(key)}
    t0 = time.time()
    tp0 = time.perf_counter()
    best, trials, start_cfg, deciding, wall_rec = search_config(
        engine, program, scope, place, feed, fetch_names)
    mode = _objective_mode()
    if mode == "attribution" and best != start_cfg:
        # attribution hard floor: the penalties guide the SEARCH, the
        # wall decides ADOPTION — a winner whose raw wall regressed
        # against the start config is discarded, so the attribution
        # objective can never adopt a config worse than the wall-time
        # objective would have kept
        bw = wall_rec.get((knobs.config_digest(best), deciding))
        sw = wall_rec.get((knobs.config_digest(start_cfg), deciding))
        if bw is not None and sw is not None and bw > sw:
            best = dict(start_cfg)

    def _wall_at(cfg):
        # the config's wall ms at the DECIDING budget (every adoption
        # comparison happened there; lower budgets are screening)
        w = wall_rec.get((knobs.config_digest(cfg), deciding))
        if w is not None:
            return w
        for t in trials:
            if t.budget == deciding and t.config == cfg:
                return t.score
        return None

    best_ms = _wall_at(best)
    default_ms = _wall_at(start_cfg)
    # winner != start only on a STRICT measured improvement
    # (search.coordinate_descent), so this delta is <= 0 by
    # construction; winner == start reports exactly 0.0
    delta_ms = (best_ms - default_ms
                if best_ms is not None and default_ms is not None
                and best != start_cfg else 0.0)
    kernel_variants = None
    if _variants_enabled():
        try:
            from . import variants
            kernel_variants = variants.search_variants()
        except Exception:
            kernel_variants = None
    path = cache.store(key, best, objective_ms=best_ms,
                       trials=len(trials),
                       kernel_variants=kernel_variants,
                       extras={"default_ms": default_ms,
                               "delta_ms": delta_ms,
                               "objective": mode})
    _apply_entry(best, "search")
    _register_variants(kernel_variants)
    metrics.counter("pt_tuning_searches_total").inc()
    engine.counters["tuning_searches"] += 1
    engine.counters["tuning_trials"] += len(trials)
    if best_ms is not None:
        metrics.gauge("pt_tuning_best_ms").set(float(best_ms))
    tracing.record_span(
        "tuning.search", t0, (time.perf_counter() - tp0) * 1e3,
        kind="tuning",
        ann={"trials": len(trials),
             "config": knobs.config_digest(best)})
    return {"source": "search", "config": best, "trials": len(trials),
            "objective_ms": best_ms, "default_ms": default_ms,
            "delta_ms": delta_ms, "path": path}
