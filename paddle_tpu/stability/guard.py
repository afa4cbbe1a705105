"""On-device anomaly guard fused into the traced step.

``FLAGS_check_nan_inf`` answers "which op produced the NaN" by keeping
one finite-flag per checked op output — a debugging tool whose verdict
is a host-visible flag VECTOR. This guard answers the production
question — "is this step's update safe to apply" — with ONE int32
scalar computed inside the step itself:

* bit ``NONFINITE``: any loss fetch or parameter gradient holds a
  NaN/Inf (overflow shows up as Inf);
* bit ``SPIKE``: the (unscaled) gradient global norm exceeds
  ``PT_GUARD_SPIKE_FACTOR`` x its EMA (``PT_GUARD_EMA_BETA``).

The same trace GATES every persistable update on the verdict —
``where(nonfinite, old, where(spike, damped_or_old, new))`` — so an
anomalous step leaves params/optimizer state bit-identical to the
pre-step values and the host can decide recovery lazily. On clean
steps the gate selects ``new`` elementwise, which is bit-exact: guard
on/off parity holds (tests/test_stability.py).

Host side, :class:`StabilityGuard` reads the verdict (one scalar
fetch), applies the per-class policy (``PT_STABILITY_POLICY``:
skip|clip|rescale|rollback|abort), escalates repeated anomalies,
restores the ghost ring on rollback (ghost.py) and dumps a
deterministic repro bundle (replay.py). See docs/STABILITY.md.
"""
from __future__ import annotations

import os
import time
import warnings
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.flags import FLAGS, set_flags
from .ghost import GhostRing

# scope/state variable names (same @...@ convention as @RNG_STATE@)
GUARD_EMA_VAR = "@GUARD_EMA@"            # f32 EMA of grad global norm
GUARD_NORM_VAR = "@GUARD_NORM@"          # f32 this step's grad norm
GUARD_VERDICT_VAR = "@GUARD_VERDICT@"    # int32 anomaly bitmask
GUARD_PRESCALE_VAR = "@GUARD_PRESCALE@"  # f32 loss scale BEFORE update
LOSS_SCALE_VAR = "@LOSS_SCALE@"          # f32[1] dynamic loss scale
LOSS_SCALE_GOOD_VAR = "@LOSS_SCALE_GOOD@"  # i32 consecutive good steps

NONFINITE = 1
SPIKE = 2

# "integrity" verdicts come from the integrity sentinel
# (stability/integrity.py), not the in-trace guard math, but share the
# policy vocabulary so PT_STABILITY_POLICY configures all three
CLASSES = ("nonfinite", "spike", "integrity")
POLICIES = ("skip", "clip", "rescale", "rollback", "abort")

_MIN_SCALE = 2.0 ** -14
_MAX_SCALE = 2.0 ** 31

# state vars the gate must never revert: the guard's own outputs and
# the loss scale (which must shrink ON the anomalous step), plus RNG
# and the integrity sentinel's shadow fingerprints (gating those would
# make the sentinel compare a reverted shadow against live params)
_NO_GATE = frozenset({
    GUARD_EMA_VAR, GUARD_NORM_VAR, GUARD_VERDICT_VAR,
    GUARD_PRESCALE_VAR, LOSS_SCALE_VAR, LOSS_SCALE_GOOD_VAR,
    "@RNG_STATE@",
    "@INTEGRITY_STEP@", "@INTEGRITY_SUM@", "@INTEGRITY_CK@",
    "@INTEGRITY_BAD@", "@INTEGRITY_DRIFT@", "@INTEGRITY_AGREE@"})


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def policy_map(spec: Optional[str] = None) -> Dict[str, str]:
    """Parse ``PT_STABILITY_POLICY``: one token for all classes
    (``rollback``) or per-class pairs (``nonfinite=rollback,
    spike=clip``). Default: nonfinite=skip, spike=clip,
    integrity=rollback (corrupt params can't be "skipped" — the
    corruption persists in the scope — so the default rewinds to a
    clean ghost)."""
    if spec is None:
        spec = os.environ.get("PT_STABILITY_POLICY", "")
    out = {"nonfinite": "skip", "spike": "clip",
           "integrity": "rollback"}
    spec = (spec or "").strip()
    if not spec:
        return out
    if "=" not in spec:
        if spec not in POLICIES:
            raise ValueError(
                f"PT_STABILITY_POLICY={spec!r}: policy must be one of "
                f"{POLICIES}")
        return {c: spec for c in CLASSES}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        cls, _, pol = part.partition("=")
        cls, pol = cls.strip(), pol.strip()
        if cls not in CLASSES or pol not in POLICIES:
            raise ValueError(
                f"PT_STABILITY_POLICY entry {part!r}: expected "
                f"<class>=<policy> with class in {CLASSES} and policy "
                f"in {POLICIES}")
        out[cls] = pol
    return out


class GuardPlan:
    """Static per-program guard configuration, baked into the trace
    (policy is part of the engine cache key: a changed policy means a
    changed gate)."""

    __slots__ = ("grad_names", "spike_factor", "ema_beta", "scale_cfg",
                 "policies", "_epilogue_jit")

    def __init__(self, grad_names: Sequence[str],
                 scale_cfg: Optional[dict] = None,
                 spike_factor: Optional[float] = None,
                 ema_beta: Optional[float] = None,
                 policies: Optional[Dict[str, str]] = None):
        self.grad_names = list(grad_names)
        self.scale_cfg = dict(scale_cfg) if scale_cfg else None
        self.spike_factor = (spike_factor if spike_factor is not None
                             else _env_float("PT_GUARD_SPIKE_FACTOR",
                                             10.0))
        self.ema_beta = (ema_beta if ema_beta is not None
                         else _env_float("PT_GUARD_EMA_BETA", 0.9))
        self.policies = dict(policies) if policies else policy_map()
        self._epilogue_jit = None

    @property
    def spike_damps(self) -> bool:
        """True when the spike gate dampens the update toward the EMA
        threshold instead of dropping it (clip/rescale policies)."""
        return self.policies.get("spike") in ("clip", "rescale")

    def input_state_names(self) -> List[str]:
        names = [GUARD_EMA_VAR]
        if self.scale_cfg:
            names += [LOSS_SCALE_VAR, LOSS_SCALE_GOOD_VAR]
        return names

    def output_names(self) -> List[str]:
        names = [GUARD_VERDICT_VAR, GUARD_NORM_VAR, GUARD_EMA_VAR]
        if self.scale_cfg:
            names += [LOSS_SCALE_VAR, LOSS_SCALE_GOOD_VAR,
                      GUARD_PRESCALE_VAR]
        return names

    def state_var_names(self) -> List[str]:
        return sorted(set(self.input_state_names())
                      | set(self.output_names()))

    # -- epilogue entry point (scheduler / islands paths) ---------------
    def run_epilogue(self, env: dict, orig: dict,
                     fetch_names: Sequence[str],
                     gate_names: Sequence[str]) -> None:
        """Guard a step that did NOT run through one whole-block trace:
        compute verdict + gated updates in one cached jitted call over
        the step's final arrays and write the results into ``env`` in
        place. ``orig`` holds the pre-step values of ``gate_names``.
        Tolerates missing gradients (an island may have consumed them
        internally) — the spike detector simply sees no grads."""
        loss_vals = {n: env[n] for n in fetch_names
                     if _is_float_array(env.get(n))}
        grad_vals = {n: env[n] for n in self.grad_names
                     if _is_float_array(env.get(n))}
        state = {"ema": _state_scalar(env, orig, GUARD_EMA_VAR, 0.0)}
        if self.scale_cfg:
            state["scale"] = _state_scalar(
                env, orig, LOSS_SCALE_VAR,
                float(self.scale_cfg.get("init", 1.0)))
            state["good"] = _state_scalar(env, orig,
                                          LOSS_SCALE_GOOD_VAR, 0)
        new_vals, old_vals = {}, {}
        for n in gate_names:
            if n in _NO_GATE:
                continue
            new, old = env.get(n), orig.get(n)
            if not _gateable(old, new):
                continue
            new_vals[n] = new
            old_vals[n] = old
        if self._epilogue_jit is None:
            self._epilogue_jit = jax.jit(self._epilogue)
        gated, outs = self._epilogue_jit(loss_vals, grad_vals, state,
                                         new_vals, old_vals)
        env.update(gated)
        env.update(outs)

    def _epilogue(self, loss_vals, grad_vals, state, new_vals,
                  old_vals):
        r = _verdict_math(self, list(loss_vals.values()),
                          list(grad_vals.values()), state)
        damp = _damp_factor(self, r, state)
        gated = {n: _gate_value(self, old_vals[n], v, r, damp)
                 for n, v in new_vals.items()}
        return gated, _guard_outputs(self, r)


def build_plan(program, block_idx: int = 0) -> Optional[GuardPlan]:
    """Guard plan for one (program, block): gradient names come from
    the comm scheduler's production-order walk (the same tensors its
    all-reduce buckets carry), so the guard watches exactly what the
    collective path communicates. Returns None for programs with
    nothing to guard (no param grads, no dynamic loss scale) — startup
    and inference programs stay untouched."""
    grad_names: List[str] = []
    try:
        from ..parallel.comm_scheduler import grad_production_order
        grad_names = [g for g, _, _, _ in
                      grad_production_order(program, block_idx)]
    except Exception:
        grad_names = []
    if not grad_names:
        # fallback: gradients the optimize ops consume
        try:
            block = program.block(block_idx)
            seen = set()
            for op in block.ops:
                if op.attr("op_role", "forward") != "optimize":
                    continue
                for slot in op.input_slots():
                    for n in op.input(slot):
                        if n.endswith("@GRAD") and n not in seen:
                            seen.add(n)
                            grad_names.append(n)
        except Exception:
            pass
    scale_cfg = getattr(program, "_dynamic_loss_scale", None)
    if not grad_names and not scale_cfg:
        return None
    return GuardPlan(grad_names, scale_cfg=scale_cfg)


def ensure_state(scope, plan: GuardPlan) -> None:
    """Seed the guard's persistent state vars in ``scope`` (idempotent)
    so they can join the traced step's donated inputs."""
    def _seed(name, value):
        v = scope.find_var(name)
        if v is None or not v.is_initialized():
            scope.var(name).set_value(value)

    _seed(GUARD_EMA_VAR, jnp.zeros((), jnp.float32))
    if plan.scale_cfg:
        _seed(LOSS_SCALE_VAR,
              jnp.full((1,), float(plan.scale_cfg.get("init", 1.0)),
                       jnp.float32))
        _seed(LOSS_SCALE_GOOD_VAR, jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# in-trace math
# ---------------------------------------------------------------------------

def _is_float_array(v) -> bool:
    if v is None:
        return False
    try:
        from ..core.selected_rows import is_selected_rows
        if is_selected_rows(v):
            return False
    except ImportError:
        pass
    try:
        return jnp.issubdtype(jnp.result_type(v), jnp.floating)
    except (TypeError, ValueError):
        return False


def _gateable(old, new) -> bool:
    if old is None or new is None:
        return False
    try:
        from ..core.selected_rows import is_selected_rows
        if is_selected_rows(old) or is_selected_rows(new):
            return False
    except ImportError:
        pass
    try:
        return (jnp.shape(old) == jnp.shape(new)
                and jnp.result_type(new) is not None)
    except (TypeError, ValueError):
        return False


def _state_scalar(env: dict, orig: dict, name: str, default):
    v = env.get(name)
    if v is None:
        v = orig.get(name)
    return v if v is not None else jnp.asarray(default)


def _verdict_math(plan: GuardPlan, loss_vals, grad_vals,
                  state: dict) -> dict:
    """The fused verdict: finite-AND over every watched tensor, grad
    global norm vs its EMA, loss-scale bookkeeping. Pure jnp — runs
    inside the step trace (whole-block) or inside the cached epilogue
    jit (scheduler/islands)."""
    f32 = jnp.float32
    finite = jnp.asarray(True)
    for v in loss_vals:
        finite = jnp.logical_and(
            finite, jnp.all(jnp.isfinite(v.astype(f32))))
    gsq = jnp.zeros((), f32)
    for g in grad_vals:
        g32 = g.astype(f32)
        finite = jnp.logical_and(finite,
                                 jnp.all(jnp.isfinite(g32)))
        gsq = gsq + jnp.sum(g32 * g32)
    norm = jnp.sqrt(gsq)
    scale = state.get("scale")
    if scale is not None:
        # grads carry the loss scale; the spike detector compares
        # UNSCALED norms so a scale change is not a false spike
        norm = norm / jnp.maximum(
            jnp.reshape(scale, ()).astype(f32), _MIN_SCALE)
    ema = jnp.reshape(state["ema"], ()).astype(f32)
    nonfinite = jnp.logical_not(finite)
    if grad_vals:
        spike = ((ema > 0) & finite
                 & (norm > plan.spike_factor * ema))
        obs_ok = finite & jnp.isfinite(norm) & (norm > 0)
        ema_new = jnp.where(
            spike | jnp.logical_not(obs_ok), ema,
            jnp.where(ema > 0,
                      plan.ema_beta * ema
                      + (1.0 - plan.ema_beta) * norm,
                      norm))
    else:
        spike = jnp.asarray(False)
        ema_new = ema
    out = {
        "verdict": (nonfinite.astype(jnp.int32) * NONFINITE
                    + spike.astype(jnp.int32) * SPIKE),
        "norm": norm, "nonfinite": nonfinite, "spike": spike,
        "ema": ema, "ema_new": ema_new,
    }
    if plan.scale_cfg and scale is not None:
        cfg = plan.scale_cfg
        scale0 = jnp.reshape(scale, ()).astype(f32)
        good0 = jnp.reshape(state["good"], ()).astype(jnp.int32)
        good1 = jnp.where(nonfinite, 0, good0 + 1)
        grew = jnp.logical_and(
            jnp.logical_not(nonfinite),
            good1 >= int(cfg.get("incr_every_n", 1000)))
        scale1 = jnp.where(
            nonfinite,
            jnp.maximum(scale0 * float(cfg.get("decr_ratio", 0.5)),
                        _MIN_SCALE),
            jnp.where(grew,
                      jnp.minimum(scale0
                                  * float(cfg.get("incr_ratio", 2.0)),
                                  _MAX_SCALE),
                      scale0))
        out["scale_new"] = jnp.reshape(
            scale1, jnp.shape(scale)).astype(jnp.result_type(scale))
        out["good_new"] = jnp.where(grew, 0, good1)
        out["prescale"] = scale0
    return out


def _damp_factor(plan: GuardPlan, r: dict, state: dict):
    """Spike damping: shrink the update so the effective grad norm
    equals the trip threshold (spike policy clip/rescale)."""
    return jnp.minimum(
        1.0, (plan.spike_factor * r["ema"])
        / jnp.maximum(r["norm"], _MIN_SCALE))


def _gate_value(plan: GuardPlan, old, new, r: dict, damp):
    """where(nonfinite, old, where(spike, damped_or_old, new)).

    The no-anomaly path selects ``new`` elementwise — bit-exact, so the
    guard cannot perturb a clean run (parity test). NaN updates always
    revert to ``old``; spikes either revert or damp toward the
    threshold depending on the spike policy."""
    dt = jnp.result_type(new)
    old_c = old.astype(dt) if jnp.result_type(old) != dt else old
    if plan.spike_damps and jnp.issubdtype(dt, jnp.floating):
        damped = (old_c.astype(jnp.float32)
                  + (new.astype(jnp.float32)
                     - old_c.astype(jnp.float32)) * damp).astype(dt)
        upd = jnp.where(r["spike"], damped, new)
    else:
        upd = jnp.where(r["spike"], old_c, new)
    return jnp.where(r["nonfinite"], old_c, upd)


def _guard_outputs(plan: GuardPlan, r: dict) -> dict:
    outs = {GUARD_VERDICT_VAR: r["verdict"],
            GUARD_NORM_VAR: r["norm"],
            GUARD_EMA_VAR: r["ema_new"]}
    if "scale_new" in r:
        outs[LOSS_SCALE_VAR] = r["scale_new"]
        outs[LOSS_SCALE_GOOD_VAR] = r["good_new"]
        outs[GUARD_PRESCALE_VAR] = r["prescale"]
    return outs


def apply_in_trace(env, params: dict, plan: GuardPlan,
                   fetch_names: Sequence[str],
                   persistable_all) -> None:
    """Whole-block path: called inside ``trace_step``'s ``step()`` after
    the ops ran, before the updated-persistable harvest. Rewrites every
    written persistable through the gate and emits the guard outputs
    into ``env`` (a _TrackingDict — the writes mark them updated)."""
    loss_vals = [env[n] for n in fetch_names
                 if _is_float_array(env.get(n))]
    grad_vals = [env[n] for n in plan.grad_names
                 if _is_float_array(env.get(n))]
    state = {"ema": _state_scalar(env, params, GUARD_EMA_VAR, 0.0)}
    if plan.scale_cfg:
        state["scale"] = _state_scalar(
            env, params, LOSS_SCALE_VAR,
            float(plan.scale_cfg.get("init", 1.0)))
        state["good"] = _state_scalar(env, params,
                                      LOSS_SCALE_GOOD_VAR, 0)
    r = _verdict_math(plan, loss_vals, grad_vals, state)
    damp = _damp_factor(plan, r, state)
    for n in list(getattr(env, "written", ())):
        if n in _NO_GATE or n not in persistable_all:
            continue
        old = params.get(n)
        if not _gateable(old, env.get(n)):
            continue
        env[n] = _gate_value(plan, old, env[n], r, damp)
    for n, v in _guard_outputs(plan, r).items():
        # item assignment, not .update(): the _TrackingDict must see
        # these writes so the guard outputs join the updated dict
        env[n] = v


def apply_post(plan: GuardPlan, fetches, updated: dict, params: dict,
               fetch_names: Sequence[str]):
    """Islands-fallback path: guard the step from its OUTPUTS (fetches
    + updated persistables) after the island runner finished. Grads may
    have been consumed inside a compiled segment; the guard degrades to
    loss finiteness + whatever grads survived."""
    env = dict(params)
    env.update(zip(fetch_names, fetches))
    env.update(updated)
    plan.run_epilogue(env, params, fetch_names,
                      gate_names=list(updated))
    out = {n: env[n] for n in updated}
    for n in plan.output_names():
        if n in env:
            out[n] = env[n]
    return fetches, out


# ---------------------------------------------------------------------------
# host-side controller
# ---------------------------------------------------------------------------

class _GuardPending:
    """Deferred verdict accounting under FLAGS_async_dispatch: rides the
    engine's pending ring (duck-types async_dispatch.PendingStep.check)
    so anomaly counters stay correct without a per-step sync. Recovery
    policies that must act on the live step (rollback/abort) force the
    sync path instead — see StabilityGuard.after_step."""

    __slots__ = ("_verdict", "_guard", "_engine", "_fingerprint",
                 "_done")

    def __init__(self, verdict, guard, engine, fingerprint):
        self._verdict = verdict
        self._guard = guard
        self._engine = engine
        self._fingerprint = fingerprint
        self._done = False

    def check(self):
        if self._done:
            return
        self._done = True
        try:
            v = int(np.asarray(self._verdict).reshape(-1)[0])
        except Exception:
            return
        if v:
            self._guard.note_deferred(self._engine, v)


def _metrics():
    try:
        from ..observability import metrics
        return metrics
    except Exception:
        return None


class StabilityGuard:
    """Per-engine recovery controller: verdict -> policy -> action.

    The device gate already protected the state; this class decides
    what happens NEXT — count and continue (skip/clip/rescale), restore
    the ghost ring and re-execute (rollback), or raise (abort) — plus
    repeated-anomaly escalation, the quantized-allreduce exact-bucket
    fallback, and the replay-bundle dump."""

    def __init__(self):
        # ghost cadence/depth through the knob registry
        # (tuning/knobs.py): the autotuner searches ghost_every —
        # snapshot cost vs rollback loss window, host-side only
        from ..tuning import knobs as _knobs
        self.ghost = GhostRing(max(1, int(_knobs.value("ghost_keep"))))
        self.ghost_every = max(1, int(_knobs.value("ghost_every")))
        self.escalate_after = max(1, _env_int(
            "PT_GUARD_ESCALATE_AFTER", 3))
        self.replay_max = _env_int("PT_GUARD_REPLAY_MAX", 4)
        self.consecutive = 0
        self.replay_dumps = 0
        self.quant_fallback_done = False
        self.last: Dict[str, object] = {}
        self._pol_spec: Optional[str] = None
        self._pol: Dict[str, str] = policy_map("")
        self._warned_no_ghost = False

    def _policies(self) -> Dict[str, str]:
        spec = os.environ.get("PT_STABILITY_POLICY", "")
        if spec != self._pol_spec:
            self._pol = policy_map(spec)
            self._pol_spec = spec
        return self._pol

    # -- metric plumbing -------------------------------------------------
    @staticmethod
    def _count_anomaly(engine, classes, policy):
        engine.counters["anomalies"] += 1
        m = _metrics()
        if m is not None:
            c = m.counter(
                "pt_anomalies_total",
                "stability-guard anomaly verdicts by class and "
                "applied policy (docs/STABILITY.md)")
            for cls in classes:
                c.inc(1.0, **{"class": cls, "policy": policy})

    def note_deferred(self, engine, verdict: int):
        classes = [c for c, bit in (("nonfinite", NONFINITE),
                                    ("spike", SPIKE))
                   if verdict & bit]
        self._count_anomaly(engine, classes,
                            "deferred")

    # -- the per-step decision ------------------------------------------
    def after_step(self, engine, program, scope, traced, arrays,
                   fetches, updated, rng_key, async_defer,
                   reexec: bool = False) -> str:
        """Returns "ok" (continue) or "reexecute" (state was rolled
        back to a ghost; the engine must re-dispatch the step)."""
        verdict_dev = updated.get(GUARD_VERDICT_VAR)
        if verdict_dev is None:
            return "ok"
        pol = self._policies()
        step_no = int(engine.counters.get("runs", 0))
        needs_sync = reexec or any(
            p in ("rollback", "abort") for p in pol.values())
        if not needs_sync and async_defer:
            # one pending record, zero syncs: counting happens at the
            # materialization point. Ghosts still refresh on cadence —
            # gating keeps even an anomalous step's state clean, so a
            # captured ghost is always a valid restore target.
            from ..core.engine import _MAX_PENDING_STEPS
            engine._pending.append(_GuardPending(
                verdict_dev, self, engine, program.fingerprint))
            while len(engine._pending) > _MAX_PENDING_STEPS:
                engine._pending.pop(0).check()
            self._maybe_capture(engine, scope, updated, step_no)
            return "ok"

        verdict = int(np.asarray(verdict_dev).reshape(-1)[0])
        if verdict == 0:
            self.consecutive = 0
            if not reexec:
                self._maybe_capture(engine, scope, updated, step_no)
            return "ok"

        classes = [c for c, bit in (("nonfinite", NONFINITE),
                                    ("spike", SPIKE))
                   if verdict & bit]
        primary = "nonfinite" if verdict & NONFINITE else "spike"
        policy = pol[primary]
        self.consecutive += 1
        escalated = False
        if (policy in ("skip", "clip", "rescale")
                and self.consecutive >= self.escalate_after):
            policy = "rollback"
            escalated = True
        norm = _scalar_or(updated.get(GUARD_NORM_VAR), float("nan"))
        ema = _scalar_or(updated.get(GUARD_EMA_VAR), float("nan"))
        self._count_anomaly(engine, classes, policy)
        self.last = {"step": step_no, "verdict": verdict,
                     "classes": classes, "policy": policy,
                     "norm": norm, "ema": ema,
                     "escalated": escalated, "reexec": reexec}
        warnings.warn(
            f"stability guard: step {step_no} anomaly "
            f"{'+'.join(classes)} (grad_norm={norm:.4g} "
            f"ema={ema:.4g}) -> policy {policy!r}"
            f"{' [escalated]' if escalated else ''}", stacklevel=2)

        # quantized collectives are the one anomaly source we can turn
        # off: fall back to exact buckets BEFORE burning a ghost on it
        # (the flag is in the trace cache key — next run retraces)
        if (str(getattr(FLAGS, "quantized_allreduce", "") or "")
                not in ("", "0", "False", "none")
                and not self.quant_fallback_done):
            self.quant_fallback_done = True
            engine.counters["quant_fallbacks"] += 1
            set_flags({"FLAGS_quantized_allreduce": ""})
            warnings.warn(
                "stability guard: disabling FLAGS_quantized_allreduce "
                "(exact gradient buckets) after anomaly", stacklevel=2)

        self._maybe_dump_replay(engine, program, scope, traced,
                                arrays, fetches, updated, rng_key,
                                verdict, classes, policy, step_no)

        if policy == "abort":
            engine.counters["guard_aborts"] += 1
            from ..core.enforce import EnforceNotMet
            raise EnforceNotMet(
                f"stability guard: anomaly {'+'.join(classes)} at step "
                f"{step_no} (grad_norm={norm:.4g}, ema={ema:.4g}) and "
                f"PT_STABILITY_POLICY demands abort "
                f"(docs/STABILITY.md)")
        if policy == "rollback":
            if reexec:
                # the re-executed step tripped again (deterministic
                # cause, e.g. a poisoned feed): the gate already kept
                # the state clean — degrade to skip and move on rather
                # than loop
                engine.counters["rollback_reexec_failures"] += 1
                self.consecutive = 0
                warnings.warn(
                    "stability guard: re-executed step tripped again; "
                    "accepting gated skip", stacklevel=2)
                return "ok"
            if len(self.ghost) == 0:
                if not self._warned_no_ghost:
                    self._warned_no_ghost = True
                    warnings.warn(
                        "stability guard: rollback requested but the "
                        "ghost ring is empty; degrading to skip",
                        stacklevel=2)
                return "ok"
            entry = self.ghost.restore(scope)
            engine.counters["rollbacks"] += 1
            m = _metrics()
            if m is not None:
                m.counter(
                    "pt_rollbacks_total",
                    "ghost-snapshot rollbacks performed by the "
                    "stability guard").inc()
            warnings.warn(
                f"stability guard: rolled back to ghost of step "
                f"{entry.step}; re-executing", stacklevel=2)
            return "reexecute"
        # skip / clip / rescale: the on-device gate already applied the
        # recovery; nothing further to do host-side
        return "ok"

    def _maybe_capture(self, engine, scope, updated, step_no: int):
        if len(self.ghost) and step_no % self.ghost_every != 0:
            return
        names = sorted(set(updated) | {"@RNG_STATE@"})
        t0 = time.perf_counter()
        if self.ghost.capture(scope, names, step_no) is not None:
            engine.counters["ghost_snapshots"] += 1
            engine.counters["ghost_ms"] += (time.perf_counter()
                                            - t0) * 1e3

    def _maybe_dump_replay(self, engine, program, scope, traced,
                           arrays, fetches, updated, rng_key, verdict,
                           classes, policy, step_no: int):
        if self.replay_dumps >= self.replay_max:
            return
        try:
            from .replay import dump_bundle
            path = dump_bundle(
                program=program, scope=scope, traced=traced,
                arrays=arrays, fetches=fetches, updated=updated,
                rng_key=rng_key, verdict=verdict, classes=classes,
                policy=policy, step=step_no, guard=self)
            self.replay_dumps += 1
            engine.counters["replay_bundles"] += 1
            self.last["replay_bundle"] = path
            warnings.warn(
                f"stability guard: wrote replay bundle {path} "
                f"(tools/replay_step.py --bundle {path})",
                stacklevel=2)
        except Exception as exc:  # a failed dump must not fail the step
            warnings.warn(
                f"stability guard: replay bundle dump failed: {exc}",
                stacklevel=2)


def _scalar_or(v, default: float) -> float:
    if v is None:
        return default
    try:
        return float(np.asarray(v).reshape(-1)[0])
    except Exception:
        return default
