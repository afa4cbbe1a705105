"""Cross-replica / cross-step parameter integrity sentinel.

Behind ``FLAGS_integrity_sentinel`` (docs/RESILIENCE.md): silent
parameter corruption — a flipped HBM bit, a diverged replica under
ZeRO-1 sharded updates or lossy quantized all-reduce, an out-of-band
writer scribbling on a donated buffer — is invisible to the stability
guard (the update math itself stays finite) and shows up only as an
unexplained quality regression. The sentinel makes it a *detected,
attributed, recoverable* anomaly:

* **In-trace shadow fingerprint.** Every traced step computes a cheap
  per-bucket fingerprint of the parameters — float32 sum (drift
  magnitude) + a bit-level int32 wrap-sum checksum (order-independent,
  hence bit-exact across compilations) — over the SAME greedy bucket
  layout the comm scheduler uses (parallel/comm_scheduler
  ``plan_named_buckets``). The post-update checksum is carried in a
  state var; the next step's pre-update checksum must match it
  bit-for-bit. Any mutation that happened OUTSIDE the traced update
  increments that bucket's mismatch accumulator and records its drift,
  on device, with no host sync.

* **Host verdict every ``PT_INTEGRITY_EVERY`` steps.** The controller
  (:class:`IntegritySentinel`) reads the accumulators (one small
  device->host read per sentinel window), and on mismatch raises a
  classified ``integrity`` anomaly through the stability-guard policy
  machinery (``PT_STABILITY_POLICY``: ``integrity=rollback`` default),
  writes EXACTLY ONE attributed postmortem per incident (worker,
  bucket, member params, drift) through the flight recorder, restores
  its ghost ring on rollback, and escalates to abort after
  ``PT_INTEGRITY_ESCALATE_AFTER`` consecutive bad windows.

* **Cross-replica agreement.** Under a named mapped axis (pmap-style
  paths) ``agreement_delta`` folds a pmax-vs-pmin comparison of the
  bucket fingerprints into the trace, so replicas that silently
  diverged disagree within one sentinel window. The jit/SPMD engine
  path has no named axis; there the pserver deployment compares
  worker-vs-server copies over the hardened RPC instead
  (``compare_param_sets`` / ``worker_server_compare``).

Sentinel OFF is the default and does literally nothing: no plan is
built, no state vars exist, the traced step is bit-identical to a
build without this module.
"""
from __future__ import annotations

import os
import time
import warnings
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.flags import FLAGS
from .ghost import GhostRing

__all__ = [
    "INTEGRITY_STEP_VAR", "INTEGRITY_SUM_VAR", "INTEGRITY_CK_VAR",
    "INTEGRITY_BAD_VAR", "INTEGRITY_DRIFT_VAR", "INTEGRITY_AGREE_VAR",
    "IntegrityPlan", "IntegritySentinel", "build_plan", "ensure_state",
    "invalidate_shadow", "apply_in_trace", "fingerprint_arrays",
    "agreement_delta", "compare_param_sets", "worker_server_compare"]

# scope/state variable names (same @...@ convention as the guard)
INTEGRITY_STEP_VAR = "@INTEGRITY_STEP@"    # i32 step counter
INTEGRITY_SUM_VAR = "@INTEGRITY_SUM@"      # f32[n] post-update sums
INTEGRITY_CK_VAR = "@INTEGRITY_CK@"        # i32[n] post-update checksums
INTEGRITY_BAD_VAR = "@INTEGRITY_BAD@"      # i32[n] mismatch counts
INTEGRITY_DRIFT_VAR = "@INTEGRITY_DRIFT@"  # f32[n] max |sum drift|
INTEGRITY_AGREE_VAR = "@INTEGRITY_AGREE@"  # f32 cross-replica delta

STATE_VARS = (INTEGRITY_STEP_VAR, INTEGRITY_SUM_VAR, INTEGRITY_CK_VAR,
              INTEGRITY_BAD_VAR, INTEGRITY_DRIFT_VAR,
              INTEGRITY_AGREE_VAR)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _metrics():
    try:
        from ..observability import metrics
        return metrics
    except Exception:
        return None


def check_every() -> int:
    """Host verification cadence (steps per sentinel window)."""
    return max(1, _env_int("PT_INTEGRITY_EVERY", 16))


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------

class IntegrityPlan:
    """Static fingerprint layout for one program: the parameter names
    of each bucket, in the comm scheduler's deterministic greedy
    order. Baked into the trace (FLAGS_integrity_sentinel is part of
    the engine cache key)."""

    __slots__ = ("buckets", "every", "axis_name")

    def __init__(self, buckets: Sequence[Sequence[str]],
                 axis_name: Optional[str] = None):
        self.buckets = [tuple(b) for b in buckets]
        self.every = check_every()
        self.axis_name = axis_name

    @property
    def nbuckets(self) -> int:
        return len(self.buckets)

    def param_names(self) -> List[str]:
        return [n for b in self.buckets for n in b]

    def bucket_of(self, param: str) -> Optional[int]:
        for i, b in enumerate(self.buckets):
            if param in b:
                return i
        return None

    def input_state_names(self) -> List[str]:
        return list(STATE_VARS)

    def state_var_names(self) -> List[str]:
        return list(STATE_VARS)


def build_plan(program, block_idx: int = 0,
               axis_name: Optional[str] = None) -> Optional[IntegrityPlan]:
    """Bucket the program's float parameters with the comm scheduler's
    greedy layout (same ``bucket_bytes_from_flags`` sizing, so sentinel
    attribution lines up with collective buckets). None when the
    program has no float parameters to fingerprint — or no optimizer
    UPDATE ops (``Param`` in, ``ParamOut`` out — the transpiler's own
    test): only a step that updates its parameters IN-TRACE owns them
    exclusively. For a startup or inference program, host-side writes
    between runs (initialization, a checkpoint restore, a manual
    ``set_value``) are legitimate; on the fully-async pserver path the
    update ops moved to the server and the communicator's recv thread
    refreshes params between steps (use ``worker_server_compare``
    there). A shadow checksum would misread every one of those writes
    as corruption."""
    from ..parallel.comm_scheduler import (bucket_bytes_from_flags,
                                           plan_named_buckets)
    from ..core.types import dtype_to_np
    program = getattr(program, "_program", program)
    block = program.block(block_idx)
    if not any(op.attr("op_role", "forward") == "optimize"
               and op.input("Param") and op.output("ParamOut")
               for op in block.ops):
        return None
    items = []
    for p in program.all_parameters():
        try:
            np_dtype = np.dtype(dtype_to_np(p.dtype))
        except Exception:
            continue
        if not np.issubdtype(np_dtype, np.floating):
            continue
        shape = tuple(int(d) for d in p.shape)
        items.append((p.name, shape, np_dtype))
    if not items:
        return None
    items.sort(key=lambda it: it[0])
    buckets = plan_named_buckets(items, bucket_bytes_from_flags())
    return IntegrityPlan([b.names for b in buckets],
                         axis_name=axis_name)


def ensure_state(scope, plan: IntegrityPlan) -> None:
    """Seed the sentinel's state vars in ``scope`` (idempotent) so they
    can join the traced step's donated inputs. A bucket-count change
    (a different program sharing the scope) re-seeds EVERYTHING,
    including the step counter — a shadow from another layout is
    meaningless, and ``step == 0`` is the in-trace "no shadow yet"
    gate."""
    n = plan.nbuckets
    ck = scope.find_var(INTEGRITY_CK_VAR)
    fresh = (ck is None or not ck.is_initialized()
             or tuple(jnp.shape(ck.get_value())) != (n,))

    def _seed(name, value):
        v = scope.find_var(name)
        if fresh or v is None or not v.is_initialized():
            scope.var(name).set_value(value)

    _seed(INTEGRITY_STEP_VAR, jnp.zeros((), jnp.int32))
    _seed(INTEGRITY_SUM_VAR, jnp.zeros((n,), jnp.float32))
    _seed(INTEGRITY_CK_VAR, jnp.zeros((n,), jnp.int32))
    _seed(INTEGRITY_BAD_VAR, jnp.zeros((n,), jnp.int32))
    _seed(INTEGRITY_DRIFT_VAR, jnp.zeros((n,), jnp.float32))
    _seed(INTEGRITY_AGREE_VAR, jnp.zeros((), jnp.float32))


def invalidate_shadow(scope, drop_layout: bool = False) -> None:
    """Reset the continuity shadow (step counter -> 0) after a
    LEGITIMATE out-of-band parameter write — a checkpoint restore, a
    deliberate host-side ``set_value``. The next traced step rebuilds
    the shadow without raising a false ``integrity`` anomaly.

    ``drop_layout=True`` (elastic restore, docs/RESILIENCE.md "Elastic
    topology") additionally clears the per-bucket state vars: the new
    topology re-buckets the fingerprint plan, and ``ensure_state``
    re-seeds everything for the new bucket count the moment the next
    program builds its plan — so an elastic resume never compares
    fingerprints across bucketings."""
    v = scope.find_var(INTEGRITY_STEP_VAR)
    if v is not None and v.is_initialized():
        v.set_value(np.zeros((), np.int32))
    if drop_layout:
        # un-initialize by re-seeding the CK var to a zero-length
        # vector: its shape can never equal any plan's (nbuckets,), so
        # the next ensure_state takes the `fresh` path and rebuilds
        # the whole per-bucket family for the new layout
        for name in (INTEGRITY_CK_VAR, INTEGRITY_SUM_VAR,
                     INTEGRITY_BAD_VAR, INTEGRITY_DRIFT_VAR):
            vv = scope.find_var(name)
            if vv is not None and vv.is_initialized():
                vv.set_value(np.zeros((0,), np.int32
                                      if name in (INTEGRITY_CK_VAR,
                                                  INTEGRITY_BAD_VAR)
                                      else np.float32))


# ---------------------------------------------------------------------------
# fingerprint math (pure jnp — runs inside the step trace)
# ---------------------------------------------------------------------------

def _bucket_fingerprint(vals):
    """(f32 sum, i32 wrap-sum checksum) of one bucket's arrays. The
    checksum sums the raw float32 bit patterns with int32 wraparound:
    exact and order-independent, so it is reproducible bit-for-bit
    across recompilations — the equality signal. The float sum is the
    human-readable drift magnitude, reporting only."""
    s = jnp.zeros((), jnp.float32)
    ck = jnp.zeros((), jnp.int32)
    for v in vals:
        v32 = jnp.ravel(v).astype(jnp.float32)
        s = s + jnp.sum(v32)
        bits = jax.lax.bitcast_convert_type(v32, jnp.int32)
        ck = ck + jnp.sum(bits)
    return s, ck


def fingerprint_arrays(plan: IntegrityPlan, lookup) -> tuple:
    """Per-bucket fingerprints: ``lookup(name)`` -> array (or None to
    skip). Returns (f32[n] sums, i32[n] checksums)."""
    sums, cks = [], []
    for names in plan.buckets:
        vals = [v for v in (lookup(n) for n in names) if v is not None]
        if vals:
            s, ck = _bucket_fingerprint(vals)
        else:
            s, ck = jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)
        sums.append(s)
        cks.append(ck)
    return jnp.stack(sums), jnp.stack(cks)


def agreement_delta(sums, axis_name: Optional[str]):
    """Cross-replica pmax-vs-pmin agreement over the bucket sums; 0.0
    when no mapped axis is in scope (the jit/SPMD engine path — the
    pserver deployment uses worker_server_compare instead)."""
    if not axis_name:
        return jnp.zeros((), jnp.float32)
    hi = jax.lax.pmax(sums, axis_name)
    lo = jax.lax.pmin(sums, axis_name)
    return jnp.max(jnp.abs(hi - lo))


def apply_in_trace(env, params: dict, plan: IntegrityPlan) -> None:
    """Whole-block path: called inside ``trace_step``'s ``step()``
    AFTER the guard (so the post fingerprint covers the gated, final
    values), before the updated-persistable harvest. Emits the shadow
    state through ``env`` (a _TrackingDict — writes mark them
    updated)."""
    def _state(name, default):
        v = env.get(name)
        if v is None:
            v = params.get(name)
        return v if v is not None else default

    step0 = jnp.reshape(_state(INTEGRITY_STEP_VAR,
                               jnp.zeros((), jnp.int32)), ()
                        ).astype(jnp.int32)
    prev_sum = _state(INTEGRITY_SUM_VAR,
                      jnp.zeros((plan.nbuckets,), jnp.float32))
    prev_ck = _state(INTEGRITY_CK_VAR,
                     jnp.zeros((plan.nbuckets,), jnp.int32))
    bad0 = _state(INTEGRITY_BAD_VAR,
                  jnp.zeros((plan.nbuckets,), jnp.int32))
    drift0 = _state(INTEGRITY_DRIFT_VAR,
                    jnp.zeros((plan.nbuckets,), jnp.float32))

    # pre: the parameters as this step RECEIVED them; post: as it
    # leaves them (env wins over params for updated names)
    pre_sum, pre_ck = fingerprint_arrays(plan, params.get)
    post_sum, post_ck = fingerprint_arrays(
        plan, lambda n: env.get(n, params.get(n)))

    # continuity: pre(step k) must equal post(step k-1) bit-for-bit;
    # the first step of an incarnation (step0 == 0) has no shadow yet
    valid = step0 > 0
    mism = jnp.logical_and(valid, pre_ck != prev_ck)
    bad1 = bad0 + mism.astype(jnp.int32)
    drift1 = jnp.where(mism,
                       jnp.maximum(drift0, jnp.abs(pre_sum - prev_sum)),
                       drift0)
    agree = agreement_delta(pre_sum, plan.axis_name)
    if plan.axis_name:
        # replicas disagreeing is an integrity mismatch too: charge
        # every bucket whose fingerprint differs across the axis
        hi = jax.lax.pmax(pre_ck, plan.axis_name)
        lo = jax.lax.pmin(pre_ck, plan.axis_name)
        dis = hi != lo
        bad1 = bad1 + dis.astype(jnp.int32)
        drift1 = jnp.where(dis, jnp.maximum(drift1, agree), drift1)

    env[INTEGRITY_STEP_VAR] = step0 + 1
    env[INTEGRITY_SUM_VAR] = post_sum
    env[INTEGRITY_CK_VAR] = post_ck
    env[INTEGRITY_BAD_VAR] = bad1
    env[INTEGRITY_DRIFT_VAR] = drift1
    env[INTEGRITY_AGREE_VAR] = agree


# ---------------------------------------------------------------------------
# host-side controller
# ---------------------------------------------------------------------------

def _worker_id() -> str:
    for key in ("PT_WORKER", "PADDLE_TRAINER_ID"):
        v = os.environ.get(key)
        if v:
            return str(v)
    try:
        return str(jax.process_index())
    except Exception:
        return "0"


class IntegritySentinel:
    """Per-engine verdict controller: every ``PT_INTEGRITY_EVERY``
    steps read the on-device mismatch accumulators and act — count,
    attribute, dump one postmortem per incident, roll back to the
    sentinel ghost ring, escalate to abort."""

    def __init__(self):
        self.ghost = GhostRing(2)
        self.escalate_after = max(1, _env_int(
            "PT_INTEGRITY_ESCALATE_AFTER", 3))
        self.steps = 0            # host mirror of @INTEGRITY_STEP@
        self.consecutive = 0      # consecutive bad windows
        self.incident_open = False
        self.incidents = 0

    def _policy(self) -> str:
        from .guard import policy_map
        return policy_map().get("integrity", "rollback")

    def after_step(self, engine, program, scope, traced,
                   updated) -> str:
        """Called from the engine after writeback. Cheap on non-window
        steps (one int increment); on window steps reads the small
        accumulator arrays (device->host sync of O(nbuckets) values).
        Returns "ok" or "abort" (after raising)."""
        plan = traced.integrity_plan
        self.steps += 1
        if self.steps % plan.every != 0:
            return "ok"
        t0 = time.perf_counter()
        # resync the mirror from the device counter: a guard rollback
        # or ghost restore rewinds the traced counter under us
        step_dev = updated.get(INTEGRITY_STEP_VAR)
        if step_dev is not None:
            self.steps = int(np.asarray(step_dev).reshape(())[()])
        bad = np.asarray(updated.get(
            INTEGRITY_BAD_VAR, np.zeros(plan.nbuckets, np.int32)))
        engine.counters["integrity_checks"] += 1
        m = _metrics()
        if m is not None:
            m.counter(
                "pt_integrity_checks_total",
                "sentinel verification windows completed "
                "(docs/RESILIENCE.md)").inc(1.0)
        if not bad.any():
            # clean window: close any open incident, refresh the ghost
            self.incident_open = False
            self.consecutive = 0
            names = sorted(set(updated) | set(plan.state_var_names()))
            self.ghost.capture(scope, names, self.steps)
            engine.counters["ghost_snapshots"] += 1
            engine.counters["integrity_overhead_ms"] += (
                time.perf_counter() - t0) * 1e3
            return "ok"
        return self._incident(engine, program, scope, plan, updated,
                              bad, t0)

    # -- mismatch handling ----------------------------------------------
    def _incident(self, engine, program, scope, plan, updated, bad,
                  t0) -> str:
        drift = np.asarray(updated.get(
            INTEGRITY_DRIFT_VAR, np.zeros(plan.nbuckets, np.float32)))
        agree = float(np.asarray(updated.get(
            INTEGRITY_AGREE_VAR, 0.0)).reshape(-1)[0])
        worker = _worker_id()
        buckets = [{
            "bucket": int(i),
            "mismatched_steps": int(bad[i]),
            "params": list(plan.buckets[i]),
            "drift": float(drift[i]),
        } for i in np.nonzero(bad)[0]]
        policy = self._policy()
        self.consecutive += 1
        engine.counters["integrity_mismatches"] += 1
        m = _metrics()
        if m is not None:
            c = m.counter(
                "pt_integrity_mismatch_total",
                "parameter-integrity mismatches by worker and bucket "
                "(docs/RESILIENCE.md)")
            for b in buckets:
                c.inc(1.0, worker=worker, bucket=str(b["bucket"]))
            m.gauge(
                "pt_integrity_drift",
                "max |fingerprint sum drift| of the last integrity "
                "incident").set(float(drift.max()))
        # PR 8 policy machinery: count through the guard's anomaly
        # counter so chaos_report sees one unified anomaly stream
        try:
            from .guard import StabilityGuard
            StabilityGuard._count_anomaly(engine, ["integrity"], policy)
        except Exception:
            pass
        # exactly ONE attributed postmortem per incident: re-dumping
        # every window of a persistent corruption would bury the
        # first, attributable record
        if not self.incident_open:
            self.incident_open = True
            self.incidents += 1
            try:
                from ..observability import recorder
                recorder.dump("integrity_mismatch", extra={
                    "worker": worker,
                    "step": int(self.steps),
                    "policy": policy,
                    "agreement_delta": agree,
                    "consecutive_windows": int(self.consecutive),
                    "buckets": buckets,
                })
            except Exception:
                pass
        action = "ok"
        if self.consecutive >= self.escalate_after:
            policy = "abort"
        if policy == "rollback":
            entry = self.ghost.restore(scope)
            if entry is None:
                if not getattr(self, "_warned_no_ghost", False):
                    self._warned_no_ghost = True
                    warnings.warn(
                        "integrity sentinel: mismatch before the first "
                        "clean window — no ghost to roll back to; "
                        "counting only", stacklevel=2)
            else:
                engine.counters["integrity_rollbacks"] += 1
                engine.counters["rollbacks"] += 1
                self.steps = int(entry.step)
                if m is not None:
                    m.counter(
                        "pt_integrity_rollbacks_total",
                        "integrity incidents recovered by ghost-ring "
                        "rollback (docs/RESILIENCE.md)").inc(1.0)
        elif policy == "abort":
            engine.counters["integrity_aborts"] += 1
            engine.counters["integrity_overhead_ms"] += (
                time.perf_counter() - t0) * 1e3
            from ..core.enforce import EnforceNotMet
            raise EnforceNotMet(
                f"integrity sentinel: parameter corruption on worker "
                f"{worker} (buckets "
                f"{[b['bucket'] for b in buckets]}, max drift "
                f"{float(drift.max()):g}) — policy "
                f"{'escalation' if self.consecutive >= self.escalate_after else 'integrity=abort'}"
                f" aborted the run (docs/RESILIENCE.md)")
        # skip / clip / rescale have no meaningful integrity action
        # beyond counting: the corrupt values are already absorbed
        self._reset_accumulators(scope, plan)
        engine.counters["integrity_overhead_ms"] += (
            time.perf_counter() - t0) * 1e3
        return action

    def _reset_accumulators(self, scope, plan) -> None:
        """Zero the on-device mismatch accumulators after an incident
        was handled, so the next window reports fresh corruption only.
        (A ghost restore already reset them — restoring a clean
        window's capture — but non-rollback policies must clear them
        by hand.)"""
        n = plan.nbuckets
        for name, val in ((INTEGRITY_BAD_VAR, np.zeros(n, np.int32)),
                          (INTEGRITY_DRIFT_VAR,
                           np.zeros(n, np.float32))):
            v = scope.find_var(name)
            if v is not None and v.is_initialized():
                v.set_value(val)


# ---------------------------------------------------------------------------
# pserver path: worker-vs-server fingerprint compare
# ---------------------------------------------------------------------------

def _np_fingerprint(arr) -> tuple:
    """Host-side (f32 sum, i32 wrap checksum) of one array, matching
    the checksum semantics of the traced fingerprint (int32 wraparound
    over float32 bit patterns; exact, order-independent)."""
    v32 = np.ascontiguousarray(np.ravel(np.asarray(arr)),
                               dtype=np.float32)
    s = float(v32.sum(dtype=np.float64))
    bits = v32.view(np.int32).astype(np.int64)
    ck = int(bits.sum()) & 0xFFFFFFFF
    if ck >= 1 << 31:
        ck -= 1 << 32
    return s, ck


def compare_param_sets(local: Dict[str, np.ndarray],
                       remote: Dict[str, np.ndarray],
                       atol: float = 0.0) -> List[dict]:
    """Per-parameter integrity compare of two copies of the same
    parameter set (trainer's local view vs the pserver's authoritative
    shard). ``atol`` > 0 tolerates float-sum drift up to that bound
    while still requiring it to be reported; ``atol == 0`` demands
    bit-exact checksums. Returns the mismatch records (empty = agree)."""
    out = []
    for name in sorted(set(local) & set(remote)):
        ls, lck = _np_fingerprint(local[name])
        rs, rck = _np_fingerprint(remote[name])
        if lck == rck:
            continue
        drift = abs(ls - rs)
        if atol > 0.0 and drift <= atol:
            continue
        out.append({"param": name, "local_sum": ls, "remote_sum": rs,
                    "drift": drift})
    return out


def worker_server_compare(endpoint: str, scope, names: Sequence[str],
                          atol: float = 0.0) -> List[dict]:
    """Pull per-param FINGERPRINTS from the pserver at ``endpoint``
    over the hardened RPC (retry + breaker, distributed/async_ps) and
    compare against fingerprints of the worker's scope copies — full
    tensors never cross the wire. The async-PS analog of the
    collective path's pmax-vs-pmin agreement."""
    from ..distributed.async_ps import pull_fingerprints
    local = {}
    for n in names:
        v = scope.find_var(n)
        if v is not None and v.is_initialized():
            val = v.get_value()
            local[n] = np.asarray(getattr(val, "array", val))
    remote = pull_fingerprints(endpoint, list(local))
    mismatches = []
    for name in sorted(set(local) & set(remote)):
        ls, lck = _np_fingerprint(local[name])
        rs, rck = remote[name]
        if lck == int(rck):
            continue
        drift = abs(ls - float(rs))
        if atol > 0.0 and drift <= atol:
            continue
        mismatches.append({"param": name, "local_sum": ls,
                           "remote_sum": float(rs), "drift": drift})
    if mismatches:
        m = _metrics()
        if m is not None:
            c = m.counter(
                "pt_integrity_mismatch_total",
                "parameter-integrity mismatches by worker and bucket "
                "(docs/RESILIENCE.md)")
            for rec in mismatches:
                c.inc(1.0, worker=_worker_id(), bucket=rec["param"])
    return mismatches
