"""Liveness-based static HBM planner (the memory half of the program
verifier).

Computes, *before anything compiles*, where a step's bytes go:

* **resident** — persistable vars (params, optimizer state, BN
  running stats) that occupy HBM for the whole run;
* **feed** — per-step input batch;
* **transient** — the peak of live non-persistable intermediates over
  a forward walk of the block (def site to last use, fetched vars
  live to the end) — the static analog of XLA's ``temp`` allocation;
* **overheads** — flag-conditional copies the runtime layers add on
  top of the program's own vars: the stability guard's ghost ring
  (``PT_GHOST_KEEP`` param snapshots), the device feed prefetcher
  (``PT_PREFETCH_DEPTH`` staged batches), and the async-checkpoint
  snapshot (reported, but only added to the peak while a save is in
  flight — the plan records it separately).

The plan is **calibrated**, not trusted: ``reconcile`` compares it
against the measured owner census (``observability/memory.census``)
and the compiled step's ``memory_analysis()``, and reports the error
ratio.
A static plan cannot see XLA's fusion/rematerialization choices or
allocator padding; the reconciliation quantifies exactly how much that
costs in accuracy instead of letting the estimate drift silently.

The ``memory-plan`` pass stays silent unless a byte limit is
configured (``PT_STATIC_HBM_LIMIT``, or the observatory's
``PT_HBM_LIMIT_BYTES`` device-limit override): book models must lint
clean by default, and an absolute OOM verdict needs a budget to
compare against.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .diagnostics import Diagnostic, Severity

__all__ = ["MemoryPlan", "plan_memory", "reconcile",
           "configured_limit_bytes"]


def _var_bytes(var, dynamic_dim: int) -> int:
    """Declared byte size of one var; 0 when shape/dtype is unknown
    (readers, LoD plumbing) — the plan counts those separately."""
    try:
        shape = list(var.shape)
    except Exception:
        return 0
    if shape is None:
        return 0
    from ..core.types import dtype_to_np
    try:
        itemsize = np.dtype(dtype_to_np(var.dtype)).itemsize
    except Exception:
        return 0
    n = 1
    for d in shape:
        d = int(d)
        n *= dynamic_dim if d < 0 else d
    return int(n) * int(itemsize)


class MemoryPlan:
    """Static per-step HBM budget for one block. All byte fields are
    plain ints so ``to_dict`` is JSON-ready."""

    __slots__ = ("resident_bytes", "feed_bytes", "transient_peak_bytes",
                 "overheads", "top_vars", "assumptions",
                 "block_idx", "label")

    def __init__(self):
        self.resident_bytes = 0
        self.feed_bytes = 0
        self.transient_peak_bytes = 0
        self.overheads: Dict[str, int] = {}
        self.top_vars: List[Dict[str, Any]] = []
        self.assumptions: Dict[str, Any] = {}
        self.block_idx = 0
        self.label = ""

    @property
    def peak_bytes(self) -> int:
        """Whole-program steady-state peak: residency + one batch +
        transient high-water + always-on overheads (the conditional
        checkpoint snapshot is reported but not added)."""
        extra = sum(v for k, v in self.overheads.items()
                    if k != "ckpt_snapshot")
        return (self.resident_bytes + self.feed_bytes +
                self.transient_peak_bytes + extra)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "peak_bytes": self.peak_bytes,
            "resident_bytes": self.resident_bytes,
            "feed_bytes": self.feed_bytes,
            "transient_peak_bytes": self.transient_peak_bytes,
            "overheads": dict(self.overheads),
            "top_vars": [dict(r) for r in self.top_vars],
            "assumptions": dict(self.assumptions),
        }

    def format(self) -> str:
        mb = 1024.0 * 1024.0
        lines = [
            f"static HBM plan{' (' + self.label + ')' if self.label else ''}:"
            f" peak {self.peak_bytes / mb:.2f} MB",
            f"  resident (persistables) {self.resident_bytes / mb:.2f} MB"
            f", feed {self.feed_bytes / mb:.2f} MB"
            f", transient peak {self.transient_peak_bytes / mb:.2f} MB",
        ]
        for k, v in sorted(self.overheads.items()):
            lines.append(f"  overhead {k}: {v / mb:.2f} MB")
        return "\n".join(lines)


def _flag_overheads(param_bytes: int, feed_bytes: int) -> Dict[str, int]:
    """Flag-conditional runtime copies, from CURRENT flag/knob state —
    the plan describes the process that would run right now."""
    from ..core.flags import FLAGS
    out: Dict[str, int] = {}
    if getattr(FLAGS, "stability_guard", False):
        try:
            from ..tuning import knobs
            keep = max(1, int(knobs.value("ghost_keep")))
        except Exception:
            keep = 2
        out["ghost_ring"] = keep * param_bytes
    try:
        depth = int(os.environ.get("PT_PREFETCH_DEPTH", "0") or 0)
    except ValueError:
        depth = 0
    if depth > 0 and feed_bytes:
        out["prefetch"] = depth * feed_bytes
    # async checkpoint snapshot: one full param copy while a save is in
    # flight; conditional, so reported but excluded from peak_bytes
    out["ckpt_snapshot"] = param_bytes
    return out


def plan_memory(program, block_idx: int = 0, feed_names=None,
                fetch_names: Sequence[str] = (), dynamic_dim: int = 1,
                include_overheads: bool = True,
                label: str = "") -> MemoryPlan:
    """Build the static plan. ``dynamic_dim`` substitutes for -1 dims
    (pass the real batch size for calibration runs; the default of 1
    gives a per-sample lower bound and is recorded as an assumption).
    """
    from ..core.islands import op_reads, op_writes
    block = program.block(block_idx)
    ops = list(block.ops)
    plan = MemoryPlan()
    plan.block_idx = block_idx
    plan.label = label
    plan.assumptions["dynamic_dim"] = int(dynamic_dim)

    # -- residency: persistables + feeds ----------------------------------
    feed_set = set(feed_names) if feed_names is not None else None
    sized: Dict[str, int] = {}
    unknown = 0

    def bytes_of(name: str) -> int:
        if name in sized:
            return sized[name]
        v = block._find_var_recursive(name)
        b = _var_bytes(v, dynamic_dim) if v is not None else 0
        if b == 0:
            nonlocal unknown
            unknown += 1
        sized[name] = b
        return b

    persistable: set = set()
    feeds: set = set()
    for name, v in block.vars.items():
        if getattr(v, "persistable", False):
            persistable.add(name)
        elif (feed_set is not None and name in feed_set) or \
                (feed_set is None and getattr(v, "is_data", False)):
            feeds.add(name)
    param_bytes = sum(_var_bytes(p, dynamic_dim)
                      for p in program.all_parameters())
    plan.resident_bytes = sum(bytes_of(n) for n in sorted(persistable))
    plan.feed_bytes = sum(bytes_of(n) for n in sorted(feeds))

    # -- transient liveness sweep -----------------------------------------
    first_def: Dict[str, int] = {}
    last_use: Dict[str, int] = {}
    for i, op in enumerate(ops):
        for n in op_reads(op):
            if n in persistable or n in feeds:
                continue
            last_use[n] = i
        for n in op_writes(op):
            if n in persistable or n in feeds:
                continue
            first_def.setdefault(n, i)
            last_use.setdefault(n, i)
    for n in set(fetch_names) & set(first_def):
        last_use[n] = len(ops)  # fetched: alive to the end of the step
    delta = [0] * (len(ops) + 2)
    for n, d in first_def.items():
        b = bytes_of(n)
        if not b:
            continue
        delta[d] += b
        delta[last_use[n] + 1] -= b
    live, peak = 0, 0
    for i in range(len(ops) + 1):
        live += delta[i]
        peak = max(peak, live)
    plan.transient_peak_bytes = int(peak)
    plan.assumptions["unsized_vars"] = unknown

    # -- top contributors (actionable "what do I shrink") -----------------
    contrib = sorted(
        ((bytes_of(n), n) for n in set(persistable) | set(first_def)),
        reverse=True)[:8]
    plan.top_vars = [
        {"name": n, "bytes": b,
         "resident": n in persistable} for b, n in contrib if b]

    if include_overheads:
        plan.overheads = _flag_overheads(param_bytes, plan.feed_bytes)
    return plan


def configured_limit_bytes() -> Optional[int]:
    """The byte budget the memory-plan pass enforces: the analysis
    limit ``PT_STATIC_HBM_LIMIT`` (bytes) if set, else the memory
    observatory's explicit ``PT_HBM_LIMIT_BYTES`` override. ``None``
    (the default) keeps the pass silent."""
    for env in ("PT_STATIC_HBM_LIMIT", "PT_HBM_LIMIT_BYTES"):
        raw = os.environ.get(env)
        if raw:
            try:
                return int(float(raw))
            except ValueError:
                continue
    return None


def reconcile(plan: MemoryPlan, census: Optional[Dict] = None,
              measured_step: Optional[Dict] = None) -> Dict[str, Any]:
    """Static-vs-measured reconciliation report.

    * ``census`` — ``observability.memory.census()`` output: its
      ``live_bytes`` is compared against the plan's steady-state
      residency (resident + feed + active overheads);
    * ``measured_step`` — a compiled step's ``memory_analysis`` split
      (``argument_bytes``/``temp_bytes``): temp is compared against
      the plan's transient peak.

    ``*_error_ratio`` fields are ``|static - measured| / measured``.
    """
    out: Dict[str, Any] = {"static": plan.to_dict()}
    if census:
        measured = float(census.get("live_bytes") or 0.0)
        static_resident = float(
            plan.resident_bytes + plan.feed_bytes +
            sum(v for k, v in plan.overheads.items()
                if k != "ckpt_snapshot"))
        out["census_live_bytes"] = measured
        out["static_resident_bytes"] = static_resident
        if measured > 0:
            out["resident_error_ratio"] = round(
                abs(static_resident - measured) / measured, 4)
    if measured_step:
        temp = float(measured_step.get("temp_bytes") or 0.0)
        if temp > 0:
            out["temp_error_ratio"] = round(
                abs(plan.transient_peak_bytes - temp) / temp, 4)
    return out


# -- the registered pass ----------------------------------------------------

from .passes import register_analysis_pass  # noqa: E402


@register_analysis_pass("memory-plan")
def memory_plan_pass(ctx) -> List[Diagnostic]:
    """Pre-compile OOM check: ERROR when the static peak exceeds the
    configured byte budget, WARNING within 10% of it. Silent when no
    budget is configured (the common case) — an absolute verdict needs
    a limit to compare against, and the plan itself is available
    through ``plan_memory`` regardless."""
    limit = configured_limit_bytes()
    if not limit:
        return []
    feed = None if ctx.feed_names is None else sorted(ctx.feed_names)
    plan = plan_memory(ctx.program, feed_names=feed,
                       fetch_names=ctx.fetch_names, label=ctx.label)
    peak = plan.peak_bytes
    mb = 1024.0 * 1024.0
    if peak > limit:
        top = ", ".join(f"{r['name']} ({r['bytes'] / mb:.1f} MB)"
                        for r in plan.top_vars[:3])
        return [ctx.diag(
            Severity.ERROR, "memory-plan",
            f"static HBM plan exceeds the configured limit: peak "
            f"{peak / mb:.2f} MB > {limit / mb:.2f} MB (resident "
            f"{plan.resident_bytes / mb:.2f} MB, transient "
            f"{plan.transient_peak_bytes / mb:.2f} MB); top "
            f"contributors: {top}",
            var_names=tuple(r["name"] for r in plan.top_vars[:3]))]
    if peak > 0.9 * limit:
        return [ctx.diag(
            Severity.WARNING, "memory-plan",
            f"static HBM plan is within 10% of the configured limit: "
            f"peak {peak / mb:.2f} MB of {limit / mb:.2f} MB — "
            f"fragmentation or allocator padding may tip it over")]
    return []
