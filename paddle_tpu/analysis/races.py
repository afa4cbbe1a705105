"""Engine-state, donation and bucket-plan hazard detection (the
concurrency half of the program verifier).

The engine compiles guard / loss-scale / fingerprint epilogues into the
same trace as the program's ops, donates updated-persistable input
buffers to XLA, and fuses gradient all-reduces into bucketed
collectives; each is safe only under invariants this pass proves:

* program ops must not read or write the engine's *in-trace* state
  (``@LOSS_SCALE@``, ``@GUARD_*@``, ``@INTEGRITY_*@``,
  ``@RNG_STATE@``): a user op racing the epilogues is a same-trace
  conflict nothing orders;
* a donated / aliased buffer (an updated persistable's input) must
  not be held by a pending async fetch when the next step's donation
  invalidates it;
* a ``c_allreduce_fused`` bucket plan must tile the program's grad
  production order exactly — a dropped, duplicated, or reordered
  member changes the fused payload layout and silently mixes
  tensors (or hangs) on a real ring.

The pipeline halves (``verify_stage_partition``,
``verify_pipeline_schedule``) prove a stage cutting and a micro-batch
slot table the same way, re-derived from the op slots.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from ..core.islands import op_reads as _op_reads
from ..core.islands import op_writes as _op_writes
from .diagnostics import Diagnostic, Severity

__all__ = ["donation_plan", "static_updated_names", "ENGINE_STATE_RE",
           "verify_stage_partition", "verify_pipeline_schedule"]

# engine-managed in-trace state: fully-enclosed upper-case @NAME@ vars
# (core/engine.py RNG_STATE_VAR, stability/guard.py @GUARD_*@ /
# @LOSS_SCALE@, stability/integrity.py @INTEGRITY_*@). Suffix-style
# decorations (p.name + "@SNAPSHOT", grad @RENAME@ accumulation) do
# NOT match — those are ordinary scope vars.
ENGINE_STATE_RE = re.compile(r"^@[A-Z][A-Z0-9_]*@$")


def static_updated_names(program, block_idx: int = 0) -> List[str]:
    """Static approximation of the engine's traced ``updated_names``:
    every persistable var the block writes (param updates, optimizer
    state, BN running stats). The trace-time set can only be smaller
    (an op may write a persistable a value identical to its input),
    which errs conservative for hazard analysis."""
    block = program.block(block_idx)
    out: List[str] = []
    seen: set = set()
    for op in block.ops:
        for n in _op_writes(op):
            if n in seen:
                continue
            seen.add(n)
            v = block._find_var_recursive(n)
            if v is not None and getattr(v, "persistable", False):
                out.append(n)
    return out


def donation_plan(program, block_idx: int = 0,
                  updated_names: Optional[Sequence[str]] = None
                  ) -> Dict[str, object]:
    """Static donation metadata: which buffers the engine will donate
    (updated persistables — ``core/engine.py`` computes the same set
    from its phase-1 trace) and which of them a fetch could alias.
    Consumed by the island-race pass and by observability dashboards.
    """
    if updated_names is None:
        updated_names = static_updated_names(program, block_idx)
    block = program.block(block_idx)
    donated = []
    for n in updated_names:
        v = block._find_var_recursive(n)
        if v is not None and getattr(v, "persistable", False):
            donated.append(n)
    return {"donated": donated, "block_idx": block_idx}


def _implicit_state_diags(ctx) -> List[Diagnostic]:
    """Program ops racing the engine's in-trace state epilogues."""
    diags: List[Diagnostic] = []
    for block_idx, block in enumerate(ctx.program.blocks):
        for op_idx, op in enumerate(block.ops):
            for name in _op_writes(op):
                if ENGINE_STATE_RE.match(name):
                    diags.append(ctx.diag(
                        Severity.ERROR, "island-race",
                        f"op {op.type!r} writes engine-managed "
                        f"in-trace state {name!r} — the engine's "
                        f"guard/loss-scale/fingerprint epilogue "
                        f"updates this var inside the same trace, so "
                        f"a program-op write races it with no "
                        f"ordering",
                        op=op, block_idx=block_idx, op_idx=op_idx,
                        var_names=(name,)))
            for name in _op_reads(op):
                if ENGINE_STATE_RE.match(name):
                    diags.append(ctx.diag(
                        Severity.WARNING, "island-race",
                        f"op {op.type!r} reads engine-managed "
                        f"in-trace state {name!r} — the value is "
                        f"only defined after the engine epilogue "
                        f"runs, so an in-program read observes the "
                        f"previous step's state",
                        op=op, block_idx=block_idx, op_idx=op_idx,
                        var_names=(name,)))
    return diags


def _donated_fetch_diags(ctx) -> List[Diagnostic]:
    """A fetch target that is also a donated (updated-persistable)
    buffer: under FLAGS_async_dispatch the pending fetch handle and
    the next step's donated input alias the same array."""
    if not ctx.fetch_names:
        return []
    plan = donation_plan(ctx.program)
    hot = sorted(set(ctx.fetch_names) & set(plan["donated"]))
    diags: List[Diagnostic] = []
    for name in hot:
        diags.append(ctx.diag(
            Severity.WARNING, "island-race",
            f"fetch target {name!r} is an updated persistable whose "
            f"input buffer is donated to the compiled step — under "
            f"FLAGS_async_dispatch a still-pending fetch handle "
            f"aliases a buffer the next step's donation invalidates; "
            f"fetch a copy or synchronize before the next run",
            var_names=(name,)))
    return diags


def _bucket_plan_diags(ctx) -> List[Diagnostic]:
    """Cross-path ``c_allreduce_fused`` bucket-plan consistency.

    The engine plans buckets through ``parallel/comm_scheduler``
    (greedy, production-order, dtype-homogeneous, size-capped); the
    transpiler materializes the same plan as fused ops; the dygraph
    path buckets through the same planner. Whatever path produced the
    program, a *valid* plan must tile the block's param-grad
    production order: every grad in exactly one bucket, members
    contiguous and in production order, one dtype per bucket. Those
    invariants hold for any bucket-size cap, so the check needs no
    knowledge of the cap the producer used — it catches dropped /
    duplicated / reordered members, which change the fused payload
    layout and silently mix tensors (or hang) on a real ring.
    """
    from ..parallel.comm_scheduler import grad_production_order
    program = ctx.program
    diags: List[Diagnostic] = []
    for block_idx, block in enumerate(program.blocks):
        fused = [(i, op) for i, op in enumerate(block.ops)
                 if op.type == "c_allreduce_fused"]
        if not fused:
            continue
        order = [n for n, _, _, _ in
                 grad_production_order(program, block_idx)]
        pos = {n: i for i, n in enumerate(order)}
        seen: Dict[str, int] = {}
        cursor = 0
        for op_idx, op in fused:
            names = [n for n in op.input("X") if n]
            for n in names:
                if n in seen:
                    diags.append(ctx.diag(
                        Severity.ERROR, "island-race",
                        f"bucket plan divergence: grad {n!r} is a "
                        f"member of two c_allreduce_fused buckets "
                        f"(ops #{seen[n]} and #{op_idx}) — it would "
                        f"be reduced twice",
                        op=op, block_idx=block_idx, op_idx=op_idx,
                        var_names=(n,)))
                seen[n] = op_idx
            known = [n for n in names if n in pos]
            if known != sorted(known, key=lambda n: pos[n]):
                diags.append(ctx.diag(
                    Severity.ERROR, "island-race",
                    f"bucket plan divergence: c_allreduce_fused "
                    f"members {known} are not in grad production "
                    f"order — member order defines the fused payload "
                    f"offsets, so ranks disagreeing on it mix "
                    f"tensors element-wise with no error",
                    op=op, block_idx=block_idx, op_idx=op_idx,
                    var_names=tuple(known)))
            if known and pos[known[0]] < cursor:
                diags.append(ctx.diag(
                    Severity.ERROR, "island-race",
                    f"bucket plan divergence: bucket at op "
                    f"#{op_idx} starts at grad {known[0]!r} which "
                    f"precedes a grad already fused — buckets must "
                    f"tile the production order contiguously",
                    op=op, block_idx=block_idx, op_idx=op_idx,
                    var_names=(known[0],)))
            if known:
                cursor = max(cursor, pos[known[-1]] + 1)
            dtypes = set()
            for n in names:
                v = block._find_var_recursive(n) or \
                    block._find_var_recursive(n.split("@GRAD")[0])
                if v is not None:
                    dtypes.add(str(v.dtype))
            if len(dtypes) > 1:
                diags.append(ctx.diag(
                    Severity.ERROR, "island-race",
                    f"bucket plan divergence: c_allreduce_fused op "
                    f"#{op_idx} mixes dtypes {sorted(dtypes)} in one "
                    f"bucket — the fused flat payload is single-dtype",
                    op=op, block_idx=block_idx, op_idx=op_idx,
                    var_names=tuple(names)))
        missing = [n for n in order if n not in seen]
        if missing:
            diags.append(ctx.diag(
                Severity.ERROR, "island-race",
                f"bucket plan divergence: param grads {missing} are "
                f"in the block's production order but in no "
                f"c_allreduce_fused bucket — their updates silently "
                f"skip the ring on this rank and desync replicas",
                block_idx=block_idx, var_names=tuple(missing)))
    return diags


def verify_stage_partition(program, cut_vars, block_idx: int = 0,
                           stacked: bool = False,
                           label: Optional[str] = None
                           ) -> List[Diagnostic]:
    """Cross-stage hazards of a pipeline cutting (category
    ``pipeline-race``): the pipeline engines split one block at
    ``cut_vars`` and run the stages on different devices under a
    micro-batch schedule, so hazards the single-program executor could
    never exhibit become possible:

    * activation-handoff WRITE-WRITE — a value that crosses a stage
      boundary is (re)written by a second stage: the consumer may
      observe either producer depending on dispatch order;
    * consumed-before-produced (RW) — a stage reads a value whose only
      producer is a LATER stage: the schedule moves activations
      strictly forward, so the read can never be satisfied;
    * stacked-param update aliasing — a param read by several stages.
      With ``stacked=True`` (the SPMD engine, which stacks per-stage
      param slabs into one leading-``pp``-dim array) two slab rows
      alias ONE scope var and the per-stage updates silently diverge
      from the single-device semantics: ERROR.  The MPMD engine sums
      the per-stage grads and updates once, so there it is only a
      replication-cost WARNING.

    The stage read/write sets are re-derived from the op slots via
    ``parallel/auto_cut.stage_partition``, not from any engine
    bookkeeping.
    """
    from ..parallel.auto_cut import stage_partition
    diags: List[Diagnostic] = []
    try:
        part = stage_partition(program, cut_vars, block_idx)
    except ValueError as e:
        return [Diagnostic(
            Severity.ERROR, "pipeline-race",
            f"invalid stage cutting: {e}", block_idx=block_idx,
            var_names=tuple(cut_vars), program_label=label)]
    produced_by: Dict[str, int] = {}
    for s, w in enumerate(part.stage_writes):
        for n in w:
            produced_by.setdefault(n, s)
    # 1. activation-handoff WW: any name written by 2+ stages that some
    # OTHER stage reads (a purely stage-internal rewrite is the normal
    # in-stage dataflow the def-use pass already covers)
    for name in sorted(set().union(*part.stage_writes)
                       if part.stage_writes else ()):
        writers = [s for s, w in enumerate(part.stage_writes)
                   if name in w]
        if len(writers) < 2:
            continue
        readers = [s for s, r in enumerate(part.stage_reads)
                   if name in r and s not in writers]
        if readers or name in part.cut_vars:
            diags.append(Diagnostic(
                Severity.ERROR, "pipeline-race",
                f"activation-handoff write-write hazard: stages "
                f"{writers} all write {name!r} which stage(s) "
                f"{readers or writers} consume across the boundary — "
                f"the handoff value depends on stage dispatch order",
                block_idx=block_idx, var_names=(name,),
                program_label=label))
    # 2. consumed-before-produced: reader stage strictly before the
    # producing stage (params/feeds have no producer — skipped)
    for s, reads in enumerate(part.stage_reads):
        for name in sorted(reads - part.stage_writes[s]):
            src = produced_by.get(name)
            if src is not None and src > s:
                diags.append(Diagnostic(
                    Severity.ERROR, "pipeline-race",
                    f"consumed-before-produced hazard: stage {s} "
                    f"reads {name!r} but its only producer is stage "
                    f"{src} — activations flow strictly forward, so "
                    f"no schedule can satisfy this read",
                    block_idx=block_idx, var_names=(name,),
                    program_label=label))
    # 3. stacked-param aliasing
    tied = part.tied_params()
    if tied:
        sev = Severity.ERROR if stacked else Severity.WARNING
        what = ("the SPMD engine stacks per-stage param slabs, so two "
                "slab rows alias one scope var and the per-stage "
                "updates silently diverge" if stacked else
                "the MPMD engine replicates it per stage and sums the "
                "grads — correct, but the memory cost is per-stage")
        diags.append(Diagnostic(
            sev, "pipeline-race",
            f"{len(tied)} param(s) read by more than one stage "
            f"({', '.join(tied[:5])}{'...' if len(tied) > 5 else ''})"
            f" — {what}",
            block_idx=block_idx, var_names=tuple(tied[:8]),
            program_label=label))
    return diags


def verify_pipeline_schedule(events, n_stages: int, n_micro: int,
                             label: Optional[str] = None
                             ) -> List[Diagnostic]:
    """Prove a pipeline slot table (``parallel/pipeline.pipeline_schedule``
    events ``(tick, device, kind, stage, micro)``) safe before anything
    dispatches: every F/B event exactly once, every event's pipeline
    dependencies strictly earlier (F(s,m) after F(s-1,m); B(s,m) after
    F(s,m) and after B(s+1,m) — the activation/cotangent handoffs),
    and no device double-booked in a tick.  A violated edge is exactly
    a cross-stage read-before-write on the handoff buffer, so the
    diagnostics use the same ``pipeline-race`` category as
    ``verify_stage_partition``.
    """
    diags: List[Diagnostic] = []
    slot: Dict[Tuple[str, int, int], int] = {}
    busy: Dict[Tuple[int, int], Tuple[str, int, int]] = {}
    for tick, dev, kind, s, m in events:
        k = (kind, s, m)
        if k in slot:
            diags.append(Diagnostic(
                Severity.ERROR, "pipeline-race",
                f"duplicate event {kind}(stage={s}, micro={m}) at "
                f"ticks {slot[k]} and {tick} — the micro-batch would "
                f"be computed twice (grads double-counted)",
                program_label=label))
            continue
        slot[k] = tick
        prev = busy.get((tick, dev))
        if prev is not None:
            diags.append(Diagnostic(
                Severity.ERROR, "pipeline-race",
                f"device {dev} double-booked at tick {tick}: "
                f"{prev[0]}(stage={prev[1]}, micro={prev[2]}) and "
                f"{kind}(stage={s}, micro={m})",
                program_label=label))
        busy[(tick, dev)] = k
    expect = [(kind, s, m) for kind in ("F", "B")
              for s in range(n_stages) for m in range(n_micro)]
    missing = [k for k in expect if k not in slot]
    if missing:
        k0 = missing[0]
        diags.append(Diagnostic(
            Severity.ERROR, "pipeline-race",
            f"{len(missing)} event(s) missing from the schedule "
            f"(first: {k0[0]}(stage={k0[1]}, micro={k0[2]})) — the "
            f"step would silently drop micro-batch work",
            program_label=label))
    last = n_stages - 1
    for (kind, s, m), t in sorted(slot.items()):
        deps = []
        if kind == "F" and s > 0:
            deps.append(("F", s - 1, m))
        if kind == "B":
            deps.append(("F", s, m))
            if s < last:
                deps.append(("B", s + 1, m))
        for d in deps:
            td = slot.get(d)
            if td is None:
                continue  # reported as missing above
            if td >= t:
                diags.append(Diagnostic(
                    Severity.ERROR, "pipeline-race",
                    f"handoff read-before-write: {kind}(stage={s}, "
                    f"micro={m}) at tick {t} consumes the output of "
                    f"{d[0]}(stage={d[1]}, micro={d[2]}) scheduled at "
                    f"tick {td} — the "
                    f"{'activation' if d[0] == 'F' else 'cotangent'} "
                    f"buffer is read before it is produced",
                    program_label=label))
    return diags


# registered last so importing either module order works: passes.py
# pulls this module in at its own bottom, by which point
# register_analysis_pass is already defined
from .passes import register_analysis_pass  # noqa: E402


@register_analysis_pass("island-race")
def island_race_pass(ctx) -> List[Diagnostic]:
    """Engine-state conflicts, donated-fetch aliasing and fused-bucket
    plan divergence."""
    return (_implicit_state_diags(ctx) + _donated_fetch_diags(ctx)
            + _bucket_plan_diags(ctx))
