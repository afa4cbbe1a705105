"""Programmable operator scheduler: concurrent island dispatch +
micro-batch pipelining (docs/SCHEDULING.md).

A synchronous step is host dispatch + fetch serialization behind ONE
monolithic whole-block executable (July 2026, previous installation:
178.9 ms sync vs a 59.1 ms device bound; git history — not measured on
the present machine). DynaFlow's observation (PAPERS.md) is that a
block is rarely one dependence chain — forward, backward, and the
per-parameter optimizer updates are data-independent subgraphs that a
programmable scheduler can dispatch on separate lanes. This module
generalizes ``core/islands.py`` from "split only at dynamic ops" to
"split wherever subgraphs are data-independent":

* the block is cut into contiguous *phases* at the forward/backward/
  optimize ``op_role`` boundaries (any contiguous cut is dependence-
  safe: program order only ever carries values forward);
* within a phase, union-find over def-use connects every reader and
  writer of a name that the phase WRITES (read-read sharing of params
  or feeds does not merge), yielding data-independent *islands*;
* each island compiles to its own ``jax.jit`` executable; same-phase
  islands are dispatched concurrently on a small thread-pool of
  dispatch lanes, and phases are dispatched back-to-back WITHOUT
  waiting on device results — jax arrays are futures, so island k+1's
  host dispatch overlaps island k's device compute.

The payoff for the synchronous loop is structural: the loss is a
*forward-phase* output, so fetching it completes as soon as the forward
island finishes — the backward and optimizer islands are still running
on-device when ``run()`` returns. The whole-block executable cannot
offer that: one dispatch, one completion event, the fetch waits for the
optimizer.

For gradient accumulation (``engine._run_accumulated`` semantics,
multi_batch_merge parity) the scheduler pipelines the micro-batch loop:
one compiled compute executable dispatched K times with per-slice
``fold_in`` keys (slice k+1's feed slicing + dispatch overlaps slice
k's device work), grads averaged exactly as the host loop does, then
one compiled optimizer executable.

Numerical identity with the whole-block jit is by construction:
per-op RNG keys fold the op's *uid* into the step key
(``registry.ExecContext.rng``), never the op's position, so splitting
the block cannot change any op's randomness; islands partition the ops
(each op runs exactly once) and values flow through the same names.
The parity tests in ``tests/test_op_scheduler.py`` assert bit-identical
losses with the flag on and off.

Everything here is gated behind ``FLAGS_op_scheduler`` and returns
``None`` from :func:`build_scheduled_step` whenever a program is not
eligible (SPMD meshes, sub-block ops, LoD feeds, iterations > 1,
single-island blocks) — the engine's whole-block jit stays the
fallback, with buffer donation; scheduled steps do not donate (an
updated param crosses island boundaries, so the input buffer must stay
alive until the consuming island has it).
"""
from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .registry import _RngCtx

__all__ = ["build_scheduled_step", "partition_block", "last_read_table",
           "op_reads", "op_writes", "Island", "ScheduledStep",
           "PipelinedAccumStep", "PartitionInfo", "partition_metadata",
           "static_updated_names", "pipeline_schedule",
           "gpipe_bubble_fraction", "interleaved_bubble_fraction"]

# dispatch lanes: submitting a jitted call is host work (arg flattening
# + runtime enqueue), so a handful of threads is enough to keep the
# device queue full; PT_SCHED_LANES overrides (read at runtime through
# the knob registry, tuning/knobs.py — an import-time read here froze
# the lane count before the autotuner or a test could change it)
def lanes() -> int:
    from ..tuning import knobs
    try:
        return max(2, int(knobs.value("sched_lanes")))
    except (TypeError, ValueError):
        return 4


_POOL: Optional[ThreadPoolExecutor] = None
_POOL_LOCK = threading.Lock()


def _pool() -> ThreadPoolExecutor:
    """The shared dispatch pool, rebuilt when the lane knob changes.

    Rebuild is safe mid-flight: the old executor keeps draining the
    futures already submitted to it (shutdown(wait=False) only stops
    NEW submissions), while new steps land on the resized pool."""
    global _POOL
    n = lanes()
    with _POOL_LOCK:
        if _POOL is None or _POOL._max_workers != n:
            old, _POOL = _POOL, ThreadPoolExecutor(
                max_workers=n, thread_name_prefix="pt-sched-lane")
            if old is not None:
                old.shutdown(wait=False)
    return _POOL


# ---------------------------------------------------------------------------
# def-use analysis helpers (shared with islands.IslandRunner)
# ---------------------------------------------------------------------------

def op_reads(op) -> List[str]:
    return [n for slot in op.input_slots() for n in op.input(slot) if n]


def op_writes(op) -> List[str]:
    return [n for slot in op.output_slots() for n in op.output(slot)
            if n]


def last_read_table(ops: Sequence, reads_fn=op_reads) -> Dict[str, int]:
    """name -> highest op index that READS it. One O(ops) pass; lets a
    partitioner answer "is this name used at/after index i" without
    rescanning the op suffix per segment (the O(n²) the old
    ``IslandRunner._segment_for`` paid)."""
    table: Dict[str, int] = {}
    for i, op in enumerate(ops):
        for n in reads_fn(op):
            table[n] = i
    return table


def _phase_ranges(ops) -> List[Tuple[int, int]]:
    """Contiguous [start, end) phase ranges cut at the first backward
    and first optimize ``op_role``. ANY contiguous cut is dependence-
    safe — program order only carries values forward — so the roles are
    purely a quality heuristic that separates the three naturally
    independent op populations."""
    n = len(ops)
    b = next((i for i, op in enumerate(ops)
              if op.attr("op_role", "forward") == "backward"), n)
    o = next((i for i in range(b, n)
              if ops[i].attr("op_role", "forward") == "optimize"), n)
    cuts = sorted({0, b, o, n})
    return [(s, e) for s, e in zip(cuts, cuts[1:]) if e > s]


def _components(ops, start: int, end: int) -> List[List[int]]:
    """Union-find connected components of ops[start:end] under the
    def-use relation: ops are connected iff they touch a common name
    that the range WRITES. Names nobody in the range writes (params,
    feeds, activations from earlier phases) are shared read-only inputs
    and must NOT merge their readers — that read-read sharing is
    exactly the independence being harvested."""
    size = end - start
    parent = list(range(size))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    def union(a, b):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[rb] = ra

    readers: Dict[str, List[int]] = {}
    writers: Dict[str, List[int]] = {}
    for i in range(start, end):
        li = i - start
        for n in op_reads(ops[i]):
            readers.setdefault(n, []).append(li)
        for n in op_writes(ops[i]):
            writers.setdefault(n, []).append(li)
    for n, ws in writers.items():
        for w in ws[1:]:
            union(ws[0], w)
        for r in readers.get(n, ()):
            union(ws[0], r)
    groups: Dict[int, List[int]] = {}
    for li in range(size):
        groups.setdefault(find(li), []).append(start + li)
    return sorted(groups.values(), key=lambda g: g[0])


def _cap_components(comps: List[List[int]], cap: int) -> List[List[int]]:
    """Merge the smallest components until at most `cap` remain — one
    executable per tiny optimizer update would trade the dispatch win
    back for per-call overhead."""
    comps = list(comps)
    while len(comps) > cap:
        comps.sort(key=len)
        merged = sorted(comps[0] + comps[1])
        comps = comps[2:] + [merged]
    return sorted(comps, key=lambda g: g[0])


class Island:
    """One data-independent subgraph: op indices plus its dataflow
    interface (external reads in, externally-consumed writes out)."""

    __slots__ = ("indices", "phase", "in_names", "out_names",
                 "writes", "jfn", "labels")

    def __init__(self, indices: List[int], phase: int):
        self.indices = indices
        self.phase = phase
        self.in_names: List[str] = []
        self.out_names: List[str] = []
        self.writes: set = set()
        self.jfn = None
        self.labels: List[Tuple[str, str]] = []


def _island_interface(ops, isl: Island) -> None:
    """First-reads (names read before any local write) and the local
    write set, in op order."""
    reads: List[str] = []
    writes: set = set()
    for i in isl.indices:
        for n in op_reads(ops[i]):
            if n not in writes and n not in reads:
                reads.append(n)
        writes.update(op_writes(ops[i]))
    isl.in_names = reads
    isl.writes = writes


def partition_block(ops, fetch_names: Sequence[str],
                    updated_names: Sequence[str],
                    cap: Optional[int] = None) -> List[List[Island]]:
    """Partition `ops` into phases of data-independent islands.

    Returns phases in program order; islands within a phase are mutually
    data-independent (no name written by one is read by another — the
    invariant ``tests/test_op_scheduler.py`` checks against
    ``analysis.def_use.DefUseGraph``). Each op lands in exactly one
    island. ``out_names`` is each island's externally-consumed write
    set: reads of OTHER islands plus the step outputs (fetches, updated
    persistables). ``cap`` (same-phase island bound) defaults to the
    CURRENT lane count — resolved per call, not at import, so the
    sched_lanes knob shapes the partition the step is traced with."""
    if cap is None:
        cap = lanes()
    phases: List[List[Island]] = []
    for pi, (s, e) in enumerate(_phase_ranges(ops)):
        comps = _cap_components(_components(ops, s, e), cap)
        phase = []
        for comp in comps:
            isl = Island(comp, pi)
            _island_interface(ops, isl)
            phase.append(isl)
        phases.append(phase)
    all_islands = [isl for phase in phases for isl in phase]
    keep = set(fetch_names) | set(updated_names)
    for isl in all_islands:
        external: set = set(keep)
        for other in all_islands:
            if other is not isl:
                external.update(other.in_names)
        isl.out_names = sorted(isl.writes & external)
    return phases


# ---------------------------------------------------------------------------
# analysis-facing partition metadata (paddle_tpu/analysis/races.py,
# memplan.py, cost_model.py) — the verifier reasons about the SAME
# partition the dispatcher would run, instead of re-deriving its own
# approximation of the phase-cut union-find
# ---------------------------------------------------------------------------

class PartitionInfo:
    """The scheduler's partition decision, packaged for the static
    analyzer: the phases-of-islands (each with its dataflow
    interface), the ops they index into, and — when the block cannot
    be scheduled — the reason, so a pass can distinguish "verified
    conflict-free" from "never dispatched concurrently"."""

    __slots__ = ("phases", "ops", "eligible", "reason", "cap",
                 "block_idx", "updated_names", "fetch_names")

    def __init__(self, phases, ops, eligible, reason, cap, block_idx,
                 updated_names, fetch_names):
        self.phases = phases          # List[List[Island]] ([] if inel.)
        self.ops = ops                # the block's op list
        self.eligible = eligible      # statically schedulable?
        self.reason = reason          # "" when eligible
        self.cap = cap                # same-phase island bound used
        self.block_idx = block_idx
        self.updated_names = list(updated_names)
        self.fetch_names = list(fetch_names)

    def islands(self):
        """(global_island_idx, phase_idx, Island) in dispatch order —
        the same global indices attribution/memory rows use."""
        idx = 0
        for pi, phase in enumerate(self.phases):
            for isl in phase:
                yield idx, pi, isl
                idx += 1

    def island_count(self) -> int:
        return sum(len(p) for p in self.phases)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "eligible": self.eligible, "reason": self.reason,
            "cap": self.cap, "block_idx": self.block_idx,
            "phases": [
                [{"ops": len(isl.indices), "in": list(isl.in_names),
                  "out": list(isl.out_names)} for isl in phase]
                for phase in self.phases],
        }


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
        for n in op_writes(op):
            if n in seen:
                continue
            seen.add(n)
            v = block._find_var_recursive(n)
            if v is not None and getattr(v, "persistable", False):
                out.append(n)
    return out


def partition_metadata(program, block_idx: int = 0,
                       fetch_names: Sequence[str] = (),
                       updated_names: Optional[Sequence[str]] = None,
                       cap: Optional[int] = None) -> PartitionInfo:
    """Compute the partition the op scheduler WOULD dispatch for this
    block, without building executables. ``updated_names=None`` infers
    the static persistable-write set (the engine passes its traced set
    at validation tier 2). Mirrors ``build_scheduled_step``'s static
    eligibility gates; runtime-only gates (mesh, accumulation,
    LoD feeds, integrity sentinel) are the caller's to apply."""
    block = program.block(block_idx)
    ops = list(block.ops)
    if updated_names is None:
        updated_names = static_updated_names(program, block_idx)
    if cap is None:
        cap = lanes()
    if any(_has_sub_block(op) for op in ops):
        return PartitionInfo([], ops, False, "control-flow sub-block",
                             cap, block_idx, updated_names, fetch_names)
    phases = partition_block(ops, fetch_names, updated_names, cap=cap)
    n = sum(len(p) for p in phases)
    if n <= 1:
        return PartitionInfo(phases, ops, False,
                             "single island (whole-block jit)",
                             cap, block_idx, updated_names, fetch_names)
    return PartitionInfo(phases, ops, True, "", cap, block_idx,
                         updated_names, fetch_names)


def scheduler_gate(program, block_idx: int = 0,
                   fetch_names: Sequence[str] = (),
                   mesh=None, iterations: int = 1, feed_lods=None,
                   integrity_plan=None,
                   updated_names: Optional[Sequence[str]] = None,
                   check_partition: bool = False,
                   multi_step: int = 1
                   ) -> Tuple[bool, str]:
    """The island-path gate as ONE shared predicate: could the op
    scheduler take this (program, runtime state)?

    ``engine.trace_step`` calls this (``check_partition=False``) before
    attempting ``build_scheduled_step``; the conformance verifier and
    the tier-2 cross-check (analysis/conformance.py) call the same
    predicate so the static claim "islands are impossible here" can
    never drift from what the engine actually does.  Returns
    (eligible, reason) — with ``check_partition=True`` the static
    partition eligibility is folded in too (build_scheduled_step still
    has runtime-only outs, so True means "possible", not "certain")."""
    from .flags import FLAGS
    if not FLAGS.op_scheduler:
        return False, "FLAGS_op_scheduler is off"
    if integrity_plan is not None:
        return False, ("integrity sentinel requires the whole-block "
                       "trace (fingerprint cannot span islands)")
    if mesh is not None:
        return False, ("a device mesh forces the whole-block SPMD "
                       "path: islands never run multi-device")
    if int(iterations) != 1:
        return False, ("num_iteration_per_run > 1 compiles one "
                       "scanned whole-block executable")
    if int(multi_step) != 1:
        return False, ("PT_MULTI_STEP > 1 compiles one scanned "
                       "whole-block executable")
    if feed_lods:
        return False, "LoD feeds take the whole-block path"
    if check_partition:
        info = partition_metadata(program, block_idx,
                                  fetch_names=fetch_names,
                                  updated_names=updated_names)
        if not info.eligible:
            return False, f"partition ineligible: {info.reason}"
    return True, "eligible"


def _has_sub_block(op) -> bool:
    """Ops carrying sub-blocks (while/cond/py_func trampolines) need the
    engine's block_runner recursion rooted in ONE env — splitting them
    across islands is not worth modeling. Detected structurally so this
    module needs no framework import."""
    for _name, val in op._all_attrs():
        if hasattr(val, "idx"):
            return True
        if isinstance(val, (list, tuple)) and val and \
                all(hasattr(v, "idx") for v in val):
            return True
    return False


# ---------------------------------------------------------------------------
# scheduled execution
# ---------------------------------------------------------------------------

class _TraceBase:
    """Shared tracing machinery: run an op subset inside a jit trace
    with amp + nan-check collection (the islands.py pattern)."""

    def __init__(self, program, block, amp_cfg, check_nan):
        self.program = program
        self.block = block
        self.ops = list(block.ops)
        self.amp_cfg = amp_cfg
        self.check_nan = check_nan
        self.labels: List[Tuple[str, str]] = []
        self.last_stats: Dict[str, Any] = {}
        # stability guard epilogue (paddle_tpu/stability/guard.py):
        # the scheduled step runs as many small executables, so the
        # verdict + update gate run as ONE cached jitted epilogue over
        # the step's final arrays instead of inside the (nonexistent)
        # whole-block trace
        self.guard_plan = None

    def _amp(self):
        if self.amp_cfg:
            from .amp import amp_guard
            return amp_guard(True,
                             self.amp_cfg.get("dtype", jnp.bfloat16),
                             self.amp_cfg.get("black_ops", ()),
                             self.amp_cfg.get("white_ops", ()))
        import contextlib
        return contextlib.nullcontext()

    def _run_collecting(self, ops, env, rng_ctx, checks, use_amp=True):
        from . import engine as _eng

        def block_runner(idx, sub_env=None):
            _eng.run_block_ops(self.program.block(idx),
                               sub_env if sub_env is not None else env,
                               rng_ctx, {}, block_runner)
            return sub_env if sub_env is not None else env

        if self.check_nan:
            _eng._nan_check_ctx.items = []
        try:
            with self._amp() if use_amp else _nullctx():
                _eng.run_block_ops(self.block, env, rng_ctx, {},
                                   block_runner, ops=ops)
        finally:
            got = getattr(_eng._nan_check_ctx, "items", None)
            _eng._nan_check_ctx.items = None
        if self.check_nan and got:
            checks.extend(got)


def _nullctx():
    import contextlib
    return contextlib.nullcontext()


class ScheduledStep(_TraceBase):
    """TracedStep-compatible callable dispatching islands on lanes.

    ``(donated_params, const_params, feeds, key) -> (fetches, updated,
    nan_flags)`` — donated is always {} here (no donation under the
    scheduler). The first call runs islands inline so every executable
    traces deterministically; steady-state calls submit same-phase
    islands to the lane pool and gather in build order, keeping fetch
    tuples, updated dicts, and nan-flag stacking deterministic."""

    def __init__(self, program, block, phases: List[List[Island]],
                 fetch_names, updated_names, amp_cfg, check_nan):
        super().__init__(program, block, amp_cfg, check_nan)
        self.phases = phases
        self.fetch_names = list(fetch_names)
        self.updated_names = list(updated_names)
        self.n_islands = sum(len(p) for p in phases)
        self._traced_once = False

    # -- build --------------------------------------------------------------
    def _make_fn(self, isl: Island):
        ops = [self.ops[i] for i in isl.indices]
        captured: Dict[str, Any] = {}

        def f(ins, key):
            env = dict(ins)
            checks: List = []
            self._run_collecting(ops, env, _RngCtx(key), checks)
            captured["labels"] = [(t, n) for t, n, _ in checks]
            outs = {n: env[n] for n in isl.out_names if n in env}
            return outs, tuple(fl for _, _, fl in checks)

        return f, captured

    def build(self, env_sig: Dict[str, Any], key_sig) -> None:
        """Abstractly validate + wire every island (raises on anything
        the per-island trace cannot express — the caller falls back to
        the whole-block path)."""
        sig = dict(env_sig)
        for phase in self.phases:
            outs_sigs = []
            for isl in phase:
                f, captured = self._make_fn(isl)
                ins_sig = {n: sig[n] for n in isl.in_names if n in sig}
                outs_sig, _flags = jax.eval_shape(f, ins_sig, key_sig)
                isl.jfn = jax.jit(f)
                isl.labels = list(captured.get("labels", ()))
                self.labels.extend(isl.labels)
                outs_sigs.append(outs_sig)
            for outs_sig in outs_sigs:
                sig.update(outs_sig)
        self._final_sig = sig

    # -- dispatch -----------------------------------------------------------
    @staticmethod
    def _call_island(isl: Island, ins, key):
        t0 = time.perf_counter()
        outs, flags = isl.jfn(ins, key)
        t1 = time.perf_counter()
        return outs, flags, t0, t1, threading.current_thread().name

    def __call__(self, donated_params, const_params, feeds, key):
        env: Dict[str, Any] = dict(const_params)
        env.update(donated_params)
        env.update(feeds)
        guard_orig = None
        if self.guard_plan is not None:
            # pre-step values of everything the gate may revert, plus
            # the guard's own input state
            guard_orig = {n: env[n]
                          for n in set(self.updated_names)
                          | set(self.guard_plan.input_state_names())
                          if n in env}
        t_step = time.perf_counter()
        spans: List[dict] = []
        flags_all: List = []
        idle_ms = 0.0
        isl_base = 0   # global island index across phases — the key
        # device-time attribution joins on (docs/TRACING.md)
        inline = not self._traced_once
        for pi, phase in enumerate(self.phases):
            # snapshot inputs for the whole phase BEFORE any island of
            # it writes back — islands of one phase are independent and
            # must each see the pre-phase env
            ins_list = [{n: env[n] for n in isl.in_names if n in env}
                        for isl in phase]
            if len(phase) == 1 or inline:
                results = [self._call_island(isl, ins, key)
                           for isl, ins in zip(phase, ins_list)]
            else:
                futs = [_pool().submit(self._call_island, isl, ins, key)
                        for isl, ins in zip(phase, ins_list)]
                results = [f.result() for f in futs]
            if len(phase) > 1 and not inline:
                t0s = [r[2] for r in results]
                t1s = [r[3] for r in results]
                window = max(t1s) - min(t0s)
                idle_ms += sum(window - (t1 - t0)
                               for t0, t1 in zip(t0s, t1s)) * 1e3
            for ii, (isl, (outs, flags, t0, t1, lane)) in enumerate(
                    zip(phase, results)):
                env.update(outs)
                flags_all.extend(flags)
                spans.append({"phase": pi, "i": isl_base + ii,
                              "ops": len(isl.indices),
                              "lane": lane,
                              "t0_ms": round((t0 - t_step) * 1e3, 3),
                              "dur_ms": round((t1 - t0) * 1e3, 3)})
            isl_base += len(phase)
        self._traced_once = True
        if self.guard_plan is not None:
            self.guard_plan.run_epilogue(env, guard_orig,
                                         self.fetch_names,
                                         self.updated_names)
        fetches = []
        for n in self.fetch_names:
            if n not in env:
                raise KeyError(
                    f"fetch target {n!r} was not produced by the "
                    f"program")
            fetches.append(env[n])
        updated = {n: env[n] for n in self.updated_names if n in env}
        nan_flags = jnp.stack([jnp.asarray(f) for f in flags_all]) \
            if flags_all else ()
        self.last_stats = {"islands": self.n_islands,
                           "islands_concurrent": max(
                               len(p) for p in self.phases),
                           "lane_idle_ms": round(idle_ms, 3),
                           "spans": spans}
        return tuple(fetches), updated, nan_flags


class PipelinedAccumStep(_TraceBase):
    """Micro-batch pipeline for the gradient-accumulation path.

    Mirrors ``engine._run_accumulated`` exactly — dense slice per
    micro-batch, per-slice ``fold_in(key, i)`` RNG, mean-of-slice-grads,
    optimizer once with the step key, NO amp guard (the host loop
    applies none) — but as one compiled compute executable dispatched K
    times plus one compiled optimizer executable. Dispatches are
    futures: slice k+1's host feed-slicing + dispatch overlaps slice
    k's device work, and grad accumulation chains on-device."""

    def __init__(self, program, block, accum_k: int, fetch_names,
                 updated_names, check_nan):
        # amp_cfg None: parity with the host accumulation loop
        super().__init__(program, block, None, check_nan)
        self.accum_k = int(accum_k)
        self.fetch_names = list(fetch_names)
        self.updated_names = list(updated_names)
        self.compute_ops = [op for op in self.ops
                            if op.attr("op_role", "forward")
                            != "optimize"]
        self.opt_ops = [op for op in self.ops
                        if op.attr("op_role", "forward") == "optimize"]
        self.grad_names = sorted({
            n for op in self.opt_ops for slot in op.input_slots()
            for n in op.input(slot) if n.endswith("@GRAD")})

    def build(self, params_sig, feed_sig, key_sig) -> None:
        if not self.opt_ops or not self.grad_names:
            raise NotImplementedError(
                "no optimize phase / grads to accumulate")
        from .selected_rows import is_selected_rows  # noqa: F401
        k = self.accum_k
        # dense slice signatures (trace_step validated divisibility)
        slice_sig = {n: jax.ShapeDtypeStruct(
            (s.shape[0] // k,) + tuple(s.shape[1:]), s.dtype)
            for n, s in feed_sig.items()}
        c_writes: set = set()
        for op in self.compute_ops:
            c_writes.update(op_writes(op))
        opt_reads: List[str] = []
        opt_writes: set = set()
        for op in self.opt_ops:
            for n in op_reads(op):
                if n not in opt_writes and n not in opt_reads:
                    opt_reads.append(n)
            opt_writes.update(op_writes(op))
        keep = set(self.fetch_names) | set(self.updated_names)
        self._compute_outs = sorted(
            c_writes & (set(self.grad_names) | set(opt_reads) | keep))
        self._opt_outs = sorted(opt_writes & keep)
        self._opt_reads = opt_reads
        captured_c: Dict[str, Any] = {}

        def f_compute(params, feed_slice, key):
            env = dict(params)
            env.update(feed_slice)
            checks: List = []
            self._run_collecting(self.compute_ops, env, _RngCtx(key),
                                 checks, use_amp=False)
            captured_c["labels"] = [(t, n) for t, n, _ in checks]
            outs = {n: env[n] for n in self._compute_outs if n in env}
            return outs, tuple(fl for _, _, fl in checks)

        outs_sig, _ = jax.eval_shape(f_compute, params_sig, slice_sig,
                                     key_sig)
        self._compute_labels = list(captured_c.get("labels", ()))
        self._compute_jfn = jax.jit(f_compute)
        captured_o: Dict[str, Any] = {}

        def f_opt(ins, key):
            env = dict(ins)
            checks: List = []
            self._run_collecting(self.opt_ops, env, _RngCtx(key),
                                 checks, use_amp=False)
            captured_o["labels"] = [(t, n) for t, n, _ in checks]
            outs = {n: env[n] for n in self._opt_outs if n in env}
            return outs, tuple(fl for _, _, fl in checks)

        opt_ins_sig = {}
        for n in opt_reads:
            if n in outs_sig:
                opt_ins_sig[n] = outs_sig[n]
            elif n in params_sig:
                opt_ins_sig[n] = params_sig[n]
            elif n in slice_sig:
                opt_ins_sig[n] = slice_sig[n]
        jax.eval_shape(f_opt, opt_ins_sig, key_sig)
        self._opt_labels = list(captured_o.get("labels", ()))
        self._opt_jfn = jax.jit(f_opt)
        # one label entry per flag in dispatch order: K compute slices
        # then the optimizer
        self.labels = self._compute_labels * self.accum_k \
            + self._opt_labels

    def __call__(self, donated_params, const_params, feeds, key):
        from .selected_rows import SelectedRows, is_selected_rows
        params = dict(const_params)
        params.update(donated_params)
        k = self.accum_k
        t_step = time.perf_counter()
        spans: List[dict] = []
        flags_all: List = []
        dispatch_ms = 0.0
        g_acc: Dict[str, Any] = {}
        outs = {}
        sl = {}
        for i in range(k):
            sl = {}
            for n, arr in feeds.items():
                sz = arr.shape[0] // k
                sl[n] = arr[i * sz:(i + 1) * sz]
            t0 = time.perf_counter()
            outs, flags = self._compute_jfn(
                params, sl, jax.random.fold_in(key, i))
            t1 = time.perf_counter()
            dispatch_ms += (t1 - t0) * 1e3
            spans.append({"phase": 0, "micro_batch": i,
                          "ops": len(self.compute_ops),
                          "t0_ms": round((t0 - t_step) * 1e3, 3),
                          "dur_ms": round((t1 - t0) * 1e3, 3)})
            flags_all.extend(flags)
            for n in self.grad_names:
                g = outs.get(n)
                if g is None:
                    continue
                prev = g_acc.get(n)
                if prev is None:
                    g_acc[n] = g
                elif is_selected_rows(g):
                    g_acc[n] = SelectedRows(
                        jnp.concatenate([prev.rows, g.rows]),
                        jnp.concatenate([prev.values, g.values]),
                        g.height)
                else:
                    g_acc[n] = prev + g
        inv = 1.0 / k
        g_avg = {}
        for n, g in g_acc.items():
            g_avg[n] = g.map_values(
                lambda v: (v * inv).astype(v.dtype)) \
                if is_selected_rows(g) else g * inv
        opt_ins = {}
        for n in self._opt_reads:
            if n in g_avg:
                opt_ins[n] = g_avg[n]
            elif n in outs:
                opt_ins[n] = outs[n]
            elif n in params:
                opt_ins[n] = params[n]
            elif n in sl:
                opt_ins[n] = sl[n]
        t0 = time.perf_counter()
        opt_outs, opt_flags = self._opt_jfn(opt_ins, key)
        t1 = time.perf_counter()
        dispatch_ms += (t1 - t0) * 1e3
        spans.append({"phase": 1, "ops": len(self.opt_ops),
                      "t0_ms": round((t0 - t_step) * 1e3, 3),
                      "dur_ms": round((t1 - t0) * 1e3, 3)})
        flags_all.extend(opt_flags)
        window_ms = (time.perf_counter() - t_step) * 1e3
        env = dict(outs)
        env.update(g_avg)
        env.update(opt_outs)
        if self.guard_plan is not None:
            # guard over the AVERAGED grads (same tensors the host
            # accumulation loop's guard sees); pre-step values come
            # from params
            self.guard_plan.run_epilogue(env, params,
                                         self.fetch_names,
                                         self.updated_names)
        fetches = []
        for n in self.fetch_names:
            if n not in env:
                raise KeyError(
                    f"fetch target {n!r} was not produced by the "
                    f"program")
            fetches.append(env[n])
        updated = {n: env[n] for n in self.updated_names if n in env}
        nan_flags = jnp.stack([jnp.asarray(f) for f in flags_all]) \
            if flags_all else ()
        # host-side duty cycle of the accumulation window: 1.0 means
        # micro-batch dispatches issued back-to-back with no host stall
        fill = min(1.0, dispatch_ms / window_ms) if window_ms > 0 \
            else 0.0
        self.last_stats = {"micro_batches": k,
                           "pipeline_fill_frac": round(fill, 4),
                           "lane_idle_ms": 0.0,
                           "spans": spans}
        return tuple(fetches), updated, nan_flags


# ---------------------------------------------------------------------------
# pipeline micro-batch schedules (GPipe fill/drain vs interleaved 1F1B)
# ---------------------------------------------------------------------------
# The dispatch-loop generalization of PipelinedAccumStep: where the
# accumulation step dispatches K compute slices on ONE executable, a
# pipeline dispatches forward/backward slots of MANY per-stage
# executables (parallel/mpmd_pipeline.py) — the schedule below decides
# the slot ORDER, and the same span/fill accounting PipelinedAccumStep
# keeps in ``last_stats`` extends to a measured bubble fraction (idle
# device-slots over the schedule makespan).


def gpipe_bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Analytic GPipe fill/drain bubble: (S-1)/(M+S-1)."""
    s, m = int(n_stages), int(n_micro)
    return (s - 1) / float(m + s - 1) if m + s > 1 else 0.0


def interleaved_bubble_fraction(n_devices: int, n_micro: int,
                                n_chunks: int) -> float:
    """Analytic interleaved-1F1B bubble: (D-1)/(V*M + D-1) for D
    devices each hosting V model chunks (Megatron-style virtual
    stages). V=1 degenerates to the GPipe fraction."""
    d, m, v = int(n_devices), int(n_micro), max(1, int(n_chunks))
    return (d - 1) / float(v * m + d - 1) if v * m + d > 1 else 0.0


def pipeline_schedule(n_stages: int, n_micro: int,
                      n_devices: int = None,
                      kind: str = "1f1b") -> Dict[str, Any]:
    """Build a static pipeline micro-batch schedule as a slot table.

    Stages are assigned round-robin to devices (``device = stage %
    n_devices``), so ``n_stages > n_devices`` means each device hosts
    ``V = n_stages / n_devices`` interleaved model chunks — the
    Megatron-style virtual-stage layout that shrinks the 1F1B bubble
    from (D-1)/(M+D-1) to (D-1)/(V*M+D-1).

    The table is produced by a deterministic list-scheduling pass over
    the F/B dependence DAG (F(s,m) needs F(s-1,m); B(s,m) needs F(s,m)
    and B(s+1,m)), one unit-time slot per event per device tick:

    * ``kind="gpipe"``  — forwards before backwards (fill/drain);
    * ``kind="1f1b"``   — each device runs forwards only up to its
      warmup quota of un-drained micro-batches (the Megatron warmup
      count, ``2*(D-d-1) + (V-1)*D + 1``), then prefers the readiest
      backward — highest chunk first, oldest micro first — which caps
      the activation stash at the pipeline depth and reaches the
      analytic interleaved bubble (D-1)/(V*M+D-1).

    Returns ``{"events": [(tick, device, kind, stage, micro), ...] in
    dispatch order, "makespan", "bubble_frac" (measured from the slot
    table: idle device-slots / total device-slots), "stash_peak"
    (max in-flight forward stashes), "kind", "n_chunks"}``.
    """
    S, M = int(n_stages), int(n_micro)
    D = int(n_devices) if n_devices else S
    if S < 1 or M < 1 or D < 1:
        raise ValueError(f"pipeline_schedule: need n_stages/n_micro/"
                         f"n_devices >= 1, got {S}/{M}/{D}")
    if kind not in ("gpipe", "1f1b"):
        raise ValueError(f"pipeline_schedule: unknown kind {kind!r}")
    dev_of = [s % D for s in range(S)]
    n_chunks = (S + D - 1) // D
    done: set = set()          # completed events ("F"|"B", s, m)
    pending = {("F", s, m) for s in range(S) for m in range(M)}
    pending |= {("B", s, m) for s in range(S) for m in range(M)}

    def _ready(ev):
        k, s, m = ev
        if k == "F":
            return s == 0 or ("F", s - 1, m) in done
        if ("F", s, m) not in done:
            return False
        return s == S - 1 or ("B", s + 1, m) in done

    def _quota(d):
        return 2 * (D - d - 1) + (n_chunks - 1) * D + 1

    def _prio(ev, prefer_b):
        k, s, m = ev
        chunk = s // D
        if k == "F":
            return (1 if prefer_b else 0, m, chunk)
        # backwards drain the HIGHEST chunk first (it unblocks the
        # reverse wavefront of every lower chunk), oldest micro first
        return (0 if prefer_b else 1, -chunk, m)

    events: List[Tuple[int, int, str, int, int]] = []
    dev_flight = [0] * D
    stash_peak = 0
    tick = 0
    while pending:
        fired = []
        for d in range(D):
            cand = [ev for ev in pending
                    if dev_of[ev[1]] == d and _ready(ev)]
            if not cand:
                continue
            prefer_b = (kind == "1f1b" and
                        dev_flight[d] >= _quota(d))
            fired.append(min(
                cand, key=lambda ev: _prio(ev, prefer_b)))
        if not fired:  # cannot happen on a well-formed DAG
            raise RuntimeError("pipeline_schedule: deadlock")
        for ev in fired:
            pending.discard(ev)
            events.append((tick, dev_of[ev[1]], ev[0], ev[1], ev[2]))
        for ev in fired:
            done.add(ev)
            dev_flight[dev_of[ev[1]]] += 1 if ev[0] == "F" else -1
        stash_peak = max(stash_peak, sum(dev_flight))
        tick += 1
    makespan = tick
    busy = 2 * S * M
    bubble = 1.0 - busy / float(D * makespan) if makespan else 0.0
    return {"events": events, "makespan": makespan,
            "bubble_frac": round(bubble, 6), "stash_peak": stash_peak,
            "kind": kind, "n_chunks": n_chunks, "n_devices": D,
            "n_stages": S, "n_micro": M}


# ---------------------------------------------------------------------------
# entry point (called from engine.trace_step after phase-1 discovery)
# ---------------------------------------------------------------------------

def build_scheduled_step(program, block, params_sig, feed_sig,
                         fetch_names, avail, updated_names, amp_cfg,
                         accum_k, check_nan, fetch_lod_box,
                         uses_rng=True, guard_plan=None):
    """Build a scheduler-backed TracedStep, or None when the program is
    not eligible (the caller's whole-block jit is the fallback).
    Never raises: any build/validation failure means "not schedulable",
    not "broken program" — the standard path will surface real errors.
    """
    from .engine import TracedStep, _split_first
    ops = list(block.ops)
    try:
        if any(_has_sub_block(op) for op in ops):
            return None
        env_sig = dict(params_sig)
        env_sig.update(feed_sig)
        key_sig = jax.ShapeDtypeStruct((2,), jnp.uint32)
        if accum_k > 1:
            sched: Any = PipelinedAccumStep(
                program, block, accum_k, fetch_names, updated_names,
                check_nan)
            sched.build(dict(params_sig), dict(feed_sig), key_sig)
        else:
            keep_names = list(fetch_names)
            if guard_plan is not None:
                # islands must EXPORT the watched gradients so the
                # guard epilogue sees them even when producer and
                # consumer share an island
                keep_names += [g for g in guard_plan.grad_names
                               if g not in keep_names]
            phases = partition_block(ops, keep_names, updated_names)
            if sum(len(p) for p in phases) <= 1:
                # one island == the whole-block jit, which also gets
                # buffer donation; nothing to schedule
                return None
            sched = ScheduledStep(program, block, phases, fetch_names,
                                  updated_names, amp_cfg, check_nan)
            sched.build(env_sig, key_sig)
        sched.guard_plan = guard_plan
    except Exception:
        return None
    ts = TracedStep(_split_first(sched.__call__), [], list(avail),
                    sorted(feed_sig),
                    list(fetch_names), list(updated_names),
                    fetch_lod_box, uses_rng,
                    nan_check_labels=sched.labels)
    ts.op_sched = sched
    return ts
