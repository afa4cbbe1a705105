"""Whole-block XLA compilation engine.

This is the TPU-native replacement for the reference's per-op interpreter
loop (Executor::RunPreparedContext hot loop, /root/reference/paddle/fluid/
framework/executor.cc:433-438) and for its entire IR fusion / memory-pass
stack (framework/ir/*): an executor run traces EVERY op of a block into one
jittable JAX function (feeds + persistables -> fetches + updated
persistables), compiles it once per (program version, feed signature), and
dispatches a single XLA executable per step. Buffer donation of updated
persistables gives in-place optimizer updates (replacing the in-place /
memory-reuse passes); XLA fusion replaces the fuse_* pass family; XLA
liveness replaces the eager-deletion GC.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import os

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

import threading
import time

from .enforce import EnforceNotMet, wrap_op_error
from .flags import FLAGS
from .registry import OPS, ExecContext, _RngCtx
from .scope import LoDTensor, Scope
from .types import dtype_to_np
from ..observability import metrics as _obs
from ..observability import recorder as _obs_recorder
from ..observability import tracing as _obs_tracing
from ..observability import memory as _obs_memory
from .. import profiler as _profiler

RNG_STATE_VAR = "@RNG_STATE@"

# active check_nan_inf collection for the trace on this thread (engine +
# control-flow sub-blocks all append to the same list); None = off
_nan_check_ctx = threading.local()

# ops the tracing engine handles itself / skips
_ENGINE_OPS = {"feed", "fetch"}

# lazily bound fault-injection module (avoids importing the distributed
# package during core bootstrap); see paddle_tpu/distributed/faults.py
_faults_mod = None


def _fault_plan():
    global _faults_mod
    if _faults_mod is None:
        from ..distributed import faults as _f
        _faults_mod = _f
    return _faults_mod.current()


class _TrackingDict(dict):
    """env that records which names were (re)written during tracing."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.written = set()

    def __setitem__(self, k, v):
        self.written.add(k)
        super().__setitem__(k, v)


class TracedStep:
    """A compiled step. ``fn(donated_params, const_params, feeds,
    rng_state)`` takes the RAW rng state, splits it as a host-side
    ``jax.random.split`` would and returns ``(fetches, updated,
    nan_flags, info)``: ``info["rng_state"]`` is the state the scope
    keeps for the next step."""

    def __init__(self, fn, donated_names, const_names, feed_names,
                 fetch_names, updated_names, fetch_lods, uses_rng,
                 nan_check_labels=()):
        self.fn = fn
        self.donated_names = donated_names
        self.const_names = const_names
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        self.updated_names = updated_names
        self.fetch_lods = fetch_lods  # name -> lod (host metadata)
        self.uses_rng = uses_rng
        # the first call of fn lowers and compiles (or loads) the
        # executable: Engine._first_dispatch spans it, once
        self.dispatched = False
        # PT_MULTI_STEP: K > 1 means fn scans K stacked batches through
        # one executable, splitting the state once a substep: the
        # fetches come back stacked and info["valid"] counts the
        # substeps that took effect
        self.multi_step = 1
        # live reference to the trace's (op_type, var_name) label box, one
        # entry per all-finite flag when check_nan_inf is on. A reference,
        # not a snapshot: on the eager-interpreter path the box is only
        # filled while a step runs, after TracedStep construction
        self._nan_labels_box = nan_check_labels

    @property
    def nan_check_labels(self):
        return tuple(self._nan_labels_box)


def _compiler_options():
    """Backend compiler knobs for the compiled step, from
    PT_COMPILER_OPTIONS="k=v,k=v" (e.g.
    "xla_tpu_scoped_vmem_limit_kib=65536"). The reference exposed its
    backend tuning the same way (conv_workspace_size_limit,
    cudnn_exhaustive_search — gflags through the env); these reach the
    compiler as per-executable compile options, not XLA_FLAGS. Read
    through the knob registry (tuning/knobs.py) so an applied tuning
    config takes effect without re-import."""
    from ..tuning import knobs as _knobs
    spec = str(_knobs.value("compiler_options") or "").strip()
    if not spec:
        return None
    opts = {}
    for kv in spec.split(","):
        if not kv.strip():
            continue
        k, _, v = kv.partition("=")
        opts[k.strip()] = v.strip()
    return opts or None


def _collect_persistable_inputs(program, block, scope: Scope):
    """Names of persistable vars referenced by the block (params, opt state,
    LR, bn stats, ...) that must come from the scope."""
    names = []
    seen = set()
    for op in block.ops:
        for slot in op.input_slots():
            for n in op.input(slot):
                if n in seen:
                    continue
                seen.add(n)
                v = block._find_var_recursive(n)
                if v is not None and v.persistable:
                    names.append(n)
        # in-place updated persistables appear only as outputs of init ops
        for slot in op.output_slots():
            for n in op.output(slot):
                seen.add(n)
    return names


# Row-preserving ops that share their first LoD input's offsets with
# same-row-count outputs — the opt-in analog of the reference's per-op
# ShareLoD calls (a blanket row-count heuristic would mis-tag e.g.
# transpose of a square tensor). Covers the common token-wise pipeline:
# embedding -> fc/mul -> activation -> norm -> emission.
_LOD_SHARING_OPS = frozenset({
    "lookup_table", "mul", "sum", "scale", "cast", "clip", "dropout",
    "softmax", "log_softmax", "layer_norm", "elementwise_add",
    "elementwise_sub", "elementwise_mul", "elementwise_div",
    "elementwise_max", "elementwise_min", "elementwise_pow", "assign",
    "relu", "relu6", "sigmoid", "tanh", "exp", "log", "sqrt", "rsqrt",
    "abs", "square", "gelu", "swish", "softplus", "softsign",
    "leaky_relu", "elu", "brelu", "soft_relu", "hard_sigmoid", "selu",
    "stanh", "logsigmoid", "pow", "concat", "row_conv",
})


def _share_lod(op, env, lod_env):
    """Default LoD propagation (reference ShareLoD in InferShape): for
    row-preserving ops, an output that kept the row count of a
    LoD-carrying input inherits its offsets, unless the lowering set
    one explicitly. This is what lets `emission = fc(embedding(word))`
    stay per-sequence for the CRF."""
    if op.type not in _LOD_SHARING_OPS:
        return
    src = None
    for slot in op.input_slots():
        for n in op.input(slot):
            if lod_env.get(n):
                src = n
                break
        if src:
            break
    if src is None:
        return
    sv = env.get(src)
    src_rows = sv.shape[0] if hasattr(sv, "shape") and \
        getattr(sv, "shape", None) else None
    if src_rows is None:
        return
    for slot in op.output_slots():
        for n in op.output(slot):
            if n in lod_env:
                continue
            v = env.get(n)
            shape = getattr(v, "shape", None)
            if shape and shape[0] == src_rows:
                lod_env[n] = lod_env[src]


def _recompute_types():
    """Op types to RECOMPUTE at the forward/backward boundary
    (PT_RECOMPUTE="batch_norm,relu,elementwise_add"). The stash these
    ops' outputs would otherwise carry fwd→bwd is re-derived behind an
    optimization_barrier (so XLA cannot CSE it back into the original),
    letting buffer assignment end the originals' lifetimes inside the
    forward — the program-level analog of jax.checkpoint for a graph
    whose backward is explicit grad ops. Trades one extra pass of
    cheap compute for the carried bytes (the ResNet BN/relu/residual
    chains are ~10.5 GB of a 54 GB step; July 2026, previous
    installation, git history).

    MEASURED (July 2026, previous installation): on ResNet-50 B=128 this
    LOSES — 2,429 → 1,815 img/s (full list) / 1,932 (relu+residual
    only). The barriers that keep XLA from CSE-ing the recompute away
    also keep it from fusing the recomputed ops into their consumers,
    so the pass materializes MORE buffers than the stash it frees. The
    knob stays for experimentation; default off. Read through the knob
    registry (tuning/knobs.py): runtime changes take effect, and the
    value is key-audited into both trace cache keys."""
    from ..tuning import knobs as _knobs
    spec = str(_knobs.value("recompute") or "").strip()
    return frozenset(t for t in spec.split(",") if t) if spec else None


def _op_scope(op):
    """The op's name in the compiled step's HLO metadata
    (`op_name="jit(step1)/forward/layer_norm/..."`): the name scope it
    was built under (`fluid.name_scope`, the `op_namescope` attribute),
    its role and its type. Metadata only: fusion decisions and
    instruction names stay as they are."""
    return (f"{op.attr('op_namescope', '')}"
            f"{op.attr('op_role', 'forward')}/{op.type}")


def _recompute_stash(fwd_ops, bwd_ops, env, types, rng_ctx, lod_env,
                     block_runner):
    bwd_reads = set()
    for op in bwd_ops:
        for slot in op.input_slots():
            bwd_reads.update(op.input(slot))
    for op in fwd_ops:
        if op.type not in types:
            continue
        outs = [n for slot in op.output_slots()
                for n in op.output(slot)]
        if not any(n in bwd_reads for n in outs):
            continue
        sub = dict(env)
        for slot in op.input_slots():
            for n in op.input(slot):
                v = sub.get(n)
                if v is not None and hasattr(v, "dtype"):
                    sub[n] = jax.lax.optimization_barrier(v)
        ctx = ExecContext(op, sub, rng_ctx, block_runner, lod_env)
        with jax.named_scope(_op_scope(op)):
            OPS.get(op.type).lowering(ctx)
        for n in outs:
            # rebind ONLY bwd-consumed, non-persistable outputs; a
            # persistable output (bn running stats) must not apply its
            # update twice
            var = op.block._find_var_recursive(n) \
                if hasattr(op, "block") else None
            if n in bwd_reads and n in sub and \
                    (var is None or not var.persistable):
                env[n] = sub[n]


def run_block_ops(block, env, rng_ctx, lod_env, block_runner, ops=None,
                  comm_points=None):
    """Trace ops (default: all of the block) into the env (shared by
    executor + control flow sub-blocks). `comm_points` maps op index ->
    hook(env): the comm scheduler's fused-bucket collective points,
    invoked right after the op that seals each bucket so the collective
    interleaves with (and overlaps) the remaining backward
    (parallel/comm_scheduler.py)."""
    recompute = _recompute_types()
    recomputed = recompute is None
    for i, op in enumerate(block.ops if ops is None else ops):
        if not recomputed and \
                op.attr("op_role", "forward") == "backward":
            recomputed = True
            op_list = block.ops if ops is None else ops
            try:
                _recompute_stash(op_list[:i], op_list[i:], env,
                                 recompute, rng_ctx, lod_env,
                                 block_runner)
            except Exception as exc:
                import warnings
                warnings.warn(f"PT_RECOMPUTE pass skipped: {exc}",
                              stacklevel=2)
        if op.type in _ENGINE_OPS:
            # feed: value is pre-seeded into env; fetch: alias out name
            if op.type == "fetch":
                src = op.input("X")[0]
                dst = op.output("Out")[0]
                env[dst] = env[src]
                if src in lod_env and dst not in lod_env:
                    lod_env[dst] = lod_env[src]
            if comm_points is not None:
                hook = comm_points.get(i)
                if hook is not None:
                    hook(env)
            continue
        try:
            info = OPS.get(op.type)
            ctx = ExecContext(op, env, rng_ctx, block_runner, lod_env)
            with jax.named_scope(_op_scope(op)):
                info.lowering(ctx)
        except (NotImplementedError, jax.errors.JAXTypeError) as exc:
            # handled by the island partitioner; overwrite so the
            # OUTERMOST frame's index wins (a dynamic op inside a
            # control-flow sub-block demotes the whole control-flow op).
            # JAXTypeError covers lowerings that CONCRETIZE tracer
            # values (np.asarray on data-dependent results, e.g. the
            # `where` index op) — same host-op treatment as an explicit
            # NotImplementedError
            exc._island_op_index = i
            raise
        except EnforceNotMet:
            # already carries op context
            raise
        except Exception as exc:  # re-raise with op/var context (enforce.h)
            raise wrap_op_error(exc, op, env, i) from exc
        _share_lod(op, env, lod_env)
        checks = getattr(_nan_check_ctx, "items", None)
        if checks is not None:
            _append_nan_checks(checks, op, env)
        if comm_points is not None:
            hook = comm_points.get(i)
            if hook is not None:
                hook(env)


def _append_nan_checks(checks, op, env):
    """check_nan_inf instrumentation (reference operator.cc:953-983):
    record an all-finite flag per float output; the engine fetches the
    stacked flags and raises on the first False, naming op and var."""
    for slot in op.output_slots():
        for n in op.output(slot):
            v = env.get(n)
            dt = getattr(v, "dtype", None)
            if dt is not None and jnp.issubdtype(dt, jnp.floating):
                checks.append((op.type, n, jnp.all(jnp.isfinite(v))))


def _slice_lod(lod, s0, s1):
    """Slice sequences [s0, s1) out of a (possibly multi-level) LoD.
    Returns (rebased_lod, row0, row1) where rows index the tensor's
    leading dim (offsets partition the next level's entries, the last
    level partitions rows — reference lod_tensor.h:58 semantics)."""
    out = []
    lo, hi = s0, s1
    for level in lod:
        seg = [int(x) for x in level[lo:hi + 1]]
        base = seg[0]
        out.append([x - base for x in seg])
        lo, hi = seg[0], seg[-1]
    return out, lo, hi


def _lod_accum_slices(feed_sig, feed_lods, accum_k):
    """Per-micro-batch feed slicing plan for ragged feeds: each entry
    maps feed name -> (row0, row1, sliced_lod or None)."""
    seq_counts = {n: len(lod[0]) - 1 for n, lod in feed_lods.items()
                  if lod}
    counts = set(seq_counts.values())
    if len(counts) != 1:
        raise EnforceNotMet(
            f"gradient accumulation over ragged feeds requires every "
            f"LoD feed to hold the same number of sequences; got "
            f"{seq_counts}")
    (n_seq,) = counts
    if n_seq % accum_k != 0:
        raise EnforceNotMet(
            f"{n_seq} sequences are not divisible by "
            f"gradient_accumulation_steps={accum_k}")
    per = n_seq // accum_k
    for n, sig in feed_sig.items():
        if n not in feed_lods and (not sig.shape or
                                   sig.shape[0] != n_seq):
            raise EnforceNotMet(
                f"dense feed {n!r} (shape {tuple(sig.shape)}) must "
                f"have one row per sequence ({n_seq}) to combine with "
                f"ragged feeds under gradient accumulation")
    plans = []
    for i in range(accum_k):
        s0, s1 = i * per, (i + 1) * per
        plan = {}
        for n in feed_sig:
            lod = feed_lods.get(n)
            if lod:
                sliced, r0, r1 = _slice_lod(lod, s0, s1)
                plan[n] = (r0, r1, sliced)
            else:
                plan[n] = (s0, s1, None)
        plans.append(plan)
    return plans


def _split_first(fn):
    """``fn`` over a step key -> the step's contract over the RAW rng
    state (:class:`TracedStep`): the state is split as the host's
    ``jax.random.split`` would split it, ``fn`` runs under the first
    half and the second is handed back as the next state. Inside the
    jitted step the split is part of the executable, so a steady step
    is ONE executable call; the eager, island and scheduler paths split
    on the host as they always did."""

    @functools.wraps(fn)
    def stepped(donated_params, const_params, feeds, rng):
        pair = jax.random.split(rng)
        fetches, updated, nan_flags = fn(donated_params, const_params,
                                         feeds, pair[0])
        return fetches, updated, nan_flags, {"rng_state": pair[1]}

    return stepped


def _loop_fallback(fn, iterations):
    """num_iteration_per_run on the eager/islands paths: host loop with
    state chained through the updated-persistables dict."""
    if iterations <= 1:
        return fn

    def looped(donated_params, const_params, feeds, key):
        donated = dict(donated_params)
        const = dict(const_params)
        merged_upd = {}
        nf_acc = None
        for i in range(iterations):
            f, upd, nf = fn(donated, const, feeds,
                            jax.random.fold_in(key, i))
            # a transient NaN/Inf in ANY iteration must trip the check,
            # not just the last one's flags
            if nf_acc is None or (isinstance(nf_acc, tuple)
                                  and not nf_acc):
                nf_acc = nf
            else:
                nf_acc = jax.tree_util.tree_map(jnp.logical_and,
                                                nf_acc, nf)
            merged_upd.update(upd)
            for n, v in upd.items():
                if n in donated:
                    donated[n] = v
                elif n in const:
                    const[n] = v
        return f, merged_upd, nf_acc

    return looped


def _multi_loop_fallback(fn, k):
    """PT_MULTI_STEP on the eager/islands paths: host loop over the K
    stacked batches with the same split-per-substep RNG chain the
    compiled scan driver uses, so trajectories stay bit-identical to K
    sequential dispatches. The guard verdict is checked per substep
    (these paths are host-bound anyway) so an anomaly breaks out early
    exactly like the compiled carry freeze."""

    def multi(donated_params, const_params, feeds, key):
        from ..stability.guard import GUARD_VERDICT_VAR
        donated = dict(donated_params)
        const = dict(const_params)
        merged_upd = {}
        nf_acc = None
        fs_list = []
        rng = key
        valid = 0
        for _j in range(k):
            pair = jax.random.split(rng)
            step_key, rng_next = pair[0], pair[1]
            sub = {n: v[_j] for n, v in feeds.items()}
            f, upd, nf = fn(donated, const, sub, step_key)
            fs_list.append(f)
            if nf_acc is None or (isinstance(nf_acc, tuple)
                                  and not nf_acc):
                nf_acc = nf
            else:
                nf_acc = jax.tree_util.tree_map(jnp.logical_and,
                                                nf_acc, nf)
            merged_upd.update(upd)
            for n, v in upd.items():
                if n in donated:
                    donated[n] = v
                elif n in const:
                    const[n] = v
            rng = rng_next
            valid += 1
            verdict = upd.get(GUARD_VERDICT_VAR)
            if verdict is not None and int(np.asarray(verdict)) != 0:
                break
        # pad to K rows so the stacked fetch shape is stable; consumers
        # only read rows [:valid] (the host replays the rest)
        while len(fs_list) < k:
            fs_list.append(fs_list[-1])
        fetches = tuple(
            jnp.stack([fs_list[j][i] for j in range(k)])
            for i in range(len(fs_list[0])))
        ms_info = {"rng_state": rng,
                   "valid": jnp.asarray(valid, jnp.int32)}
        return fetches, merged_upd, nf_acc, ms_info

    return multi


def _kernel_scope(mesh):
    """Trace-time kernel-routing scope: a step XLA partitions over more
    than one device keeps every op on its lowered path (a Mosaic kernel
    cannot be partitioned automatically; kernels/registry.py)."""
    if mesh is None or getattr(mesh, "size", 1) <= 1:
        return contextlib.nullcontext()
    from ..kernels import registry as _kreg
    return _kreg.auto_partitioned()


def _activation_scope(mesh, strategy):
    """Trace-time activation-sharding scope (parallel/strategy.py):
    the tp-sharded matmul/attention lowerings in ops/ consult it while
    the step body traces. Only live for multi-axis (fsdp/tp) meshes or
    explicit activation rules, so the long-standing dp path traces
    byte-identically."""
    if mesh is None or strategy is None:
        return contextlib.nullcontext()
    rules = getattr(strategy, "activation_rules", None)
    multi = any(a in getattr(mesh, "shape", {}) for a in ("fsdp", "tp"))
    if not multi and (rules is None or len(rules) == 0):
        return contextlib.nullcontext()
    from ..parallel.strategy import activation_sharding_scope
    return activation_sharding_scope(mesh, strategy)


def trace_step(program, block_idx: int, feed_sig: Dict[str, Any],
               feed_lods: Dict[str, list], fetch_names: Sequence[str],
               scope: Scope, mesh=None, data_axis: str = "dp",
               strategy=None, iterations: int = 1,
               multi_step: int = 1) -> TracedStep:
    """:func:`_trace_step` under its set-up span (`trace_step`, with
    the child `trace_step.op_walk`: the abstract walk over every op's
    lowering that finds the updated persistables; the rest, building
    the jitted callable, is the parent's own time;
    observability/tracing.py): what a process spends tracing is kept
    whether or not telemetry is on."""
    with _obs_tracing.setup_span(
            "trace_step", program=program.fingerprint[0],
            ops=len(program.block(block_idx).ops)) as span:
        return _trace_step(program, block_idx, feed_sig, feed_lods,
                           fetch_names, scope, mesh, data_axis,
                           strategy, iterations, multi_step, span)


def _trace_step(program, block_idx, feed_sig, feed_lods, fetch_names,
                scope, mesh, data_axis, strategy, iterations,
                multi_step, span) -> TracedStep:
    """Build + jit the step function for one (program, feed-sig) pair.

    With `mesh`, the step is compiled SPMD: feeds sharded on their batch
    (leading) dim over `data_axis`, persistables replicated — XLA's
    partitioner inserts the gradient all-reduces over ICI. This one code
    path replaces the reference's ParallelExecutor graph-cloning +
    AllReduceOpHandle machinery (parallel_executor.cc:356-606,
    multi_devices_graph_pass.cc:454).

    With ``multi_step`` K > 1 (PT_MULTI_STEP, docs/ASYNC_DISPATCH.md)
    ``feed_sig`` describes K-stacked feed slabs (leading K axis) and the
    compiled step scans K DIFFERENT batches through one dispatched
    executable; the RNG state, guard/loss-scale state and integrity
    fingerprints ride the scan carry and a verdict-conditioned carry
    freeze breaks out early on anomaly."""
    block = program.block(block_idx)
    multi_step = int(multi_step or 1)
    if multi_step > 1:
        if iterations > 1:
            raise NotImplementedError(
                "PT_MULTI_STEP cannot combine with "
                "num_iteration_per_run > 1 — the multi-step scan "
                "already amortizes dispatch over K batches")
        if feed_lods:
            raise NotImplementedError(
                "PT_MULTI_STEP cannot scan over LoD (ragged) feeds; "
                "pad to dense first")
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            raise NotImplementedError(
                "PT_MULTI_STEP under a multi-device mesh is not "
                "supported yet: feed slabs carry a leading K axis the "
                "batch-dim shardings would mis-shard")
        sub_sig = {}
        for n, s in feed_sig.items():
            if not s.shape or int(s.shape[0]) != multi_step:
                raise EnforceNotMet(
                    f"multi-step feed {n!r} must be stacked with a "
                    f"leading K={multi_step} axis; got shape {s.shape}")
            sub_sig[n] = jax.ShapeDtypeStruct(tuple(s.shape[1:]),
                                              s.dtype)
        # everything below traces the PER-SUBSTEP body; only the final
        # jitted entry point sees the stacked slabs (as lax.scan xs)
        feed_sig = sub_sig
    persist_names = _collect_persistable_inputs(program, block, scope)
    # only those actually initialized in scope can be inputs; others must be
    # produced by the block itself (e.g. startup program initializers)
    avail = []
    for n in persist_names:
        v = scope.find_var(n)
        if v is not None and v.is_initialized():
            avail.append(n)
    missing = [n for n in persist_names
               if n not in avail and n not in feed_sig]
    produced = set()
    for op in block.ops:
        for slot in op.output_slots():
            produced.update(op.output(slot))
    really_missing = [n for n in missing if n not in produced]
    if really_missing:
        raise RuntimeError(
            f"persistable var(s) {really_missing} are used by the program "
            f"but not initialized in scope — run the startup program first")

    # every persistable name the block can write (covers startup programs
    # that CREATE params not yet present in the scope)
    persistable_all = set()
    for b in program.blocks:
        for name, v in b.vars.items():
            if v.persistable:
                persistable_all.add(name)

    # stability guard (docs/STABILITY.md): the verdict + update gate
    # compile INTO the step, its persistent state (EMA, loss scale)
    # joins the donated inputs, and its outputs ride the updated dict —
    # uniform across the whole-block, scheduler, islands and eager
    # paths, so the host controller always reads one scope var
    guard_plan = None
    if FLAGS.stability_guard:
        from ..stability import build_plan, ensure_state
        guard_plan = build_plan(program, block_idx)
        if guard_plan is not None:
            ensure_state(scope, guard_plan)
            for n in guard_plan.input_state_names():
                if n not in avail:
                    avail.append(n)
            persistable_all.update(guard_plan.state_var_names())

    # integrity sentinel (docs/RESILIENCE.md): per-bucket parameter
    # fingerprints + continuity checksums compile into the step the
    # same way; its accumulators ride the updated dict and the host
    # controller reads them every PT_INTEGRITY_EVERY steps
    integrity_plan = None
    if FLAGS.integrity_sentinel:
        from ..stability import integrity as _integrity
        integrity_plan = _integrity.build_plan(program, block_idx)
        if integrity_plan is not None:
            _integrity.ensure_state(scope, integrity_plan)
            for n in integrity_plan.input_state_names():
                if n not in avail:
                    avail.append(n)
            persistable_all.update(integrity_plan.state_var_names())

    fetch_lod_box: Dict[str, list] = {}
    updated_box: List[str] = []
    uses_rng_box = [False]

    class _Rng(_RngCtx):
        def step_key(self):
            uses_rng_box[0] = True
            return super().step_key()

    amp_cfg = getattr(program, "_amp", None)
    accum_k = int(getattr(program, "_gradient_accumulation_steps", 1)
                  or 1)
    accum_slices = None
    if accum_k > 1 and feed_lods:
        # Ragged feeds split on SEQUENCE boundaries: LoD offsets are
        # host metadata, static per trace, so each micro-batch slice is
        # a static row range with rebased offsets (lifts the r2
        # restriction; reference ir/multi_batch_merge_pass.cc has no
        # LoD restriction either).
        accum_slices = _lod_accum_slices(feed_sig, feed_lods, accum_k)
    elif accum_k > 1:
        batch_dims = {n: (s.shape[0] if s.shape else None)
                      for n, s in feed_sig.items()}
        sizes = set(batch_dims.values())
        if len(sizes) != 1 or None in sizes:
            raise EnforceNotMet(
                f"gradient_accumulation_steps={accum_k} requires every "
                f"feed to share one leading batch dim; got {batch_dims}")
        (b,) = sizes
        if b % accum_k != 0:
            raise EnforceNotMet(
                f"batch size {b} is not divisible by "
                f"gradient_accumulation_steps={accum_k}")

    # comm scheduler: fused-bucket collective points interleaved into
    # the traced backward (parallel/comm_scheduler.py). Only built for
    # multi-device meshes; programs with explicit collective ops manage
    # their own comm and get static counter stats instead.
    comm_sched = None
    comm_stats = None
    if mesh is not None and getattr(mesh, "size", 1) > 1:
        from ..parallel.comm_scheduler import (
            CommScheduler, static_collective_stats)
        comm_sched = CommScheduler.for_program(
            program, block_idx, mesh, data_axis, strategy)
        comm_stats = comm_sched.stats if comm_sched is not None \
            else static_collective_stats(program, block_idx)
    comm_points = comm_sched.comm_points() \
        if comm_sched is not None and accum_k == 1 else None

    def _run_whole(env, rng_ctx, lod_env):
        def block_runner(idx, sub_env=None):
            run_block_ops(program.block(idx),
                          sub_env if sub_env is not None else env,
                          rng_ctx, lod_env, block_runner)
            return sub_env if sub_env is not None else env

        if amp_cfg:
            from .amp import amp_guard
            with amp_guard(True, amp_cfg.get("dtype", jnp.bfloat16),
                           amp_cfg.get("black_ops", ()),
                           amp_cfg.get("white_ops", ())):
                run_block_ops(block, env, rng_ctx, lod_env,
                              block_runner, comm_points=comm_points)
        else:
            run_block_ops(block, env, rng_ctx, lod_env, block_runner,
                          comm_points=comm_points)
        return env

    def _run_accumulated(params, feeds, key):
        """multi_batch_merge parity (reference ir/multi_batch_merge_
        pass.cc:72), TPU-native: re-trace the compute phase per feed
        slice, average the grads the optimize phase consumes, run the
        optimize phase once. Mean-of-slice-grads == full-batch grad for
        mean losses, so the parameter trajectory matches big-batch."""
        from .selected_rows import SelectedRows, is_selected_rows
        compute_ops = [op for op in block.ops
                       if op.attr("op_role", "forward") != "optimize"]
        opt_ops = [op for op in block.ops
                   if op.attr("op_role", "forward") == "optimize"]
        grad_names = sorted({
            n for op in opt_ops for slot in op.input_slots()
            for n in op.input(slot) if n.endswith("@GRAD")})
        g_acc = {}
        env = None
        for i in range(accum_k):
            env = _TrackingDict()
            env.update(params)
            lod_env_i = {}
            if accum_slices is not None:
                for n, arr in feeds.items():
                    r0, r1, sliced = accum_slices[i][n]
                    env[n] = arr[r0:r1]
                    if sliced:
                        lod_env_i[n] = [list(l) for l in sliced]
            else:
                for n, arr in feeds.items():
                    sz = arr.shape[0] // accum_k  # validated above
                    env[n] = arr[i * sz:(i + 1) * sz]
            rng_ctx = _Rng(jax.random.fold_in(key, i))

            def block_runner(idx, sub_env=None):
                run_block_ops(program.block(idx),
                              sub_env if sub_env is not None else env,
                              rng_ctx, lod_env_i, block_runner)
                return sub_env if sub_env is not None else env

            run_block_ops(block, env, rng_ctx, lod_env_i, block_runner,
                          ops=compute_ops)
            for n in grad_names:
                g = env.get(n)
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
        inv = 1.0 / accum_k
        for n, g in g_acc.items():
            env[n] = g.map_values(lambda v: (v * inv).astype(v.dtype)) \
                if is_selected_rows(g) else g * inv
        if comm_sched is not None:
            # one fused collective point on the averaged grads (the
            # per-op interleave cannot span the re-traced slices)
            comm_sched.apply_all(env)
        rng_ctx = _Rng(key)

        def block_runner2(idx, sub_env=None):
            run_block_ops(program.block(idx),
                          sub_env if sub_env is not None else env,
                          rng_ctx, {}, block_runner2)
            return sub_env if sub_env is not None else env

        run_block_ops(block, env, rng_ctx, {}, block_runner2,
                      ops=opt_ops)
        return env

    check_nan = bool(FLAGS.check_nan_inf)
    nan_labels_box: List[Tuple[str, str]] = []

    def _step_body(params, feeds, key):
        lod_env = {k: [list(l) for l in v] for k, v in feed_lods.items()}
        rng_ctx = _Rng(key)
        if check_nan:
            _nan_check_ctx.items = []
        try:
            if accum_k > 1:
                env = _run_accumulated(params, feeds, key)
            else:
                env = _TrackingDict()
                env.update(params)
                env.update(feeds)
                env = _run_whole(env, rng_ctx, lod_env)
        finally:
            checks = getattr(_nan_check_ctx, "items", None)
            _nan_check_ctx.items = None
        nan_flags = ()
        if check_nan and checks:
            nan_labels_box.clear()
            nan_labels_box.extend((t, n) for t, n, _ in checks)
            nan_flags = jnp.stack([f for _, _, f in checks])

        if guard_plan is not None:
            from ..stability.guard import apply_in_trace
            apply_in_trace(env, params, guard_plan, fetch_names,
                           persistable_all)
        if integrity_plan is not None:
            # AFTER the guard: the post fingerprint must cover the
            # gated values that actually reach the scope
            from ..stability.integrity import \
                apply_in_trace as _integrity_in_trace
            _integrity_in_trace(env, params, integrity_plan)
        updated = sorted(n for n in env.written if n in persistable_all)
        updated_box.clear()
        updated_box.extend(updated)
        for n in fetch_names:
            if n in lod_env:
                fetch_lod_box[n] = lod_env[n]
        fetches = []
        for n in fetch_names:
            if n not in env:
                raise KeyError(
                    f"fetch target {n!r} was not produced by the program")
            fetches.append(env[n])
        return tuple(fetches), {n: env[n] for n in updated}, nan_flags

    def step(params, feeds, key):
        # the activation scope must be LIVE while the body traces (the
        # ops/ lowerings consult it at lowering time, which happens on
        # the jitted function's first dispatch) — so it enters inside
        # the traced function, not around the jit call
        with _kernel_scope(mesh), _activation_scope(mesh, strategy):
            return _step_body(params, feeds, key)

    # --- phase 1: abstract trace to discover updated persistables ---------
    params_sig = {}
    opaque_state = False
    for n in avail:
        val = scope.find_var(n).get_value()
        arr = val.array if isinstance(val, LoDTensor) else val
        try:
            params_sig[n] = jax.ShapeDtypeStruct(jnp.shape(arr),
                                                 jnp.result_type(arr))
        except (TypeError, ValueError):
            # host-state object persistable (e.g. the DetectionMAP
            # evaluator's accumulation state): not jittable by
            # definition — run the whole block eagerly
            opaque_state = True
            break
    key_sig = jax.ShapeDtypeStruct((2,), jnp.uint32)
    try:
        if opaque_state:
            raise NotImplementedError(
                f"persistable {n!r} holds a host-side state object")
        with _obs_tracing.setup_span("trace_step.op_walk", parent=span):
            jax.eval_shape(step, params_sig, feed_sig, key_sig)
    except (NotImplementedError, jax.errors.JAXTypeError) as reason:
        # Block contains value-dependent-shape ops (edit_distance,
        # sequence_erase, save, ...) or host-state persistables: compile
        # maximal static segments as XLA islands and interpret only the
        # dynamic ops on host — the TPU-native analog of the reference's
        # per-op CPU dispatch (operator.cc:884-940). With gradient
        # accumulation the step re-slices feeds inside one trace, which
        # the island partitioner cannot split; that combination keeps
        # the whole-program eager interpreter.
        if accum_k > 1:
            import warnings as _warnings
            _warnings.warn(
                f"program falls back to the EAGER interpreter (no XLA "
                f"step compilation): {reason}; gradient accumulation "
                f"prevents island partitioning. Expect per-step Python "
                f"overhead.", stacklevel=2)

            def eager_fn(donated_params, const_params, feeds, key):
                params = dict(const_params)
                params.update(donated_params)
                return step(params, feeds, key)

            ts = TracedStep(_multi_loop_fallback(eager_fn, multi_step)
                            if multi_step > 1 else _split_first(
                                _loop_fallback(eager_fn, iterations)),
                            [], avail, sorted(feed_sig),
                            list(fetch_names), [], fetch_lod_box,
                            True, nan_check_labels=nan_labels_box)
            ts.guard_plan = guard_plan  # guard ran inside step()
            ts.integrity_plan = integrity_plan  # ditto (eager step())
            ts.multi_step = multi_step
            return ts

        from .islands import IslandRunner
        opaque_names = set()
        if opaque_state:
            for pn in avail:
                val = scope.find_var(pn).get_value()
                arr = val.array if isinstance(val, LoDTensor) else val
                try:
                    jax.ShapeDtypeStruct(jnp.shape(arr),
                                         jnp.result_type(arr))
                except (TypeError, ValueError):
                    opaque_names.add(pn)
        first_idx = getattr(reason, "_island_op_index", None)
        runner = IslandRunner(
            program, block, fetch_names, persistable_all, feed_lods,
            amp_cfg, check_nan, nan_labels_box, fetch_lod_box,
            first_dynamic_idx=first_idx)
        for idx, op in enumerate(runner.ops):
            if opaque_names and (
                    opaque_names & set(runner._op_reads(op)) or
                    opaque_names & set(runner._op_writes(op))):
                runner.dynamic_idx.add(idx)

        def islands_fn(donated_params, const_params, feeds, key):
            params = dict(const_params)
            params.update(donated_params)
            fetches, updated, nan_flags = runner.step(params, feeds,
                                                      key)
            if guard_plan is not None:
                # islands ran outside one trace: guard from the step's
                # outputs (grads consumed inside a compiled segment
                # degrade the spike detector, never the finite check
                # on the loss)
                from ..stability.guard import apply_post
                fetches, updated = apply_post(
                    guard_plan, fetches, updated, params, fetch_names)
            return fetches, updated, nan_flags

        ts = TracedStep(_multi_loop_fallback(islands_fn, multi_step)
                        if multi_step > 1 else _split_first(
                            _loop_fallback(islands_fn, iterations)),
                        [], avail, sorted(feed_sig),
                        list(fetch_names), [], fetch_lod_box, True,
                        nan_check_labels=nan_labels_box)
        ts.guard_plan = guard_plan
        ts.multi_step = multi_step
        if integrity_plan is not None:
            import warnings as _warnings
            _warnings.warn(
                "integrity sentinel is unavailable on the island-"
                "partitioned path (the fingerprint cannot span host-"
                "interpreted ops); sentinel disabled for this program",
                stacklevel=2)
        ts.integrity_plan = None
        return ts
    updated_names = list(updated_box)
    from .scheduler import scheduler_gate
    if scheduler_gate(program, block_idx, fetch_names, mesh=mesh,
                      iterations=iterations, feed_lods=feed_lods,
                      integrity_plan=integrity_plan,
                      multi_step=multi_step)[0]:
        # programmable operator scheduler (core/scheduler.py,
        # docs/SCHEDULING.md): data-independent islands dispatched on
        # concurrent lanes (accum_k == 1) or a pipelined micro-batch
        # grad-accumulation loop (accum_k > 1). The gate predicate is
        # shared with the conformance verifier
        # (analysis/conformance.py) so the static claim about when
        # islands apply cannot drift from this call site. Returns None
        # when the block is not schedulable (sub-blocks, single
        # island, opaque state) — the whole-block jit below stays the
        # fallback.
        from .scheduler import build_scheduled_step
        ts = build_scheduled_step(
            program, block, params_sig, feed_sig, fetch_names, avail,
            updated_names, amp_cfg, accum_k, check_nan, fetch_lod_box,
            uses_rng=uses_rng_box[0], guard_plan=guard_plan)
        if ts is not None:
            ts.comm_stats = comm_stats
            ts.guard_plan = guard_plan
            ts.integrity_plan = None  # scheduler path: sentinel off
            return ts
    donated = [n for n in avail if n in updated_names]
    const = [n for n in avail if n not in updated_names]

    # --- phase 2: jit with donation of updated persistables ---------------
    def step1(donated_params, const_params, feeds, key):
        params = dict(const_params)
        params.update(donated_params)
        return step(params, feeds, key)

    if iterations > 1:
        # ExecutionStrategy.num_iteration_per_run, TPU-native: K chained
        # steps compile into ONE executable (lax.scan over the donated
        # state), amortizing the per-dispatch host cost — the
        # reference's knob exists for exactly this amortization in its
        # threaded executor. Fetches come from the LAST iteration.
        donated_set = set(donated)

        def step2(donated_params, const_params, feeds, key):
            def body(carry, i):
                f, upd, nf = step1(carry, const_params, feeds,
                                   jax.random.fold_in(key, i))
                carry2 = {n: upd.get(n, carry[n]) for n in carry}
                extra = {n: v for n, v in upd.items()
                         if n not in donated_set}
                return carry2, (f, extra, nf)

            carry, (fs, extras, nfs) = jax.lax.scan(
                body, dict(donated_params),
                jnp.arange(iterations))
            fetches = tuple(jax.tree_util.tree_map(lambda x: x[-1], f)
                            for f in fs)
            upd_out = {n: carry[n] for n in updated_names
                       if n in carry}
            upd_out.update({n: v[-1] for n, v in extras.items()})
            # AND the all-finite flags over the scan axis: a transient
            # NaN/Inf in iterations 0..K-2 must trip check_nan_inf too
            nan_flags = jax.tree_util.tree_map(
                lambda x: jnp.all(x, axis=0), nfs)
            return fetches, upd_out, nan_flags
    elif multi_step > 1:
        # PT_MULTI_STEP (docs/ASYNC_DISPATCH.md): K DIFFERENT batches —
        # stacked on a leading K axis — scan through ONE dispatched
        # executable, amortizing the per-step host dispatch cost the
        # bench measures at ~3x the device time. Three invariants:
        #   1. Bit-identity: the RNG state rides the carry and splits
        #      per substep exactly like K sequential dispatches
        #      (_split_first), and guard EMA /
        #      loss scale / integrity fingerprints chain through the
        #      donated carry just as they chain through the scope — so
        #      anomaly-free trajectories match K=1 bit-for-bit.
        #   2. Early break-out: a nonzero guard verdict at substep j
        #      freezes the carry (params, RNG) for substeps > j — the
        #      gate already kept the pre-anomaly params at substep j
        #      itself, so the slab lands on the pre-anomaly step and the
        #      host replays the unconsumed batches after running policy.
        #   3. Frozen substeps still execute (a scan body cannot
        #      shrink) but every output is discarded: fetches/extras
        #      index the last VALID substep and frozen nan flags are
        #      masked so garbage compute cannot trip check_nan_inf.
        donated_set = set(donated)
        has_guard = guard_plan is not None
        if has_guard:
            from ..stability.guard import GUARD_VERDICT_VAR as _verd

        def step2(donated_params, const_params, feeds, key):
            # `key` here is the RAW rng STATE, not a step key: the
            # per-substep split happens inside the carry
            def body(carry, sub_feeds):
                state, rng, halted = carry
                pair = jax.random.split(rng)
                step_key, rng_next = pair[0], pair[1]
                f, upd, nf = step1(state, const_params, sub_feeds,
                                   step_key)
                new_state = {n: upd.get(n, state[n]) for n in state}
                if has_guard:
                    frozen = {n: jnp.where(halted, state[n],
                                           new_state[n])
                              for n in state}
                    rng2 = jnp.where(halted, rng, rng_next)
                    trip = jnp.any(upd[_verd] != 0) \
                        if _verd in upd else jnp.zeros((), dtype=bool)
                    halted2 = jnp.logical_or(halted, trip)
                    nf2 = jax.tree_util.tree_map(
                        lambda x: jnp.logical_or(x, halted), nf)
                else:
                    frozen, rng2, halted2, nf2 = (new_state, rng_next,
                                                  halted, nf)
                extra = {n: v for n, v in upd.items()
                         if n not in donated_set}
                return (frozen, rng2, halted2), (f, extra, nf2, halted)

            halted0 = jnp.zeros((), dtype=bool)
            (carry, rng_out, _h), (fs, extras, nfs, halted_before) = \
                jax.lax.scan(body, (dict(donated_params), key, halted0),
                             feeds)
            valid = jnp.sum(
                jnp.logical_not(halted_before)).astype(jnp.int32)
            last_valid = valid - 1
            upd_out = {n: carry[n] for n in updated_names
                       if n in carry}
            upd_out.update({
                n: jax.lax.dynamic_index_in_dim(
                    v, last_valid, axis=0, keepdims=False)
                for n, v in extras.items()})
            nan_flags = jax.tree_util.tree_map(
                lambda x: jnp.all(x, axis=0), nfs)
            ms_info = {"rng_state": rng_out, "valid": valid}
            # fetches stay stacked (K, ...): the dispatch slices per
            # substep lazily so losses materialize without a sync
            return tuple(fs), upd_out, nan_flags, ms_info
    else:
        step2 = step1
    if multi_step == 1:
        step2 = _split_first(step2)

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        repl = NamedSharding(mesh, P())
        dp_size = mesh.shape.get(data_axis, mesh.size) \
            if hasattr(mesh.shape, "get") else mesh.size
        batch = NamedSharding(mesh, P(data_axis))

        shard_update = bool(FLAGS.sharded_weight_update)

        def param_sh(n):
            shape = params_sig[n].shape if n in params_sig else ()
            if strategy is not None:
                spec = strategy.param_spec(n, shape)
                if spec is not None:
                    return NamedSharding(mesh, spec)
            if shard_update:
                # cross-replica sharded weight update (arXiv:
                # 2004.13336): optimizer state shards dim 0 over dp,
                # the partitioner computes each update on the shard
                # that owns it (reduce-scatter + local update +
                # all-gather)
                from ..parallel.comm_scheduler import \
                    sharded_update_spec
                spec = sharded_update_spec(n, shape, mesh, data_axis)
                if spec is not None and tuple(spec):
                    return NamedSharding(mesh, spec)
            return repl

        def feed_sh(n):
            if strategy is not None:
                spec = strategy.feed_spec(n, feed_sig[n].shape)
                if spec is not None:
                    return NamedSharding(mesh, spec)
            if (len(feed_sig[n].shape) >= 1 and
                    feed_sig[n].shape[0] % dp_size == 0):
                return batch
            return repl

        in_shardings = ({n: param_sh(n) for n in donated},
                        {n: param_sh(n) for n in const},
                        {n: feed_sh(n) for n in feed_sig},
                        repl)
        # fetches replicated; updated persistables keep their sharding
        out_shardings = (tuple(repl for _ in fetch_names),
                         {n: param_sh(n) for n in updated_names},
                         repl, repl)
        fn = jax.jit(step2, donate_argnums=(0,),
                     in_shardings=in_shardings,
                     out_shardings=out_shardings,
                     compiler_options=_compiler_options())
    else:
        fn = jax.jit(step2, donate_argnums=(0,),
                     compiler_options=_compiler_options())
    ts = TracedStep(fn, donated, const, sorted(feed_sig),
                    list(fetch_names), updated_names,
                    fetch_lod_box, uses_rng_box[0],
                    nan_check_labels=nan_labels_box)
    ts.comm_stats = comm_stats
    ts.guard_plan = guard_plan
    ts.integrity_plan = integrity_plan
    ts.multi_step = multi_step
    return ts


def _on_device(arr, dev) -> bool:
    """True when `arr` is a jax.Array already resident on exactly `dev`
    — the case where a `device_put` would be a pure no-op transfer call
    (the per-step tax the sync hot loop used to pay every run)."""
    if not isinstance(arr, jax.Array):
        return False
    try:
        return arr.devices() == {dev}
    except Exception:
        return False


class _FastPathEntry:
    """Steady-state dispatch record for one (program, feed-sig, fetch)
    tuple: everything `run()` needs to skip signature reconstruction,
    scope-persistable re-walking, and redundant `device_put`s after the
    first run. Variable objects are cached by REFERENCE (valid while the
    entry's scope is live and not erased underneath it; an entry is only
    consulted when `entry.scope is scope`)."""

    __slots__ = ("scope", "place", "dev", "feed_names", "shapes",
                 "dtypes", "lods", "traced", "donated_vars",
                 "const_vars", "updated_vars", "sig_hash")

    def __init__(self, scope, place, dev, arrays, lods, traced):
        self.scope = scope
        self.place = place
        self.dev = dev
        self.feed_names = tuple(sorted(arrays))
        self.shapes = {n: tuple(a.shape) for n, a in arrays.items()}
        self.dtypes = {n: str(a.dtype) for n, a in arrays.items()}
        self.lods = {n: [list(level) for level in lod]
                     for n, lod in lods.items()}
        self.traced = traced
        self.donated_vars = scope.var_refs(traced.donated_names)
        self.const_vars = scope.var_refs(traced.const_names)
        # filled lazily by the writeback (eager fallbacks only discover
        # their updated set while running)
        self.updated_vars: Dict[str, Any] = {}
        # short feed-sig identifier for flight-recorder step records
        self.sig_hash: Optional[str] = None


def _sig_hash(sig):
    """Short feed-signature identifier of a flight record."""
    return sig if isinstance(sig, str) \
        else f"{hash(sig) & 0xffffffff:08x}"


class _SlowSteps:
    """An engine's running view of its own step times, from the
    always-on StepClock: a step is slow when it takes more than
    `FACTOR` times the median of the last `WINDOW` steps, judged only
    once `MIN_HISTORY` exist (so a first, compiling step never is).
    The median is refreshed every `REFRESH` steps, not every step; the
    interpreter's collection counts are read at each refresh and at
    each slow step, so a slow step's `gc` delta covers the steps since
    the last of those (at most `REFRESH`)."""

    WINDOW, MIN_HISTORY, REFRESH, FACTOR = 64, 16, 16, 3.0

    __slots__ = ("_ns", "_i", "median_ns", "_limit_ns", "_gc", "_gc_i")

    def __init__(self):
        self._ns: List[int] = []
        self._i = 0
        self.median_ns = 0
        self._limit_ns = None
        self._gc = None
        self._gc_i = 0

    def is_slow(self, total_ns) -> bool:
        limit = self._limit_ns
        slow = limit is not None and total_ns > limit
        ns = self._ns
        if len(ns) < self.WINDOW:
            ns.append(total_ns)
        else:
            ns[self._i % self.WINDOW] = total_ns
        self._i += 1
        if self._i % self.REFRESH == 0 and self._i >= self.MIN_HISTORY:
            self.median_ns = sorted(ns)[len(ns) // 2]
            self._limit_ns = self.FACTOR * self.median_ns
            if not slow:
                self._read_gc()
        return slow

    def _read_gc(self):
        seen = [g["collections"] for g in gc.get_stats()]
        before, self._gc = self._gc, seen
        steps, self._gc_i = self._i - self._gc_i, self._i
        return before, seen, steps

    def gc_delta(self):
        """Collections of each generation since the last reading."""
        before, seen, steps = self._read_gc()
        return {"collections": [b - a for a, b in zip(before, seen)],
                "over_steps": steps}


# deferred-check records kept in flight before the oldest is forced to
# materialize — the pipeline-depth backstop that keeps an un-materialized
# async training loop from accumulating unchecked device flags forever
_MAX_PENDING_STEPS = 8
# fast-path entries kept per (program, fetch, iterations) key — one per
# live feed signature (a loop typically alternates train + eval tail)
_MAX_FAST_ENTRIES = 4


class Engine:
    """Compile cache + step dispatch for one (program, scope) pair."""

    def __init__(self, mesh=None, data_axis: str = "dp", strategy=None,
                 replicated_feeds=()):
        if strategy is not None and mesh is None:
            mesh = strategy.mesh
            data_axis = strategy.data_axis
        self.strategy = strategy
        self._cache: Dict[Any, TracedStep] = {}
        self._fast: Dict[Any, _FastPathEntry] = {}
        self._pending: List[Any] = []
        self._last_updated = ()
        self._census_feed = None  # owner "feed" in the memory census
        self._multihost_cached: Optional[bool] = None
        self.mesh = mesh
        self.data_axis = data_axis
        # dispatch instrumentation (asserted by tests/test_async_dispatch
        # .py: steady state must show zero new traces / sig builds /
        # device_puts)
        # ckpt_saves / ckpt_inflight are maintained by CheckpointManager
        # instances constructed with engine=<this engine>: inflight
        # returns to 0 once every queued async save is durable
        # (docs/CHECKPOINTING.md)
        # collective_* / grad_collectives_per_step / comm_overlap_frac
        # are maintained from TracedStep.comm_stats (the comm
        # scheduler's bucket plan or a transpiled block's static
        # collective census): cumulative bytes/buckets/quantized plus
        # two per-step gauges — fused gradient collectives issued per
        # step and the fraction that can overlap remaining backward
        # (docs/COLLECTIVES.md)
        # EngineCounters: still a plain dict to every reader, plus
        # snapshot()/reset() and scrape-time export through the
        # observability registry (docs/OBSERVABILITY.md)
        self.counters: Dict[str, int] = _obs.EngineCounters({
            "runs": 0, "fast_path_hits": 0, "traces": 0,
            "sig_builds": 0, "device_puts": 0,
            "ckpt_saves": 0, "ckpt_inflight": 0,
            "collective_bytes": 0, "collective_buckets": 0,
            "collective_quantized": 0, "grad_collectives_per_step": 0,
            "comm_overlap_frac": 0.0,
            # op scheduler (core/scheduler.py, docs/SCHEDULING.md):
            # steps through a scheduled TracedStep, max same-phase
            # island width, grad-accum pipeline host duty cycle, and
            # cumulative same-phase lane idle time
            "scheduled_steps": 0, "islands_concurrent": 0,
            "pipeline_fill_frac": 0.0, "lane_idle_ms": 0.0,
            # stability guard (paddle_tpu/stability,
            # docs/STABILITY.md): anomaly verdicts handled, ghost
            # snapshots captured + capture time, rollbacks performed,
            # re-executed steps that tripped again, quantized-allreduce
            # exact-bucket fallbacks, repro bundles written, host-side
            # controller time
            "anomalies": 0, "ghost_snapshots": 0, "ghost_ms": 0.0,
            "rollbacks": 0, "rollback_reexec_failures": 0,
            "quant_fallbacks": 0, "replay_bundles": 0,
            "guard_aborts": 0,
            "guard_overhead_ms": 0.0,
            # integrity sentinel (FLAGS_integrity_sentinel,
            # paddle_tpu/stability/integrity.py,
            # docs/RESILIENCE.md): verification windows completed,
            # corrupt windows detected, ghost rollbacks, aborts, and
            # host-side controller time on window steps
            "integrity_checks": 0, "integrity_mismatches": 0,
            "integrity_rollbacks": 0, "integrity_aborts": 0,
            "integrity_overhead_ms": 0.0,
            # feedback-directed autotuner (FLAGS_autotune,
            # paddle_tpu/tuning, docs/TUNING.md): searches run, trials
            # measured, winners replayed from the on-disk cache
            "tuning_searches": 0, "tuning_trials": 0,
            "tuning_cache_hits": 0,
            # automatic SPMD placement (PT_PLACEMENT_AUTO,
            # analysis/placement.py, docs/PARALLELISM.md): cost-model
            # searches run vs plans replayed from the tuning cache
            "placement_searches": 0, "placement_cache_hits": 0,
            # multi-step scan driver (PT_MULTI_STEP,
            # docs/ASYNC_DISPATCH.md): slab dispatches, substeps that
            # actually executed, slabs that broke out early on a guard
            # verdict, and frozen substeps replayed sequentially
            "multistep_dispatches": 0, "multistep_substeps": 0,
            "multistep_early_exits": 0, "multistep_replays": 0})
        _obs.register_engine(self)
        # lazily built per-engine stability controller
        # (FLAGS_stability_guard; paddle_tpu/stability/guard.py)
        self._stability = None
        # lazily built per-engine integrity sentinel controller
        # (FLAGS_integrity_sentinel; paddle_tpu/stability/integrity.py)
        self._integrity = None
        # program fingerprints already autotuned this process
        # (FLAGS_autotune; paddle_tpu/tuning/driver.py)
        self._tuned = set()
        # automatic placement runs once per engine (PT_PLACEMENT_AUTO;
        # analysis/placement.py) and only when the caller passed no
        # mesh/strategy of their own
        self._placed = False
        # feed names that are identical on every process under multihost
        # SPMD (shared tables, per-step constants) — globalized by
        # replication instead of batch-dim concatenation
        self.replicated_feeds = set(replicated_feeds)
        # lazily built when FLAGS.step_timeout_s > 0 (docs/RESILIENCE.md)
        self._watchdog = None
        # last multi-step dispatch record ({"k", "valid"}) + the
        # per-substep fetch rows of the last multi-step run()
        # (docs/ASYNC_DISPATCH.md "Multi-step dispatch")
        self._last_multi = None
        self.last_multi_fetches = None
        # step totals off the always-on StepClock: a slow step reaches
        # the flight recorder without the telemetry switch
        self._slow = _SlowSteps()

    def _step_watchdog(self):
        """The armed-per-dispatch hang detector (FLAGS_step_timeout_s);
        None while the flag is off. Rebuilt if the timeout changes."""
        t = float(FLAGS.step_timeout_s or 0)
        if t <= 0:
            return None
        if self._watchdog is None or self._watchdog.timeout_s != t:
            from ..distributed.resilience import StepWatchdog
            self._watchdog = StepWatchdog(
                t, context_fn=self._watchdog_context)
        return self._watchdog

    def _watchdog_context(self) -> str:
        """Diagnosis attached to a watchdog trip: what the async
        dispatch layer still has in flight when the step hung."""
        pending = list(self._pending)
        parts = [f"{len(pending)} pending async step(s)",
                 f"{self.counters['runs']} run(s) dispatched"]
        for rec in pending[-3:]:
            parts.append(f"pending program {rec._fingerprint}")
        return "; ".join(parts)

    def _normalize_feed(self, feed: Optional[Dict[str, Any]], place):
        self.counters["sig_builds"] += 1
        arrays, lods, sig = {}, {}, []
        dev = place.jax_device() if place is not None else None
        for name in sorted(feed or {}):
            val = feed[name]
            if isinstance(val, LoDTensor):
                lod = val.lod()
                arr = val.array
                if lod:
                    lods[name] = lod
            else:
                arr = val
            if not isinstance(arr, jax.Array):
                self.counters["device_puts"] += 1
                arr = np.asarray(arr)
                arr = jax.device_put(arr, dev) if dev is not None \
                    else jnp.asarray(arr)
            elif dev is not None and not _on_device(arr, dev):
                self.counters["device_puts"] += 1
                arr = jax.device_put(arr, dev)
            arrays[name] = arr
            sig.append((name, tuple(arr.shape), str(arr.dtype),
                        tuple(map(tuple, lods.get(name, [])))))
        return arrays, lods, tuple(sig)

    def _is_multihost(self):
        if self.mesh is None:
            return False
        if self._multihost_cached is None:
            procs = {d.process_index for d in self.mesh.devices.flat}
            self._multihost_cached = procs != {jax.process_index()}
        return self._multihost_cached

    def _globalize(self, arrays):
        """Multi-host SPMD (reference multi-trainer NCCL mode): each
        process feeds its LOCAL batch shard; assemble global arrays
        over the cross-process mesh so the one jitted step runs SPMD
        with XLA collectives over the wire. Feeds named in
        `replicated_feeds` (and scalars) are identical across processes
        and globalized by replication, not batch concatenation."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        batch = NamedSharding(self.mesh, P(self.data_axis))
        repl = NamedSharding(self.mesh, P())
        out = {}
        for n, a in arrays.items():
            if a.ndim >= 1 and n not in self.replicated_feeds:
                out[n] = jax.make_array_from_process_local_data(
                    batch, np.asarray(a), self._global_shape(n, a))
            else:
                out[n] = jax.make_array_from_process_local_data(
                    repl, np.asarray(a), tuple(a.shape))
        return out

    def _global_shape(self, name, a):
        if a.ndim >= 1 and name not in self.replicated_feeds:
            return ((a.shape[0] * jax.process_count(),)
                    + tuple(a.shape[1:]))
        return tuple(a.shape)

    @staticmethod
    def _verify_uniform_lods(lods):
        """Every process must hold identical feed offsets: allgather a
        cheap fingerprint and compare (a mismatch would otherwise
        desynchronize program caches and hang the cluster)."""
        import hashlib
        from jax.experimental import multihost_utils
        blob = repr(sorted((n, tuple(map(tuple, l)))
                           for n, l in lods.items())).encode()
        h = np.frombuffer(hashlib.sha256(blob).digest()[:8],
                          np.uint64).astype(np.float64)
        gathered = np.asarray(
            multihost_utils.process_allgather(h))
        if not (gathered == gathered[0]).all():
            raise EnforceNotMet(
                "multihost ragged feeds require every process to feed "
                "the SAME LoD signature (use length bucketing); "
                "fingerprints differ across processes")

    @staticmethod
    def _replicate_lod(lod):
        """Global offsets of nproc same-signature ragged shards
        concatenated on the row dim: each level is the per-process
        offsets repeated with a cumulative shift (the next level's
        entry count per process)."""
        nproc = jax.process_count()
        out = []
        for level in lod:
            level = [int(x) for x in level]
            span = level[-1]
            g = [0]
            for p in range(nproc):
                g.extend(x + p * span for x in level[1:])
            out.append(g)
        return out

    def _global_sig_key(self, arrays, lods):
        return tuple(
            (n, self._global_shape(n, arrays[n]),
             str(arrays[n].dtype),
             tuple(map(tuple, lods.get(n, []))))
            for n in sorted(arrays))

    def _globalize_replicated(self, params):
        from jax.sharding import NamedSharding, PartitionSpec as P
        repl = NamedSharding(self.mesh, P())
        return {n: jax.make_array_from_process_local_data(
                    repl, np.asarray(a), tuple(np.asarray(a).shape))
                for n, a in params.items()}

    @staticmethod
    def _tuning_key_items():
        """Trace-affecting inputs BOTH cache keys must carry beyond the
        long-standing flag set: the applied-tuning token (an applied
        config changes flag/env values the trace read — the token makes
        pre/post-apply traces distinct even if a knob round-trips), and
        the env knobs the key audit found missing (the scheduler lane
        cap shapes the island partition; compiler options and recompute
        types are baked into the compiled step). The audit test in
        tests/test_tuning.py asserts every trace-affecting knob in the
        tuning catalog moves both keys."""
        from ..tuning import state as _tuning_state
        return (_tuning_state.applied_token(),
                os.environ.get("PT_SCHED_LANES", ""),
                os.environ.get("PT_COMPILER_OPTIONS", ""),
                os.environ.get("PT_RECOMPUTE", ""),
                # flash-attention A/B dispatch overrides pick the kernel
                # at trace time (tools/lint_flags.py found these unkeyed)
                os.environ.get("PT_FORCE_KERNEL", ""),
                os.environ.get("PT_FORCE_COMPOSED", ""),
                # multi-axis SPMD placement (analysis/placement.py):
                # the chosen mesh layout changes the traced shardings,
                # and the pins/budget steer which layout is chosen
                os.environ.get("PT_PLACEMENT_AUTO", ""),
                os.environ.get("PT_PLACEMENT_BUDGET", ""),
                os.environ.get("PT_MESH_AXES", ""),
                os.environ.get("PT_MESH_FSDP", ""),
                os.environ.get("PT_MESH_TP", ""),
                os.environ.get("PT_MESH_PP", ""),
                os.environ.get("PT_PIPELINE_MICRO", ""),
                # multi-step scan driver (docs/ASYNC_DISPATCH.md): K is
                # also an explicit key component where the slab arrives,
                # but the env knob arms the prefetcher's slab mode, so a
                # flip must invalidate steady-state entries too
                os.environ.get("PT_MULTI_STEP", ""))

    @staticmethod
    def _cache_key(program, block_idx, feed_sig_key, fetch_names,
                   iterations=1, multi_step=1):
        return (program.fingerprint, block_idx, feed_sig_key,
                tuple(fetch_names), bool(FLAGS.check_nan_inf),
                int(getattr(program, "_gradient_accumulation_steps", 1)
                    or 1), int(iterations), int(multi_step),
                float(FLAGS.allreduce_bucket_mb),
                str(FLAGS.quantized_allreduce),
                bool(FLAGS.sharded_weight_update),
                bool(FLAGS.op_scheduler),
                bool(FLAGS.stability_guard),
                # the sentinel's fingerprint + shadow checksums are
                # compiled into the step (bucket layout follows
                # allreduce_bucket_mb, already keyed above)
                bool(FLAGS.integrity_sentinel),
                os.environ.get("PT_STABILITY_POLICY", ""),
                # GuardPlan bakes these into the compiled gate too
                os.environ.get("PT_GUARD_SPIKE_FACTOR", ""),
                os.environ.get("PT_GUARD_EMA_BETA", ""),
                # kernel-registry selection happens at trace time
                bool(FLAGS.use_custom_kernels),
                os.environ.get("PT_KERNEL_DENY", ""),
                os.environ.get("PT_KERNEL_MIN_NUMEL", ""),
                os.environ.get("PT_KERNEL_QUANT_MATMUL", ""),
                *Engine._tuning_key_items())

    def compiled_step(self, program, scope: Scope, feed, fetch_names,
                      block_idx: int = 0, iterations: int = 1,
                      multi_step: int = 1):
        """The XLA-compiled executable of the already-run step (lowered
        once and cached on the traced entry). Returns None on the
        eager-interpreter fallback. The single source for everything
        that inspects the compiled artifact — cost analysis
        (compiled_stats), HLO text."""
        compiled, _ = self._compiled_entry(program, scope, feed,
                                           fetch_names, block_idx,
                                           iterations, multi_step)
        return compiled

    def _compiled_entry(self, program, scope, feed, fetch_names,
                        block_idx=0, iterations=1, multi_step=1):
        """(compiled, traced) as ONE pair — no cross-call state."""
        multi_step = max(int(multi_step or 1),
                         int(getattr(feed, "multi_step", 1) or 1))
        arrays, lods, feed_sig_key = self._normalize_feed(feed, None)
        if self._is_multihost():
            feed_sig_key = self._global_sig_key(arrays, lods)
        key = self._cache_key(program, block_idx, feed_sig_key,
                              fetch_names, iterations, multi_step)
        traced = self._cache.get(key)
        if traced is None:
            if self._cache:
                raise ValueError(
                    "compiled_step: no compiled step for this "
                    "(program, feed, fetch) signature — pass the same "
                    "feed/fetch that run() used")
            return None, None
        if not hasattr(traced.fn, "lower"):
            # eager-interpreter fallback: nothing compiled
            return None, None
        compiled = getattr(traced, "_compiled_cache", None)
        if compiled is None:
            # lower with the argument shardings the run dispatched
            # with: the same module as the running step, so the
            # persistent compile cache serves it instead of XLA
            # compiling the step again
            def _committed(a):
                return a.sharding if getattr(a, "committed", False) \
                    else None

            def _sig(n):
                a = _scope_array(scope, n)
                return jax.ShapeDtypeStruct(jnp.shape(a),
                                            jnp.result_type(a),
                                            sharding=_committed(a))

            donated = {n: _sig(n) for n in traced.donated_names}
            const = {n: _sig(n) for n in traced.const_names}
            multihost = self._is_multihost()
            # un-meshed feeds were committed to the place's device —
            # the device the step left its donated outputs on
            feed_sh = None
            if self.mesh is None:
                feed_sh = next((s.sharding for s in donated.values()
                                if s.sharding is not None), None)
            feeds = {n: jax.ShapeDtypeStruct(
                         self._global_shape(n, a) if multihost
                         else a.shape, a.dtype, sharding=feed_sh)
                     for n, a in arrays.items()}
            key_sig = jax.ShapeDtypeStruct((2,), jnp.uint32)
            compiled = traced.fn.lower(donated, const, feeds,
                                       key_sig).compile()
            traced._compiled_cache = compiled
        return compiled, traced

    def compiled_stats(self, program, scope: Scope, feed, fetch_names,
                       block_idx: int = 0, iterations: int = 1,
                       multi_step: int = 1
                       ) -> Optional[Dict[str, float]]:
        """XLA analytical cost of the already-compiled step: flops,
        bytes accessed, and temp (scratch) memory per step. Returns None
        on the eager-interpreter fallback (nothing is compiled there).
        The TPU-native analog of the reference's per-op benchmark
        bookkeeping
        (/root/reference/paddle/fluid/operators/benchmark/op_tester.cc).
        """
        compiled, traced = self._compiled_entry(
            program, scope, feed, fetch_names, block_idx, iterations,
            multi_step)
        if compiled is None:
            return None
        cached = getattr(traced, "_stats_cache", None)
        if cached is not None:
            return cached
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        # XLA cost_analysis counts a while/scan body ONCE (trip counts
        # are not multiplied in), so flops/bytes here are ~per-STEP
        # costs even for scanned executables. `trip_count` carries the
        # steps-per-DISPATCH multiplier (num_iteration_per_run x
        # PT_MULTI_STEP): anything dividing by per-dispatch device time
        # must multiply body FLOPs by it or the scanned path reports
        # impossibly low MFU.
        out = {"flops": float(ca.get("flops", 0.0)),
               "bytes_accessed":
                   float(ca.get("bytes accessed", 0.0)),
               "trip_count": float(
                   max(1, int(iterations)) *
                   max(1, int(multi_step or 1),
                       int(getattr(traced, "multi_step", 1) or 1)))}
        try:
            ma = compiled.memory_analysis()
            out["temp_bytes"] = float(ma.temp_size_in_bytes)
            out["argument_bytes"] = float(ma.argument_size_in_bytes)
        except Exception:
            pass
        traced._stats_cache = out
        return out

    def step_executables(self) -> List[int]:
        """For each cached jitted step, the number of executables JAX
        holds for it: one per distinct argument signature it was
        dispatched with (shapes, dtypes, shardings, committed-ness).
        The steady state is 1. A 2 means the step's arguments changed
        between dispatches — uncommitted startup outputs first, the
        step's own committed outputs after — and XLA compiled the whole
        step a second time, which ``counters["traces"]`` cannot see:
        the Python trace is shared."""
        return [t.fn._cache_size() for t in self._cache.values()
                if hasattr(t.fn, "_cache_size")]

    def donation_metadata(self) -> List[Dict[str, Any]]:
        """Per-trace donation metadata for the verifier and the memory
        observatory: which buffers each cached step donates to XLA
        (updated persistables, aliased in-place) and which it keeps
        const. The static analyzer's ``analysis.donation_plan``
        predicts this set pre-trace; this is the ground truth to
        reconcile against."""
        rows: List[Dict[str, Any]] = []
        for traced in list(self._cache.values()):
            rows.append({
                "donated": list(traced.donated_names),
                "const_count": len(traced.const_names),
                "updated": list(traced.updated_names),
                "scheduled": getattr(traced, "op_sched", None)
                is not None})
        return rows

    def _fast_key(self, program, block_idx, fetch_names, iterations,
                  multi_step=1):
        return (program.fingerprint, block_idx, tuple(fetch_names),
                int(iterations), int(multi_step),
                bool(FLAGS.check_nan_inf),
                int(getattr(program, "_gradient_accumulation_steps", 1)
                    or 1),
                float(FLAGS.allreduce_bucket_mb),
                str(FLAGS.quantized_allreduce),
                bool(FLAGS.sharded_weight_update),
                bool(FLAGS.op_scheduler),
                # the guard's gate (and its policy's damping, spike
                # threshold, and EMA decay) is baked into the trace,
                # as are the sentinel's fingerprints
                bool(FLAGS.stability_guard),
                bool(FLAGS.integrity_sentinel),
                os.environ.get("PT_STABILITY_POLICY", ""),
                os.environ.get("PT_GUARD_SPIKE_FACTOR", ""),
                os.environ.get("PT_GUARD_EMA_BETA", ""),
                # kernel-registry selection happens at trace time
                bool(FLAGS.use_custom_kernels),
                os.environ.get("PT_KERNEL_DENY", ""),
                os.environ.get("PT_KERNEL_MIN_NUMEL", ""),
                os.environ.get("PT_KERNEL_QUANT_MATMUL", ""),
                *Engine._tuning_key_items())

    def _fast_feed_arrays(self, entry: _FastPathEntry, feed):
        """Feed dict -> device arrays through the cached signature: no
        sorted() walk, no per-name sig tuple, no redundant device_put.
        Returns None on ANY mismatch (shape/dtype/LoD/name set) — the
        slow path then re-normalizes and refreshes the entry."""
        feed = feed or {}
        if len(feed) != len(entry.feed_names):
            return None
        arrays = {}
        shapes, dtypes, lods, dev = (entry.shapes, entry.dtypes,
                                     entry.lods, entry.dev)
        for n in entry.feed_names:
            val = feed.get(n)
            if val is None:
                return None
            if isinstance(val, LoDTensor):
                if val.lod() != lods.get(n, []):
                    return None
                arr = val.array
            else:
                if lods.get(n):
                    return None
                arr = val
            if isinstance(arr, jax.Array):
                if (tuple(arr.shape) != shapes[n]
                        or str(arr.dtype) != dtypes[n]):
                    return None
                if dev is not None and not _on_device(arr, dev):
                    self.counters["device_puts"] += 1
                    arr = jax.device_put(arr, dev)
            else:
                arr = np.asarray(arr)
                if tuple(arr.shape) != shapes[n]:
                    return None
                self.counters["device_puts"] += 1
                arr = jax.device_put(arr, dev) if dev is not None \
                    else jnp.asarray(arr)
                if str(arr.dtype) != dtypes[n]:
                    return None
            arrays[n] = arr
        return arrays

    def _maybe_autotune(self, program, scope, place, feed,
                        fetch_names) -> None:
        """FLAGS_autotune: once per program fingerprint, replay (cache
        hit) or search for (cache miss) the winning knob config before
        the first trace (paddle_tpu/tuning/driver.py). Trials recurse
        into run() — the search_in_progress guard keeps them from
        autotuning themselves. A tuning failure degrades to untuned
        execution, never breaks the step."""
        from ..tuning import state as _tuning_state
        if _tuning_state.search_in_progress():
            return
        if not fetch_names:
            # nothing to fetch-fence a measurement on — init/startup
            # programs run once, tuning them is pure waste. Not marked
            # tuned: a later fetching run of this program still tunes.
            return
        fp = program.fingerprint
        if fp in self._tuned:
            return
        self._tuned.add(fp)
        try:
            from ..tuning import driver as _tuning_driver
            _tuning_driver.autotune_for_run(self, program, scope,
                                            place, feed, fetch_names)
        except Exception as exc:  # degrade, don't break training
            import warnings
            warnings.warn(f"autotune skipped: {exc!r}")

    def _maybe_place(self, program, fetch_names) -> None:
        """PT_PLACEMENT_AUTO: once per engine, pick the multi-axis
        mesh layout for this program — cache hit replays the stored
        PlacementPlan with zero search trials, a miss runs the static
        cost-model search (analysis/placement.py). Degrades to the
        un-meshed path on any failure, never breaks the step."""
        import jax as _jax
        if not fetch_names:
            # init/startup programs run once; placing them is pure
            # waste. Not marked placed: the training program that
            # follows still gets its layout.
            return
        self._placed = True
        if len(_jax.devices()) < 2:
            return
        try:
            from ..analysis import placement as _placement
            plan = _placement.plan_for_program(program)
            self.counters["placement_cache_hits" if plan.cached
                          else "placement_searches"] += 1
            strategy = _placement.strategy_for_plan(plan)
            if strategy is None:
                return
            self.strategy = strategy
            self.mesh = strategy.mesh
            self.data_axis = strategy.data_axis
        except Exception as exc:  # degrade, don't break training
            import warnings
            warnings.warn(f"automatic placement skipped: {exc!r}")

    def run(self, program, scope: Scope, place, feed, fetch_names,
            block_idx: int = 0,
            return_numpy: bool = True,
            iterations: int = 1,
            use_program_cache: bool = True) -> List[Any]:
        if FLAGS.autotune:
            # before the fast-path lookup: applying a tuning config
            # changes both cache keys (applied token + knob values),
            # so the winner must be live before the first trace
            self._maybe_autotune(program, scope, place, feed,
                                 fetch_names)
        if self.mesh is None and self.strategy is None and \
                not self._placed and \
                os.environ.get("PT_PLACEMENT_AUTO", ""):
            # cost-driven automatic SPMD placement: resolve (or replay
            # from the tuning cache) the mesh layout before the first
            # trace — a caller-supplied mesh/strategy always wins
            self._maybe_place(program, fetch_names)
        # multi-step slab feed (PT_MULTI_STEP, docs/ASYNC_DISPATCH.md):
        # a FeedSlab (reader/prefetcher.py) carries K stacked batches
        # and its K on the `multi_step` attribute — captured before the
        # fault plan may swap the dict out under us
        multi_step = int(getattr(feed, "multi_step", 1) or 1)
        self.counters["runs"] += 1
        plan = _fault_plan()
        if plan is not None:
            # injected preemption: kill this process at step N (the
            # supervised-restart path CI exercises without hardware)
            plan.on_step(self.counters["runs"])
            # injected silent corruption (bitflip fault kind): XOR one
            # bit of a parameter in scope BEFORE the step reads it, so
            # the integrity sentinel's detect + rollback path is
            # exercised end to end in chaos runs
            plan.corrupt_scope(self.counters["runs"], scope, program)
            # injected numeric anomaly (nan / grad_spike fault kinds):
            # corrupt the feed so the stability guard's detection +
            # recovery path is exercised end to end in chaos runs
            if feed:
                feed = plan.corrupt_feed(self.counters["runs"], feed)
        # the step's phases stamp this thread's StepClock (profiler.py):
        # spans in any open profiler session and two clock reads each,
        # whatever the telemetry switch says. The flight/telemetry
        # record is built from the stamps at the end of the step, and
        # only while metrics._HOT is set or the step was slow
        clock = _profiler.step_clock()
        if clock.opened:
            clock.opened = False    # Executor.run began this step
        else:
            clock.begin_step()
        if _obs._HOT[0]:
            # deterministic trace id for this step: RPCs, deferred
            # fetches and checkpoint saves issued below inherit it
            # (docs/TRACING.md)
            _obs_tracing.begin_step(self.counters["runs"])
        iterations = int(iterations or 1)
        fast_key = entry = None
        with clock.phase(_profiler.P_FEED):
            if use_program_cache:
                fast_key = self._fast_key(program, block_idx,
                                          fetch_names, iterations,
                                          multi_step)
                # one entry per live feed signature (entries disagree
                # on shapes, so at most one converts the feed); small
                # list — a training loop sees 1-2 signatures (train +
                # eval tail)
                for e in self._fast.get(fast_key, ()):
                    if e.scope is scope and (
                            e.place is place or e.dev == (
                                place.jax_device()
                                if place is not None
                                and self.mesh is None else None)):
                        arrays = self._fast_feed_arrays(e, feed)
                        if arrays is not None:
                            entry = e
                            break
            if entry is None:
                arrays, lods, feed_sig_key = self._normalize_feed(
                    feed, None if self.mesh is not None else place)
                multihost = self._is_multihost()
                if multihost:
                    if lods:
                        # Ragged feeds are supported when every
                        # process's batch has the SAME LoD signature
                        # (what length-bucketing produces): the single
                        # global program then sees the k-fold
                        # replicated offsets, and row blocks
                        # concatenate uniformly. Divergent per-process
                        # lods would need per-process programs — SPMD
                        # cannot express that.
                        self._verify_uniform_lods(lods)
                        lods = {n: self._replicate_lod(lod)
                                for n, lod in lods.items()}
                    feed_sig_key = self._global_sig_key(arrays, lods)
                    arrays = self._globalize(arrays)
        if entry is not None:
            self.counters["fast_path_hits"] += 1
            clock.fast_path = True
            clock.sig = entry.sig_hash
            with clock.phase(_profiler.P_ARGS):
                donated = {n: _var_array(v)
                           for n, v in entry.donated_vars}
                const = {n: _var_array(v) for n, v in entry.const_vars}
            outs = self._dispatch(
                program, scope, entry.traced, arrays, donated, const,
                return_numpy, entry.dev,
                updated_vars=entry.updated_vars)
            self._finish_step(clock, entry.traced, arrays)
            if multi_step > 1:
                return self._finish_multi(
                    outs, program, scope, place, feed, fetch_names,
                    block_idx, return_numpy, multi_step)
            return outs
        clock.sig = feed_sig_key    # hashed only if a record is built
        if iterations > 1 and lods:
            raise NotImplementedError(
                "num_iteration_per_run > 1 cannot scan over LoD "
                "(ragged) feeds; pad to dense first")
        if multi_step > 1 and lods:
            raise NotImplementedError(
                "PT_MULTI_STEP > 1 cannot scan over LoD (ragged) "
                "feeds; pad to dense first")
        key = self._cache_key(program, block_idx, feed_sig_key,
                              fetch_names, iterations, multi_step)
        traced = self._cache.get(key) if use_program_cache else None
        if traced is not None and traced.dispatched:
            cold = contextlib.nullcontext()
        else:
            # this call traces or first-dispatches: one set-up span
            # over the whole of it, begun where Executor.run began the
            # step, with `trace_step` and `first_dispatch` inside
            cold = _obs_tracing.setup_span(
                "cold_run", p0=clock.step_ns / 1e9,
                program=program.fingerprint[0])
        with cold:
            if traced is None:
                self.counters["traces"] += 1
                clock.traced = True
                with clock.phase(_profiler.P_TRACE):
                    traced = self._trace(program, block_idx, arrays, lods,
                                         fetch_names, scope, iterations,
                                         multi_step)
                if use_program_cache:
                    self._cache[key] = traced

            # the one device an un-meshed step's arguments are committed to
            dev = place.jax_device() \
                if place is not None and self.mesh is None else None
            with clock.phase(_profiler.P_ARGS):
                donated_params = {}
                const_params = {}
                for n in traced.donated_names:
                    donated_params[n] = _scope_array(scope, n)
                for n in traced.const_names:
                    const_params[n] = _scope_array(scope, n)
                if dev is not None:
                    # a startup program has no committed input, so it
                    # leaves the params UNCOMMITTED; this step's outputs
                    # are committed (the feeds are). Commit what the step
                    # donates now, or the second dispatch sees other
                    # argument shardings than the first and XLA compiles
                    # the whole step a second time
                    donated_params = jax.device_put(donated_params, dev)
                if multihost:
                    # params already produced by a previous multihost step
                    # are global arrays; only host-local values need
                    # assembling — and globalized const params are written
                    # back to the scope so the transfer happens once, not
                    # per step
                    def _as_global(n, v, write_back):
                        if isinstance(v, jax.Array) and \
                                not v.is_fully_addressable:
                            return v
                        g = self._globalize_replicated({n: v})[n]
                        if write_back:
                            scope.var(n).set_value(g)
                        return g

                    donated_params = {n: _as_global(n, v, False)
                                      for n, v in donated_params.items()}
                    const_params = {n: _as_global(n, v, True)
                                    for n, v in const_params.items()}
                elif fast_key is not None:
                    # steady-state record: subsequent runs of this
                    # (program, feed-sig, fetch) tuple skip signature
                    # reconstruction, persistable re-walks, and no-op
                    # device_puts
                    entries = self._fast.setdefault(fast_key, [])
                    entry = _FastPathEntry(scope, place, dev, arrays, lods,
                                           traced)
                    entry.sig_hash = _sig_hash(feed_sig_key)
                    entries.append(entry)
                    if len(entries) > _MAX_FAST_ENTRIES:
                        entries.pop(0)
                # cold path only: register the scope with the memory census
                # (one weak-set add per trace, nothing per steady-state
                # step)
                _obs_memory.track_scope(scope)
            outs = self._dispatch(program, scope, traced, arrays,
                                  donated_params, const_params,
                                  return_numpy, dev)
            self._finish_step(clock, traced, arrays)
            if multi_step > 1:
                return self._finish_multi(outs, program, scope, place,
                                          feed, fetch_names, block_idx,
                                          return_numpy, multi_step)
            return outs

    def _trace(self, program, block_idx, arrays, lods, fetch_names,
               scope, iterations, multi_step):
        """The cold path of :meth:`run`: trace the step, then tier-2
        validation of what was traced."""
        feed_sig = {n: jax.ShapeDtypeStruct(a.shape, a.dtype)
                    for n, a in arrays.items()}
        traced = trace_step(program, block_idx, feed_sig, lods,
                            fetch_names, scope, mesh=self.mesh,
                            data_axis=self.data_axis,
                            strategy=self.strategy,
                            iterations=iterations,
                            multi_step=multi_step)
        if FLAGS.validate_program and int(FLAGS.validate_tier) >= 2:
            # tier 2: re-verify the step we ACTUALLY traced — the
            # partition the scheduler would dispatch, proven
            # conflict-free under the ground-truth updated/donated
            # sets phase 1 discovered (vs tier 1's static inference at
            # the executor boundary). Runs once per trace build;
            # raises before anything compiles.
            from ..analysis.validate import validate_traced
            validate_traced(program, block_idx, traced.updated_names,
                            traced.donated_names, fetch_names)
            # ... and cross-check the step's lowering decisions (guard
            # gate, collective plan, island-gate choice) against the
            # static conformance trace — same tier, same
            # once-per-trace-build cost (analysis/conformance.py).
            from ..analysis.conformance import crosscheck_traced
            crosscheck_traced(program, block_idx, traced,
                              mesh=self.mesh, data_axis=self.data_axis,
                              strategy=self.strategy)
        return traced

    def _finish_multi(self, outs, program, scope, place, feed,
                      fetch_names, block_idx, return_numpy, k):
        """Post-process one multi-step (PT_MULTI_STEP=K) dispatch.

        ``outs`` is the list of K per-substep fetch rows built by
        :meth:`_package_multi`. When the stability guard froze the
        scan carry early (anomaly at substep j), only ``valid``
        substeps took effect — the frozen tail is replayed host-side
        through the plain K=1 path, so the post-anomaly trajectory
        (gated params, halved loss scale) is bit-identical to
        sequential execution and every batch is consumed exactly
        once. Returns the LAST substep's row so run() callers see the
        usual single-step shape; all K rows stay on
        ``last_multi_fetches``."""
        rec = self._last_multi or {"k": k, "valid": k}
        valid = max(1, min(int(rec.get("valid", k)), k))
        rows = list(outs) if isinstance(outs, list) else [outs]
        if valid < k:
            self.counters["multistep_replays"] += (k - valid)
            for j in range(valid, k):
                sub = {n: v[j] for n, v in feed.items()}
                rows[j] = self.run(program, scope, place, sub,
                                   fetch_names, block_idx=block_idx,
                                   return_numpy=return_numpy)
        self.last_multi_fetches = rows
        return rows[-1] if rows else rows

    def run_multi(self, program, scope: Scope, place, feeds,
                  fetch_names, block_idx: int = 0,
                  return_numpy: bool = True,
                  use_program_cache: bool = True) -> List[Any]:
        """Run K training steps as ONE dispatched executable.

        ``feeds`` is a FeedSlab (reader/prefetcher.py) or a list of K
        per-step feed dicts — the latter is stacked here. Returns the
        K per-substep fetch rows (docs/ASYNC_DISPATCH.md,
        "Multi-step dispatch"); ``run()`` itself returns only the
        last row."""
        from ..reader.prefetcher import FeedSlab
        if not isinstance(feeds, FeedSlab):
            feeds = list(feeds)
            if len(feeds) == 1:
                out = self.run(program, scope, place, feeds[0],
                               fetch_names, block_idx=block_idx,
                               return_numpy=return_numpy,
                               use_program_cache=use_program_cache)
                self.last_multi_fetches = [out]
                return [out]
            feeds = FeedSlab.stack(feeds)
        out = self.run(program, scope, place, feeds, fetch_names,
                       block_idx=block_idx, return_numpy=return_numpy,
                       use_program_cache=use_program_cache)
        if int(getattr(feeds, "multi_step", 1) or 1) == 1:
            self.last_multi_fetches = [out]
        return self.last_multi_fetches

    def _dispatch(self, program, scope, traced, arrays, donated_params,
                  const_params, return_numpy, dev, updated_vars=None):
        """Watchdog wrapper over :meth:`_dispatch_inner`: with
        FLAGS_step_timeout_s > 0 the step runs armed, and a hang is
        converted into the watchdog's diagnosable EnforceNotMet (the
        monitor interrupts this thread; disarm() is inside the
        converting try so a late interrupt cannot leak)."""
        wd = self._step_watchdog()
        if wd is None:
            return self._dispatch_inner(
                program, scope, traced, arrays, donated_params,
                const_params, return_numpy, dev, updated_vars)
        try:
            try:
                wd.arm()
                return self._dispatch_inner(
                    program, scope, traced, arrays, donated_params,
                    const_params, return_numpy, dev, updated_vars)
            finally:
                wd.disarm()
        except KeyboardInterrupt:
            if wd.fired and wd.error is not None:
                raise wd.error from None
            raise

    def _finish_step(self, clock, traced, feed_arrays):
        """Close out one step from the clock's stamps. Always: the
        step's total joins the slow-step history. While metrics._HOT is
        set, or when the step was slow, the flight/telemetry record is
        built — the one place that is — and handed to the recorder
        (histogram observes + ring append); while _HOT, the step's
        trace spans are derived from the same record and the
        deep-profile and memory ticks run."""
        total_ns = clock.end_step()
        slow = self._slow.is_slow(total_ns)
        hot = _obs._HOT[0]
        if not (hot or slow):
            return
        dur, off = clock.phases()
        obs = {"step": self.counters["runs"],
               "t_host": time.time() - total_ns / 1e9,
               "phases": dur, "phase_t0_ms": off,
               "fast_path": clock.fast_path, "traced": clock.traced,
               "pending_fetches": len(self._pending)}
        if clock.sig is not None:
            obs["sig"] = _sig_hash(clock.sig)
        comm_stats = getattr(traced, "comm_stats", None)
        if comm_stats:
            obs["comm_plan"] = comm_stats.get("plan_id",
                                              comm_stats["buckets"])
        sched = getattr(traced, "op_sched", None)
        if sched is not None and sched.last_stats:
            obs["lanes"] = sched.last_stats.get("spans")
            obs["phases"]["lane_idle_ms"] = sched.last_stats.get(
                "lane_idle_ms", 0.0)
        last = getattr(self._stability, "last", None)
        if last and last.get("step") == obs["step"]:
            obs["anomaly"] = dict(last)
        if slow:
            obs["slow"] = True
            obs["median_ms"] = self._slow.median_ns / 1e6
            obs["gc"] = self._slow.gc_delta()
        if hot:
            # census attribution for the step's device-side feed batch:
            # held until the next step replaces it (owner "feed"),
            # cleared when the census is off so the batch is not kept
            # alive
            self._census_feed = (feed_arrays
                                 if _obs_memory.census_active() else None)
        _obs_recorder.record_step(obs)
        if not hot:
            return
        _obs_tracing.finish_step(obs)
        try:
            from ..observability import attribution as _obs_attr
            _obs_attr.deep_profile_tick()
        except Exception:
            pass
        try:
            _obs_memory.step_tick()
        except Exception:
            pass

    def _dispatch_inner(self, program, scope, traced, arrays,
                        donated_params, const_params, return_numpy,
                        dev, updated_vars=None, _guard_reexec=False):
        """Shared dispatch tail of fast and slow paths: the RNG state,
        the ONE executable call (it splits the state itself,
        :func:`_split_first`), then everything that needs neither the
        step's result nor the next feed — the argument dicts released,
        the device-resident scope writeback — and only then the call
        that blocks on the result: NaN-check surfacing (inline or
        deferred) and fetch wrapping. EMPTIES ``donated_params`` and ``const_params``.
        Under FLAGS.async_dispatch nothing here forces a device sync —
        the persistable writebacks stay jax.Array futures and the
        nan-flag host sync moves to the materialization point."""
        clock = _profiler.step_clock()
        multi_k = int(getattr(traced, "multi_step", 1) or 1)
        async_defer = bool(FLAGS.async_dispatch) and not return_numpy
        with clock.phase(_profiler.P_RNG):
            rng_key = _get_rng_state(scope, program)
            if dev is not None and (arrays or donated_params) and not (
                    getattr(rng_key, "committed", False)
                    and _on_device(rng_key, dev)):
                # the state a step hands back is committed like the
                # step's other outputs; a startup program's, a fresh
                # seed's or a user's own is not. Commit it beside the
                # feeds and the donated arrays, or the next dispatch
                # sees another argument signature and XLA compiles the
                # whole step a second time
                rng_key = jax.device_put(rng_key, dev)
        first = not traced.dispatched
        try:
            if first:
                res = self._first_dispatch(
                    clock, program, traced, donated_params,
                    const_params, arrays, rng_key)
            else:
                with clock.phase(_profiler.P_DISPATCH):
                    # async dispatch: this is the enqueue span; device
                    # time lands in the fetch phase (sync) or at the
                    # materialization point
                    res = traced.fn(donated_params, const_params,
                                    arrays, rng_key)
        except Exception as exc:
            # RESOURCE_EXHAUSTED here = compile/alloc OOM: capture who
            # owns the HBM before unwinding (one dump per exception)
            _obs_memory.oom_postmortem(exc, where="engine_dispatch")
            raise
        fetches, updated, nan_flags, info = res
        with clock.phase(_profiler.P_RELEASE):
            # nothing below needs the arguments: drop them under the
            # device's work, not after the fetch has waited for it. The
            # scope's variables hold the donated arrays until the
            # writeback replaces them, so their wrappers die there
            donated_params.clear()
            const_params.clear()
        guard_plan = getattr(traced, "guard_plan", None)
        reexec = False
        with clock.phase(_profiler.P_WRITEBACK):
            _set_rng_state(scope, info["rng_state"])
            comm_stats = getattr(traced, "comm_stats", None)
            if comm_stats:
                c = self.counters
                c["collective_bytes"] += comm_stats["bytes"]
                c["collective_buckets"] += comm_stats["buckets"]
                c["collective_quantized"] += comm_stats["quantized"]
                c["grad_collectives_per_step"] = comm_stats["buckets"]
                c["comm_overlap_frac"] = comm_stats["overlap_frac"]
            sched = getattr(traced, "op_sched", None)
            if sched is not None and sched.last_stats:
                st = sched.last_stats
                c = self.counters
                c["scheduled_steps"] += 1
                if "islands_concurrent" in st:
                    c["islands_concurrent"] = st["islands_concurrent"]
                if "pipeline_fill_frac" in st:
                    c["pipeline_fill_frac"] = st["pipeline_fill_frac"]
                c["lane_idle_ms"] += st.get("lane_idle_ms", 0.0)
            for n, v in updated.items():
                var = updated_vars.get(n) if updated_vars is not None \
                    else None
                if var is None:
                    var = scope.var(n)
                    if updated_vars is not None:
                        updated_vars[n] = var
                var.set_value(v)
            # the synchronize() barrier target: the updated
            # persistables are the step's full dependency cone (same
            # arrays the scope holds — no extra live buffers)
            self._last_updated = tuple(updated.values())
            if guard_plan is not None:
                _g0 = time.perf_counter()
                ctl = self._stability
                if ctl is None:
                    from ..stability import StabilityGuard
                    ctl = self._stability = StabilityGuard()
                action = ctl.after_step(
                    self, program, scope, traced, arrays, fetches,
                    updated, rng_key, async_defer and multi_k == 1,
                    reexec=_guard_reexec)
                self.counters["guard_overhead_ms"] += (
                    time.perf_counter() - _g0) * 1e3
                if _obs.telemetry_active():
                    _obs.histogram(
                        "pt_guard_overhead_seconds",
                        "host-side stability-guard controller time per "
                        "step (verdict read + policy + ghost capture)"
                    ).observe(time.perf_counter() - _g0)
                reexec = action == "reexecute"
            if not reexec:
                self._after_writeback(program, scope, traced, updated,
                                      guard_plan, info, multi_k)
        if reexec:
            # the scope now holds the restored ghost (params, optimizer
            # state, loss scale, RNG); re-run THIS step from it —
            # recursion depth is bounded to one by the controller's
            # reexec handling
            donated2 = {n: _scope_array(scope, n)
                        for n in traced.donated_names}
            const2 = {n: _scope_array(scope, n)
                      for n in traced.const_names}
            return self._dispatch_inner(
                program, scope, traced, arrays, donated2, const2,
                return_numpy, dev, updated_vars, _guard_reexec=True)
        with clock.phase(_profiler.P_FETCH):
            rec = None
            if traced.nan_check_labels:
                if async_defer:
                    from .async_dispatch import PendingStep
                    rec = PendingStep(nan_flags,
                                      traced.nan_check_labels,
                                      program.fingerprint)
                    self._pending.append(rec)
                    if len(self._pending) > _MAX_PENDING_STEPS:
                        self._pending.pop(0).check()
                else:
                    flags_host = np.asarray(nan_flags)
                    if not flags_host.all():
                        bad = int(np.argmin(flags_host))
                        op_type, var = traced.nan_check_labels[bad]
                        raise EnforceNotMet(
                            f"Operator {op_type!r} output {var!r} "
                            f"contains NaN or Inf (FLAGS_check_nan_inf; "
                            f"reference operator.cc:953-983)",
                            op_type=op_type)
            try:
                out = self._package(traced, fetches, rec, program,
                                    async_defer, return_numpy, multi_k)
            except Exception as exc:
                # deferred XLA OOM surfaces at the sync D2H
                _obs_memory.oom_postmortem(exc, where="fetch")
                raise
        return out

    def _first_dispatch(self, clock, program, traced, donated_params,
                        const_params, arrays, rng_key):
        """The first call of an executable: JAX's trace of the step
        function, lowering, XLA compile or persistent-cache load, and
        the first execution, once. A set-up span (`first_dispatch`,
        observability/tracing.py) beside the profiler's; what
        `jax.monitoring` times inside it become its children
        (`first_dispatch.jit_trace` / `.lower` / `.compile` or
        `.cache_load`), and it is annotated with what the compilation
        cache said where `jax.monitoring` tells."""
        traced.dispatched = True
        hits0, misses0 = _obs_tracing.compile_cache_events()
        with _obs_tracing.setup_span(
                "first_dispatch",
                program=program.fingerprint[0]) as span, \
                clock.phase(_profiler.P_DISPATCH,
                            _profiler.FIRST_DISPATCH):
            res = traced.fn(donated_params, const_params, arrays,
                            rng_key)
            hits, misses = _obs_tracing.compile_cache_events()
            if misses > misses0:
                span.ann["cache"] = "miss"
            elif hits > hits0:
                span.ann["cache"] = "hit"
        return res

    def _after_writeback(self, program, scope, traced, updated,
                         guard_plan, info, multi_k):
        """Tail of the writeback phase: the integrity controller and
        the multi-step slab's accounting."""
        if getattr(traced, "integrity_plan", None) is not None:
            ctl = self._integrity
            if ctl is None:
                from ..stability import IntegritySentinel
                ctl = self._integrity = IntegritySentinel()
            # cheap increment off-window; device->host accumulator
            # read + verdict every PT_INTEGRITY_EVERY steps. A
            # rollback restores the scope in place — the NEXT step
            # picks the rewound params up from the scope; nothing to
            # re-execute here (the corruption happened outside the
            # step, not inside it)
            ctl.after_step(self, program, scope, traced, updated)
        if multi_k > 1:
            # executed-substep count: guard-off slabs run all K by
            # construction (no sync); guard-on pays ONE scalar sync per
            # slab — amortized 1/K vs the per-step verdict sync of K=1
            valid = multi_k
            if guard_plan is not None:
                valid = int(np.asarray(info["valid"]))
                valid = max(1, min(valid, multi_k))
            self._last_multi = {"k": multi_k, "valid": valid}
            c = self.counters
            c["multistep_dispatches"] += 1
            c["multistep_substeps"] += valid
            if valid < multi_k:
                c["multistep_early_exits"] += 1
            if _obs.telemetry_active():
                _obs.gauge(
                    "pt_multistep_k",
                    "substeps fused per dispatched executable "
                    "(PT_MULTI_STEP)").set(multi_k)
                _obs.counter(
                    "pt_multistep_dispatches_total",
                    "multi-step slab dispatches").inc(1)
                _obs.counter(
                    "pt_multistep_substeps_total",
                    "training substeps executed inside multi-step "
                    "slabs").inc(valid)
                if valid < multi_k:
                    _obs.counter(
                        "pt_multistep_early_exits_total",
                        "slabs cut short by a guard verdict "
                        "(carry freeze)").inc(1)

    def _package(self, traced, fetches, rec, program, async_defer,
                 return_numpy, k):
        """The fetch phase's result: the step's fetches as the caller
        asked for them. One step gives one row; a multi-step dispatch
        (k > 1) gives K per-substep rows out of the stacked fetches.
        Async: lazy FetchHandles (over device-side row slices when
        k > 1, so per-substep losses materialize individually without
        a slab-wide sync); sync: one host transfer per fetch."""
        lods = traced.fetch_lods
        if async_defer:
            from .async_dispatch import FetchHandle
            hot = _obs._HOT[0]
            # capture the step's trace context NOW — materialization
            # happens on a later step (or another thread), after this
            # thread's context has moved on
            tctx = _obs_tracing.current_context() if hot else None

            def handle(v, n, label):
                h = FetchHandle(v, lods.get(n), rec, label,
                                program.fingerprint, tctx=tctx)
                if hot:
                    _obs_memory.track_fetch_handle(h)
                return h

            if k == 1:
                return [handle(v, n, n)
                        for n, v in zip(traced.fetch_names, fetches)]
            return [[handle(v[j], n, f"{n}[{j}]")
                     for n, v in zip(traced.fetch_names, fetches)]
                    for j in range(k)]

        def host(n, v, hv):
            lod = lods.get(n)
            return hv if return_numpy and not lod \
                else LoDTensor(v, lod or [])

        if k == 1:
            return [host(n, v, np.asarray(v)
                         if return_numpy and not lods.get(n) else None)
                    for n, v in zip(traced.fetch_names, fetches)]
        hosts = [np.asarray(v) for v in fetches]
        return [[host(n, v[j], hv[j])
                 for n, v, hv in zip(traced.fetch_names, fetches, hosts)]
                for j in range(k)]

    def synchronize(self):
        """Materialization barrier for FLAGS.async_dispatch: drain every
        deferred NaN/Inf check (re-raising with the original op context)
        and block until the last step's updated persistables are
        resident — after this returns, the scope holds finished values
        and any deferred XLA error has surfaced. Runs under the step
        watchdog (FLAGS_step_timeout_s): a barrier that never returns —
        a dead collective peer, a wedged runtime — trips the same
        diagnosable timeout as a hung step."""
        wd = self._step_watchdog()
        if wd is not None:
            try:
                try:
                    wd.arm()
                    self._synchronize_inner()
                finally:
                    wd.disarm()
            except KeyboardInterrupt:
                if wd.fired and wd.error is not None:
                    raise wd.error from None
                raise
        else:
            self._synchronize_inner()

    def _synchronize_inner(self):
        pending, self._pending = self._pending, []
        for rec in pending:
            rec.check()
        last, self._last_updated = self._last_updated, ()
        if last:
            try:
                jax.block_until_ready(last)
            except EnforceNotMet:
                raise
            except Exception as exc:
                _obs_memory.oom_postmortem(exc, where="synchronize")
                err = EnforceNotMet(
                    f"deferred XLA error surfaced at synchronize(): "
                    f"{exc}")
                err.__cause__ = exc
                raise err


def _scope_array(scope: Scope, name: str):
    val = scope.find_var(name).get_value()
    return val.array if isinstance(val, LoDTensor) else val


def _var_array(var):
    """_scope_array over a cached Variable reference (fast path: no
    scope-chain walk per persistable per step)."""
    val = var.get_value()
    return val.array if isinstance(val, LoDTensor) else val


def _get_rng_state(scope: Scope, program):
    v = scope.find_var(RNG_STATE_VAR)
    if v is None or not v.is_initialized():
        seed = getattr(program, "_seed", 0) or FLAGS.seed or 0
        state = jax.random.PRNGKey(seed)
        scope.var(RNG_STATE_VAR).set_value(state)
        return state
    return v.get_value()


def _set_rng_state(scope: Scope, state):
    scope.var(RNG_STATE_VAR).set_value(state)
