"""Cross-path lowering conformance verifier (docs/STATIC_ANALYSIS.md).

Every program lowers along one of three paths — engine whole-block
jit, the transpiler-emitted explicit-collective program, and eager
dygraph — and each path re-implements the
decisions that matter: kernel routing, gradient bucket planning,
quantization, the stability-guard gate, loss scaling, sharding hints,
and trace-cache keying.  This module extracts a canonical **lowering
trace** per path by *abstract interpretation of the lowering hooks*
(the same planners/registries the real paths call, with no device
execution), then diffs the traces pairwise against the declared
``support_matrix``:

* records equal                         → conformant, silence;
* records differ, both cells supported  → NEW drift, ERROR;
* records differ, a cell is declared
  degraded/unsupported                  → known gap, INFO with the
                                          cell's written justification.

A tier-2 runtime hook (``crosscheck_traced``) additionally compares the
static engine-path trace against the step the engine ACTUALLY traced.

No jax import at module level: extraction is pure program/registry
inspection so the CLI and tier-1 validation can afford it.
"""
from __future__ import annotations

import ast
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .diagnostics import Diagnostic, Severity
from .support_matrix import (DEGRADED, FEATURES, PATHS, SUPPORTED,
                             SupportMatrix, UNSUPPORTED, default_matrix,
                             worst_status)

__all__ = [
    "TraceConfig", "LoweringTrace", "extract_trace", "extract_traces",
    "diff_traces", "verify_conformance", "crosscheck_traced",
    "inject_drift", "DRIFT_KINDS",
]

PASS_NAME = "conformance"

# Stage tag for where quantization is applied relative to the reduce:
# the in-trace emulated collective quantizes the logically-reduced
# global-view value; the per-device paths quantize each rank's
# pre-reduction payload (docs/COLLECTIVES.md).
_STAGE_GLOBAL_VIEW = "global-view-emulated"
_STAGE_PER_DEVICE = "per-device-payload"


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

class TraceConfig:
    """What to assume while abstractly interpreting the lowerings.

    ``capability()`` (the verifier default) arms every feature — guard
    on, bucketing on, a live multi-axis mesh for the engine path — so
    the comparison covers what each path WOULD lower when the feature
    is exercised, independent of ambient flags.  ``current()`` mirrors
    the live flag/mesh state and backs the tier-2 runtime cross-check.
    """

    __slots__ = ("bucket_bytes", "quantize_mode", "guard", "multi_axis",
                 "loss_scale", "dynamic_dim", "platform")

    def __init__(self, bucket_bytes: int, quantize_mode: str,
                 guard: bool, multi_axis: bool,
                 loss_scale: Optional[bool] = None,
                 dynamic_dim: int = 64, platform: str = "tpu"):
        self.bucket_bytes = int(bucket_bytes)
        self.quantize_mode = str(quantize_mode or "")
        self.guard = bool(guard)
        self.multi_axis = bool(multi_axis)
        # None = read Program._dynamic_loss_scale; bool = force
        self.loss_scale = loss_scale
        self.dynamic_dim = int(dynamic_dim)
        self.platform = platform

    @classmethod
    def capability(cls, **overrides) -> "TraceConfig":
        from ..parallel.comm_scheduler import (bucket_bytes_from_flags,
                                               quantize_mode_from_flags)
        bb = bucket_bytes_from_flags()
        kw = dict(bucket_bytes=bb if bb > 0 else 32 << 20,
                  quantize_mode=quantize_mode_from_flags(),
                  guard=True, multi_axis=True)
        kw.update(overrides)
        return cls(**kw)

    @classmethod
    def current(cls, mesh=None) -> "TraceConfig":
        from ..core.flags import FLAGS
        from ..parallel.comm_scheduler import (bucket_bytes_from_flags,
                                               quantize_mode_from_flags)
        return cls(bucket_bytes=bucket_bytes_from_flags(),
                   quantize_mode=quantize_mode_from_flags(),
                   guard=bool(FLAGS.stability_guard),
                   multi_axis=(mesh is not None
                               and getattr(mesh, "size", 1) > 1))


class LoweringTrace:
    """Canonical per-path record of the lowering decisions.

    ``features[name]`` is a dict:
      ``applies``  — the path would exercise the feature on this program
                     under the config;
      ``content``  — the canonical, comparable decision record (tuples
                     all the way down);
      ``note``     — human context for reports;
      ``skip``     — set when the feature is NOT comparable on this
                     program for structural, non-drift reasons (e.g.
                     the engine defers to a program's own explicit
                     collective ops); the differ ignores such records.
    """

    def __init__(self, path: str):
        if path not in PATHS:
            raise ValueError(f"unknown path {path!r}; known: {PATHS}")
        self.path = path
        self.features: Dict[str, Dict[str, Any]] = {}
        self.meta: Dict[str, Any] = {}

    def record(self, feature: str, applies: bool, content,
               note: str = "", skip: bool = False) -> None:
        if feature not in FEATURES:
            raise ValueError(f"unknown feature {feature!r}")
        self.features[feature] = {"applies": bool(applies),
                                  "content": content, "note": note,
                                  "skip": bool(skip)}

    def to_dict(self) -> Dict[str, Any]:
        return {"path": self.path, "meta": dict(self.meta),
                "features": {k: dict(v)
                             for k, v in self.features.items()}}


def _key(rec: Dict[str, Any]) -> Tuple[Any, Any]:
    return (rec["applies"], rec["content"])


def _pairs(d: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    return tuple(sorted(d.items()))


# ---------------------------------------------------------------------------
# shared program facts
# ---------------------------------------------------------------------------

def _grad_items(program, block_idx: int):
    """[(grad_name, producing_op_idx, shape, np_dtype)] in production
    order — the engine/transpiler planning order."""
    from ..parallel.comm_scheduler import grad_production_order
    return grad_production_order(program, block_idx)

def _has_explicit_collectives(program, block_idx: int) -> bool:
    from .passes import COLLECTIVE_OP_TYPES
    block = program.block(block_idx)
    return any(op.type in COLLECTIVE_OP_TYPES for op in block.ops)


def _dygraph_grad_items(program, block_idx: int):
    """The grads apply_collective_grads would bucket, in ITS order:
    reversed parameter-creation order of params that have a grad
    (dygraph/parallel.py walks reversed(layers.parameters()))."""
    prod = _grad_items(program, block_idx)
    by_name = {n: (shape, dt) for n, _idx, shape, dt in prod}
    block = program.block(block_idx)
    out = []
    for p in reversed(block.all_parameters()):
        g = p.name + "@GRAD"
        if g in by_name:
            shape, dt = by_name[g]
            out.append((g, shape, dt))
    # grads the param walk missed (e.g. params in another block) keep
    # production order at the tail so nothing silently disappears
    seen = {n for n, _s, _d in out}
    for n, _idx, shape, dt in prod:
        if n not in seen:
            out.append((n, shape, dt))
    return out


def _engine_keyed_names() -> Tuple[str, ...]:
    """Knobs the engine folds into its trace-cache key, read off the
    AST of core/engine.py's key functions — the same ground truth
    tools/lint_flags.py audits against."""
    global _KEYED_CACHE
    if _KEYED_CACHE is not None:
        return _KEYED_CACHE
    names: set = set()
    try:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "core", "engine.py")
        with open(path, "r") as f:
            tree = ast.parse(f.read(), filename=path)
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            if fn.name not in ("_cache_key", "_fast_key",
                               "_trace_key_items"):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Attribute) and \
                        isinstance(node.value, ast.Name) and \
                        node.value.id == "FLAGS":
                    names.add(f"FLAGS.{node.attr}")
                if isinstance(node, ast.Constant) and \
                        isinstance(node.value, str) and \
                        node.value.startswith("PT_"):
                    names.add(node.value)
    except Exception:
        pass
    _KEYED_CACHE = tuple(sorted(names))
    return _KEYED_CACHE


_KEYED_CACHE: Optional[Tuple[str, ...]] = None


# ---------------------------------------------------------------------------
# per-feature extraction
# ---------------------------------------------------------------------------

def _kernel_records(program, block_idx: int, cfg: TraceConfig):
    """(op_idx, op_type, kernel_name | None) for every op with at least
    one registered kernel candidate — the registry decision each path
    would get, since all three paths execute ops through
    OPS.get(type).lowering(ctx) and one select() point."""
    from ..core.types import dtype_to_np
    from ..kernels import registry as kreg
    block = program.block(block_idx)
    cand = set(kreg.candidate_op_types())
    recs = []
    for idx, op in enumerate(block.ops):
        if op.type not in cand:
            continue
        dts: List[str] = []
        shps: List[Tuple[int, ...]] = []
        for n in op.input_arg_names:
            v = block._find_var_recursive(n)
            if v is None or v.shape is None:
                continue
            shps.append(tuple(
                cfg.dynamic_dim if (d is None or int(d) < 0) else int(d)
                for d in v.shape))
            try:
                dts.append(str(np.dtype(dtype_to_np(v.dtype))))
            except Exception:
                dts.append(str(v.dtype))
        sig = kreg.Signature(op.type, tuple(dts), tuple(shps))
        recs.append((idx, op.type,
                     kreg.abstract_select(op.type, sig,
                                          platform=cfg.platform)))
    return tuple(recs)


def _bucket_content(buckets) -> Tuple[Tuple[int, Tuple[str, ...], str],
                                      ...]:
    return tuple((i, tuple(b["names"]), b["dtype"])
                 for i, b in enumerate(buckets))


def _quant_content(buckets, mode: str, stage: str):
    if not mode:
        return ()
    return tuple((i, bool(b["quantized"]), stage)
                 for i, b in enumerate(buckets))


def _planned_buckets(program, block_idx: int, cfg: TraceConfig):
    from ..parallel.comm_scheduler import bucket_plan_records
    return bucket_plan_records(program, block_idx, cfg.bucket_bytes,
                               quantize_mode=cfg.quantize_mode)


def _dygraph_buckets(program, block_idx: int, cfg: TraceConfig):
    from ..parallel.comm_scheduler import (plan_named_buckets,
                                           should_quantize)
    items = _dygraph_grad_items(program, block_idx)
    if not items or cfg.bucket_bytes <= 0:
        return []
    buckets = plan_named_buckets(
        [(n, shape, dt) for n, shape, dt in items], cfg.bucket_bytes)
    return [{"names": tuple(b.names), "dtype": str(np.dtype(b.dtype)),
             "bytes": int(b.bytes),
             "quantized": bool(should_quantize(b.dtype, b.bytes,
                                               cfg.quantize_mode))}
            for b in buckets]


def _parsed_buckets(transpiled_program, cfg: TraceConfig):
    """Read the emitted collective plan off a transpiled program's
    explicit c_allreduce_* ops — the strongest form of the transpiled
    trace (it sees what was actually emitted, not what the planner
    would plan)."""
    from ..core.types import dtype_to_np
    from ..parallel.comm_scheduler import should_quantize
    block = transpiled_program.block(0)
    out = []
    for op in block.ops:
        if op.type not in ("c_allreduce_fused", "c_allreduce_sum"):
            continue
        names = tuple(op.input("X"))
        mode = str(op.attr("quantize", "") or "")
        dt = ""
        nbytes = 0
        for n in names:
            v = block._find_var_recursive(n)
            if v is None or v.shape is None:
                continue
            npdt = np.dtype(dtype_to_np(v.dtype))
            dt = dt or str(npdt)
            shape = [int(d) for d in v.shape if d and int(d) > 0]
            nbytes += int(np.prod(shape)) * npdt.itemsize if shape \
                else npdt.itemsize
        quant = bool(mode) and should_quantize(np.dtype(dt or "f4"),
                                               nbytes, mode)
        out.append({"names": names, "dtype": dt, "bytes": nbytes,
                    "quantized": quant})
    return out


def _guard_content(program, block_idx: int, cfg: TraceConfig,
                   path: str):
    """The stability-guard gate as each path lowers it."""
    plan = None
    if cfg.guard:
        from ..stability.guard import build_plan
        plan = build_plan(program, block_idx)
    grads = tuple(sorted(getattr(plan, "grad_names", ()) or ())) \
        if plan is not None else ()
    if path == "dygraph":
        present = bool(cfg.guard) and bool(
            _grad_items(program, block_idx))
        return _pairs({
            "present": present, "in_trace": False,
            "grads": tuple(sorted(
                n for n, _s, _d in _dygraph_grad_items(
                    program, block_idx))) if present else (),
            "policies": ("nonfinite",) if present else (),
            "spike_ema": False,
        })
    present = plan is not None
    return _pairs({
        "present": present,
        # engine/transpiled gate inside the step trace itself
        "in_trace": True,
        "grads": grads,
        "policies": ("integrity", "nonfinite", "spike") if present
        else (),
        "spike_ema": present,
    })


def _loss_scale_content(program, cfg: TraceConfig, path: str):
    if cfg.loss_scale is not None:
        wants = bool(cfg.loss_scale)
    else:
        wants = getattr(program, "_dynamic_loss_scale", None) is not None
    present = wants and path != "dygraph"
    return _pairs({"present": present})


def _shard_hint_records(program, block_idx: int):
    """(op_idx, op_type, output_slot) for every op whose registered
    lowering routes through core.registry.shard_hint — discovered from
    the lowering source, so new hint sites are picked up without a
    second registry."""
    from ..core.registry import OPS, shard_hinted_slots
    block = program.block(block_idx)
    recs = []
    for idx, op in enumerate(block.ops):
        if not OPS.has(op.type):
            continue
        for slot in shard_hinted_slots(op.type):
            recs.append((idx, op.type, slot))
    return tuple(recs)


def _tier2_content(path: str):
    # what FLAGS_validate_tier>=2 re-verifies on each path: the traced
    # step's lowering (crosscheck_traced) and the collective bucket plan
    return _pairs({"lowering_crosscheck": path != "dygraph",
                   "bucket_plan_verify": True})


def _cache_key_content(path: str):
    if path == "dygraph":
        return _pairs({"mode": "per-callable-memo",
                       "keyed": ("quantize_mode",)})
    return _pairs({"mode": "engine-trace-cache",
                   "keyed": _engine_keyed_names()})


# ---------------------------------------------------------------------------
# trace extraction
# ---------------------------------------------------------------------------

def extract_trace(program, path: str, block_idx: int = 0,
                  fetch_names: Sequence[str] = (),
                  config: Optional[TraceConfig] = None,
                  transpiled_program=None) -> LoweringTrace:
    """The canonical lowering trace of `program` along `path`.

    ``transpiled_program`` (path "transpiled" only): a real transpiled
    clone to read the EMITTED collective plan from; without it the
    transpiler's planning calls are replayed abstractly.
    """
    cfg = config or TraceConfig.capability()
    tr = LoweringTrace(path)
    explicit = _has_explicit_collectives(program, block_idx)
    grads = _grad_items(program, block_idx)
    tr.meta["explicit_collectives"] = explicit
    tr.meta["n_grads"] = len(grads)

    # kernel selection: one select() point serves every path
    tr.record("kernel_selection", True,
              _kernel_records(program, block_idx, cfg),
              note="kernels.registry.select via OPS lowerings "
                   "(shared by all paths)")

    # collective bucket plan
    if path == "engine":
        if explicit:
            tr.record("collective_bucketing", False, (), skip=True,
                      note="program carries explicit collective ops; "
                           "the engine defers to them "
                           "(CommScheduler.for_program returns None)")
            tr.record("collective_quantization", False, (), skip=True,
                      note="see collective_bucketing")
        else:
            buckets = _planned_buckets(program, block_idx, cfg) \
                if cfg.multi_axis and cfg.bucket_bytes > 0 else []
            applies = bool(buckets)
            tr.record("collective_bucketing", applies,
                      _bucket_content(buckets),
                      note="plan_program_buckets over grad production "
                           "order, applied in-trace at comm_points")
            tr.record("collective_quantization",
                      applies and bool(cfg.quantize_mode),
                      _quant_content(buckets, cfg.quantize_mode,
                                     _STAGE_GLOBAL_VIEW),
                      note="emulated collective quantizes the "
                           "global-view reduced value")
    elif path == "transpiled":
        if transpiled_program is not None:
            buckets = _parsed_buckets(transpiled_program, cfg)
            src = "parsed from emitted c_allreduce_* ops"
        else:
            buckets = _planned_buckets(program, block_idx, cfg) \
                if cfg.bucket_bytes > 0 else []
            src = "replayed transpiler planning " \
                  "(plan_program_buckets)"
        applies = cfg.multi_axis and bool(buckets)
        tr.record("collective_bucketing", applies,
                  _bucket_content(buckets) if applies else (),
                  note=src)
        tr.record("collective_quantization",
                  applies and bool(cfg.quantize_mode),
                  _quant_content(buckets, cfg.quantize_mode,
                                 _STAGE_PER_DEVICE) if applies else (),
                  note="c_allreduce_fused quantizes each rank's "
                       "pre-reduction payload")
    else:  # dygraph
        buckets = _dygraph_buckets(program, block_idx, cfg)
        applies = cfg.multi_axis and bool(buckets)
        tr.record("collective_bucketing", applies,
                  _bucket_content(buckets) if applies else (),
                  note="plan_named_buckets over reversed parameter-"
                       "creation order (apply_collective_grads)")
        tr.record("collective_quantization",
                  applies and bool(cfg.quantize_mode),
                  _quant_content(buckets, cfg.quantize_mode,
                                 _STAGE_PER_DEVICE) if applies else (),
                  note="fused_stacked_sum quantizes the pre-reduction "
                       "rows")

    # stability guard + loss scale
    tr.record("stability_guard", cfg.guard,
              _guard_content(program, block_idx, cfg, path))
    tr.record("loss_scale", True,
              _loss_scale_content(program, cfg, path))

    # shard hints: only a live engine mesh + strategy activation scope
    # makes shard_hint() bind
    hints = _shard_hint_records(program, block_idx)
    if path == "engine":
        tr.record("shard_hints", cfg.multi_axis,
                  hints if cfg.multi_axis else (),
                  note="bound inside parallel.strategy "
                       "activation_scope on the mesh path")
    else:
        tr.record("shard_hints", False, (),
                  note="no activation scope on this path")

    # multi-step dispatch (PT_MULTI_STEP): only the engine whole-block
    # trace compiles the K-substep scan driver; the other paths
    # dispatch per step (declared in analysis/support_matrix.py)
    if path == "engine":
        tr.record("multi_step", True,
                  ("driver=scan-carry-freeze",
                   "early_exit=guard-verdict",
                   "per_substep_phase_spans=false"),
                  note="lax.scan over K stacked feed batches; a guard "
                       "verdict freezes the carry for early break-out "
                       "(core/engine.py trace_step)")
    elif path == "transpiled":
        tr.record("multi_step", False, (),
                  note="explicit-collective programs dispatch per "
                       "step; no scan driver is emitted")
    else:  # dygraph
        tr.record("multi_step", False, (),
                  note="eager execution has no compiled step to scan")

    # serving export (inference/serving, docs/SERVING.md): only the
    # engine whole-block trace can be frozen into the bucketed
    # prefill/decode executables + AOT StableHLO artifacts the
    # continuous-batching engine dispatches (declared in
    # analysis/support_matrix.py)
    if path == "engine":
        tr.record("serving", True,
                  ("export=trace_step-whole-block",
                   "signatures=bucketed-batch-seq",
                   "artifact=stablehlo-aot",
                   "sharding=meshspec-speclayout"),
                  note="frozen program exports through the predictor's "
                       "trace_step + __aot__ path with fixed bucketed "
                       "signatures (inference/serving/export.py)")
    elif path == "transpiled":
        tr.record("serving", False, (),
                  note="serving shards inside one traced executable "
                       "(MeshSpec/SpecLayout); no explicit-collective "
                       "program is emitted")
    else:  # dygraph
        tr.record("serving", False, (),
                  note="no Program to freeze, no trace to serialize")

    # pipeline parallelism (pp mesh axis, docs/PARALLELISM.md): only
    # the engine path hosts the stage-cut engines — SPMD GPipe over
    # the pp axis and MPMD 1F1B per-stage dispatch, both fed by the
    # automatic cutter (declared in analysis/support_matrix.py)
    if path == "engine":
        tr.record("pipeline", True,
                  ("cutter=auto-cost-model",
                   "schedule=1f1b-interleaved",
                   "axis=pp-outermost",
                   "hazards=cross-stage-verified"),
                  note="propose_cuts synthesizes the stage boundary, "
                       "verify_stage_partition + the 1F1B slot-table "
                       "verifier gate engine construction "
                       "(parallel/auto_cut.py, analysis/races.py)")
    elif path == "transpiled":
        tr.record("pipeline", False, (),
                  note="no transpiler pass splits a block into stage "
                       "programs or emits send/recv pairs")
    else:  # dygraph
        tr.record("pipeline", False, (),
                  note="no Program to cut, no schedule to verify")

    # cache keying + tier-2 verifier coverage
    tr.record("cache_key", True, _cache_key_content(path))
    tr.record("tier2_verifier", True, _tier2_content(path))
    return tr


def extract_traces(program, block_idx: int = 0,
                   fetch_names: Sequence[str] = (),
                   config: Optional[TraceConfig] = None,
                   transpiled_program=None,
                   paths: Sequence[str] = PATHS
                   ) -> Dict[str, LoweringTrace]:
    cfg = config or TraceConfig.capability()
    return {p: extract_trace(program, p, block_idx, fetch_names, cfg,
                             transpiled_program=transpiled_program
                             if p == "transpiled" else None)
            for p in paths}


# ---------------------------------------------------------------------------
# diffing
# ---------------------------------------------------------------------------

def _content_delta(a, b) -> str:
    """Short human description of how two content records differ."""
    try:
        sa, sb = set(a), set(b)
        only_a = sorted(map(repr, sa - sb))[:3]
        only_b = sorted(map(repr, sb - sa))[:3]
        bits = []
        if only_a:
            bits.append("only-left: " + ", ".join(only_a))
        if only_b:
            bits.append("only-right: " + ", ".join(only_b))
        if bits:
            return "; ".join(bits)
    except TypeError:
        pass
    return f"left={a!r} right={b!r}"


def diff_traces(traces: Dict[str, LoweringTrace],
                matrix: Optional[SupportMatrix] = None,
                label: str = "",
                note_stale: bool = False) -> List[Diagnostic]:
    """Pairwise trace diff against the declared support matrix."""
    matrix = matrix or default_matrix()
    paths = [p for p in PATHS if p in traces]
    diags: List[Diagnostic] = []
    observed: set = set()
    for feature in FEATURES:
        for i in range(len(paths)):
            for j in range(i + 1, len(paths)):
                pa, pb = paths[i], paths[j]
                ra = traces[pa].features.get(feature)
                rb = traces[pb].features.get(feature)
                if ra is None or rb is None:
                    continue
                if ra.get("skip") or rb.get("skip"):
                    continue
                if _key(ra) == _key(rb):
                    continue
                status = worst_status(matrix.status(feature, pa),
                                      matrix.status(feature, pb))
                observed.add((feature, pa))
                observed.add((feature, pb))
                if status == SUPPORTED:
                    diags.append(Diagnostic(
                        Severity.ERROR, PASS_NAME,
                        f"undeclared lowering divergence: feature "
                        f"'{feature}' lowers differently on paths "
                        f"'{pa}' and '{pb}' "
                        f"({_content_delta(ra['content'], rb['content'])}); "
                        f"either fix the drift or declare the cell in "
                        f"analysis/support_matrix.py with a "
                        f"justification",
                        program_label=label))
                else:
                    gapped = pb if matrix.status(feature, pb) != \
                        SUPPORTED else pa
                    diags.append(Diagnostic(
                        Severity.INFO, PASS_NAME,
                        f"declared divergence ({status}): feature "
                        f"'{feature}' differs between '{pa}' and "
                        f"'{pb}' — "
                        f"{matrix.justification(feature, gapped)}",
                        program_label=label))
    if note_stale:
        for feature, path, status, _why in matrix.declared_cells():
            if (feature, path) in observed or path not in traces:
                continue
            ref = traces.get("engine", traces[paths[0]]) \
                .features.get(feature)
            if ref is None or not ref["applies"]:
                continue
            diags.append(Diagnostic(
                Severity.INFO, PASS_NAME,
                f"support-matrix cell ({feature}, {path}) is declared "
                f"{status} but no divergence was observed on this "
                f"program — candidate for retirement if this holds "
                f"across the model suite", program_label=label))
    return diags


# ---------------------------------------------------------------------------
# verification entry points
# ---------------------------------------------------------------------------

def _self_check(ref: LoweringTrace, given: LoweringTrace,
                label: str) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    for feature in FEATURES:
        ra = ref.features.get(feature)
        rb = given.features.get(feature)
        if ra is None or rb is None or ra.get("skip") or \
                rb.get("skip"):
            continue
        if _key(ra) != _key(rb):
            diags.append(Diagnostic(
                Severity.ERROR, PASS_NAME,
                f"lowering drift within path '{ref.path}': the "
                f"supplied trace of feature '{feature}' does not "
                f"match what the path's lowering hooks declare "
                f"({_content_delta(ra['content'], rb['content'])})",
                program_label=label))
    return diags


def verify_conformance(program, block_idx: int = 0,
                       fetch_names: Sequence[str] = (),
                       config: Optional[TraceConfig] = None,
                       traces: Optional[Dict[str, LoweringTrace]] = None,
                       transpiled_program=None,
                       matrix: Optional[SupportMatrix] = None,
                       label: str = "",
                       note_stale: bool = False) -> List[Diagnostic]:
    """Prove the three paths lower `program` the same way, modulo the
    declared support matrix.  Returns diagnostics; ERROR = undeclared
    drift.

    When ``traces`` is supplied (CLI / tier-2 callers), each trace is
    first checked against a fresh extraction for its own path — so a
    trace captured from a path that dropped a bucket, skipped the guard
    gate, or lost a shard hint fails even when every cross-path cell is
    declared.
    """
    t0 = time.perf_counter()
    cfg = config or TraceConfig.capability()
    matrix = matrix or default_matrix()
    base = extract_traces(program, block_idx, fetch_names, cfg,
                          transpiled_program=transpiled_program)
    diags: List[Diagnostic] = []
    if traces is not None:
        for path in PATHS:
            if path in traces and path in base:
                diags.extend(_self_check(base[path], traces[path],
                                         label))
    else:
        traces = base
    diags.extend(diff_traces(traces, matrix, label, note_stale))
    _emit_metrics(diags, time.perf_counter() - t0)
    return diags


def conformance_summary(diags: Sequence[Diagnostic]) -> Dict[str, int]:
    mine = [d for d in diags if d.pass_name == PASS_NAME]
    return {
        "undeclared": sum(1 for d in mine
                          if d.severity == Severity.ERROR),
        "declared": sum(1 for d in mine
                        if d.severity == Severity.INFO and
                        d.message.startswith("declared divergence")),
    }


def _emit_metrics(diags: Sequence[Diagnostic], seconds: float) -> None:
    try:
        from ..observability import metrics as _m
        if not _m.telemetry_active():
            return
        s = conformance_summary(diags)
        _m.counter("pt_conformance_checks_total").inc(1)
        if s["declared"]:
            _m.counter("pt_conformance_divergences_total").inc(
                s["declared"], declared="yes")
        if s["undeclared"]:
            _m.counter("pt_conformance_divergences_total").inc(
                s["undeclared"], declared="no")
        _m.gauge("pt_conformance_verify_seconds").set(seconds)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# tier-2 runtime cross-check (engine path)
# ---------------------------------------------------------------------------

def crosscheck_traced(program, block_idx: int, traced, mesh=None,
                      data_axis: str = "dp", strategy=None,
                      label: str = "traced step") -> None:
    """Compare the STATIC engine-path lowering trace against the step
    the engine ACTUALLY traced.  Raises EnforceNotMet on mismatch.

    Checks, under the LIVE flag/mesh state:
    * guard gate presence + gated grad set vs ``traced.guard_plan``;
    * the static bucket plan's count/bytes/quantized-count vs the
      ``comm_stats`` attached to the traced step.
    """
    from ..core.flags import FLAGS
    problems: List[str] = []

    # guard gate
    static_plan = None
    if FLAGS.stability_guard:
        from ..stability.guard import build_plan
        static_plan = build_plan(program, block_idx)
    actual_plan = getattr(traced, "guard_plan", None)
    if (static_plan is None) != (actual_plan is None):
        problems.append(
            f"stability-guard gate: static lowering says "
            f"{'present' if static_plan is not None else 'absent'}, "
            f"traced step has it "
            f"{'present' if actual_plan is not None else 'absent'}")
    elif static_plan is not None and actual_plan is not None:
        sg = tuple(sorted(getattr(static_plan, "grad_names", ()) or ()))
        ag = tuple(sorted(getattr(actual_plan, "grad_names", ()) or ()))
        if sg != ag:
            problems.append(
                f"stability-guard gated grads differ: static {sg} "
                f"vs traced {ag}")

    # collective plan — mirror engine.trace_step exactly: the plan
    # (or the static census of explicit collective ops) exists only
    # under a multi-device mesh
    expected = None
    if mesh is not None and getattr(mesh, "size", 1) > 1:
        from ..parallel.comm_scheduler import (CommScheduler,
                                               static_collective_stats)
        sched = CommScheduler.for_program(program, block_idx, mesh,
                                          data_axis, strategy)
        expected = sched.stats if sched is not None \
            else static_collective_stats(program, block_idx)
    actual = getattr(traced, "comm_stats", None)
    if (expected is None) != (actual is None):
        problems.append(
            f"collective plan: static lowering "
            f"{'plans buckets' if expected else 'plans none'}, traced "
            f"step carries "
            f"{'a plan' if actual else 'none'}")
    elif expected is not None and actual is not None:
        for k in ("buckets", "quantized"):
            if k in expected and k in actual and \
                    int(expected[k]) != int(actual[k]):
                problems.append(
                    f"collective plan {k}: static {expected[k]} vs "
                    f"traced {actual[k]}")

    if problems:
        from ..core.enforce import EnforceNotMet
        lines = "\n".join(f"  - {p}" for p in problems)
        raise EnforceNotMet(
            f"cross-path conformance check failed for {label} "
            f"(tier 2): the engine's actually-traced step disagrees "
            f"with the static lowering trace\n{lines}")


# ---------------------------------------------------------------------------
# drift injection (lint_program self-test + tests)
# ---------------------------------------------------------------------------

DRIFT_KINDS = ("dropped_bucket", "skipped_guard", "missing_shard_hint")


def inject_drift(traces: Dict[str, LoweringTrace], kind: str) -> str:
    """Mutate `traces` in place to simulate a lowering regression on
    one path (a path dropping a bucket member, skipping the guard
    gate, or losing a shard hint).  Returns a description of what was
    injected; ``verify_conformance(..., traces=traces)`` must then
    report an ERROR."""
    if kind == "dropped_bucket":
        rec = traces["transpiled"].features["collective_bucketing"]
        content = list(rec["content"])
        if not content:
            raise ValueError(
                "program has no gradient buckets to drop (enable "
                "bucketing / use a model with parameters)")
        i, names, dt = content[0]
        if len(names) > 1:
            content[0] = (i, names[:-1], dt)
            what = f"dropped member {names[-1]!r} from bucket {i}"
        else:
            content.pop(0)
            what = f"dropped bucket {i} ({names[0]!r})"
        rec["content"] = tuple(content)
        return f"transpiled: {what}"
    if kind == "skipped_guard":
        rec = traces["transpiled"].features["stability_guard"]
        c = dict(rec["content"])
        c["present"] = False
        c["grads"] = ()
        c["policies"] = ()
        c["spike_ema"] = False
        rec["content"] = _pairs(c)
        return "transpiled: stability-guard gate skipped"
    if kind == "missing_shard_hint":
        rec = traces["engine"].features["shard_hints"]
        content = list(rec["content"])
        if not content:
            raise ValueError(
                "program has no shard-hinted ops (needs a matmul/"
                "softmax-bearing model and a multi-axis config)")
        dropped = content.pop(0)
        rec["content"] = tuple(content)
        return (f"engine: shard hint on op #{dropped[0]} "
                f"({dropped[1]}/{dropped[2]}) not attached")
    raise ValueError(f"unknown drift kind {kind!r}; "
                     f"known: {DRIFT_KINDS}")


# ---------------------------------------------------------------------------
# pass registration (analyze_program / tier-1 validation)
# ---------------------------------------------------------------------------

from .passes import register_analysis_pass

# fingerprint + fetch set -> filtered diagnostics; the trace diff is a
# pure function of the program under the capability config, so repeated
# analyze_program calls (tier-1 validation caches miss on feed-set
# changes, tests re-lint the same model) pay extraction once
_PASS_CACHE: Dict[tuple, List[Diagnostic]] = {}


@register_analysis_pass("conformance")
def conformance_pass(ctx) -> List[Diagnostic]:
    """Cross-path lowering conformance as a standard analysis pass.

    Runs under the capability config (every feature armed) so the diff
    is flag-independent.  Declared (INFO) divergences are filtered out
    here — in the standard pipeline only NEW drift should surface; the
    full declared-gap report stays available through
    ``verify_conformance`` directly (lint_program --check-conformance).
    """
    try:
        key = None
        fp = getattr(ctx.program, "fingerprint", None)
        if fp is not None:
            key = (fp, frozenset(ctx.fetch_names or ()))
            hit = _PASS_CACHE.get(key)
            if hit is not None:
                return list(hit)
        diags = verify_conformance(ctx.program,
                                   fetch_names=ctx.fetch_names or (),
                                   label=ctx.label)
        out = [d for d in diags if d.severity >= Severity.WARNING]
        if key is not None:
            if len(_PASS_CACHE) > 256:
                _PASS_CACHE.clear()
            _PASS_CACHE[key] = list(out)
        return out
    except Exception as exc:  # never let extraction break validation
        return [Diagnostic(
            Severity.WARNING, PASS_NAME,
            f"conformance extraction failed: "
            f"{type(exc).__name__}: {exc}",
            program_label=ctx.label)]
