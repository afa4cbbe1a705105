"""Declared feature × execution-path support matrix (docs/STATIC_ANALYSIS.md).

The framework lowers every program along one of three paths — the
engine whole-block jit trace, the transpiler-emitted explicit-collective
program, and eager dygraph — and
ROADMAP item 5 records that keeping those paths in agreement by hand is
the dominant cost of every feature.  This module is the *contract* half
of the conformance verifier (analysis/conformance.py): for every
(feature, path) cell it declares

* ``supported``   — the path lowers the feature exactly like the
                    reference engine path; any observed divergence is
                    NEW drift and an ERROR;
* ``degraded``    — the path carries the feature with a known, justified
                    difference (the justification string says what and
                    why); observed divergence is expected and reported
                    as INFO;
* ``unsupported`` — the path structurally cannot carry the feature
                    today; the justification says which gate forbids it.

Every ``degraded``/``unsupported`` cell is a burn-down item for the
item-5 "one lowering pipeline" refactor: retiring a cell means making
the paths agree, flipping the cell to ``supported``, and letting the
conformance diff prove it stays that way.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

__all__ = [
    "PATHS", "FEATURES", "SUPPORTED", "DEGRADED", "UNSUPPORTED",
    "STATUSES", "SupportMatrix", "default_matrix",
]

# Execution paths, in reference order: "engine" is the semantics the
# other paths are compared against.
PATHS: Tuple[str, ...] = ("engine", "transpiled", "dygraph")

# Lowering decisions the conformance trace records per path.
FEATURES: Tuple[str, ...] = (
    "kernel_selection",          # which custom kernel select() routes to
    "collective_bucketing",      # grad bucket membership + order + dtype
    "collective_quantization",   # per-bucket quantize decision + stage
    "stability_guard",           # verdict/gate placement + policy set
    "loss_scale",                # dynamic loss-scale wrap of the update
    "shard_hints",               # multi-axis sharding constraints attached
    "cache_key",                 # which knobs key the compiled artifact
    "tier2_verifier",            # runtime re-verification coverage
    "multi_step",                # PT_MULTI_STEP K-substep scan driver
    "serving",                   # frozen-program serving export
    "pipeline",                  # pp mesh axis: stage cutting + 1F1B
)

SUPPORTED = "supported"
DEGRADED = "degraded"
UNSUPPORTED = "unsupported"
STATUSES: Tuple[str, ...] = (SUPPORTED, DEGRADED, UNSUPPORTED)


class SupportMatrix:
    """feature × path → (status, justification).

    Cells default to ``supported`` with an empty justification; every
    ``degraded``/``unsupported`` cell MUST carry a non-empty
    justification (``validate()`` enforces it, and the round-trip test
    keeps it enforced).
    """

    def __init__(self):
        self._cells: Dict[Tuple[str, str], Tuple[str, str]] = {}

    def declare(self, feature: str, path: str, status: str,
                justification: str = "") -> "SupportMatrix":
        if feature not in FEATURES:
            raise ValueError(f"unknown feature {feature!r}; "
                             f"known: {FEATURES}")
        if path not in PATHS:
            raise ValueError(f"unknown path {path!r}; known: {PATHS}")
        if status not in STATUSES:
            raise ValueError(f"unknown status {status!r}; "
                             f"known: {STATUSES}")
        self._cells[(feature, path)] = (status, justification)
        return self

    def status(self, feature: str, path: str) -> str:
        return self._cells.get((feature, path), (SUPPORTED, ""))[0]

    def justification(self, feature: str, path: str) -> str:
        return self._cells.get((feature, path), (SUPPORTED, ""))[1]

    def declared_cells(self) -> List[Tuple[str, str, str, str]]:
        """Every non-default cell as (feature, path, status, why)."""
        return [(f, p, s, j)
                for (f, p), (s, j) in sorted(self._cells.items())]

    def validate(self) -> List[str]:
        """Contract check: every non-supported cell needs a written
        justification.  Returns problem strings (empty = valid)."""
        problems = []
        for (f, p), (s, j) in sorted(self._cells.items()):
            if s != SUPPORTED and not j.strip():
                problems.append(
                    f"cell ({f}, {p}) is {s} but has no justification")
        return problems

    def to_dict(self) -> Dict[str, Dict[str, Dict[str, str]]]:
        """Full matrix (defaults included) for JSON tails / docs."""
        out: Dict[str, Dict[str, Dict[str, str]]] = {}
        for f in FEATURES:
            out[f] = {}
            for p in PATHS:
                out[f][p] = {"status": self.status(f, p),
                             "justification": self.justification(f, p)}
        return out

    @classmethod
    def from_dict(cls, d) -> "SupportMatrix":
        m = cls()
        for f, row in d.items():
            for p, cell in row.items():
                if cell["status"] != SUPPORTED or \
                        cell.get("justification"):
                    m.declare(f, p, cell["status"],
                              cell.get("justification", ""))
        return m


def worst_status(*statuses: str) -> str:
    """The least-supported of the given statuses (supported < degraded
    < unsupported)."""
    order = {SUPPORTED: 0, DEGRADED: 1, UNSUPPORTED: 2}
    return max(statuses, key=lambda s: order[s])


def default_matrix() -> SupportMatrix:
    """The declared state of this codebase today — every cell below is
    a divergence the conformance verifier OBSERVES (or would observe
    when the feature is exercised) and that the item-5 refactor must
    either fix or keep justified."""
    m = SupportMatrix()

    # -- transpiled programs: engine semantics, except sharding hints.
    m.declare(
        "shard_hints", "transpiled", UNSUPPORTED,
        "transpiled programs run process-level SPMD (one process per "
        "rank, collectives as explicit c_* ops); there is no jit mesh "
        "for with_sharding_constraint to bind to, so shard_hint() is "
        "structurally a no-op (transpiler/collective.py).")

    # -- dygraph: eager per-op execution.
    m.declare(
        "collective_bucketing", "dygraph", DEGRADED,
        "apply_collective_grads plans buckets over the REVERSED "
        "parameter-creation order of live grads rather than the "
        "program's grad-production order; the two coincide for "
        "sequential models but can reorder under graph-level "
        "scheduling, shifting bucket boundaries (and with them "
        "per-bucket quantization scale groups) "
        "(dygraph/parallel.py).")
    m.declare(
        "stability_guard", "dygraph", DEGRADED,
        "_guard_reduced is a host-side np.isfinite check on each "
        "reduced bucket: the nonfinite policy honors skip/abort only "
        "(clip/rescale/rollback degrade to skip), and there is no "
        "spike EMA and no traced verdict/gate vars "
        "(dygraph/parallel.py).")
    m.declare(
        "loss_scale", "dygraph", UNSUPPORTED,
        "dynamic loss scale rides Program._dynamic_loss_scale "
        "metadata consumed by GuardPlan; eager mode has no Program, "
        "so no loss-scale state exists on this path "
        "(stability/guard.py build_plan).")
    m.declare(
        "shard_hints", "dygraph", UNSUPPORTED,
        "dygraph executes ops eagerly outside any activation_scope; "
        "core.registry.shard_hint returns its input unchanged without "
        "one.")
    m.declare(
        "cache_key", "dygraph", DEGRADED,
        "no program-level trace cache exists: only the fused "
        "all-reduce callable is memoized, keyed by quantize mode "
        "(DataParallel._fused_fn), so other FLAGS flips take effect "
        "on the next call instead of being folded into a step key.")
    m.declare(
        "tier2_verifier", "dygraph", DEGRADED,
        "tier-2 re-verification covers the collective bucket plan "
        "(analysis.validate.validate_collective_plan) but there is "
        "no traced step to cross-check against the static lowering "
        "(analysis.conformance.crosscheck_traced).")

    # -- engine in-trace collectives: emulated global view.
    m.declare(
        "collective_quantization", "engine", DEGRADED,
        "global-view in-trace collectives EMULATE the all-reduce, so "
        "quantization applies to the logically-reduced value rather "
        "than to each device's pre-reduction payload as on the "
        "transpiled/dygraph per-device paths; the quantize DECISION "
        "(should_quantize) is shared, the wire format is not "
        "(parallel/comm_scheduler.py _apply_bucket vs "
        "ops/collective.py c_allreduce_fused).")

    # -- multi-step dispatch (PT_MULTI_STEP, docs/ASYNC_DISPATCH.md):
    #    only the engine whole-block trace compiles the K-substep scan
    #    driver, and even there observability is coarser per substep.
    m.declare(
        "multi_step", "engine", DEGRADED,
        "the K-substep lax.scan driver runs bit-identical to K "
        "sequential steps, but per-substep flight-recorder phase "
        "spans collapse into ONE dispatch span (the recorder sees one "
        "run()), ghost-snapshot cadence counts slabs rather than "
        "substeps, and a guard-on slab pays one verdict sync per slab "
        "with the whole-slab re-dispatch standing in for per-step "
        "re-execution (core/engine.py trace_step multi-step branch).")
    m.declare(
        "multi_step", "transpiled", UNSUPPORTED,
        "transpiled programs run process-level SPMD with explicit "
        "c_* collective ops executed per step; no jitted scan driver "
        "exists to fuse K substeps (transpiler/collective.py).")
    m.declare(
        "multi_step", "dygraph", UNSUPPORTED,
        "eager per-op execution has no compiled step to scan; K "
        "substeps are simply K eager steps (dygraph/parallel.py).")

    # -- serving export (inference/serving, docs/SERVING.md): only the
    #    engine whole-block trace can be frozen into the bucketed
    #    prefill/decode executables the continuous-batching engine
    #    dispatches.
    m.declare(
        "serving", "transpiled", UNSUPPORTED,
        "transpiled programs are process-level SPMD training "
        "programs with explicit c_* collective ops; serving shards "
        "through MeshSpec/SpecLayout inside one traced executable "
        "instead, so there is nothing for the transpiler to emit "
        "(inference/serving/export.py).")
    m.declare(
        "serving", "dygraph", UNSUPPORTED,
        "the serving contract is a FROZEN Program with stable feed/"
        "fetch signatures and AOT StableHLO artifacts; eager dygraph "
        "has no Program to freeze and no trace to serialize "
        "(inference/serving/export.py).")

    # -- pipeline parallelism (pp mesh axis, docs/PARALLELISM.md): the
    #    engine path carries it through the dedicated pipeline engines
    #    (SPMD GPipe over the pp axis, MPMD 1F1B per-stage dispatch),
    #    both fed by the same automatic stage cutter.  No other path
    #    can host a cut program.
    m.declare(
        "pipeline", "transpiled", UNSUPPORTED,
        "the transpiler emits process-level SPMD programs with "
        "explicit c_* collective ops; it has no pass that splits a "
        "block at cut activations into per-rank stage programs or "
        "emits the send/recv pairs a 1F1B schedule needs "
        "(transpiler/collective.py).")
    m.declare(
        "pipeline", "dygraph", UNSUPPORTED,
        "stage cutting is a static Program transform "
        "(parallel/auto_cut.py propose_cuts walks block ops); eager "
        "dygraph has no Program to cut and no schedule to verify "
        "(dygraph/parallel.py).")

    assert not m.validate()
    return m
