"""Static analysis over the Program IR (the Python analog of the
reference's ``framework/ir`` + ``inference/analysis`` verification
layer). See ``passes.py`` for the pass pipeline, ``races.py`` /
``memplan.py`` / ``cost_model.py`` for the verifier pass families
(engine-state + donation hazards, the static HBM planner, the per-op
cost model), ``validate.py`` for the flag-gated executor/engine hooks,
and ``tools/lint_program.py`` for the CLI front-end.

``analysis.cost`` is the stable alias for the cost-model module — the
API surface ROADMAP item 1's placement search consumes.
"""
from .diagnostics import (Diagnostic, Severity, format_report, has_errors,
                          max_severity, split_by_severity)
from .def_use import DefUseGraph, Site, sub_block_indices
from .passes import (AnalysisContext, COLLECTIVE_OP_TYPES, analysis_passes,
                     analyze_program, analyze_shard_programs,
                     check_collective_ordering, register_analysis_pass)
from .validate import (clear_validation_cache, validate_cached,
                       validate_collective_plan, validate_program)
from .conformance import (LoweringTrace, TraceConfig,
                          conformance_summary, crosscheck_traced,
                          diff_traces, extract_trace, extract_traces,
                          inject_drift, verify_conformance)
from .support_matrix import SupportMatrix, default_matrix
from . import conformance, support_matrix
from .races import donation_plan
from .memplan import MemoryPlan, plan_memory, reconcile
from .cost_model import OpCost, ProgramCost, program_cost
from . import cost_model as cost
from .placement import (PlacementPlan, plan_for_program,
                        search_placement, strategy_for_plan)
from . import placement

__all__ = [
    "Diagnostic", "Severity", "format_report", "has_errors",
    "max_severity", "split_by_severity",
    "DefUseGraph", "Site", "sub_block_indices",
    "AnalysisContext", "COLLECTIVE_OP_TYPES", "analysis_passes",
    "analyze_program", "analyze_shard_programs",
    "check_collective_ordering", "register_analysis_pass",
    "clear_validation_cache", "validate_cached",
    "validate_collective_plan", "validate_program",
    "LoweringTrace", "TraceConfig", "conformance_summary",
    "crosscheck_traced", "diff_traces",
    "extract_trace", "extract_traces", "inject_drift",
    "verify_conformance", "conformance",
    "SupportMatrix", "default_matrix", "support_matrix",
    "donation_plan",
    "MemoryPlan", "plan_memory", "reconcile",
    "OpCost", "ProgramCost", "program_cost",
    "cost",
    "PlacementPlan", "plan_for_program", "search_placement",
    "strategy_for_plan", "placement",
]
