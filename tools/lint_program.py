"""lint_program: run the static analyzer over a Program and report.

The front-end of ``paddle_tpu/analysis`` (the Python analog of the
reference's C++ ``framework/ir`` verification passes). Lints either a
serialized ProgramDesc or a named book model built in-process, prints
every diagnostic (severity, pass, op type, var names, block/op
location), and exits non-zero when any error-severity finding exists —
suitable for CI gating of exported models.

Usage:
  python tools/lint_program.py --model mlp
  python tools/lint_program.py --model fit_a_line --inject dangling_read
  python tools/lint_program.py --program /path/to/__model__ --fetch y
  python tools/lint_program.py --model mlp --shards 2 \
      --inject shuffled_collectives
  python tools/lint_program.py --model mlp --check-races
  python tools/lint_program.py --model mlp --check-memory 2e9 --batch 64
  python tools/lint_program.py --model mlp --check-cost
  python tools/lint_program.py --model mlp --check-conformance
  python tools/lint_program.py --model mlp --check-conformance \
      --inject dropped_bucket
  python tools/lint_program.py --all-models

``--inject`` corrupts the program before linting (dev aid + the CLI's
own test fixture): dangling_read, dtype_mismatch, dead_output,
shuffled_collectives (needs --shards >= 2).
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import paddle_tpu as fluid                              # noqa: E402
from paddle_tpu import layers                           # noqa: E402
from paddle_tpu.analysis import (analysis_passes, analyze_program,  # noqa: E402
                                 analyze_shard_programs, format_report,
                                 has_errors)

EXIT_CLEAN = 0
EXIT_ERRORS = 1
EXIT_USAGE = 2


# ---------------------------------------------------------------------------
# named model builders (the book suite's standard nets)
# ---------------------------------------------------------------------------

def _build_mlp():
    img = layers.data("img", [784], dtype="float32")
    label = layers.data("label", [1], dtype="int64")
    h = layers.fc(img, 64, act="relu")
    h = layers.fc(h, 64, act="relu")
    pred = layers.fc(h, 10, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    return ["img", "label"], loss


def _build_conv():
    img = layers.data("img", [1, 28, 28], dtype="float32")
    label = layers.data("label", [1], dtype="int64")
    c1 = layers.conv2d(img, 8, 5, act="relu")
    p1 = layers.pool2d(c1, 2, "max", 2)
    c2 = layers.conv2d(p1, 16, 5, act="relu")
    p2 = layers.pool2d(c2, 2, "max", 2)
    pred = layers.fc(p2, 10, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    return ["img", "label"], loss


def _build_fit_a_line():
    x = layers.data("x", [13], dtype="float32")
    y = layers.data("y", [1], dtype="float32")
    pred = layers.fc(x, 1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    return ["x", "y"], loss


MODELS = {"mlp": _build_mlp, "conv": _build_conv,
          "fit_a_line": _build_fit_a_line}


def build_model(name: str, optimize: bool = True):
    """(main, startup, feed_names, loss) for a named book model."""
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        feed_names, loss = MODELS[name]()
        if optimize:
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, feed_names, loss


# ---------------------------------------------------------------------------
# defect injection
# ---------------------------------------------------------------------------

def inject_defect(program, kind: str):
    """Corrupt `program` in place; returns a short description."""
    block = program.global_block()
    if kind == "dangling_read":
        for op in block.ops:
            if op.input_slots():
                slot = op.input_slots()[0]
                op._inputs[slot] = ["__lint_ghost__"]
                program._bump_version()
                return (f"op '{op.type}' now reads undefined var "
                        f"'__lint_ghost__'")
        raise ValueError("no op with inputs to corrupt")
    if kind == "dtype_mismatch":
        from paddle_tpu.core.types import convert_dtype
        for op in block.ops:
            if op.type in ("elementwise_add", "mul", "matmul"):
                out = op.output("Out")[0]
                block.vars[out].dtype = convert_dtype("int64")
                program._bump_version()
                return (f"declared dtype of '{out}' flipped to int64 "
                        f"under op '{op.type}'")
        raise ValueError("no elementwise_add/mul/matmul op to corrupt")
    if kind == "dead_output":
        with fluid.program_guard(program):
            feeds = [v for v in block.vars.values()
                     if getattr(v, "is_data", False)]
            src = feeds[0] if feeds else next(iter(block.vars.values()))
            dead = layers.fc(src, 3)
        return f"appended an fc whose output '{dead.name}' is never read"
    if kind == "shuffled_collectives":
        idxs = [i for i, op in enumerate(block.ops)
                if op.type.startswith("c_allreduce")]
        if len(idxs) < 2:
            raise ValueError("fewer than 2 collectives; use --shards 2")
        i, j = idxs[0], idxs[1]
        block.ops[i], block.ops[j] = block.ops[j], block.ops[i]
        program._bump_version()
        return f"swapped collectives at op #{i} and op #{j}"
    raise ValueError(f"unknown injection {kind!r}")


def transpile_shards(model: str, n_shards: int, bucket_mb=None):
    """Build `model` once per rank and run the collective transpiler.

    ``bucket_mb`` routes to GradAllReduce(bucket_mb=...): 0 forces the
    per-tensor c_allreduce_sum layout, None follows
    FLAGS_allreduce_bucket_mb (bucketed c_allreduce_fused by default).
    """
    from paddle_tpu.transpiler.collective import GradAllReduce
    eps = [f"127.0.0.1:{6170 + i}" for i in range(n_shards)]
    shards, feed_names, loss_name = [], None, None
    for rank in range(n_shards):
        main, startup, feed_names, loss = build_model(model)
        GradAllReduce(bucket_mb=bucket_mb).transpile(
            startup_program=startup, main_program=main, rank=rank,
            endpoints=eps, current_endpoint=eps[rank], wait_port=False)
        shards.append(main)
        loss_name = loss.name
    return shards, feed_names, loss_name


def load_serialized_program(path: str):
    """(Program, meta|None) from either an inference-model ``__model__``
    container (version + feed/fetch meta + ProgramDesc, io.py) or raw
    ProgramDesc bytes."""
    import json
    import struct
    from paddle_tpu.core.op_version import check_program
    from paddle_tpu.proto import framework_pb2 as fpb

    def _parse(raw):
        proto = fpb.ProgramDesc()
        proto.ParseFromString(raw)
        check_program(proto)   # version gate + strip @OP_VERSIONS@
        return fluid.Program.from_proto(proto)

    with open(path, "rb") as f:
        blob = f.read()
    try:
        (ver,) = struct.unpack_from("<I", blob, 0)
        (meta_len,) = struct.unpack_from("<I", blob, 4)
        if ver in (1, 2) and 8 + meta_len < len(blob):
            meta = None
            if ver == 2:
                meta = json.loads(blob[8:8 + meta_len].decode("utf-8"))
                if not (isinstance(meta, dict) and "feed" in meta):
                    raise ValueError("not an inference-model container")
            # ver 1 framed pickle metadata: skip it UNREAD — a lint tool
            # must not unpickle an untrusted model file
            return _parse(blob[8 + meta_len:]), meta
    except Exception:
        pass
    return _parse(blob), None


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _parser():
    p = argparse.ArgumentParser(
        prog="lint_program",
        description="static analysis over a paddle_tpu Program")
    src = p.add_mutually_exclusive_group(required=False)
    src.add_argument("--model", choices=sorted(MODELS),
                     help="build this book model in-process and lint it")
    src.add_argument("--program", metavar="FILE",
                     help="path to a serialized ProgramDesc (the "
                          "__model__ file save_inference_model writes)")
    p.add_argument("--fetch", nargs="*", default=None, metavar="NAME",
                   help="fetch targets to check reachability for "
                        "(default: the model's loss when --model)")
    p.add_argument("--inject", choices=["dangling_read", "dtype_mismatch",
                                        "dead_output",
                                        "shuffled_collectives",
                                        "cross_stage_hazard",
                                        "dropped_bucket",
                                        "skipped_guard",
                                        "missing_shard_hint"],
                   help="corrupt the program before linting "
                        "(cross_stage_hazard makes a later pipeline "
                        "stage rewrite a handoff activation and needs "
                        "--check-placement; dropped_bucket / "
                        "skipped_guard / missing_shard_hint corrupt "
                        "one path's lowering trace and need "
                        "--check-conformance)")
    p.add_argument("--shards", type=int, default=1,
                   help="transpile the model into N data-parallel shard "
                        "programs and also check collective ordering")
    p.add_argument("--bucket-mb", type=float, default=None,
                   metavar="MB",
                   help="all-reduce bucket size for --shards transpile: "
                        "0 = per-tensor c_allreduce_sum, default follows "
                        "FLAGS_allreduce_bucket_mb (bucketed "
                        "c_allreduce_fused)")
    p.add_argument("--passes", nargs="*", default=None,
                   metavar="PASS", help=f"subset of passes to run "
                   f"(default all: {', '.join(analysis_passes())})")
    p.add_argument("--warnings-as-errors", action="store_true",
                   help="exit non-zero on warnings too")
    p.add_argument("--check-kernels", action="store_true",
                   help="registry-completeness lint: every kernel "
                        "registered in paddle_tpu/kernels must have a "
                        "numerics-parity entry (kernels/parity.py); "
                        "exits non-zero on gaps")
    p.add_argument("--check-tuning-cache", nargs="?", const="",
                   default=None, metavar="DIR",
                   help="validate every entry in the persistent tuning "
                        "cache (default dir: PT_TUNING_CACHE_DIR, "
                        "docs/TUNING.md): schema version, key/digest "
                        "consistency, known knob names; exits non-zero "
                        "on invalid entries")
    p.add_argument("--check-races", action="store_true",
                   help="run the island-race pass alone (program ops "
                        "racing engine-managed state, donated fetch "
                        "targets, fused-bucket plan divergence); exits "
                        "non-zero on any hazard")
    p.add_argument("--check-memory", type=float, default=None,
                   metavar="BYTES",
                   help="build the liveness-based static HBM plan, "
                        "print it, and exit non-zero when the static "
                        "peak exceeds BYTES (0 = report only)")
    p.add_argument("--check-cost", action="store_true",
                   help="print the static per-op cost model (FLOPs / "
                        "bytes moved)")
    p.add_argument("--check-placement", action="store_true",
                   help="multi-axis layout lint (docs/PARALLELISM.md): "
                        "every trainable parameter must resolve to "
                        "exactly one PartitionSpec under the SpecLayout "
                        "table, and the transpiled shard programs must "
                        "issue an identical collective sequence; exits "
                        "non-zero on gaps, ambiguity, or divergence")
    p.add_argument("--check-conformance", action="store_true",
                   help="cross-path lowering conformance (docs/"
                        "STATIC_ANALYSIS.md): extract the canonical "
                        "lowering trace on the engine / transpiled / "
                        "dygraph paths and diff them "
                        "against the declared support matrix; exits "
                        "non-zero on any undeclared divergence")
    p.add_argument("--batch", type=int, default=64, metavar="N",
                   help="value substituted for dynamic (-1) dims in "
                        "--check-memory/--check-cost plans (default 64)")
    p.add_argument("--all-models", action="store_true",
                   help="CI gate: run the full pass pipeline plus the "
                        "placement and conformance checks over every "
                        "named book model; exits non-zero if any model "
                        "has an error")
    return p


def _check_kernels() -> int:
    """Registry-completeness lint (docs/KERNELS.md): a custom kernel
    with no parity case is unverifiable and fails the build."""
    from paddle_tpu.kernels import parity, registry
    case_count = {}
    for c in parity.cases():
        case_count[c.kernel] = case_count.get(c.kernel, 0) + 1
    missing = parity.missing_parity()
    for name in registry.kernel_names():
        n = case_count.get(name, 0)
        mark = "MISSING" if name in missing else f"{n} case(s)"
        print(f"  {name:24s} parity: {mark}")
    if missing:
        print(f"check-kernels: {len(missing)} registered kernel(s) "
              f"without a parity entry: {', '.join(missing)}",
              file=sys.stderr)
        return EXIT_ERRORS
    print(f"check-kernels: {len(case_count)} kernel(s), all covered")
    return EXIT_CLEAN


def _check_tuning_cache(directory: str) -> int:
    """Tuning-cache hygiene lint (docs/TUNING.md): an entry the engine
    would silently treat as a miss — stale schema, digest mismatch,
    unknown knob — is surfaced here instead of costing a re-search."""
    from paddle_tpu.tuning import cache
    rows = cache.scan(directory or None)
    bad = 0
    for row in rows:
        errs = row["errors"]
        name = os.path.basename(row["path"])
        if errs:
            bad += 1
            for e in errs:
                print(f"  {name}: ERROR {e}")
        else:
            print(f"  {name}: ok")
    d = directory or cache.cache_dir()
    if bad:
        print(f"check-tuning-cache: {bad}/{len(rows)} invalid "
              f"entr{'y' if bad == 1 else 'ies'} in {d}",
              file=sys.stderr)
        return EXIT_ERRORS
    print(f"check-tuning-cache: {len(rows)} entr"
          f"{'y' if len(rows) == 1 else 'ies'} in {d}, all valid")
    return EXIT_CLEAN


# ---------------------------------------------------------------------------
# verifier modes (races / memory / cost)
# ---------------------------------------------------------------------------

def _check_races(program, feed_names, fetch_names, label="") -> int:
    """The island-race pass alone (docs/STATIC_ANALYSIS.md)."""
    from paddle_tpu.analysis import (donation_plan, format_report,
                                     has_errors)
    diags = analyze_program(program, feed_names=feed_names,
                            fetch_names=fetch_names or (),
                            passes=["island-race"], label=label)
    donated = donation_plan(program)["donated"]
    print(format_report(
        diags, header=f"check-races {label}: {len(donated)} donated"))
    return EXIT_ERRORS if has_errors(diags) else EXIT_CLEAN


def _check_memory(program, feed_names, fetch_names, limit_bytes: float,
                  batch: int, label="") -> int:
    """Static HBM plan + optional budget verdict."""
    from paddle_tpu.analysis import plan_memory
    plan = plan_memory(program, feed_names=feed_names,
                       fetch_names=fetch_names or (), dynamic_dim=batch,
                       label=label)
    print(plan.format())
    limit = int(limit_bytes)
    if limit > 0 and plan.peak_bytes > limit:
        top = ", ".join(f"{r['name']} ({r['bytes']:,} B)"
                        for r in plan.top_vars[:3])
        print(f"check-memory: static peak {plan.peak_bytes:,} B exceeds "
              f"the {limit:,} B limit — largest contributors: {top}",
              file=sys.stderr)
        return EXIT_ERRORS
    if limit > 0:
        print(f"check-memory: static peak {plan.peak_bytes:,} B within "
              f"the {limit:,} B limit")
    return EXIT_CLEAN


def _check_cost(program, batch: int, label="") -> int:
    """Static per-op cost model report (always informational; the
    registered pass enforces PT_STATIC_FLOP_LIMIT when set)."""
    from paddle_tpu.analysis import cost as cost_model
    cost = cost_model.program_cost(program, dynamic_dim=batch)
    d = cost.to_dict(top=5)
    print(f"check-cost {label}: {d['ops']} ops, "
          f"{d['total_flops']:.3e} FLOPs, "
          f"{d['total_bytes']:.3e} bytes moved (batch={batch})")
    for t, agg in d["by_type"].items():
        print(f"  {t:28s} x{agg['count']:<3d} {agg['flops']:.3e} FLOPs")
    return EXIT_CLEAN


def _check_placement(model: str, batch: int, n_shards: int = 2,
                     inject=None, label="") -> int:
    """Multi-axis layout lint (docs/PARALLELISM.md).

    Three invariants: (1) the SpecLayout table must give every
    trainable parameter exactly ONE PartitionSpec — zero matches means
    the parameter silently replicates under FSDP (an HBM regression),
    two distinct matches means first-match-wins is hiding a rule-set
    ambiguity; (2) the collective sequence must be identical across
    transpiled shard programs (reuses check_collective_ordering —
    layout-induced divergence hangs every rank on hardware); (3) the
    pipeline axis must be executable: the synthesized cutting
    validates clean (every cut produced before consumed, consumed
    after its boundary, no tied param silently replicated, per-stage
    SpecLayout coverage) and the cross-stage race verifier + the 1F1B
    slot-table verifier find no hazard. ``--inject
    cross_stage_hazard`` makes a later stage rewrite a handoff
    activation — the WW hazard the verifier must catch."""
    from paddle_tpu.analysis import (check_collective_ordering,
                                     format_report, has_errors)
    from paddle_tpu.parallel.mesh import MeshSpec
    from paddle_tpu.parallel.strategy import SpecLayout

    program, _, feed_names, loss = build_model(model)
    # a full multi-axis spec keeps every rule in the table live
    rules = SpecLayout().param_rules(MeshSpec(data=2, fsdp=2, tp=2))
    rc = EXIT_CLEAN
    params = program.global_block().all_parameters()
    bad = 0
    for p in sorted(params, key=lambda v: v.name):
        specs = rules.matching_specs(p.name)
        if len(specs) == 1:
            continue
        bad += 1
        rc = EXIT_ERRORS
        if not specs:
            print(f"  {p.name}: ERROR no PartitionSpec rule matches "
                  f"(would replicate under FSDP)")
        else:
            print(f"  {p.name}: ERROR ambiguous — {len(specs)} "
                  f"distinct specs match: "
                  + ", ".join(str(s) for s in specs))
    print(f"check-placement {label}: {len(params)} parameter(s), "
          f"{bad} without a unique PartitionSpec")

    shards, _, _ = transpile_shards(model, n_shards)
    diags = check_collective_ordering(shards)
    if diags:
        print(format_report(
            diags, header=f"check-placement {label}: collective "
                          f"ordering over {n_shards} shards"))
    else:
        print(f"check-placement {label}: collective sequence "
              f"consistent across {n_shards} shards")
    if has_errors(diags):
        rc = EXIT_ERRORS

    rc = max(rc, _check_pipeline_cuts(model, rules, batch,
                                      inject=inject, label=label))
    return rc


def _check_pipeline_cuts(model: str, rules, batch: int,
                         inject=None, label="") -> int:
    """Pipeline leg of --check-placement: synthesize a 2-stage cutting
    (no manual cut_vars — the same path the engines take), validate it
    statically, and prove it free of cross-stage hazards; also verify
    the 1F1B slot table the MPMD engine would execute. Works on the
    FORWARD program (up to the loss, no optimizer ops) — the only
    shape the pipeline engines accept."""
    program, _, _, loss = build_model(model, optimize=False)
    from paddle_tpu.analysis import format_report, has_errors
    from paddle_tpu.analysis.races import (verify_pipeline_schedule,
                                           verify_stage_partition)
    from paddle_tpu.parallel.pipeline import pipeline_schedule
    from paddle_tpu.parallel.auto_cut import propose_cuts, validate_cuts
    from paddle_tpu.parallel.mesh import MeshSpec

    n_stages = 2
    try:
        plan = propose_cuts(program, loss.name, n_stages,
                            dynamic_dim=batch, uniform=False)
    except ValueError as e:
        print(f"check-placement {label}: pipeline lint skipped — {e}")
        return EXIT_CLEAN
    if inject == "cross_stage_hazard":
        # a later stage rewrites the handoff activation: the WW hazard
        # a cutter/engine regression could produce. Program surgery on
        # the lint copy only.
        block = program.global_block()
        fwd = [op for op in block.ops
               if op.type not in ("feed", "fetch")]
        victim = fwd[-1]
        slot = victim.output_slots()[0]
        victim._outputs[slot] = [plan.cut_vars[0]]
        program._bump_version()
        print(f"injected: op '{victim.type}' (last forward op) now "
              f"rewrites handoff activation '{plan.cut_vars[0]}'")
    problems = validate_cuts(program, plan.cut_vars,
                             rules=rules,
                             mesh_spec=MeshSpec(pp=n_stages))
    for pr in problems:
        print(f"  cut-validation: ERROR {pr}")
    diags = verify_stage_partition(program, plan.cut_vars, label=label)
    sched = pipeline_schedule(n_stages, 4, n_stages, kind="1f1b")
    diags += verify_pipeline_schedule(sched["events"], n_stages, 4,
                                      label=label)
    print(format_report(
        diags, header=f"check-placement {label}: pipeline cuts "
                      f"{plan.cut_vars} (balance {plan.balance:.3f}), "
                      f"1f1b bubble {sched['bubble_frac']:.4f}"))
    if problems or has_errors(diags):
        return EXIT_ERRORS
    return EXIT_CLEAN


def _check_conformance(model: str, batch: int, inject=None,
                       label="") -> int:
    """Cross-path lowering conformance (docs/STATIC_ANALYSIS.md): the
    engine / scheduler / transpiled / dygraph paths must lower `model`
    identically modulo the declared support matrix. Undeclared drift
    is an error; ``--inject dropped_bucket/skipped_guard/
    missing_shard_hint`` simulates a one-path lowering regression and
    must flip the exit code (the CLI's own self-test)."""
    from paddle_tpu.analysis import (conformance_summary, extract_traces,
                                     format_report, has_errors,
                                     inject_drift, verify_conformance)
    from paddle_tpu.analysis.conformance import TraceConfig
    program, _, feed_names, loss = build_model(model)
    shards, _, _ = transpile_shards(model, 2)
    cfg = TraceConfig.capability(dynamic_dim=batch)
    traces = extract_traces(program, fetch_names=[loss.name], config=cfg,
                            transpiled_program=shards[0])
    if inject:
        print(f"injected: {inject_drift(traces, inject)}")
    diags = verify_conformance(program, fetch_names=[loss.name],
                               config=cfg, traces=traces,
                               transpiled_program=shards[0], label=label)
    s = conformance_summary(diags)
    print(format_report(
        diags, header=f"check-conformance {label}: "
                      f"{len(traces)} paths, "
                      f"{s['declared']} declared / "
                      f"{s['undeclared']} undeclared divergence(s)"))
    return EXIT_ERRORS if has_errors(diags) else EXIT_CLEAN


def _all_models(batch: int) -> int:
    """CI gate: every named book model must pass the full pipeline,
    the placement lint and the conformance check with zero errors."""
    from paddle_tpu.analysis import format_report, has_errors
    rc = EXIT_CLEAN
    for name in sorted(MODELS):
        program, _, feed_names, loss = build_model(name)
        diags = analyze_program(program, feed_names=feed_names,
                                fetch_names=[loss.name], label=name)
        print(format_report(diags, header=f"lint {name}"))
        if has_errors(diags):
            rc = EXIT_ERRORS
        if _check_placement(name, batch, label=name) != EXIT_CLEAN:
            rc = EXIT_ERRORS
        if _check_conformance(name, batch, label=name) != EXIT_CLEAN:
            rc = EXIT_ERRORS
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    ns = _parser().parse_args(argv)
    if ns.check_kernels:
        return _check_kernels()
    if ns.check_tuning_cache is not None:
        return _check_tuning_cache(ns.check_tuning_cache)
    if ns.all_models:
        return _all_models(ns.batch)
    if not ns.model and not ns.program:
        print("lint_program: one of --model/--program (or "
              "--check-kernels/--check-tuning-cache) is required",
              file=sys.stderr)
        return EXIT_USAGE
    if ns.program and ns.shards > 1:
        print("lint_program: --shards requires --model", file=sys.stderr)
        return EXIT_USAGE
    if ns.inject == "shuffled_collectives" and ns.shards < 2:
        print("lint_program: --inject shuffled_collectives requires "
              "--shards >= 2", file=sys.stderr)
        return EXIT_USAGE
    if ns.inject == "cross_stage_hazard" and not ns.check_placement:
        print("lint_program: --inject cross_stage_hazard corrupts a "
              "pipeline stage cutting and requires --check-placement",
              file=sys.stderr)
        return EXIT_USAGE
    from paddle_tpu.analysis.conformance import DRIFT_KINDS
    if ns.inject in DRIFT_KINDS and not ns.check_conformance:
        print("lint_program: --inject dropped_bucket/skipped_guard/"
              "missing_shard_hint corrupts a lowering trace and "
              "requires --check-conformance", file=sys.stderr)
        return EXIT_USAGE
    if ns.check_conformance and not ns.model:
        print("lint_program: --check-conformance requires --model",
              file=sys.stderr)
        return EXIT_USAGE

    feed_names = None
    fetch_names = ns.fetch
    if ns.program:
        program, meta = load_serialized_program(ns.program)
        if meta:
            feed_names = meta.get("feed")
            if fetch_names is None:
                fetch_names = meta.get("fetch")
        label = os.path.basename(ns.program)
        programs = [program]
    elif ns.shards > 1:
        bucket_mb = ns.bucket_mb
        if bucket_mb is None and ns.inject == "shuffled_collectives":
            # swapping needs >= 2 collectives; the bucketed default can
            # fuse a small model's grads into a single op
            bucket_mb = 0
        programs, feed_names, loss_name = transpile_shards(
            ns.model, ns.shards, bucket_mb=bucket_mb)
        label = ns.model
        if fetch_names is None:
            fetch_names = [loss_name]
    else:
        program, _, feed_names, loss = build_model(ns.model)
        label = ns.model
        programs = [program]
        if fetch_names is None:
            fetch_names = [loss.name]

    if ns.check_races or ns.check_memory is not None or ns.check_cost \
            or ns.check_placement or ns.check_conformance:
        rc = EXIT_CLEAN
        if ns.check_races:
            rc = max(rc, _check_races(programs[0], feed_names,
                                      fetch_names, label=label))
        if ns.check_memory is not None:
            rc = max(rc, _check_memory(programs[0], feed_names,
                                       fetch_names, ns.check_memory,
                                       ns.batch, label=label))
        if ns.check_cost:
            rc = max(rc, _check_cost(programs[0], ns.batch, label=label))
        if ns.check_placement:
            if not ns.model:
                print("lint_program: --check-placement requires "
                      "--model", file=sys.stderr)
                return EXIT_USAGE
            inj_p = ns.inject if ns.inject == "cross_stage_hazard" \
                else None
            rc = max(rc, _check_placement(ns.model, ns.batch,
                                          max(2, ns.shards),
                                          inject=inj_p, label=label))
        if ns.check_conformance:
            inj = ns.inject if ns.inject in DRIFT_KINDS else None
            rc = max(rc, _check_conformance(ns.model, ns.batch,
                                            inject=inj, label=label))
        return rc

    if ns.inject:
        # corrupt the last shard so cross-shard divergence is visible
        desc = inject_defect(programs[-1], ns.inject)
        print(f"injected: {desc}")

    if len(programs) > 1:
        diags = analyze_shard_programs(
            programs, feed_names=feed_names,
            fetch_names=fetch_names or ())
    else:
        diags = analyze_program(
            programs[0], feed_names=feed_names,
            fetch_names=fetch_names or (), passes=ns.passes,
            label="")
    print(format_report(diags, header=f"lint {label}"))
    if has_errors(diags):
        return EXIT_ERRORS
    if ns.warnings_as_errors and diags:
        return EXIT_ERRORS
    return EXIT_CLEAN


if __name__ == "__main__":
    sys.exit(main())
