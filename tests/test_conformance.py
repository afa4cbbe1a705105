"""Cross-path lowering conformance verifier (docs/STATIC_ANALYSIS.md).

The claim under test: the engine whole-block path, the collective
transpiler, and dygraph lower every book model IDENTICALLY, except where analysis/support_matrix.py declares
(and justifies) a gap. Undeclared divergence is an error; a supplied
trace that disagrees with its own path's fresh extraction (the
injected-drift self-test) is an error even when every cross-path cell
is declared.
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.analysis import (Severity, conformance_summary,
                                 extract_trace, extract_traces,
                                 inject_drift, verify_conformance)
from paddle_tpu.analysis.conformance import (DRIFT_KINDS, PASS_NAME,
                                             TraceConfig,
                                             crosscheck_traced)
from paddle_tpu.analysis.support_matrix import (DEGRADED, FEATURES,
                                                PATHS, SUPPORTED,
                                                SupportMatrix,
                                                UNSUPPORTED,
                                                default_matrix,
                                                worst_status)
from paddle_tpu.analysis.validate import (validate_collective_plan,
                                          validate_transpiled)
from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.core.flags import get_flags, set_flags

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tools"))

import lint_program  # noqa: E402  (tools/lint_program.py)


def _errors(diags):
    return [d for d in diags if d.severity == Severity.ERROR]


def _model(name):
    main, startup, feed_names, loss = lint_program.build_model(name)
    return main, startup, feed_names, loss


def _traces(name, with_shard=True):
    main, _, _, loss = _model(name)
    shard = None
    if with_shard:
        shards, _, _ = lint_program.transpile_shards(name, 2)
        shard = shards[0]
    cfg = TraceConfig.capability()
    return main, loss, shard, extract_traces(
        main, fetch_names=[loss.name], config=cfg,
        transpiled_program=shard), cfg


# ---------------------------------------------------------------------------
# the headline invariant: book models × 3 paths, zero undeclared drift
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(lint_program.MODELS))
def test_book_models_conform(name):
    main, loss, shard, traces, cfg = _traces(name)
    assert set(traces) == set(PATHS)
    diags = verify_conformance(main, fetch_names=[loss.name], config=cfg,
                               traces=traces, transpiled_program=shard,
                               label=name)
    assert _errors(diags) == []
    s = conformance_summary(diags)
    assert s["undeclared"] == 0
    # the declared gaps are real on every model with parameters
    assert s["declared"] > 0


@pytest.mark.parametrize("name", sorted(lint_program.MODELS))
def test_trace_shapes(name):
    main, loss, _, traces, _ = _traces(name, with_shard=False)
    for path in PATHS:
        tr = traces[path]
        assert set(tr.features) == set(FEATURES)
        for feat, rec in tr.features.items():
            assert isinstance(rec["applies"], bool), (path, feat)
            assert isinstance(rec["content"], tuple), (path, feat)
    # kernel selection is structurally identical everywhere: same
    # registry, same abstract signatures
    keys = {tuple(traces[p].features["kernel_selection"]["content"])
            for p in PATHS}
    assert len(keys) == 1


def test_parsed_shard_matches_abstract_replay():
    # the transpiled trace read from a REAL emitted shard must equal
    # the abstract replay of the transpiler's planning calls — the
    # emitted c_allreduce_* ops are the plan, not an approximation
    main, loss, shard, traces, cfg = _traces("mlp")
    replayed = extract_trace(main, "transpiled",
                             fetch_names=[loss.name], config=cfg)
    parsed = traces["transpiled"]
    for feat in ("collective_bucketing", "collective_quantization"):
        assert parsed.features[feat]["content"] == \
            replayed.features[feat]["content"], feat


def test_engine_skips_bucketing_on_explicit_collective_program():
    # a transpiled program fed to the ENGINE carries its own c_* ops;
    # the engine executes them rather than planning buckets, so the
    # bucketing record must be skip (not a divergence)
    shards, _, loss_name = lint_program.transpile_shards("mlp", 2)
    tr = extract_trace(shards[0], "engine", fetch_names=[loss_name])
    assert tr.meta["explicit_collectives"]
    assert tr.features["collective_bucketing"]["skip"]


def test_loss_scale_gap_is_declared_not_error():
    main, loss, _, _, cfg = _traces("fit_a_line", with_shard=False)
    main._dynamic_loss_scale = {"init": 2.0 ** 15,
                                "incr_every_n": 1000,
                                "incr_ratio": 2.0, "decr_ratio": 0.5}
    traces = extract_traces(main, fetch_names=[loss.name], config=cfg)
    eng = dict(traces["engine"].features["loss_scale"]["content"])
    dyg = dict(traces["dygraph"].features["loss_scale"]["content"])
    assert eng["present"] and not dyg["present"]
    diags = verify_conformance(main, fetch_names=[loss.name], config=cfg,
                               traces=traces)
    assert _errors(diags) == []   # declared unsupported, so INFO only
    assert any("loss_scale" in d.message for d in diags
               if d.severity == Severity.INFO)


# ---------------------------------------------------------------------------
# support matrix contract
# ---------------------------------------------------------------------------

def test_default_matrix_validates_and_roundtrips():
    m = default_matrix()
    assert m.validate() == []
    cells = m.declared_cells()
    assert cells, "default matrix must declare the known gaps"
    for feat, path, status, why in cells:
        assert status in (DEGRADED, UNSUPPORTED)
        assert why.strip(), (feat, path)
    m2 = SupportMatrix.from_dict(m.to_dict())
    assert m2.declared_cells() == cells


def test_matrix_validate_catches_bare_declaration():
    m = SupportMatrix().declare(FEATURES[0], PATHS[0], DEGRADED, "")
    assert m.validate()


def test_worst_status_ordering():
    assert worst_status(SUPPORTED, SUPPORTED) == SUPPORTED
    assert worst_status(SUPPORTED, DEGRADED) == DEGRADED
    assert worst_status(DEGRADED, UNSUPPORTED) == UNSUPPORTED


def test_undeclared_cell_is_error():
    # strip ONE declaration and the same divergence flips to ERROR —
    # the matrix is load-bearing, not decorative
    main, loss, _, traces, cfg = _traces("mlp", with_shard=False)
    stripped = SupportMatrix.from_dict(default_matrix().to_dict())
    stripped._cells.pop(("cache_key", "dygraph"))
    diags = verify_conformance(main, fetch_names=[loss.name], config=cfg,
                               traces=traces, matrix=stripped)
    errs = _errors(diags)
    assert errs and all("cache_key" in d.message for d in errs)


# ---------------------------------------------------------------------------
# injected drift (the verifier's self-test)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", DRIFT_KINDS)
def test_injected_drift_is_detected(kind):
    main, loss, shard, traces, cfg = _traces("mlp")
    desc = inject_drift(traces, kind)
    assert desc
    diags = verify_conformance(main, fetch_names=[loss.name], config=cfg,
                               traces=traces, transpiled_program=shard,
                               label=f"inject:{kind}")
    errs = _errors(diags)
    assert errs, kind
    assert any("drift within path" in d.message or
               "undeclared lowering divergence" in d.message
               for d in errs)


@pytest.mark.parametrize("kind", DRIFT_KINDS)
def test_lint_program_inject_exit_codes(kind, capsys):
    rc = lint_program.main(["--model", "fit_a_line",
                            "--check-conformance", "--inject", kind])
    assert rc == lint_program.EXIT_ERRORS
    out = capsys.readouterr().out
    assert "injected:" in out and "undeclared" in out


def test_lint_program_conformance_clean(capsys):
    rc = lint_program.main(["--model", "fit_a_line",
                            "--check-conformance"])
    assert rc == lint_program.EXIT_CLEAN
    assert "0 undeclared" in capsys.readouterr().out


def test_lint_program_inject_requires_check(capsys):
    rc = lint_program.main(["--model", "mlp", "--inject",
                            "dropped_bucket"])
    assert rc == lint_program.EXIT_USAGE


# ---------------------------------------------------------------------------
# tier-2 runtime hooks
# ---------------------------------------------------------------------------

def test_validate_transpiled_clean_and_corrupt():
    shards, _, _ = lint_program.transpile_shards("mlp", 2)
    validate_transpiled(shards[0])   # must not raise
    fused = [op for op in shards[1].global_block().ops
             if op.type == "c_allreduce_fused"]
    assert fused
    slot = fused[0].input_slots()[0]
    fused[0]._inputs[slot] = fused[0]._inputs[slot][:-1]
    shards[1]._bump_version()
    with pytest.raises(EnforceNotMet, match="tier 2"):
        validate_transpiled(shards[1])


def test_validate_collective_plan_clean_and_missing():
    from paddle_tpu.parallel import comm_scheduler as _cs
    items = [(i, (64, 64), np.dtype("float32")) for i in range(4)]
    buckets = _cs.plan_named_buckets(items, 1 << 20)
    validate_collective_plan(items, buckets, 1 << 20)   # must not raise
    pruned = [b for b in buckets]
    pruned[0].names = pruned[0].names[:-1]
    pruned[0].shapes = pruned[0].shapes[:-1]
    with pytest.raises(EnforceNotMet, match="tier 2"):
        validate_collective_plan(items, pruned, 1 << 20)


def test_crosscheck_traced_flags_missing_guard():
    class _Traced:
        guard_plan = None
        comm_stats = None
        fn = None

    main, _, _, loss = _model("mlp")
    old = get_flags(["stability_guard"])
    set_flags({"stability_guard": True})
    try:
        with pytest.raises(EnforceNotMet, match="stability-guard gate"):
            crosscheck_traced(main, 0, _Traced())
    finally:
        set_flags(old)


def test_crosscheck_traced_accepts_matching_step():
    from paddle_tpu.stability.guard import build_plan
    main, _, _, loss = _model("mlp")
    old = get_flags(["stability_guard"])
    set_flags({"stability_guard": True})
    try:
        class _Traced:
            guard_plan = build_plan(main, 0)
            comm_stats = None
            fn = None

        crosscheck_traced(main, 0, _Traced())   # must not raise
    finally:
        set_flags(old)


def test_engine_tier2_crosscheck_runs_clean():
    # end-to-end: a real engine step under validate_tier=2 routes
    # through crosscheck_traced and must come out clean
    from paddle_tpu import layers
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [13], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(x, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    old = get_flags(["validate_program", "validate_tier",
                     "stability_guard"])
    set_flags({"validate_program": True, "validate_tier": 2,
               "stability_guard": True})
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            feed = {"x": np.random.rand(4, 13).astype(np.float32),
                    "y": np.random.rand(4, 1).astype(np.float32)}
            out = exe.run(main, feed=feed, fetch_list=[loss.name])
        assert np.isfinite(np.asarray(out[0])).all()
    finally:
        set_flags(old)


# ---------------------------------------------------------------------------
# pass registration (the default pipeline stays clean)
# ---------------------------------------------------------------------------

def test_conformance_pass_registered_and_quiet():
    from paddle_tpu.analysis import analysis_passes, analyze_program
    assert PASS_NAME in analysis_passes()
    main, _, feed_names, loss = _model("conv")
    diags = analyze_program(main, feed_names=feed_names,
                            fetch_names=[loss.name], label="conv")
    assert [d for d in diags if d.pass_name == PASS_NAME] == []
