"""Program verifier: engine-state / donation / bucket-plan hazard
detection (the island-race pass), the liveness-based static HBM
planner, the static cost model, the tier-2 engine hook, and the lint
CLI / calibration hooks that surface them.
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.analysis import (Severity, analyze_program,
                                 check_collective_ordering,
                                 donation_plan, plan_memory, reconcile)
from paddle_tpu.analysis import cost as cost_model
from paddle_tpu.analysis.races import (ENGINE_STATE_RE,
                                       static_updated_names)
from paddle_tpu.core.flags import get_flags, set_flags

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tools"))

import lint_flags  # noqa: E402  (tools/lint_flags.py)
import lint_program  # noqa: E402  (tools/lint_program.py)


def _mlp_program():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = layers.data("img", [784], dtype="float32")
        label = layers.data("label", [1], dtype="int64")
        h = layers.fc(img, 64, act="relu")
        pred = layers.fc(h, 10, act="softmax")
        loss = layers.mean(layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _errors(diags):
    return [d for d in diags if d.is_error]


# ---------------------------------------------------------------------------
# static updated / donated sets
# ---------------------------------------------------------------------------

def test_static_updated_names_are_the_params():
    main, _, _ = _mlp_program()
    updated = set(static_updated_names(main))
    params = {p.name for p in main.all_parameters()}
    assert params <= updated


# ---------------------------------------------------------------------------
# race verifier
# ---------------------------------------------------------------------------

def test_donation_plan_lists_updated_persistables():
    main, _, _ = _mlp_program()
    plan = donation_plan(main)
    params = {p.name for p in main.all_parameters()}
    assert params <= set(plan["donated"])


def test_engine_state_regex_scope():
    assert ENGINE_STATE_RE.match("@LOSS_SCALE@")
    assert ENGINE_STATE_RE.match("@RNG_STATE@")
    assert ENGINE_STATE_RE.match("@INTEGRITY_SUM@")
    assert ENGINE_STATE_RE.match("@GUARD_VERDICT@")
    # suffix decorations are ordinary scope vars, not engine state
    assert not ENGINE_STATE_RE.match("fc_0.w_0@SNAPSHOT")
    assert not ENGINE_STATE_RE.match("x@GRAD@RENAME@block0@0")
    assert not ENGINE_STATE_RE.match("@lower@")


def test_op_writing_engine_state_is_error():
    main, _, loss = _mlp_program()
    block = main.global_block()
    block.create_var(name="@LOSS_SCALE@", shape=[1], dtype="float32",
                     persistable=True)
    block.append_op(type="scale", inputs={"X": [loss.name]},
                    outputs={"Out": ["@LOSS_SCALE@"]},
                    attrs={"scale": 2.0}, infer_shape=False)
    diags = analyze_program(main, feed_names=["img", "label"],
                            fetch_names=[loss.name],
                            passes=["island-race"])
    errs = [d for d in _errors(diags)
            if "engine-managed in-trace state" in d.message]
    assert errs and "@LOSS_SCALE@" in errs[0].var_names


def test_fetching_donated_param_is_warning():
    main, _, _ = _mlp_program()
    p = main.all_parameters()[0].name
    diags = analyze_program(main, feed_names=["img", "label"],
                            fetch_names=[p], passes=["island-race"])
    warns = [d for d in diags if d.severity == Severity.WARNING
             and "donated" in d.message]
    assert warns and p in warns[0].var_names


# ---------------------------------------------------------------------------
# fused bucket-plan consistency
# ---------------------------------------------------------------------------

def _bucketed_shards(n=2):
    return lint_program.transpile_shards("mlp", n, bucket_mb=32)[0]


def test_fused_bucket_member_order_divergence_is_error():
    shards = _bucketed_shards()
    block = shards[1].global_block()
    for op in block.ops:
        if op.type == "c_allreduce_fused" and len(op.input("X")) >= 2:
            names = list(op.input("X"))
            names[0], names[1] = names[1], names[0]
            op._inputs["X"] = names
            op._outputs["Out"] = list(names)
            shards[1]._bump_version()
            break
    else:
        pytest.skip("no multi-member fused bucket at this size")
    diags = check_collective_ordering(shards)
    errs = [d for d in _errors(diags) if "ORDER" in d.message]
    assert errs, [d.message for d in diags]
    assert "fused payload" in errs[0].message


def test_fused_bucket_duplicate_member_is_error():
    shards = _bucketed_shards()
    block = shards[0].global_block()
    for op in block.ops:
        if op.type == "c_allreduce_fused" and len(op.input("X")) >= 2:
            names = list(op.input("X"))
            names[1] = names[0]
            op._inputs["X"] = names
            shards[0]._bump_version()
            break
    else:
        pytest.skip("no multi-member fused bucket at this size")
    diags = analyze_program(shards[0], feed_names=["img", "label"],
                            passes=["island-race"])
    assert any("reduced twice" in d.message or
               "two c_allreduce_fused buckets" in d.message
               for d in _errors(diags))


def test_fused_bucket_missing_grad_is_error():
    shards = _bucketed_shards()
    block = shards[0].global_block()
    for op in block.ops:
        if op.type == "c_allreduce_fused" and len(op.input("X")) >= 2:
            names = list(op.input("X"))[:-1]
            op._inputs["X"] = names
            op._outputs["Out"] = list(names)
            shards[0]._bump_version()
            break
    else:
        pytest.skip("no multi-member fused bucket at this size")
    diags = analyze_program(shards[0], feed_names=["img", "label"],
                            passes=["island-race"])
    assert any("in no c_allreduce_fused bucket" in d.message
               for d in _errors(diags))


# ---------------------------------------------------------------------------
# static HBM planner
# ---------------------------------------------------------------------------

def test_plan_memory_mlp_accounting():
    main, _, loss = _mlp_program()
    plan = plan_memory(main, feed_names=["img", "label"],
                       fetch_names=[loss.name], dynamic_dim=64)
    assert plan.resident_bytes > 0
    assert plan.feed_bytes > 0
    assert plan.transient_peak_bytes > 0
    # peak = resident + feed + transient + always-on overheads
    extra = sum(v for k, v in plan.overheads.items()
                if k != "ckpt_snapshot")
    assert plan.peak_bytes == (plan.resident_bytes + plan.feed_bytes +
                               plan.transient_peak_bytes + extra)
    # feed scales with the dynamic dim
    plan1 = plan_memory(main, feed_names=["img", "label"],
                        fetch_names=[loss.name], dynamic_dim=1)
    assert plan.feed_bytes == 64 * plan1.feed_bytes
    assert plan.top_vars == sorted(plan.top_vars,
                                   key=lambda r: -r["bytes"])
    d = plan.to_dict()
    assert d["peak_bytes"] == plan.peak_bytes
    assert "dynamic_dim" in d["assumptions"]


def test_plan_memory_ghost_ring_overhead_follows_flag():
    main, _, loss = _mlp_program()
    old = get_flags(["stability_guard"])
    set_flags({"stability_guard": True})
    try:
        plan = plan_memory(main, feed_names=["img", "label"],
                           fetch_names=[loss.name])
    finally:
        set_flags(old)
    assert plan.overheads.get("ghost_ring", 0) > 0
    plain = plan_memory(main, feed_names=["img", "label"],
                        fetch_names=[loss.name])
    assert "ghost_ring" not in plain.overheads


def test_memory_plan_pass_silent_without_limit():
    main, _, loss = _mlp_program()
    assert os.environ.get("PT_STATIC_HBM_LIMIT") is None
    diags = analyze_program(main, feed_names=["img", "label"],
                            fetch_names=[loss.name],
                            passes=["memory-plan"])
    assert diags == []


def test_memory_plan_pass_flags_over_limit(monkeypatch):
    main, _, loss = _mlp_program()
    monkeypatch.setenv("PT_STATIC_HBM_LIMIT", "1000")
    diags = analyze_program(main, feed_names=["img", "label"],
                            fetch_names=[loss.name],
                            passes=["memory-plan"])
    errs = _errors(diags)
    assert errs and "exceeds the configured limit" in errs[0].message
    # names the top contributors so the finding is actionable
    assert errs[0].var_names


def test_memory_plan_pass_warns_near_limit(monkeypatch):
    main, _, loss = _mlp_program()
    plan = plan_memory(main, feed_names=["img", "label"],
                       fetch_names=[loss.name])
    monkeypatch.setenv("PT_STATIC_HBM_LIMIT",
                       str(int(plan.peak_bytes * 1.05)))
    diags = analyze_program(main, feed_names=["img", "label"],
                            fetch_names=[loss.name],
                            passes=["memory-plan"])
    assert any(d.severity == Severity.WARNING and
               "within 10%" in d.message for d in diags)


def test_reconcile_error_ratios():
    main, _, loss = _mlp_program()
    plan = plan_memory(main, feed_names=["img", "label"],
                       fetch_names=[loss.name], dynamic_dim=64)
    static_resident = float(plan.resident_bytes + plan.feed_bytes)
    rec = reconcile(plan,
                    census={"live_bytes": static_resident * 1.25},
                    measured_step={
                        "temp_bytes": plan.transient_peak_bytes * 2})
    assert rec["resident_error_ratio"] == pytest.approx(0.2)
    assert rec["temp_error_ratio"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# static cost model
# ---------------------------------------------------------------------------

def test_program_cost_mlp():
    main, _, _ = _mlp_program()
    cost = cost_model.program_cost(main, dynamic_dim=64)
    assert cost.total_flops > 0 and cost.total_bytes > 0
    by_type = cost.by_type()
    # dense backward ~ 2x forward per GEMM pair
    assert by_type["mul_grad"]["flops"] == 2 * by_type["mul"]["flops"]
    # the first GEMM dominates an MLP: 2*B*784*64 at B=64
    assert by_type["mul"]["flops"] >= 2 * 64 * 784 * 64
    assert sum(r.flops for r in cost.rows) == cost.total_flops


def test_cost_scales_with_batch():
    main, _, _ = _mlp_program()
    c1 = cost_model.program_cost(main, dynamic_dim=1)
    c64 = cost_model.program_cost(main, dynamic_dim=64)
    assert c64.total_flops > 30 * c1.total_flops


def test_cost_model_pass_opt_in(monkeypatch):
    main, _, loss = _mlp_program()
    diags = analyze_program(main, feed_names=["img", "label"],
                            fetch_names=[loss.name],
                            passes=["cost-model"])
    assert diags == []
    monkeypatch.setenv("PT_STATIC_FLOP_LIMIT", "1")
    diags = analyze_program(main, feed_names=["img", "label"],
                            fetch_names=[loss.name],
                            passes=["cost-model"])
    assert diags and all(d.severity == Severity.WARNING for d in diags)
    assert "PT_STATIC_FLOP_LIMIT" in diags[0].message


# ---------------------------------------------------------------------------
# tier-2 traced-step validation + engine integration
# ---------------------------------------------------------------------------

def test_engine_tier2_runs_clean_step():
    main, startup, loss = _mlp_program()
    old = get_flags(["validate_program", "validate_tier"])
    set_flags({"validate_program": True, "validate_tier": 2})
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            feed = {"img": np.random.rand(4, 784).astype(np.float32),
                    "label": np.random.randint(0, 10, (4, 1))
                    .astype(np.int64)}
            out = exe.run(main, feed=feed, fetch_list=[loss.name])
        assert np.isfinite(np.asarray(out[0])).all()
        rows = exe._engine.donation_metadata()
        assert rows and all("donated" in r for r in rows)
    finally:
        set_flags(old)


# ---------------------------------------------------------------------------
# satellite 2: current op vocabulary stays diagnostic-free
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", sorted(lint_program.MODELS))
def test_book_models_verify_clean(model):
    main, _, feed_names, loss = lint_program.build_model(model)
    diags = analyze_program(main, feed_names=feed_names,
                            fetch_names=[loss.name])
    assert diags == [], [d.message for d in diags]


def test_transformer_block_verifies_clean():
    # post-PR-4 vocabulary: layer_norm / matmul / dropout / softmax —
    # the liveness pass must not flag autodiff byproducts as dead
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [16, 32], dtype="float32")
        y = layers.data("y", [16, 32], dtype="float32")
        h = layers.layer_norm(x)
        q = layers.fc(h, 32, num_flatten_dims=2)
        k = layers.fc(h, 32, num_flatten_dims=2)
        v = layers.fc(h, 32, num_flatten_dims=2)
        att = layers.matmul(q, k, transpose_y=True, alpha=32 ** -0.5)
        att = layers.softmax(att)
        att = layers.dropout(att, 0.1)
        ctx = layers.matmul(att, v)
        out = layers.fc(ctx, 32, num_flatten_dims=2)
        loss = layers.reduce_mean(
            layers.square_error_cost(out, y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    diags = analyze_program(main, feed_names=["x", "y"],
                            fetch_names=[loss.name])
    assert diags == [], [d.message for d in diags]


def test_bucketed_shards_verify_clean():
    shards = _bucketed_shards()
    from paddle_tpu.analysis import analyze_shard_programs
    diags = analyze_shard_programs(shards,
                                   feed_names=["img", "label"])
    assert _errors(diags) == [], [d.message for d in diags]
    assert check_collective_ordering(shards) == []


# ---------------------------------------------------------------------------
# lint CLI exit codes (each injected defect class -> the right verdict)
# ---------------------------------------------------------------------------

def test_cli_check_races_clean():
    assert lint_program.main(["--model", "mlp", "--check-races"]) == 0


def test_cli_race_inject_requires_check_races():
    # partition injections are gone with the partition: argparse
    # refuses the name, and an injection without its check mode is
    # still a usage error
    with pytest.raises(SystemExit) as e:
        lint_program.main(["--model", "mlp", "--check-races",
                           "--inject", "island_conflict"])
    assert e.value.code == 2
    rc = lint_program.main(["--model", "mlp",
                            "--inject", "cross_stage_hazard"])
    assert rc == 2


def test_cli_check_memory_exit_codes():
    assert lint_program.main(["--model", "mlp",
                              "--check-memory", "2e9"]) == 0
    assert lint_program.main(["--model", "mlp",
                              "--check-memory", "1000"]) == 1
    assert lint_program.main(["--model", "mlp",
                              "--check-memory", "0"]) == 0  # report only


def test_cli_check_cost(capsys):
    assert lint_program.main(["--model", "conv", "--check-cost",
                              "--batch", "8"]) == 0
    assert "FLOPs" in capsys.readouterr().out


def test_cli_all_models_gate():
    assert lint_program.main(["--all-models"]) == 0
