"""Error layer + flag system (reference platform/enforce.h:194,
FLAGS_check_nan_inf operator.cc:953-983, __bootstrap__ env-var flags
python/paddle/fluid/__init__.py:124-221) and BuildStrategy knob
consumption (details/build_strategy.h:58-139)."""
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import flags as flags_mod
from paddle_tpu.core.scope import Scope


def _run(main, startup, feed, fetch):
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return exe.run(main, feed=feed, fetch_list=fetch)


# ---------------------------------------------------------------- enforce

def test_trace_error_carries_op_context():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [5], dtype="float32")
        # shape-invalid: (n,4) x (n,5) elementwise
        bad = main.global_block().append_op(
            type="elementwise_add", inputs={"X": [x.name], "Y": [y.name]},
            outputs={"Out": ["bad_out"]}, attrs={"axis": -1})
        main.global_block().create_var(
            name="bad_out", shape=[-1, 4], dtype="float32")
    with pytest.raises(fluid.EnforceNotMet) as ei:
        _run(main, startup,
             {"x": np.zeros((2, 4), np.float32),
              "y": np.zeros((2, 5), np.float32)}, ["bad_out"])
    msg = str(ei.value)
    assert "elementwise_add" in msg
    assert "x" in msg and "y" in msg and "bad_out" in msg
    assert ei.value.op_type == "elementwise_add"


def test_enforce_helper():
    with pytest.raises(fluid.EnforceNotMet):
        fluid.enforce(False, "must hold", op_type="demo")


# ----------------------------------------------------------- check_nan_inf

def test_check_nan_inf_names_offending_op():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [3], dtype="float32")
        y = layers.log(x)          # log of negative input -> NaN
        z = layers.mean(y)
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with pytest.raises(fluid.EnforceNotMet) as ei:
            _run(main, startup,
                 {"x": -np.ones((2, 3), np.float32)}, [z.name])
        assert "log" in str(ei.value)
        assert "NaN" in str(ei.value) or "Inf" in str(ei.value)
        # clean input passes under the same flag
        out = _run(main, startup,
                   {"x": np.ones((2, 3), np.float32)}, [z.name])
        assert np.allclose(out[0], 0.0)
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})


def test_check_nan_inf_fires_on_eager_fallback_path():
    """A value-dependent op (edit_distance) demotes the program to the
    eager interpreter; the NaN sweep must still fire there (ADVICE r2:
    the label box is only filled while an eager step runs)."""
    from paddle_tpu.core.scope import create_lod_tensor
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        b = main.global_block()
        b.create_var(name="hyp", shape=[4, 1], dtype="int64")
        b.create_var(name="ref", shape=[4, 1], dtype="int64")
        b.create_var(name="dist", shape=[2, 1], dtype="float32")
        b.create_var(name="seqn", shape=[1], dtype="int64")
        b.append_op(type="edit_distance",
                    inputs={"Hyps": ["hyp"], "Refs": ["ref"]},
                    outputs={"Out": ["dist"], "SequenceNum": ["seqn"]},
                    attrs={}, infer_shape=False)
        x = layers.data("x", [3], dtype="float32")
        y = layers.log(x)          # log of negative input -> NaN
        z = layers.mean(y)
    ids = np.array([[1], [2], [3], [4]], np.int64)
    feed = {"hyp": create_lod_tensor(ids, [[2, 2]]),
            "ref": create_lod_tensor(ids, [[2, 2]]),
            "x": -np.ones((2, 3), np.float32)}
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # eager-fallback warning
            with pytest.raises(fluid.EnforceNotMet) as ei:
                _run(main, startup, feed, [z.name])
        assert "log" in str(ei.value)
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})


# ------------------------------------------------------------------ flags

def test_flags_get_set_roundtrip():
    assert fluid.get_flags("FLAGS_check_nan_inf") == {
        "FLAGS_check_nan_inf": False}
    fluid.set_flags({"check_nan_inf": True})
    try:
        assert fluid.get_flags(["check_nan_inf"])[
            "FLAGS_check_nan_inf"] is True
    finally:
        fluid.set_flags({"check_nan_inf": False})


def test_unknown_flag_raises():
    with pytest.raises(ValueError):
        fluid.set_flags({"FLAGS_definitely_not_a_flag": 1})
    with pytest.raises(ValueError):
        fluid.get_flags("FLAGS_definitely_not_a_flag")


def test_env_bootstrap_coerces_types():
    os.environ["FLAGS_eager_delete_tensor_gb"] = "0.5"
    os.environ["FLAGS_check_nan_inf"] = "false"
    os.environ["FLAGS_not_a_known_flag"] = "1"  # ignored, no raise
    try:
        flags_mod.__bootstrap__()
        got = fluid.get_flags(["eager_delete_tensor_gb", "check_nan_inf"])
        assert got["FLAGS_eager_delete_tensor_gb"] == 0.5
        assert got["FLAGS_check_nan_inf"] is False
    finally:
        for k in ("FLAGS_eager_delete_tensor_gb", "FLAGS_check_nan_inf",
                  "FLAGS_not_a_known_flag"):
            os.environ.pop(k, None)
        fluid.set_flags({"eager_delete_tensor_gb": -1.0,
                         "check_nan_inf": False})


def test_flag_info_distinguishes_live_from_subsumed():
    assert flags_mod.flag_info("check_nan_inf").live
    assert not flags_mod.flag_info("allocator_strategy").live


# ------------------------------------------------- BuildStrategy wiring

def _mnist_like():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(x, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = fluid.optimizer.SGD(learning_rate=0.1)
        opt.minimize(loss)
    return main, startup, loss


def test_gradient_scale_strategy_fails_loudly():
    main, startup, loss = _mnist_like()
    bs = fluid.BuildStrategy()
    bs.gradient_scale_strategy = \
        fluid.BuildStrategy.GradientScaleStrategy.Customized
    cp = fluid.CompiledProgram(main, build_strategy=bs).with_data_parallel(
        loss_name=loss.name)
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with pytest.raises(NotImplementedError):
            exe.run(cp, feed={"x": np.zeros((8, 8), np.float32),
                              "y": np.zeros((8, 1), np.float32)},
                    fetch_list=[loss.name])


def test_subsumed_knob_warns_once():
    from paddle_tpu import compiler as compiler_mod
    compiler_mod._warned_knobs.clear()
    bs = fluid.BuildStrategy()
    bs.fuse_all_reduce_ops = True
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        compiler_mod._validate_strategies(bs, None)
        compiler_mod._validate_strategies(bs, None)
    hits = [x for x in w if "fuse_all_reduce_ops" in str(x.message)]
    assert len(hits) == 1


def test_debug_graphviz_path_dumps_dot(tmp_path):
    main, startup, loss = _mnist_like()
    path = str(tmp_path / "prog.dot")
    bs = fluid.BuildStrategy()
    bs.debug_graphviz_path = path
    from paddle_tpu.compiler import _validate_strategies
    _validate_strategies(bs, None, main)
    dot = open(path).read()
    assert dot.startswith("digraph")
    assert "mul" in dot and "sgd" in dot


def test_num_iteration_per_run_executes_k_steps():
    main, startup, loss = _mnist_like()
    es = fluid.ExecutionStrategy()
    es.num_iteration_per_run = 3
    cp = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, exec_strategy=es)
    scope = Scope()
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 8).astype(np.float32),
            "y": rng.rand(8, 1).astype(np.float32)}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w0 = np.array(scope.find_var(
            main.all_parameters()[0].name).get_value())
        exe.run(cp, feed=feed, fetch_list=[loss.name])
        w3 = np.array(scope.find_var(
            main.all_parameters()[0].name).get_value())
    # compare against 3 manual plain-executor steps from the same init
    main2, startup2, loss2 = _mnist_like()
    scope2 = Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(startup2)
        scope2.find_var(main2.all_parameters()[0].name).set_value(w0)
        for _ in range(3):
            exe2.run(main2, feed=feed, fetch_list=[loss2.name])
        w_ref = np.array(scope2.find_var(
            main2.all_parameters()[0].name).get_value())
    np.testing.assert_allclose(w3, w_ref, rtol=2e-5, atol=2e-6)


def test_num_iteration_per_run_on_island_fallback():
    """iterations>1 on the islands/eager fallback path host-loops with
    state chained (the jit path lax.scans instead)."""
    from paddle_tpu.core.scope import create_lod_tensor
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(x, 1, param_attr=fluid.ParamAttr(name="wit"))
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        b = main.global_block()
        for n, s, d in (("hyp", [4, 1], "int64"),
                        ("ref", [4, 1], "int64"),
                        ("dist", [2, 1], "float32"),
                        ("seqn", [1], "int64")):
            b.create_var(name=n, shape=s, dtype=d)
        b.append_op(type="edit_distance",
                    inputs={"Hyps": ["hyp"], "Refs": ["ref"]},
                    outputs={"Out": ["dist"], "SequenceNum": ["seqn"]},
                    attrs={}, infer_shape=False)
    es = fluid.ExecutionStrategy()
    es.num_iteration_per_run = 3
    cp = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, exec_strategy=es)
    ids = np.array([[1], [2], [3], [4]], np.int64)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 4).astype(np.float32),
            "y": rng.rand(8, 1).astype(np.float32),
            "hyp": create_lod_tensor(ids, [[2, 2]]),
            "ref": create_lod_tensor(ids, [[2, 2]])}
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w0 = np.array(scope.find_var("wit").get_value())
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            exe.run(cp, feed=feed, fetch_list=[loss.name])
        w3 = np.array(scope.find_var("wit").get_value())

    # manual 3 plain steps from identical init
    scope2 = Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope2.var("wit").set_value(w0)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss.name])
        w_ref = np.array(scope2.find_var("wit").get_value())
    np.testing.assert_allclose(w3, w_ref, rtol=1e-5, atol=1e-6)


def test_pt_recompute_trajectory_parity(monkeypatch):
    """PT_RECOMPUTE re-derives the fwd stash behind optimization
    barriers; without AMP the trajectory must be EXACT (the pass only
    changes buffer lifetimes, not math)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers

    def run():
        fluid.framework.unique_name.reset()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img = layers.data("img", [3, 8, 8], dtype="float32")
            lbl = layers.data("lbl", [1], dtype="int64")
            c = layers.conv2d(img, 4, 3, padding=1, act=None)
            b = layers.batch_norm(c, act="relu")
            c2 = layers.conv2d(b, 4, 3, padding=1, act=None)
            b2 = layers.batch_norm(c2)
            s = layers.elementwise_add(b2, b, act="relu")
            p = layers.pool2d(s, pool_type="avg", global_pooling=True)
            fc = layers.fc(p, 10, act="softmax")
            loss = layers.mean(layers.cross_entropy(fc, lbl))
            fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
        scope = fluid.core.Scope()
        rng = np.random.RandomState(0)
        losses = []
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for _ in range(3):
                x = rng.rand(4, 3, 8, 8).astype(np.float32)
                y = rng.randint(0, 10, (4, 1)).astype(np.int64)
                out = exe.run(main, feed={"img": x, "lbl": y},
                              fetch_list=[loss.name])
                losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        # BN running stats must update exactly once per step
        stats = sorted(
            n for n in scope.local_var_names() if "batch_norm" in n)
        sums = {}
        for n in stats:
            v = scope.find_var(n).get_value()
            arr = np.asarray(v.array if hasattr(v, "array") else v)
            sums[n] = arr.astype(np.float64).sum()
        return losses, sums

    base_losses, base_sums = run()
    monkeypatch.setenv("PT_RECOMPUTE", "batch_norm,relu,elementwise_add")
    remat_losses, remat_sums = run()
    np.testing.assert_allclose(base_losses, remat_losses, rtol=1e-6)
    for n in base_sums:
        np.testing.assert_allclose(base_sums[n], remat_sums[n],
                                   rtol=1e-6, err_msg=n)
