"""Gradient accumulation (multi_batch_merge parity, reference
ir/multi_batch_merge_pass.cc:72): k forward/backward passes on feed
slices + one optimizer application must reproduce the big-batch
parameter trajectory exactly (mean loss => mean of slice grads equals
the full-batch grad). Accumulation runs inside the one compiled
whole-block step (core/engine.py ``_run_accumulated``)."""
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.engine import Engine
from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.core.flags import set_flags
from paddle_tpu.core.scope import Scope, create_lod_tensor


def _net(is_sparse=False):
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        if is_sparse:
            ids = layers.data("x", [1], dtype="int64")
            emb = layers.embedding(
                ids, [50, 8], is_sparse=True,
                param_attr=fluid.ParamAttr(name="emb_w"))
            pred = layers.fc(emb, 1,
                             param_attr=fluid.ParamAttr(name="w"))
        else:
            x = layers.data("x", [6], dtype="float32")
            h = layers.fc(x, 16, act="relu",
                          param_attr=fluid.ParamAttr(name="w0"))
            pred = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="w"))
        y = layers.data("y", [1], dtype="float32")
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
    return main, startup, loss


def _train(main, startup, loss, feeds, accumulation_steps=1,
           param_names=("w",)):
    scope = Scope()
    prog = main
    if accumulation_steps > 1:
        bs = fluid.BuildStrategy()
        bs.gradient_accumulation_steps = accumulation_steps
        prog = fluid.CompiledProgram(main, build_strategy=bs)
    losses = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for feed in feeds:
            l, = exe.run(prog, feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(l)))
        params = {n: np.asarray(scope.var(n).get_tensor()._array)
                  for n in param_names}
    return losses, params


@pytest.mark.parametrize("k", [2, 4, 8])
def test_trajectory_matches_big_batch(k):
    rng = np.random.default_rng(0)
    feeds = []
    for _ in range(6):
        xb = rng.standard_normal((32, 6)).astype(np.float32)
        feeds.append({"x": xb,
                      "y": (xb.sum(1, keepdims=True) +
                            0.1 * rng.standard_normal((32, 1))
                            ).astype(np.float32)})
    m1, s1, l1 = _net()
    _, p_big = _train(m1, s1, l1, feeds, 1, ("w", "w0"))
    m2, s2, l2 = _net()
    _, p_acc = _train(m2, s2, l2, feeds, k, ("w", "w0"))
    for n in p_big:
        np.testing.assert_allclose(p_acc[n], p_big[n],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("k", [2, 4])
def test_sparse_grads_accumulate(k):
    rng = np.random.default_rng(1)
    feeds = []
    for _ in range(5):
        ids = rng.integers(0, 50, (24, 1)).astype(np.int64)
        feeds.append({"x": ids,
                      "y": (ids % 5).astype(np.float32)})
    m1, s1, l1 = _net(is_sparse=True)
    _, p_big = _train(m1, s1, l1, feeds, 1, ("emb_w", "w"))
    m2, s2, l2 = _net(is_sparse=True)
    _, p_acc = _train(m2, s2, l2, feeds, k, ("emb_w", "w"))
    for n in p_big:
        np.testing.assert_allclose(p_acc[n], p_big[n],
                                   rtol=1e-4, atol=1e-5)


def test_loss_still_decreases_with_accumulation():
    rng = np.random.default_rng(2)
    feeds = []
    for _ in range(30):
        xb = rng.standard_normal((16, 6)).astype(np.float32)
        feeds.append({"x": xb,
                      "y": xb.sum(1, keepdims=True).astype(np.float32)})
    m, s, l = _net()
    losses, _ = _train(m, s, l, feeds, 2)
    assert losses[-1] < 0.3 * losses[0]


# ---------------------------------------------------------------------------
# the whole-block step under accumulation: ragged feeds, randomness,
# NaN checks, feed validation, cache keys, async dispatch, iterations
# ---------------------------------------------------------------------------

def _engine_run(main, startup, loss, feeds, accum=1, iterations=1,
                param_names=("w",)):
    """Fresh scope + Engine; ``accum`` set on the program. Returns
    (losses, params, engine)."""
    if accum > 1:
        main._gradient_accumulation_steps = accum
    scope = Scope()
    losses = []
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        eng = Engine()
        for feed in feeds:
            out = eng.run(main, scope, None, feed, [loss.name],
                          iterations=iterations)
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        eng.synchronize()
        params = {n: np.asarray(scope.var(n).get_tensor()._array)
                  for n in param_names}
    return losses, params, eng


def _dense_feeds(steps, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    feeds = []
    for _ in range(steps):
        xb = rng.standard_normal((batch, 6)).astype(np.float32)
        feeds.append({"x": xb,
                      "y": xb.sum(1, keepdims=True).astype(np.float32)})
    return feeds


def _seq_net():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32", lod_level=1)
        h = layers.fc(x, 8, act="tanh",
                      param_attr=fluid.ParamAttr(name="w0"))
        pooled = layers.sequence_pool(h, "sum")
        pred = layers.fc(pooled, 1, param_attr=fluid.ParamAttr(name="w"))
        y = layers.data("y", [1], dtype="float32")
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize("k", [2, 3])
def test_lod_feeds_split_on_sequence_boundaries(k):
    """Ragged feeds slice on SEQUENCE boundaries (_lod_accum_slices):
    six sequences of uneven length, one dense row per sequence; the
    mean-of-slice-grads trajectory matches the big batch."""
    lens = [2, 3, 1, 4, 2, 3]
    rng = np.random.default_rng(3)
    feeds = []
    for _ in range(3):
        xs = rng.standard_normal((sum(lens), 4)).astype(np.float32)
        feeds.append({"x": create_lod_tensor(xs, [lens]),
                      "y": rng.standard_normal((6, 1)).astype(np.float32)})
    m1, s1, l1 = _seq_net()
    _, p_big, _ = _engine_run(m1, s1, l1, feeds, 1, param_names=("w", "w0"))
    m2, s2, l2 = _seq_net()
    _, p_acc, _ = _engine_run(m2, s2, l2, feeds, k,
                              param_names=("w", "w0"))
    for n in p_big:
        np.testing.assert_allclose(p_acc[n], p_big[n],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n_dense,k,match", [
    (6, 4, "sequences are not divisible"),
    (5, 2, "one row per sequence"),
], ids=["seqs_not_divisible", "dense_rows_not_per_seq"])
def test_lod_feeds_that_cannot_split_raise(n_dense, k, match):
    lens = [2, 3, 1, 4, 2, 3]
    rng = np.random.default_rng(4)
    xs = rng.standard_normal((sum(lens), 4)).astype(np.float32)
    feed = {"x": create_lod_tensor(xs, [lens]),
            "y": rng.standard_normal((n_dense, 1)).astype(np.float32)}
    with pytest.raises(EnforceNotMet, match=match):
        _engine_run(*_seq_net(), [feed], accum=k)


def test_fetched_loss_is_the_last_micro_batch_loss():
    """The fetch reads the last micro-batch's environment: at step 0
    the K=2 loss equals a plain forward of the batch's second half."""
    feed = _dense_feeds(1)[0]
    half = {n: v[8:] for n, v in feed.items()}
    acc, _, _ = _engine_run(*_net(), [feed], accum=2)
    plain, _, _ = _engine_run(*_net(), [half])
    np.testing.assert_allclose(acc[0], plain[0], rtol=1e-6)


def _dropout_net(seed):
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data("x", [6], dtype="float32")
        h = layers.fc(x, 32, act="relu",
                      param_attr=fluid.ParamAttr(name="w0"))
        h = layers.dropout(h, dropout_prob=0.3)
        pred = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="w"))
        y = layers.data("y", [1], dtype="float32")
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
    return main, startup, loss


def test_dropout_under_accumulation_is_seed_deterministic():
    """Each micro-batch folds its index into the step key: the same
    seed gives identical trajectories from two fresh scopes, another
    seed does not."""
    feeds = _dense_feeds(4)
    a = _engine_run(*_dropout_net(11), feeds, accum=2,
                    param_names=("w", "w0"))
    b = _engine_run(*_dropout_net(11), feeds, accum=2,
                    param_names=("w", "w0"))
    c = _engine_run(*_dropout_net(12), feeds, accum=2,
                    param_names=("w", "w0"))
    assert a[0] == b[0]
    for n in a[1]:
        np.testing.assert_array_equal(a[1][n], b[1][n])
    assert a[0] != c[0]


@pytest.mark.parametrize("async_dispatch", [False, True],
                         ids=["sync", "async"])
def test_check_nan_inf_names_op_under_accumulation(async_dispatch):
    """FLAGS_check_nan_inf across the micro-batch loop: the error names
    the op and the variable, whether raised at the step or at the
    async materialization point."""
    set_flags({"FLAGS_check_nan_inf": True,
               "FLAGS_async_dispatch": async_dispatch})
    try:
        feeds = _dense_feeds(2)
        feeds[1]["x"][-1, 0] = np.nan
        with pytest.raises(EnforceNotMet,
                           match=r"Operator '\w+' output '\S+'"):
            _engine_run(*_net(), feeds, accum=2)
    finally:
        set_flags({"FLAGS_check_nan_inf": False,
                   "FLAGS_async_dispatch": False})


def test_batch_not_divisible_by_k_raises():
    feeds = _dense_feeds(1, batch=10)
    with pytest.raises(EnforceNotMet, match="not divisible"):
        _engine_run(*_net(), feeds, accum=4)


def test_feeds_with_different_batch_dims_raise():
    feed = _dense_feeds(1, batch=16)[0]
    feed["y"] = feed["y"][:8]
    with pytest.raises(EnforceNotMet, match="one leading batch dim"):
        _engine_run(*_net(), [feed], accum=2)


def test_changing_accumulation_steps_retraces():
    """gradient_accumulation_steps is part of both cache keys: the
    second run at K=2 is a fast-path hit, a switch to K=4 traces
    anew."""
    main, startup, loss = _net()
    feeds = _dense_feeds(3)
    main._gradient_accumulation_steps = 2
    scope = Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        eng = Engine()
        k2 = Engine._fast_key(main, 0, [loss.name])
        eng.run(main, scope, None, feeds[0], [loss.name])
        eng.run(main, scope, None, feeds[1], [loss.name])
        assert eng.counters["traces"] == 1
        main._gradient_accumulation_steps = 4
        assert Engine._fast_key(main, 0, [loss.name]) != k2
        eng.run(main, scope, None, feeds[2], [loss.name])
        assert eng.counters["traces"] == 2


def test_accumulation_under_async_dispatch_matches_sync():
    feeds = _dense_feeds(4)
    ls, ps, _ = _engine_run(*_net(), feeds, accum=2,
                            param_names=("w", "w0"))
    set_flags({"FLAGS_async_dispatch": True})
    try:
        la, pa, _ = _engine_run(*_net(), feeds, accum=2,
                                param_names=("w", "w0"))
    finally:
        set_flags({"FLAGS_async_dispatch": False})
    assert la == ls
    for n in ps:
        np.testing.assert_array_equal(pa[n], ps[n])


def test_iterations_scan_matches_sequential_runs():
    """num_iteration_per_run > 1 on the plain whole-block step: one
    scanned executable of K chained steps lands where K runs do."""
    feed = _dense_feeds(1)[0]
    _, p_seq, _ = _engine_run(*_net(), [feed] * 3,
                              param_names=("w", "w0"))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _, p_scan, eng = _engine_run(*_net(), [feed], iterations=3,
                                     param_names=("w", "w0"))
    assert eng.counters["traces"] == 1
    for n in p_seq:
        np.testing.assert_allclose(p_scan[n], p_seq[n],
                                   rtol=1e-5, atol=1e-6)
