"""Async step dispatch: fast-path step cache, deferred fetches, and the
feed prefetcher (docs/ASYNC_DISPATCH.md).

The acceptance bar is counter-asserted: in steady state with
device-resident feeds a run() performs ZERO signature rebuilds, ZERO
re-traces, and ZERO redundant device_put calls (Engine.counters)."""
import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.async_dispatch import FetchHandle
from paddle_tpu.core.scope import Scope


def _sgd_model(in_dim=4, hidden=8):
    """fc -> fc -> mse, SGD. Returns (main, startup, loss)."""
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [in_dim], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, hidden, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feeds(batch=8, in_dim=4, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(batch, in_dim).astype(np.float32),
            "y": rng.rand(batch, 1).astype(np.float32)}


def _device_feeds(place, **kw):
    dev = place.jax_device()
    return {k: jax.device_put(v, dev) for k, v in _feeds(**kw).items()}


def _delta(before, after):
    return {k: after[k] - before[k] for k in after}


# ---------------------------------------------------------------------------
# fast-path step cache
# ---------------------------------------------------------------------------

def test_step_compiles_once_across_dispatches():
    """Startup leaves the params uncommitted and a step's outputs are
    committed: unless the engine commits what the step donates before
    the FIRST dispatch, the second one has other argument shardings and
    XLA compiles the whole step again (invisible to counters["traces"];
    on the chip it doubled the cold set-up)."""
    main, startup, loss = _sgd_model()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=_feeds(), fetch_list=[loss.name])
    counts = exe._engine.step_executables()
    assert counts and set(counts) == {1}, counts


def test_steady_state_counters_zero_redundant_work():
    """After warmup, device-resident feeds hit the fast path: no
    signature rebuild, no re-trace, no device_put — per run."""
    main, startup, loss = _sgd_model()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = _device_feeds(exe.place)
        exe.run(main, feed=feed, fetch_list=[loss.name])  # warmup/trace
        before = dict(exe._engine.counters)
        vals = [exe.run(main, feed=feed, fetch_list=[loss.name])[0]
                for _ in range(5)]
        d = _delta(before, exe._engine.counters)
    assert d["runs"] == 5
    assert d["fast_path_hits"] == 5
    assert d["traces"] == 0
    assert d["sig_builds"] == 0
    assert d["device_puts"] == 0
    # and it is still actually training
    assert float(np.asarray(vals[-1])) < float(np.asarray(vals[0]))


def test_host_feeds_still_fast_path_with_one_put_each():
    """np feeds can't skip the H2D copy, but they must still skip the
    signature rebuild and trace."""
    main, startup, loss = _sgd_model()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = _feeds()
        exe.run(main, feed=feed, fetch_list=[loss.name])
        before = dict(exe._engine.counters)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss.name])
        d = _delta(before, exe._engine.counters)
    assert d["fast_path_hits"] == 3
    assert d["traces"] == 0 and d["sig_builds"] == 0
    assert d["device_puts"] == 3 * len(feed)  # exactly one put per feed


def test_fast_path_misses_on_shape_change():
    """A different feed signature must fall back to the slow path (and
    trace a second executable), not silently reuse the cached step."""
    main, startup, loss = _sgd_model()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=_feeds(batch=8), fetch_list=[loss.name])
        before = dict(exe._engine.counters)
        exe.run(main, feed=_feeds(batch=4), fetch_list=[loss.name])
        d = _delta(before, exe._engine.counters)
        assert d["traces"] == 1 and d["fast_path_hits"] == 0
        # both signatures now cached: each hits its own fast entry
        before = dict(exe._engine.counters)
        exe.run(main, feed=_feeds(batch=8), fetch_list=[loss.name])
        exe.run(main, feed=_feeds(batch=4), fetch_list=[loss.name])
        d = _delta(before, exe._engine.counters)
    assert d["fast_path_hits"] == 2 and d["traces"] == 0


def test_use_program_cache_false_bypasses_and_does_not_populate():
    main, startup, loss = _sgd_model()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = _feeds()
        before = dict(exe._engine.counters)
        exe.run(main, feed=feed, fetch_list=[loss.name],
                use_program_cache=False)
        exe.run(main, feed=feed, fetch_list=[loss.name],
                use_program_cache=False)
        d = _delta(before, exe._engine.counters)
        assert d["traces"] == 2          # re-traced every call
        assert d["fast_path_hits"] == 0  # never consulted
        # ...and nothing was cached for later either
        before = dict(exe._engine.counters)
        exe.run(main, feed=feed, fetch_list=[loss.name])
        d = _delta(before, exe._engine.counters)
    assert d["traces"] == 1 and d["fast_path_hits"] == 0


# ---------------------------------------------------------------------------
# async fetch handles
# ---------------------------------------------------------------------------

def test_sync_async_numeric_equivalence():
    """The same 3 steps run sync and async (FetchHandles) must produce
    identical losses and identical final params."""
    main, startup, loss = _sgd_model()
    feed = _feeds()
    w_name = [p.name for p in main.global_block().all_parameters()]

    def run3(async_mode):
        scope = Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for n in w_name:  # deterministic init
                v = scope.find_var(n).get_value()
                arr = np.asarray(v)
                scope.var(n).set_value(jax.numpy.zeros_like(arr) + 0.01)
            losses = []
            for _ in range(3):
                out = exe.run(main, feed=feed, fetch_list=[loss.name],
                              return_numpy=not async_mode)
                losses.append(out[0])
            if async_mode:
                assert all(isinstance(h, FetchHandle) for h in losses)
                exe.synchronize()
                losses = [h.numpy() for h in losses]
            params = {n: np.asarray(scope.find_var(n).get_value())
                      for n in w_name}
        return [np.asarray(l).reshape(()) for l in losses], params

    fluid.set_flags({"FLAGS_async_dispatch": True})
    try:
        la, pa = run3(async_mode=True)
    finally:
        fluid.set_flags({"FLAGS_async_dispatch": False})
    ls, ps = run3(async_mode=False)
    np.testing.assert_allclose(la, ls, rtol=1e-6, atol=1e-7)
    for n in ps:
        np.testing.assert_allclose(pa[n], ps[n], rtol=1e-6, atol=1e-7)


def test_fetch_handle_api_surface():
    main, startup, loss = _sgd_model()
    scope = Scope()
    fluid.set_flags({"FLAGS_async_dispatch": True})
    try:
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            h, = exe.run(main, feed=_feeds(), fetch_list=[loss.name],
                         return_numpy=False)
            assert isinstance(h, FetchHandle)
            assert isinstance(h.array, jax.Array)  # live, not a copy
            assert h.lod() is None or h.lod() == []
            val = float(h)  # materializes
            assert np.isfinite(val)
            assert h.is_ready()
            assert loss.name in repr(h)
            np.testing.assert_allclose(np.asarray(h).reshape(()), val)
    finally:
        fluid.set_flags({"FLAGS_async_dispatch": False})


def test_return_numpy_false_without_flag_stays_eager_arrays():
    """Without FLAGS.async_dispatch, return_numpy=False keeps the seed
    behavior (no FetchHandle wrapper)."""
    main, startup, loss = _sgd_model()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out, = exe.run(main, feed=_feeds(), fetch_list=[loss.name],
                       return_numpy=False)
        assert not isinstance(out, FetchHandle)


# ---------------------------------------------------------------------------
# deferred error surfacing
# ---------------------------------------------------------------------------

def _nan_program():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [3], dtype="float32")
        out = layers.mean(layers.log(x))  # log(-1) -> nan
    return main, startup, out


def test_deferred_nan_reraise_is_sticky_and_names_op():
    main, startup, out = _nan_program()
    feed = {"x": -np.ones((2, 3), np.float32)}
    scope = Scope()
    fluid.set_flags({"FLAGS_async_dispatch": True,
                     "FLAGS_check_nan_inf": True})
    try:
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            # dispatch does NOT raise: the nan check is deferred
            h, = exe.run(main, feed=feed, fetch_list=[out.name],
                         return_numpy=False)
            with pytest.raises(fluid.EnforceNotMet) as ei:
                h.numpy()
            assert "log" in str(ei.value)
            # sticky: the same poisoned step raises again
            with pytest.raises(fluid.EnforceNotMet):
                np.asarray(h)
    finally:
        fluid.set_flags({"FLAGS_async_dispatch": False,
                         "FLAGS_check_nan_inf": False})


def test_synchronize_drains_pending_checks():
    main, startup, out = _nan_program()
    scope = Scope()
    fluid.set_flags({"FLAGS_async_dispatch": True,
                     "FLAGS_check_nan_inf": True})
    try:
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            # healthy step: synchronize is a clean barrier
            exe.run(main, feed={"x": np.ones((2, 3), np.float32)},
                    fetch_list=[out.name], return_numpy=False)
            exe.synchronize()
            # poisoned step: synchronize surfaces it even if no handle
            # is ever materialized
            exe.run(main, feed={"x": -np.ones((2, 3), np.float32)},
                    fetch_list=[out.name], return_numpy=False)
            with pytest.raises(fluid.EnforceNotMet):
                exe.synchronize()
            # drained: a second synchronize is clean again
            exe.synchronize()
    finally:
        fluid.set_flags({"FLAGS_async_dispatch": False,
                         "FLAGS_check_nan_inf": False})


def test_sync_path_still_raises_inline():
    """check_nan_inf without async keeps the seed's inline raise."""
    main, startup, out = _nan_program()
    scope = Scope()
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            with pytest.raises(fluid.EnforceNotMet):
                exe.run(main, feed={"x": -np.ones((2, 3), np.float32)},
                        fetch_list=[out.name])
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})


# ---------------------------------------------------------------------------
# feed prefetcher
# ---------------------------------------------------------------------------

def test_prefetcher_preserves_order_and_moves_to_device():
    from paddle_tpu.reader import DeviceFeedPrefetcher
    batches = [{"x": np.full((2, 2), i, np.float32)} for i in range(6)]
    pf = DeviceFeedPrefetcher(lambda: iter(batches),
                              place=fluid.CPUPlace(), depth=2)
    got = list(pf)
    assert len(got) == 6
    for i, b in enumerate(got):
        assert isinstance(b["x"], jax.Array)
        np.testing.assert_array_equal(np.asarray(b["x"]),
                                      np.full((2, 2), i, np.float32))


def test_prefetcher_reiterable_and_propagates_errors():
    from paddle_tpu.reader import DeviceFeedPrefetcher

    def bad_reader():
        yield {"x": np.zeros((1,), np.float32)}
        raise ValueError("boom in reader thread")

    pf = DeviceFeedPrefetcher(bad_reader, depth=2)
    it = iter(pf)
    next(it)
    with pytest.raises(ValueError, match="boom in reader thread"):
        next(it)
    with pytest.raises(ValueError):  # generator factory: re-iterable
        list(pf)


def test_prefetcher_feeds_hit_the_fast_path():
    """End-to-end: prefetched device batches keep steady state at zero
    device_puts inside run()."""
    from paddle_tpu.reader import DeviceFeedPrefetcher
    main, startup, loss = _sgd_model()
    rng = np.random.RandomState(7)
    batches = [{"x": rng.rand(8, 4).astype(np.float32),
                "y": rng.rand(8, 1).astype(np.float32)}
               for _ in range(4)]
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        pf = DeviceFeedPrefetcher(lambda: iter(batches), place=exe.place)
        it = iter(pf)
        exe.run(main, feed=next(it), fetch_list=[loss.name])  # warmup
        before = dict(exe._engine.counters)
        for b in it:
            exe.run(main, feed=b, fetch_list=[loss.name])
        d = _delta(before, exe._engine.counters)
    assert d["runs"] == 3 and d["fast_path_hits"] == 3
    assert d["device_puts"] == 0  # prefetcher already placed them
