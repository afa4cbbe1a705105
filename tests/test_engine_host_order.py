"""What the engine does between the enqueue of a step and the call that
blocks on its result (core/engine.py `_dispatch_inner`): the rng state
is split inside the compiled step and the argument dicts are dropped
before the fetch waits. None of it may change a value: the stream of
step keys, the scope's rng state and the losses are what host-side
`jax.random.split`s of the seed give, and every fetch comes back in the
form it always had.
"""
import os
import warnings

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import registry
from paddle_tpu.core.engine import Engine, RNG_STATE_VAR
from paddle_tpu.core.async_dispatch import FetchHandle
from paddle_tpu.core.flags import set_flags
from paddle_tpu.core.scope import LoDTensor, Scope

SEED = 7
_ENV_KEYS = ("PT_STABILITY_POLICY", "PT_GHOST_EVERY",
             "PT_GUARD_REPLAY_MAX")


@pytest.fixture(autouse=True)
def _reset():
    saved = {k: os.environ.get(k) for k in _ENV_KEYS}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    set_flags({"FLAGS_stability_guard": False,
               "FLAGS_async_dispatch": False,
               "FLAGS_check_nan_inf": False})


@pytest.fixture
def step_keys(monkeypatch):
    """Every step key a compiled step draws, as the device saw it: the
    trace-time `step_key()` also hands its value to the host."""
    seen, told = [], []
    real = registry._RngCtx.step_key

    def step_key(self):
        key = real(self)
        if not any(ctx is self for ctx in told):    # once a trace
            told.append(self)
            jax.debug.callback(
                lambda k: seen.append(np.asarray(k).tolist()), key)
        return key

    monkeypatch.setattr(registry._RngCtx, "step_key", step_key)
    return seen


def _dropout_program():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = SEED
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.dropout(layers.fc(x, 8, act="relu"), dropout_prob=0.5)
        loss = layers.mean(layers.square(layers.fc(h, 1) - y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feeds(steps, nan_at=None):
    rng = np.random.RandomState(0)
    feeds = []
    for i in range(steps):
        xv = rng.rand(8, 4).astype("float32")
        yv = rng.rand(8, 1).astype("float32")
        if i == nan_at:
            xv[0, 0] = np.nan
        feeds.append({"x": xv, "y": yv})
    return feeds


def _host_chain(state, n):
    """([step key], [state after each step]) of n host-side splits."""
    keys, states = [], []
    for _ in range(n):
        key, state = jax.random.split(state)
        keys.append(np.asarray(key).tolist())
        states.append(np.asarray(state).tolist())
    return keys, states


def _state(scope):
    return np.asarray(scope.find_var(RNG_STATE_VAR).get_value()).tolist()


def _bits(out):
    return np.asarray(out[0]).reshape(-1)[0].tobytes()


def _drive(mode, feeds):
    """Fresh program, scope and engine; the startup program is the
    stream's first step. Returns (loss bits, states after each main
    dispatch, the state the main program started from)."""
    main, startup, loss = _dropout_program()
    scope = Scope()
    losses, states = [], []
    with fluid.scope_guard(scope), warnings.catch_warnings():
        warnings.simplefilter("ignore")
        fluid.Executor().run(startup)
        start = scope.find_var(RNG_STATE_VAR).get_value()
        eng = Engine()
        if mode == "multi2":
            for i in range(0, len(feeds), 2):
                rows = eng.run_multi(main, scope, None, feeds[i:i + 2],
                                     [loss.name])
                losses += [_bits(r) for r in rows]
                states.append(_state(scope))
        else:
            for i, feed in enumerate(feeds):
                if mode == "set_value" and i == 3:
                    scope.find_var(RNG_STATE_VAR).set_value(
                        np.array([5, 9], np.uint32))
                out = eng.run(main, scope, None, feed, [loss.name])
                losses.append(_bits(out))
                states.append(_state(scope))
        jax.effects_barrier()
    return losses, states, start, eng


def test_startup_state_is_one_split_of_the_seed():
    _, _, start, _ = _drive("plain", _feeds(1))
    _, (after_startup,) = _host_chain(jax.random.PRNGKey(SEED), 1)
    assert np.asarray(start).tolist() == after_startup


def test_keys_states_and_losses_follow_host_splits(step_keys):
    losses, states, start, _ = _drive("plain", _feeds(5))
    keys, want = _host_chain(start, 5)
    # the startup program draws too (its initialisers): its key first
    assert step_keys[-5:] == keys
    assert states == want
    assert len(set(losses)) == 5        # dropout really drew


def test_set_value_between_steps_restarts_the_chain(step_keys):
    losses, states, start, _ = _drive("set_value", _feeds(5))
    keys, want = _host_chain(start, 3)
    keys2, want2 = _host_chain(np.array([5, 9], np.uint32), 2)
    assert step_keys[-5:] == keys + keys2
    assert states == want + want2
    plain, _, _, _ = _drive("plain", _feeds(5))
    assert losses[:3] == plain[:3] and losses[3:] != plain[3:]


def test_multi_step_draws_the_same_chain(step_keys):
    plain, p_states, start, _ = _drive("plain", _feeds(4))
    del step_keys[:]
    losses, states, start2, eng = _drive("multi2", _feeds(4))
    keys, want = _host_chain(start, 4)
    assert np.asarray(start2).tolist() == np.asarray(start).tolist()
    assert eng.counters["multistep_dispatches"] == 2
    assert step_keys[-4:] == keys
    assert states == want[1::2] == p_states[1::2]
    assert losses == plain


def test_guard_reexecution_draws_the_step_key_again(step_keys):
    """A rollback restores the ghost's rng state (the state the bad
    step started from) and the re-execution draws the same key; the
    steps after it go on down the chain."""
    os.environ["PT_STABILITY_POLICY"] = "rollback"
    os.environ["PT_GHOST_EVERY"] = "1"
    os.environ["PT_GUARD_REPLAY_MAX"] = "0"
    set_flags({"FLAGS_stability_guard": True})
    losses, states, start, eng = _drive("plain", _feeds(5, nan_at=2))
    assert eng.counters["rollbacks"] == 1
    keys, want = _host_chain(start, 5)
    assert step_keys[-6:] == keys[:3] + keys[2:]
    assert states == want
    set_flags({"FLAGS_stability_guard": False})
    plain, _, _, _ = _drive("plain", _feeds(5))
    assert losses[:2] == plain[:2]
    assert np.isnan(np.frombuffer(losses[2], np.float32)[0])


def test_one_executable_whoever_made_the_state():
    """The state a step hands back is committed like its other outputs;
    a startup program's and a user's own are not. The engine commits
    what it is given, so the step never compiles a second time."""
    main, startup, loss = _dropout_program()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for i, feed in enumerate(_feeds(5)):
            if i == 3:
                scope.find_var(RNG_STATE_VAR).set_value(
                    np.array([5, 9], np.uint32))
            exe.run(main, feed=feed, fetch_list=[loss])
    assert exe._engine.step_executables() == [1, 1]


# ---------------------------------------------------------------------------
# the fetch: the last phase of the step, its form what it always was
# ---------------------------------------------------------------------------

def _fetch_program():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        seq = layers.data("seq", [1], dtype="float32", lod_level=1)
        hidden = layers.fc(x, 3)
        loss = layers.mean(hidden)
        pooled = layers.sequence_softmax(seq)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
    feed = {"x": np.arange(8, dtype=np.float32).reshape(2, 4),
            "seq": LoDTensor(np.arange(5, dtype=np.float32).reshape(5, 1),
                            [[0, 2, 5]])}
    return main, scope, feed, [loss.name, hidden.name, pooled.name]


@pytest.mark.parametrize("check_nan", [False, True])
def test_numpy_fetch_equals_the_unfetched_array(check_nan):
    set_flags({"FLAGS_check_nan_inf": check_nan})
    main, scope, feed, names = _fetch_program()
    a = Engine().run(main, scope, None, feed, names, return_numpy=True)
    main, scope, feed, names = _fetch_program()
    b = Engine().run(main, scope, None, feed, names, return_numpy=False)
    for got in a[:2]:
        assert type(got) is np.ndarray
    for got, dev in zip(a[:2], b[:2]):
        assert isinstance(dev, LoDTensor) and not dev.lod()
        assert isinstance(dev.array, jax.Array)
        assert got.tobytes() == np.asarray(dev.array).tobytes()
        assert got.dtype == dev.array.dtype and got.shape == dev.shape()
    # a fetch with a LoD stays a LoDTensor over the device array
    for out in (a, b):
        assert isinstance(out[2], LoDTensor)
        assert out[2].lod() == [[0, 2, 5]]
        assert isinstance(out[2].array, jax.Array)
    assert np.asarray(a[2].array).tobytes() \
        == np.asarray(b[2].array).tobytes()


def test_async_defer_fetch_is_a_handle():
    set_flags({"FLAGS_async_dispatch": True})
    main, scope, feed, names = _fetch_program()
    eng = Engine()
    out = eng.run(main, scope, None, feed, names, return_numpy=False)
    assert all(isinstance(h, FetchHandle) for h in out)
    eng.synchronize()
    set_flags({"FLAGS_async_dispatch": False})
    main, scope, feed, names = _fetch_program()
    want = Engine().run(main, scope, None, feed, names)
    assert np.asarray(out[0]).tobytes() == want[0].tobytes()
    assert np.asarray(out[1]).tobytes() == want[1].tobytes()
