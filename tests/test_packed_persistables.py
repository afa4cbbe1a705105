"""Packed one-element persistables (core/packing.py): the step takes
its `f32[1]` accumulators and counters as a few packed arguments, and
the scope still reads and writes every one of them by name.

The reference is the same program on a one-device mesh, which keeps one
argument per persistable. The reduced programs are two benchmark cells
at their rehearsal sizes: the encoder-decoder Transformer and the
Mamba-2 / attention / MoE decoder."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.engine import Engine
from paddle_tpu.core.flags import set_flags
from paddle_tpu.core.scope import Scope
from paddle_tpu.distributed import faults

CELLS = ("tbase_s128", "twotower_s4096")


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def _persistables(program):
    return sorted(n for n, v in program.global_block().vars.items()
                  if v.persistable and not n.startswith("@"))


def _state(scope, names):
    out = {}
    for n in names:
        v = scope.find_var(n)
        if v is not None and v.is_initialized():
            val = v.get_value()
            out[n] = np.array(getattr(val, "array", val))
    return out


def _assert_close(got, want, rtol=1e-6):
    assert set(got) == set(want)
    for n in want:
        scale = max(float(np.max(np.abs(want[n]), initial=0.0)), 1e-30)
        err = float(np.max(np.abs(got[n] - want[n]), initial=0.0))
        assert err <= rtol * scale, (n, err, scale)


def _copy_scope(src, dst, names):
    for n, a in _state(src, names).items():
        dst.var(n).set_value(jnp.asarray(a))


def _packed_names(engine, updated_only=False):
    return [n for t in engine._cache.values() for lay in t.packs
            if lay.donated or not updated_only for n in lay.names]


class _Pair:
    """One program run side by side: packed (the default engine) and on
    a one-device mesh (per-name arguments), from one initial state."""

    def __init__(self, main, startup, fetch):
        self.main, self.fetch = main, fetch
        self.names = _persistables(main)
        self.packed, self.meshed = Scope(), Scope()
        self.eng = Engine()
        self.ref = Engine(mesh=_one_device_mesh())
        self.eng.run(startup, self.packed, fluid.CPUPlace(), {}, [])
        _copy_scope(self.packed, self.meshed, self.names)

    def step(self, feed, fetch=None, **kw):
        fetch = list(fetch or self.fetch)
        a = self.eng.run(self.main, self.packed, fluid.CPUPlace(), feed,
                         fetch, **kw)
        b = self.ref.run(self.main, self.meshed, None, feed, fetch, **kw)
        return a, b

    def states(self):
        return _state(self.packed, self.names), _state(self.meshed,
                                                       self.names)


def _cell_pair(cell):
    from benchmark.lib.cells import Cell
    c = Cell(cell)
    fam = c.family
    sz = fam.sizes(c.config, True)
    tr = fam.traffic(c.traffic, True)
    main, startup, cost = fam.build(fluid, sz, 0)
    return _Pair(main, startup, [cost.name]), fam.make_pool(sz, tr, 0)


@pytest.fixture(scope="module", params=CELLS)
def cell_run(request):
    """Three steps of a reduced cell, packed and per-name; then a host
    write to a packed member, and a fourth step that fetches it."""
    pair, pool = _cell_pair(request.param)
    got = {"pair": pair}
    for i in range(3):
        pair.step(pool[i % len(pool)])
        if i in (0, 2):
            got[f"after_{i + 1}"] = pair.states()
    name = _packed_names(pair.eng, updated_only=True)[0]
    value = np.array(pair.packed.find_var(name).get_value()) * 0.5 + 0.25
    got["written"] = (name, value)
    for scope in (pair.packed, pair.meshed):
        scope.var(name).set_value(jnp.asarray(value))
    got["pack_after_write"] = pair.packed.find_var(name).pack
    got["fetched"] = pair.step(pool[3 % len(pool)],
                               pair.fetch + [name])
    got["after_4"] = pair.states()
    return got


@pytest.mark.parametrize("steps", [1, 3])
def test_state_matches_per_name_step(cell_run, steps):
    pair = cell_run["pair"]
    assert pair.eng.counters["packed_persistables"] > 0
    assert all(not t.packs for t in pair.ref._cache.values())
    got, want = cell_run[f"after_{steps}"]
    _assert_close(got, want)


def test_host_write_to_packed_name_reaches_next_step(cell_run):
    name, value = cell_run["written"]
    assert cell_run["pack_after_write"] is None
    got, want = cell_run["after_4"]
    _assert_close(got, want)
    # the engine packed the member again, and the step read the write
    pair = cell_run["pair"]
    assert pair.packed.find_var(name).pack is not None
    assert not np.allclose(got[name], value)


def test_fetch_of_packed_name(cell_run):
    (a, b) = cell_run["fetched"]
    assert len(a) == len(b) == 2
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=0)


def test_step_args_count_the_packs(cell_run):
    """The arguments of the packed step: every persistable it reads,
    less the packed members, plus one per pack, the feeds and the rng
    state — below two thirds of the persistables the program has."""
    pair = cell_run["pair"]
    traced = next(t for t in pair.eng._cache.values() if t.packs)
    c = pair.eng.counters
    read = len(traced.donated_names) + len(traced.const_names)
    packed = sum(len(lay.names) for lay in traced.packs)
    assert c["packed_persistables"] == packed
    assert c["step_args"] == (read - packed + len(traced.packs)
                              + len(traced.feed_names) + 1)
    assert c["step_args"] < 2 * len(pair.names) / 3
    meta = pair.eng.donation_metadata()
    assert any(row["packed"] for row in meta)


def test_only_one_element_persistables_are_packed(cell_run):
    """What the memory repair rests on: a vector (norm scale, bias, a
    moment of either) is never a member of a pack, so the compiled step
    reads and writes every multi-element persistable as its own
    argument, as the per-name step does; packing vectors moved XLA's
    placement of the step's temporaries by hundreds of MB."""
    pair = cell_run["pair"]
    traced = next(t for t in pair.eng._cache.values() if t.packs)
    sizes = {n: v.size for n, v in cell_run["after_4"][0].items()}
    members = {n for lay in traced.packs for n in lay.names}
    assert members and all(sizes[n] == 1 for n in members)
    vectors = {n for n in traced.donated_names + traced.const_names
               if sizes.get(n, 1) > 1}
    assert vectors
    assert vectors <= set(traced.donated_args + traced.const_args)
    for lay in traced.packs:
        assert lay.total == len(lay.names)


# ---------------------------------------------------------------------------
# the rest of the scope's contract, on a small Adam MLP
# ---------------------------------------------------------------------------

def _mlp(seed=7):
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, 8, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(pred - y))
        fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _feeds(n, seed=0, nan_at=None):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        xv = rng.rand(8, 4).astype("float32")
        if i == nan_at:
            xv[0, 0] = np.nan
        out.append({"x": xv, "y": rng.rand(8, 1).astype("float32")})
    return out


def test_save_load_round_trip(tmp_path):
    main, startup, loss = _mlp()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        for feed in _feeds(2):
            exe.run(main, feed=feed, fetch_list=[loss])
        names = _persistables(main)
        packed = _packed_names(exe._engine)
        assert packed
        saved = _state(scope, names)
        fluid.io.save_persistables(exe, str(tmp_path), main)
        for n in names:
            scope.var(n).set_value(jnp.zeros_like(saved[n]))
        fluid.io.load_persistables(exe, str(tmp_path), main)
        _assert_close(_state(scope, names), saved, rtol=0)
        # the loaded values are what the next step reads
        a = exe.run(main, feed=_feeds(3)[2], fetch_list=[loss])
    ref = Scope()
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(ref):
        exe2.run(startup)
        for feed in _feeds(3):
            b = exe2.run(main, feed=feed, fetch_list=[loss])
    np.testing.assert_array_equal(a[0], b[0])


def test_iterations_and_multi_step_match_single_steps():
    main, startup, loss = _mlp()
    feed = _feeds(1)[0]
    names = _persistables(main)

    def fresh():
        scope, eng = Scope(), Engine()
        eng.run(startup, scope, fluid.CPUPlace(), {}, [])
        return scope, eng

    s1, e1 = fresh()
    for _ in range(2):
        e1.run(main, s1, fluid.CPUPlace(), feed, [loss.name])
    assert e1.counters["packed_persistables"] > 0
    s2, e2 = fresh()
    e2.run(main, s2, fluid.CPUPlace(), feed, [loss.name], iterations=2)
    assert e2.counters["packed_persistables"] > 0
    _assert_close(_state(s2, names), _state(s1, names))
    s3, e3 = fresh()
    e3.run_multi(main, s3, fluid.CPUPlace(), [feed, feed], [loss.name])
    assert e3.counters["multistep_dispatches"] == 1
    assert e3.counters["packed_persistables"] > 0
    _assert_close(_state(s3, names), _state(s1, names))


def test_meshed_step_keeps_per_name_arguments():
    main, startup, loss = _mlp()
    pair = _Pair(main, startup, [loss.name])
    pair.step(_feeds(1)[0])
    traced = list(pair.ref._cache.values())
    assert traced and all(not t.packs for t in traced)
    c = pair.ref.counters
    assert c["packed_persistables"] == 0
    main_traced = next(t for t in traced if t.fetch_names)
    assert c["step_args"] == (len(main_traced.donated_names)
                              + len(main_traced.const_names)
                              + len(main_traced.feed_names) + 1)
    assert pair.eng.counters["step_args"] < c["step_args"]


def test_corrupt_scope_reaches_a_packed_member():
    main, startup, loss = _mlp()
    feeds = _feeds(2)
    runs = []
    for plan in (None, "x"):
        scope, eng = Scope(), Engine()
        eng.run(startup, scope, fluid.CPUPlace(), {}, [])
        eng.run(main, scope, fluid.CPUPlace(), feeds[0], [loss.name])
        name = sorted(n for n in _packed_names(eng)
                      if n.endswith("beta1_pow_acc_0"))[0]
        before = np.array(scope.find_var(name).get_value())
        fp = None if plan is None else faults.FaultPlan(
            seed=1, bitflip_step=eng.counters["runs"] + 1,
            bitflip_param=name, bitflip_bit=30)
        with faults.scoped(fp):
            eng.run(main, scope, fluid.CPUPlace(), feeds[1], [loss.name])
        runs.append((name, before,
                     np.array(scope.find_var(name).get_value())))
    (name, before, clean), (_, _, hit) = runs
    flipped = before.copy()
    flipped.view(np.uint32)[0] ^= np.uint32(1 << 30)
    # the step multiplied what the fault wrote, not the old value
    np.testing.assert_allclose(clean, before * np.float32(0.9), rtol=1e-6)
    np.testing.assert_allclose(hit, flipped * np.float32(0.9), rtol=1e-6)


def test_guard_rollback_reexecutes_from_packed_state(tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("PT_STABILITY_POLICY", "rollback")
    monkeypatch.setenv("PT_GHOST_EVERY", "1")
    monkeypatch.setenv("PT_REPLAY_DIR", str(tmp_path))
    feeds = _feeds(4, nan_at=2)

    def run(fs):
        main, startup, loss = _mlp()
        scope, eng = Scope(), Engine()
        eng.run(startup, scope, fluid.CPUPlace(), {}, [])
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for f in fs:
                eng.run(main, scope, fluid.CPUPlace(), f, [loss.name])
        return _state(scope, _persistables(main)), eng

    set_flags({"FLAGS_stability_guard": True})
    try:
        ref, _ = run(feeds[:2] + feeds[3:])
        bad, eng = run(feeds)
    finally:
        set_flags({"FLAGS_stability_guard": False})
    assert eng.counters["rollbacks"] >= 1
    assert eng.counters["packed_persistables"] > 0
    packed = set(_packed_names(eng))
    assert packed
    for n in ref:
        np.testing.assert_array_equal(bad[n], ref[n], err_msg=n)


def test_host_write_of_another_shape_traces_again():
    """A host write that reshapes a packed member is a new signature,
    as it is for the per-name step: the step is traced again."""
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        a = layers.create_global_var(shape=[1], value=0.0, dtype="float32",
                                     persistable=True, name="ctr_a")
        b = layers.create_global_var(shape=[1], value=1.0, dtype="float32",
                                     persistable=True, name="ctr_b")
        layers.increment(a, value=1.0, in_place=True)
        layers.increment(b, value=2.0, in_place=True)
    scope, eng = Scope(), Engine()
    eng.run(startup, scope, fluid.CPUPlace(), {}, [])
    eng.run(main, scope, fluid.CPUPlace(), {}, [])
    assert eng.counters["packed_persistables"] == 2
    traces = eng.counters["traces"]
    scope.var("ctr_a").set_value(jnp.zeros((3,), jnp.float32))
    eng.run(main, scope, fluid.CPUPlace(), {}, [])
    assert eng.counters["traces"] == traces + 1
    assert eng.counters["packed_persistables"] == 0
    np.testing.assert_array_equal(
        np.asarray(scope.find_var("ctr_a").get_value()), [1.0, 1.0, 1.0])
    np.testing.assert_array_equal(
        np.asarray(scope.find_var("ctr_b").get_value()), [5.0])
