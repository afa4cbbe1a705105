"""Multi-step dispatch (PT_MULTI_STEP, docs/ASYNC_DISPATCH.md).

The K-substep ``lax.scan`` driver fuses K training steps into ONE
dispatched executable. Its contract, pinned here:

* anomaly-free slabs are BIT-identical to K sequential ``run()`` calls
  — losses and every persistable (params, optimizer accumulators, RNG
  chain), guard off and guard on alike;
* an anomaly at substep j < K trips the verdict-conditioned carry
  freeze: substeps > j execute as no-ops on device, the host replays
  the frozen tail through the K=1 path, and the stitched trajectory is
  bit-identical to sequential guard-on training;
* the prefetcher's slab mode keeps the exactly-once cursor contract:
  a kill mid-slab replays the WHOLE in-flight slab after resume —
  no batch repeated, none skipped (slab-atomic rewind).
"""
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.engine import Engine
from paddle_tpu.core.flags import set_flags
from paddle_tpu.core.scope import Scope
from paddle_tpu.reader.prefetcher import DeviceFeedPrefetcher, FeedSlab

_ENV_KEYS = ("PT_MULTI_STEP", "PT_STABILITY_POLICY", "PT_GHOST_EVERY",
             "PT_PREFETCH_DEPTH")


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
               "FLAGS_async_dispatch": False})


def _build_mlp():
    x = layers.data("x", [4], dtype="float32")
    y = layers.data("y", [1], dtype="float32")
    h = layers.fc(x, 8, act="relu")
    pred = layers.fc(h, 1)
    loss = layers.mean(layers.square(pred - y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def _feeds(steps, nan_at=None, seed=0):
    rng = np.random.RandomState(seed)
    feeds = []
    for i in range(steps):
        xv = rng.rand(8, 4).astype("float32")
        yv = rng.rand(8, 1).astype("float32")
        if i == nan_at:
            xv = xv.copy()
            xv[0, 0] = np.nan
        feeds.append({"x": xv, "y": yv})
    return feeds


def _run(steps=4, k=1, guard=False, nan_at=None, seed=7):
    """Fresh program/scope/engine; k=1 drives sequential ``run()``,
    k>1 drives ``run_multi`` over K-batch slabs. Returns
    (losses, params, engine)."""
    set_flags({"FLAGS_stability_guard": guard})
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        loss = _build_mlp()
    scope = Scope()
    exe = fluid.Executor()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        eng = Engine()
        feeds = _feeds(steps, nan_at=nan_at)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            if k == 1:
                for feed in feeds:
                    out = eng.run(main, scope, None, feed, [loss.name])
                    losses.append(
                        float(np.asarray(out[0]).reshape(-1)[0]))
            else:
                for i in range(0, steps, k):
                    rows = eng.run_multi(main, scope, None,
                                         feeds[i:i + k], [loss.name])
                    for row in rows:
                        losses.append(
                            float(np.asarray(row[0]).reshape(-1)[0]))
            eng.synchronize()
        params = {
            n: np.array(scope.var(n).get_tensor()._array)
            for n in sorted(main.global_block().vars)
            if main.global_block().vars[n].persistable
            and not n.startswith("@")}
    return losses, params, eng


# ---------------------------------------------------------------------------
# bit-identity: K fused substeps == K sequential steps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 4])
def test_multistep_bit_identical_trajectory(k):
    l_ref, p_ref, _ = _run(steps=4, k=1)
    l_k, p_k, eng = _run(steps=4, k=k)
    assert l_ref == l_k
    assert sorted(p_ref) == sorted(p_k)
    for n in p_ref:
        np.testing.assert_array_equal(p_ref[n], p_k[n])
    if k > 1:
        assert eng.counters["multistep_dispatches"] == 4 // k
        assert eng.counters["multistep_substeps"] == 4
        assert eng.counters["multistep_early_exits"] == 0
        assert eng.counters["multistep_replays"] == 0


def test_multistep_run_multi_accepts_prestacked_slab():
    """run_multi takes a FeedSlab built by the prefetcher's slab mode
    (or FeedSlab.stack) verbatim — same trajectory as the list form."""
    l_ref, p_ref, _ = _run(steps=4, k=1)
    set_flags({"FLAGS_stability_guard": False})
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with fluid.program_guard(main, startup):
        loss = _build_mlp()
    scope = Scope()
    exe = fluid.Executor()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        eng = Engine()
        feeds = _feeds(4)
        for i in range(0, 4, 2):
            slab = FeedSlab.stack(feeds[i:i + 2])
            assert slab.multi_step == 2
            rows = eng.run_multi(main, scope, None, slab, [loss.name])
            losses += [float(np.asarray(r[0]).reshape(-1)[0])
                       for r in rows]
        eng.synchronize()
    assert losses == l_ref


# ---------------------------------------------------------------------------
# guard: anomaly-free parity, early break-out + host tail replay
# ---------------------------------------------------------------------------

def test_multistep_guard_parity_anomaly_free():
    l_ref, p_ref, _ = _run(steps=4, k=1, guard=True)
    l_k, p_k, eng = _run(steps=4, k=4, guard=True)
    assert l_ref == l_k
    for n in p_ref:
        np.testing.assert_array_equal(p_ref[n], p_k[n])
    assert eng.counters["multistep_early_exits"] == 0
    assert eng._last_multi == {"k": 4, "valid": 4}


def test_multistep_guard_nan_early_exit_and_replay():
    """NaN injected at substep 2 of a K=4 slab: the carry freeze halts
    substep 3 on device (valid=3: substeps 0,1 plus the gated anomaly
    step), the host replays the frozen tail through the K=1 path, and
    the stitched result is bit-identical to sequential guard-on
    training (loss rows compared with NaN==NaN)."""
    l_ref, p_ref, _ = _run(steps=4, k=1, guard=True, nan_at=2)
    l_k, p_k, eng = _run(steps=4, k=4, guard=True, nan_at=2)
    np.testing.assert_array_equal(np.asarray(l_ref), np.asarray(l_k))
    for n in p_ref:
        np.testing.assert_array_equal(p_ref[n], p_k[n])
    assert eng._last_multi == {"k": 4, "valid": 3}
    assert eng.counters["multistep_early_exits"] == 1
    assert eng.counters["multistep_replays"] == 1


# ---------------------------------------------------------------------------
# slab construction guards
# ---------------------------------------------------------------------------

def test_feedslab_rejects_ragged_lod_batches():
    from paddle_tpu.core.scope import LoDTensor
    ragged = {"x": LoDTensor(np.zeros((3, 4), np.float32), [[0, 1, 3]])}
    with pytest.raises(ValueError, match="LoD"):
        FeedSlab.stack([ragged, ragged])


def test_multistep_rejects_lod_feeds_at_run():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = _build_mlp()
    scope = Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
        eng = Engine()
        from paddle_tpu.core.scope import LoDTensor
        slab = FeedSlab()
        slab["x"] = LoDTensor(np.zeros((2, 8, 4), np.float32),
                              [[0, 4, 8]])
        slab["y"] = np.zeros((2, 8, 1), np.float32)
        slab.multi_step = 2
        with pytest.raises(NotImplementedError, match="LoD"):
            eng.run(main, scope, None, slab, [loss.name])


# ---------------------------------------------------------------------------
# prefetcher slab mode: exactly-once kill-and-resume, slab-atomic
# ---------------------------------------------------------------------------

def _src_pipeline(n=16):
    from paddle_tpu import reader as rd
    from paddle_tpu.reader.decorators import _CursorForwardingReader

    def src():
        def r():
            for i in range(n):
                yield (np.full((2,), i, np.float32),)
        return r

    b = rd.batch(src(), batch_size=2)
    return _CursorForwardingReader(
        lambda: ({"x": np.stack([s[0] for s in samples])}
                 for samples in b()), b)


def test_prefetcher_slab_mode_groups_k_batches():
    pf = DeviceFeedPrefetcher(_src_pipeline(), depth=2, multi_step=2)
    slabs = list(pf)
    # 8 source batches -> 4 slabs of K=2, leading axis = K
    assert len(slabs) == 4
    for slab in slabs:
        assert getattr(slab, "multi_step", 1) == 2
        assert np.asarray(slab["x"]).shape == (2, 2, 2)
    # samples 0..15 in order, 2 per batch, 2 batches per slab
    flat = np.concatenate([np.asarray(s["x"]).reshape(-1) for s in
                           slabs])
    np.testing.assert_array_equal(flat, np.repeat(np.arange(16.0), 2))


def test_kill_mid_slab_resume_is_exactly_once():
    """Kill the consumer after 2 of 4 slabs with more staged in flight:
    state_dict() rewinds the source cursor by every batch no step ever
    consumed (in BATCH units, slab-atomic), so the resumed incarnation
    replays exactly batches 4..7 — none repeated, none skipped."""
    import time
    # 64 samples / batch 2 = 32 batches: long enough that the bounded
    # fill window cannot drain the epoch before the kill
    clean = [d["x"].copy() for d in _src_pipeline(64)()]
    assert len(clean) == 32

    pf = DeviceFeedPrefetcher(_src_pipeline(64), depth=3, multi_step=2)
    it = iter(pf)
    seen = [np.asarray(next(it)["x"]) for _ in range(2)]  # 2 slabs
    for j, got in enumerate(seen):
        np.testing.assert_array_equal(
            got, np.stack(clean[2 * j:2 * j + 2]))
    time.sleep(0.3)  # let the fill thread stage slabs ahead
    assert pf._produced > pf._consumed  # batches genuinely in flight
    cur = pf.state_dict()  # the "kill": capture, drop the iterator
    # 2 slabs x K=2 consumed; everything staged beyond that rewinds
    assert cur["offset"] == 4

    fresh = _src_pipeline(64)
    fresh.load_state_dict(cur)
    pf2 = DeviceFeedPrefetcher(fresh, depth=3, multi_step=2)
    rest = [np.asarray(s["x"]) for s in pf2]
    assert len(rest) == 14
    for j, got in enumerate(rest):
        np.testing.assert_array_equal(
            got, np.stack(clean[4 + 2 * j:4 + 2 * j + 2]))


def test_prefetcher_short_tail_falls_back_to_single_steps():
    """16 samples / batch 2 = 8 batches; K=3 -> two slabs + a 2-batch
    tail yielded as plain K=1 feeds (short tails never pad)."""
    pf = DeviceFeedPrefetcher(_src_pipeline(), depth=2, multi_step=3)
    items = list(pf)
    assert [int(getattr(i, "multi_step", 1) or 1) for i in items] == \
        [3, 3, 1, 1]
