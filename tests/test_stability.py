"""Training stability guard (paddle_tpu/stability/,
FLAGS_stability_guard; docs/STABILITY.md).

The guard's contract has two halves. OFF-path: with no anomaly, the
guard's in-trace verdict + elementwise gate must be bit-identical to a
guard-off run — on the whole-block jit, with and without gradient
accumulation.
ON-path: an injected NaN must be detected from ONE scalar fetch, the
policy applied (gated skip / ghost rollback + re-execution), and
training must continue without a process restart; the dumped replay
bundle must re-execute the bad step deterministically.
"""
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.engine import Engine
from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.core.flags import set_flags
from paddle_tpu.core.scope import Scope

_ENV_KEYS = ("PT_STABILITY_POLICY", "PT_GHOST_EVERY", "PT_GHOST_KEEP",
             "PT_GUARD_SPIKE_FACTOR", "PT_GUARD_ESCALATE_AFTER",
             "PT_REPLAY_DIR", "PT_GUARD_REPLAY_MAX", "PT_FAULT_PLAN")


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


def _run(steps=4, guard=False, accum=1, async_dispatch=False,
         nan_at=None, seed=7, feeds=None):
    """Fresh program/scope/engine; returns (losses, params, engine).
    ``accum`` > 1 runs every step as that many micro-batches."""
    set_flags({"FLAGS_stability_guard": guard,
               "FLAGS_async_dispatch": async_dispatch})
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        loss = _build_mlp()
    if accum > 1:
        main._gradient_accumulation_steps = accum
    scope = Scope()
    exe = fluid.Executor()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        eng = Engine()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for feed in (feeds if feeds is not None
                         else _feeds(steps, nan_at=nan_at)):
                out = eng.run(main, scope, None, feed, [loss.name])
                losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
            eng.synchronize()
        params = {
            n: np.array(scope.var(n).get_tensor()._array)
            for n in sorted(main.global_block().vars)
            if main.global_block().vars[n].persistable
            and not n.startswith("@")}
    return losses, params, eng


# ---------------------------------------------------------------------------
# parity: guard on, no anomaly == guard off, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("accum", [1, 2],
                         ids=["whole_block", "grad_accum"])
def test_guard_off_on_parity(accum):
    l0, p0, _ = _run(guard=False, accum=accum)
    l1, p1, eng = _run(guard=True, accum=accum)
    assert l0 == l1
    assert sorted(p0) == sorted(p1)
    for n in p0:
        np.testing.assert_array_equal(p0[n], p1[n])
    assert eng.counters["traces"] == 1
    assert eng.counters["anomalies"] == 0


# ---------------------------------------------------------------------------
# detection + recovery, across dispatch paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("accum,async_dispatch",
                         [(1, False), (2, False), (1, True), (2, True)],
                         ids=["plain", "accum", "async", "accum_async"])
def test_nan_rollback_recovers(accum, async_dispatch, tmp_path):
    os.environ["PT_STABILITY_POLICY"] = "rollback"
    os.environ["PT_GHOST_EVERY"] = "1"
    os.environ["PT_REPLAY_DIR"] = str(tmp_path)
    feeds = _feeds(4, nan_at=2)
    if accum > 1:
        # poison the last row too: under accumulation the fetched loss
        # is the last micro-batch's
        feeds[2]["x"][-1, 0] = np.nan
    # reference: the same job with the poisoned step left out entirely
    ref, rp, _ = _run(guard=True, accum=accum,
                      async_dispatch=async_dispatch,
                      feeds=feeds[:2] + feeds[3:])
    bad, bp, eng = _run(guard=True, accum=accum,
                        async_dispatch=async_dispatch, feeds=feeds)
    # detected + rolled back + completed in-process; the poisoned feed
    # trips again on re-execution, so recovery lands as a gated skip
    assert eng.counters["anomalies"] >= 1
    assert eng.counters["rollbacks"] >= 1
    assert eng.counters["ghost_snapshots"] >= 1
    assert np.isnan(bad[2])
    # state protection: rollback + gated skip make the poisoned step a
    # no-op, so the rest of the trajectory is bit-identical to a run
    # that never saw it
    assert [bad[0], bad[1], bad[3]] == ref
    for n in bp:
        np.testing.assert_array_equal(bp[n], rp[n], err_msg=n)


def test_async_deferred_counting():
    # skip-policy + async dispatch: the verdict rides the pending-step
    # record and is counted at the synchronize() materialization point,
    # never forcing a mid-stream device sync
    os.environ["PT_STABILITY_POLICY"] = "skip"
    os.environ["PT_GUARD_REPLAY_MAX"] = "0"
    _, params, eng = _run(steps=4, guard=True, async_dispatch=True,
                          nan_at=1)
    assert eng.counters["anomalies"] >= 1
    assert eng.counters["rollbacks"] == 0
    for n in params:
        assert np.isfinite(params[n]).all(), n


def test_abort_policy_raises():
    os.environ["PT_STABILITY_POLICY"] = "abort"
    os.environ["PT_GUARD_REPLAY_MAX"] = "0"
    with pytest.raises(EnforceNotMet, match="stability guard"):
        _run(steps=3, guard=True, nan_at=1)


# ---------------------------------------------------------------------------
# ghost ring memory bound
# ---------------------------------------------------------------------------

def test_ghost_ring_bounded():
    from paddle_tpu.stability.ghost import GhostRing
    scope = Scope()
    names = [f"v{i}" for i in range(3)]
    for n in names:
        scope.var(n).set_value(np.zeros((16, 16), np.float32))
    ring = GhostRing(capacity=2)
    per_entry = 3 * 16 * 16 * 4
    for step in range(6):
        ring.capture(scope, names, step)
        assert len(ring) <= 2
        assert ring.nbytes() <= 2 * per_entry
    assert len(ring) == 2
    assert ring.latest().step == 5
    # restore hands back fresh copies; the entry survives
    scope.var("v0").set_value(np.ones((16, 16), np.float32))
    entry = ring.restore(scope)
    assert entry.step == 5
    np.testing.assert_array_equal(
        np.asarray(scope.var("v0").get_tensor()._array),
        np.zeros((16, 16), np.float32))
    assert len(ring) == 2


# ---------------------------------------------------------------------------
# replay bundle
# ---------------------------------------------------------------------------

def test_replay_bundle_reproduces(tmp_path):
    os.environ["PT_STABILITY_POLICY"] = "skip"
    os.environ["PT_REPLAY_DIR"] = str(tmp_path)
    _, _, eng = _run(steps=3, guard=True, nan_at=1)
    assert eng.counters["replay_bundles"] >= 1
    bundle = eng._stability.last.get("replay_bundle")
    assert bundle and os.path.isdir(bundle)
    from paddle_tpu.stability.replay import replay
    report = replay(bundle, quiet=True)
    assert report["verdict_match"]
    assert report["reproduced"]


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("async_dispatch", [False, True],
                         ids=["sync", "async"])
def test_scheduler_preserves_nan_check_labels(async_dispatch):
    # FLAGS_check_nan_inf on a block of two independent branches: the
    # sticky error must still name the op/var, sync or async
    set_flags({"FLAGS_check_nan_inf": True,
               "FLAGS_async_dispatch": async_dispatch})
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h1 = layers.fc(x, 8, act="relu")
        h2 = layers.fc(x, 8, act="relu")
        pred = layers.fc(layers.concat([h1, h2], axis=1), 1)
        loss = layers.mean(layers.square(pred - y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(startup)
        eng = Engine()
        feeds = _feeds(3, nan_at=1)
        with pytest.raises(EnforceNotMet,
                           match=r"Operator '\w+' output '\S+'"):
            for feed in feeds:
                eng.run(main, scope, None, feed, [loss.name])
            eng.synchronize()


def test_bf16_dynamic_scaling_routes_through_guard():
    # satellite: bf16 + use_dynamic_loss_scaling must warn (not
    # silently disable) and drive the on-device @LOSS_SCALE@ var —
    # growing after incr_every_n clean steps, shrinking on a NaN step
    from paddle_tpu.contrib.mixed_precision import decorator as mp
    from paddle_tpu.stability.guard import LOSS_SCALE_VAR
    os.environ["PT_STABILITY_POLICY"] = "skip"
    os.environ["PT_GUARD_REPLAY_MAX"] = "0"
    set_flags({"FLAGS_stability_guard": True})
    mp._GUARD_SCALING_WARNED[0] = False
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(layers.fc(x, 8, act="relu"), 1)
        loss = layers.mean(layers.square(pred - y))
        with warnings.catch_warnings(record=True) as ws:
            warnings.simplefilter("always")
            mopt = mp.decorate(fluid.optimizer.SGD(learning_rate=0.1),
                               init_loss_scaling=8.0,
                               use_dynamic_loss_scaling=True,
                               incr_every_n_steps=2, dtype="bfloat16")
        mopt.minimize(loss)
    assert any("stability" in str(w.message).lower() or
               "scale" in str(w.message).lower() for w in ws)
    assert mopt._use_guard_scaling
    scope = Scope()
    exe = fluid.Executor()
    scales = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        eng = Engine()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for feed in _feeds(4, nan_at=2):
                eng.run(main, scope, None, feed, [loss.name])
                scales.append(float(np.asarray(
                    scope.var(LOSS_SCALE_VAR).get_tensor()._array
                ).reshape(-1)[0]))
    assert scales[1] == 16.0          # grew after 2 clean steps
    assert scales[2] < scales[1]      # shrank on the NaN step
    assert eng.counters["anomalies"] == 1


def test_fault_plan_anomaly_kinds():
    from paddle_tpu.distributed.faults import FaultPlan
    plan = FaultPlan.from_spec("seed=3,nan=1.0")
    feed = {"x": np.ones((4, 4), np.float32),
            "step": np.array([1], np.int64)}
    out = plan.corrupt_feed(0, feed)
    assert out is not feed
    assert np.isnan(out["x"]).any()
    assert not np.isnan(feed["x"]).any()      # caller's feed untouched
    assert plan.counts["nan"] == 1
    spike = FaultPlan.from_spec("seed=3,grad_spike=1.0,spike_mag=100")
    flat = spike.on_grad_bucket(np.ones(8, np.float32))
    np.testing.assert_array_equal(flat, np.full(8, 100.0, np.float32))
    assert spike.counts["grad_spike"] == 1
    with pytest.raises(ValueError):
        FaultPlan.from_spec("seed=1,bogus_kind=0.5")


def test_policy_map_parsing():
    from paddle_tpu.stability.guard import policy_map
    assert policy_map("") == {"nonfinite": "skip", "spike": "clip",
                              "integrity": "rollback"}
    assert policy_map("rollback") == {"nonfinite": "rollback",
                                      "spike": "rollback",
                                      "integrity": "rollback"}
    assert policy_map("nonfinite=abort,spike=rescale") == {
        "nonfinite": "abort", "spike": "rescale",
        "integrity": "rollback"}
    with pytest.raises(ValueError):
        policy_map("nonfinite=explode")


def test_dygraph_guard_reduced_readonly_buffer():
    # regression: the dygraph allreduce hands _guard_reduced a numpy
    # VIEW of a jax.Array (writeable=False) — the skip recovery must
    # return a zeroed replacement bucket, not mutate in place
    import jax.numpy as jnp
    from paddle_tpu.dygraph.parallel import DataParallel
    set_flags({"FLAGS_stability_guard": True})
    os.environ["PT_STABILITY_POLICY"] = "skip"
    dp = DataParallel.__new__(DataParallel)
    bad = np.asarray(jnp.asarray([np.nan, 2.0], dtype=jnp.float32))
    assert not bad.flags.writeable
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        fixed = dp._guard_reduced(bad, [None], [(2,)])
    np.testing.assert_array_equal(
        fixed, np.zeros(2, np.float32))
    # a finite bucket passes through unchanged
    ok = np.asarray(jnp.asarray([1.0, 2.0], dtype=jnp.float32))
    assert dp._guard_reduced(ok, [None], [(2,)]) is ok
    # abort raises instead of zeroing
    os.environ["PT_STABILITY_POLICY"] = "abort"
    with pytest.raises(EnforceNotMet):
        dp._guard_reduced(bad, [None], [(2,)])
