"""Preemption-resume: the elastic-training story (SURVEY section 5).

The reference has no elastic training (2019): its story is external
process management + checkpoint/restore (paddle.distributed.launch
respawns; pservers snapshot via checkpoint_notify). This framework's
explicit contract is the same — preemption is survived by periodic
`save_persistables` (params + optimizer state + RNG live in the scope as
persistables), and resume = fresh process + `load_persistables` +
continue. These tests pin that contract:

* resuming mid-run reproduces the uninterrupted trajectory EXACTLY
  (optimizer accumulators included — adam moments/beta pows);
* the resumed process is a genuinely fresh scope/engine (new compile);
* a stale/partial checkpoint directory fails loudly, not silently.
"""
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.scope import Scope


def _build():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [6], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, 16, act="tanh",
                      param_attr=fluid.ParamAttr(name="rw0"))
        pred = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="rw1"))
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.AdamOptimizer(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _batch(step):
    rng = np.random.RandomState(1000 + step)
    xs = rng.rand(8, 6).astype(np.float32)
    return {"x": xs, "y": xs.sum(1, keepdims=True).astype(np.float32)}


@pytest.fixture
def async_ckpt_flag(request):
    """Parametrize a test over the legacy and the async-subsystem
    save paths; always restores the flag."""
    fluid.set_flags({"FLAGS_async_checkpoint": request.param})
    yield request.param
    fluid.set_flags({"FLAGS_async_checkpoint": False})


@pytest.mark.parametrize("async_ckpt_flag", [False, True],
                         indirect=True,
                         ids=["legacy", "async_subsystem"])
def test_preemption_resume_exact_trajectory(tmp_path, async_ckpt_flag):
    ckpt = str(tmp_path / "ckpt")

    # uninterrupted run: 8 steps (snapshot the INIT first)
    main, startup, loss = _build()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        init = {}
        for p in main.all_parameters():
            src = scope.find_var(p.name).get_value()
            init[p.name] = np.asarray(
                src.array if hasattr(src, "array") else src).copy()
        ref = [float(np.asarray(exe.run(
            main, feed=_batch(i), fetch_list=[loss.name])[0]))
            for i in range(8)]

    # preempted run: 4 steps, checkpoint, "kill" (drop scope+engine)
    main2, startup2, loss2 = _build()
    scope_a = Scope()
    with fluid.scope_guard(scope_a):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup2)
        for name, arr in init.items():     # same init as the ref run
            scope_a.var(name).set_value(arr)
        first = [float(np.asarray(exe.run(
            main2, feed=_batch(i), fetch_list=[loss2.name])[0]))
            for i in range(4)]
        fluid.io.save_persistables(exe, ckpt, main2)
    del scope_a  # the preemption: process state is gone

    # fresh process analog: new programs, scope, engine; load + resume
    main3, startup3, loss3 = _build()
    scope_b = Scope()
    with fluid.scope_guard(scope_b):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup3)
        fluid.io.load_persistables(exe, ckpt, main3)
        resumed = [float(np.asarray(exe.run(
            main3, feed=_batch(i), fetch_list=[loss3.name])[0]))
            for i in range(4, 8)]

    # the interrupted + resumed trajectory == the uninterrupted one;
    # exactness proves adam moments and beta-pow accumulators traveled
    np.testing.assert_allclose(first, ref[:4], rtol=1e-6)
    np.testing.assert_allclose(resumed, ref[4:], rtol=1e-5, atol=1e-6)


def test_resume_restores_optimizer_accumulators(tmp_path):
    ckpt = str(tmp_path / "ckpt2")
    main, startup, loss = _build()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for i in range(3):
            exe.run(main, feed=_batch(i), fetch_list=[loss.name])
        fluid.io.save_persistables(exe, ckpt, main)
        moment_names = [n for n in os.listdir(ckpt)
                        if "moment" in n or "beta" in n]
    assert moment_names, "optimizer accumulators must be persisted"

    main2, _, _ = _build()
    scope2 = Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor(fluid.CPUPlace())
        fluid.io.load_persistables(exe, ckpt, main2)
        for n in moment_names:
            v = scope2.find_var(n)
            assert v is not None and v.is_initialized()
            if "moment" in n:
                assert float(np.abs(np.asarray(
                    v.get_value())).max()) > 0


def test_crash_between_shard_write_and_latest_falls_back(
        tmp_path, monkeypatch):
    """Atomicity of the async-subsystem commit: a crash after the shard
    write but before the LATEST pointer swap must leave restore on the
    previous complete checkpoint — never a partial one. Both crash
    windows are injected: before the commit rename (stale .tmp) and
    after it (committed step LATEST doesn't name)."""
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.checkpoint import writer as ckpt_writer

    root = str(tmp_path / "ackpt")
    main, startup, loss = _build()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=_batch(0), fetch_list=[loss.name])
        with CheckpointManager(root) as m:
            m.save(1, scope=scope, program=main, sync=True)
        w1 = np.asarray(scope.find_var("rw1").get_value()).copy()

        # crash window A: process dies before the commit rename —
        # only step_00000002.tmp exists
        exe.run(main, feed=_batch(1), fetch_list=[loss.name])
        m = CheckpointManager(root)
        real_commit = ckpt_writer.commit_step
        monkeypatch.setattr(
            ckpt_writer, "commit_step",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("injected crash before commit rename")))
        with pytest.raises(RuntimeError, match="injected crash"):
            m.save(2, scope=scope, program=main, sync=True)
        monkeypatch.setattr(ckpt_writer, "commit_step", real_commit)
        assert os.path.isdir(os.path.join(root, "step_00000002.tmp"))
        assert not os.path.isdir(os.path.join(root, "step_00000002"))

        # crash window B: rename happened, LATEST swap did not
        monkeypatch.setattr(
            ckpt_writer, "_write_latest",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("injected crash before LATEST update")))
        with pytest.raises(RuntimeError, match="injected crash"):
            m2 = CheckpointManager(root)
            m2.save(3, scope=scope, program=main, sync=True)
        assert os.path.isdir(os.path.join(root, "step_00000003"))
        with open(os.path.join(root, "LATEST")) as f:
            assert f.read().strip() == "step_00000001"

    # fresh-process restore follows LATEST -> the last checkpoint whose
    # commit protocol COMPLETED, with the pre-crash parameter values
    main2, _, _ = _build()
    scope2 = Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor(fluid.CPUPlace())
        with CheckpointManager(root) as m3:
            restored = m3.restore(scope=scope2, program=main2,
                                  place=exe.place)
    assert restored == 1
    np.testing.assert_array_equal(
        np.asarray(scope2.find_var("rw1").get_value()), w1)


# ---------------------------------------------------------------------------
# exactly-once elastic resume: TrainState + reader cursors
# (checkpoint/train_state.py, docs/RESILIENCE.md)
# ---------------------------------------------------------------------------

def _sample_source():
    def r():
        rng = np.random.RandomState(77)
        for _ in range(64):
            x = rng.rand(6).astype(np.float32)
            yield x, np.float32(x.sum())
    return r


def _pipeline():
    """batch(shuffle(src)) — both layers carry a resumable cursor."""
    from paddle_tpu import reader as rd
    return rd.batch(rd.shuffle(_sample_source(), 16, seed=5),
                    batch_size=8)


def _feed_of(samples):
    return {"x": np.stack([s[0] for s in samples]),
            "y": np.asarray([[s[1]] for s in samples], np.float32)}


def _train_steps(exe, main, loss, rdr, total, start=0, kill_at=None,
                 manager=None, scope=None):
    """Drive ``total - start`` steps off the reader pipeline; the
    reader's own cursor decides WHICH batches those are (after a
    ``load_state_dict`` the first ``rdr()`` call fast-forwards).
    Returns (losses, last completed step)."""
    losses = []
    step = start
    while step < total:
        for samples in rdr():
            if step >= total:
                break
            out = exe.run(main, feed=_feed_of(samples),
                          fetch_list=[loss.name])
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
            step += 1
            if manager is not None:
                manager.save(step, scope=scope, program=main,
                             sync=True, train_state=True)
            if kill_at is not None and step == kill_at:
                return losses, step
    return losses, step


@pytest.mark.parametrize("kill_at,variant", [
    (2, "plain"), (5, "grad_accum"), (7, "async_dispatch"),
], ids=["kill2_plain", "kill5_grad_accum", "kill7_async"])
def test_kill_at_step_resume_is_bit_identical(tmp_path, kill_at,
                                              variant):
    """Exactly-once resume: kill the run at an arbitrary step, restart
    from the TrainState checkpoint (global step + reader cursors), and
    the stitched trajectory must be BIT-identical to an uninterrupted
    run — no batch repeated, none skipped — on the plain,
    gradient-accumulation and async-dispatch engine paths alike."""
    from paddle_tpu.checkpoint import (CheckpointManager,
                                       register_reader,
                                       unregister_reader)
    flags = {"async_dispatch": {"FLAGS_async_dispatch": True}}.get(
        variant, {})

    def build():
        main, startup, loss = _build()
        if variant == "grad_accum":
            main._gradient_accumulation_steps = 2
        return main, startup, loss

    total = 12
    ckpt = str(tmp_path / "ckpt")
    fluid.set_flags(flags)
    try:
        # uninterrupted reference run (snapshot the init for the rest)
        main, startup, loss = build()
        scope = Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            init = {p.name: np.asarray(
                scope.find_var(p.name).get_value()).copy()
                for p in main.all_parameters()}
            ref, _ = _train_steps(exe, main, loss, _pipeline(), total)
            ref_params = {n: np.asarray(
                scope.find_var(n).get_value()).copy() for n in init}

        # killed run: same init, TrainState-checkpoint every step,
        # stop cold at kill_at (scope + engine + reader all dropped)
        main2, startup2, loss2 = build()
        scope_a = Scope()
        rdr_a = _pipeline()
        register_reader("train", rdr_a)
        try:
            with fluid.scope_guard(scope_a):
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup2)
                for name, arr in init.items():
                    scope_a.var(name).set_value(arr.copy())
                with CheckpointManager(ckpt) as m:
                    first, stopped = _train_steps(
                        exe, main2, loss2, rdr_a, total,
                        kill_at=kill_at, manager=m, scope=scope_a)
            assert stopped == kill_at
        finally:
            unregister_reader("train")
        del scope_a, rdr_a  # the preemption

        # relaunched incarnation: fresh everything; maybe_restore
        # delivers params + global step + the reader cursor
        main3, startup3, loss3 = build()
        scope_b = Scope()
        rdr_b = _pipeline()
        register_reader("train", rdr_b)
        try:
            with fluid.scope_guard(scope_b):
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup3)
                with CheckpointManager(ckpt) as m2:
                    restored = m2.maybe_restore(scope=scope_b,
                                                program=main3)
                    assert restored == kill_at
                    ts = m2.restored_train_state
                    assert ts is not None
                    assert ts.global_step == kill_at
                resumed, _ = _train_steps(exe, main3, loss3, rdr_b,
                                          total, start=kill_at)
                end_params = {n: np.asarray(
                    scope_b.find_var(n).get_value()).copy()
                    for n in init}
        finally:
            unregister_reader("train")

        # bit-identical stitch: losses AND final parameters
        assert first == ref[:kill_at]
        assert resumed == ref[kill_at:]
        for n in init:
            np.testing.assert_array_equal(end_params[n], ref_params[n])
    finally:
        fluid.set_flags({k: False for k in flags})


def test_prefetcher_cursor_rewinds_inflight_batches():
    """Drain-or-replay: DeviceFeedPrefetcher.state_dict() rewinds the
    wrapped reader's cursor by the staged-but-unconsumed batches, so a
    restore replays exactly the batches no step ever saw — the
    prefetch queue can never silently swallow data across a restart."""
    from paddle_tpu import reader as rd
    from paddle_tpu.reader.decorators import _CursorForwardingReader

    def src():
        def r():
            for i in range(32):
                yield (np.full((2,), i, np.float32),)
        return r

    def feed_pipeline():
        b = rd.batch(src(), batch_size=2)
        return _CursorForwardingReader(
            lambda: ({"x": np.stack([s[0] for s in samples])}
                     for samples in b()), b)

    clean = [d["x"].copy() for d in feed_pipeline()()]

    pf = rd.DeviceFeedPrefetcher(feed_pipeline(), depth=3)
    it = iter(pf)
    consumed = [np.asarray(next(it)["x"]) for _ in range(5)]
    for got, want in zip(consumed, clean):
        np.testing.assert_array_equal(got, want)
    import time
    time.sleep(0.3)  # let the fill thread block on the full queue
    cur = pf.state_dict()
    # the cursor points at the NEXT unconsumed batch, not at the fill
    # thread's read-ahead position
    assert cur["offset"] == 5

    fresh = feed_pipeline()
    fresh.load_state_dict(cur)
    pf2 = rd.DeviceFeedPrefetcher(fresh, depth=3)
    rest = [np.asarray(d["x"]) for d in pf2]
    assert len(rest) == len(clean) - 5
    for got, want in zip(rest, clean[5:]):
        np.testing.assert_array_equal(got, want)


def test_train_state_survives_in_manifest_and_lints_clean(tmp_path):
    """The train_state section rides the atomic manifest commit and is
    what ckpt_inspect --train-state audits."""
    import subprocess
    import sys as _sys
    from paddle_tpu.checkpoint import (CheckpointManager,
                                       read_train_state,
                                       register_reader,
                                       unregister_reader)
    ckpt = str(tmp_path / "ckpt")
    main, startup, loss = _build()
    rdr = _pipeline()
    next(iter(rdr()))  # advance the cursor past batch 0
    register_reader("train", rdr)
    try:
        scope = Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run(main, feed=_batch(0), fetch_list=[loss.name])
            with CheckpointManager(ckpt) as m:
                m.save(1, scope=scope, program=main, sync=True,
                       train_state=True)
    finally:
        unregister_reader("train")
    ts = read_train_state(ckpt)
    assert ts is not None and ts.global_step == 1
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [_sys.executable, os.path.join(repo, "tools", "ckpt_inspect.py"),
         ckpt, "--train-state", "--verify"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "train_state: v1 global_step=1" in proc.stdout


# ---------------------------------------------------------------------------
# elastic topology: saved-vs-current mismatch, cross-factorization
# round-trips, cursor redistribution, supervisor shrink / crash loop
# (distributed/elastic.py, docs/RESILIENCE.md "Elastic topology")
# ---------------------------------------------------------------------------

def _train_and_save(ckpt, mesh_spec, n_devices, steps=3):
    """Train a few Adam steps and save with the given claimed topology;
    returns {name: array} of every persistable at save time."""
    from paddle_tpu.checkpoint import CheckpointManager
    main, startup, loss = _build()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for i in range(steps):
            exe.run(main, feed=_batch(i), fetch_list=[loss.name])
        with CheckpointManager(ckpt, mesh_spec=mesh_spec,
                               n_devices=n_devices) as m:
            m.save(steps, scope=scope, program=main, sync=True,
                   train_state=True)
        return main, {
            n: np.asarray(scope.find_var(n).get_value()).copy()
            for n in (v.name for v in main.list_vars()
                      if getattr(v, "persistable", False))
            if scope.find_var(n) is not None
            and scope.find_var(n).is_initialized()}


def test_topology_mismatch_fails_loudly_without_elastic(tmp_path):
    """Satellite guard: a checkpoint written by a different topology
    must NOT silently assemble under a non-elastic restore — the error
    names both topologies; elastic=True (or PT_ELASTIC_RESUME=1) opts
    into re-place + reshard."""
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.core.enforce import EnforceNotMet
    from paddle_tpu.parallel.mesh import MeshSpec

    ckpt = str(tmp_path / "ckpt")
    main, saved = _train_and_save(
        ckpt, MeshSpec(data=2, fsdp=2), n_devices=4)

    main2, _, _ = _build()
    scope2 = Scope()
    with fluid.scope_guard(scope2):
        with CheckpointManager(ckpt) as m:  # claims 1 device
            with pytest.raises(EnforceNotMet) as ei:
                m.restore(scope=scope2, program=main2)
    import jax
    live = jax.device_count()
    msg = str(ei.value)
    # the error must NAME both topologies, not just reject
    assert "data=2,fsdp=2" in msg
    assert "n_devices=4" in msg and f"n_devices={live}" in msg
    assert "PT_ELASTIC_RESUME" in msg

    # same manager, elastic opt-in: restore succeeds and assembles the
    # exact saved values onto the 1-device fleet
    scope3 = Scope()
    with fluid.scope_guard(scope3):
        main3, startup3, _ = _build()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup3)
        with CheckpointManager(ckpt) as m2:
            step = m2.restore(scope=scope3, program=main3,
                              elastic=True)
            info = m2.elastic_resume_info
        assert step == 3
        assert info is not None
        assert info["saved"]["n_devices"] == 4
        assert info["current"]["n_devices"] == live
        for n, want in saved.items():
            got = np.asarray(scope3.find_var(n).get_value())
            np.testing.assert_array_equal(got, want)


def test_meshless_tensoronly_restore_crosses_world_size(tmp_path):
    """The fail-loud check guards world-size-coupled state (cursors,
    mesh layouts). A checkpoint with NO mesh and NO train_state is the
    plain format-property case — two writer processes, any-world
    restore by shard-index assembly — and must keep restoring
    non-elastically with only a warning."""
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.checkpoint.snapshot import Snapshot, SnapshotEntry

    root = str(tmp_path / "ck")
    full = np.arange(24, dtype=np.float32).reshape(6, 4)
    m1 = CheckpointManager(root, process_index=1, process_count=2)
    m1.save(1, snapshot=Snapshot([SnapshotEntry(
        "w", (6, 4), "float32", [], [([[3, 6], [0, 4]], full[3:])])]),
        sync=True)
    m0 = CheckpointManager(root, process_index=0, process_count=2,
                           commit_timeout=10)
    m0.save(1, snapshot=Snapshot([SnapshotEntry(
        "w", (6, 4), "float32", [], [([[0, 3], [0, 4]], full[:3])])]),
        sync=True)
    m0.close(), m1.close()

    sc = Scope()
    with CheckpointManager(root) as m:  # world_size 1, no mesh
        with pytest.warns(UserWarning, match="shard-index assembly"):
            assert m.restore(scope=sc, vars=["w"],
                             include_rng=False) == 1
        assert m.elastic_resume_info is None
    np.testing.assert_array_equal(
        np.asarray(sc.find_var("w").get_value()), full)


def test_topology_mismatch_env_optin(tmp_path, monkeypatch):
    """PT_ELASTIC_RESUME=1 — the env the shrinking supervisor sets —
    is equivalent to restore(elastic=True)."""
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.parallel.mesh import MeshSpec

    ckpt = str(tmp_path / "ckpt")
    _, saved = _train_and_save(ckpt, MeshSpec(data=2), n_devices=2)
    monkeypatch.setenv("PT_ELASTIC_RESUME", "1")
    main2, startup2, _ = _build()
    scope2 = Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup2)
        with CheckpointManager(ckpt) as m:
            assert m.maybe_restore(scope=scope2, program=main2) == 3
            assert m.elastic_resume_info is not None
    np.testing.assert_array_equal(
        np.asarray(scope2.find_var("rw1").get_value()), saved["rw1"])


@pytest.mark.parametrize("target_spec,target_devices", [
    ("data=4", 4), ("fsdp=4", 4), ("data=1", 1),
], ids=["onto_data4", "onto_fsdp4", "onto_single_device"])
def test_cross_factorization_roundtrip(tmp_path, target_spec,
                                       target_devices):
    """Checkpoints written under data2_fsdp2_tp2 restore bit-equal onto
    ANY factorization of any world size — resharding is a property of
    the format (writer shard-index metadata), not of the saving mesh.
    Covers dense params AND Adam moments / beta-pow accumulators."""
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.parallel.mesh import MeshSpec

    ckpt = str(tmp_path / "ckpt")
    _, saved = _train_and_save(
        ckpt, MeshSpec(data=2, fsdp=2, tp=2), n_devices=8)
    assert any("moment" in n for n in saved), \
        "Adam moments must be in the checkpoint for this to prove " \
        "optimizer-state resharding"

    main2, startup2, _ = _build()
    scope2 = Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup2)
        with CheckpointManager(
                ckpt, mesh_spec=MeshSpec.from_string(target_spec),
                n_devices=target_devices) as m:
            assert m.restore(scope=scope2, program=main2,
                             elastic=True) == 3
            info = m.elastic_resume_info
    assert info is not None
    assert MeshSpec.from_dict(info["saved"]["mesh"]) == \
        MeshSpec(data=2, fsdp=2, tp=2)
    for n, want in saved.items():
        got = np.asarray(scope2.find_var(n).get_value())
        np.testing.assert_array_equal(got, want)


def test_pp_cut_checkpoint_restores_elastically(tmp_path):
    """A checkpoint claiming a pp=2 cut restores onto a single device
    through the same elastic path."""
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.parallel.mesh import MeshSpec

    ckpt = str(tmp_path / "ckpt")
    _, saved = _train_and_save(
        ckpt, MeshSpec(data=2, pp=2), n_devices=4)
    main2, startup2, _ = _build()
    scope2 = Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup2)
        with CheckpointManager(ckpt) as m:
            assert m.restore(scope=scope2, program=main2,
                             elastic=True) == 3
            assert MeshSpec.from_dict(
                m.elastic_resume_info["saved"]["mesh"]) == \
                MeshSpec(data=2, pp=2)
    for n, want in saved.items():
        np.testing.assert_array_equal(
            np.asarray(scope2.find_var(n).get_value()), want)


def test_cursor_redistribution_is_exactly_once():
    """TrainState.redistribute: a deterministic pure function of
    (saved workers, new count) — survivors keep their own cursors
    byte-for-byte, orphans park namespaced on rank ``o % new_count``,
    nothing dropped, nothing overridden."""
    from paddle_tpu.checkpoint import TrainState

    ts = TrainState(global_step=7, workers={
        str(p): {"readers": {"train": {"offset": 10 + p}},
                 "host_rng": ["MT19937", [p], 0, 0, 0.0]}
        for p in range(4)})
    small = ts.redistribute(2)
    assert sorted(small.workers) == ["0", "1"]
    assert small.workers["0"]["readers"] == {
        "train": {"offset": 10}, "train@2": {"offset": 12}}
    assert small.workers["1"]["readers"] == {
        "train": {"offset": 11}, "train@3": {"offset": 13}}
    # survivors keep their host RNG; orphans' RNG is dropped (a parked
    # cursor can be drained later, an RNG stream cannot be split)
    assert small.workers["0"]["host_rng"] == ["MT19937", [0], 0, 0, 0.0]
    total = sum(len(w["readers"]) for w in small.workers.values())
    assert total == 4  # exactly-once: every saved cursor survives
    # a second shrink keeps all four too; an already-parked orphan
    # cursor chains its namespace ("train@3@1" = worker 3's cursor,
    # parked on worker 1, now parked on worker 0) so provenance is
    # kept and keys can never collide
    one = small.redistribute(1)
    assert sorted(one.workers["0"]["readers"]) == [
        "train", "train@1", "train@2", "train@3@1"]

    with pytest.warns(UserWarning, match="grow"):
        grown = ts.redistribute(6)
    assert sorted(grown.workers) == ["0", "1", "2", "3"]  # no invented
    assert grown.global_step == 7


def test_multiprocess_manifest_redistributes_on_shrink(tmp_path):
    """Integration: a 2-process checkpoint (rank 1 contributes only
    its train_state entry) restored by a 1-process elastic fleet
    delivers rank 0's cursor live and parks rank 1's namespaced."""
    from paddle_tpu.checkpoint import (CheckpointManager,
                                       register_reader,
                                       unregister_reader)
    from paddle_tpu.parallel.mesh import MeshSpec

    ckpt = str(tmp_path / "ckpt")
    main, startup, loss = _build()
    rdr = _pipeline()
    register_reader("train", rdr)
    try:
        scope = Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run(main, feed=_batch(0), fetch_list=[loss.name])
            # rank 1 writes first (its shard is manifest-only), then
            # rank 0 — whose save also runs the commit barrier
            with CheckpointManager(ckpt, process_index=1,
                                   process_count=2,
                                   mesh_spec=MeshSpec(data=2),
                                   n_devices=2) as m1:
                m1.save(1, scope=scope, vars=[], include_rng=False,
                        sync=True, train_state=True)
            with CheckpointManager(ckpt, process_index=0,
                                   process_count=2,
                                   mesh_spec=MeshSpec(data=2),
                                   n_devices=2) as m0:
                m0.save(1, scope=scope, program=main, sync=True,
                        train_state=True)
    finally:
        unregister_reader("train")

    main2, startup2, _ = _build()
    rdr2 = _pipeline()
    register_reader("train", rdr2)
    try:
        scope2 = Scope()
        with fluid.scope_guard(scope2):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup2)
            with CheckpointManager(ckpt) as m:
                assert m.restore(scope=scope2, program=main2,
                                 elastic=True) == 1
                ts = m.restored_train_state
        assert sorted(ts.workers) == ["0"]
        assert sorted(ts.workers["0"]["readers"]) == [
            "train", "train@1"]
    finally:
        unregister_reader("train")


def test_supervise_crash_loop_aborts_early(tmp_path, monkeypatch,
                                           capfd):
    """Satellite guard: N immediate consecutive failures at the same
    checkpoint step abort with a postmortem pointer instead of burning
    the whole --max-restarts budget."""
    from paddle_tpu.distributed import launch as pt_launch

    script = tmp_path / "always_dies.py"
    script.write_text("import sys\nsys.exit(1)\n")
    monkeypatch.setenv("PT_CRASH_LOOP_N", "2")
    code, used = pt_launch.supervise(
        [str(script)], max_restarts=8, nproc=1, backend="cpu",
        backoff_base_s=0.0)
    assert code == 1
    assert used < 8, "crash loop must not burn the restart budget"
    err = capfd.readouterr().err
    assert "crash loop" in err
    assert "workerlog" in err  # the postmortem pointer


def test_supervise_elastic_shrink_on_device_loss(tmp_path, capfd):
    """A worker exiting DEVICE_LOSS_EXIT_CODE (its device is
    PERMANENTLY gone) makes the supervisor relaunch with the surviving
    rank count and PT_ELASTIC_RESUME=1 — even without --elastic."""
    from paddle_tpu.distributed import launch as pt_launch
    from paddle_tpu.distributed.faults import DEVICE_LOSS_EXIT_CODE

    out = tmp_path / "out"
    out.mkdir()
    script = tmp_path / "lossy.py"
    script.write_text(
        "import os, sys\n"
        "rank = int(os.environ.get('PADDLE_TRAINER_ID', '0'))\n"
        "attempt = int(os.environ.get('PADDLE_RESTART_ATTEMPT', '0'))\n"
        "if attempt == 0 and rank == 1:\n"
        f"    sys.exit({DEVICE_LOSS_EXIT_CODE})\n"
        "if attempt >= 1:\n"
        "    with open(os.path.join(sys.argv[1],\n"
        "              f'env_{rank}.txt'), 'w') as f:\n"
        "        f.write(os.environ.get('PT_ELASTIC_RESUME', '-') +\n"
        "                ' ' + os.environ['PADDLE_TRAINERS_NUM'])\n"
        "sys.exit(0)\n")
    attempt_log = []
    code, used = pt_launch.supervise(
        [str(script), str(out)], max_restarts=3, nproc=2,
        backend="cpu", backoff_base_s=0.0, min_nproc=1,
        attempt_log=attempt_log)
    assert code == 0 and used == 1
    assert [a["nproc"] for a in attempt_log] == [2, 1]
    assert attempt_log[0]["shrunk"] is True
    assert attempt_log[0]["first_fail"] == DEVICE_LOSS_EXIT_CODE
    # the surviving incarnation saw the elastic env at world size 1
    assert (out / "env_0.txt").read_text() == "1 1"
    assert "elastic shrink" in capfd.readouterr().err


def test_elastic_restore_rearms_integrity_sentinel(tmp_path):
    """An elastic restore must drop the sentinel's bucket layout so the
    re-bucketed fingerprint plan is rebuilt — never a false
    integrity_mismatch on the first post-resume verdict."""
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.parallel.mesh import MeshSpec

    ckpt = str(tmp_path / "ckpt")
    _train_and_save(ckpt, MeshSpec(data=2), n_devices=2)

    fluid.set_flags({"FLAGS_integrity_sentinel": True})
    try:
        main2, startup2, loss2 = _build()
        scope2 = Scope()
        with fluid.scope_guard(scope2):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup2)
            # arm the sentinel's shadow on the PRE-restore params
            exe.run(main2, feed=_batch(0), fetch_list=[loss2.name])
            with CheckpointManager(ckpt) as m:
                m.restore(scope=scope2, program=main2, elastic=True)
            # post-restore steps must not raise / count a mismatch
            for i in range(4):
                exe.run(main2, feed=_batch(i), fetch_list=[loss2.name])
            assert exe._engine.counters.get(
                "integrity_mismatches", 0) == 0
    finally:
        fluid.set_flags({"FLAGS_integrity_sentinel": False})


def test_partial_checkpoint_fails_loudly(tmp_path):
    ckpt = str(tmp_path / "ckpt3")
    main, startup, loss = _build()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=_batch(0), fetch_list=[loss.name])
        fluid.io.save_persistables(exe, ckpt, main)
    # corrupt: delete one persistable file
    victim = [n for n in os.listdir(ckpt) if n == "rw1"][0]
    os.remove(os.path.join(ckpt, victim))
    main2, _, _ = _build()
    with fluid.scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        with pytest.raises(Exception):
            fluid.io.load_persistables(exe, ckpt, main2)
