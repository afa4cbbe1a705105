"""Pipeline parallelism as the fourth mesh axis (docs/PARALLELISM.md):
``pp`` on MeshSpec, automatic stage cutting (parallel/auto_cut), the
interleaved 1F1B slot table (parallel/pipeline.pipeline_schedule), the
cross-stage race verifier (analysis/races), and the joint
(data, fsdp, tp, pp) placement search with its HBM gate + cache replay.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import models
from paddle_tpu.parallel.pipeline import pipeline_schedule
from paddle_tpu.core.scope import Scope
from paddle_tpu.analysis.races import (verify_pipeline_schedule,
                                       verify_stage_partition)
from paddle_tpu.parallel.mesh import MeshSpec
from paddle_tpu.parallel.mpmd_pipeline import MPMDPipelineEngine


# ---------------------------------------------------------------------------
# MeshSpec: pp is a first-class axis
# ---------------------------------------------------------------------------

def test_meshspec_pp_axis_vocabulary():
    spec = MeshSpec.from_string("data=2,pp=4")
    assert spec.pp == 4 and spec.data == 2 and spec.size == 8
    # pp is OUTERMOST: handoffs are point-to-point, lowest bandwidth
    assert list(spec.axis_shapes()) == ["pp", "data"]
    assert MeshSpec.AXES[0] == "pp"


def test_meshspec_pp_round_trip_and_identity():
    spec = MeshSpec(data=2, tp=2, pp=2)
    again = MeshSpec.from_dict(spec.to_dict())
    assert again == spec and hash(again) == hash(spec)
    assert again.to_dict()["pp"] == 2
    assert MeshSpec(data=2, tp=2) != spec


def test_meshspec_pp_validation():
    with pytest.raises(ValueError):
        MeshSpec(pp=0)
    with pytest.raises(ValueError, match="at most one"):
        MeshSpec(pp=-1, data=-1)
    with pytest.raises(ValueError):
        MeshSpec.from_string("pp=2,stage=4")  # unknown axis name


def test_meshspec_pp_build_rejects_stranded_devices():
    import jax
    n = len(jax.devices())
    if n < 8:
        pytest.skip("needs 8 devices")
    with pytest.raises(ValueError, match="stranded"):
        MeshSpec(pp=3).build(jax.devices()[:8])


# ---------------------------------------------------------------------------
# 1F1B slot table: bubble never worse than GPipe, verifier-clean
# ---------------------------------------------------------------------------

SHAPES = [(2, 4, 2), (4, 8, 4), (4, 4, 2), (8, 8, 4), (3, 6, 3)]


@pytest.mark.parametrize("S,M,D", SHAPES,
                         ids=[f"S{s}M{m}D{d}" for s, m, d in SHAPES])
def test_1f1b_bubble_not_worse_than_gpipe(S, M, D):
    g = pipeline_schedule(S, M, D, kind="gpipe")
    f = pipeline_schedule(S, M, D, kind="1f1b")
    assert f["bubble_frac"] <= g["bubble_frac"] + 1e-9
    # and never worse than the ANALYTIC GPipe fill/drain bubble
    assert f["bubble_frac"] <= (D - 1) / (M + D - 1) + 1e-9
    # interleaving caps the activation stash near the pipeline depth,
    # GPipe stashes every in-flight micro-batch
    assert f["stash_peak"] <= g["stash_peak"]


@pytest.mark.parametrize("kind", ["gpipe", "1f1b"])
@pytest.mark.parametrize("S,M,D", SHAPES,
                         ids=[f"S{s}M{m}D{d}" for s, m, d in SHAPES])
def test_generated_schedules_pass_race_verifier(S, M, D, kind):
    sched = pipeline_schedule(S, M, D, kind=kind)
    assert verify_pipeline_schedule(sched["events"], S, M) == []


def test_race_verifier_catches_injected_hazards():
    sched = pipeline_schedule(4, 8, 4, kind="1f1b")
    events = list(sched["events"])

    # duplicate a micro-batch's forward: grads double-counted
    diags = verify_pipeline_schedule(events + [events[0]], 4, 8)
    assert any("duplicate" in d.message for d in diags)
    assert all(d.pass_name == "pipeline-race" for d in diags)

    # drop a backward: work silently lost
    dropped = [e for e in events if not (e[2] == "B" and e[3] == 2
                                         and e[4] == 3)]
    diags = verify_pipeline_schedule(dropped, 4, 8)
    assert any("missing" in d.message for d in diags)

    # swap ticks of F(0,0) and F(1,0): stage 1 consumes the handoff
    # activation before stage 0 produced it
    def _tick_of(kind, s, m):
        return next(e[0] for e in events
                    if e[2:] == (kind, s, m))
    t0, t1 = _tick_of("F", 0, 0), _tick_of("F", 1, 0)
    swapped = [((t1 if e[2:] == ("F", 0, 0) else
                 t0 if e[2:] == ("F", 1, 0) else e[0]),) + e[1:]
               for e in events]
    diags = verify_pipeline_schedule(swapped, 4, 8)
    assert diags and all(d.pass_name == "pipeline-race" for d in diags)


def test_stage_partition_verifier_catches_miscut():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8], dtype="float32")
        h0 = fluid.layers.fc(x, 16, act="relu")
        h1 = fluid.layers.fc(h0, 16, act="relu")
        y = fluid.layers.fc(h1, 4)
        loss = fluid.layers.mean(y)
    # a real dataflow frontier is clean
    assert not verify_stage_partition(main, [h1.name])
    # cutting at a value that ops BEFORE the cut still feed from makes
    # a later stage the only producer of an earlier stage's input:
    # consumed-before-produced, the canonical cross-stage hazard
    diags = verify_stage_partition(main, [h0.name, h1.name, h0.name])
    assert diags and all(d.pass_name == "pipeline-race" for d in diags)
    assert loss is not None


# ---------------------------------------------------------------------------
# automatic cutting: pp=2 transformer training parity vs single device
# ---------------------------------------------------------------------------

def _build_transformer_fwd():
    fluid.framework.unique_name.reset()
    cfg = models.transformer.TransformerConfig(
        src_vocab_size=64, trg_vocab_size=64, d_model=32, d_inner=64,
        n_head=4, n_layer=2, dropout=0.0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cost, logits, feeds = models.transformer_train(cfg)
    return cfg, main, startup, cost


def test_auto_cut_transformer_matches_single_device():
    cfg, main, startup, cost = _build_transformer_fwd()
    popt = fluid.optimizer.PipelineOptimizer(
        fluid.optimizer.SGD(learning_rate=0.1), num_microbatches=4)
    with fluid.program_guard(main, startup):
        popt.minimize(cost, startup_program=startup)
    batch = models.transformer.make_batch(
        cfg, 8, 8, 8, rng=np.random.default_rng(0))

    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        # NO cut_vars: the engine synthesizes the stage boundary from
        # the auto_cut cost model
        eng = MPMDPipelineEngine(
            main, cost.name, None,
            optimizer_program=popt.opt_program,
            num_microbatches=4, n_stages=2)
        assert eng.cut_plan is not None
        assert len(eng.cut_plan.cut_vars) == 1
        losses = [eng.run(scope, batch) for _ in range(3)]
        st = eng.last_stats
        w_pipe = np.asarray(
            scope.find_var("src_word_emb.w_0").get_value())
    assert st["n_stages"] == 2
    assert st["schedule"] == "1f1b"
    # measured bubble never worse than the analytic GPipe fill/drain
    assert st["bubble_frac"] <= st["bubble_frac_gpipe"] + 1e-9

    # single-device reference: same model, plain SGD, one big batch
    cfg2, main2, startup2, cost2 = _build_transformer_fwd()
    with fluid.program_guard(main2, startup2):
        fluid.optimizer.SGDOptimizer(
            learning_rate=0.1).minimize(cost2)
    scope2 = Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup2)
        ref = []
        for _ in range(3):
            out, = exe.run(main2, feed=batch,
                           fetch_list=[cost2.name])
            ref.append(float(out))
        w_ref = np.asarray(
            scope2.find_var("src_word_emb.w_0").get_value())

    np.testing.assert_allclose(losses, ref, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(w_pipe, w_ref, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# placement: HBM limit FSDP can't satisfy forces pp>1, cache-replayed
# ---------------------------------------------------------------------------

def _build_fat_embedding_transformer():
    """Embedding-dominated model: FSDP's 2x-max-param all-gather floor
    and tp's unsharded transients both keep every pp==1 candidate
    above an HBM line the pp>1 candidates (largest-stage share of
    resident AND transient bytes) fit under."""
    fluid.framework.unique_name.reset()
    cfg = models.transformer.TransformerConfig(
        src_vocab_size=32768, trg_vocab_size=32768, d_model=32,
        d_inner=64, n_head=4, n_layer=2, dropout=0.0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cost, logits, feeds = models.transformer_train(cfg)
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(cost)
    return main


def _hbm_split(main, n_devices=8, dynamic_dim=1):
    """Best candidate HBM on each side of the pp line, computed with
    the search's own estimator."""
    from paddle_tpu.analysis import placement
    from paddle_tpu.parallel.auto_cut import propose_cuts
    stats = placement.program_stats(main, dynamic_dim=dynamic_dim)
    mp, gb = stats["memplan"], 2 * stats["max_param_bytes"]
    best = {True: None, False: None}
    for spec, red in placement.enumerate_candidates(n_devices, 64, {}):
        sf = None
        if spec.pp > 1:
            try:
                cp = propose_cuts(main, "", spec.pp,
                                  dynamic_dim=dynamic_dim,
                                  uniform=False)
            except Exception:
                continue
            tot = sum(cp.stage_param_bytes)
            sf = (max(cp.stage_param_bytes) / tot if tot
                  else 1.0 / spec.pp)
        h = placement.candidate_hbm_bytes(
            mp, spec, stage_frac=sf,
            gather_bytes=gb if spec.fsdp > 1 else 0)
        side = spec.pp > 1
        if best[side] is None or h < best[side]:
            best[side] = h
    return best[False], best[True]  # (best pp==1, best pp>1)


def test_hbm_limit_fsdp_cannot_satisfy_selects_pp(monkeypatch,
                                                  tmp_path):
    from paddle_tpu.analysis.placement import plan_for_program
    main = _build_fat_embedding_transformer()
    best_flat, best_pp = _hbm_split(main)
    # the model is built so NO pp==1 mesh (fsdp=8 included) fits where
    # a pp>1 mesh does — otherwise the limit below would prove nothing
    assert best_pp < best_flat
    limit = (best_flat + best_pp) // 2
    monkeypatch.setenv("PT_STATIC_HBM_LIMIT", str(limit))
    monkeypatch.setenv("PT_TUNING_CACHE_DIR", str(tmp_path))

    first = plan_for_program(main, n_devices=8)
    assert first.spec.pp > 1
    assert first.hbm_bytes <= limit
    assert not first.cached and first.trials > 0

    # second run replays the plan from the cache: ZERO search trials,
    # and the replayed plan (decoded from the cache entry) is the
    # byte-for-byte encoding of the searched one
    second = plan_for_program(main, n_devices=8)
    assert second.cached and second.trials == 0
    assert second.spec == first.spec
    assert second.to_dict() == first.to_dict()
