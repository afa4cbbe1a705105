"""Feedback-directed autotuner (paddle_tpu/tuning/, FLAGS_autotune;
docs/TUNING.md).

Contracts pinned here:

* the search driver is deterministic — same space + objective + seed
  produces the identical trial sequence and winner, and the winner is
  adopted only on a STRICT measured improvement at the deciding budget;
* the on-disk cache round-trips a winner and reads corrupt / stale /
  cross-program entries as a MISS, never an exception;
* knob apply/restore puts flags AND env (including absence) back
  exactly, even when a trial raises mid-flight;
* with lossy knobs excluded (the default) an autotuned run's training
  trajectory is bit-identical to a default run — the search happens on
  a scope snapshot and the winner is value-preserving;
* a second engine run of the same program content applies the cached
  winner with ZERO trials (the persistence loop the ISSUE demands);
* every trace_affecting knob in the catalog moves BOTH engine cache
  keys (the audit that PR 8's review had to patch twice);
* Pallas GEMM variants pass parity against the composed XLA baseline
  for every epilogue family, and only parity-passing variants are
  admitted by the search.
"""
import json
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.engine import Engine
from paddle_tpu.core.flags import set_flags
from paddle_tpu.core.scope import Scope
from paddle_tpu.tuning import cache, driver, knobs, search, state

_ENV_KEYS = ("PT_TUNING_CACHE_DIR", "PT_TUNE_BUDGETS", "PT_TUNE_ROUNDS",
             "PT_TUNE_SEED", "PT_TUNE_KNOBS", "PT_TUNE_VARIANTS",
             "PT_TUNE_ALLOW_LOSSY", "PT_TUNE_OBJECTIVE")


@pytest.fixture(autouse=True)
def _reset():
    saved_env = {k: os.environ.get(k) for k in _ENV_KEYS}
    saved_knobs = knobs.snapshot()
    yield
    for k, v in saved_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    knobs.restore(saved_knobs)
    state.clear_applied()
    state.set_search_in_progress(False)
    set_flags({"FLAGS_autotune": False})


# ---------------------------------------------------------------------------
# search driver: deterministic convergence, strict adoption
# ---------------------------------------------------------------------------

_SPACE = [("a", (1, 2, 4)), ("b", (0.1, 0.5, 0.9))]
_START = {"a": 1, "b": 0.5}


def _synthetic(config, budget):
    # separable bowl with its minimum at a=4, b=0.1; budget-independent
    # so memoization and halving decisions are exact
    return abs(config["a"] - 4) + 10.0 * abs(config["b"] - 0.1)


def test_search_converges_deterministically():
    best, trials = search.coordinate_descent(
        _SPACE, _synthetic, _START, seed=3, budgets=(1, 3), rounds=2)
    assert best == {"a": 4, "b": 0.1}
    assert trials, "search must record its trials"
    # same seed: identical trial sequence, bit for bit
    best2, trials2 = search.coordinate_descent(
        _SPACE, _synthetic, _START, seed=3, budgets=(1, 3), rounds=2)
    assert best2 == best
    assert [t.as_dict() for t in trials] == [t.as_dict() for t in trials2]
    # a different seed shuffles coordinate order but still converges
    best3, _ = search.coordinate_descent(
        _SPACE, _synthetic, _START, seed=99, budgets=(1, 3), rounds=2)
    assert best3 == best


def test_search_every_survivor_reaches_deciding_budget():
    seen = []
    search.coordinate_descent(
        _SPACE, _synthetic, _START, seed=0, budgets=(1, 2, 4), rounds=1,
        on_trial=seen.append)
    # the adopted comparison only ever happens at budgets[-1]
    for name, cands in _SPACE:
        winners = [t for t in seen if t.knob == name and t.budget == 4]
        assert winners, f"no deciding-budget trial for {name}"


def test_search_flat_objective_keeps_start():
    # strict-improvement rule: a tie never moves the incumbent, so a
    # flat objective returns the start config unchanged
    best, _ = search.coordinate_descent(
        _SPACE, lambda c, b: 1.0, _START, seed=0, budgets=(1, 2))
    assert best == _START


# ---------------------------------------------------------------------------
# knob registry: apply / restore / lossy policy
# ---------------------------------------------------------------------------

def test_lossy_knobs_excluded_unless_opted_in():
    names = {n for n, _ in knobs.search_space()}
    lossy = {k.name for k in knobs.knobs() if k.lossy}
    assert lossy == {"quantized_allreduce", "kernel_quant_matmul"}
    assert not (names & lossy)
    os.environ["PT_TUNE_ALLOW_LOSSY"] = "1"
    try:
        assert lossy <= {n for n, _ in knobs.search_space()}
    finally:
        os.environ.pop("PT_TUNE_ALLOW_LOSSY", None)


def test_apply_restore_exact_env_and_flag_state():
    os.environ.pop("PT_PREFETCH_DEPTH", None)   # absent, not ""
    os.environ["PT_KERNEL_MIN_NUMEL"] = "65536"
    try:
        before = knobs.snapshot()
        with knobs.applied({"prefetch_depth": 4,
                            "kernel_min_numel": 16384,
                            "allreduce_bucket_mb": 128.0}):
            assert os.environ["PT_PREFETCH_DEPTH"] == "4"
            assert os.environ["PT_KERNEL_MIN_NUMEL"] == "16384"
            assert knobs.value("allreduce_bucket_mb") == 128.0
        assert knobs.snapshot() == before
        # absence restored as absence, not as an empty string
        assert "PT_PREFETCH_DEPTH" not in os.environ
        assert os.environ["PT_KERNEL_MIN_NUMEL"] == "65536"
    finally:
        os.environ.pop("PT_KERNEL_MIN_NUMEL", None)


def test_apply_is_all_or_nothing():
    before = knobs.snapshot()
    with pytest.raises(KeyError):
        knobs.apply({"prefetch_depth": 4, "no_such_knob": 1})
    assert knobs.snapshot() == before
    # failure mid-way (bad value after a good one) rolls back too
    with pytest.raises((TypeError, ValueError)):
        knobs.apply({"prefetch_depth": 4,
                     "kernel_min_numel": "not-an-int"})
    assert knobs.snapshot() == before


def test_search_restores_state_after_mid_trial_exception(
        monkeypatch, tmp_path):
    eng, prog, scope, feed, fetch = _mlp(seed=11)
    before = knobs.snapshot()

    def boom(*a, **kw):
        # the knob config IS applied at this point (knobs.applied wraps
        # the measurement) — the crash must not leak it
        assert os.environ.get("PT_PREFETCH_DEPTH") is not None
        raise RuntimeError("trial crashed")

    monkeypatch.setattr(driver, "_step_ms", boom)
    os.environ["PT_TUNE_KNOBS"] = "prefetch_depth"
    os.environ["PT_TUNE_BUDGETS"] = "1"
    with fluid.scope_guard(scope), pytest.raises(RuntimeError):
        driver.search_config(eng, prog, scope, None, feed, fetch)
    assert knobs.snapshot() == before
    assert not state.search_in_progress()


# ---------------------------------------------------------------------------
# cache: round-trip and fallback-to-miss
# ---------------------------------------------------------------------------

def test_cache_roundtrip(tmp_path):
    os.environ["PT_TUNING_CACHE_DIR"] = str(tmp_path)
    key = cache.cache_key("deadbeef")
    assert cache.lookup(key) is None
    cfg = {"prefetch_depth": 4, "kernel_min_numel": 16384}
    path = cache.store(key, cfg, objective_ms=1.25, trials=7,
                       extras={"default_ms": 1.5, "delta_ms": -0.25})
    assert os.path.exists(path)
    entry = cache.lookup(key)
    assert entry is not None
    assert entry["config"] == cfg
    assert entry["objective_ms"] == 1.25
    assert entry["trials"] == 7
    assert entry["delta_ms"] == -0.25
    assert cache.entry_errors(entry, path) == []
    # a different program fingerprint is a different entry
    assert cache.lookup(cache.cache_key("cafebabe")) is None


def test_cache_corrupt_and_stale_read_as_miss(tmp_path):
    os.environ["PT_TUNING_CACHE_DIR"] = str(tmp_path)
    key = cache.cache_key("deadbeef")
    path = cache.store(key, {"prefetch_depth": 2})
    assert cache.lookup(key) is not None
    # corrupt JSON -> miss, and the lint scan flags it
    with open(path, "w") as f:
        f.write("{not json")
    assert cache.lookup(key) is None
    scan = cache.scan(str(tmp_path))
    assert len(scan) == 1 and scan[0]["errors"]
    # stale schema version -> miss
    cache.store(key, {"prefetch_depth": 2})
    with open(path) as f:
        entry = json.load(f)
    entry["schema"] = 999
    with open(path, "w") as f:
        json.dump(entry, f)
    assert cache.lookup(key) is None
    # edited config (digest mismatch) -> miss
    cache.store(key, {"prefetch_depth": 2})
    with open(path) as f:
        entry = json.load(f)
    entry["key"]["fingerprint"] = "someone-else"
    with open(path, "w") as f:
        json.dump(entry, f)
    assert cache.lookup(key) is None


def test_cache_key_depends_on_knob_baseline(tmp_path):
    os.environ["PT_TUNING_CACHE_DIR"] = str(tmp_path)
    k0 = cache.cache_key("deadbeef")
    os.environ["PT_KERNEL_MIN_NUMEL"] = "16384"
    try:
        k1 = cache.cache_key("deadbeef")
    finally:
        os.environ.pop("PT_KERNEL_MIN_NUMEL", None)
    assert cache.key_digest(k0) != cache.key_digest(k1)


def test_lint_check_tuning_cache_exit_codes(tmp_path):
    from tools.lint_program import main as lint_main
    d = tmp_path / "tcache"
    d.mkdir()
    assert lint_main(["--check-tuning-cache", str(d)]) == 0
    (d / "bad.json").write_text("{not json")
    assert lint_main(["--check-tuning-cache", str(d)]) == 1


# ---------------------------------------------------------------------------
# engine cache-key audit: every trace-affecting knob moves BOTH keys
# ---------------------------------------------------------------------------

class _ProgStub:
    fingerprint = (7, 1)
    _gradient_accumulation_steps = 1


def _both_keys():
    eng = Engine.__new__(Engine)   # keys don't touch instance state
    prog = _ProgStub()
    return (Engine._cache_key(prog, 0, ("sig",), ["loss"], 1),
            eng._fast_key(prog, 0, ["loss"], 1))


def _altered(knob):
    cur = knob.get()
    for c in knob.candidates:
        if c != cur:
            return c
    if knob.type is bool:
        return not cur
    if knob.type in (int, float):
        return cur + knob.type(1)
    return (cur or "") + "x"


@pytest.mark.parametrize(
    "name", [k.name for k in knobs.knobs() if k.trace_affecting])
def test_trace_affecting_knob_moves_both_engine_keys(name):
    knob = knobs.get(name)
    snap = knobs.snapshot([name])
    base_cache, base_fast = _both_keys()
    try:
        knob.set(_altered(knob))
        new_cache, new_fast = _both_keys()
    finally:
        knobs.restore(snap)
    assert new_cache != base_cache, f"{name} missing from _cache_key"
    assert new_fast != base_fast, f"{name} missing from _fast_key"


def test_applied_token_moves_both_engine_keys():
    base_cache, base_fast = _both_keys()
    state.set_applied("tok123", {"prefetch_depth": 4}, "test")
    try:
        new_cache, new_fast = _both_keys()
    finally:
        state.clear_applied()
    assert new_cache != base_cache
    assert new_fast != base_fast


def test_non_trace_knobs_leave_keys_alone():
    # host-side knobs (prefetch depth, ghost cadence) must NOT retrace
    base = _both_keys()
    with knobs.applied({"prefetch_depth": 4, "ghost_every": 5}):
        state.clear_applied()     # applied() does not set the token
        assert _both_keys() == base


# ---------------------------------------------------------------------------
# end to end: FLAGS_autotune on a real engine
# ---------------------------------------------------------------------------

def _mlp(seed=9):
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, 8, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(pred - y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 4).astype("float32"),
            "y": rng.rand(8, 1).astype("float32")}
    return Engine(), main, scope, feed, [loss.name]


def _cheap_search_env(tmp_path):
    os.environ["PT_TUNING_CACHE_DIR"] = str(tmp_path)
    os.environ["PT_TUNE_KNOBS"] = "prefetch_depth,ghost_every"
    os.environ["PT_TUNE_BUDGETS"] = "1,2"
    os.environ["PT_TUNE_ROUNDS"] = "1"


def _train(steps=4, autotune=False):
    set_flags({"FLAGS_autotune": autotune})
    eng, main, scope, feed, fetch = _mlp()
    losses = []
    with fluid.scope_guard(scope):
        with warnings.catch_warnings():
            warnings.simplefilter("error")   # autotune must not warn-skip
            for _ in range(steps):
                out = eng.run(main, scope, None, feed, fetch)
                losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        params = {n: np.array(scope.var(n).get_tensor()._array)
                  for n in sorted(main.global_block().vars)
                  if main.global_block().vars[n].persistable
                  and not n.startswith("@")}
    set_flags({"FLAGS_autotune": False})
    return losses, params, eng


def test_autotuned_trajectory_matches_default(tmp_path):
    _cheap_search_env(tmp_path)
    l0, p0, _ = _train(autotune=False)
    state.clear_applied()
    l1, p1, eng = _train(autotune=True)
    assert eng.counters["tuning_searches"] == 1
    assert eng.counters["tuning_trials"] > 0
    # lossless knobs only: searching on a scope snapshot + applying the
    # winner must leave the training trajectory bit-identical
    assert l0 == l1
    assert sorted(p0) == sorted(p1)
    for n in p0:
        np.testing.assert_array_equal(p0[n], p1[n])


def test_second_engine_run_hits_cache_with_zero_trials(tmp_path):
    _cheap_search_env(tmp_path)
    _, _, eng1 = _train(autotune=True)
    assert eng1.counters["tuning_searches"] == 1
    entries = [p for p in os.listdir(tmp_path) if p.endswith(".json")]
    assert len(entries) == 1, "exactly one persisted winner"
    applied_cfg = dict(state.applied_config() or {})
    assert applied_cfg, "search must apply its winner"
    state.clear_applied()
    # second run: same program CONTENT, fresh engine + fresh process
    # state — must replay the winner from disk without a single trial
    _, _, eng2 = _train(autotune=True)
    assert eng2.counters["tuning_cache_hits"] == 1
    assert eng2.counters["tuning_searches"] == 0
    assert eng2.counters["tuning_trials"] == 0
    assert state.applied_source() == "cache"
    assert dict(state.applied_config()) == applied_cfg
    assert [p for p in os.listdir(tmp_path)
            if p.endswith(".json")] == entries


def test_attribution_objective_no_worse_and_replays(tmp_path):
    """PT_TUNE_OBJECTIVE=attribution (docs/TUNING.md): per-knob credit
    penalties re-rank trials but the wall-adoption gate keeps the
    adopted config no worse than the wall objective would have kept —
    with lossless knobs the trajectory stays bit-identical, the entry
    records which objective produced it, and the second run replays
    from the cache with zero trials."""
    import json

    _cheap_search_env(tmp_path)
    l0, p0, _ = _train(autotune=False)
    state.clear_applied()
    os.environ["PT_TUNE_OBJECTIVE"] = "attribution"
    l1, p1, eng = _train(autotune=True)
    assert eng.counters["tuning_searches"] == 1
    assert eng.counters["tuning_trials"] > 0
    assert l0 == l1
    for n in p0:
        np.testing.assert_array_equal(p0[n], p1[n])
    entries = [p for p in os.listdir(tmp_path) if p.endswith(".json")]
    assert len(entries) == 1
    with open(os.path.join(str(tmp_path), entries[0])) as f:
        rec = json.load(f)
    assert rec["objective"] == "attribution"
    state.clear_applied()
    _, _, eng2 = _train(autotune=True)
    assert eng2.counters["tuning_cache_hits"] == 1
    assert eng2.counters["tuning_searches"] == 0
    assert eng2.counters["tuning_trials"] == 0


def test_autotune_reports_tuning_metrics(tmp_path):
    _cheap_search_env(tmp_path)
    from paddle_tpu.observability import metrics
    base = metrics.counter("pt_tuning_searches_total").get()
    _train(autotune=True)
    assert metrics.counter("pt_tuning_searches_total").get() == base + 1
    assert metrics.counter("pt_tuning_trials_total").get() > 0


# ---------------------------------------------------------------------------
# kernel variant search: parity-gated admission
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("epilogue,blocks", [
    ("none", (64, 128, 128)),
    ("layer_norm", (128, 256, 128)),
    ("dropout_residual", (128, 128, 128)),
])
def test_variant_parity_per_epilogue(epilogue, blocks):
    from paddle_tpu.tuning import variants
    v = variants.Variant(*blocks, epilogue)
    res = variants.verify_variant(v)
    assert res["passed"], res
    assert res["value"] <= variants._REL_TOL


def test_variant_enumeration_respects_constraints():
    from paddle_tpu.tuning import variants
    vs = variants.enumerate_variants(256, 256, 256)
    assert vs, "non-empty legal space"
    for v in vs:
        assert 256 % v.bm == 0 and 256 % v.bn == 0 and 256 % v.bk == 0
        if v.epilogue == "layer_norm":
            assert v.bn == 256   # row stats need the full feature axis


def test_register_winner_routes_only_plain_gemm():
    from paddle_tpu.kernels import registry as kreg
    from paddle_tpu.tuning import variants
    assert variants.register_winner({}) is None
    winners = {"none": {"bm": 64, "bn": 128, "bk": 128, "ms": 0.5},
               "layer_norm": {"bm": 128, "bn": 256, "bk": 128,
                              "ms": 0.7}}
    try:
        assert variants.register_winner(winners) == "tuned_matmul"
        kern = kreg.get("tuned_matmul")
        assert kern is not None
        sig = kreg.Signature(op_type="matmul",
                             shapes=((256, 256), (256, 256)),
                             dtypes=("float32", "float32"))
        big_enough = sig.numel >= kreg.min_numel()
        assert kern.eligible(sig) == big_enough
        bad = kreg.Signature(op_type="matmul",
                             shapes=((250, 256), (256, 256)),
                             dtypes=("float32", "float32"))
        assert not kern.eligible(bad)   # 250 % 64 != 0
    finally:
        kreg._KERNELS.pop("tuned_matmul", None)
        for lst in kreg._BY_OP.values():
            lst[:] = [k for k in lst if k.name != "tuned_matmul"]
