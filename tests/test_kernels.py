"""Custom-kernel subsystem (paddle_tpu/kernels, FLAGS_use_custom_kernels;
docs/KERNELS.md).

Covers the registry contract end to end on the CPU backend (kernels
execute under the Pallas interpreter via the ``_INTERPRET`` hook):
selection/fallback/deny gating, the numerics-parity harness for every
registered kernel, fused-optimizer trajectory parity against the host
optimizer through the real engine (plain, stability-guard-gated),
bucket_sweep ZeRO-1 shard composition and in-kernel guard gating,
quantized-matmul opt-in wiring, bit-identical fallback when nothing is
eligible, cache-key awareness of the kernel flag and PT_KERNEL_* env,
and the need_dbias ds-suppression regression for flash attention.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.engine import Engine
from paddle_tpu.core.flags import FLAGS, set_flags
from paddle_tpu.core.scope import Scope
from paddle_tpu.kernels import fused_optimizer as fo
from paddle_tpu.kernels import parity
from paddle_tpu.kernels import registry as kreg

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_use_custom_kernels": True,
               "FLAGS_stability_guard": False})


@pytest.fixture
def interp(monkeypatch):
    """Arm the interpret-mode hook + drop the numel floor so the
    registry selects kernels on the CPU backend."""
    monkeypatch.setattr(kreg, "_INTERPRET", True)
    monkeypatch.setenv("PT_KERNEL_MIN_NUMEL", "1")
    yield


def _sig_f32(op, *shapes):
    arrs = [jnp.zeros(s, jnp.float32) for s in shapes]
    return kreg.signature(op, *arrs)


# ---------------------------------------------------------------------------
# registry selection / fallback
# ---------------------------------------------------------------------------

def test_select_picks_fused_adam(interp):
    sel = kreg.select("adam", _sig_f32("adam", (256,), (256,), (256,),
                                       (256,)))
    assert sel is not None and sel.name == "fused_adam"


def test_select_respects_flag(interp):
    set_flags({"FLAGS_use_custom_kernels": False})
    assert kreg.select("adam", _sig_f32("adam", (256,))) is None
    set_flags({"FLAGS_use_custom_kernels": True})
    assert kreg.select("adam", _sig_f32("adam", (256,))) is not None


def test_select_respects_deny(interp, monkeypatch):
    monkeypatch.setenv("PT_KERNEL_DENY", "fused_adam, fused_sgd")
    assert kreg.select("adam", _sig_f32("adam", (256,))) is None
    assert kreg.select("sgd", _sig_f32("sgd", (256,))) is None
    assert not kreg.allowed("fused_adam")
    assert kreg.allowed("quantized_matmul")


def test_select_rejects_wrong_dtype_and_size(interp, monkeypatch):
    sig = kreg.signature("adam", jnp.zeros((256,), jnp.int32))
    assert kreg.select("adam", sig) is None
    monkeypatch.setenv("PT_KERNEL_MIN_NUMEL", "100000")
    assert kreg.select("adam", _sig_f32("adam", (256,))) is None


def test_select_off_on_cpu_without_hook():
    # no interp fixture: the CPU backend must keep the lowered path
    assert not kreg._INTERPRET
    assert kreg.select("adam", _sig_f32("adam", (1 << 20,))) is None


def test_routable_pre_gate(interp):
    # lowerings consult routable() before paying for a Signature: it
    # must agree with select()'s structural gates
    assert kreg.routable("adam") and kreg.routable("mul")
    assert not kreg.routable("layer_norm")
    set_flags({"FLAGS_use_custom_kernels": False})
    assert not kreg.routable("adam")
    set_flags({"FLAGS_use_custom_kernels": True})


def test_routable_off_on_cpu_without_hook():
    assert not kreg._INTERPRET
    assert not kreg.routable("adam")


def test_dispatch_stats_and_metric(interp):
    from paddle_tpu.observability import metrics
    kreg.reset_stats()
    before = metrics.counter("pt_kernel_dispatch_total").get(
        kernel="fused_adam", outcome="custom")
    assert kreg.select("adam", _sig_f32("adam", (256,))) is not None
    st = kreg.dispatch_stats()
    assert st["per_kernel"]["fused_adam"]["custom"] == 1
    assert st["custom"] == 1 and st["hit_rate"] > 0
    after = metrics.counter("pt_kernel_dispatch_total").get(
        kernel="fused_adam", outcome="custom")
    assert after == before + 1


def test_unknown_op_selects_nothing(interp):
    assert kreg.select("layer_norm", _sig_f32("layer_norm",
                                              (256,))) is None


def test_mesh_step_counts_every_decision_lowered(interp):
    """A step XLA partitions over a mesh cannot hold a Mosaic kernel
    (JAX refuses to lower it): the engine traces it inside
    registry.auto_partitioned(), the product lowerings still reach
    select(), and every decision is counted ``lowered`` — the same
    Adam step without a mesh routes to the kernel."""
    from paddle_tpu.parallel import DistributedStrategy

    def adam_counts(strategy):
        fluid.framework.unique_name.reset()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            loss = _mlp_adam()
        scope = Scope()
        kreg.reset_stats()
        with fluid.scope_guard(scope):
            fluid.Executor().run(startup)
            Engine(strategy=strategy).run(main, scope, None, _feed(),
                                          [loss.name])
        return kreg.dispatch_stats()["per_kernel"]["fused_adam"]

    meshed = adam_counts(DistributedStrategy(axes={"dp": 2}))
    assert set(meshed) == {"lowered"} and meshed["lowered"] > 0, meshed
    assert adam_counts(None).get("custom", 0) > 0


def test_select_propagates_eligible_error(interp):
    """A kernel whose eligibility check raises is a bug to see, not a
    quiet "not eligible" that leaves a slower path running."""
    def boom(sig):
        raise ValueError("eligibility bug")

    kreg.register_kernel("boom", op_types=("boom_op",), eligible=boom,
                         run=lambda *a: None)
    try:
        with pytest.raises(ValueError, match="eligibility bug"):
            kreg.select("boom_op", _sig_f32("boom_op", (256,)))
        with pytest.raises(ValueError, match="eligibility bug"):
            kreg.abstract_select("boom_op", _sig_f32("boom_op", (256,)))
    finally:
        kreg._KERNELS.pop("boom")
        kreg._BY_OP.pop("boom_op")


# ---------------------------------------------------------------------------
# numerics parity (the tier-1 gate for every registered kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", parity.cases(),
                         ids=lambda c: c.label)
def test_parity(case):
    res = parity.run_case(case)
    assert res["passed"], (
        f"{res['label']}: {res['metric']}={res['value']:.4g} "
        f"exceeds tol {res['tol']}")


def _eqns(jaxpr):
    """Every equation around the kernels: nested calls opened, the
    kernels' own bodies not."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub)


@pytest.mark.parametrize("shape", [(64, 256), (4, 16, 256)],
                         ids=["rank2", "rank3"])
def test_fused_adam_blocks_the_native_layout(shape):
    """A rank >= 2 operand reaches the kernel as it lies: nothing is
    padded or flattened to rows of 128 lanes (each a relayout of a tiled
    array on the chip), p, m and v are aliased onto their outputs (a
    donated buffer is updated in place), and a caller that keeps its
    input finds it intact."""
    r = np.random.default_rng(3)
    p, g, m = (jnp.asarray(r.standard_normal(shape, dtype=np.float32))
               for _ in range(3))
    v = jnp.abs(m)

    def step(p, g, m, v):
        return fo.fused_adam(p, g, m, v, 1e-3)

    eqns = list(_eqns(jax.make_jaxpr(step)(p, g, m, v).jaxpr))
    assert not [e for e in eqns if e.primitive.name in ("pad", "slice")]
    views = [tuple(e.params["new_sizes"]) for e in eqns
             if e.primitive.name == "reshape"
             and e.invars[0].aval.size == p.size]
    assert all(v[-2:] == shape[-2:] for v in views), views
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    # operands: hyper, p, g, m, v -> outputs p', m', v'
    assert tuple(calls[0].params["input_output_aliases"]) == (
        (1, 0), (3, 1), (4, 2))
    kept = [np.array(x) for x in (p, g, m, v)]
    po, mo, vo = jax.jit(step)(p, g, m, v)
    for before, after in zip(kept, (p, g, m, v)):
        np.testing.assert_array_equal(before, np.asarray(after))
    assert not np.array_equal(kept[0], np.asarray(po))


def test_fused_optimizer_view_counts(interp):
    """Each routed site says which view it took, beside the decision
    and outside `decisions` / `hit_rate`: a [K, N] parameter the
    native one, a rank-1 parameter the flat one."""
    from paddle_tpu.core.registry import OPS, ExecContext, _SlotView
    kreg.reset_stats()
    for shape in ((16, 256), (256,)):
        x = jnp.ones(shape, jnp.float32)
        one = jnp.ones((1,), jnp.float32)
        env = {"p": x, "g": x, "m": x, "v": x, "lr": one,
               "b1p": 0.9 * one, "b2p": 0.999 * one}
        op = _SlotView(
            "adam",
            {"Param": ["p"], "Grad": ["g"], "Moment1": ["m"],
             "Moment2": ["v"], "LearningRate": ["lr"],
             "Beta1Pow": ["b1p"], "Beta2Pow": ["b2p"]},
            {"ParamOut": ["po"], "Moment1Out": ["mo"],
             "Moment2Out": ["vo"], "Beta1PowOut": [],
             "Beta2PowOut": []},
            {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8})
        OPS.get("adam").lowering(ExecContext(op, env))
        op = _SlotView("sgd", {"Param": ["p"], "Grad": ["g"],
                               "LearningRate": ["lr"]},
                       {"ParamOut": ["po"]}, {})
        OPS.get("sgd").lowering(ExecContext(op, env))
    st = kreg.dispatch_stats()
    want = {"custom": 2, "native_view": 1, "flat_view": 1}
    assert st["per_kernel"]["fused_adam"] == want
    assert st["per_kernel"]["fused_sgd"] == want
    assert st["decisions"] == st["custom"] == 4 and st["hit_rate"] == 1.0


def test_parity_covers_every_kernel():
    assert parity.missing_parity() == []


def test_lint_check_kernels_exit_code():
    from tools.lint_program import main as lint_main
    assert lint_main(["--check-kernels"]) == 0


# ---------------------------------------------------------------------------
# engine trajectory parity: fused optimizer vs host optimizer
# ---------------------------------------------------------------------------

_LR = 1e-2


def _mlp_adam():
    x = layers.data(name="x", shape=[64], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="int64")
    h = layers.fc(x, size=48, act="relu")
    pred = layers.fc(h, size=10, act="softmax")
    loss = layers.mean(layers.cross_entropy(input=pred, label=y))
    fluid.optimizer.AdamOptimizer(learning_rate=_LR).minimize(loss)
    return loss


def _feed(batch=16, seed=0):
    r = np.random.default_rng(seed)
    return {"x": r.standard_normal((batch, 64)).astype(np.float32),
            "y": r.integers(0, 10, (batch, 1)).astype(np.int64)}


def _train(steps=4, seed=7):
    """Fresh program/scope/engine; returns (losses, params)."""
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        loss = _mlp_adam()
    scope = Scope()
    feed = _feed()
    losses = []
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
        eng = Engine()
        for _ in range(steps):
            out = eng.run(main, scope, None, feed, [loss.name])
            losses.append(float(np.asarray(out[0])))
        params = {n: np.array(scope.var(n).get_tensor()._array)
                  for n in sorted(main.global_block().vars)
                  if main.global_block().vars[n].persistable
                  and scope.find_var(n) is not None
                  and scope.find_var(n).is_initialized()
                  and hasattr(scope.var(n).get_tensor(), "_array")}
    return losses, params


def _assert_params_close(a, b, ulp_tol):
    """Every float persistable of *b* within *ulp_tol* of *a*'s, in
    units of the last place of the larger of the value and one Adam
    step (|update| is about the learning rate whatever the gradient):
    p' = p - update is good to THAT place, so a weight that ends near
    zero sits a hundred ulp of its own value from another correct f32
    program's (FMA contraction, jax 0.9.0's CPU: 128) and within one or
    two of the sum's; over four steps the roundings reach the next
    gradients too, and where |g| is near Adam's epsilon the quotient
    m'/sqrt(v') answers them in kind (13.5 read)."""
    assert a.keys() == b.keys()
    for n in a:
        if a[n].dtype.kind != "f":
            np.testing.assert_array_equal(a[n], b[n], err_msg=n)
            continue
        u = parity.max_ulp(a[n], b[n],
                           scale=np.maximum(np.abs(a[n]), _LR))
        assert u <= ulp_tol, f"{n}: {u} ulp > {ulp_tol}"


def test_engine_trajectory_parity(interp):
    set_flags({"FLAGS_use_custom_kernels": False})
    l_host, p_host = _train()
    set_flags({"FLAGS_use_custom_kernels": True})
    l_kern, p_kern = _train()
    # losses come off the forward (identical either way); params go
    # through 4 fused adam steps — same math, same op order, a few
    # ulp of XLA-fusion slack on each step's sum
    np.testing.assert_allclose(l_host, l_kern, rtol=1e-6)
    _assert_params_close(p_host, p_kern, ulp_tol=32.0)


def test_engine_trajectory_parity_with_guard(interp):
    set_flags({"FLAGS_stability_guard": True,
               "FLAGS_use_custom_kernels": False})
    l_host, p_host = _train()
    set_flags({"FLAGS_use_custom_kernels": True})
    l_kern, p_kern = _train()
    np.testing.assert_allclose(l_host, l_kern, rtol=1e-6)
    _assert_params_close(p_host, p_kern, ulp_tol=32.0)


def test_kernels_on_no_eligible_bit_identical():
    """With kernels on but nothing eligible (CPU backend, no interpret
    hook) the trace must be the lowered trace, bit for bit."""
    set_flags({"FLAGS_use_custom_kernels": False})
    l_off, p_off = _train()
    set_flags({"FLAGS_use_custom_kernels": True})
    l_on, p_on = _train()
    assert l_off == l_on
    for n in p_off:
        np.testing.assert_array_equal(p_off[n], p_on[n], err_msg=n)


# ---------------------------------------------------------------------------
# bucket sweep: ZeRO-1 shards + stability-guard gate
# ---------------------------------------------------------------------------

_B1P, _B2P = 0.9 ** 2, 0.999 ** 2


def _host_adam_flat(p, g, m, v, lr):
    @jax.jit
    def f(p, g, m, v):
        # pows are f32 tensors in the engine (Beta1Pow/Beta2Pow scope
        # vars), so 1 - pow cancels in f32 — replicate that here or the
        # folded lr_t differs by ~1e-5 relative
        lr_t = (lr * jnp.sqrt(1.0 - jnp.float32(_B2P))
                / (1.0 - jnp.float32(_B1P)))
        m2 = 0.9 * m + (1 - 0.9) * g
        v2 = 0.999 * v + (1 - 0.999) * g * g
        return p - lr_t * m2 / (jnp.sqrt(v2) + 1e-8), m2, v2
    return f(p, g, m, v)


def _f64_adam_flat(p, g, m, v, lr):
    """[(ref, scale)] for p', m', v': the float64 yardstick host and
    kernel are both held to (parity.adam_f64), with lr_t folded in f32
    as both fold it."""
    one = np.float32(1.0)
    lr_t = (np.float32(lr) * np.sqrt(one - np.float32(_B2P))
            / (one - np.float32(_B1P)))
    return parity.adam_f64(p, g, m, v, lr_t)


def _assert_adam_close(refs, outs, what):
    """Within parity's Adam bound of the float64 values, in units of
    each sum's largest addend: where m' or p' cancels, host and kernel
    are tens of ulp of the RESULT apart under different FMA contraction
    (48 on jax 0.9.0's CPU) and both one to four of these from
    float64."""
    for (ref, scale), out, name in zip(refs, outs, "pmv"):
        u = parity.max_ulp(ref, out, scale)
        assert u <= parity.ADAM_TOL, f"{what} {name}': {u} addend-ulp"


# jit the sweeps like the engine does (a whole-block jit): an eager
# interpret-mode run skips XLA's FMA contraction and diverges from the
# jitted host baseline by O(1000) ulp on near-zero params — see the
# rationale in kernels/parity.py
_sweep_adam = jax.jit(lambda p, g, m, v: fo.bucket_sweep(
    "adam", p, g, m, v, lr=1e-3, beta1_pow=_B1P, beta2_pow=_B2P))
_sweep_adam_shard = jax.jit(lambda p, g, m, v, idx: fo.bucket_sweep(
    "adam", p, g, m, v, lr=1e-3, beta1_pow=_B1P, beta2_pow=_B2P,
    shard=(idx, 2)))
_sweep_adam_guard = jax.jit(lambda p, g, m, v, nf, sp, damp:
                            fo.bucket_sweep(
                                "adam", p, g, m, v, lr=1e-3,
                                beta1_pow=_B1P, beta2_pow=_B2P,
                                guard=(nf, sp, damp)))
_sweep_sgd = jax.jit(lambda p, g: fo.bucket_sweep("sgd", p, g, lr=0.1))


def _flats(n, seed=5):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.standard_normal(n, dtype=np.float32)),
            jnp.asarray(r.standard_normal(n, dtype=np.float32)),
            jnp.asarray(0.1 * r.standard_normal(n, dtype=np.float32)),
            jnp.asarray(np.abs(
                0.01 * r.standard_normal(n, dtype=np.float32))))


def test_bucket_sweep_matches_host():
    n = 256 * 128          # one block, no padding
    p, g, m, v = _flats(n)
    refs = _f64_adam_flat(p, g, m, v, 1e-3)
    _assert_adam_close(refs, _host_adam_flat(p, g, m, v, 1e-3), "host")
    _assert_adam_close(refs, _sweep_adam(p, g, m, v), "kernel")


def test_bucket_sweep_zero1_shards():
    """Each replica's sharded sweep updates only its slice; the
    concatenation of per-shard slices is the full host update — the
    ZeRO-1 composition (sharded_update_spec shards dim 0 evenly)."""
    n = 2 * 256 * 128      # two blocks -> two 128-lane-aligned shards
    p, g, m, v = _flats(n)
    refs = _f64_adam_flat(p, g, m, v, 1e-3)
    half = n // 2
    got = np.empty(n, np.float32)
    for idx in (0, 1):
        pk, _, _ = _sweep_adam_shard(p, g, m, v, jnp.int32(idx))
        pk = np.asarray(pk)
        lo, hi = idx * half, (idx + 1) * half
        # inside the shard: updated; outside: old values untouched
        other = np.r_[0:lo, hi:n]
        np.testing.assert_array_equal(pk[other], np.asarray(p)[other])
        got[lo:hi] = pk[lo:hi]
    _assert_adam_close(refs[:1], [got], "shards")


def test_bucket_sweep_guard_gate():
    """In-kernel gate == stability/guard.py _gate_value: nonfinite
    reverts to old, spike damps old + (new-old)*damp, clean selects
    new bit-exactly."""
    n = 256 * 128
    p, g, m, v = _flats(n)

    def sweep(guard):
        return _sweep_adam_guard(p, g, m, v, *guard)

    # clean step: the gate selects the new values, as the ungated sweep
    # computes them
    clean = sweep((jnp.float32(0), jnp.float32(0), jnp.float32(0)))
    _assert_adam_close(_f64_adam_flat(p, g, m, v, 1e-3), clean, "clean")
    p_new = clean[0]
    # nonfinite verdict: full revert of param AND moments
    pk, mk, vk = sweep((jnp.float32(1), jnp.float32(0),
                        jnp.float32(0)))
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(p))
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(m))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(v))
    # spike with damping 0.5: old + (new - old)*0.5
    pk, _, _ = sweep((jnp.float32(0), jnp.float32(1),
                      jnp.float32(0.5)))
    want = np.asarray(p) + (np.asarray(p_new) - np.asarray(p)) * 0.5
    np.testing.assert_allclose(np.asarray(pk), want, rtol=1e-6,
                               atol=1e-7)
    # spike with damping 0 == revert policies
    pk, _, _ = sweep((jnp.float32(0), jnp.float32(1), jnp.float32(0)))
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(p))


def test_bucket_sweep_sgd_and_padding():
    n = 1000                    # forces a padded tail
    r = np.random.default_rng(9)
    p = jnp.asarray(r.standard_normal(n, dtype=np.float32))
    g = jnp.asarray(r.standard_normal(n, dtype=np.float32))
    pk = _sweep_sgd(p, g)
    np.testing.assert_allclose(np.asarray(pk),
                               np.asarray(p) - 0.1 * np.asarray(g),
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# quantized matmul wiring
# ---------------------------------------------------------------------------

def test_quant_matmul_requires_opt_in(interp):
    sig = _sig_f32("mul", (128, 256), (256, 128))
    assert kreg.select("mul", sig) is None   # env not set


def test_quant_matmul_selected_and_wired(interp, monkeypatch):
    monkeypatch.setenv("PT_KERNEL_QUANT_MATMUL", "int8")
    sig = _sig_f32("mul", (128, 256), (256, 128))
    sel = kreg.select("mul", sig)
    assert sel is not None and sel.name == "quantized_matmul"
    # shape gates: non-128-multiple dims keep the lowered path
    assert kreg.select("mul", _sig_f32("mul", (100, 256),
                                       (256, 128))) is None

    # through the real mul lowering (what the engine traces)
    from paddle_tpu.core.registry import OPS, ExecContext, _SlotView
    r = np.random.default_rng(2)
    x = jnp.asarray(r.standard_normal((128, 256), dtype=np.float32))
    y = jnp.asarray(r.standard_normal((256, 128), dtype=np.float32))
    env = {"x": x, "y": y}
    op = _SlotView("mul", {"X": ["x"], "Y": ["y"]}, {"Out": ["o"]},
                   {"x_num_col_dims": 1, "y_num_col_dims": 1})
    OPS.get("mul").lowering(ExecContext(op, env))
    ref = np.asarray(jnp.matmul(x, y))
    assert parity.rel_err(ref, env["o"]) < 5e-2
    # the int8 path is NOT the f32 path (it actually quantized)
    assert not np.array_equal(ref, np.asarray(env["o"]))


# ---------------------------------------------------------------------------
# cache keys (stale-artifact bug class, PR 8 review)
# ---------------------------------------------------------------------------

def test_kernel_flag_in_cache_key():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with fluid.program_guard(main, startup):
        loss = _mlp_adam()
    scope = Scope()
    feed = _feed()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
        eng = Engine()
        set_flags({"FLAGS_use_custom_kernels": True})
        eng.run(main, scope, None, feed, [loss.name])
        t0 = eng.counters["traces"]
        set_flags({"FLAGS_use_custom_kernels": False})
        eng.run(main, scope, None, feed, [loss.name])
        assert eng.counters["traces"] == t0 + 1


def test_kernel_env_in_cache_key(monkeypatch):
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with fluid.program_guard(main, startup):
        loss = _mlp_adam()
    scope = Scope()
    feed = _feed()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
        eng = Engine()
        eng.run(main, scope, None, feed, [loss.name])
        t0 = eng.counters["traces"]
        monkeypatch.setenv("PT_KERNEL_DENY", "fused_adam")
        eng.run(main, scope, None, feed, [loss.name])
        assert eng.counters["traces"] == t0 + 1
        monkeypatch.setenv("PT_KERNEL_QUANT_MATMUL", "int8")
        eng.run(main, scope, None, feed, [loss.name])
        assert eng.counters["traces"] == t0 + 2


# ---------------------------------------------------------------------------
# flash attention: need_dbias ds suppression (satellite regression)
# ---------------------------------------------------------------------------

def _fa_shapes():
    r = np.random.default_rng(4)
    q = jnp.asarray(r.standard_normal((1, 2, 128, 64)) * 0.3,
                    jnp.float32)
    k = jnp.asarray(r.standard_normal((1, 2, 128, 64)) * 0.3,
                    jnp.float32)
    v = jnp.asarray(r.standard_normal((1, 2, 128, 64)) * 0.3,
                    jnp.float32)
    b = jnp.asarray(r.standard_normal((1, 2, 128, 128)) * 0.1,
                    jnp.float32)
    return q, k, v, b


def test_need_dbias_false_has_no_ds_output(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    q, k, v, b = _fa_shapes()

    def loss(need_dbias):
        def f(q):
            return fa.flash_attention(q, k, v, b, 0.125, 128, 128,
                                      "bhsd", False, need_dbias).sum()
        return f

    with_ds = str(jax.make_jaxpr(jax.grad(loss(True)))(q))
    no_ds = str(jax.make_jaxpr(jax.grad(loss(False)))(q))
    # the forward bias reshape contributes [B*H, Sq, Sk] avals to both
    # jaxprs; the EXTRA one in the need_dbias=True trace is the ds
    # output of the dq pallas kernel — suppression must drop exactly it
    ds_shape = "f32[2,128,128]"
    assert with_ds.count(ds_shape) == no_ds.count(ds_shape) + 1


def test_need_dbias_values_and_grads_agree(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    q, k, v, b = _fa_shapes()

    def f(need):
        return lambda q: fa.flash_attention(
            q, k, v, b, 0.125, 128, 128, "bhsd", False, need).sum()

    np.testing.assert_array_equal(np.asarray(f(True)(q)),
                                  np.asarray(f(False)(q)))
    dq_t = jax.grad(f(True))(q)
    dq_f = jax.grad(f(False))(q)
    np.testing.assert_allclose(np.asarray(dq_t), np.asarray(dq_f),
                               rtol=1e-6, atol=1e-6)


def test_need_dbias_none_keeps_dbias(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    q, k, v, b = _fa_shapes()

    def f(b):
        return fa.flash_attention(q, k, v, b, 0.125, 128, 128).sum()

    db = jax.grad(f)(b)
    assert db.shape == b.shape
    assert float(jnp.abs(db).max()) > 0


def test_flash_attention_respects_deny(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setenv("PT_KERNEL_DENY", "flash_attention")
    q, k, v, _ = _fa_shapes()
    assert not fa.use_kernel_path(q, k, 128, 128)
    monkeypatch.delenv("PT_KERNEL_DENY")
    assert fa.use_kernel_path(q, k, 128, 128)


# ---------------------------------------------------------------------------
# Mamba-2's state-space scan: kernels and lowering against the recurrence
# ---------------------------------------------------------------------------

def _ssd_case(b, t, h, p, g, n, seed, dtype=jnp.float32):
    r = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(r.standard_normal(shape), jnp.float32)
    return dict(x=draw(b, t, h, p).astype(dtype),
                dt=jax.nn.softplus(draw(b, t, h) - 1.0),
                a=-jnp.asarray(r.uniform(0.5, 4.0, (h,)), jnp.float32),
                b=draw(b, t, g, n).astype(dtype),
                c=draw(b, t, g, n).astype(dtype), d=draw(h),
                dy=draw(b, t, h, p).astype(dtype))


def _recurrence(x, dt, a, b, c, d):
    from benchmark.families import mamba_gqa_moe_decoder_reference as ref
    return ref.recurrence(x.astype(jnp.float32), dt, a,
                          b.astype(jnp.float32), c.astype(jnp.float32), d,
                          block=x.shape[1])


@pytest.mark.parametrize("path", ["lowered", "mamba2_ssd_fwd_bwd"])
@pytest.mark.parametrize("tokens,chunk", [(37, 16), (64, 16), (9, 16)],
                         ids=["ragged_tail", "whole_chunks", "one_chunk"])
def test_ssd_against_the_token_by_token_recurrence(tokens, chunk, path):
    """`mamba2_ssd_fwd` / `_bwd` under the interpreter and the lowered
    chunked form: y and the six gradients against the recurrence, B = 2,
    at a length that is no multiple of the chunk among them; the states
    handed to the backward are those of the chunks' starts."""
    from paddle_tpu.kernels import mamba2_ssd as ssd
    kernels = path != "lowered"
    c = _ssd_case(2, tokens, 4, 8, 2, 16, seed=tokens)
    args = [c[k] for k in "x dt a b c d".split()]
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*args)
        r_grads = jax.vjp(_recurrence, *args)[1](c["dy"])
        y, states = ssd.ssd(*args, kernels, chunk=chunk)
        grads = ssd.ssd_grad(*args, states, c["dy"], kernels, chunk=chunk)
    assert states.shape == (2, -(-tokens // chunk), 4, 8, 16)
    assert not np.asarray(states[:, 0]).any()       # S_0 = 0
    np.testing.assert_allclose(y, want, atol=3e-5, rtol=3e-5)
    for name, g, w in zip("x dt a b c d".split(), grads, r_grads):
        assert g.shape == w.shape, name
        scale = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(g - w))) <= 3e-5 * max(scale, 1), name


def test_ssd_kernels_equal_their_lowering_in_bfloat16():
    """bf16 operands, float32 accumulation, dt and every exp float32 on
    both paths: the kernels and the lowered form round the same products,
    so they agree far inside bf16's own rounding of the result."""
    from paddle_tpu.kernels import mamba2_ssd as ssd
    c = _ssd_case(1, 48, 4, 8, 2, 16, seed=5, dtype=jnp.bfloat16)
    args = [c[k] for k in "x dt a b c d".split()]
    (y_k, st_k), (y_l, st_l) = (ssd.ssd(*args, kern, chunk=16)
                                for kern in (True, False))
    assert y_k.dtype == jnp.bfloat16 and st_k.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_l, np.float32), atol=0.06,
                               rtol=0.02)
    np.testing.assert_allclose(st_k, st_l, atol=0.02, rtol=0.02)
    g_k, g_l = (ssd.ssd_grad(*args, st_l, c["dy"], kern, chunk=16)
                for kern in (True, False))
    for name, a, b in zip("x dt a b c d".split(), g_k, g_l):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 0.03 * max(np.abs(b).max(), 1), name


def test_ssd_is_registered_and_routes_like_the_others(interp, monkeypatch):
    from paddle_tpu.kernels import mamba2_ssd as ssd
    assert "mamba2_ssd" in kreg.kernel_names()
    x, b = jnp.zeros((1, 32, 4, 8)), jnp.zeros((1, 32, 2, 16))
    kreg.reset_stats()
    assert ssd.use_kernels(x, b)
    monkeypatch.setenv("PT_KERNEL_DENY", "mamba2_ssd")
    assert not ssd.use_kernels(x, b)
    with kreg.auto_partitioned():
        monkeypatch.delenv("PT_KERNEL_DENY")
        assert not ssd.use_kernels(x, b)         # a mesh: lowered
    assert kreg.dispatch_stats()["per_kernel"]["mamba2_ssd"] == {
        "custom": 1, "denied": 1, "lowered": 1}
    # off the interpreter the shapes must tile: a group's heads a sublane
    # tile, their channels and the state whole lane blocks
    monkeypatch.setattr(kreg, "_INTERPRET", False)
    sig = lambda h, p, g, n: kreg.signature(  # noqa: E731
        "mamba2_ssd", jnp.zeros((1, 8, h, p), jnp.bfloat16),
        jnp.zeros((1, 8, g, n), jnp.bfloat16))
    assert ssd._eligible(sig(64, 64, 8, 128))     # the published mixer
    assert not ssd._eligible(sig(4, 8, 2, 16))
    assert not ssd._eligible(sig(64, 64, 8, 64))
