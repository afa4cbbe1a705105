"""Collective communication scheduler tests (parallel/comm_scheduler.py).

Covers the ISSUE-4 acceptance surface: bucket-plan determinism and
caps, the grad_collectives_per_step <= ceil(total_bytes / cap) bound
via Engine.counters, quantized all-reduce numerics within the
documented tolerance (docs/COLLECTIVES.md), sharded-weight-update
parity on a 2-layer Adam MLP, the c_allreduce_fused lowering under
shard_map (including mixed int64/int32 canonicalization with x64
disabled), and transpiled bucketed programs still running single
process. The 8-device virtual CPU mesh comes from conftest.py.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.engine import Engine
from jax import shard_map
from paddle_tpu.core.scope import Scope
from paddle_tpu.parallel import DistributedStrategy
from paddle_tpu.parallel import comm_scheduler as cs


@pytest.fixture
def flag_guard():
    """Restore the comm-scheduler flags after each test that sets them."""
    yield
    fluid.set_flags({"FLAGS_allreduce_bucket_mb": 32.0,
                     "FLAGS_quantized_allreduce": "",
                     "FLAGS_sharded_weight_update": False})


def _build_adam_mlp():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [16], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, 32, act="relu",
                      param_attr=fluid.ParamAttr(name="q_w0"),
                      bias_attr=fluid.ParamAttr(name="q_b0"))
        pred = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="q_w1"),
                         bias_attr=fluid.ParamAttr(name="q_b1"))
        cost = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.AdamOptimizer(0.01).minimize(cost)
    return main, startup, cost


def _batches(n=3, bs=8):
    rng = np.random.default_rng(0)
    return [{"x": rng.normal(size=(bs, 16)).astype(np.float32),
             "y": rng.normal(size=(bs, 1)).astype(np.float32)}
            for _ in range(n)]


def _run_steps(main, startup, cost, batches, strategy=None,
               engine=None):
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        eng = engine or Engine(strategy=strategy)
        losses = []
        for b in batches:
            out = eng.run(main, scope, None, b, [cost.name])
            losses.append(float(np.asarray(out[0])))
    return losses, eng


# ---------------------------------------------------------------------------
# bucket planning
# ---------------------------------------------------------------------------

def test_plan_respects_cap_and_dtype():
    items = [
        ("a", (256,), np.float32),   # 1 KB
        ("b", (256,), np.float32),   # 1 KB
        ("c", (256,), np.int32),     # dtype change seals
        ("d", (2048,), np.float32),  # 8 KB > cap: own bucket
        ("e", (256,), np.float32),
    ]
    buckets = cs.plan_named_buckets(items, bucket_bytes=4096)
    assert [b.names for b in buckets] == [
        ("a", "b"), ("c",), ("d",), ("e",)]
    assert all(b.dtype == np.dtype(np.float32) for b in buckets
               if b.names != ("c",))
    # caps: only the deliberately oversized tensor exceeds the cap
    assert [b.bytes <= 4096 for b in buckets] == \
        [True, True, False, True]


def test_plan_deterministic_across_shards():
    """Same program built twice (as two ranks would) -> identical
    bucket plans: membership, order, byte counts, seal points."""
    plans = []
    for _ in range(2):
        main, _, _ = _build_adam_mlp()
        plans.append(cs.plan_program_buckets(main, bucket_bytes=1 << 20))
    assert [b.key() for b in plans[0]] == [b.key() for b in plans[1]]
    assert [b.last_op_idx for b in plans[0]] == \
        [b.last_op_idx for b in plans[1]]


def test_plan_reverse_backward_order():
    """Grads bucket in production order: the LAST layer's grads come
    first (autodiff emits them first)."""
    main, _, _ = _build_adam_mlp()
    buckets = cs.plan_program_buckets(main, bucket_bytes=1 << 30)
    names = [n for b in buckets for n in b.names]
    assert set(names) == {"q_w0@GRAD", "q_b0@GRAD",
                          "q_w1@GRAD", "q_b1@GRAD"}
    assert names.index("q_w1@GRAD") < names.index("q_w0@GRAD")


def test_plan_overlap_stats():
    main, _, _ = _build_adam_mlp()
    # tiny cap -> one bucket per grad; all but the last seal strictly
    # before the final backward op => overlap-eligible
    buckets = cs.plan_program_buckets(main, bucket_bytes=1)
    stats = cs.plan_stats(buckets, max(b.last_op_idx for b in buckets))
    assert stats["buckets"] == 4
    assert stats["overlap_frac"] == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# engine integration: parity + counter bound
# ---------------------------------------------------------------------------

def test_bucketed_engine_matches_single_device(flag_guard):
    main, startup, cost = _build_adam_mlp()
    batches = _batches()
    single, _ = _run_steps(main, startup, cost, batches)
    fluid.set_flags({"FLAGS_allreduce_bucket_mb": 32.0})
    strat = DistributedStrategy(axes={"dp": 8})
    bucketed, eng = _run_steps(main, startup, cost, batches, strat)
    np.testing.assert_allclose(single, bucketed, rtol=2e-4, atol=2e-5)
    # the whole MLP fits one 32MB bucket -> exactly 1 fused collective
    assert eng.counters["grad_collectives_per_step"] == 1
    assert eng.counters["collective_bytes"] > 0


def test_counter_bound_matches_acceptance(flag_guard):
    """grad_collectives_per_step <= ceil(total_grad_bytes/cap) + slack
    for dtype/adjacency seals — here all grads are f32 and the cap is
    sized so the bound is tight."""
    main, startup, cost = _build_adam_mlp()
    total = sum(b.bytes for b in
                cs.plan_program_buckets(main, bucket_bytes=1 << 30))
    cap_mb = 1e-3  # 1048 bytes: forces multiple buckets
    fluid.set_flags({"FLAGS_allreduce_bucket_mb": cap_mb})
    strat = DistributedStrategy(axes={"dp": 8})
    _, eng = _run_steps(main, startup, cost, _batches(1), strat)
    per_step = eng.counters["grad_collectives_per_step"]
    cap_bytes = int(cap_mb * 1024 * 1024)
    # +len(grads) slack: a tensor never splits across buckets
    bound = math.ceil(total / cap_bytes) + 4
    assert 1 < per_step <= bound, (per_step, bound)
    assert eng.counters["collective_bytes"] == total
    assert 0.0 < eng.counters["comm_overlap_frac"] <= 1.0


# ---------------------------------------------------------------------------
# quantized all-reduce numerics
# ---------------------------------------------------------------------------

def test_fused_axis_psum_int8_tolerance():
    """int8 EQuARX psum error bound: |err| <= nranks * scale/2 per
    element (each rank rounds once to the shared grid)."""
    rng = np.random.default_rng(1)
    nranks = 8
    x = rng.normal(size=(nranks, 1 << 15)).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:nranks]), ("dp",))
    fm = shard_map(lambda a: cs.fused_axis_psum(a[0], "dp", "int8"),
                   mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    out = np.asarray(jax.jit(fm)(x)).reshape(nranks, -1)[0]
    exact = x.sum(0)
    scale = np.abs(x).max() / 127.0
    np.testing.assert_allclose(out, exact,
                               atol=nranks * scale / 2 + 1e-6)
    # and it genuinely differs from exact (quantization happened)
    assert np.abs(out - exact).max() > 0


def test_fused_axis_psum_bf16_tolerance():
    rng = np.random.default_rng(2)
    nranks = 8
    x = rng.normal(size=(nranks, 4096)).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:nranks]), ("dp",))
    fm = shard_map(lambda a: cs.fused_axis_psum(a[0], "dp", "bf16"),
                   mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    out = np.asarray(jax.jit(fm)(x)).reshape(nranks, -1)[0]
    exact = x.sum(0)
    # bf16 has 8 mantissa bits -> ~2^-8 relative per addend
    np.testing.assert_allclose(out, exact, rtol=0.05,
                               atol=nranks * 2 ** -8)


def test_fused_stacked_sum_quantized_matches():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(4, 1 << 14)).astype(np.float32)
    exact = np.asarray(cs.fused_stacked_sum(jnp.asarray(x)))
    np.testing.assert_allclose(exact, x.sum(0), rtol=1e-5, atol=1e-5)
    q = np.asarray(cs.fused_stacked_sum(jnp.asarray(x), "int8"))
    scale = np.abs(x).max() / 127.0
    np.testing.assert_allclose(q, x.sum(0), atol=4 * scale / 2 + 1e-6)
    b = np.asarray(cs.fused_stacked_sum(jnp.asarray(x), "bf16"))
    np.testing.assert_allclose(b, x.sum(0), rtol=0.05, atol=4 * 2 ** -8)


def test_small_buckets_fall_back_to_exact():
    assert not cs.should_quantize(np.float32, 1024, "int8")
    assert cs.should_quantize(np.float32, cs.MIN_QUANT_BYTES, "int8")
    assert not cs.should_quantize(np.int32, 1 << 20, "int8")
    assert not cs.should_quantize(np.float32, 1 << 20, "")


def test_quantized_engine_loss_within_tolerance(flag_guard):
    """End-to-end: FLAGS_quantized_allreduce trains the same MLP to a
    loss matching exact mode within the documented tolerance. With
    MIN_QUANT_BYTES the tiny-MLP buckets fall back to exact, so the
    trajectory is identical; the numerics tolerance for big buckets is
    covered by the fused_axis_psum tests above."""
    main, startup, cost = _build_adam_mlp()
    batches = _batches()
    fluid.set_flags({"FLAGS_allreduce_bucket_mb": 32.0})
    strat = DistributedStrategy(axes={"dp": 8})
    exact, _ = _run_steps(main, startup, cost, batches, strat)
    fluid.set_flags({"FLAGS_quantized_allreduce": "int8"})
    quant, eng = _run_steps(main, startup, cost, batches,
                            DistributedStrategy(axes={"dp": 8}))
    np.testing.assert_allclose(exact, quant, rtol=5e-2, atol=1e-3)
    assert eng.counters["collective_buckets"] > 0


def test_bad_quantize_flag_raises(flag_guard):
    fluid.set_flags({"FLAGS_quantized_allreduce": "fp4"})
    with pytest.raises(ValueError, match="quantized_allreduce"):
        cs.quantize_mode_from_flags()


# ---------------------------------------------------------------------------
# sharded weight update (FLAGS_sharded_weight_update)
# ---------------------------------------------------------------------------

def test_sharded_weight_update_parity(flag_guard):
    """2-layer Adam MLP: bucketed collectives + dp-sharded optimizer
    update match the single-device trajectory, and the moments are
    ACTUALLY 1/|dp| per device while params stay replicated."""
    main, startup, cost = _build_adam_mlp()
    batches = _batches()
    single, _ = _run_steps(main, startup, cost, batches)
    fluid.set_flags({"FLAGS_allreduce_bucket_mb": 32.0,
                     "FLAGS_sharded_weight_update": True})
    strat = DistributedStrategy(axes={"dp": 8})
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        eng = Engine(strategy=strat)
        sharded = [float(np.asarray(
            eng.run(main, scope, None, b, [cost.name])[0]))
            for b in batches]
        names = [n for n in scope.local_var_names()
                 if "moment1" in n and n.startswith("q_w0")]
        assert names, sorted(scope.local_var_names())
        m = scope.find_var(names[0]).get_value()
        arr = m.array if hasattr(m, "array") else m
        assert tuple(arr.sharding.spec)[:1] == ("dp",), arr.sharding
        assert arr.sharding.shard_shape(arr.shape)[0] * 8 == \
            arr.shape[0]
        w = scope.find_var("q_w0").get_value()
        warr = w.array if hasattr(w, "array") else w
        wspec = tuple(warr.sharding.spec) if warr.sharding.spec else ()
        assert all(ax is None for ax in wspec), wspec
    np.testing.assert_allclose(single, sharded, rtol=2e-4, atol=2e-5)


def test_sharded_update_spec_routes_accumulators():
    mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
    spec = cs.sharded_update_spec("q_w0_moment1_0", (16, 32), mesh,
                                  "dp")
    assert tuple(spec)[:1] == ("dp",)
    # params do not shard under ZeRO-1
    pspec = cs.sharded_update_spec("q_w0", (16, 32), mesh, "dp")
    assert pspec is None or all(ax is None for ax in tuple(pspec))
    # no dp axis on the mesh -> inert
    mp = Mesh(np.array(jax.devices()[:8]), ("mp",))
    assert cs.sharded_update_spec("q_w0_moment1_0", (16, 32), mp,
                                  "dp") is None


# ---------------------------------------------------------------------------
# c_allreduce_fused lowering (transpiled per-device path)
# ---------------------------------------------------------------------------

class _FusedOp:
    type = "c_allreduce_fused"

    def __init__(self, names, attrs=None):
        self._names = list(names)
        self._attrs = dict(attrs or {})

    def input(self, slot):
        return self._names if slot == "X" else []

    def output(self, slot):
        return self._names if slot == "Out" else []

    def attr(self, name, default=None):
        return self._attrs.get(name, default)

    def has_attr(self, name):
        return name in self._attrs


def _lower_fused(env, names, attrs=None, axis="dp"):
    from paddle_tpu.ops.collective import collective_axis_guard
    from paddle_tpu.core.registry import OPS, ExecContext
    op = _FusedOp(names, attrs)
    if axis:
        with collective_axis_guard(axis):
            OPS.get("c_allreduce_fused").lowering(ExecContext(op, env))
    else:
        OPS.get("c_allreduce_fused").lowering(ExecContext(op, env))
    return env


def test_fused_lowering_psum_and_scale():
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))

    def f(a, b):
        env = {"g0": a, "g1": b}
        _lower_fused(env, ["g0", "g1"], {"scale": 0.25})
        return env["g0"], env["g1"]

    fm = shard_map(f, mesh=mesh, in_specs=(P("dp"), P("dp")),
                   out_specs=(P("dp"), P("dp")))
    a = jnp.arange(8, dtype=jnp.float32)
    b = jnp.arange(8, dtype=jnp.float32) * 2
    oa, ob = jax.jit(fm)(a, b)
    ea = np.tile(np.asarray(a).reshape(4, 2).sum(0) * 0.25, 4)
    eb = np.tile(np.asarray(b).reshape(4, 2).sum(0) * 0.25, 4)
    np.testing.assert_allclose(np.asarray(oa), ea, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ob), eb, rtol=1e-6)


def test_fused_lowering_identity_without_axis():
    a = jnp.arange(4, dtype=jnp.float32)
    env = _lower_fused({"g0": a}, ["g0"], axis=None)
    np.testing.assert_array_equal(np.asarray(env["g0"]),
                                  np.asarray(a))


def test_fused_lowering_canonicalizes_int64_operands():
    """Satellite: a host-side np.int64 constant mixed with int32
    operands must not crash the fused reduce under x64-disabled JAX —
    both canonicalize to int32 and group together."""
    assert not jax.config.jax_enable_x64
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))

    def f(a):
        env = {"g32": a,
               "g64": np.asarray([7, 9], dtype=np.int64)}
        _lower_fused(env, ["g32", "g64"])
        return env["g32"], env["g64"]

    fm = shard_map(f, mesh=mesh, in_specs=P("dp"),
                   out_specs=(P("dp"), P()))
    a = jnp.arange(8, dtype=jnp.int32)
    o32, o64 = jax.jit(fm)(a)
    assert o32.dtype == jnp.int32 and o64.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(o32), np.tile(np.asarray(a).reshape(4, 2).sum(0), 4))
    np.testing.assert_array_equal(np.asarray(o64),
                                  np.asarray([28, 36]))


def test_transpiled_bucketed_program_runs_single_process(flag_guard):
    """world_size-1: c_allreduce_fused is identity (no axis guard);
    a bucketed transpiled program trains exactly as the plain one."""
    plain, _ = _run_steps(*_build_adam_mlp(), _batches(4))
    main, startup, cost = _build_adam_mlp()
    cfg = fluid.DistributeTranspilerConfig()
    cfg.mode = "collective"
    t = fluid.DistributeTranspiler(config=cfg)
    t.transpile(trainer_id=0, program=main, trainers=1,
                startup_program=startup)
    trainer = t.get_trainer_program()
    ops = [op.type for op in trainer.global_block().ops]
    assert "c_allreduce_fused" in ops
    losses, eng = _run_steps(trainer, startup, cost, _batches(4))
    np.testing.assert_array_equal(losses, plain)
    assert all(np.isfinite(losses))
    # no mesh -> the identity collective moves no bytes; honest zero
    assert eng.counters["grad_collectives_per_step"] == 0


# ---------------------------------------------------------------------------
# dygraph bucketing building blocks
# ---------------------------------------------------------------------------

def test_dygraph_plan_reverse_param_order():
    arrs = [np.zeros((4, 4), np.float32), np.zeros((4,), np.float32),
            np.zeros((2, 2), np.float32)]
    buckets = cs.plan_named_buckets(
        [(i, a.shape, a.dtype) for i, a in enumerate(arrs)],
        bucket_bytes=1 << 20)
    assert len(buckets) == 1 and buckets[0].names == (0, 1, 2)
    assert buckets[0].bytes == sum(a.nbytes for a in arrs)
