"""The decoder-only language model's ops, kernels and model file: RMS
normalisation, rotary positions, the gated activation, the sigmoid top-k
router, the dropless expert layer that holds a share of the experts
(lowered and, under the Pallas interpreter, through the grouped-matmul
kernels), the flash kernels with q/k and v of different widths, and the
whole model through `Executor.run` against the benchmark's plain
reference (benchmark/families/mla_moe_decoder_reference.py)."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.scope import Scope
from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels import registry as kreg

from benchmark.families import mla_moe_decoder as family
from benchmark.families import mla_moe_decoder_reference as ref

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


@pytest.fixture
def interp(monkeypatch):
    """Route the registry's kernels and the flash kernels through the
    Pallas interpreter on this CPU."""
    monkeypatch.setattr(kreg, "_INTERPRET", True)
    monkeypatch.setattr(fa, "_INTERPRET", True)
    kreg.reset_stats()
    yield


def _r(shape, seed, scale=1.0):
    return (np.random.default_rng(seed).standard_normal(shape)
            * scale).astype(np.float32)


def _run(build, feeds, wrt):
    """Build `out = build(**data vars)`, weight it by a fed cotangent, and
    return (out, {name: d sum(out * cot) / d name}) for `wrt` (feeds or
    parameters), through Executor.run on the CPU."""
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with fluid.program_guard(main, startup):
        vs = {}
        for n, a in feeds.items():
            vs[n] = layers.data(n, list(a.shape), append_batch_size=False,
                                dtype=str(a.dtype))
            vs[n].stop_gradient = not np.issubdtype(a.dtype, np.floating)
        out = build(**vs)
        cot = layers.data("cot", list(out.shape), append_batch_size=False,
                          dtype="float32")
        loss = layers.reduce_sum(layers.elementwise_mul(out, cot))
        block = main.global_block()
        targets = [vs[n] if n in vs else block.var(n) for n in wrt]
        grads = fluid.backward.gradients(loss, targets)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return main, scope, exe, out, grads


def _fetch(main, scope, exe, feeds, out, grads, cot, params=None):
    with fluid.scope_guard(scope):
        for n, a in (params or {}).items():
            scope.find_var(n).set_value(jnp.asarray(a))
        res = exe.run(main, feed={**feeds, "cot": cot},
                      fetch_list=[out] + list(grads))
    return np.asarray(res[0]), [np.asarray(g) for g in res[1:]]


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape, (a.shape, b.shape)
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), \
        np.max(np.abs(a - b))


# ------------------------------------------------------------------ ops

def test_rms_norm_forward_and_grad():
    x, w, cot = _r((2, 5, 16), 0), 1 + _r((16,), 1, 0.1), _r((2, 5, 16), 2)
    prog = _run(lambda x: layers.rms_norm(
        x, epsilon=1e-6, param_attr=fluid.ParamAttr(name="w")),
        {"x": x}, ["x", "w"])
    out, (dx, dw) = _fetch(*prog[:3], {"x": x}, *prog[3:], cot, {"w": w})
    f = lambda x, w: ref._rms_norm(x, w, 1e-6)
    _close(out, f(x, w))
    gx, gw = jax.grad(lambda x, w: jnp.sum(f(x, w) * cot), (0, 1))(x, w)
    _close(dx, gx)
    _close(dw, gw)


@pytest.mark.parametrize("rotary_dim", [None, 8])
def test_rotary_embedding_forward_and_grad(rotary_dim):
    x, cot = _r((2, 6, 3, 24), 3), _r((2, 6, 3, 24), 4)
    prog = _run(lambda x: layers.rotary_embedding(
        x, theta=1e6, rotary_dim=rotary_dim), {"x": x}, ["x"])
    out, (dx,) = _fetch(*prog[:3], {"x": x}, *prog[3:], cot)
    keep = 24 - (rotary_dim or 24)

    def f(x):
        return jnp.concatenate(
            [x[..., :keep], ref._rope(x[..., keep:], 1e6)], -1)
    _close(out, f(x))
    _close(dx, jax.grad(lambda x: jnp.sum(f(x) * cot))(x))
    # the pairs are adjacent channels, the angle pos * theta^(-2i/R)
    r = rotary_dim or 24
    pos, i = 5, 1
    a, b = x[1, pos, 2, keep + 2 * i], x[1, pos, 2, keep + 2 * i + 1]
    ang = pos * 1e6 ** (-2 * i / r)
    np.testing.assert_allclose(
        out[1, pos, 2, keep + 2 * i],
        a * np.cos(ang) - b * np.sin(ang), rtol=1e-5, atol=1e-6)


def test_swiglu_forward_and_grad():
    g, u, cot = _r((7, 12), 5), _r((7, 12), 6), _r((7, 12), 7)
    prog = _run(lambda g, u: layers.swiglu(g, u), {"g": g, "u": u},
                ["g", "u"])
    out, (dg, du) = _fetch(*prog[:3], {"g": g, "u": u}, *prog[3:], cot)
    f = lambda g, u: jax.nn.silu(g) * u
    _close(out, f(g, u))
    rg, ru = jax.grad(lambda g, u: jnp.sum(f(g, u) * cot), (0, 1))(g, u)
    _close(dg, rg)
    _close(du, ru)


SZ = dict(num_experts_per_tok=3, norm_topk_prob=True,
          routed_scaling_factor=2.448)


def _router_program(e, held, first):
    def build(x):
        choice, weight, counts = layers.moe_router(
            x, e, 3, experts_held=held, first_expert=first,
            routed_scaling_factor=2.448,
            param_attr=fluid.ParamAttr(name="w_r"),
            bias_attr=fluid.ParamAttr(name="b_r"))
        build.extra = [choice, counts]
        return weight
    return build


def test_moe_router_choices_weights_counts_and_grad():
    t, d, e = 40, 16, 12
    x, w_r, cot = _r((t, d), 8), _r((e, d), 9, 0.3), _r((t, 3), 10)
    # a bias large enough to change the selection: the weights must
    # still be the scores without it
    b = _r((e,), 11, 0.5)
    build = _router_program(e, 4, 4)
    prog = _run(build, {"x": x}, ["x", "w_r"])
    main, scope, exe, out, grads = prog
    with fluid.scope_guard(scope):
        scope.find_var("w_r").set_value(jnp.asarray(w_r))
        scope.find_var("b_r").set_value(jnp.asarray(b))
        weight, choice, counts, dx, dw = [np.asarray(a) for a in exe.run(
            main, feed={"x": x, "cot": cot},
            fetch_list=[out] + build.extra + list(grads))]
    r_choice, r_weight = ref.route(x, w_r, b, SZ)
    assert (choice == np.asarray(r_choice)).all()
    no_bias, _ = ref.route(x, w_r, np.zeros_like(b), SZ)
    assert (np.asarray(no_bias) != choice).any()
    _close(weight, r_weight)
    np.testing.assert_allclose(weight.sum(-1), 2.448, rtol=1e-5)
    want = [(choice == 4 + j).sum() for j in range(4)]
    assert counts.tolist() == want
    gx, gw = jax.grad(lambda x, w: jnp.sum(
        ref.route(x, w, b, SZ)[1] * cot), (0, 1))(x, w_r)
    _close(dx, gx)
    _close(dw, gw)
    # the buffer takes no gradient and no update op
    assert main.global_block().var("b_r").trainable is False


def _experts_case(t, d, f, held, choice, seed=0):
    k = choice.shape[1]
    return dict(
        x=_r((t, d), seed), choice=choice.astype(np.int32),
        weight=np.abs(_r((t, k), seed + 1)) + 0.1,
        wg=_r((held, d, f), seed + 2, 0.3), wu=_r((held, d, f), seed + 3, 0.3),
        wd=_r((held, f, d), seed + 4, 0.3), cot=_r((t, d), seed + 5))


def _experts_reference(c, first):
    ar = ref.base._Arithmetic("f32")

    def f(x, weight, wg, wu, wd):
        return ref.routed_experts(ar, x, jnp.asarray(c["choice"]), weight,
                                  wg, wu, wd, first)
    args = (c["x"], c["weight"], c["wg"], c["wu"], c["wd"])
    out = f(*args)
    grads = jax.grad(lambda *a: jnp.sum(f(*a) * c["cot"]),
                     (0, 1, 2, 3, 4))(*args)
    return out, grads


def _experts_program(c, num_experts, held, first):
    f = c["wg"].shape[2]
    prog = _run(lambda x, choice, weight: layers.moe_experts(
        x, choice, weight, num_experts, f, experts_held=held,
        first_expert=first, gate_attr=fluid.ParamAttr(name="wg"),
        up_attr=fluid.ParamAttr(name="wu"),
        down_attr=fluid.ParamAttr(name="wd")),
        {"x": c["x"], "choice": c["choice"], "weight": c["weight"]},
        ["x", "weight", "wg", "wu", "wd"])
    return _fetch(*prog[:3], {"x": c["x"], "choice": c["choice"],
                              "weight": c["weight"]}, *prog[3:], c["cot"],
                  {"wg": c["wg"], "wu": c["wu"], "wd": c["wd"]})


def _check_experts(c, num_experts, held, first, tol=2e-5):
    with jax.default_matmul_precision("highest"):
        out, grads = _experts_program(c, num_experts, held, first)
        r_out, r_grads = _experts_reference(c, first)
    _close(out, r_out, tol)
    for got, want in zip(grads, r_grads):
        _close(got, want, tol)


def _random_choice(t, k, num_experts, seed):
    rng = np.random.default_rng(seed)
    return np.stack([rng.choice(num_experts, k, replace=False)
                     for _ in range(t)])


@pytest.mark.parametrize("path", ["lowered", "kernels"])
def test_moe_experts_forward_and_grad(path, request):
    if path == "kernels":
        request.getfixturevalue("interp")
    c = _experts_case(150, 16, 8, 4, _random_choice(150, 3, 12, 20))
    _check_experts(c, 12, 4, 4)
    routed = kreg.dispatch_stats()["per_kernel"].get(
        "moe_grouped_matmul", {})
    assert bool(routed.get("custom")) == (path == "kernels")


@pytest.mark.parametrize("path", ["lowered", "kernels"])
@pytest.mark.parametrize("case", ["all_to_one_expert", "none_held"])
def test_moe_experts_dropless_under_imbalance(case, path, request):
    """No token is dropped whatever the imbalance: every token to ONE
    held expert (three times the mean load of the buffer's first group,
    over two tiles), and no token to any held expert."""
    if path == "kernels":
        request.getfixturevalue("interp")
    t = 200
    if case == "all_to_one_expert":
        choice = np.tile(np.array([[5, 0, 11]]), (t, 1))   # 5 is held
    else:
        choice = _random_choice(t, 3, 4, 21)               # 0..3: none
    c = _experts_case(t, 16, 8, 4, choice, seed=30)
    _check_experts(c, 12, 4, 4)
    if case == "none_held":
        out, _ = _experts_program(c, 12, 4, 4)
        assert not out.any()


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares: the routed parts of all shares plus the
    shared expert counted once equal the uncut reference layer."""
    t, d, f, e = 96, 16, 8, 16
    sz = dict(SZ, num_experts_per_tok=4, first_expert=0)
    y = _r((t, d), 40)
    p = {"router.w_0": _r((e, d), 41, 0.3), "router.b_0": _r((e,), 42, 0.2),
         "experts_gate.w_0": _r((e, d, f), 43, 0.3),
         "experts_up.w_0": _r((e, d, f), 44, 0.3),
         "experts_down.w_0": _r((e, f, d), 45, 0.3),
         "shared_gate.w_0": _r((d, 2 * f), 46, 0.3),
         "shared_up.w_0": _r((d, 2 * f), 47, 0.3),
         "shared_down.w_0": _r((2 * f, d), 48, 0.3)}
    ar = ref.base._Arithmetic("f32")
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref._moe(ar, p, jnp.asarray(y), sz, None)[0])
        shared = np.asarray(ref._swiglu(
            ar, y, p["shared_gate.w_0"], p["shared_up.w_0"],
            p["shared_down.w_0"]))
        choice, weight = ref.route(y, p["router.w_0"], p["router.b_0"], sz)
        total = shared.copy()
        for share in range(4):
            lo = 4 * share
            c = dict(x=y, choice=np.asarray(choice),
                     weight=np.asarray(weight), cot=np.zeros_like(y),
                     wg=p["experts_gate.w_0"][lo:lo + 4],
                     wu=p["experts_up.w_0"][lo:lo + 4],
                     wd=p["experts_down.w_0"][lo:lo + 4])
            out, _ = _experts_program(c, e, 4, lo)
            total += out
    _close(total, whole)


def test_row_plan_holds_every_held_choice_once():
    rng = np.random.default_rng(50)
    local = rng.integers(-3, 9, 700).astype(np.int32)   # 0..3 are held
    plan = {k: np.asarray(v) for k, v in gm.plan_rows(
        jnp.asarray(local), 4, tile=16).items()}
    held = (local >= 0) & (local < 4)
    assert (plan["held"] == held).all()
    assert plan["sizes"].tolist() == [(local == e).sum() for e in range(4)]
    rows = plan["row_of_choice"][held]
    assert len(set(rows.tolist())) == held.sum()          # one row each
    assert plan["valid"].sum() == held.sum()
    assert (plan["choice_of_row"][rows] == np.flatnonzero(held)).all()
    assert (plan["tile_expert"][rows // 16] == local[held]).all()
    assert plan["n_active"][0] == sum(max(1, -(-s // 16))
                                      for s in plan["sizes"])
    assert rows.max() < plan["n_active"][0] * 16
    assert len(plan["valid"]) == gm.buffer_rows(700, 4, 16)


# ------------------------------------------- flash kernels, two widths

@pytest.mark.parametrize("d_qk,d_v", [(24, 16), (16, 16)])
@pytest.mark.parametrize("what", ["forward", "dq", "dkv"])
def test_flash_kernels_take_two_head_widths(d_qk, d_v, what, interp):
    b, s, h = 2, 64, 4
    q, k = _r((b, s, h, d_qk), 60), _r((b, s, h, d_qk), 61)
    v, g = _r((b, s, h, d_v), 62), _r((b, s, h, d_v), 63)
    scale = d_qk ** -0.5
    assert fa._kernel_ok(q, k, 32, 32, "bshd", v)
    with jax.default_matmul_precision("highest"):
        out, lse = fa._fa_forward(q, k, v, None, scale, 32, 32,
                                  return_lse=True, layout="bshd",
                                  causal=True)
        want, vjp = jax.vjp(lambda q, k, v: fa._attn_reference(
            q, k, v, None, scale, layout="bshd", causal=True), q, k, v)
        if what == "forward":
            assert out.shape == (b, s, h, d_v)
            return _close(out, want, 1e-5)
        dq, dk, dv, _ = fa._fa_backward(q, k, v, None, out, lse, g, scale,
                                        32, 32, layout="bshd", causal=True)
        rq, rk, rv = vjp(g)
    if what == "dq":
        _close(dq, rq, 1e-5)
    else:
        _close(dk, rk, 1e-5)
        _close(dv, rv, 1e-5)


def test_heads_per_block_for_equal_widths_is_what_it_was():
    for d in (8, 16, 32, 64, 128, 256):
        old = max(1, 128 // d) if d < 128 else 1
        assert fa._heads_per_block(32, d) == old
        assert fa._heads_per_block(32, d, d) == old
    assert fa._heads_per_block(32, 192, 128) == 2      # 384 and 256 lanes
    assert (2 * 192) % 128 == 0 and (2 * 128) % 128 == 0


# ------------------------------------------------------ the whole model

def _model_sizes():
    import json
    import os
    from benchmark.lib import cells
    with open(os.path.join(cells.BENCH, "configs",
                           "kanana2_30b_a3b.json")) as f:
        return family.sizes(json.load(f), rehearsal=True)


def _train(sz, tr, seed, amp, steps=3):
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    from paddle_tpu import models
    with fluid.program_guard(main, startup):
        cost, _, _ = models.decoder_lm_train(family.model_config(sz))
        opt = fluid.optimizer.AdamOptimizer(
            learning_rate=sz["learning_rate"], beta1=sz["adam_beta1"],
            beta2=sz["adam_beta2"], epsilon=sz["adam_epsilon"])
        if amp:
            opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(cost)
    scope = Scope()
    pool = family.make_pool(sz, tr, seed)
    names = family.param_names(sz)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for n, a in family.init_params(sz, seed).items():
            scope.find_var(n).set_value(a)
        get = lambda n: scope.find_var(n).get_value()
        losses = [float(np.asarray(exe.run(
            main, feed=pool[0], fetch_list=[cost])[0]))]
        grads = family.read_first_gradient_norms(get, names, sz)
        losses += [float(np.asarray(exe.run(
            main, feed=pool[i], fetch_list=[cost])[0]))
            for i in range(1, steps)]
        delta = family.read_delta_norms(get, names, sz, seed)
    return {"losses": losses, "grad_norms": grads, "delta_norms": delta}


@pytest.mark.parametrize("path", ["lowered", "kernels"])
def test_model_trains_like_the_reference_for_three_adam_steps(path,
                                                              request):
    """The model file through Executor.run in float32 against the plain
    reference: losses, every leaf's first gradient and every leaf's
    change after three Adam steps, from the seed's weights."""
    if path == "kernels":
        request.getfixturevalue("interp")
    sz = _model_sizes()
    tr = family.traffic({"pool": 3, "reference_rows_per_block": 1}, True)
    with jax.default_matmul_precision("highest"):
        got = _train(sz, tr, 7, amp=False)
        want = family.run_reference(sz, tr, family.make_pool(sz, tr, 7), 7, 3)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=2e-6)
    assert set(got["grad_norms"]) == set(want["grad_norms"]) \
        == set(ref.trainable_names(sz))
    for n, w in want["grad_norms"].items():
        assert abs(got["grad_norms"][n] - w) <= 2e-4 * max(w, 1e-6), n
    for n, w in want["delta_norms"].items():
        assert abs(got["delta_norms"][n] - w) <= 5e-3 * w, n
    # the counter saw three steps of every token's top-k choices
    load = family.expert_load(sz)
    assert load.shape == (2, sz["experts_held"])
    tokens = 3 * tr["batch"] * tr["seq_len"]
    assert 0 < load.sum() <= 2 * tokens * sz["num_experts_per_tok"]
    if path == "kernels":
        stats = kreg.dispatch_stats()["per_kernel"]
        assert stats["moe_grouped_matmul"].get("custom")
        assert stats["flash_attention"].get("custom")


def test_model_under_mixed_precision_keeps_the_router_in_float32():
    sz = _model_sizes()
    tr = family.traffic({"pool": 3, "reference_rows_per_block": 1}, True)
    got = _train(sz, tr, 9, amp=True)
    want = family.run_reference(sz, tr, family.make_pool(sz, tr, 9), 9, 3)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=2e-3)
    from paddle_tpu.core import amp
    assert "moe_router" in amp.BLACK_OPS and "rms_norm" in amp.NORM_OPS
    assert "moe_experts" not in amp.WHITE_OPS   # its weights stay f32


def test_expert_load_reader():
    from paddle_tpu.observability import moe
    scope = Scope()
    assert moe.expert_load(scope) is None
    scope.var(moe.EXPERT_LOAD_VAR).set_value(
        jnp.asarray([[30, 10, 20, 0], [15, 15, 15, 15]], jnp.int32))
    load = moe.expert_load(scope)
    assert load.dtype == np.int64 and load.shape == (2, 4)
    stats = moe.load_stats(load, tokens=80)
    assert stats["held_rows_per_token"] == pytest.approx(0.75)
    assert stats["load_max_over_mean"] == pytest.approx(2.0)
    assert moe.load_stats(np.zeros((2, 4)), 80) is None
