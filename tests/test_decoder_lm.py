"""The decoder-only language model's ops, kernels and model file: RMS
normalisation, rotary positions (adjacent and half-split pairs), the
gated activation, the top-k router (sigmoid and softmax), the dropless
expert layer that holds a share of the experts (lowered and, under the
Pallas interpreter, through the grouped-matmul kernels), a learned sparse
attention's index (scores and exact top-k selection, lowered and through
its kernels), the flash kernels with q/k and v of different widths, with
fewer key heads than query heads and with a keep mask, a Mamba-2 mixer's
ops (the causal convolution, the state-space scan lowered and through
its kernels, the gated group norm), the ungated squared-ReLU expert, and
the three whole models the file builds through `Executor.run` against
the benchmark's plain references (benchmark/families/*_reference.py)."""
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

from benchmark.families import conv_gqa_moe_decoder as conv_family
from benchmark.families import conv_gqa_moe_decoder_reference as conv_ref
from benchmark.families import gqa_dsa_moe_decoder as gqa_family
from benchmark.families import gqa_dsa_moe_decoder_reference as gqa_ref
from benchmark.families import mamba_gqa_moe_decoder as hybrid_family
from benchmark.families import mamba_gqa_moe_decoder_reference as hybrid_ref
from benchmark.families import mla_moe_decoder as family
from benchmark.families import mla_moe_decoder_reference as ref
from paddle_tpu.kernels import sparse_index

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


@pytest.mark.parametrize("interleaved", [True, False],
                         ids=["adjacent_pairs", "half_split_pairs"])
@pytest.mark.parametrize("rotary_dim", [None, 8])
def test_rotary_embedding_forward_and_grad(rotary_dim, interleaved):
    x, cot = _r((2, 6, 3, 24), 3), _r((2, 6, 3, 24), 4)
    prog = _run(lambda x: layers.rotary_embedding(
        x, theta=1e6, rotary_dim=rotary_dim, interleaved=interleaved),
        {"x": x}, ["x"])
    out, (dx,) = _fetch(*prog[:3], {"x": x}, *prog[3:], cot)
    keep = 24 - (rotary_dim or 24)
    rope = ref._rope if interleaved else gqa_ref._rope

    def f(x):
        return jnp.concatenate(
            [x[..., :keep], rope(x[..., keep:], 1e6)], -1)
    _close(out, f(x))
    _close(dx, jax.grad(lambda x: jnp.sum(f(x) * cot))(x))
    # pair i is the adjacent channels (2i, 2i + 1) or the half-split
    # ones (i, i + R/2); its angle pos * theta^(-2i/R) either way
    r = rotary_dim or 24
    pos, i = 5, 1
    first, second = (2 * i, 2 * i + 1) if interleaved else (i, i + r // 2)
    a, b = x[1, pos, 2, keep + first], x[1, pos, 2, keep + second]
    ang = pos * 1e6 ** (-2 * i / r)
    np.testing.assert_allclose(
        out[1, pos, 2, keep + first],
        a * np.cos(ang) - b * np.sin(ang), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        out[1, pos, 2, keep + second],
        a * np.sin(ang) + b * np.cos(ang), rtol=1e-5, atol=1e-6)


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


def test_moe_router_scores_by_softmax_without_a_bias():
    """Softmax over ALL the experts, the top-k of it renormalised, no
    selection bias: choices, weights, counts and gradients against the
    plain reference's router."""
    t, d, e = 40, 16, 12
    x, w_r, cot = _r((t, d), 8), _r((e, d), 9, 0.3), _r((t, 3), 10)

    def build(x):
        choice, weight, counts = layers.moe_router(
            x, e, 3, experts_held=4, first_expert=4,
            scoring_func="softmax", param_attr=fluid.ParamAttr(name="w_r"),
            bias_attr=False)
        build.extra = [choice, counts]
        return weight
    main, scope, exe, out, grads = _run(build, {"x": x}, ["x", "w_r"])
    router, = [op for op in main.global_block().ops
               if op.type == "moe_router"]
    assert "Bias" not in router.input_slots() or not router.input("Bias")
    with fluid.scope_guard(scope):
        scope.find_var("w_r").set_value(jnp.asarray(w_r))
        weight, choice, counts, dx, dw = [np.asarray(a) for a in exe.run(
            main, feed={"x": x, "cot": cot},
            fetch_list=[out] + build.extra + list(grads))]
    sz = dict(num_experts_per_tok=3, norm_topk_prob=True)
    r_choice, r_weight = gqa_ref.route(x, w_r, sz)
    assert (choice == np.asarray(r_choice)).all()
    _close(weight, r_weight)
    np.testing.assert_allclose(weight.sum(-1), 1.0, rtol=1e-5)
    # the weights are softmax probabilities over all 12, renormalised
    p = np.asarray(jax.nn.softmax(x @ w_r.T, -1))
    top = np.sort(p, -1)[:, -3:]
    _close(np.sort(weight, -1), top / top.sum(-1, keepdims=True), 1e-5)
    assert counts.tolist() == [(choice == 4 + j).sum() for j in range(4)]
    gx, gw = jax.grad(lambda x, w: jnp.sum(
        gqa_ref.route(x, w, sz)[1] * cot), (0, 1))(x, w_r)
    _close(dx, gx)
    _close(dw, gw)


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


def _experts_program(c, num_experts, held, first, slots=(),
                     activation="swiglu"):
    """(Out, [the five gradients (four for the ungated expert, which has
    no gate matrix), then the op's outputs `slots`])."""
    f = c["wu"].shape[2]
    weights = ["wg", "wu", "wd"] if activation == "swiglu" else ["wu", "wd"]
    main, scope, exe, out, grads = _run(
        lambda x, choice, weight: layers.moe_experts(
            x, choice, weight, num_experts, f, experts_held=held,
            first_expert=first, gate_attr=fluid.ParamAttr(name="wg"),
            up_attr=fluid.ParamAttr(name="wu"),
            down_attr=fluid.ParamAttr(name="wd"), activation=activation),
        {"x": c["x"], "choice": c["choice"], "weight": c["weight"]},
        ["x", "weight"] + weights)
    more = [out.block.var(out.op.output(slot)[0]) for slot in slots]
    return _fetch(main, scope, exe, {"x": c["x"], "choice": c["choice"],
                                     "weight": c["weight"]}, out,
                  list(grads) + more, c["cot"], {n: c[n] for n in weights})


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


# 256 tokens x 4 choices, 4 of 16 experts held (4..7): prefixes of 6 and
# 8 tiles (the buffers of a quarter and of half the 1,024 choices) under
# the worst case's 12. A routing is the rows sent to each held expert.
_PREFIX_ROUTINGS = {
    "a_quarter_of_the_choices": ((70, 60, 66, 60), 768, 4),
    "at_the_first_edge": ((384, 1, 1, 1), 768, 6),
    "one_tile_past_the_first_edge": ((385, 1, 1, 1), 1024, 7),
    "at_the_second_edge": ((300, 300, 100, 100), 1024, 8),
    "every_choice_to_one_held_expert": ((0, 1024, 0, 0), 1536, 11),
}


def _routed(sizes, t=256, k=4, first=4, seed=60):
    """int32 [t, k] choices that send sizes[e] rows to held expert e and
    the rest to experts held elsewhere, shuffled."""
    flat = np.concatenate(
        [np.full(n, first + e) for e, n in enumerate(sizes)]
        + [np.arange(t * k - sum(sizes)) % first])
    return np.random.default_rng(seed).permutation(flat).reshape(t, k)


@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
@pytest.mark.parametrize("path", ["lowered", "kernels"])
@pytest.mark.parametrize("routing", sorted(_PREFIX_ROUTINGS))
def test_moe_experts_over_a_prefix_equals_the_worst_case_body(
        routing, path, activation, request, monkeypatch):
    """Whatever prefix of the row buffer the routing picks, the worst
    case included, Out and every gradient (five for the gated expert,
    four for the ungated) are BIT-EQUAL to the one body over the whole
    worst-case buffer; n_active at a prefix's edge takes that prefix and
    one tile more takes the next. One exception: through the kernels a
    short prefix sums a token's held rows in row order (the combine
    kernel, tests/test_moe_combine.py), so Out and dX, the two sums of
    up to top_k rows, are equal to float32 reassociation there."""
    if path == "kernels":
        request.getfixturevalue("interp")
    sizes, prefix, tiles = _PREFIX_ROUTINGS[routing]
    c = _experts_case(256, 16, 8, 4, _routed(sizes), seed=61)
    assert gm.prefix_rows(1024, 4, 16) == [768, 1024, 1536]
    out, got = _experts_program(c, 16, 4, 4, slots=["RowsWorked"],
                                activation=activation)
    assert got.pop().tolist() == [prefix, tiles * gm.TILE_ROWS]
    monkeypatch.setattr(
        gm, "prefix_rows", lambda n, held, num: [gm.buffer_rows(n, held)])
    whole, want = _experts_program(c, 16, 4, 4, slots=["RowsWorked"],
                                   activation=activation)
    assert want.pop().tolist() == [1536, tiles * gm.TILE_ROWS]
    assert np.abs(whole).max() > 0
    names = ("x", "weight", "wg", "wu", "wd")
    if activation == "relu2":
        names = names[:2] + names[3:]
    assert len(got) == len(names)
    by_rows = path == "kernels" and gm.combine_by_rows(prefix, 1024)
    assert by_rows == (path == "kernels" and prefix == 768)
    for name, g, w in zip(("out",) + names, [out] + got, [whole] + want):
        if by_rows and name in ("out", "x"):
            np.testing.assert_allclose(
                g, w, rtol=0, atol=6 * 2.0 ** -24 * 4 * np.abs(w).max(),
                err_msg=name)
        else:
            np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("shape,want", [
    ((8192 * 8, 16, 128), [10240, 18432, 34816, 67584]),   # keye2_s8192
    ((4096 * 6, 16, 128), [5120, 8192, 14336, 26624]),     # kanana2_s4096
    ((4096 * 6, 32, 128), [10240, 16384, 28672]),         # a quarter held
    ((4096 * 6, 128, 128), [40960]),                       # every expert
    ((150 * 3, 12, 12), [2048]),
], ids=["keye", "kanana", "a_quarter", "all_of_128", "all_of_12"])
def test_prefix_ladder_by_hand(shape, want):
    """The buffers of a share s = held / experts of the choices, of 2s
    and of 4s (those under all the choices), then the worst case."""
    assert gm.prefix_rows(*shape) == want
    assert want[-1] == gm.buffer_rows(shape[0], shape[1])


def _experts_jaxprs(num_experts, held):
    """The jaxprs of the forward and the grad lowering of one
    `moe_experts` op built by the layer."""
    from paddle_tpu.core.registry import OPS, ExecContext
    c = _experts_case(300, 16, 8, held,
                      _random_choice(300, 2, num_experts, 3))
    main = _run(lambda x, choice, weight: layers.moe_experts(
        x, choice, weight, num_experts, 8, experts_held=held),
        {"x": c["x"], "choice": c["choice"], "weight": c["weight"]},
        ["x", "weight"])[0]
    ops = {op.type: op for op in main.global_block().ops
           if op.type.startswith("moe_experts")}

    def lower(op_type, env):
        env = dict(env)
        OPS.get(op_type).lowering(ExecContext(ops[op_type], env))
        return env

    fwd = ops["moe_experts"]
    env = {fwd.input("X")[0]: c["x"], fwd.input("TopkIdx")[0]: c["choice"],
           fwd.input("TopkWeight")[0]: c["weight"],
           fwd.input("WGate")[0]: c["wg"], fwd.input("WUp")[0]: c["wu"],
           fwd.input("WDown")[0]: c["wd"]}
    after = jax.eval_shape(lambda e: lower("moe_experts", e), env)
    env_grad = {n: np.zeros(a.shape, a.dtype) for n, a in after.items()}
    env_grad[ops["moe_experts_grad"].input("Out@GRAD")[0]] = c["cot"]
    return (str(jax.make_jaxpr(lambda e: lower("moe_experts", e))(env)),
            str(jax.make_jaxpr(
                lambda e: lower("moe_experts_grad", e))(env_grad)))


@pytest.mark.parametrize("held,branches", [(12, 0), (4, 1)],
                         ids=["every_expert_held", "a_third_held"])
def test_moe_experts_branches_only_where_a_share_is_held(held, branches):
    """Every expert held: one prefix, the body itself, no `cond` in the
    forward's or the grad op's jaxpr. A share held: one `cond` each."""
    for jaxpr in _experts_jaxprs(12, held):
        assert jaxpr.count("cond[") == branches, jaxpr[:2000]


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


@pytest.mark.parametrize("e,top_k", [(16, 4), (64, 8)],
                         ids=["16_top4", "64_top8_mellum2"])
def test_eight_softmax_shares_add_up_to_the_uncut_layer(e, top_k):
    """e softmax-routed experts, top-k, no shared expert, over 8 shares
    of e / 8: the routed outputs of all eight shares add up to the uncut
    reference layer (each share given the router's choices over all e,
    as every chip computes them alike). 64 top-8 in shares of 8 is the
    mellum2_s8192 cell's expert layer."""
    t, d, f = 96, 16, 8
    held = e // 8
    sz = dict(num_experts_per_tok=top_k, norm_topk_prob=True,
              first_expert=0)
    y = _r((t, d), 70)
    p = {"router.w_0": _r((e, d), 71, 0.3),
         "experts_gate.w_0": _r((e, d, f), 72, 0.3),
         "experts_up.w_0": _r((e, d, f), 73, 0.3),
         "experts_down.w_0": _r((e, f, d), 74, 0.3)}
    ar = ref.base._Arithmetic("f32")
    with jax.default_matmul_precision("highest"):
        whole, choice = gqa_ref.moe_layer(ar, p, jnp.asarray(y), sz)
        _, weight = gqa_ref.route(y, p["router.w_0"], sz)
        total = np.zeros_like(y)
        for share in range(8):
            lo = held * share
            c = dict(x=y, choice=np.asarray(choice),
                     weight=np.asarray(weight), cot=np.zeros_like(y),
                     wg=p["experts_gate.w_0"][lo:lo + held],
                     wu=p["experts_up.w_0"][lo:lo + held],
                     wd=p["experts_down.w_0"][lo:lo + held])
            out, _ = _experts_program(c, e, held, lo)
            total += out
    _close(total, whole)
    # every token's choices lie in some share: nothing is left out
    assert np.abs(np.asarray(whole)).sum(-1).min() > 0


# ------------------------------------------- sparse attention's index

def _index_case(b, s, heads, dim, seed=80):
    return _r((b, s, heads, dim), seed), _r((b, s, dim), seed + 1), \
        _r((b, s, heads), seed + 2)


def _plain_index(q, k, w, top_k):
    """(scores [B, S, S], keep bool [B, S, S]) by plain loops over rows:
    the top_k best-scored keys not after the query, every such key while
    there are at most top_k, ties at the threshold kept."""
    q, k, w = (np.asarray(a, np.float64) for a in (q, k, w))
    b, s = q.shape[:2]
    scores = np.einsum("bqh,bhqk->bqk", w, np.maximum(
        np.einsum("bqhd,bkd->bhqk", q, k), 0.0))
    keep = np.zeros((b, s, s), bool)
    for i in range(b):
        for t in range(s):
            row = scores[i, t, :t + 1]
            kth = np.sort(row)[::-1][min(top_k, t + 1) - 1]
            keep[i, t, :t + 1] = row >= kth
    return scores, keep


@pytest.mark.parametrize("path", ["lowered", "kernels"])
def test_sparse_attention_index_against_a_plain_top_k(path, request):
    if path == "kernels":
        request.getfixturevalue("interp")
    b, s, heads, dim, top_k = 2, 64, 4, 16, 12
    q, k, w = _index_case(b, s, heads, dim)

    def build(q, k, w):
        mask, kept = layers.sparse_attention_index(q, k, w, top_k,
                                                   scale=0.25)
        build.extra = [mask, kept]
        return layers.cast(kept, "float32")
    main, scope, exe, out, _ = _run(build, {"q": q, "k": k, "w": w}, [])
    with jax.default_matmul_precision("highest"), fluid.scope_guard(scope):
        mask, kept = [np.asarray(a) for a in exe.run(
            main, feed={"q": q, "k": k, "w": w,
                        "cot": np.zeros((1,), np.float32)},
            fetch_list=build.extra)]
    _, want = _plain_index(q, k, w, top_k)
    assert mask.shape == (b, 1, s, s) and mask.dtype == np.int8
    assert (mask[:, 0].astype(bool) == want).all()
    assert not np.triu(mask[0, 0], 1).any()            # causal bound
    for t in range(top_k):                    # too few keys: keep them all
        assert mask[1, 0, t, :t + 1].all()
    # by hand: sum_t min(t + 1, top_k) a sequence, more only for ties
    by_hand = b * sum(min(t + 1, top_k) for t in range(s))
    assert kept.tolist() == [want.sum()] and want.sum() >= by_hand
    assert by_hand == gqa_ref.kept_pairs_by_hand(b, s, top_k)
    # the op takes no gradient and gives none
    assert not [op for op in main.global_block().ops
                if op.type.startswith("sparse_attention_index_grad")]
    routed = kreg.dispatch_stats()["per_kernel"].get(
        "sparse_index_scores", {})
    assert bool(routed.get("custom")) == (path == "kernels")


def test_sparse_index_kernels_equal_their_lowering(interp):
    """Each kernel body under the interpreter against the `jax.numpy`
    lowering: the scores tile by tile (several tiles a side, -inf above
    the diagonal), then the selection on the SAME scores, bit for bit —
    with ties (two index heads give exact zeros) and with every top_k
    from 1 to more keys than there are."""
    b, s = 2, 64
    q, k, w = _index_case(b, s, 2, 8, seed=90)
    with jax.default_matmul_precision("highest"):
        got = sparse_index.index_scores(q, k, w, True, tile=16)
        want = sparse_index.index_scores(q, k, w, False)
    finite = np.isfinite(np.asarray(want))
    assert (np.isfinite(np.asarray(got)) == finite).all()
    assert (finite[0] == np.tril(np.ones((s, s), bool))).all()
    _close(np.where(finite, got, 0), np.where(finite, want, 0), 1e-5)
    plain, _ = _plain_index(q, k, w, 1)
    _close(np.where(finite, want, 0), np.where(finite, plain, 0), 1e-5)
    assert (np.asarray(want) == 0).sum() > 10          # ties to break
    for top_k in (1, 7, 16, 63, 64, 200):
        kernel, counted = sparse_index.select_mask(want, top_k, True,
                                                   rows=8, chunk=32)
        lowered, summed = sparse_index.select_mask(want, top_k, False)
        assert kernel.dtype == lowered.dtype == jnp.int8
        assert (np.asarray(kernel) == np.asarray(lowered)).all(), top_k
        # each row's count, as the kernel's last accepted pass had it
        assert counted.shape == summed.shape == (b, s, 1)
        assert (np.asarray(counted)[..., 0]
                == np.asarray(lowered).sum(-1)).all(), top_k
        assert (np.asarray(counted) == np.asarray(summed)).all()


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


@pytest.mark.parametrize("backward", ["fused", "split"])
def test_flash_kernels_take_fewer_key_heads_and_a_keep_mask(
        backward, interp, monkeypatch):
    """32 query heads over 4 key / value heads of 128 under a keep mask
    the index made (int8 [B, 1, S, S]) and the causal bound: the forward
    and dq / dk / dv, in the fused backward and in the split pair,
    against the composed float32 vjp; k and v are never expanded and dk
    and dv come back at 4 heads."""
    if backward == "split":
        monkeypatch.setattr(fa, "_FUSED_DQ_VMEM_BUDGET", 0)
    b, s, h, hkv, d = 1, 64, 32, 4, 128
    q, g = _r((b, s, h, d), 64), _r((b, s, h, d), 67)
    k, v = _r((b, s, hkv, d), 65), _r((b, s, hkv, d), 66)
    mask = sparse_index.index_mask(*_index_case(b, s, 4, 16, seed=68), 12,
                                   False)[0][:, None]
    scale = d ** -0.5
    assert fa._kernel_ok(q, k, 32, 32, "bshd", v)
    # at two heads of 64 a lane block the kernels take fewer key heads
    # where the block's two query heads read ONE key head (PR 39: a group
    # that is a multiple of two, key heads that fill lane blocks), and
    # refuse a group of three and three key heads
    assert fa._kernel_ok(q[..., :64], k[..., :64], 32, 32, "bshd",
                         v[..., :64])
    assert not fa._kernel_ok(q[:, :, :12, :64], k[..., :64], 32, 32, "bshd",
                             v[..., :64])
    assert not fa._kernel_ok(q[:, :, :6, :64], k[:, :, :3, :64], 32, 32,
                             "bshd", v[:, :, :3, :64])

    def plain(q, k, v):
        """Dense float32 attention over the kept pairs, k and v repeated
        to the query heads."""
        kk, vv = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * scale
        keep = (mask != 0) & (jnp.arange(s)[:, None]
                              >= jnp.arange(s)[None, :])
        p = jax.nn.softmax(jnp.where(keep, sc, -1e30), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    with jax.default_matmul_precision("highest"):
        out, lse = fa._fa_forward(q, k, v, mask, scale, 32, 32,
                                  return_lse=True, layout="bshd",
                                  causal=True)
        want, vjp = jax.vjp(plain, q, k, v)
        _close(out, want, 1e-5)
        _close(fa._attn_reference(q, k, v, mask, scale, layout="bshd",
                                  causal=True), want, 1e-5)
        kreg.reset_stats()
        dq, dk, dv, dbias = fa._fa_backward(
            q, k, v, mask, out, lse, g, scale, 32, 32, layout="bshd",
            causal=True)
        rq, rk, rv = vjp(g)
    assert dbias is None and dk.shape == dv.shape == (b, s, hkv, d)
    _close(dq, rq, 1e-5)
    _close(dk, rk, 1e-5)
    _close(dv, rv, 1e-5)
    took = kreg.dispatch_stats()["per_kernel"]["flash_attention"]
    assert took == {backward + "_bwd": 1, "narrow_lse": 1}


def test_fused_attention_op_takes_fewer_key_heads_and_a_mask(interp):
    """The op through Executor.run: K and V at 4 heads, the mask as
    BiasQK, gradients for Q, K and V at their own head counts and none
    for the mask; `routing` says fused_bwd."""
    b, s, h, hkv, d = 1, 64, 8, 2, 128
    feeds = {"q": _r((b, s, h, d), 75), "k": _r((b, s, hkv, d), 76),
             "v": _r((b, s, hkv, d), 77),
             "mask": np.asarray(sparse_index.index_mask(
                 *_index_case(b, s, 4, 16, seed=78), 12, False)[0]
                 )[:, None]}
    cot = _r((b, s, h, d), 79)
    kreg.reset_stats()
    prog = _run(lambda q, k, v, mask: layers.fused_attention(
        q, k, v, mask, layout="bshd", causal=True, block_q=32, block_k=32),
        feeds, ["q", "k", "v"])
    with jax.default_matmul_precision("highest"):
        out, (dq, dk, dv) = _fetch(*prog[:3], feeds, *prog[3:], cot)
        want, vjp = jax.vjp(lambda q, k, v: fa._attn_reference(
            q, k, v, feeds["mask"], d ** -0.5, layout="bshd", causal=True),
            feeds["q"], feeds["k"], feeds["v"])
        rq, rk, rv = vjp(cot)
    _close(out, want, 1e-5)
    for got, ref_g in ((dq, rq), (dk, rk), (dv, rv)):
        _close(got, ref_g, 1e-5)
    grad_op, = [op for op in prog[0].global_block().ops
                if op.type == "fused_attention_grad"]
    assert not (grad_op.output("BiasQK@GRAD") or [""])[0]
    took = kreg.dispatch_stats()["per_kernel"]["flash_attention"]
    assert took.get("fused_bwd") and not took.get("split_bwd")


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


def _train(sz, tr, seed, amp, steps=3, fam=family):
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    from paddle_tpu import models
    with fluid.program_guard(main, startup):
        cost, _, _ = models.decoder_lm_train(fam.model_config(sz))
        opt = fluid.optimizer.AdamOptimizer(
            learning_rate=sz["learning_rate"], beta1=sz["adam_beta1"],
            beta2=sz["adam_beta2"], epsilon=sz["adam_epsilon"])
        if amp:
            opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(cost)
    scope = Scope()
    pool = fam.make_pool(sz, tr, seed)
    names = fam.param_names(sz)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for n, a in fam.init_params(sz, seed).items():
            scope.find_var(n).set_value(a)
        get = lambda n: scope.find_var(n).get_value()
        losses = [float(np.asarray(exe.run(
            main, feed=pool[0], fetch_list=[cost])[0]))]
        grads = fam.read_first_gradient_norms(get, names, sz)
        kept = scope.find_var("sparse_attn_kept")
        kept = None if kept is None else np.asarray(kept.get_value())
        losses += [float(np.asarray(exe.run(
            main, feed=pool[i], fetch_list=[cost])[0]))
            for i in range(1, steps)]
        delta = fam.read_delta_norms(get, names, sz, seed)
    return {"losses": losses, "grad_norms": grads, "delta_norms": delta,
            "kept_after_one_step": kept, "state": get}


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


def _gqa_sizes(**over):
    import json
    import os
    from benchmark.lib import cells
    with open(os.path.join(cells.BENCH, "configs",
                           "keye_vl2_30b_a3b.json")) as f:
        return dict(gqa_family.sizes(json.load(f), rehearsal=True), **over)


@pytest.mark.parametrize("path", ["lowered", "kernels"])
def test_sparse_attention_model_trains_like_its_reference(path, request):
    """The same model file, told by its configuration's keys to build
    grouped-query attention under a learned sparse index and a softmax
    router: losses, every leaf's first gradient and every leaf's change
    after three Adam steps against the plain reference; the indexer's
    buffers as the seed drew them; the counter equal to the reference's
    own count of kept pairs."""
    over = {}
    if path == "kernels":
        request.getfixturevalue("interp")
        over = dict(head_dim=128)      # one head a lane block
    sz = _gqa_sizes(**over)
    tr = gqa_family.traffic({"pool": 3, "reference_rows_per_block": 1},
                            True)
    fam = gqa_family
    with jax.default_matmul_precision("highest"):
        got = _train(sz, tr, 7, amp=False, fam=fam)
        want = fam.run_reference(sz, tr, fam.make_pool(sz, tr, 7), 7, 3)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=2e-6)
    assert set(got["grad_norms"]) == set(want["grad_norms"]) \
        == set(gqa_ref.trainable_names(sz))
    for n, w in want["grad_norms"].items():
        assert abs(got["grad_norms"][n] - w) <= 2e-4 * max(w, 1e-6), n
    for n, w in want["delta_norms"].items():
        assert abs(got["delta_norms"][n] - w) <= 5e-3 * w, n
    # the indexer's buffers: in the program, not trained, not moved
    buffers = [n for n in fam.param_names(sz) if gqa_ref.is_buffer(n)]
    assert len(buffers) == 5 * sz["num_hidden_layers"]
    seeded = fam.init_params(sz, 7)
    for n in buffers:
        assert (np.asarray(got["state"](n)) == np.asarray(seeded[n])).all()
        assert n not in got["grad_norms"]
    # the counter holds the LAST step's kept pairs a layer (overwritten,
    # not added up): at most every causal pair, at least the count by
    # hand; after one step it is the reference's own count
    kept = fam.kept_pairs(sz)
    causal = fam.causal_pairs(tr)
    by_hand = gqa_ref.kept_pairs_by_hand(tr["batch"], tr["seq_len"],
                                         sz["index_topk"])
    assert kept.shape == (sz["num_hidden_layers"],)
    assert ((by_hand <= kept) & (kept < causal)).all()
    assert got["kept_after_one_step"].tolist() == \
        np.asarray(want["first_kept"]).tolist()
    assert fam.expert_load(sz).shape == (sz["num_hidden_layers"],
                                         sz["experts_held"])
    if path == "kernels":
        stats = kreg.dispatch_stats()["per_kernel"]
        assert stats["moe_grouped_matmul"].get("custom")
        assert stats["sparse_index_scores"].get("custom")
        assert stats["flash_attention"].get("custom")
        assert stats["flash_attention"].get("fused_bwd")
        assert not stats["flash_attention"].get("split_bwd")


def test_the_two_models_are_chosen_by_their_published_keys():
    """One model file: latent attention, a sigmoid router with its bias,
    a shared expert and a dense first layer from the one key family;
    grouped queries, the index, a softmax router without bias and
    experts in every layer from the other. kanana's program op for op
    as PR 32 built it."""
    from paddle_tpu import models

    def ops(cfg):
        fluid.framework.unique_name.reset()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            models.decoder_lm_train(cfg)
        return main, [op.type for op in main.global_block().ops]

    main, latent = ops(family.model_config(_model_sizes()))
    layer = ["rms_norm", "mul", "reshape2", "rotary_embedding", "mul",
             "split", "rms_norm", "mul", "reshape2", "split", "reshape2",
             "rotary_embedding", "expand", "concat", "fused_attention",
             "reshape2", "mul", "elementwise_add", "rms_norm"]
    assert latent[:1 + len(layer)] == ["lookup_table"] + layer
    assert latent.count("moe_router") == 2 and "layer_norm" not in latent
    assert "sparse_attention_index" not in latent
    router = [op for op in main.global_block().ops
              if op.type == "moe_router"][0]
    assert router.attr("scoring_func") == "sigmoid" and router.input("Bias")
    assert "layer_0_mlp_gate.w_0" in {p.name for p in main.all_parameters()}

    main, sparse = ops(gqa_family.model_config(_gqa_sizes()))
    assert sparse.count("sparse_attention_index") == 2
    assert sparse.count("moe_router") == 2 and "split" not in sparse
    router = [op for op in main.global_block().ops
              if op.type == "moe_router"][0]
    assert router.attr("scoring_func") == "softmax"
    assert not ("Bias" in router.input_slots() and router.input("Bias"))
    names = {p.name for p in main.all_parameters()}
    assert not [n for n in names if "_shared_" in n or "_mlp_" in n]
    attn, = [op for op in main.global_block().ops
             if op.type == "fused_attention"][:1]
    block = main.global_block()
    assert block.var(attn.input("K")[0]).shape[2] == 2       # 2 key heads
    assert block.var(attn.input("Q")[0]).shape[2] == 4
    from paddle_tpu.core.types import dtype_to_str
    assert dtype_to_str(block.var(attn.input("BiasQK")[0]).dtype) == "int8"
    rot = [op for op in block.ops if op.type == "rotary_embedding"]
    assert rot and not any(op.attr("interleaved") for op in rot)
    buffers = [p for p in main.all_parameters() if "_attn_index_" in p.name]
    assert len(buffers) == 10 and not any(p.trainable for p in buffers)


def test_kept_pairs_reader():
    from paddle_tpu.observability import sparse_attention as sa
    scope = Scope()
    assert sa.kept_pairs(scope) is None
    scope.var(sa.KEPT_PAIRS_VAR).set_value(
        jnp.asarray([300, 330], jnp.int32))
    kept = sa.kept_pairs(scope)
    assert kept.dtype == np.int64 and kept.tolist() == [300, 330]
    assert sa.causal_pairs(2, 20) == 420
    assert sa.kept_share(kept, 2, 20) == pytest.approx(0.75)
    assert sa.kept_share(np.zeros(2), 2, 20) is None
    # 8,192 tokens, top-2,048: 43.75% of the causal pairs
    by_hand = gqa_ref.kept_pairs_by_hand(1, 8192, 2048)
    assert by_hand == 14_681_088
    assert sa.kept_share(np.asarray([by_hand]), 1, 8192) == \
        pytest.approx(0.4375, abs=1e-4)


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


def test_rows_worked_counter_adds_up_over_two_steps():
    """The model's `moe_rows_worked` after each of two steps against the
    same steps' `moe_expert_load`: a layer's tiles in use are its held
    experts' rows in whole tiles (one for an expert nobody chose), and
    the prefix it took is the ladder's first that holds them."""
    from paddle_tpu import models
    from paddle_tpu.observability import moe
    sz = _model_sizes()
    tr = family.traffic({"pool": 2, "reference_rows_per_block": 1}, True)
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup):
        cost, _, _ = models.decoder_lm_train(family.model_config(sz))
    scope, pool = Scope(), family.make_pool(sz, tr, 11)
    choices = tr["batch"] * tr["seq_len"] * sz["num_experts_per_tok"]
    ladder = gm.prefix_rows(choices, sz["experts_held"],
                            sz["router_experts"])
    assert len(ladder) == 3
    want, load = np.zeros((2, 2), np.int64), 0
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for step in range(2):
            exe.run(main, feed=pool[step], fetch_list=[cost])
            seen, load = moe.expert_load(scope) - load, moe.expert_load(scope)
            tiles = np.maximum(1, -(-seen // gm.TILE_ROWS)).sum(axis=1)
            want[:, 0] += [min(r for r in ladder if r >= n * gm.TILE_ROWS)
                           for n in tiles]
            want[:, 1] += tiles * gm.TILE_ROWS
            worked = moe.rows_worked(scope)
            assert worked.dtype == np.int64 and (worked == want).all()
    stats = moe.rows_worked_stats(worked, 2, ladder[-1])
    assert stats["worked_share_of_worst"] == pytest.approx(
        (want[:, 0] / (2 * ladder[-1])).tolist())
    assert max(stats["worked_share_of_worst"]) < 1


def test_rows_worked_reader():
    from paddle_tpu.observability import moe
    scope = Scope()
    assert moe.rows_worked(scope) is None       # a program without it
    scope.var(moe.ROWS_WORKED_VAR).set_value(
        jnp.asarray([[2048, 1024], [3072, 1536]], jnp.int32))
    worked = moe.rows_worked(scope)
    assert worked.dtype == np.int64 and worked.shape == (2, 2)
    stats = moe.rows_worked_stats(worked, steps=2, worst_rows=2048)
    assert stats["worked_share_of_worst"] == pytest.approx([0.5, 0.75])
    assert stats["worked_over_in_use"] == pytest.approx([2.0, 2.0])
    assert moe.rows_worked_stats(np.zeros((2, 2)), 2, 2048) is None


# ------------------------------------------- the ungated (relu^2) expert

def _ungated_reference(c, first):
    ar = ref.base._Arithmetic("f32")

    def f(x, weight, wu, wd):
        return hybrid_ref.routed_experts(ar, x, jnp.asarray(c["choice"]),
                                         weight, wu, wd, first)
    args = (c["x"], c["weight"], c["wu"], c["wd"])
    return f(*args), jax.grad(lambda *a: jnp.sum(f(*a) * c["cot"]),
                              (0, 1, 2, 3))(*args)


@pytest.mark.parametrize("path", ["lowered", "kernels"])
def test_ungated_experts_forward_and_grad(path, request):
    """activation "relu2": down(relu(up(x))^2), two matrices, no gate
    and no GateAct, against the plain loop over the held experts."""
    kreg.reset_stats()
    if path == "kernels":
        request.getfixturevalue("interp")
    c = _experts_case(150, 16, 8, 4, _random_choice(150, 3, 12, 21))
    with jax.default_matmul_precision("highest"):
        out, grads = _experts_program(c, 12, 4, 4, activation="relu2")
        r_out, r_grads = _ungated_reference(c, 4)
    _close(out, r_out)
    assert len(grads) == 4
    for got, want in zip(grads, r_grads):
        _close(got, want)
    routed = kreg.dispatch_stats()["per_kernel"].get(
        "moe_grouped_matmul", {})
    assert bool(routed.get("custom")) == (path == "kernels")


def test_ungated_experts_op_has_no_gate_slots():
    c = _experts_case(32, 16, 8, 4, _random_choice(32, 2, 8, 22))
    main, *_ = _run(
        lambda x, choice, weight: layers.moe_experts(
            x, choice, weight, 8, 8, experts_held=4, activation="relu2",
            up_attr=fluid.ParamAttr(name="wu"),
            down_attr=fluid.ParamAttr(name="wd")),
        {"x": c["x"], "choice": c["choice"], "weight": c["weight"]},
        ["x", "wu", "wd"])
    op, = [o for o in main.global_block().ops if o.type == "moe_experts"]
    assert "WGate" not in op.input_slots()
    assert "GateAct" not in op.output_slots()
    assert op.attr("activation") == "relu2"
    assert "wg" not in {p.name for p in main.all_parameters()}
    with pytest.raises(ValueError):
        layers.moe_experts(None, None, None, 8, 8, activation="gelu")


def test_sixteen_ungated_shares_add_up_to_the_uncut_layer():
    """32 sigmoid-routed ungated experts, top-6, over 16 shares of 2: the
    routed outputs of all sixteen shares plus the shared expert counted
    ONCE equal the uncut reference layer (each share given the router's
    choices over all 32, as every chip computes them alike)."""
    t, d, f, e = 96, 16, 8, 32
    sz = dict(num_experts_per_tok=6, norm_topk_prob=True,
              routed_scaling_factor=2.5, first_expert=0)
    y = _r((t, d), 90)
    p = {"router.w_0": _r((e, d), 91, 0.3),
         "router.b_0": np.zeros((e,), np.float32),
         "experts_up.w_0": _r((e, d, f), 92, 0.3),
         "experts_down.w_0": _r((e, f, d), 93, 0.3),
         "shared_up.w_0": _r((d, 2 * f), 94, 0.3),
         "shared_down.w_0": _r((2 * f, d), 95, 0.3)}
    ar = ref.base._Arithmetic("f32")
    with jax.default_matmul_precision("highest"):
        whole, choice = hybrid_ref.moe_layer(ar, p, jnp.asarray(y), sz)
        _, weight = hybrid_ref.route(y, p["router.w_0"], p["router.b_0"],
                                     sz)
        total = np.asarray(hybrid_ref.relu2_ffn(
            ar, jnp.asarray(y), p["shared_up.w_0"],
            p["shared_down.w_0"])).copy()
        for share in range(16):
            lo = 2 * share
            c = dict(x=y, choice=np.asarray(choice),
                     weight=np.asarray(weight), cot=np.zeros_like(y),
                     wu=p["experts_up.w_0"][lo:lo + 2],
                     wd=p["experts_down.w_0"][lo:lo + 2])
            out, _ = _experts_program(c, e, 2, lo, activation="relu2")
            total += out
    _close(total, whole)


# ----------------------------------------------------- a Mamba-2 mixer's ops

def test_relu2_forward_and_grad():
    x, cot = _r((6, 10), 100), _r((6, 10), 101)
    main, scope, exe, out, grads = _run(lambda x: layers.relu2(x),
                                        {"x": x}, ["x"])
    got, (dx,) = _fetch(main, scope, exe, {"x": x}, out, grads, cot)
    _close(got, np.maximum(x, 0) ** 2)
    _close(dx, 2 * np.maximum(x, 0) * cot)


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
def test_causal_conv1d_forward_and_grad(bias):
    """Four taps, depthwise, looking back only: token t reads tokens
    t - 3 .. t, the first tokens read zeros; against the reference's
    convolution, and the output at a token does not move with a later
    token."""
    x, w, b = _r((2, 9, 6), 110), _r((6, 4), 111), _r((6,), 112)
    cot = _r((2, 9, 6), 113)
    main, scope, exe, out, grads = _run(
        lambda x: layers.causal_conv1d(
            x, 4, act="silu", param_attr=fluid.ParamAttr(name="w"),
            bias_attr=fluid.ParamAttr(name="b") if bias else False),
        {"x": x}, ["x", "w"] + (["b"] if bias else []))
    params = {"w": w, **({"b": b} if bias else {})}
    got, gs = _fetch(main, scope, exe, {"x": x}, out, grads, cot, params)

    def f(x, w, b):
        return jax.nn.silu(hybrid_ref.causal_conv(x, w, b))
    zero = b if bias else np.zeros_like(b)
    _close(got, f(x, w, zero))
    want = jax.grad(lambda *a: jnp.sum(f(*a) * cot), (0, 1, 2))(x, w, zero)
    for g, r in zip(gs, want):
        _close(g, r)
    later = x.copy()
    later[:, 5:] += 1.0
    moved, _ = _fetch(main, scope, exe, {"x": later}, out, grads, cot)
    np.testing.assert_array_equal(moved[:, :5], got[:, :5])
    assert np.abs(moved[:, 5:] - got[:, 5:]).min() > 0


@pytest.mark.parametrize("groups", [1, 4])
def test_gated_rms_norm_forward_and_grad(groups):
    """The gate goes in before the norm and the mean square is taken
    within each group: 4 groups differ from one over all channels."""
    x, z, w = _r((3, 5, 16), 120), _r((3, 5, 16), 121), _r((16,), 122)
    cot = _r((3, 5, 16), 123)
    main, scope, exe, out, grads = _run(
        lambda x, z: layers.gated_rms_norm(
            x, z, groups=groups, epsilon=1e-5,
            param_attr=fluid.ParamAttr(name="w")),
        {"x": x, "z": z}, ["x", "z", "w"])
    got, gs = _fetch(main, scope, exe, {"x": x, "z": z}, out, grads, cot,
                     {"w": w})

    def f(x, z, w):
        return hybrid_ref.gated_group_norm(x, z, w, groups, 1e-5)
    _close(got, f(x, z, w))
    for g, r in zip(gs, jax.grad(lambda *a: jnp.sum(f(*a) * cot),
                                 (0, 1, 2))(x, z, w)):
        _close(g, r, 5e-5)
    assert np.abs(np.asarray(f(x, z, w)) - np.asarray(
        hybrid_ref.gated_group_norm(x, z, w, 5 - groups, 1e-5))).max() > 1e-3


def _scan_case(b, t, h, p, g, n, seed=130):
    return dict(x=_r((b, t, h, p), seed), dt=_r((b, t, h), seed + 1) - 1.0,
                bm=_r((b, t, g, n), seed + 2), cm=_r((b, t, g, n), seed + 3),
                dt_bias=_r((h,), seed + 4, 0.5),
                a_log=np.log(np.random.default_rng(seed + 5).uniform(
                    1, 8, (h,))).astype(np.float32),
                d=_r((h,), seed + 6), cot=_r((b, t, h, p), seed + 7))


@pytest.mark.parametrize("path", ["lowered", "kernels"])
def test_mamba2_ssd_op_against_the_recurrence(path, request):
    """The scan op through Executor.run — softplus and its bias, -exp(A),
    chunks of 16 over 37 tokens (no multiple of the chunk) and B = 2 —
    and all seven gradients against the token-by-token recurrence."""
    kreg.reset_stats()
    if path == "kernels":
        request.getfixturevalue("interp")
    c = _scan_case(2, 37, 4, 8, 2, 16)
    feeds = {k: c[k] for k in ("x", "dt", "bm", "cm")}
    main, scope, exe, out, grads = _run(
        lambda x, dt, bm, cm: layers.mamba2_ssd(
            x, dt, bm, cm, chunk_size=16,
            dt_bias_attr=fluid.ParamAttr(name="dt_bias"),
            a_log_attr=fluid.ParamAttr(name="a_log"),
            d_attr=fluid.ParamAttr(name="d"))[0],
        feeds, ["x", "dt", "bm", "cm", "dt_bias", "a_log", "d"])
    params = {k: c[k] for k in ("dt_bias", "a_log", "d")}

    def f(x, dt, bm, cm, dt_bias, a_log, d):
        return hybrid_ref.recurrence(x, jax.nn.softplus(dt + dt_bias),
                                     -jnp.exp(a_log), bm, cm, d, block=37)
    args = [c[k] for k in ("x", "dt", "bm", "cm", "dt_bias", "a_log", "d")]
    with jax.default_matmul_precision("highest"):
        got, gs = _fetch(main, scope, exe, feeds, out, grads, c["cot"],
                         params)
        want = f(*args)
        r_grads = jax.grad(lambda *a: jnp.sum(f(*a) * c["cot"]),
                           tuple(range(7)))(*args)
    _close(got, want)
    for name, g, r in zip(("x", "dt", "b", "c", "dt_bias", "a_log", "d"),
                          gs, r_grads):
        assert np.abs(np.asarray(r)).max() > 0, name
        _close(g, r, 5e-5)
    routed = kreg.dispatch_stats()["per_kernel"].get("mamba2_ssd", {})
    assert bool(routed.get("custom")) == (path == "kernels")
    op, = [o for o in main.global_block().ops if o.type == "mamba2_ssd"]
    tokens = out.block.var(op.output("Tokens")[0])
    with fluid.scope_guard(scope):
        n, = exe.run(main, feed={**feeds, "cot": c["cot"]},
                     fetch_list=[tokens])
    assert np.asarray(n).tolist() == [2 * 37]


# ------------------------------------------------------ the hybrid model

def _hybrid_sizes(**over):
    import json
    import os
    from benchmark.lib import cells
    with open(os.path.join(cells.BENCH, "configs",
                           "nemotron_twotower_30b_a3b.json")) as f:
        return dict(hybrid_family.sizes(json.load(f), rehearsal=True),
                    **over)


@pytest.mark.parametrize("path", ["lowered", "kernels"])
def test_hybrid_model_trains_like_its_reference(path, request):
    """The same model file, told by `hybrid_override_pattern` to build
    one part a layer (a mixer, an expert layer, an attention without
    positions, an expert layer): losses, EVERY leaf's first gradient and
    every leaf's change after three Adam steps against the plain
    reference, whose mixer is the token-by-token recurrence; 40 tokens
    in chunks of 16, B = 2. The two counters as the model fills them."""
    over = {}
    if path == "kernels":
        request.getfixturevalue("interp")
        over = dict(head_dim=128)      # one head a lane block
    sz = _hybrid_sizes(**over)
    fam = hybrid_family
    tr = fam.traffic({"pool": 3, "reference_rows_per_block": 1}, True)
    with jax.default_matmul_precision("highest"):
        got = _train(sz, tr, 7, amp=False, fam=fam)
        want = fam.run_reference(sz, tr, fam.make_pool(sz, tr, 7), 7, 3)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=2e-6)
    assert set(got["grad_norms"]) == set(want["grad_norms"]) \
        == set(hybrid_ref.trainable_names(sz))
    for n, w in want["grad_norms"].items():
        assert w > 0, n
        assert abs(got["grad_norms"][n] - w) <= 2e-4 * max(w, 1e-6), n
    for n, w in want["delta_norms"].items():
        assert abs(got["delta_norms"][n] - w) <= 5e-3 * w, n
    # the router's correction bias: zero, in the program, never moved
    for n in fam.param_names(sz):
        if hybrid_ref.is_buffer(n):
            assert not np.asarray(got["state"](n)).any()
    assert fam.scanned_tokens(sz).tolist() == [tr["batch"] * tr["seq_len"]]
    assert fam.expert_load(sz).shape == (2, sz["experts_held"])
    if path == "kernels":
        stats = kreg.dispatch_stats()["per_kernel"]
        assert stats["mamba2_ssd"].get("custom")
        assert stats["moe_grouped_matmul"].get("custom")
        assert stats["flash_attention"].get("custom")
        assert not stats["mamba2_ssd"].get("lowered")


def test_hybrid_model_under_mixed_precision():
    sz = _hybrid_sizes()
    fam = hybrid_family
    tr = fam.traffic({"pool": 3, "reference_rows_per_block": 1}, True)
    got = _train(sz, tr, 9, amp=True, fam=fam)
    want = fam.run_reference(sz, tr, fam.make_pool(sz, tr, 9), 9, 3)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=2e-3)
    from paddle_tpu.core import amp
    assert "relu2" in amp.GRAY_OPS
    for op in ("mamba2_ssd", "gated_rms_norm", "causal_conv1d"):
        assert amp.op_mode(op) is None       # float32 inside, by the op


@pytest.mark.parametrize("char,part", [("M", "mamba"), ("E", "moe"),
                                       ("*", "attn"), ("-", "mlp")])
def test_pattern_parser_takes_each_part(char, part):
    from paddle_tpu.models import decoder_lm
    assert decoder_lm.parse_pattern(char) == [part]
    assert decoder_lm.parse_pattern("M" + char + char) == ["mamba", part,
                                                           part]


@pytest.mark.parametrize("bad", ["MEX", "", "ME M", "m"])
def test_pattern_parser_refuses_another_character(bad):
    from paddle_tpu.models import decoder_lm
    with pytest.raises(ValueError):
        decoder_lm.parse_pattern(bad)


def test_the_hybrid_is_built_one_part_a_layer():
    """`MEMEM*E-`: the op scopes and types of each one-part layer; the
    attention has no rotary and no q / k norm; the experts are ungated;
    a pattern whose length is not num_hidden_layers raises."""
    from paddle_tpu import models
    sz = _hybrid_sizes(pattern="MEMEM*E-")
    cfg = hybrid_family.model_config(sz)
    assert cfg.num_hidden_layers == 8 and cfg.moe_layers == [1, 3, 6]
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        models.decoder_lm_train(cfg)
    ops = main.global_block().ops
    types = [op.type for op in ops]
    assert types.count("mamba2_ssd") == 3
    assert types.count("causal_conv1d") == 3
    assert types.count("gated_rms_norm") == 3
    assert types.count("moe_experts") == 3
    assert types.count("fused_attention") == 1
    assert "rotary_embedding" not in types and "swiglu" not in types
    assert types.count("rms_norm") == 8 + 1        # one a layer, one final
    assert types.count("relu2") == 3 + 1           # shared experts, the MLP
    scope_of = {op.type: op.attr("op_namescope", "") for op in ops}
    assert scope_of["mamba2_ssd"].endswith("/mamba/")
    assert scope_of["moe_experts"].endswith("layer_6/moe/")
    assert scope_of["fused_attention"] == "layer_5/attn/"
    assert scope_of["relu2"] == "layer_7/mlp/"
    names = {p.name for p in main.all_parameters()}
    assert "layer_0_mixer_a_log.w_0" in names
    assert "layer_7_mlp_up.w_0" in names and "layer_7_mlp_gate.w_0" not in names
    assert not [n for n in names if "_experts_gate" in n or "q_norm" in n]
    with pytest.raises(ValueError):
        models.DecoderLMConfig(hybrid_override_pattern="ME",
                               num_hidden_layers=3, n_routed_experts=8,
                               mamba_num_heads=4, mamba_head_dim=8)


_PARENT_PROGRAMS = {
    # sha256 over every op of main and startup (type, inputs, outputs,
    # attributes with their name scopes) as the parent of PR 35 built
    # them, with Adam: (configuration, rehearsal sizes) -> (ops, digest)
    ("kanana2_30b_a3b", True): (435, "e3754e0f5d66484da5e01f0706160a6b9afb"
                                "171f045f0b940c971864a445f543"),
    ("kanana2_30b_a3b", False): (721, "12e306a7b1fa2ceaecf431db803618ca939b"
                                 "ac00303b74d523c8ac895c690e1e"),
    ("keye_vl2_30b_a3b", True): (298, "96dc911cc5d2af6876e3dad503bd3dc84735"
                                 "553cd6aedcc4830ca0d607205aae"),
    ("keye_vl2_30b_a3b", False): (556, "5c4db69f75a2558857703e110ef2ec83ffe0"
                                  "e40f649c6c9aae0f9b2fefe8640e"),
    # as the parent of PR 39 built them
    ("nemotron_twotower_30b_a3b", True): (
        285, "4b5d44467e17f55735401eb7631b839131c3d459326b300a5cd6ecfad5168c35"),
    ("nemotron_twotower_30b_a3b", False): (
        502, "f7097b3dba5720c46ae765433eeae362b9ecf0e77f2776e30ad8a5096ca1922f"),
    # as the parent of PR 41 built them
    ("lfm2_24b_a2b", True): (
        451, "469421ddaecf07b2aa66ceff28e40f202bbe52deca2618b8adc8f9fc9fdc40e5"),
    ("lfm2_24b_a2b", False): (
        451, "545bc4d6687dfa6d6f04e97c530d9afb7c2a81c90c386450043ccddf5cff2697"),
    # as the commit before the decoder-hybrid-decoder layers built them
    ("mellum2_12b_a2p5b", True): (
        432, "0778881731bce8c8b1e0fcd09318ee9e3028f2df7d2e11b75e4b2cd877600575"),
    ("mellum2_12b_a2p5b", False): (
        432, "bf2c6b18b958aee94e9a4eadf28d27376a06ec77cfbbaf7ade5d32955cba9551"),
}


@pytest.mark.parametrize("config,rehearsal", sorted(_PARENT_PROGRAMS))
def test_accepted_decoder_programs_are_op_for_op_the_parents(config,
                                                             rehearsal):
    """The model file grew a second kind of layer, then a third, then
    window layers and YaRN, then a decoder-hybrid-decoder's layers,
    LayerNorm and differential attention; the five accepted decoder
    configurations still build the parent's programs, op for op: types,
    inputs, outputs and every attribute (name scopes among them; no
    router op gained a `norm_epsilon`, no attention a `window`, no
    rotary a `yarn`, no norm became a LayerNorm), at
    the rehearsal's sizes and at the cell's."""
    import hashlib
    import json
    import os
    from benchmark.lib import cells
    from paddle_tpu import models
    fam = {"kanana2_30b_a3b": family, "keye_vl2_30b_a3b": gqa_family,
           "nemotron_twotower_30b_a3b": hybrid_family,
           "lfm2_24b_a2b": conv_family,
           "mellum2_12b_a2p5b": swa_family}[config]
    with open(os.path.join(cells.BENCH, "configs", config + ".json")) as f:
        sz = fam.sizes(json.load(f), rehearsal=rehearsal)
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cost, _, _ = models.decoder_lm_train(fam.model_config(sz))
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(cost)
    lines = []
    for prog in (main, startup):
        for op in prog.global_block().ops:
            attrs = {k: repr(v) for k, v in sorted(dict(
                op._all_attrs()).items())
                if k not in ("op_callstack", "op_uid")
                and not k.startswith("__")}
            lines.append(json.dumps(
                [op.type,
                 {s: op.input(s) for s in sorted(op.input_slots())},
                 {s: op.output(s) for s in sorted(op.output_slots())},
                 attrs], sort_keys=True))
    assert (len(lines), hashlib.sha256("\n".join(lines).encode())
            .hexdigest()) == _PARENT_PROGRAMS[(config, rehearsal)]


@pytest.mark.parametrize("key,value", [
    ("moe_latent_size", 1024), ("num_nextn_predict_layers", 1),
    ("sliding_window", 4096),
    ("layer_types", ["full_attention", "chunked_attention"]),
    ("layer_types", ["conv", "linear_attention"]), ("conv_bias", True),
    ("mlp_layer_types", ["sparse", "sparse", "moe", "dense"]),
    ("use_differential_attention", True)])
def test_keys_that_change_a_layers_equations_raise_by_name(key, value):
    """`DecoderLMConfig` swallowed every key it did not know: a file
    with experts in a compressed latent, multi-token heads, a window no
    `layer_types` places, a per-layer attention type the model file does
    not build (it builds "conv", "full_attention" and
    "sliding_attention"), a biased convolution or a feed-forward type it
    does not build ("sparse" and "dense") would have built some other
    model."""
    from paddle_tpu import models
    with pytest.raises(NotImplementedError, match=key):
        models.DecoderLMConfig(n_routed_experts=8, **{key: value})
    # absent, null or zero: the model that was always built
    quiet = {"sliding_window": None, "layer_types": None,
             "moe_latent_size": None, "num_nextn_predict_layers": 0,
             "conv_bias": False, "mlp_layer_types": None,
             "use_differential_attention": False}
    models.DecoderLMConfig(n_routed_experts=8, **{key: quiet[key]},
                           max_position_embeddings=4096, model_type="any")


@pytest.mark.parametrize("config,fam", [
    ("kanana2_30b_a3b", family), ("keye_vl2_30b_a3b", gqa_family),
    ("nemotron_twotower_30b_a3b", hybrid_family),
    ("lfm2_24b_a2b", conv_family)])
def test_configuration_files_load_whole(config, fam):
    """Every published key of a configuration file handed to the model
    file at once (what `**unused` is for), the harness's own groups
    left out: none of the three raises, `sliding_window: null` and
    `layer_types` absent among them."""
    import json
    import os
    from benchmark.lib import cells
    from paddle_tpu import models
    with open(os.path.join(cells.BENCH, "configs", config + ".json")) as f:
        raw = json.load(f)
    keys = {k: v for k, v in raw.items()
            if k not in ("name", "family", "source", "deployment",
                         "reduced", "assumed", "time_step_limit")}
    cfg = models.DecoderLMConfig(**keys)
    assert cfg.hidden_size == raw["hidden_size"]
    assert (cfg.parts is not None) == (config.startswith("nemotron"))
    assert (cfg.mixers is not None) == (config.startswith("lfm2"))


def test_scanned_tokens_reader():
    from paddle_tpu.observability import mamba
    scope = Scope()
    assert mamba.scanned_tokens(scope) is None
    scope.var(mamba.SSD_TOKENS_VAR).set_value(
        jnp.asarray([4096, 4096, 4096], jnp.int32))
    got = mamba.scanned_tokens(scope)
    assert got.dtype == np.int64 and got.tolist() == [4096] * 3


# ------------------------------------- gated short convolution, LFM2

def test_gated_short_conv_op_forward_and_grad():
    """Cg * conv3(Bg * x) through Executor.run against the reference's
    own function and `jax.grad` of it: the output, dX over all three
    thirds and dWeight; a token's output does not move with a later
    token (2e-5: float32 on both sides, sums of three products)."""
    d, k = 6, 3
    x, w, cot = _r((2, 9, 3 * d), 140), _r((d, k), 141), _r((2, 9, d), 142)
    main, scope, exe, out, grads = _run(
        lambda x: layers.gated_short_conv(
            x, k, param_attr=fluid.ParamAttr(name="w"))[0],
        {"x": x}, ["x", "w"])
    got, gs = _fetch(main, scope, exe, {"x": x}, out, grads, cot, {"w": w})
    _close(got, conv_ref.gated_conv(x, w))
    want = jax.grad(lambda x, w: jnp.sum(conv_ref.gated_conv(x, w) * cot),
                    (0, 1))(x, w)
    for g, r in zip(gs, want):
        _close(g, r)
    later = x.copy()
    later[:, 5:] += 1.0
    moved, _ = _fetch(main, scope, exe, {"x": later}, out, grads, cot)
    np.testing.assert_array_equal(moved[:, :5], got[:, :5])
    assert np.abs(moved[:, 5:] - got[:, 5:]).min() > 0
    assert [op.type for op in main.global_block().ops].count(
        "gated_short_conv_grad") == 1


@pytest.mark.parametrize("shape", [(2, 296, 64, 3, 32), (1, 40, 128, 3, 256),
                                   (2, 100, 32, 4, 16)],
                         ids=["across_blocks", "one_short_block",
                              "four_taps"])
def test_short_conv_kernels_equal_their_lowering(shape):
    """`gated_short_conv_fwd` / `_bwd` under the interpreter against the
    `jax.numpy` lowering and its `jax.vjp`, and that against `jax.grad`
    of the reference's function: sequences that are no multiple of the
    block (296 and 100 tokens in blocks of 32 and 16: the halo crosses
    nine and six block boundaries; 40 tokens in one padded block), float32
    (5e-6 relative: the same products summed in another order)."""
    from paddle_tpu.kernels import short_conv as sc
    b, t, d, k, rows = shape
    x, w, g = _r((b, t, 3 * d), 150), _r((d, k), 151), _r((b, t, d), 152)
    low = sc._lowered(x, w)
    _close(low, conv_ref.gated_conv(x, w), 2e-6)
    _close(sc._fwd_call(x, w, rows=rows), low, 5e-6)
    want = jax.grad(lambda x, w: jnp.sum(conv_ref.gated_conv(x, w) * g),
                    (0, 1))(x, w)
    for got in (sc._lowered_grad(x, w, g), sc._bwd_call(x, w, g, rows=rows)):
        for a, r in zip(got, want):
            _close(a, r, 5e-6)


def test_short_conv_routes_by_the_registry(interp, monkeypatch):
    from paddle_tpu.kernels import short_conv as sc
    assert "gated_short_conv" in kreg.kernel_names()
    x, w = jnp.zeros((1, 32, 3 * 8), jnp.bfloat16), jnp.zeros((8, 3))
    assert sc.use_kernels(x, w)
    assert not sc.use_kernels(x.astype(jnp.int32), w)
    monkeypatch.setenv("PT_KERNEL_DENY", "gated_short_conv")
    assert not sc.use_kernels(x, w)
    assert kreg.dispatch_stats()["per_kernel"]["gated_short_conv"] == {
        "custom": 1, "lowered": 1, "denied": 1}


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_kernels_take_packed_heads_over_shared_key_heads(causal,
                                                               interp):
    """8 query heads over 2 key / value heads of 64: two query heads a
    lane block, both reading ONE key head, a half of a key lane block
    that the grid step picks. Forward and the fused backward against the
    composed path (k and v repeated there); dk and dv leave at 2 heads
    (2e-6 of the largest element: float32 both sides)."""
    b, s, h, hkv, d = 2, 256, 8, 2, 64
    q, k, v = _r((b, s, h, d), 160), _r((b, s, hkv, d), 161), \
        _r((b, s, hkv, d), 162)
    plan = fa._Plan("bshd", b, h, s, s, d, 128, 128, d, hkv)
    assert plan.hpb == 2 and plan.group == 4 and plan.packed_shared
    assert fa._kernel_ok(jnp.asarray(q), jnp.asarray(k), 128, 128, "bshd",
                         jnp.asarray(v))
    # three key heads under six query heads: a lane block would straddle
    # two key heads' groups, which the kernels do not do
    assert not fa._kernel_ok(jnp.zeros((b, s, 6, d)), jnp.zeros((b, s, 3, d)),
                             128, 128, "bshd")
    sc = d ** -0.5

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, None, sc, 128, 128, "bshd",
                                  causal)

    def composed(q, k, v):
        return fa._attn_reference(q, k, v, None, sc, layout="bshd",
                                  causal=causal)
    with jax.default_matmul_precision("highest"):
        _close(kernel(q, k, v), composed(q, k, v), 2e-6)
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) ** 2), (0, 1, 2))(
            q, k, v)
        want = jax.grad(lambda *a: jnp.sum(composed(*a) ** 2), (0, 1, 2))(
            q, k, v)
    for a, r in zip(got, want):
        assert a.shape == r.shape
        _close(a, r, 5e-6)
    assert kreg.dispatch_stats()["per_kernel"]["flash_attention"][
        "fused_bwd"] >= 1


@pytest.mark.parametrize("site,digest", [
    # as PR 42 traced them (the forward on its transposed tile)
    ((8, 8, 64), "2b5ea94e3c35a45878c99c8052f6733823adba8138dd24f1faed9fcdc3"
                 "62d4de"),
    ((8, 2, 128), "93adeafa43539e4ba0f80e9a920a8f92f9f88499bfe7deed43b14bc9"
                  "a714611d"),
    ((32, 4, 128), "38a38ac9068e225707fd2f23d1831e27d37112cbb7182ad63bc2227e"
                   "70ffe786"),
    ((8, 2, 64), "607fcb065b3b4ee23ca6b18ec5e650b4ebceb961b2fe506a9f7ac4324e"
                 "9d4eb2")],
    ids=["packed_ungrouped", "grouped_at_128", "grouped_32_over_4",
         "packed_over_shared_key_heads"])
def test_unchanged_flash_sites_trace_to_the_parents_kernels(site, digest,
                                                            interp):
    """The sites the accepted cells have — packed heads with as many key
    heads (tbase_s4096), fewer key heads at one head a lane block
    (keye2_s8192 at 32 over 4, twotower_s4096), packed heads over shared
    key heads (lfm2_s8192) — trace to the jaxpr PR 42 left, kernel bodies
    included (sha256 of the text, source positions taken out). PR 40
    changed the bodies (the lse crosses one number a row) and renewed the
    digests on a record that Out, lse, dQ, dK and dV stayed the same bits
    on the chip (PERF.md §6, PR 40); PR 42 changed the forward's body (its
    tile transposed: the same sums in another order) and renewed them on
    a record of how far Out and lse moved on the chip (PERF.md §6, PR
    42): a PR that means to leave these sites alone keeps the digests,
    one that changes them brings such a record."""
    import hashlib
    import re
    h, hkv, d = site
    q = jnp.zeros((1, 256, h, d), jnp.float32)
    k = jnp.zeros((1, 256, hkv, d), jnp.float32)
    assert fa._Plan("bshd", 1, h, 256, 256, d, 128, 128, d,
                    hkv).packed_shared == (site == (8, 2, 64))

    def f(q, k, v, g):
        out, lse = fa._fa_forward(q, k, v, None, d ** -0.5, 128, 128,
                                  return_lse=True, layout="bshd",
                                  causal=True)
        return fa._fa_backward(q, k, v, None, out, lse, g, d ** -0.5, 128,
                               128, layout="bshd", causal=True)[:3]
    text = re.sub(r" at \S+:\d+", "", str(jax.make_jaxpr(f)(q, k, k, q)))
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def _lfm2_sizes(**over):
    import json
    import os
    from benchmark.lib import cells
    with open(os.path.join(cells.BENCH, "configs",
                           "lfm2_24b_a2b.json")) as f:
        return dict(conv_family.sizes(json.load(f), rehearsal=True), **over)


@pytest.mark.parametrize("path", ["lowered", "kernels"])
def test_conv_attention_model_trains_like_its_reference(path, request):
    """The same model file, told by `layer_types` to build convolution
    and attention layers (conv, attention, conv, conv, conv; one leading
    dense layer; a tied head), against the plain reference in float32:
    losses (2e-6), EVERY leaf's first gradient (2e-4 of its norm) and
    every leaf's change after three Adam steps (5e-3: Adam divides by
    the gradient's own size, so a leaf's rounding is not damped). The
    expert bias is a NONZERO seeded buffer (std 0.1 against sigmoid
    scores that differ by less): it decides the choice and never the
    weights, is in the program, and never moves. With the kernels: 8
    query heads over 2 key heads of 64 (packed and shared), the
    convolution kernels and the grouped matmuls, all interpreted."""
    over = dict(expert_bias_std=0.1)
    if path == "kernels":
        request.getfixturevalue("interp")
        over.update(num_attention_heads=8, num_key_value_heads=2,
                    head_dim=64)
    sz = _lfm2_sizes(**over)
    fam = conv_family
    tr = fam.traffic({"pool": 3, "reference_rows_per_block": 1}, True)
    with jax.default_matmul_precision("highest"):
        got = _train(sz, tr, 7, amp=False, fam=fam)
        want = fam.run_reference(sz, tr, fam.make_pool(sz, tr, 7), 7, 3)
        biased = fam.run_reference(sz, tr, fam.make_pool(sz, tr, 7), 7, 1,
                                   fault="bias_in_weights")
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=2e-6)
    assert set(got["grad_norms"]) == set(want["grad_norms"]) \
        == set(conv_ref.trainable_names(sz))
    assert "lm_head.w_0" not in got["grad_norms"]      # one tied table
    for n, w in want["grad_norms"].items():
        assert w > 0, n
        assert abs(got["grad_norms"][n] - w) <= 2e-4 * max(w, 1e-6), n
    for n, w in want["delta_norms"].items():
        assert abs(got["delta_norms"][n] - w) <= 5e-3 * w, n
    # a bias that weighed would show: the planted fault's loss is apart
    assert abs(biased["losses"][0] - want["losses"][0]) \
        > 1e-4 * want["losses"][0]
    buffers = [n for n in fam.param_names(sz) if conv_ref.is_buffer(n)]
    assert len(buffers) == 4
    params = fam.init_params(sz, 7)
    for n in buffers:
        assert np.asarray(params[n]).any()
        np.testing.assert_array_equal(got["state"](n), params[n])
    tokens = tr["batch"] * tr["seq_len"]
    assert fam.convolved_tokens(sz).tolist() == [tokens] * 4
    assert fam.expert_load(sz).shape == (4, sz["experts_held"])
    if path == "kernels":
        stats = kreg.dispatch_stats()["per_kernel"]
        assert stats["gated_short_conv"].get("custom")
        assert not stats["gated_short_conv"].get("lowered")
        assert stats["moe_grouped_matmul"].get("custom")
        assert stats["flash_attention"].get("custom")
        assert stats["flash_attention"].get("fused_bwd")


def test_conv_attention_model_under_mixed_precision():
    sz = _lfm2_sizes()
    fam = conv_family
    tr = fam.traffic({"pool": 3, "reference_rows_per_block": 1}, True)
    got = _train(sz, tr, 9, amp=True, fam=fam)
    want = fam.run_reference(sz, tr, fam.make_pool(sz, tr, 9), 9, 3)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=2e-3)
    from paddle_tpu.core import amp
    assert amp.op_mode("gated_short_conv") is None   # float32 inside


def test_the_tied_heads_gradient_is_the_sum_of_both_uses():
    """One table under the lookup and, transposed, under the head: the
    program has no `lm_head`, ONE Adam state for the table, and the
    gradient the optimizer got is the lookup's plus the head's — read
    apart from two programs that each stop one of the two paths."""
    from paddle_tpu import models
    sz = _lfm2_sizes(layers="C", num_dense_layers=1)
    fam = conv_family
    tr = fam.traffic({"pool": 1}, True)
    batch = fam.make_pool(sz, tr, 3)[0]
    params = fam.init_params(sz, 3)

    def table_grad(stop):
        fluid.framework.unique_name.reset()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            cost, _, _ = models.decoder_lm_train(fam.model_config(sz))
            block = main.global_block()
            if stop:
                op = [o for o in block.ops if o.type == stop][0]
                block.var(op.output("Out")[0]).stop_gradient = True
            table = block.var("embed_tokens.w_0")
            grad, = fluid.backward.gradients(cost, [table])
        names = {p.name for p in main.all_parameters()}
        assert "lm_head.w_0" not in names
        scope = Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for n in names:
                scope.find_var(n).set_value(params[n])
            return np.asarray(exe.run(main, feed=batch,
                                      fetch_list=[grad])[0])
    with jax.default_matmul_precision("highest"):
        both = table_grad(None)
        head_only = table_grad("lookup_table")
    rest = both - head_only              # what reached it through the lookup
    ids = np.unique(batch["input_ids"])
    absent = np.setdiff1d(np.arange(sz["vocab_held"]), ids)
    # the head reaches every row; the lookup only the rows that were fed
    assert np.abs(head_only).sum(-1).min() > 0
    assert np.abs(rest[ids]).sum(-1).min() > 0
    np.testing.assert_allclose(rest[absent], 0, atol=1e-6)
    # and Adam holds one state for it
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cost, _, _ = models.decoder_lm_train(fam.model_config(sz))
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(cost)
    moments = [v for v in main.global_block().vars
               if v.startswith("embed_tokens.w_0_moment1")]
    assert len(moments) == 1
    assert [op.type for op in main.global_block().ops].count("adam") == len(
        main.all_parameters())


def test_eight_sigmoid_shares_with_a_bias_add_up_to_the_uncut_layer():
    """16 sigmoid-routed experts, top-4 of score + bias, the weights
    without the bias over their sum + 1e-6, NO shared expert, over 8
    shares of 2: the routed outputs of all eight shares add up to the
    uncut reference layer (nothing is counted twice: there is nothing
    every chip computes alike)."""
    t, d, f, e = 96, 16, 8, 16
    sz = dict(num_experts_per_tok=4, norm_topk_prob=True, first_expert=0,
              routed_scaling_factor=1.0, router_norm_epsilon=1e-6)
    y = _r((t, d), 170)
    p = {"router.w_0": _r((e, d), 171, 0.3), "router.b_0": _r((e,), 172, 0.2),
         "experts_gate.w_0": _r((e, d, f), 173, 0.3),
         "experts_up.w_0": _r((e, d, f), 174, 0.3),
         "experts_down.w_0": _r((e, f, d), 175, 0.3)}
    ar = ref.base._Arithmetic("f32")
    with jax.default_matmul_precision("highest"):
        whole, choice = conv_ref.moe_layer(ar, p, jnp.asarray(y), sz)
        _, weight = conv_ref.route(y, p["router.w_0"], p["router.b_0"], sz)
        plain, _ = conv_ref.route(y, p["router.w_0"], None, sz)
        total = np.zeros_like(y)
        for share in range(8):
            lo = 2 * share
            c = dict(x=y, choice=np.asarray(choice),
                     weight=np.asarray(weight), cot=np.zeros_like(y),
                     wg=p["experts_gate.w_0"][lo:lo + 2],
                     wu=p["experts_up.w_0"][lo:lo + 2],
                     wd=p["experts_down.w_0"][lo:lo + 2])
            out, _ = _experts_program(c, e, 2, lo)
            total += out
    _close(total, whole)
    assert (np.asarray(plain) != np.asarray(choice)).any()   # the bias chose
    assert np.abs(np.asarray(whole)).sum(-1).min() > 0


def test_router_takes_the_normalisers_epsilon_and_a_selecting_bias():
    """`moe_router` with `norm_epsilon` 1e-6 and a nonzero bias against
    the reference's router: the same choices, the same weights (which
    sum to under one by the epsilon's share), and the default stays
    1e-20 with no attribute on the op."""
    t, d, e, k = 64, 16, 16, 4
    sz = dict(num_experts_per_tok=k, norm_topk_prob=True,
              routed_scaling_factor=1.0, router_norm_epsilon=1e-6)
    x, w, b = _r((t, d), 180), _r((e, d), 181, 0.3), _r((e,), 182, 0.2)

    def program(eps):
        fluid.framework.unique_name.reset()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            xv = layers.data("x", [t, d], append_batch_size=False)
            choice, weight, _ = layers.moe_router(
                xv, e, k, param_attr=fluid.ParamAttr(name="w"),
                bias_attr=fluid.ParamAttr(name="b"), norm_epsilon=eps)
        op = [o for o in main.global_block().ops
              if o.type == "moe_router"][0]
        scope = Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            scope.find_var("w").set_value(jnp.asarray(w))
            scope.find_var("b").set_value(jnp.asarray(b))
            got = exe.run(main, feed={"x": x}, fetch_list=[choice, weight])
        return op, np.asarray(got[0]), np.asarray(got[1])
    with jax.default_matmul_precision("highest"):
        op, choice, weight = program(1e-6)
        want_c, want_w = conv_ref.route(x, w, b, sz)
        op0, _, weight0 = program(None)
    assert op.attr("norm_epsilon") == pytest.approx(1e-6)
    assert not op0.has_attr("norm_epsilon")
    np.testing.assert_array_equal(choice, want_c)
    _close(weight, want_w, 1e-6)
    assert (1.0 - weight.sum(-1)).min() > 1e-7
    np.testing.assert_allclose(weight0.sum(-1), 1.0, atol=1e-6)


def test_convolved_tokens_reader():
    from paddle_tpu.observability import short_conv
    scope = Scope()
    assert short_conv.convolved_tokens(scope) is None
    scope.var(short_conv.SHORT_CONV_TOKENS_VAR).set_value(
        jnp.asarray([8192] * 4, jnp.int32))
    got = short_conv.convolved_tokens(scope)
    assert got.dtype == np.int64 and got.tolist() == [8192] * 4


def test_the_conv_model_is_built_from_its_published_keys():
    """`layer_types`, `num_dense_layers`, `conv_L_cache`,
    `use_expert_bias`, `rope_parameters` and the tied head, read off the
    configuration's own keys: op scopes `layer_<i>/conv`, one
    `gated_short_conv` a conv layer, the router's epsilon and bias, the
    rotary's theta, one `matmul` on the table for the head."""
    from paddle_tpu import models
    sz = _lfm2_sizes()
    cfg = conv_family.model_config(sz)
    assert cfg.mixers == ["conv", "attn", "conv", "conv", "conv"]
    assert cfg.dense_layers == {0} and cfg.moe_layers == [1, 2, 3, 4]
    assert cfg.scoring_func == "sigmoid" and cfg.router_bias
    assert cfg.router_norm_epsilon == 1e-6 and cfg.rope_theta == 1e6
    assert not cfg.rope_interleave and cfg.tie_word_embeddings
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        models.decoder_lm_train(cfg)
    ops = main.global_block().ops
    types = [op.type for op in ops]
    assert types.count("gated_short_conv") == 4
    assert types.count("fused_attention") == 1
    assert types.count("moe_experts") == 4 and types.count("swiglu") == 1
    scopes = {op.attr("op_namescope", "") for op in ops
              if op.type == "gated_short_conv"}
    assert scopes == {f"layer_{i}/conv/" for i in (0, 2, 3, 4)}
    router = [op for op in ops if op.type == "moe_router"][0]
    assert router.attr("norm_epsilon") == pytest.approx(1e-6)
    assert router.input("Bias")
    rotary = [op for op in ops if op.type == "rotary_embedding"][0]
    assert rotary.attr("theta") == 1e6 and not rotary.attr("interleaved")
    head = [op for op in ops if op.type == "matmul"]
    assert len(head) == 1 and head[0].input("Y") == ["embed_tokens.w_0"] \
        and head[0].attr("transpose_Y")
    names = {p.name for p in main.all_parameters()}
    assert "layer_0_conv.w_0" in names and "layer_1_attn_q_norm.w_0" in names
    assert "layer_0_mlp_gate.w_0" in names and "lm_head.w_0" not in names
    assert main.global_block().var("short_conv_tokens").persistable
    with pytest.raises(ValueError):
        models.DecoderLMConfig(layer_types=["conv"], num_hidden_layers=2,
                               num_experts=8)
    with pytest.raises(ValueError):
        models.DecoderLMConfig(layer_types=["conv"], num_experts=8,
                               num_dense_layers=1, first_k_dense_replace=2)


# -------------------------- window and full layers, YaRN: Mellum2

from benchmark.families import swa_gqa_moe_decoder as swa_family  # noqa: E402
from benchmark.families import swa_gqa_moe_decoder_reference as swa_ref  # noqa: E402,E501


def _mellum2_sizes(**over):
    import json
    import os
    from benchmark.lib import cells
    with open(os.path.join(cells.BENCH, "configs",
                           "mellum2_12b_a2p5b.json")) as f:
        return dict(swa_family.sizes(json.load(f), rehearsal=True), **over)


@pytest.mark.parametrize("path", ["lowered", "kernels"])
def test_window_model_trains_like_its_reference(path, request):
    """The same model file, told by `layer_types` to build two window
    layers and a full one (the band narrower than the sequence), by
    `rope_parameters` plain rotary on the first and YaRN on the last,
    softmax experts top-8 and an untied head, against the plain reference
    in float32: losses (2e-6), EVERY leaf's first gradient (2e-4 of its
    norm) and every leaf's change after three Adam steps (5e-3). The
    window one key wider (the reference's `window_off_by_one`, which no
    limit on the chip sees: it moves one key in 1,024) reads far outside
    both: that is where a band off by one is caught. With the kernels:
    4 query heads over 2 key heads of 128, 256 tokens in blocks of 128
    and a window of 100 (the band's edges inside tiles, a block the band
    never meets in the first window layer's grid), all interpreted."""
    sz = _mellum2_sizes(layers="SSF")
    tr = swa_family.traffic({"pool": 3, "reference_rows_per_block": 1},
                            True)
    if path == "kernels":
        request.getfixturevalue("interp")
        sz.update(num_attention_heads=4, num_key_value_heads=2,
                  head_dim=128, sliding_window=100)
        tr.update(seq_len=256, reference_query_rows=128)
    fam = swa_family
    with jax.default_matmul_precision("highest"):
        got = _train(sz, tr, 7, amp=False, fam=fam)
        pool = fam.make_pool(sz, tr, 7)
        want = fam.run_reference(sz, tr, pool, 7, 3)
        wider = fam.run_reference(sz, tr, pool, 7, 3,
                                  fault="window_off_by_one")
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=2e-6)
    assert set(got["grad_norms"]) == set(want["grad_norms"]) \
        == set(swa_ref.trainable_names(sz))
    worst = 0.0
    for n, w in want["grad_norms"].items():
        assert w > 0, n
        assert abs(got["grad_norms"][n] - w) <= 2e-4 * max(w, 1e-6), n
        worst = max(worst, abs(wider["grad_norms"][n] - w) / w)
    for n, w in want["delta_norms"].items():
        assert abs(got["delta_norms"][n] - w) <= 5e-3 * w, n
    assert worst > 50 * 2e-4
    assert abs(wider["losses"][0] - want["losses"][0]) \
        > 10 * 2e-6 * want["losses"][0]
    s, b, w = tr["seq_len"], tr["batch"], sz["sliding_window"]
    assert fam.window_pairs(sz).tolist() == \
        [b * fa.admitted_pairs(s, s, w)] * 2
    assert fam.expert_load(sz).shape == (3, sz["experts_held"])
    if path == "kernels":
        stats = kreg.dispatch_stats()["per_kernel"]
        assert stats["flash_attention"].get("custom")
        assert stats["flash_attention"].get("window")
        assert stats["flash_attention"].get("fused_bwd")
        assert stats["moe_grouped_matmul"].get("custom")


def test_window_model_under_mixed_precision():
    sz = _mellum2_sizes()
    tr = swa_family.traffic({"pool": 3, "reference_rows_per_block": 1},
                            True)
    got = _train(sz, tr, 9, amp=True, fam=swa_family)
    want = swa_family.run_reference(sz, tr, swa_family.make_pool(sz, tr, 9),
                                    9, 3)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=2e-3)


def test_yarn_table_matches_the_closed_form():
    """At the configuration's numbers (d 128, theta 5e5, L0 8,192, beta
    32 / 1, factor 16): the ramp runs from pair 18 to pair 35, the pairs
    below it keep theta^(-2i/d), those above take a sixteenth of it, and
    between them r / 16 + 1 - r; the program's table (ops/decoder.py),
    the reference's (its own code) and the closed form agree, and the
    attention factor the file carries is 0.1 ln 16 + 1."""
    import math
    from paddle_tpu.ops.decoder import yarn_scale
    cfg = _mellum2_sizes()["yarn"]
    assert swa_ref.yarn_bounds(128, 5e5, 8192, 32, 1) == (18, 35)
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(5e5)))
    high = math.ceil(128 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(5e5)))
    assert (low, high) == (18, 35)
    i = np.arange(64)
    r = np.clip((i - 18) / 17, 0, 1)
    closed = 5e5 ** (-2 * i / 128) * (r / 16 + 1 - r)
    got = 5e5 ** (-2 * i / 128) * yarn_scale(128, 5e5, 16, 8192, 32, 1)
    np.testing.assert_allclose(got, closed, rtol=1e-12)
    np.testing.assert_allclose(
        swa_ref.frequencies(128, 5e5, cfg), closed, rtol=1e-12)
    assert np.all(got[:19] == 5e5 ** (-2 * i[:19] / 128))
    np.testing.assert_allclose(got[35:], closed[35:], rtol=1e-12)
    np.testing.assert_allclose(got[35:] * 16, 5e5 ** (-2 * i[35:] / 128),
                               rtol=1e-12)
    assert cfg["attention_factor"] == pytest.approx(
        0.1 * math.log(16) + 1, abs=1e-15)


def test_yarn_rotary_op_forward_and_grad():
    """`rotary_embedding` with a YaRN table through Executor.run against
    the reference's own rotary (its frequency table, cos and sin times the
    attention factor): output and dX."""
    yarn = {"factor": 16.0, "original_max_position_embeddings": 64.0,
            "beta_fast": 32.0, "beta_slow": 1.0, "attention_factor": 1.27}
    x, cot = _r((2, 40, 3, 32), 200), _r((2, 40, 3, 32), 201)
    prog = _run(lambda x: layers.rotary_embedding(
        x, theta=5e5, interleaved=False, yarn=yarn), {"x": x}, ["x"])
    out, (dx,) = _fetch(*prog[:3], {"x": x}, *prog[3:], cot)
    freq = swa_ref.frequencies(32, 5e5, yarn)

    def f(x):
        return swa_ref.rope(x, freq, 1.27)
    _close(out, f(x))
    _close(dx, jax.grad(lambda x: jnp.sum(f(x) * cot))(x))
    # the blend binds at this context: some pairs are interpolated
    assert freq[-1] < 5e5 ** (-30 / 32) / 8


@pytest.mark.parametrize("where", ["rope_scaling", "rope_parameters",
                                   "by_layer_type"])
def test_rope_types_other_than_default_and_yarn_raise(where):
    from paddle_tpu import models
    bad = {"rope_type": "longrope", "rope_theta": 1e4}
    kw = {"rope_scaling": {"rope_scaling": bad},
          "rope_parameters": {"rope_parameters": bad},
          "by_layer_type": {"rope_parameters": {
              "full_attention": bad,
              "sliding_attention": {"rope_type": "default"}},
              "layer_types": ["sliding_attention", "full_attention"],
              "sliding_window": 8}}[where]
    with pytest.raises(NotImplementedError, match="longrope"):
        models.DecoderLMConfig(num_experts=8, **kw)


def test_the_window_model_is_built_from_its_published_keys():
    """`layer_types`, `sliding_window`, `rope_parameters` by layer type,
    `mlp_layer_types`, `use_qk_norm` false and the untied head, read off
    the configuration's own keys at the cell's widths: op scopes
    `layer_<i>/swa` and `layer_3/attn`, the window on three attention
    ops and not the fourth, each writing its admitted pairs into the
    `window_attn_pairs` counter; plain rotary on the window layers, YaRN
    on the full one; no q / k norm; 64 softmax-routed outputs top-8."""
    import json
    import os
    from benchmark.lib import cells
    from paddle_tpu import models
    with open(os.path.join(cells.BENCH, "configs",
                           "mellum2_12b_a2p5b.json")) as f:
        sz = swa_family.sizes(json.load(f))
    cfg = swa_family.model_config(sz)
    assert cfg.mixers == ["swa", "swa", "swa", "attn"]
    assert [cfg.window_at(i) for i in range(4)] == [1024] * 3 + [None]
    assert cfg.dense_layers == set() and cfg.moe_layers == [0, 1, 2, 3]
    assert cfg.scoring_func == "softmax" and not cfg.router_bias
    assert not cfg.qk_norm and not cfg.tie_word_embeddings
    assert cfg.rotary_at(0) == (5e5, None)
    theta, yarn = cfg.rotary_at(3)
    assert theta == 5e5 and yarn["factor"] == 16
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        models.decoder_lm_train(cfg)
    ops = main.global_block().ops
    attn = [op for op in ops if op.type == "fused_attention"]
    assert [op.attr("op_namescope", "") for op in attn] == \
        [f"layer_{i}/swa/" for i in range(3)] + ["layer_3/attn/"]
    assert [op.attr("window", None) for op in attn] == [1024] * 3 + [None]
    assert [bool(op.output("WindowPairs")) for op in attn] == \
        [True] * 3 + [False]
    rotary = [op for op in ops if op.type == "rotary_embedding"]
    assert len(rotary) == 8
    assert [op.attr("yarn", None) is not None for op in rotary] == \
        [False] * 6 + [True] * 2
    np.testing.assert_allclose(rotary[-1].attr("yarn"),
                               [16, 8192, 32, 1, 1.2772588722239782],
                               rtol=1e-7)
    router = [op for op in ops if op.type == "moe_router"][0]
    assert router.attr("top_k") == 8 and not router.input("Bias")
    names = {p.name for p in main.all_parameters()}
    assert not [n for n in names if "q_norm" in n or "k_norm" in n]
    assert "lm_head.w_0" in names
    assert main.global_block().var("window_attn_pairs").persistable
    assert swa_family.trained_parameters(sz) == 340_349_184
    with pytest.raises(ValueError, match="sliding_window"):
        models.DecoderLMConfig(num_experts=8, layer_types=[
            "sliding_attention", "full_attention"])
    # `layer_types` governs: the windows are where it places them,
    # whatever use_sliding_window / max_window_layers say
    quiet = models.DecoderLMConfig(
        num_experts=8, layer_types=["full_attention", "sliding_attention"],
        sliding_window=16, use_sliding_window=False, max_window_layers=28)
    assert [quiet.window_at(i) for i in range(2)] == [None, 16]


def test_admitted_pairs_reader():
    from paddle_tpu.observability import window_attention
    scope = Scope()
    assert window_attention.admitted_pairs(scope) is None
    scope.var(window_attention.WINDOW_PAIRS_VAR).set_value(
        jnp.asarray([7_864_832] * 3, jnp.int32))
    got = window_attention.admitted_pairs(scope)
    assert got.dtype == np.int64 and got.tolist() == [7_864_832] * 3
