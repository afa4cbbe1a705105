"""Flash-attention backward Pallas kernels (dq, dk/dv) parity vs the
composed formulation, in interpret mode (stands in for TPU — the exact
kernel path training uses on hardware). Round-2 verdict item 4: the
backward must be a kernel consuming the saved lse, not a composed
recompute that materializes [Sq, Sk] scores."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import importlib

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


@pytest.mark.parametrize("bias_mode", ["none", "per_batch", "per_head"])
def test_flash_backward_kernel_matches_composed(bias_mode):
    rng = np.random.default_rng(0)
    B, H, Sq, Sk, D = 2, 2, 128, 256, 16
    q, k, v = _rand(rng, B, H, Sq, D), _rand(rng, B, H, Sk, D), \
        _rand(rng, B, H, Sk, D)
    if bias_mode == "none":
        bias = None
    elif bias_mode == "per_batch":
        bias = _rand(rng, B, 1, Sq, Sk)
    else:
        bias = _rand(rng, B, H, Sq, Sk)
    scale = float(D) ** -0.5

    def loss_kernel(*args):
        return (fa.flash_attention(*args, scale, 128, 128) ** 2).sum()

    def loss_ref(*args):
        return (fa._attn_reference(*args, scale) ** 2).sum()

    argnums = (0, 1, 2) if bias is None else (0, 1, 2, 3)
    args = (q, k, v) if bias is None else (q, k, v, bias)
    if bias is None:
        gk = jax.grad(lambda q, k, v: loss_kernel(q, k, v, None),
                      argnums)(*args)
        gr = jax.grad(lambda q, k, v: loss_ref(q, k, v, None),
                      argnums)(*args)
    else:
        gk = jax.grad(loss_kernel, argnums)(*args)
        gr = jax.grad(loss_ref, argnums)(*args)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_lse_backward_kernel_with_lse_cotangent():
    """The lse output's cotangent must flow through the kernel backward
    (ring attention's merge arithmetic differentiates through lse)."""
    rng = np.random.default_rng(1)
    B, H, S, D = 1, 2, 128, 8
    q, k, v = (_rand(rng, B, H, S, D) for _ in range(3))
    scale = float(D) ** -0.5

    def loss_kernel(q, k, v):
        out, lse = fa.flash_attention_lse(q, k, v, None, scale, 128,
                                          128)
        return (out ** 2).sum() + (jnp.sin(lse) ** 2).sum()

    def loss_ref(q, k, v):
        out, lse = fa._attn_reference_lse(q, k, v, None, scale)
        return (out ** 2).sum() + (jnp.sin(lse) ** 2).sum()

    gk = jax.grad(loss_kernel, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("bias_mode", ["none", "per_batch"])
def test_bshd_layout_matches_bhsd(bias_mode):
    """The transpose-free [B,S,H,D] layout must produce identical
    outputs and grads to the classic [B,H,S,D] path (same kernels,
    different BlockSpec index maps)."""
    rng = np.random.default_rng(3)
    B, H, Sq, Sk, D = 2, 2, 128, 128, 16
    q, k, v = _rand(rng, B, H, Sq, D), _rand(rng, B, H, Sk, D), \
        _rand(rng, B, H, Sk, D)
    bias = None if bias_mode == "none" else _rand(rng, B, 1, Sq, Sk)
    scale = float(D) ** -0.5

    def loss_bhsd(q, k, v, bias):
        return (fa.flash_attention(q, k, v, bias, scale, 128, 128,
                                   "bhsd") ** 2).sum()

    def loss_bshd(q, k, v, bias):
        out = fa.flash_attention(
            jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
            jnp.moveaxis(v, 1, 2), bias, scale, 128, 128, "bshd")
        return (out ** 2).sum()

    o1 = fa.flash_attention(q, k, v, bias, scale, 128, 128, "bhsd")
    o2 = fa.flash_attention(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
        jnp.moveaxis(v, 1, 2), bias, scale, 128, 128, "bshd")
    np.testing.assert_allclose(np.asarray(o1),
                               np.asarray(jnp.moveaxis(o2, 1, 2)),
                               atol=1e-5, rtol=1e-5)
    g1 = jax.grad(loss_bhsd, (0, 1, 2))(q, k, v, bias)
    g2 = jax.grad(loss_bshd, (0, 1, 2))(q, k, v, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)
    if bias is not None:
        gb1 = jax.grad(loss_bhsd, 3)(q, k, v, bias)
        gb2 = jax.grad(loss_bshd, 3)(q, k, v, bias)
        np.testing.assert_allclose(np.asarray(gb1), np.asarray(gb2),
                                   atol=2e-4, rtol=2e-4)


def test_backward_never_materializes_scores_in_hbm():
    """Structural assertion: with the kernel path and no bias, the jitted
    backward's HLO contains no [Sq, Sk]-shaped intermediate (the O(S^2)
    score matrix) — the whole point of the flash backward."""
    B, H, S, D = 1, 1, 512, 64
    rng = np.random.default_rng(2)
    q, k, v = (_rand(rng, B, H, S, D) for _ in range(3))

    def loss(q, k, v):
        return (fa.flash_attention(q, k, v, None, 0.125, 128, 128)
                ** 2).sum()

    txt = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, v).as_text()
    assert f"{S},{S}" not in txt.replace(" ", ""), (
        "backward HLO materializes an SxS intermediate")


def test_fused_attention_op_grad_without_bias_grad():
    """The op's custom grad (ops/fused.py): with a mask bias whose
    gradient is NOT demanded, dq/dk/dv must still include the bias in
    the score recompute (kernel regime, want_dbias=False), matching the
    composed reference; and demanding the bias grad must produce it."""
    import paddle_tpu as fluid
    from paddle_tpu.core.registry import _RngCtx

    rng = np.random.default_rng(5)
    B, H, S, D = 2, 2, 128, 16
    qn = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    bn = jnp.asarray(rng.standard_normal((B, 1, S, S)), jnp.float32)

    fluid.framework.unique_name.reset()
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        block = main.global_block()
        mk = lambda n: block.create_var(name=n, dtype="float32",
                                        stop_gradient=False)
        q_v, k_v, v_v, b_v, o_v = (mk(n) for n in
                                   ("fq", "fk", "fv", "fb", "fo"))
        block.append_op(
            "fused_attention",
            inputs={"Q": q_v, "K": k_v, "V": v_v, "BiasQK": b_v},
            outputs={"Out": o_v},
            attrs={"scale": float(D) ** -0.5, "block_q": 128,
                   "block_k": 128, "layout": "bhsd"})
        fwd_op = block.ops[-1]
        # grad op desc: dbias NOT bound
        gop = block.append_op(
            "fused_attention_grad",
            inputs={"Q": q_v, "K": k_v, "V": v_v, "BiasQK": b_v,
                    "Out": o_v,
                    "Out@GRAD": block.create_var(name="fo@GRAD",
                                                 dtype="float32")},
            outputs={"Q@GRAD": mk("fq@GRAD"), "K@GRAD": mk("fk@GRAD"),
                     "V@GRAD": mk("fv@GRAD")},
            attrs=dict(fwd_op._all_attrs()))

    go = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    env = {"fq": qn, "fk": kn, "fv": vn, "fb": bn, "fo@GRAD": go}
    from paddle_tpu.core.registry import OPS, ExecContext
    OPS.get("fused_attention").lowering(
        ExecContext(fwd_op, env, _RngCtx(jax.random.PRNGKey(0))))
    OPS.get("fused_attention_grad").lowering(
        ExecContext(gop, env, _RngCtx(jax.random.PRNGKey(0))))

    def ref(q, k, v, b):
        return (fa._attn_reference(q, k, v, b, float(D) ** -0.5)
                * go).sum()

    gq, gk, gv, gb = jax.grad(ref, (0, 1, 2, 3))(qn, kn, vn, bn)
    np.testing.assert_allclose(np.asarray(env["fq@GRAD"]),
                               np.asarray(gq), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(env["fk@GRAD"]),
                               np.asarray(gk), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(env["fv@GRAD"]),
                               np.asarray(gv), atol=2e-4, rtol=2e-4)
    assert "fb@GRAD" not in env  # dbias suppressed

    # now DEMAND the bias grad through the same custom lowering
    with fluid.program_guard(main, fluid.Program()):
        block = main.global_block()
        gop2 = block.append_op(
            "fused_attention_grad",
            inputs={"Q": q_v, "K": k_v, "V": v_v, "BiasQK": b_v,
                    "Out": o_v,
                    "Out@GRAD": block.var("fo@GRAD")},
            outputs={"Q@GRAD": block.var("fq@GRAD"),
                     "BiasQK@GRAD": block.create_var(
                         name="fb@GRAD", dtype="float32",
                         stop_gradient=False)},
            attrs=dict(fwd_op._all_attrs()))
    OPS.get("fused_attention_grad").lowering(
        ExecContext(gop2, env, _RngCtx(jax.random.PRNGKey(0))))
    np.testing.assert_allclose(np.asarray(env["fb@GRAD"]),
                               np.asarray(gb), atol=2e-4, rtol=2e-4)

# ---------------------------------------------------------------------------
# round 5: causal block-skipping + in-kernel attention-weights dropout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
@pytest.mark.parametrize("bias_mode", ["none", "padding"])
def test_causal_kernel_matches_composed(layout, bias_mode):
    """causal=True must equal the composed formulation with an explicit
    lower-triangle mask — including fully-masked block skipping (S=384,
    blocks=128 -> 3x3 blocks, 3 of them strictly above the diagonal)."""
    rng = np.random.default_rng(7)
    B, H, S, D = 2, 2, 384, 16
    q, k, v = (_rand(rng, B, H, S, D) for _ in range(3))
    # padding bias: key-padding-only [B, 1, 1, S] (the transformer's
    # fused-path trg_bias shape)
    bias = None
    if bias_mode == "padding":
        pad = np.zeros((B, 1, 1, S), np.float32)
        pad[:, :, :, -32:] = -1e9
        bias = jnp.asarray(pad)
    scale = float(D) ** -0.5

    def kern(q, k, v, bias):
        if layout == "bshd":
            out = fa.flash_attention(
                jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                jnp.moveaxis(v, 1, 2), bias, scale, 128, 128,
                "bshd", True)
            return jnp.moveaxis(out, 1, 2)
        return fa.flash_attention(q, k, v, bias, scale, 128, 128,
                                  "bhsd", True)

    def ref(q, k, v, bias):
        return fa._attn_reference(q, k, v, bias, scale, causal=True)

    np.testing.assert_allclose(np.asarray(kern(q, k, v, bias)),
                               np.asarray(ref(q, k, v, bias)),
                               atol=1e-5, rtol=1e-5)
    gk = jax.grad(lambda *a: (kern(*a) ** 2).sum(), (0, 1, 2))(
        q, k, v, bias)
    gr = jax.grad(lambda *a: (ref(*a) ** 2).sum(), (0, 1, 2))(
        q, k, v, bias)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_causal_dbias_zero_store():
    """want_dbias + causal: the ds output tiles of SKIPPED blocks must
    be zeroed (never written by the main body), so dbias sums clean."""
    rng = np.random.default_rng(8)
    B, H, S, D = 1, 2, 384, 16
    q, k, v = (_rand(rng, B, H, S, D) for _ in range(3))
    bias = _rand(rng, B, 1, S, S) * 0.1
    scale = float(D) ** -0.5
    g = _rand(rng, B, H, S, D)

    out, lse = fa._fa_forward(q, k, v, bias, scale, 128, 128,
                              return_lse=True, causal=True)
    dq, dk, dv, dbias = fa._fa_backward(
        q, k, v, bias, out, lse, g, scale, 128, 128, want_dbias=True, causal=True)

    def ref(q, k, v, bias):
        return (fa._attn_reference(q, k, v, bias, scale, causal=True)
                * g).sum()

    rq, rk, rv, rb = jax.grad(ref, (0, 1, 2, 3))(q, k, v, bias)
    for a, b in ((dq, rq), (dk, rk), (dv, rv), (dbias, rb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
@pytest.mark.parametrize("causal", [False, True])
def test_dropout_kernel_fwd_bwd_consistent(layout, causal):
    """In-kernel attention-weights dropout: the kernel out/grads must
    equal a composed formulation using the EXACT mask the interpret-
    mode kernels realize (dropout_keep_mask reconstructs it), proving
    the fwd and both bwd kernels regenerate identical bits and the
    chain rule through p_drop = keep * p * 256/t is right."""
    rng = np.random.default_rng(9)
    B, H, S, D = 2, 2, 256, 16
    qb, kb, vb = (_rand(rng, B, H, S, D) for _ in range(3))
    scale = float(D) ** -0.5
    key = jax.random.PRNGKey(42)
    t = 205                      # keep ~80%
    g = _rand(rng, B, H, S, D)
    keep = fa.dropout_keep_mask(
        jax.lax.bitcast_convert_type(key, jnp.int32).reshape(2),
        B, H, S, S, t)
    assert 0.72 < float(keep.mean()) < 0.88  # mask is sane

    def to_layout(x):
        return jnp.moveaxis(x, 1, 2) if layout == "bshd" else x

    q, k, v = to_layout(qb), to_layout(kb), to_layout(vb)
    out, lse = fa._fa_forward(q, k, v, None, scale, 128, 128,
                              return_lse=True, layout=layout, causal=causal,
                              dropout=(key, t))
    dq, dk, dv, _ = fa._fa_backward(
        q, k, v, None, out, lse, g if layout == "bhsd"
        else jnp.moveaxis(g, 1, 2), scale, 128, 128, layout=layout,
        causal=causal, dropout=(key, t))

    def ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jnp.arange(S)[:, None]
            cols = jnp.arange(S)[None, :]
            s = jnp.where(rows >= cols, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(keep, p * (256.0 / t), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    out_b = out if layout == "bhsd" else jnp.moveaxis(out, 1, 2)
    np.testing.assert_allclose(np.asarray(out_b),
                               np.asarray(ref(qb, kb, vb)),
                               atol=1e-4, rtol=1e-4)
    rq, rk, rv = jax.grad(lambda *a: (ref(*a) * g).sum(),
                          (0, 1, 2))(qb, kb, vb)
    for a, b in ((dq, rq), (dk, rk), (dv, rv)):
        ab = a if layout == "bhsd" else jnp.moveaxis(a, 1, 2)
        np.testing.assert_allclose(np.asarray(ab), np.asarray(b),
                                   atol=3e-4, rtol=3e-4)


def test_fused_attention_op_dropout_edges():
    """Op-level dropout edges (ADVICE r4): prob ~ 1.0 (t<=0) emits
    zeros on BOTH paths; prob ~ 0 (t>=256) is an exact no-op."""
    import paddle_tpu as fluid
    from paddle_tpu.core.registry import OPS, ExecContext, _RngCtx

    rng = np.random.default_rng(11)
    B, H, S, D = 1, 2, 128, 16
    qn, kn, vn = (jnp.asarray(rng.standard_normal((B, H, S, D)),
                              jnp.float32) for _ in range(3))

    def run_op(prob):
        fluid.framework.unique_name.reset()
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            block = main.global_block()
            mk = lambda n: block.create_var(name=n, dtype="float32",
                                            stop_gradient=False)
            q_v, k_v, v_v, o_v = (mk(n) for n in
                                  ("dq", "dk", "dv", "do"))
            block.append_op(
                "fused_attention",
                inputs={"Q": q_v, "K": k_v, "V": v_v},
                outputs={"Out": o_v},
                attrs={"scale": float(D) ** -0.5, "block_q": 128,
                       "block_k": 128, "layout": "bhsd",
                       "dropout_prob": float(prob), "seed": 7})
            op = block.ops[-1]
        env = {"dq": qn, "dk": kn, "dv": vn}
        OPS.get("fused_attention").lowering(
            ExecContext(op, env, _RngCtx(jax.random.PRNGKey(0))))
        return env["do"]

    out_hi = run_op(0.999)       # t = round(0.001*256) = 0 -> zeros
    assert float(jnp.abs(out_hi).max()) == 0.0
    out_lo = run_op(0.001)       # t = 256 -> exact no-op
    out_none = run_op(0.0)
    np.testing.assert_array_equal(np.asarray(out_lo),
                                  np.asarray(out_none))


def test_dropout_mask_fwd_bwd_bit_identical():
    """The fwd, dq and dkv kernels must realize the SAME dropout mask.
    Here the probe runs the interpret-mode hash path; chip_smoke.py
    calls the same function on the chip, where the kernels draw from
    the hardware PRNG."""
    from paddle_tpu.kernels.parity import dropout_mask_identity
    res = dropout_mask_identity()
    assert 0.75 < res["keep_frac"] < 0.85, res
    assert res["value"] == 0, res


def test_dispatch_is_sequence_keyed(monkeypatch):
    """The kernel/composed crossover rule (measured table beside
    _KERNEL_MIN_SEQ_PRODUCT): sequence product decides, batch does
    not."""
    monkeypatch.setattr(fa, "_INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("PT_FORCE_KERNEL", raising=False)
    monkeypatch.delenv("PT_FORCE_COMPOSED", raising=False)

    def qk(B, S, H=8, D=64):
        x = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)
        return x, x

    # S=512 stays composed at ANY batch (even at the element count
    # where S=1024 wins)
    assert not fa.use_kernel_path(*qk(16, 512), 512, 512, "bshd")
    assert not fa.use_kernel_path(*qk(32, 512), 512, 512, "bshd")
    # S>=1024 takes the kernels even at small batch
    assert fa.use_kernel_path(*qk(4, 1024), 512, 1024, "bshd")
    assert fa.use_kernel_path(*qk(2, 2048), 512, 1024, "bshd")
    assert fa.use_kernel_path(*qk(4, 4096), 512, 1024, "bshd")


# ---------------------------------------------------------------------------
# PR 27: fused_attention hands Out and a narrow SoftmaxLse to its grad op;
# the backward runs no second forward kernel
# ---------------------------------------------------------------------------

_B, _H, _S, _D = 1, 2, 128, 16

# (query heads, key heads, q / k width, v width): the accepted cells'
# site forms at fewer heads
_PLAIN = (_H, _H, _D, _D)
_HEAD_FORMS = {
    "packed_64x2": (2, 2, 64, 64),              # tbase_s4096
    "one_128": (2, 2, 128, 128),
    "mla_192_128": (2, 2, 192, 128),            # kanana2_s4096
    "grouped_8_1": (8, 1, 128, 128),            # keye2_s8192 (32 / 4)
    "grouped_packed_8_2x64": (8, 2, 64, 64),    # lfm2_s8192 (32 / 8)
}


def _attn_shape(layout, S=_S, heads=_H, width=_D):
    return (_B, S, heads, width) if layout == "bshd" \
        else (_B, heads, S, width)


def _site_shapes(layout, names, form, S=_S):
    H, Hkv, D, Dv = form
    return {n: _attn_shape(layout, S, H if n == "q" else Hkv,
                           Dv if n[0] == "v" else D) for n in names}


def _attn_program(layout, mode, n_sites=1, bias_grad=False, form=_PLAIN,
                  S=_S, block=128, dtype="float32"):
    """layers.fused_attention + append_backward over `n_sites` chained
    attentions; returns (main, feed names, grad names). mode "mask": an
    int8 keep mask [B, 1, S, S]."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    fluid.framework.unique_name.reset()
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        def data(name, shape, dtype=dtype):
            var = layers.data(name=name, shape=list(shape),
                              dtype=dtype, append_batch_size=False)
            var.stop_gradient = dtype == "int8"
            return var

        # a leaf shared by two ops would need its grads summed, which
        # append_backward does only for parameters: K/V per site
        names = ["q"] + [f"{n}{i}" for i in range(n_sites) for n in "kv"]
        shapes = _site_shapes(layout, names, form, S)
        q, *kv = (data(n, shapes[n]) for n in names)
        bias = None
        if mode == "padding":
            bias = data("bias", (_B, 1, 1, S), "float32")
            bias.stop_gradient = not bias_grad
            names.append("bias")
        elif mode == "mask":
            bias = data("bias", (_B, 1, S, S), "int8")
            names.append("bias")
        x = q
        for i in range(n_sites):
            x = layers.fused_attention(
                x, kv[2 * i], kv[2 * i + 1], bias, block_q=block,
                block_k=block, layout=layout, causal=(mode == "causal"))
        loss = layers.reduce_sum(layers.square(x))
        fluid.backward.append_backward(loss)
    grads = [n + "@GRAD" for n in names
             if n != "bias" or bias_grad]
    return main, names, grads


def _attn_feed(names, layout, seed=0, form=_PLAIN, S=_S, mode="padding"):
    rng = np.random.default_rng(seed)
    shapes = _site_shapes(layout, [n for n in names if n != "bias"], form,
                          S)
    feed = {n: rng.standard_normal(shape).astype("float32")
            for n, shape in shapes.items()}
    if "bias" in names and mode == "mask":
        keep = rng.random((_B, 1, S, S)) < 0.5
        feed["bias"] = (keep | np.eye(S, dtype=bool)).astype("int8")
    elif "bias" in names:
        pad = np.zeros((_B, 1, 1, S), "float32")
        pad[..., S - 32:] = -1e9
        feed["bias"] = pad
    return feed


def _equations(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs included; a Pallas call's
    kernel body (VMEM refs and tiles, nothing in HBM) is not entered."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _pallas_calls(jaxpr):
    """name -> count of pallas_call equations, sub-jaxprs included."""
    acc = {}
    for eqn in _equations(jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            acc[name] = acc.get(name, 0) + 1
    return acc


def _lane_broadcast_arrays(jaxpr, B, H, S):
    """The float32 arrays of a jaxpr in the shape of the carrier the
    kernels spoke before PR 40 (128 copies of each row's number:
    [B, S, H*128] or [B*H, S, 128]), and its optimization barriers."""
    wide = {(B, S, H * 128), (B * H, S, 128)}
    found = []
    for eqn in _equations(jaxpr):
        if eqn.primitive.name == "optimization_barrier":
            found.append("optimization_barrier")
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            if getattr(aval, "dtype", None) == jnp.float32 \
                    and tuple(aval.shape) in wide:
                found.append((eqn.primitive.name, tuple(aval.shape)))
    return found


def _engine_step(main, feed, fetch, jaxpr=False):
    """Run one step through Executor.run; (fetched values, the step's
    Pallas calls by name, or with `jaxpr` the step's jaxpr)."""
    import paddle_tpu as fluid
    from paddle_tpu.core.engine import _scope_array
    from paddle_tpu.core.scope import Scope

    def sig(a):
        return jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a))

    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        vals = exe.run(main, feed=feed, fetch_list=fetch)
        _, traced = exe._engine._compiled_entry(main, scope, feed, fetch)
        assert exe._engine.step_executables() == [1]
        closed = traced.fn.trace(
            {n: sig(_scope_array(scope, n)) for n in traced.donated_names},
            {n: sig(_scope_array(scope, n)) for n in traced.const_names},
            {n: sig(a) for n, a in feed.items()},
            jax.ShapeDtypeStruct((2,), jnp.uint32)).jaxpr
    return vals, closed.jaxpr if jaxpr else _pallas_calls(closed.jaxpr)


def _reference_grads(feed, layout, mode, n_sites, names):
    qn = feed["q"]
    scale = float(qn.shape[-1]) ** -0.5

    def loss(*args):
        a = dict(zip(names, args))
        x = a["q"]
        for i in range(n_sites):
            x = fa._attn_reference(x, a[f"k{i}"], a[f"v{i}"],
                                   a.get("bias"), scale, layout=layout,
                                   causal=(mode == "causal"))
        return (x ** 2).sum()

    floats = tuple(i for i, n in enumerate(names)
                   if feed[n].dtype == np.float32)
    return jax.grad(loss, floats)(*(jnp.asarray(feed[n]) for n in names))


@pytest.mark.parametrize("mode", ["plain", "causal", "padding"])
@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
def test_engine_step_runs_each_flash_kernel_once_per_site(layout, mode):
    """(a) The lowered step holds one forward call and one backward
    call (the fused dq/dk/dv kernel, named flash_attention_dkv) per
    attention: the grad op reads Out and SoftmaxLse."""
    n_sites = 2
    main, names, grads = _attn_program(layout, mode, n_sites)
    feed = _attn_feed(names, layout)
    vals, calls = _engine_step(main, feed, grads)
    assert calls == {"flash_attention_fwd": n_sites,
                     "flash_attention_dkv": n_sites}, calls
    ref = _reference_grads(feed, layout, mode, n_sites, names)
    for got, want in zip(vals, ref):
        np.testing.assert_allclose(got, np.asarray(want),
                                   atol=5e-4, rtol=5e-4)


def _check_lse_and_grads(vals, feed, layout, mode, names):
    """One site's fetched (grads..., SoftmaxLse) against the composed
    formulation: the lse float32 [B, H, Sq] to 1e-6, the grads its vjp's."""
    q, k, v = (jnp.asarray(feed[n]) for n in ("q", "k0", "v0"))
    if layout == "bshd":
        q, k, v = (jnp.moveaxis(x, 1, 2) for x in (q, k, v))
    k, v = (jnp.repeat(x, q.shape[1] // k.shape[1], axis=1) for x in (k, v))
    bias = feed.get("bias")
    if mode == "mask":
        bias = np.where(bias != 0, 0.0, fa._NEG_INF).astype("float32")
    _, lse = fa._attn_reference_lse(q, k, v, bias, q.shape[-1] ** -0.5,
                                    causal=(mode == "causal"))
    assert vals[-1].shape == lse.shape and vals[-1].dtype == np.float32
    np.testing.assert_allclose(vals[-1], np.asarray(lse), atol=1e-6,
                               rtol=1e-6)
    ref = _reference_grads(feed, layout, mode, 1, names)
    for got, want in zip(vals[:-1], ref):
        np.testing.assert_allclose(got, np.asarray(want),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("form", list(_HEAD_FORMS))
@pytest.mark.parametrize("mode", ["plain", "causal", "padding", "mask"])
@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
def test_site_hands_its_lse_over_narrow(layout, mode, form):
    """PR 40: at every site form the accepted cells have, the engine's
    step holds one forward and one backward call, no float32 array in
    the lane-broadcast carrier's shape, no optimization barrier; the
    SoftmaxLse it carries is the composed formulation's to 1e-6 and the
    grads are the composed vjp's."""
    heads = _HEAD_FORMS[form]
    main, names, grads = _attn_program(layout, mode, form=heads)
    feed = _attn_feed(names, layout, seed=7, form=heads, mode=mode)
    fwd = next(op for op in main.global_block().ops
               if op.type == "fused_attention")
    lse_name, = fwd.output("SoftmaxLse")
    vals, jaxpr = _engine_step(main, feed, grads + [lse_name], jaxpr=True)
    assert _pallas_calls(jaxpr) == {"flash_attention_fwd": 1,
                                    "flash_attention_dkv": 1}
    if 128 in heads:
        # float32 q, v and out of 128-wide heads ARE [B, S, H*128]: read
        # the step's arrays where, as in the cells, the streams are bf16
        half = _attn_program(layout, mode, form=heads, dtype="bfloat16")[0]
        _, jaxpr = _engine_step(
            half, {n: a.astype(jnp.bfloat16) if a.dtype == np.float32
                   and n != "bias" else a for n, a in feed.items()},
            grads, jaxpr=True)
    assert _lane_broadcast_arrays(jaxpr, _B, heads[0], _S) == []
    _check_lse_and_grads(vals, feed, layout, mode, names)


@pytest.mark.parametrize("block", [64, 96, 192])
@pytest.mark.parametrize("mode", ["plain", "causal"])
@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
def test_sequence_of_192_stays_on_the_kernels(layout, mode, block):
    """A q block that is no multiple of 128 rows pads its lanes of the
    narrow lse in the wrapper (block 64: three padded segments; 96: two;
    192: one block whose second chunk holds 64 rows) and takes the same
    kernels, under the interpreter."""
    S, heads = 192, (2, 2, 64, 64)
    main, names, grads = _attn_program(layout, mode, form=heads, S=S,
                                       block=block)
    feed = _attn_feed(names, layout, seed=8, form=heads, S=S)
    fwd = next(op for op in main.global_block().ops
               if op.type == "fused_attention")
    lse_name, = fwd.output("SoftmaxLse")
    vals, calls = _engine_step(main, feed, grads + [lse_name])
    assert calls == {"flash_attention_fwd": 1, "flash_attention_dkv": 1}
    _check_lse_and_grads(vals, feed, layout, mode, names)


def _residual_shapes(f, *args):
    """Shapes of the float32 residuals `jax.vjp(f, *args)` keeps."""
    _, vjp = jax.vjp(f, *args)
    return [tuple(x.shape) for x in jax.tree_util.tree_leaves(vjp)
            if x.dtype == jnp.float32]


@pytest.mark.parametrize("entry,layout", [
    ("flash_attention", "bhsd"), ("flash_attention", "bshd"),
    # flash_attention_lse is [B, H, S, D] only
    ("lse_out_only", "bhsd"), ("lse_with_cotangent", "bhsd")])
def test_custom_vjp_residuals_are_narrow(entry, layout):
    """D9: what `flash_attention` and `flash_attention_lse` save for
    their backward is the float32 [B, H, Sq] lse, never 128 copies of
    it, with and without an lse cotangent; nothing of that shape is in
    the jaxpr of their grads either."""
    B, H, S, D = 1, 2, 256, 64
    rng = np.random.default_rng(13)
    q, k, v = (jnp.asarray(rng.standard_normal(
        _attn_shape(layout, S, H, D)), jnp.bfloat16) for _ in range(3))

    if entry == "flash_attention":
        def f(q, k, v):
            return fa.flash_attention(q, k, v, None, 0.125, 128, 128,
                                      layout, True)

        def loss(q, k, v):
            return (f(q, k, v).astype(jnp.float32) ** 2).sum()
    else:
        def f(q, k, v):
            return fa.flash_attention_lse(q, k, v, None, 0.125, 128, 128)

        def loss(q, k, v):
            out, lse = f(q, k, v)
            total = (out.astype(jnp.float32) ** 2).sum()
            if entry == "lse_with_cotangent":
                total = total + jnp.sin(lse).sum()
            return total

    # q, k, v and out are bf16: the one float32 residual is the lse
    assert _residual_shapes(f, q, k, v) == [(B, H, S)]
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr
    assert _lane_broadcast_arrays(jaxpr, B, H, S) == []
    assert sum(_pallas_calls(jaxpr).values()) == 2


def _lower_ops(block, env, skip_slot=None):
    """Lower the block's ops in order into env (what the engine's trace
    does), optionally hiding one input slot from the grad op."""
    from paddle_tpu.core.registry import (OPS, ExecContext, _RngCtx,
                                          _SlotView)
    for op in block.ops:
        view = op
        if skip_slot and op.type == "fused_attention_grad":
            view = _SlotView(
                op.type,
                {s: op.input(s) for s in op.input_slots()
                 if not s.startswith(skip_slot)},
                {s: op.output(s) for s in op.output_slots()},
                dict(op._all_attrs()))
        OPS.get(op.type).lowering(
            ExecContext(view, env, _RngCtx(jax.random.PRNGKey(0))))
    return env


@pytest.mark.parametrize("mode,bias_grad", [
    ("plain", False), ("causal", False), ("padding", False),
    ("padding", True)])
@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
def test_carried_lse_grads_bit_identical_to_recompute(layout, mode,
                                                      bias_grad):
    """(b) The grads from the carried pair equal the unbound-slot
    fallback's bit for bit: both hand the backward kernels the forward
    kernel's own float32 [B, H, Sq].
    (c) What is carried is that; nothing 128 times its size enters the
    grad op."""
    main, names, grads = _attn_program(layout, mode,
                                       bias_grad=bias_grad)
    block = main.global_block()
    feed = {n: jnp.asarray(a)
            for n, a in _attn_feed(names, layout, seed=3).items()}
    carried = _lower_ops(block, dict(feed))
    fallback = _lower_ops(block, dict(feed), skip_slot="SoftmaxLse")
    assert ("bias@GRAD" in grads) == bias_grad
    for g in grads:
        np.testing.assert_array_equal(np.asarray(carried[g]),
                                      np.asarray(fallback[g]))

    fwd = next(op for op in block.ops if op.type == "fused_attention")
    gop = next(op for op in block.ops
               if op.type == "fused_attention_grad")
    lse_name, = fwd.output("SoftmaxLse")
    assert gop.input("SoftmaxLse") == [lse_name]
    lse = carried[lse_name]
    assert lse.dtype == jnp.float32 and lse.shape == (_B, _H, _S)
    assert tuple(block.var(lse_name).shape) == (_B, _H, _S)
    assert block.var(lse_name).stop_gradient
    for slot in gop.input_slots():
        for n in gop.input(slot):
            if n:
                assert carried[n].size <= max(feed["q"].size, lse.size), \
                    (slot, n)
    # the real thing, not the placeholder: matches the composed lse
    ref_q, ref_k = feed["q"], feed["k0"]
    if layout == "bshd":
        ref_q, ref_k = (jnp.moveaxis(x, 1, 2) for x in (ref_q, ref_k))
    s = jnp.einsum("bhqd,bhkd->bhqk", ref_q, ref_k) * float(_D) ** -0.5
    if "bias" in feed:
        s = s + feed["bias"]
    if mode == "causal":
        s = jnp.where(jnp.tril(jnp.ones((_S, _S), bool)), s, -jnp.inf)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(s, axis=-1)),
        atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
def test_composed_path_lowers_no_pallas_call(layout, monkeypatch):
    """(d) Off the kernel path nothing is carried and nothing is a
    kernel: the step holds no Pallas call, the grads are jax.grad of the
    composed formulation, and the slot holds a placeholder."""
    monkeypatch.setattr(fa, "_INTERPRET", False)
    main, names, grads = _attn_program(layout, "padding")
    feed = _attn_feed(names, layout, seed=4)
    fwd = next(op for op in main.global_block().ops
               if op.type == "fused_attention")
    lse_name, = fwd.output("SoftmaxLse")
    vals, calls = _engine_step(main, feed, grads + [lse_name])
    assert calls == {}
    ref = _reference_grads(feed, layout, "padding", 1, names)
    for got, want in zip(vals[:len(grads)], ref):
        np.testing.assert_allclose(got, np.asarray(want),
                                   atol=2e-4, rtol=2e-4)
    assert vals[-1].shape == (_B, _H, _S) and not vals[-1].any()


def test_serialised_program_keeps_lse_slot():
    """(e) ProgramDesc round trip: the slot survives on both ops and the
    reloaded step still runs one forward call per site."""
    import paddle_tpu as fluid
    main, names, grads = _attn_program("bshd", "causal", n_sites=2)
    loaded = fluid.Program.parse_from_string(main.serialize_to_string())
    ops = loaded.global_block().ops
    fwd = [op for op in ops if op.type == "fused_attention"]
    gops = [op for op in ops if op.type == "fused_attention_grad"]
    assert len(fwd) == len(gops) == 2
    assert sorted(op.output("SoftmaxLse")[0] for op in fwd) == \
        sorted(op.input("SoftmaxLse")[0] for op in gops)
    feed = _attn_feed(names, "bshd", seed=5)
    vals, calls = _engine_step(loaded, feed, grads)
    assert calls == {"flash_attention_fwd": 2,
                     "flash_attention_dkv": 2}, calls
    want, _ = _engine_step(main, feed, grads)
    for a, b in zip(vals, want):
        np.testing.assert_array_equal(a, b)


def test_is_test_forward_with_grad_op_recomputes():
    """A forward at is_test writes no lse; a grad op bound to it must
    not read the placeholder."""
    import paddle_tpu as fluid
    main, names, grads = _attn_program("bhsd", "plain")
    block = main.global_block()
    for op in block.ops:
        if op.type.startswith("fused_attention"):
            op.set_attr("is_test", True)
    feed = {n: jnp.asarray(a)
            for n, a in _attn_feed(names, "bhsd", seed=6).items()}
    env = _lower_ops(block, dict(feed))
    ref = _reference_grads(feed, "bhsd", "plain", 1, names)
    for g, want in zip(grads, ref):
        np.testing.assert_allclose(np.asarray(env[g]), np.asarray(want),
                                   atol=5e-4, rtol=5e-4)


# ---------------------------------------------------------------------------
# the fused backward: dq, dk and dv from one pass over the score tiles
# ---------------------------------------------------------------------------

def _flash_stats():
    from paddle_tpu.kernels import registry
    return registry.dispatch_stats()["per_kernel"].get(
        "flash_attention", {})


def _composed_with_lse(q, k, v, scale, causal, keep, t):
    """[B,H,S,D] attention returning (out, lse), the weights dropped by
    the mask the interpret-mode kernels realize."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        s = fa._causal_mask_dense(s)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    if keep is not None:
        p = jnp.where(keep, p * (256.0 / t), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v), lse


@pytest.mark.parametrize("with_glse", [False, True],
                         ids=["out_only", "g_lse"])
@pytest.mark.parametrize("drop", [False, True], ids=["keep_all", "dropout"])
@pytest.mark.parametrize("widths", [(64, 64), (192, 128)],
                         ids=["64x64", "192x128"])
@pytest.mark.parametrize("seqs", [(256, 256), (256, 384)],
                         ids=["Sq_eq_Sk", "Sq_ne_Sk"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
def test_fused_backward_bit_equal_to_split(layout, causal, seqs, widths,
                                           drop, with_glse, monkeypatch):
    """One kernel builds each tile's s, p, dp and ds once and writes dq,
    dk and dv; the accumulation orders are the split pair's (dq over kv
    blocks ascending, dk / dv over q blocks ascending), so the results
    are the same bits, and within the composed vjp's tolerance."""
    from paddle_tpu.kernels import registry
    (Sq, Sk), (D, Dv) = seqs, widths
    B, H, t = 2, 4, 205
    rng = np.random.default_rng(12)
    qb, kb, vb, gb = (_rand(rng, B, H, S, W) for S, W in
                      ((Sq, D), (Sk, D), (Sk, Dv), (Sq, Dv)))
    g_lse = _rand(rng, B, H, Sq) if with_glse else None
    key = jax.random.PRNGKey(7)
    dropout = (key, t) if drop else None
    scale = float(D) ** -0.5

    def to_layout(x):
        return jnp.moveaxis(x, 1, 2) if layout == "bshd" else x

    q, k, v, g = (to_layout(x) for x in (qb, kb, vb, gb))
    out, lse = fa._fa_forward(q, k, v, None, scale, 128, 128,
                              return_lse=True, layout=layout, causal=causal,
                              dropout=dropout)

    def backward():
        return fa._fa_backward(q, k, v, None, out, lse, g, scale, 128,
                               128, g_lse=g_lse, layout=layout,
                               causal=causal,
                               dropout=dropout)[:3]

    registry.reset_stats()
    fused = backward()
    assert _flash_stats() == {"fused_bwd": 1, "narrow_lse": 1}
    # nothing fits a budget of nothing: the split pair
    monkeypatch.setattr(fa, "_FUSED_DQ_VMEM_BUDGET", 0)
    split = backward()
    assert _flash_stats() == {"fused_bwd": 1, "split_bwd": 1,
                              "narrow_lse": 2}
    for a, b in zip(fused, split):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    keep = None
    if drop:
        keep = fa.dropout_keep_mask(
            jax.lax.bitcast_convert_type(key, jnp.int32).reshape(2),
            B, H, Sq, Sk, t)
    _, vjp = jax.vjp(lambda q, k, v: _composed_with_lse(
        q, k, v, scale, causal, keep, t), qb, kb, vb)
    want = vjp((gb, jnp.zeros((B, H, Sq)) if g_lse is None else g_lse))
    for a, b in zip(fused, want):
        np.testing.assert_allclose(
            np.asarray(a if layout == "bhsd" else jnp.moveaxis(a, 1, 2)),
            np.asarray(b), atol=3e-4, rtol=3e-4)


def _traced_backward_route(shape_qk, shape_v, dtype, bias=None,
                           want_dbias=None):
    """Trace (never run) one _fa_backward at a bshd shape; the route it
    counted."""
    from paddle_tpu.kernels import registry
    B, S, H, _ = shape_qk
    qk = jax.ShapeDtypeStruct(shape_qk, dtype)
    v = jax.ShapeDtypeStruct(shape_v, dtype)
    lse = jax.ShapeDtypeStruct((B, H, S), jnp.float32)
    registry.reset_stats()
    jax.eval_shape(
        lambda q, k, v, out, lse, g: fa._fa_backward(
            q, k, v, bias, out, lse, g, 0.125, 512, 1024, layout="bshd",
            want_dbias=want_dbias, causal=True),
        qk, qk, v, v, lse, v)
    took = _flash_stats()
    # every backward call reads its lse narrow, whichever route
    assert took.pop("narrow_lse") == 1
    return took


@pytest.mark.parametrize("shape_qk,shape_v,dtype,resident,route", [
    # tbase_s4096's attention: 4 MiB of resident dq
    ((4, 4096, 8, 64), (4, 4096, 8, 64), jnp.bfloat16, 4 << 20,
     "fused_bwd"),
    # kanana2_s4096's: two heads of 192 a block, 12 MiB
    ((1, 4096, 32, 192), (1, 4096, 32, 128), jnp.bfloat16, 12 << 20,
     "fused_bwd"),
    # the same heads in float32 at S=8192: 36 MiB, the last that fits
    ((1, 8192, 32, 192), (1, 8192, 32, 128), jnp.float32, 36 << 20,
     "fused_bwd"),
    # S=65,536 at 64/64: 64 MiB of dq cannot stay in VMEM
    ((1, 65536, 8, 64), (1, 65536, 8, 64), jnp.bfloat16, 64 << 20,
     "split_bwd"),
], ids=["tbase_s4096", "kanana2_s4096", "f32_s8192", "s65536"])
def test_backward_route_follows_the_resident_dq(shape_qk, shape_v, dtype,
                                                resident, route):
    B, S, H, D = shape_qk
    plan = fa._Plan("bshd", B, H, S, S, D, 512, 1024, shape_v[3])
    assert fa._resident_dq_bytes(plan, dtype) == resident
    assert (resident <= fa._FUSED_DQ_VMEM_BUDGET) == (route == "fused_bwd")
    assert _traced_backward_route(shape_qk, shape_v, dtype) == {route: 1}


def test_demanded_dbias_takes_the_split_pair():
    """The ds output follows the dq-style grid: a bias gradient that is
    asked for runs the dq and dk/dv kernels, and says so; the same bias
    with no gradient asked runs the fused kernel."""
    shape = (2, 1024, 8, 64)
    bias = jnp.zeros((2, 1, 1024, 1024), jnp.float32)
    assert _traced_backward_route(shape, shape, jnp.bfloat16, bias,
                                  want_dbias=True) == {"split_bwd": 1}
    assert _traced_backward_route(shape, shape, jnp.bfloat16,
                                  bias) == {"split_bwd": 1}
    assert _traced_backward_route(shape, shape, jnp.bfloat16, bias,
                                  want_dbias=False) == {"fused_bwd": 1}
    main, names, grads = _attn_program("bshd", "padding", bias_grad=True)
    _, calls = _engine_step(main, _attn_feed(names, "bshd"), grads)
    assert calls == {"flash_attention_fwd": 1, "flash_attention_dq": 1,
                     "flash_attention_dkv": 1}, calls
