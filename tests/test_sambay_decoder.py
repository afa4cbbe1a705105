"""A decoder-hybrid-decoder (SambaY, the `phi4flash` block) through the
model file: Mamba-1 mixers over the selective scan, sliding-window,
full and cross attention under differential attention, gated memory
units, LayerNorm and a tied head, through `Executor.run` against the
benchmark's plain reference (benchmark/families/sambay_decoder_
reference.py); the published layout, readers with no producer, the op
scopes and counters, the differential-attention op against its
equations. In a file of its own so that the suite's workers share it
out."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.scope import Scope
from paddle_tpu.kernels import registry as kreg

from benchmark.families import sambay_decoder as sambay_family
from benchmark.families import sambay_decoder_reference as sambay_ref

from test_decoder_lm import (_close, _fetch, _r, _run, _train,  # noqa: F401
                             interp)  # (a fixture)



def _sambay_sizes(**over):
    import json
    import os
    from benchmark.lib import cells
    with open(os.path.join(cells.BENCH, "configs",
                           "phi4_mini_flash_reasoning.json")) as f:
        return dict(sambay_family.sizes(json.load(f), rehearsal=True),
                    **over)


@pytest.mark.parametrize("path", ["lowered", "kernels"])
def test_sambay_model_trains_like_its_reference(path, request):
    """The model file, told by `layer_types` to build published layers
    15-19 of a decoder-hybrid-decoder (window, Mamba, full, GMU, cross),
    through Executor.run in float32 against the plain reference: losses,
    EVERY leaf's first gradient — the full layer's k / v projections,
    whose gradient is the sum of its own attention's and the cross
    layer's, and the Mamba layer's scan parameters, whose gradient comes
    through its own gate and through the GMU among them — and every
    leaf's change after three Adam steps. 40 tokens, a window of 12,
    B = 2. The two counters as the model fills them. Under the
    interpreter the heads are 64 wide (the flash kernels pack two query
    heads of a key head into a lane block)."""
    over = {}
    if path == "kernels":
        request.getfixturevalue("interp")
        over = dict(hidden_size=256, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=64,
                    initializer_range=0.056)
    sz = _sambay_sizes(**over)
    fam = sambay_family
    tr = fam.traffic({"pool": 3, "reference_rows_per_block": 1}, True)
    with jax.default_matmul_precision("highest"):
        got = _train(sz, tr, 7, amp=False, fam=fam)
        want = fam.run_reference(sz, tr, fam.make_pool(sz, tr, 7), 7, 3)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=2e-6)
    assert set(got["grad_norms"]) == set(want["grad_norms"]) \
        == set(sambay_ref.trainable_names(sz))
    for n, w in want["grad_norms"].items():
        assert w > 0, n
        assert abs(got["grad_norms"][n] - w) <= 2e-4 * max(w, 1e-6), n
    for n, w in want["delta_norms"].items():
        assert abs(got["delta_norms"][n] - w) <= 5e-3 * w, n
    tokens = tr["batch"] * tr["seq_len"]
    assert fam.scanned_tokens(sz).tolist() == [tokens]
    assert fam.window_pairs(sz).tolist() == [
        tr["batch"] * sum(min(r + 1, 12) for r in range(40))]
    if path == "kernels":
        stats = kreg.dispatch_stats()["per_kernel"]
        assert stats["selective_scan"].get("custom")
        assert not stats["selective_scan"].get("lowered")
        assert stats["flash_attention"].get("custom")


def test_sambay_reference_faults_move_the_reference():
    """Each planted fault of the reference the cell's comparison is
    asked about changes the reference's reading: the scan's state or step
    rounded to bfloat16, the second softmax map dropped (the others move
    it by tenths: PERF.md's calibration)."""
    sz = _sambay_sizes()
    tr = sambay_family.traffic({"pool": 1, "reference_rows_per_block": 2},
                               True)
    pool = sambay_family.make_pool(sz, tr, 3)
    base = sambay_ref.run(sz, pool, 3, steps=1, query_rows=20)
    for fault in ("scan_state_bf16", "scan_dt_bf16", "lambda_a2_dropped"):
        bad = sambay_ref.run(sz, pool, 3, steps=1, query_rows=20,
                             fault=fault)
        assert any(abs(bad["grad_norms"][n] - w) > 1e-6 * w
                   for n, w in base["grad_norms"].items()), fault


def test_sambay_model_under_mixed_precision():
    """bf16 products with float32 master weights, Δ, A and the state
    float32: the losses within bf16's rounding of the reference's."""
    sz = _sambay_sizes()
    fam = sambay_family
    tr = fam.traffic({"pool": 3, "reference_rows_per_block": 1}, True)
    got = _train(sz, tr, 9, amp=True, fam=fam)
    want = fam.run_reference(sz, tr, fam.make_pool(sz, tr, 9), 9, 3)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=3e-3)


def test_sambay_published_layout():
    """Without a run list the layout follows from `num_hidden_layers`
    and `mb_per_layer`: 32 layers give 9 Mamba, 8 sliding-window, 1
    full, 7 GMU and 7 cross-attention layers, Mamba at even indices <=
    16, windows at odd indices < 16, the full layer at 17, GMUs at even
    indices >= 18, cross layers at odd ones."""
    from paddle_tpu import models
    cfg = models.DecoderLMConfig(
        num_hidden_layers=32, mb_per_layer=2, sliding_window=512,
        layer_norm_eps=1e-5, hidden_size=2560, num_attention_heads=40,
        num_key_value_heads=20, intermediate_size=10240,
        use_differential_attention=True, tie_word_embeddings=True)
    want = [("mamba" if i % 2 == 0 else "swa") if i < 16 else
            ("mamba" if i == 16 else "attn") if i < 18 else
            ("gmu" if i % 2 == 0 else "xattn") for i in range(32)]
    assert cfg.mixers == want
    assert [cfg.mixers.count(m) for m in
            ("mamba", "swa", "attn", "gmu", "xattn")] == [9, 8, 1, 7, 7]
    assert cfg.mixers.index("attn") == 17
    assert cfg.sambay and cfg.layer_norm
    assert not cfg.attention_positions and not cfg.moe_layers
    assert cfg.dense_layers == set(range(32))
    assert (cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank) == \
        (5120, 16, 160)
    assert [round(cfg.lambda_init(i), 6) for i in (0, 17)] == \
        [0.2, round(0.8 - 0.6 * np.exp(-0.3 * 17), 6)]
    held = models.DecoderLMConfig(
        layer_types=["sliding_attention", "mamba", "full_attention", "gmu",
                     "cross_attention"], layers_held=[15, 16, 17, 18, 19],
        sliding_window=512, use_differential_attention=True,
        num_attention_heads=40, num_key_value_heads=20, hidden_size=2560)
    assert held.lambda_init(0) == pytest.approx(0.8 - 0.6 * np.exp(-4.5))


@pytest.mark.parametrize("layer_types", [
    ["sliding_attention", "mamba", "full_attention", "gmu",
     "cross_attention"], ["full_attention", "mamba"]])
def test_sambay_attention_is_always_differential(layer_types):
    """A decoder-hybrid-decoder's attention is differential attention:
    the key, where a file gives it, must say so; plain attention there
    is not built and raises by name."""
    from paddle_tpu import models
    keys = dict(layer_types=layer_types, num_attention_heads=8,
                num_key_value_heads=4, sliding_window=16
                if "sliding_attention" in layer_types else None)
    assert models.DecoderLMConfig(**keys).sambay
    models.DecoderLMConfig(use_differential_attention=True, **keys)
    with pytest.raises(NotImplementedError,
                       match="use_differential_attention"):
        models.DecoderLMConfig(use_differential_attention=False, **keys)


@pytest.mark.parametrize("layer_types,name", [
    (["gmu", "mamba"], "gmu"),
    (["sliding_attention", "cross_attention"], "cross_attention"),
    (["mamba", "sliding_attention", "cross_attention"], "cross_attention"),
    (["full_attention", "gmu"], "gmu")])
def test_readers_without_a_producer_raise_by_name(layer_types, name):
    """A gated memory unit with no Mamba layer before it, a cross layer
    with no full-attention layer before it: refused by name."""
    from paddle_tpu import models
    with pytest.raises(ValueError, match=name):
        models.DecoderLMConfig(
            layer_types=layer_types, sliding_window=16
            if "sliding_attention" in layer_types else None)


def test_sambay_model_is_built_with_its_scopes_and_counters():
    """Op scopes `layer_<i>/swa`, `/mamba`, `/attn`, `/gmu`, `/xattn`;
    one flash call a site with the query heads in (pair, map, member)
    order and the value heads doubled; the cross layer projects its
    queries alone and reads the full layer's k and v; LayerNorm with a
    shift everywhere, no rotary; the new counter persistable."""
    from paddle_tpu import models
    sz = _sambay_sizes()
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        models.decoder_lm_train(sambay_family.model_config(sz))
    ops = main.global_block().ops
    attn = [op for op in ops if op.type == "fused_attention"]
    assert [op.attr("op_namescope", "") for op in attn] == [
        "layer_0/swa/", "layer_2/attn/", "layer_4/xattn/"]
    assert attn[2].input("K") == attn[1].input("K")
    assert attn[2].input("V") == attn[1].input("V")
    assert [bool(op.output("WindowPairs")) for op in attn] == \
        [True, False, False]
    scan, = [op for op in ops if op.type == "selective_scan"]
    assert scan.attr("op_namescope", "") == "layer_1/mamba/"
    gmu = [op for op in ops if op.attr("op_namescope", "") == "layer_3/gmu/"
           and op.type == "swiglu"]
    assert gmu and gmu[0].input("Y") == scan.output("Y")
    assert len([op for op in ops if op.type == "differential_attention"]) \
        == 3
    assert not [op for op in ops if op.type in ("rotary_embedding",
                                                "rms_norm")]
    assert len([op for op in ops if op.type == "layer_norm"]) == 11
    names = {p.name for p in main.all_parameters()}
    assert "layer_4_attn_k.w_0" not in names and "lm_head.w_0" not in names
    for var in ("selective_scan_tokens", "window_attn_pairs"):
        assert main.global_block().var(var).persistable
    assert sambay_family.param_shapes(sz) == {
        p.name: tuple(p.shape) for p in main.all_parameters()}


def test_phi4flash_file_builds_the_cell_at_its_widths():
    """The configuration file at the cell's sizes: 577,178,752 trained
    parameters (the tied table counted once), every width as published:
    d_inner 5,120, state 16, dt_rank 160, 40 query heads over 20 key
    heads of 64, values read as 128, MLP 10,240, window 512; and the
    whole file's published keys handed to the model file at once."""
    import json
    import os
    from benchmark.lib import cells
    from paddle_tpu import models
    with open(os.path.join(cells.BENCH, "configs",
                           "phi4_mini_flash_reasoning.json")) as f:
        raw = json.load(f)
    sz = sambay_family.sizes(raw)
    assert sambay_family.trained_parameters(sz) == 577_178_752
    shapes = sambay_family.param_shapes(sz)
    assert shapes["layer_1_mamba_a_log.w_0"] == (5120, 16)
    assert shapes["layer_1_mamba_x.w_0"] == (5120, 192)
    assert shapes["layer_2_attn_v.w_0"] == (2560, 1280)
    assert shapes["layer_2_attn_subln.w_0"] == (128,)
    assert shapes["layer_3_gmu_in.w_0"] == (2560, 5120)
    keys = {k: v for k, v in raw.items()
            if k not in ("name", "family", "source", "deployment",
                         "reduced", "assumed")}
    cfg = models.DecoderLMConfig(**keys)
    assert cfg.mixers == ["swa", "mamba", "attn", "gmu", "xattn"]
    assert cfg.layer_norm and cfg.sliding_window == 512


def test_selective_scan_and_shared_kv_readers():
    """The program's `selective_scan_tokens` counter through its reader
    (None before a step wrote it); the pairs the cross layers attend over
    the shared keys and values are a causal square's, counted from the
    shapes as the full layer's are."""
    from paddle_tpu.observability import selective_scan
    scope = Scope()
    assert selective_scan.scanned_tokens(scope) is None
    scope.var(selective_scan.SELECTIVE_SCAN_TOKENS_VAR).set_value(
        jnp.asarray([4096], jnp.int32))
    got = selective_scan.scanned_tokens(scope)
    assert got.dtype == np.int64 and got.tolist() == [4096]
    sz = _sambay_sizes()
    tr = {"batch": 1, "seq_len": 4096}
    assert sambay_family.causal_pairs_per_step(sz, tr, "cross") == \
        sambay_family.causal_pairs_per_step(sz, tr, "full") == 8_390_656


def test_differential_attention_op_against_its_equations():
    """The op's output and gradients against the equations written out:
    lambda from the four vectors, a1 - lambda a2 of each head's two
    maps, RMS-normalised over its channels, times the sub-norm's scale
    and 1 - lambda_init."""
    b, s, pairs, group, dv, init = 2, 5, 2, 2, 16, 0.31
    x = _r((b, s, pairs * 2 * group, dv), 170)
    lam = [_r((dv // 2,), 171 + i, 0.3) for i in range(4)]
    w = _r((dv,), 175) + 1.0
    cot = _r((b, s, pairs * group * dv), 176)

    def equations(x, q1, k1, q2, k2, w):
        lam_ = jnp.exp(q1 @ k1) - jnp.exp(q2 @ k2) + init
        m = x.reshape(b, s, pairs, 2, group, dv)
        o = m[:, :, :, 0] - lam_ * m[:, :, :, 1]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
        return (o * w * (1 - init)).reshape(b, s, -1)

    def build(x):
        return layers.differential_attention(
            x, group, init, lambda_attrs=[
                fluid.ParamAttr(name=f"lam_{i}",
                                initializer=fluid.initializer
                                .NumpyArrayInitializer(lam[i]))
                for i in range(4)],
            scale_attr=fluid.ParamAttr(
                name="subln", initializer=fluid.initializer
                .NumpyArrayInitializer(w)))
    main, scope, exe, out, grads = _run(build, {"x": x},
                                        ["x", "lam_0", "lam_3", "subln"])
    got, got_grads = _fetch(main, scope, exe, {"x": x}, out, grads, cot)
    with jax.default_matmul_precision("highest"):
        want = equations(x, *lam, w)
        _, vjp = jax.vjp(equations, x, *lam, w)
        gx, g0, _, _, g3, gw = vjp(jnp.asarray(cot))
    _close(got, want)
    for g, w_ in zip(got_grads, (gx, g0, g3, gw)):
        _close(g, w_, tol=1e-4)
