"""The names the program puts into the device trace.

The profiler's event name for a Pallas kernel is its HLO instruction's
text, so a `pallas_call(..., name="flash_attention_fwd")` is what lets a
trace tell the three flash kernels apart and `fused_adam` from them by
name (benchmark/families/transformer_encdec.classify_kernel reads the
instruction head). The kernels are compiled here for a described
v5e:2x2 — nothing runs, no chip needed (on-chip-measurement guide §2):
the topology is described inside a module-scoped fixture, never at
import, and all such compiles stay in this one file. The op scopes of
the compiled step (`forward/layer_norm`, `optimize/adam`, under the
model's `fluid.name_scope`s) are HLO metadata and are read off a step
lowered on the CPU.
"""
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import paddle_tpu as fluid

# transformer_base at the benchmark's long cell: B=4 S=4096, 8 heads of 64
B, S, H, D = 4, 4096, 8, 64
BLOCK_Q, BLOCK_K = 512, 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to JAX's persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _custom_call_heads(text):
    """Instruction names of the TPU custom calls in optimized HLO."""
    return re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                      r'"tpu_custom_call"', text)


def _flash_module():
    # `paddle_tpu.kernels.flash_attention` the attribute is the function
    import importlib
    return importlib.import_module("paddle_tpu.kernels.flash_attention")


QKV = ((B, S, H, D), jnp.bfloat16)
BIAS = ((B, 1, 1, S), jnp.float32)


def test_flash_forward_is_named(one_chip, no_compile_cache):
    fa = _flash_module()

    def fwd(q, k, v, bias):
        return fa._fa_forward(q, k, v, bias, D ** -0.5, BLOCK_Q, BLOCK_K,
                              return_lse=True, layout="bshd",
                              causal=True)

    heads = _custom_call_heads(
        _compiled_text(fwd, one_chip, QKV, QKV, QKV, BIAS))
    assert heads and all(h.startswith("flash_attention_fwd")
                         for h in heads), heads


def _stems(heads):
    return {h.rsplit(".", 1)[0] if h.rsplit(".", 1)[-1].isdigit() else h
            for h in heads}


@pytest.mark.parametrize("want_dbias,names", [
    # the cell's attention: the fused backward, one kernel under the
    # dk/dv kernel's name that holds the [4096, 128] dq of a (batch,
    # head group) in VMEM; Mosaic takes it with its raised limit
    (False, {"flash_attention_fwd", "flash_attention_dkv"}),
    # a demanded dbias: the ds output follows the dq kernel's grid
    (True, {"flash_attention_fwd", "flash_attention_dq",
            "flash_attention_dkv"}),
], ids=["fused", "dbias_split"])
def test_flash_backward_kernels_are_named(one_chip, no_compile_cache,
                                          want_dbias, names):
    fa = _flash_module()

    def fwd_bwd(q, k, v, bias, g):
        out, lse = fa._fa_forward(q, k, v, bias, D ** -0.5, BLOCK_Q,
                                  BLOCK_K, return_lse=True,
                                  layout="bshd", causal=True)
        return fa._fa_backward(q, k, v, bias, out, lse, g, D ** -0.5,
                               BLOCK_Q, BLOCK_K, layout="bshd",
                               want_dbias=want_dbias,
                               causal=True)

    heads = _custom_call_heads(
        _compiled_text(fwd_bwd, one_chip, QKV, QKV, QKV, BIAS, QKV))
    assert _stems(heads) == names, heads
    assert len(heads) == len(names), heads


@pytest.mark.parametrize("kernel", ["fused_adam", "fused_sgd"])
def test_fused_optimizer_kernels_are_named(one_chip, no_compile_cache,
                                           kernel, monkeypatch):
    from paddle_tpu.kernels import fused_optimizer as fo
    from paddle_tpu.kernels import registry
    # the kernels ask the default backend, here the CPU, whether to run
    # under the Pallas interpreter: this compile is for the chip
    monkeypatch.setattr(registry, "interpret", lambda: False)
    # the base model's widest routed parameter: a 32,000 x 512 table
    table = ((32000, 512), jnp.float32)
    scalar = ((), jnp.float32)
    if kernel == "fused_adam":
        fn = lambda p, g, m, v, lr: fo.fused_adam(p, g, m, v, lr)
        shapes = (table, table, table, table, scalar)
    else:
        fn = lambda p, g, lr: fo.fused_sgd(p, g, lr)
        shapes = (table, table, scalar)
    heads = _custom_call_heads(_compiled_text(fn, one_chip, *shapes))
    assert heads and all(h.startswith(kernel) for h in heads), heads


@pytest.mark.parametrize("shape", [(2048, 6144), (16, 2048, 768),
                                   (16032, 2048), (2048, 576)],
                         ids=lambda s: "x".join(map(str, s)))
def test_fused_adam_updates_a_donated_parameter_in_place(
        one_chip, no_compile_cache, shape, monkeypatch):
    """kanana2_s4096's parameters at their published widths, p, m and v
    donated as the engine donates them: the kernel stands alone in the
    compiled program. No `reshape`, `copy` or `transpose` of a whole
    operand beside it and no temporary, where the flat `[rows, 128]`
    view cost seven relayouts a parameter (PR 30). (16032, 2048) ends
    in a partial row block; (2048, 576) lies K-minor (`{0,1}`) and is
    blocked as its transpose."""
    from paddle_tpu.kernels import fused_optimizer as fo
    from paddle_tpu.kernels import registry
    monkeypatch.setattr(registry, "interpret", lambda: False)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda p, g, m, v, lr: fo.fused_adam(p, g, m, v, lr),
                       donate_argnums=(0, 2, 3)).lower(x, x, x, x,
                                                      lr).compile()
    text = compiled.as_text()
    assert [h for h in _custom_call_heads(text)
            if h.startswith("fused_adam")]
    relaid = [(op, dims) for dims, op in re.findall(
        r" = f32\[([\d,]+)\]\S* (reshape|copy|transpose)\(", text)
        if math.prod(map(int, dims.split(","))) == math.prod(shape)]
    assert not relaid, relaid
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_flash_kernels_compile_at_latent_attention_widths(one_chip,
                                                         no_compile_cache):
    """q/k 192 wide, v 128 (32 heads, B=1 S=4096, the kanana2_s4096
    cell's attention): two heads share a 384- and a 256-lane block, and
    Mosaic takes the forward and the fused backward under their names,
    the backward with 12 MiB of resident dq over its default limit."""
    fa = _flash_module()
    qk = ((1, S, 32, 192), jnp.bfloat16)
    v = ((1, S, 32, 128), jnp.bfloat16)

    def fwd_bwd(q, k, v, g):
        out, lse = fa._fa_forward(q, k, v, None, 192 ** -0.5, BLOCK_Q,
                                  BLOCK_K, return_lse=True,
                                  layout="bshd", causal=True)
        return fa._fa_backward(q, k, v, None, out, lse, g, 192 ** -0.5,
                               BLOCK_Q, BLOCK_K, layout="bshd",
                               causal=True)[:3]

    text = _compiled_text(fwd_bwd, one_chip, qk, qk, v, v)
    heads = _custom_call_heads(text)
    assert _stems(heads) == {"flash_attention_fwd",
                             "flash_attention_dkv"}, heads
    assert len(heads) == 2, heads
    # v is not padded to the q/k width: no 32 x 192 = 6144-wide v, out
    # or dv anywhere
    assert "bf16[1,4096,4096]" in text


def test_flash_kernels_compile_with_four_key_heads_and_a_keep_mask(
        one_chip, no_compile_cache):
    """32 query heads over 4 key / value heads of 128 under an int8 keep
    mask, B=1 S=8192 (the keye2_s8192 cell's attention): Mosaic takes the
    forward and the FUSED backward (8 MiB of resident dq) under their
    names, k and v are never expanded to 32 heads, and dk / dv leave at 4
    heads."""
    fa = _flash_module()
    s = 8192
    q = ((1, s, 32, 128), jnp.bfloat16)
    kv = ((1, s, 4, 128), jnp.bfloat16)
    mask = ((1, 1, s, s), jnp.int8)
    shapes = {}

    def fwd_bwd(q, k, v, m, g):
        out, lse = fa._fa_forward(q, k, v, m, 128 ** -0.5, BLOCK_Q,
                                  BLOCK_K, return_lse=True,
                                  layout="bshd", causal=True)
        dq, dk, dv, _ = fa._fa_backward(q, k, v, m, out, lse, g,
                                        128 ** -0.5, BLOCK_Q, BLOCK_K,
                                        layout="bshd", causal=True)
        shapes.update(dq=dq.shape, dk=dk.shape, dv=dv.shape)
        return dq, dk, dv

    from paddle_tpu.kernels import registry
    registry.reset_stats()
    text = _compiled_text(fwd_bwd, one_chip, q, kv, kv, mask, q)
    heads = _custom_call_heads(text)
    assert _stems(heads) == {"flash_attention_fwd",
                             "flash_attention_dkv"}, heads
    assert len(heads) == 2, heads
    assert shapes == {"dq": (1, s, 32, 128), "dk": (1, s, 4, 128),
                      "dv": (1, s, 4, 128)}
    took = registry.dispatch_stats()["per_kernel"]["flash_attention"]
    assert took == {"single_fwd": 1, "fused_bwd": 1, "narrow_lse": 1}
    # the kernels read k and v at 4 heads (512 lanes): the custom calls
    # take bf16[1,8192,512] operands, and the mask as int8
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert all("bf16[1,8192,512]" in l and "s8[1,8192,8192]" in l
               for l in calls), calls


def test_sparse_index_kernels_compile_under_their_names(one_chip,
                                                        no_compile_cache,
                                                        monkeypatch):
    """The index kernels at the keye2_s8192 cell's sizes: 16 index heads
    of 64 over 8,192 tokens in [512, 512] tiles, then the exact top-2048
    threshold 128 rows at a time with 14 MiB of VMEM over the default."""
    from paddle_tpu.kernels import registry, sparse_index
    monkeypatch.setattr(registry, "interpret", lambda: False)
    s = 8192

    def mask(q, k, w):
        return sparse_index.index_mask(q, k, w, 2048, True)

    text = _compiled_text(mask, one_chip,
                          ((1, s, 16, 64), jnp.bfloat16),
                          ((1, s, 64), jnp.bfloat16),
                          ((1, s, 16), jnp.float32))
    heads = _custom_call_heads(text)
    assert [h.split(".")[0] for h in heads] == [
        "sparse_index_scores", "sparse_index_select"], heads
    # float32 scores between them, an int8 mask out beside each row's
    # count (the mask is not read again to count it); never [16, S, S]
    assert "f32[1,8192,8192]" in text and "s8[1,8192,8192]" in text
    assert "s32[1,8192,1]" in text
    assert "[1,16,8192,8192]" not in text and "[16,8192,8192]" not in text


@pytest.mark.parametrize("which", ["fwd", "dx", "dw"])
def test_grouped_matmul_kernels_are_named(one_chip, no_compile_cache,
                                          which, monkeypatch):
    """The expert layer's three kernels at the kanana2_s4096 cell's
    widths: 4,096 tokens, top-6, 16 experts held of 2048 x 768."""
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.kernels import registry
    monkeypatch.setattr(registry, "interpret", lambda: False)
    held, d, f = 16, 2048, 768
    rows = gm.buffer_rows(4096 * 6, held)
    assert rows == 26624
    choice = ((4096 * 6,), jnp.int32)
    wide, narrow = ((rows, d), jnp.bfloat16), ((rows, f), jnp.bfloat16)
    up, down = ((held, d, f), jnp.bfloat16), ((held, f, d), jnp.bfloat16)

    def both(fn):
        return lambda c, a, b, a2, b2: (
            fn(a, b, gm.plan_rows(c, held)), fn(a2, b2,
                                                gm.plan_rows(c, held)))
    if which == "fwd":
        fn = both(lambda x, w, plan: gm.gmm(x, w, plan, True))
        shapes = (choice, wide, up, narrow, down)
    elif which == "dx":
        fn = both(lambda dy, w, plan: gm.gmm_dx(dy, w, plan, True))
        shapes = (choice, narrow, up, wide, down)
    else:
        fn = both(lambda x, dy, plan: gm.gmm_dw(x, dy, plan, held, True))
        shapes = (choice, wide, narrow, narrow, wide)
    heads = _custom_call_heads(_compiled_text(fn, one_chip, *shapes))
    assert len(heads) == 2 and all(
        h.startswith("moe_grouped_matmul_" + which) for h in heads), heads


# the Mamba-2 mixer of the twotower_s4096 cell: 64 heads of 64 in 8 groups,
# state 128, one sequence of 4,096 tokens in chunks of 128
SSD_X = ((1, 4096, 64, 64), jnp.bfloat16)
SSD_BC = ((1, 4096, 8, 128), jnp.bfloat16)
SSD_DT, SSD_HEAD = ((1, 4096, 64), jnp.float32), ((64,), jnp.float32)


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_ssd_kernels_compile_under_their_names(one_chip, no_compile_cache,
                                               which, monkeypatch):
    """`mamba2_ssd_fwd` / `mamba2_ssd_bwd` at the cell's sizes: one
    custom call each, the chunk-start states float32 [1, 32, 4096, 128]
    between them, and never a state a token."""
    from paddle_tpu.kernels import mamba2_ssd as ssd
    from paddle_tpu.kernels import registry
    monkeypatch.setattr(registry, "interpret", lambda: False)
    shapes = (SSD_X, SSD_DT, SSD_HEAD, SSD_BC, SSD_BC, SSD_HEAD)
    if which == "fwd":
        def fn(*a):
            return ssd.ssd(*a, True)
    else:
        def fn(x, dt, a, b, c, d, states, dy):
            return ssd.ssd_grad(x, dt, a, b, c, d, states, dy, True)
        shapes += (((1, 32, 64, 64, 128), jnp.float32), SSD_X)
    text = _compiled_text(fn, one_chip, *shapes)
    heads = _custom_call_heads(text)
    assert [h.split(".")[0] for h in heads] == ["mamba2_ssd_" + which], heads
    assert "f32[1,32,4096,128]" in text
    assert "[1,4096,64,64,128]" not in text and "[4096,64,64,128]" not in text


@pytest.mark.parametrize("which", ["fwd", "dx", "dw"])
def test_grouped_matmul_kernels_take_an_expert_width_of_1856(
        one_chip, no_compile_cache, which, monkeypatch):
    """The same three kernels for the ungated expert of the
    twotower_s4096 cell, two matrices of 2688 x 1856 (1856 = 29 x 64 is
    no multiple of 128: it stays whole in every block, the dw kernel's
    output is blocked along the 2688 side, and a call whose
    double-buffered blocks pass Mosaic's 16 MiB asks for more)."""
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.kernels import registry
    monkeypatch.setattr(registry, "interpret", lambda: False)
    held, d, f = 8, 2688, 1856
    rows = gm.prefix_rows(4096 * 6, held, 128)[0]
    assert rows == 2560
    assert gm._eligible(registry.signature(
        "moe_experts", jnp.zeros((rows, d), jnp.bfloat16),
        jnp.zeros((held, d, f), jnp.bfloat16)))
    tiles = ((rows // gm.TILE_ROWS,), jnp.int32)
    wide, narrow = ((rows, d), jnp.bfloat16), ((rows, f), jnp.bfloat16)
    up, down = ((held, d, f), jnp.bfloat16), ((held, f, d), jnp.bfloat16)

    def both(fn):
        def run(te, na, a, b, a2, b2):
            plan = {"tile_expert": te, "n_active": na}
            return fn(a, b, plan), fn(a2, b2, plan)
        return run
    if which == "fwd":
        fn = both(lambda x, w, plan: gm.gmm(x, w, plan, True))
        shapes = (wide, up, narrow, down)
    elif which == "dx":
        fn = both(lambda dy, w, plan: gm.gmm_dx(dy, w, plan, True))
        shapes = (narrow, up, wide, down)
    else:
        fn = both(lambda x, dy, plan: gm.gmm_dw(x, dy, plan, held, True))
        shapes = (wide, narrow, narrow, wide)
    heads = _custom_call_heads(_compiled_text(
        fn, one_chip, tiles, ((1,), jnp.int32), *shapes))
    assert len(heads) == 2 and all(
        h.startswith("moe_grouped_matmul_" + which) for h in heads), heads


def test_flash_kernels_compile_with_packed_heads_over_shared_key_heads(
        one_chip, no_compile_cache):
    """32 query heads over 8 key / value heads of 64, B=1 S=8192 (the
    lfm2_s8192 cell's attention): two query heads a 128-lane block, both
    reading one key head, which is one half of a key lane block picked by
    the grid step. Mosaic takes the forward and the FUSED backward under
    their names, k and v are never expanded to 32 heads (the custom
    calls read bf16[1,8192,512]), and dk / dv leave at 8 heads."""
    fa = _flash_module()
    s = 8192
    q = ((1, s, 32, 64), jnp.bfloat16)
    kv = ((1, s, 8, 64), jnp.bfloat16)
    shapes = {}
    plan = fa._Plan("bshd", 1, 32, s, s, 64, BLOCK_Q, BLOCK_K, 64, 8)
    assert plan.hpb == 2 and plan.group == 4 and plan.packed_shared

    def fwd_bwd(q, k, v, g):
        out, lse = fa._fa_forward(q, k, v, None, 64 ** -0.5, BLOCK_Q,
                                  BLOCK_K, return_lse=True,
                                  layout="bshd", causal=True)
        dq, dk, dv, _ = fa._fa_backward(q, k, v, None, out, lse, g,
                                        64 ** -0.5, BLOCK_Q, BLOCK_K,
                                        layout="bshd", causal=True)
        shapes.update(dq=dq.shape, dk=dk.shape, dv=dv.shape)
        return dq, dk, dv

    from paddle_tpu.kernels import registry
    registry.reset_stats()
    text = _compiled_text(fwd_bwd, one_chip, q, kv, kv, q)
    heads = _custom_call_heads(text)
    assert _stems(heads) == {"flash_attention_fwd",
                             "flash_attention_dkv"}, heads
    assert len(heads) == 2, heads
    assert shapes == {"dq": (1, s, 32, 64), "dk": (1, s, 8, 64),
                      "dv": (1, s, 8, 64)}
    took = registry.dispatch_stats()["per_kernel"]["flash_attention"]
    assert took == {"pipelined_fwd": 1, "fused_bwd": 1, "narrow_lse": 1}
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert all("bf16[1,8192,512]" in l for l in calls), calls


def test_window_flash_kernels_compile_under_their_names(one_chip,
                                                       no_compile_cache):
    """32 query heads over 4 key / value heads of 128, B=1 S=8192 in 512
    x 1024 blocks, a window of 1,024 (the mellum2_s8192 cell's window
    layers): Mosaic takes the banded forward and the banded FUSED
    backward under their own names, `flash_attention_window_fwd` /
    `_bwd`, apart from the causal layer's; the routing counts the site's
    `window` outcome beside `fused_bwd`; k and v go in at 4 heads."""
    fa = _flash_module()
    s = 8192
    q = ((1, s, 32, 128), jnp.bfloat16)
    kv = ((1, s, 4, 128), jnp.bfloat16)

    def fwd_bwd(q, k, v, g):
        out, lse = fa._fa_forward(q, k, v, None, 128 ** -0.5, BLOCK_Q,
                                  BLOCK_K, return_lse=True, layout="bshd",
                                  causal=True, window=1024)
        return fa._fa_backward(q, k, v, None, out, lse, g, 128 ** -0.5,
                               BLOCK_Q, BLOCK_K, layout="bshd",
                               causal=True, window=1024)[:3]

    from paddle_tpu.kernels import registry
    registry.reset_stats()
    text = _compiled_text(fwd_bwd, one_chip, q, kv, kv, q)
    heads = _custom_call_heads(text)
    assert _stems(heads) == {"flash_attention_window_fwd",
                             "flash_attention_window_bwd"}, heads
    assert len(heads) == 2, heads
    took = registry.dispatch_stats()["per_kernel"]["flash_attention"]
    assert took.get("window") == 1 and took.get("fused_bwd") == 1, took
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert all("bf16[1,8192,512]" in l for l in calls), calls


def test_grouped_matmul_kernels_take_an_expert_width_of_896(monkeypatch,
                                                           one_chip,
                                                           no_compile_cache):
    """The three kernels at the mellum2_s8192 cell's expert: 8 held of
    64, top-8 over 8,192 tokens, matrices of 2304 x 896 (7 x 128, 18 x
    128), under their names."""
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.kernels import registry
    monkeypatch.setattr(registry, "interpret", lambda: False)
    held, d, f = 8, 2304, 896
    rows = gm.prefix_rows(8192 * 8, held, 64)[0]
    assert gm._eligible(registry.signature(
        "moe_experts", jnp.zeros((rows, d), jnp.bfloat16),
        jnp.zeros((held, d, f), jnp.bfloat16)))
    tiles = ((rows // gm.TILE_ROWS,), jnp.int32)
    wide, narrow = ((rows, d), jnp.bfloat16), ((rows, f), jnp.bfloat16)
    up = ((held, d, f), jnp.bfloat16)

    def run(fn):
        return lambda te, na, a, b: fn(a, b, {"tile_expert": te,
                                              "n_active": na})
    for which, fn, shapes in (
            ("fwd", run(lambda x, w, p: gm.gmm(x, w, p, True)), (wide, up)),
            ("dx", run(lambda dy, w, p: gm.gmm_dx(dy, w, p, True)),
             (narrow, up)),
            ("dw", run(lambda x, dy, p: gm.gmm_dw(x, dy, p, held, True)),
             (wide, narrow))):
        heads = _custom_call_heads(_compiled_text(
            fn, one_chip, tiles, ((1,), jnp.int32), *shapes))
        assert len(heads) == 1 and heads[0].startswith(
            "moe_grouped_matmul_" + which), heads


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_short_conv_kernels_compile_under_their_names(
        one_chip, no_compile_cache, which, monkeypatch):
    """`gated_short_conv_fwd` / `_bwd` at the lfm2_s8192 cell's sizes (one
    sequence of 8,192 tokens, D 2048, three taps, bfloat16): one custom
    call each; the backward writes dX [1, 8192, 6144] whole (no three
    thirds concatenated after it) and the filter's gradient in
    float32."""
    from paddle_tpu.kernels import registry
    from paddle_tpu.kernels import short_conv as sc
    monkeypatch.setattr(registry, "interpret", lambda: False)
    x, w = ((1, 8192, 6144), jnp.bfloat16), ((2048, 3), jnp.float32)
    assert sc._eligible(registry.signature(
        "gated_short_conv", jnp.zeros(x[0], x[1]), jnp.zeros(w[0], w[1])))
    if which == "fwd":
        text = _compiled_text(lambda x, w: sc.conv(x, w, True), one_chip,
                              x, w)
    else:
        text = _compiled_text(
            lambda x, w, g: sc.conv_grad(x, w, g, True), one_chip, x, w,
            ((1, 8192, 2048), jnp.bfloat16))
        assert "concatenate" not in text
    heads = _custom_call_heads(text)
    assert [h.split(".")[0] for h in heads] == \
        ["gated_short_conv_" + which], heads


@pytest.mark.parametrize("which", ["fwd", "dx", "dw"])
def test_grouped_matmul_kernels_take_an_expert_width_of_1536(
        one_chip, no_compile_cache, which, monkeypatch):
    """The three kernels at the lfm2_s8192 cell's expert: 8 held of 64,
    top-4 over 8,192 tokens, matrices of 2048 x 1536 (12 x 128)."""
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.kernels import registry
    monkeypatch.setattr(registry, "interpret", lambda: False)
    held, d, f = 8, 2048, 1536
    rows = gm.prefix_rows(8192 * 4, held, 64)[0]
    assert gm._eligible(registry.signature(
        "moe_experts", jnp.zeros((rows, d), jnp.bfloat16),
        jnp.zeros((held, d, f), jnp.bfloat16)))
    tiles = ((rows // gm.TILE_ROWS,), jnp.int32)
    wide, narrow = ((rows, d), jnp.bfloat16), ((rows, f), jnp.bfloat16)
    up = ((held, d, f), jnp.bfloat16)

    def run(fn):
        return lambda te, na, a, b: fn(a, b, {"tile_expert": te,
                                              "n_active": na})
    if which == "fwd":
        fn, shapes = run(lambda x, w, p: gm.gmm(x, w, p, True)), (wide, up)
    elif which == "dx":
        fn = run(lambda dy, w, p: gm.gmm_dx(dy, w, p, True))
        shapes = (narrow, up)
    else:
        fn = run(lambda x, dy, p: gm.gmm_dw(x, dy, p, held, True))
        shapes = (wide, narrow)
    heads = _custom_call_heads(_compiled_text(
        fn, one_chip, tiles, ((1,), jnp.int32), *shapes))
    assert len(heads) == 1 and heads[0].startswith(
        "moe_grouped_matmul_" + which), heads


@pytest.mark.parametrize("cell", ["kanana2_s4096", "twotower_s4096"])
def test_expert_layer_compiles_with_the_combine_kernel(
        one_chip, no_compile_cache, cell, monkeypatch):
    """An expert layer's forward and backward over the first prefix of
    its row buffer at two cells' sizes (D 2048, gated, and D 2688 = 21 x
    128, ungated): the combine out of the prefix is the kernel
    `moe_combine` (its `[T, D block]` float32 output resident in VMEM),
    once forward on bf16 rows and once backward on float32 rows, beside
    `moe_grouped_matmul_fwd` / `_dx` / `_dw`; no `[T * top_k, D]` array
    is left in the layer."""
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.kernels import registry
    from paddle_tpu.ops import decoder
    monkeypatch.setattr(registry, "interpret", lambda: False)
    t, k, experts = 4096, 6, 128
    held, d, f, gated = {"kanana2_s4096": (16, 2048, 768, True),
                         "twotower_s4096": (8, 2688, 1856, False)}[cell]
    rows = gm.prefix_rows(t * k, held, experts)[0]
    assert gm.combine_by_rows(rows, t * k)
    bf16 = jnp.bfloat16

    def layer(choice, x, weight, dout, wu, wd, wg=None):
        plan = gm.plan_rows(choice, held)
        static = dict(rows=rows, held=held, kernels=True)
        out, gate, up = decoder._experts_forward(
            x, weight, wg, wu, wd, plan, out_dtype=bf16, **static)
        return out, decoder._experts_backward(
            x, weight, wg, wu, wd, plan, gate, up, dout, shape=x.shape,
            **static)
    shapes = [((t * k,), jnp.int32), ((t, d), bf16), ((t, k), jnp.float32),
              ((t, d), bf16), ((held, d, f), bf16), ((held, f, d), bf16)]
    if gated:
        shapes.append(((held, d, f), bf16))
    text = _compiled_text(layer, one_chip, *shapes)
    stems = sorted(h.split(".")[0] for h in _custom_call_heads(text))
    n = 3 if gated else 2
    assert stems == sorted(
        ["moe_combine"] * 2 + ["moe_grouped_matmul_fwd"] * n
        + ["moe_grouped_matmul_dx"] * n + ["moe_grouped_matmul_dw"] * n), \
        stems
    assert f"[{t * k},{d}]" not in text and f"[{t},{k},{d}]" not in text


def test_transformer_step_relays_out_no_projection(one_chip,
                                                   no_compile_cache,
                                                   monkeypatch):
    """A reduced Transformer training step (one encoder and one decoder
    layer at the base widths, d 512 in 8 heads, S=128, B=8, AMP and
    Adam) compiled for a described v5e: the standalone `copy`s of
    activations in its entry computation are four `[B, S, d]` a site of
    the three attentions, q, k and v after their bias and one
    projection's gradient, put S minor for the score dots (ROADMAP S3).
    `mul` flattening X to `[B*S, d]` and back added 28 more. The bound,
    24 such copies (25.2 MB), lies between the two readings it was set
    from: 12 copies, 12.6 MB, with X contracted in its own rank, and
    54.5 MB with the flatten."""
    from paddle_tpu import models
    from paddle_tpu.core import engine
    from paddle_tpu.core.scope import Scope
    b, s, d = 8, 128, 512
    cfg = models.transformer.TransformerConfig(
        src_vocab_size=1024, trg_vocab_size=1024, d_model=d,
        d_inner=4 * d, n_head=d // 64, n_layer=1, dropout=0.0,
        fuse_attention=True)
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cost, _, _ = models.transformer_train(cfg)
        fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.AdamOptimizer(learning_rate=1e-3)).minimize(cost)
    got = {}

    class Compiled(Exception):
        pass

    def compile_for_the_chip(self, clock, program, traced, donated, const,
                             arrays, key):
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                           sharding=one_chip),
            (donated, const, arrays, key))
        got["text"] = traced.fn.lower(*args).compile().as_text()
        raise Compiled()

    with fluid.scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        monkeypatch.setattr(engine.Engine, "_first_dispatch",
                            compile_for_the_chip)
        with pytest.raises(Compiled):
            exe.run(main, feed=models.transformer.make_batch(cfg, b, s, s),
                    fetch_list=[cost])
    text = got["text"]
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    width = {"bf16": 2, "f32": 4}
    copies = [(dt, dims, math.prod(map(int, dims.split(",")))
               * width.get(dt, 4))
              for dt, dims in re.findall(
                  r"= (\w+)\[([\d,]+)\]\{[^}]*\} copy\(", entry)]
    # a copy of one head over every token or more is an activation's
    activations = [c for c in copies if c[2] >= b * s * 64 * 2]
    qkv = b * s * d * 2
    assert sum(c[2] for c in activations) <= 24 * qkv, activations


def test_no_other_kernel_reads_as_flash_or_adam():
    """The accepted classifier maps a head holding `adam` to fused_adam
    and one holding `flash` or `kern` to flash attention: no other
    kernel's name may hold any of them. The expert layer's combine
    kernel holds none of the families' hints at all, so the grouped
    matmuls' metrics read the three matmul kernels alone."""
    from paddle_tpu.tuning import variants
    names = ["fused_sgd", "quantized_matmul", "moe_grouped_matmul_fwd",
             "moe_grouped_matmul_dx", "moe_grouped_matmul_dw",
             "sparse_index_scores", "sparse_index_select",
             "mamba2_ssd_fwd", "mamba2_ssd_bwd", "moe_combine",
             "gated_short_conv_fwd", "gated_short_conv_bwd",
             "selective_scan_fwd", "selective_scan_bwd"] + [
        f"tuned_matmul_{v.epilogue}_{v.bm}x{v.bn}x{v.bk}"
        for v in variants.enumerate_variants()]
    for n in names:
        assert not any(h in n for h in ("adam", "flash", "kern")), n
    assert not any(h in "moe_combine" for h in (
        "moe_grouped_matmul", "mamba2_ssd", "sparse_index"))
    src = open(variants.__file__).read()
    assert 'name=f"tuned_matmul_{variant.epilogue}_{bm}x{bn}x{bk}"' in src


def _lowered_step_text():
    """Un-optimized HLO text (with metadata) of a small Transformer
    training step, lowered on the CPU through the engine's own
    callable."""
    from paddle_tpu import models
    from paddle_tpu.core.scope import Scope
    cfg = models.transformer.TransformerConfig(
        src_vocab_size=64, trg_vocab_size=64, d_model=32, d_inner=64,
        n_head=2, n_layer=1, dropout=0.0, fuse_attention=True)
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cost, _, _ = models.transformer_train(cfg)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(cost)
    scope = Scope()
    feed = models.transformer.make_batch(cfg, 2, 8, 8)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[cost])
        compiled = exe._engine.compiled_step(main, scope, feed,
                                             [cost.name])
    return main, compiled.as_text()


def test_compiled_step_carries_op_scopes():
    main, text = _lowered_step_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    assert names, "the compiled step's HLO carries no op_name metadata"

    def has(fragment):
        return any(fragment in n for n in names)

    assert has("forward/layer_norm")
    assert has("backward/")
    assert has("optimize/adam")
    # fluid.name_scope: the model's scopes lead the role and the type,
    # on forward ops, on the grad ops made from them and on the update
    # op of a parameter created inside
    assert has("enc_0/self_attn/forward/fused_attention")
    assert has("dec_0/cross_attn/backward/")
    assert has("enc_0/ffn/optimize/adam")
    assert has("embed/forward/lookup_table")
    assert has("loss/forward/")
    ops = main.global_block().ops
    scoped = [op for op in ops if op.attr("op_namescope", "")]
    assert len(scoped) > len(ops) // 2
    assert all(op.attr("op_namescope").endswith("/") for op in scoped)


def test_name_scope_nests_and_closes():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        with fluid.name_scope("a"):
            with fluid.name_scope("b"):
                y = fluid.layers.fc(x, size=2)
            z = fluid.layers.relu(y)
        out = fluid.layers.mean(z)
    by_type = {op.type: op.attr("op_namescope", "")
               for op in main.global_block().ops}
    assert by_type["mul"] == "a/b/"
    assert by_type["relu"] == "a/"
    assert by_type["mean"] == ""


def test_hybrid_step_carries_its_scopes_and_its_counter():
    """A one-part-a-layer model: the op scopes `layer_<i>/mamba`, `/moe`
    and `/attn` lead the role and the type in the compiled step's
    metadata, and the step overwrites the persistable int32
    `mamba_ssd_tokens` [mixer layers] with the tokens each mixer
    scanned."""
    import numpy as np
    from paddle_tpu import models
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.observability import mamba
    cfg = models.DecoderLMConfig(
        vocab_size=64, hidden_size=32, hybrid_override_pattern="ME*M",
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=8,
        chunk_size=8, n_routed_experts=8, experts_held=4,
        num_experts_per_tok=2, moe_intermediate_size=16, n_shared_experts=1,
        moe_shared_expert_intermediate_size=24, mlp_hidden_act="relu2",
        layer_norm_epsilon=1e-5)
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cost, _, _ = models.decoder_lm_train(cfg)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(cost)
    ids = np.random.default_rng(0).integers(0, 64, (2, 13), dtype=np.int32)
    feed = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        assert mamba.scanned_tokens(scope).tolist() == [0, 0]
        exe.run(main, feed=feed, fetch_list=[cost])
        exe.run(main, feed=feed, fetch_list=[cost])
        text = exe._engine.compiled_step(main, scope, feed,
                                         [cost.name]).as_text()
    # overwritten, not added up: two steps read one step's tokens
    assert mamba.scanned_tokens(scope).tolist() == [2 * 12, 2 * 12]
    var = main.global_block().var(mamba.SSD_TOKENS_VAR)
    assert var.persistable and tuple(var.shape) == (2,)
    names = set(re.findall(r'op_name="([^"]+)"', text))

    def has(fragment):
        return any(fragment in n for n in names)

    assert has("layer_0/mamba/forward/mamba2_ssd")
    assert has("layer_0/mamba/forward/causal_conv1d")
    assert has("layer_3/mamba/backward/")
    assert has("layer_1/moe/forward/moe_experts")
    assert has("layer_2/attn/forward/fused_attention")
    assert has("layer_0/mamba/optimize/adam")
