"""Fused ops (reference operators/fused/: fused_elemwise_activation,
fused_embedding_seq_pool, fusion_lstm/gru, ...). On TPU XLA fuses the
elementwise families automatically, so the ops here are the ones that
need a real kernel: fused multi-head attention via the Pallas flash
kernel (kernels/flash_attention.py), and what differential attention
makes of its two maps a head."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op, override_grad_lowering
from ..core.amp import amp_cast


def _auto_block(S, target):
    """Largest 128-multiple divisor of S not exceeding target — a
    non-dividing block would disqualify the shape from the kernel path
    entirely (e.g. S=2560 with a raw 1024 target)."""
    if S % 128:
        return min(128, S)
    for cand in range(min(target, S), 0, -128):
        if S % cand == 0:
            return cand
    return min(128, S)


def _attn_args(ctx):
    """Shared forward/grad parsing: ONE source for scale, block sizes,
    layout and the dropout spec, so the backward can never silently
    differentiate a different function than the forward executed."""
    from ..kernels.flash_attention import _seq_len
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    bias = ctx.input("BiasQK") if ctx.has_input("BiasQK") else None
    layout = ctx.attr("layout", "bhsd") or "bhsd"
    scale = ctx.attr("scale", None)
    if scale is None or scale <= 0:
        scale = float(q.shape[-1]) ** -0.5
    # bias included: the FORWARD context white-casts every float input
    # (ExecContext), but the grad op is policy-unlisted — casting here
    # keeps the backward differentiating exactly the function the
    # forward executed (and the fallback's recomputed forward
    # bit-identical to it)
    q, k, v, bias = amp_cast("fused_attention", q, k, v, bias)
    # Block-size policy: user-set attrs win; otherwise scale with the
    # sequence — r4 A/B at B=4 H=8 S=4096 D=64: bq=512/bk=1024 runs
    # the forward kernel 2.3x faster than 128/128 (10.99 vs 25.07 ms;
    # bigger KV tiles amortize per-grid-step DMA + loop overhead) and
    # beats XLA's composed attention (13.77 ms)
    bq = int(ctx.attr("block_q", 0) or 0)
    bk = int(ctx.attr("block_k", 0) or 0)
    Sq, Sk = _seq_len(q, layout), _seq_len(k, layout)
    if bq <= 0:
        bq = _auto_block(Sq, 512) if Sq >= 1024 else min(128, Sq)
    if bk <= 0:
        bk = _auto_block(Sk, 1024) if Sk >= 1024 else min(128, Sk)
    p_drop = float(ctx.attr("dropout_prob", 0.0) or 0.0)
    causal = bool(ctx.attr("causal", False))
    window = int(ctx.attr("window", 0) or 0) or None
    drop = None
    if p_drop and not ctx.attr("is_test", False):
        # u8 keep-threshold, with BOTH edges handled exactly like the
        # dropout op (ops/nn.py): t >= 256 keeps everything (no-op),
        # t <= 0 drops everything (the lowerings emit zeros)
        t = int(round((1.0 - p_drop) * 256.0))
        if t < 256:
            drop = (ctx.rng(), max(t, 0))
    return q, k, v, bias, layout, scale, bq, bk, drop, causal, window


@register_op("fused_attention", intermediate_outputs=("SoftmaxLse",))
def fused_attention(ctx):
    """Q/K/V: [B, H, S, D] (layout "bhsd") or [B, S, H, D] ("bshd"); K
    and V may have fewer heads than Q (grouped queries: query head g
    reads key head g // (H / Hkv); dK and dV leave at K's and V's own
    head count, and the kernels never expand K or V);
    optional BiasQK [B, 1|H, Sq|1, Sk]: additive where float, a keep
    MASK where integer (scores where it is 0 are masked out; it takes
    no gradient; `sparse_attention_index` makes one inside the step).
    attrs: scale (default d^-0.5), block_q, block_k, layout,
    dropout_prob (attention-weights dropout, reference
    dist_transformer.py:1043-1044 — applied in BOTH regimes; the Pallas
    kernels regenerate the mask from the hardware PRNG per block),
    causal (mask rows >= cols; the kernels SKIP fully-masked KV
    blocks and elide their DMA), window (a causal site's sliding
    window: rows - window < cols <= rows; the kernels' grids step over
    the band's blocks alone, `flash_attention_window_*`).
    WindowPairs (optional, int32 [1]): the (query, key) pairs the
    window admits, B x its pairs in one head, for the
    `window_attn_pairs` counter (observability/window_attention.py).
    SoftmaxLse (intermediate, float32 [B, H, Sq]): the softmax
    log-sum-exp the forward kernel wrote, carried to the grad op so the
    backward kernels need no second forward. The kernels write and
    read it in this form themselves (docs/KERNELS.md). Only the kernel
    path in training writes a real value; everywhere else it is zeros
    nothing reads."""
    from ..kernels.flash_attention import (
        _fa_forward, _attn_reference, use_kernel_path, _dims,
        admitted_pairs)
    res_t = jnp.result_type(ctx.input("Q"))
    q, k, v, bias, layout, scale, bq, bk, drop, causal, window = \
        _attn_args(ctx)
    lse = None
    if drop is not None and drop[1] == 0:
        # dropout_prob ~ 1.0: everything dropped
        out = jnp.zeros(q.shape[:-1] + v.shape[-1:], res_t)
    elif use_kernel_path(q, k, bq, bk, layout, v):
        # long-context regime: Pallas flash kernels, O(S) HBM
        if ctx.attr("is_test", False):
            # inference: no grad op will consume lse — skip the
            # un-DCE-able lse output entirely
            out = _fa_forward(q, k, v, bias, scale, bq, bk,
                              layout=layout, causal=causal, window=window)
        else:
            # XLA does not merge two Mosaic custom calls, so the grad
            # op cannot get (out, lse) by repeating this call for free:
            # it reads Out and the narrow lse stored here
            out, lse = _fa_forward(q, k, v, bias, scale, bq, bk,
                                   return_lse=True, layout=layout,
                                   causal=causal, dropout=drop,
                                   window=window)
    else:
        # shape-bounded regime / CPU / odd shapes: XLA's fully-fused
        # composed formulation is faster while [Sq,Sk] fits (see the
        # measured dispatch table in kernels/flash_attention.py)
        out = _attn_reference(q, k, v, bias, scale, layout=layout,
                              dropout=drop, causal=causal, window=window)
    ctx.set_output("Out", out.astype(res_t))
    if window is not None and ctx.has_output("WindowPairs"):
        B, _, Sq, _ = _dims(q, layout)
        ctx.set_output("WindowPairs", jnp.full(
            (1,), B * admitted_pairs(Sq, _dims(k, layout)[2], window),
            jnp.int32))
    if lse is None:
        # never read (the grad op takes this same branch), never
        # fetched: XLA removes it from the compiled step
        lse = jnp.zeros(_dims(q, layout)[:3], jnp.float32)
    ctx.set_output("SoftmaxLse", lse)


@override_grad_lowering("fused_attention")
def fused_attention_grad(ctx):
    """Hand-written grad: the generic vjp would route through
    flash_attention's custom_vjp, which computes dbias whenever a bias
    is PRESENT — but a multi-output Pallas call cannot DCE its ds
    output, so an attention MASK (additive bias built from feeds, never
    differentiated) would pay an O(B*H*Sq*Sk) f32 buffer per site
    (measured 2.1 GB at B=4 S=4096). Here dbias work happens only when
    BiasQK@GRAD is actually bound. On the kernel path (out, lse) are
    the forward op's own Out and SoftmaxLse: a recomputed forward is a
    second Mosaic custom call, which XLA does not merge with the first
    (36 forward calls for 18 attentions, PERF.md PR 26)."""
    from ..kernels.flash_attention import (
        _fa_forward, _fa_backward, _attn_reference, use_kernel_path)
    op = ctx.op
    q, k, v, bias, layout, scale, bq, bk, drop, causal, window = \
        _attn_args(ctx)

    g_names = op.input("Out@GRAD")
    dout = ctx.env[g_names[0]]

    def _bound(slot):
        names = op.output(slot + "@GRAD")
        return bool(names and names[0])

    if drop is not None and drop[1] == 0:
        # forward emitted constant zeros: nothing flows back
        dq, dk, dv = (jnp.zeros_like(x) for x in (q, k, v))
        dbias = None if bias is None else jnp.zeros_like(bias)
    elif use_kernel_path(q, k, bq, bk, layout, v):
        if ctx.has_input("SoftmaxLse") and \
                not ctx.attr("is_test", False):
            # read past ctx.input(): lse stays float32 under any amp
            # list; Out is the kernel's own result cast to the primal
            # dtype, so casting back is exact
            out = ctx.env[op.input("Out")[0]].astype(q.dtype)
            lse = ctx.env[op.input("SoftmaxLse")[0]]
        else:
            # the one remaining recompute: SoftmaxLse unbound (a
            # program serialised before the slot existed, a hand-built
            # op desc) or a forward at is_test, which wrote no lse
            out, lse = _fa_forward(q, k, v, bias, scale, bq, bk,
                                   return_lse=True, layout=layout,
                                   causal=causal, dropout=drop,
                                   window=window)
        dq, dk, dv, dbias = _fa_backward(
            q, k, v, bias, out, lse, dout.astype(q.dtype), scale, bq,
            bk, layout=layout, want_dbias=_bound("BiasQK"),
            causal=causal, dropout=drop, window=window)
    else:
        def f(q, k, v, bias):
            return _attn_reference(q, k, v, bias, scale,
                                   layout=layout, dropout=drop,
                                   causal=causal, window=window)

        _, vjp = jax.vjp(f, q, k, v, bias)
        dq, dk, dv, dbias = vjp(dout.astype(q.dtype))
        if bias is None:
            dbias = None

    for slot, grad in (("Q", dq), ("K", dk), ("V", dv),
                       ("BiasQK", dbias)):
        names = op.output(slot + "@GRAD")
        if names and names[0] and grad is not None:
            primal = ctx.env.get(op.input(slot)[0]) \
                if op.input(slot) else None
            if primal is not None and hasattr(primal, "dtype") and \
                    grad.dtype != primal.dtype:
                grad = grad.astype(primal.dtype)
            ctx.env[names[0]] = grad


@register_op("differential_attention")
def differential_attention(ctx):
    """The head outputs of differential attention from the two softmax
    maps a pair of query heads made. X [B, S, 2 H, Dv]: the fused
    attention's output with its query heads in (key pair j, map m,
    member r) order, `group` members a pair, map 0 the one of q1 k1,
    map 1 the one of q2 k2; LambdaQ1, LambdaK1, LambdaQ2, LambdaK2 [d];
    Scale [Dv]; attrs lambda_init, epsilon.
      lambda = exp(LambdaQ1 . LambdaK1) - exp(LambdaQ2 . LambdaK2)
               + lambda_init
      o_i = (1 - lambda_init) * RMSNorm(a1_i - lambda a2_i) * Scale,
    head i = j * group + r, the norm over its Dv channels. Out [B, S,
    H Dv], the heads in that order. Float32 inside, X's type out."""
    x = ctx.input("X")
    group = int(ctx.attr("group"))
    init = float(ctx.attr("lambda_init"))
    eps = float(ctx.attr("epsilon", 1e-5))
    b, s, heads, dv = x.shape
    if heads % (2 * group):
        raise ValueError(f"{heads} attention heads are no pairs of "
                         f"{group} members' two maps")

    def dot(a, c):
        return jnp.sum(ctx.input(a).astype(jnp.float32)
                       * ctx.input(c).astype(jnp.float32))
    lam = jnp.exp(dot("LambdaQ1", "LambdaK1")) \
        - jnp.exp(dot("LambdaQ2", "LambdaK2")) + init
    maps = x.astype(jnp.float32).reshape(b, s, heads // (2 * group), 2,
                                         group, dv)
    o = maps[:, :, :, 0] - lam * maps[:, :, :, 1]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * ctx.input("Scale").astype(jnp.float32) * (1.0 - init)
    ctx.set_output("Out", o.reshape(b, s, heads // 2 * dv).astype(x.dtype))


@register_op("conv2d_inception_fusion")
def conv2d_inception_fusion(ctx):
    """GoogleNet inception block as one op: 4 conv branches + concat.

    Parity: reference fused/fusion_conv_inception_op.{cc,cu} (cuDNN
    conv+bias+activation chain). Dataflow reverse-engineered from the CUDA
    kernel (fusion_conv_inception_op.cu:192-249):

      t0 = act(conv1x1(pool3x3_s1_p1(x), F0) + B0)            # oc0 ch
      c1 = act(conv1x1(x, F1) + B1)                           # oc1 + 2*ic2
      c2 = act(conv3x3_p1_groups2(c1[:, oc1:], F2) + B2)      # oc2 + ic3
      c3 = act(conv1x1(c2[:, oc2:], F3) + B3)                 # oc3 ch
      out = concat([t0, c1[:, :oc1], c2[:, :oc2], c3], channel)

    with oc1 = F1.oc - 2*F2.ic and oc2 = F2.oc - F3.ic (the reference's
    channel bookkeeping, fusion_conv_inception_op.cc:43-49). TPU-native
    design: expressed as jnp/lax compositions in one traced block — XLA
    fuses bias+activation into the convs, so no hand-scheduled
    cudnnConvolutionBiasActivationForward equivalent is needed; the grad
    comes from the mechanical vjp (the reference registers only a CUDA
    forward).
    """
    from jax import lax

    x = ctx.input("Input")
    filters = ctx.inputs("Filter")
    biases = ctx.inputs("Bias")
    pool_type = ctx.attr("pooling_type", "max")
    exclusive = ctx.attr("exclusive", True)
    act_name = ctx.attr("activation", "relu")

    acts = {
        "identity": lambda v: v,
        "relu": jax.nn.relu,
        "relu6": lambda v: jnp.clip(v, 0.0, 6.0),
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
    }
    act = acts[act_name]
    res_t = jnp.result_type(x)

    def cba(inp, w, b, groups=1, pad=0):
        dn = lax.conv_dimension_numbers(inp.shape, w.shape,
                                        ("NCHW", "OIHW", "NCHW"))
        inp, w = amp_cast("conv2d", inp, w)
        y = lax.conv_general_dilated(
            inp, w, window_strides=(1, 1), padding=[(pad, pad)] * 2,
            dimension_numbers=dn, feature_group_count=groups)
        return act(y + b.reshape(1, -1, 1, 1).astype(y.dtype))

    # branch 0: 3x3 stride-1 pad-1 pool then 1x1 conv
    if pool_type == "max":
        pooled = lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 1, 1),
            [(0, 0), (0, 0), (1, 1), (1, 1)])
    else:
        s = lax.reduce_window(
            x, 0.0, lax.add, (1, 1, 3, 3), (1, 1, 1, 1),
            [(0, 0), (0, 0), (1, 1), (1, 1)])
        if exclusive:
            cnt = lax.reduce_window(
                jnp.ones_like(x), 0.0, lax.add, (1, 1, 3, 3), (1, 1, 1, 1),
                [(0, 0), (0, 0), (1, 1), (1, 1)])
        else:
            cnt = 9.0
        pooled = s / cnt
    ic2 = filters[2].shape[1]          # per-group in-channels of the 3x3
    ic3 = filters[3].shape[1]
    oc1 = filters[1].shape[0] - 2 * ic2
    oc2 = filters[2].shape[0] - ic3
    t0 = cba(pooled, filters[0], biases[0])
    c1 = cba(x, filters[1], biases[1])
    c2 = cba(c1[:, oc1:], filters[2], biases[2], groups=2, pad=1)
    c3 = cba(c2[:, oc2:], filters[3], biases[3])
    out = jnp.concatenate([t0, c1[:, :oc1], c2[:, :oc2], c3], axis=1)
    ctx.set_output("Output", out.astype(res_t))
