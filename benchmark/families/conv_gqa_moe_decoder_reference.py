"""Plain reference of a decoder-only language model's training step whose
layers mix tokens by a gated short convolution or by grouped-query
attention (the `lfm2_moe` block), with a leading dense SwiGLU layer and
then sigmoid-routed experts with NO shared expert, of which one chip's
SHARE is computed — the routed experts `first_expert .. first_expert +
experts_held - 1` of every expert layer and `vocab_held` rows of the
embedding, which is also the head.

Straight `jax.numpy` in float32, every matrix product at
`Precision.HIGHEST`, no kernels, no mixed precision; it imports nothing
of `paddle_tpu` and takes nothing the program has made — weights come
from `init_params(sizes, seed)`, batches from the harness, both from the
seed. The float8 arithmetic of the control, Adam and the sampling of
gradient elements are `transformer_encdec_reference`'s; the blocked
causal attention, the SwiGLU and the loop over the held experts are
`mla_moe_decoder_reference`'s; the half-split rotary is
`gqa_dsa_moe_decoder_reference`'s.

The equations (h [B, S, D]); layer i, pre-norm, two parts:
h += Op_i(RMSNorm(h)); h += FF_i(RMSNorm(h)); RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * w. `layers` is a string, one character a layer:
`C` a convolution operator, `A` attention.

  Conv, x = RMSNorm(h): [Bg | Cg | x~] = x W_in (W_in [D, 3 D], no
    bias); u = Bg * x~; c_t = sum_{j < K} w[:, j] * u_{t - (K - 1) + j}
    (depthwise over the D channels, causal, positions before the first
    read as zero, no bias, no activation; K = `conv_L_cache` taps);
    out = (Cg * c) W_out, W_out [D, D].
  Attn, x = RMSNorm(h): q = x W_q -> [S, H, d]; k = x W_k, v = x W_v ->
    [S, Hkv, d]; no bias. q and k each RMSNorm over the d channels of a
    head with a learned [d] scale (the layers' eps), then rotary over
    all d channels, HALF-SPLIT pairs (x_j, x_{j + d/2}) rotated by pos *
    theta^(-2j/d). Query head g reads key / value head g // (H / Hkv).
    o = softmax_causal(q k^T / sqrt(d)) v; out = concat_h(o) W_o.
  FF, y = RMSNorm(h): layers i < `num_dense_layers` the dense SwiGLU
    W_down(silu(y W_gate) * (y W_up)) of `intermediate_size`; after
    them the expert layer: s = sigmoid(float32(y) W_r^T) over ALL the
    layer's experts; choice = top-k of (s + b), b the expert bias, a
    buffer that selects and never weighs and takes no gradient;
    w = s[choice] / (sum s[choice] + `router_norm_epsilon`) *
    routed_scaling_factor; out = sum over the choices HELD HERE of w_e *
    E_e(y), E_e a SwiGLU of `moe_intermediate_size`. No shared expert: a
    token none of whose choices is held here gets a zero feed-forward.
  Head: a final RMSNorm; the logits are its output times the embedding's
    own matrix transposed (`tie_word_embeddings`; else an untied
    `lm_head.w_0`), over the held vocabulary slice; next-token
    cross-entropy, mean over positions, no auxiliary loss.

Attention goes `query_rows` query rows at a time under `jax.checkpoint`
(at S = 8192 one layer's [32, S, S] scores are 8.6 GB), each layer under
`jax.checkpoint`, the experts one at a time, the layers unrolled (they
differ, and the chip refused a stacked `scan` in PR 28).

`precision`: "f32" the reference proper; "fp8" the CONTROL (float8
wherever the program has bfloat16: both operands of every product, every
activation kept in the compute type, the returning gradients; the router
and the convolution's arithmetic stay float32 as they do in the
program); "fp8_mm" the products alone. `fault` plants a fault in the
reference put in the program's place:
  "conv_lookahead"     the convolution reads one token ahead (taps over
                       t - K + 2 .. t + 1);
  "gate_c_dropped"     out = c W_out, the Cg gate left out;
  "wrong_key_head"     query head g reads key head (g // (group / 2)) %
                       Hkv — what a packed lane block that picked the
                       other half of its key block would compute;
  "qk_norm_dropped"    no RMS norm of q and k;
  "rotary_half"        rotary on the first half of each head's channels
                       only;
  "unnormalised_topk"  the chosen experts' weights left un-normalised;
  "bias_in_weights"    the weights taken from s + b (the bias weighs);
  "half_positions"     the second half of every sequence left out of the
                       loss, the mean over the rest.
`rows` restricts every batch to a subset of its rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gqa_dsa_moe_decoder_reference as gqa
from . import mla_moe_decoder_reference as moe
from . import transformer_encdec_reference as base

_HI = jax.lax.Precision.HIGHEST
SAMPLE_PER_LEAF = base.SAMPLE_PER_LEAF
gather_samples = base.gather_samples
routed_experts = moe.routed_experts
_rms_norm = moe._rms_norm
_of_layer = moe._of_layer
_rope = gqa._rope

MIXERS = {"C": "conv", "A": "attention"}
FAULTS = ("conv_lookahead", "gate_c_dropped", "wrong_key_head",
          "qk_norm_dropped", "rotary_half", "unnormalised_topk",
          "bias_in_weights", "half_positions")


def mixers(sizes):
    """Each layer's token mixer, from the `layers` string."""
    return [MIXERS[ch] for ch in sizes["layers"]]


def count(sizes, part):
    """Layers with the mixer `part` ("conv", "attention"), or with the
    feed-forward `part` ("mlp", "experts")."""
    n = len(sizes["layers"])
    dense = min(sizes["num_dense_layers"], n)
    if part in ("mlp", "experts"):
        return dense if part == "mlp" else n - dense
    return mixers(sizes).count(part)


def param_specs(sizes):
    """[(name, shape, kind, std)] in the program's parameter names; kind
    is "normal", "ones", "zeros" or "conv" = U(-1 / sqrt(taps), 1 /
    sqrt(taps)), a depthwise Conv1d's own default (at std 0.02 the
    operator's output would be a few hundredths of its input and no
    fault in it could be read). The expert bias `layer_<i>_router.b_0`
    is a buffer, never updated (`is_buffer`): zero, or normal at
    `expert_bias_std` where the sizes give one (the tests' nonzero
    bias)."""
    d, std = sizes["hidden_size"], sizes["initializer_range"]
    h, hkv, hd = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"]
    held, f = sizes["experts_held"], sizes["moe_intermediate_size"]
    wide = sizes["intermediate_size"]
    specs = [("embed_tokens.w_0", (sizes["vocab_held"], d), "normal", std)]

    def add(name, shape, kind="normal", scale=None):
        specs.append((name, tuple(shape), kind,
                      (std if scale is None else scale)
                      if kind == "normal" else 0.0))

    for i, mixer in enumerate(mixers(sizes)):
        p = f"layer_{i}"
        if mixer == "conv":
            add(p + "_conv_norm.w_0", (d,), "ones")
            add(p + "_conv_in.w_0", (d, 3 * d))
            add(p + "_conv.w_0", (d, sizes["conv_L_cache"]), "conv")
            add(p + "_conv_out.w_0", (d, d))
        else:
            add(p + "_attn_norm.w_0", (d,), "ones")
            add(p + "_attn_q.w_0", (d, h * hd))
            add(p + "_attn_q_norm.w_0", (hd,), "ones")
            add(p + "_attn_k.w_0", (d, hkv * hd))
            add(p + "_attn_k_norm.w_0", (hd,), "ones")
            add(p + "_attn_v.w_0", (d, hkv * hd))
            add(p + "_attn_o.w_0", (h * hd, d))
        add(p + "_ffn_norm.w_0", (d,), "ones")
        if i < sizes["num_dense_layers"]:
            for part, shape in (("gate", (d, wide)), ("up", (d, wide)),
                                ("down", (wide, d))):
                add(f"{p}_mlp_{part}.w_0", shape)
        else:
            add(p + "_router.w_0", (sizes["router_experts"], d))
            if sizes["use_expert_bias"]:
                bias_std = sizes.get("expert_bias_std", 0.0)
                add(p + "_router.b_0", (sizes["router_experts"],),
                    "normal" if bias_std else "zeros", bias_std)
            add(p + "_experts_gate.w_0", (held, d, f))
            add(p + "_experts_up.w_0", (held, d, f))
            add(p + "_experts_down.w_0", (held, f, d))
    add("final_norm.w_0", (d,), "ones")
    if not sizes["tie_word_embeddings"]:
        add("lm_head.w_0", (d, sizes["vocab_held"]))
    return specs


def is_buffer(name):
    return name.endswith("_router.b_0")


def trainable_names(sizes):
    return [n for n, _, _, _ in param_specs(sizes) if not is_buffer(n)]


def _init_leaf(key, i, shape, kind, std, sizes):
    if kind == "conv":
        bound = sizes["conv_L_cache"] ** -0.5
        return jax.random.uniform(jax.random.fold_in(key, i), shape,
                                  jnp.float32, -bound, bound)
    return base._init_leaf(key, i, shape, kind, std)


def init_params(sizes, seed):
    """All weights (the buffers too) in float32 on the default device,
    one jitted call from the seed."""
    specs = param_specs(sizes)

    @jax.jit
    def make(key):
        return {name: _init_leaf(key, i, shape, kind, std, sizes)
                for i, (name, shape, kind, std) in enumerate(specs)}

    return make(base._seed_key(seed))


def sample_indices(sizes, seed):
    """{trainable leaf: flat indices} — up to SAMPLE_PER_LEAF elements of
    each, drawn from the seed, at which both sides' first gradients are
    read."""
    rng = np.random.default_rng([int(seed), 0x5A4D])
    out = {}
    for name, shape, _, _ in param_specs(sizes):
        if is_buffer(name):
            continue
        n = int(np.prod(shape))
        out[name] = np.sort(rng.choice(n, size=min(n, SAMPLE_PER_LEAF),
                                       replace=False)).astype(np.int32)
    return out


def delta_norms_from_seed(sizes, seed, arrays):
    """{leaf: |p - p_0|} for `arrays` {leaf: p}, p_0 drawn again from the
    seed leaf by leaf inside one jitted call."""
    specs = [(i, s) for i, s in enumerate(param_specs(sizes))
             if s[0] in arrays]

    @jax.jit
    def norms(key, ps):
        return {name: jnp.sqrt(jnp.sum(jnp.square(
            ps[name] - _init_leaf(key, i, shape, kind, std, sizes))))
            for i, (name, shape, kind, std) in specs}

    return {n: float(x)
            for n, x in norms(base._seed_key(seed), dict(arrays)).items()}


# ---------------------------------------------------------------- forward

def gated_conv(proj, w, fault=None):
    """Cg * conv(Bg * x~) of proj [B, S, 3 D] = [Bg | Cg | x~] and the
    filter w [D, K]: c_t = sum_j w[:, j] u_{t - (K - 1) + j}, tokens
    before the first zero."""
    d, k = w.shape
    s = proj.shape[1]
    u = proj[..., :d] * proj[..., 2 * d:]
    ahead = 1 if fault == "conv_lookahead" else 0
    padded = jnp.pad(u, ((0, 0), (k - 1 - ahead, ahead), (0, 0)))
    c = sum(padded[:, j:j + s] * w[:, j] for j in range(k))
    return c if fault == "gate_c_dropped" else proj[..., d:2 * d] * c


def _conv(ar, p, x, fault):
    proj = ar.act(ar.dot("bsd,de->bse", x, p["conv_in.w_0"]))
    y = ar.act(gated_conv(proj, p["conv.w_0"], fault))
    return ar.act(ar.dot("bse,ed->bsd", y, p["conv_out.w_0"]))


def _attention(ar, p, x, sizes, query_rows, fault):
    h, hkv, hd = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"]
    theta, eps = float(sizes["rope_theta"]), sizes["rms_norm_eps"]
    b, s, _ = x.shape

    def heads(part, n):
        return ar.act(ar.dot("bsd,de->bse", x, p[f"attn_{part}.w_0"])
                      ).reshape(b, s, n, hd)

    def placed(part, n):
        t = heads(part, n)
        if fault != "qk_norm_dropped":
            t = ar.act(_rms_norm(t, p[f"attn_{part}_norm.w_0"], eps))
        if fault == "rotary_half":
            return ar.act(jnp.concatenate(
                [_rope(t[..., :hd // 2], theta), t[..., hd // 2:]], -1))
        return ar.act(_rope(t, theta))

    q, k, v = placed("q", h), placed("k", hkv), heads("v", hkv)
    group = h // hkv
    if fault == "wrong_key_head":
        of_head = (jnp.arange(h) // max(group // 2, 1)) % hkv
    else:
        of_head = jnp.arange(h) // group
    ctx = moe._causal_attention(ar, q, k[:, :, of_head], v[:, :, of_head],
                                query_rows)
    return ar.act(ar.dot("bse,ed->bsd", ctx.reshape(b, s, h * hd),
                         p["attn_o.w_0"]))


def route(y, w_r, b, sizes, fault=None):
    """(choice int32 [T, k] over all experts, weight float32 [T, k]);
    float32 at the highest precision whatever `precision` is. `b` None:
    no expert bias."""
    s = jax.nn.sigmoid(jnp.einsum("td,ed->te", y, w_r, precision=_HI))
    pick = s if b is None else s + b[None, :]
    _, choice = jax.lax.top_k(jax.lax.stop_gradient(pick),
                              sizes["num_experts_per_tok"])
    w = jnp.take_along_axis(pick if fault == "bias_in_weights" else s,
                            choice, axis=-1)
    if sizes["norm_topk_prob"] and fault != "unnormalised_topk":
        w = w / (jnp.sum(w, -1, keepdims=True)
                 + sizes["router_norm_epsilon"])
    return choice.astype(jnp.int32), w * sizes["routed_scaling_factor"]


def moe_layer(ar, p, y, sizes, fault=None):
    """(the routed experts held here for tokens y [T, D], the router's
    choice). No shared expert."""
    choice, w = route(y, p["router.w_0"], p.get("router.b_0"), sizes, fault)
    return routed_experts(ar, y, choice, w, p["experts_gate.w_0"],
                          p["experts_up.w_0"], p["experts_down.w_0"],
                          sizes["first_expert"]), choice


def _layer(ar, p, h, mixer, dense, sizes, query_rows, fault):
    eps = sizes["rms_norm_eps"]
    if mixer == "conv":
        x = ar.act(_rms_norm(h, p["conv_norm.w_0"], eps))
        h = ar.act(h + _conv(ar, p, x, fault))
    else:
        x = ar.act(_rms_norm(h, p["attn_norm.w_0"], eps))
        h = ar.act(h + _attention(ar, p, x, sizes, query_rows, fault))
    y = ar.act(_rms_norm(h, p["ffn_norm.w_0"], eps))
    b, s, d = y.shape
    y = y.reshape(b * s, d)
    if dense:
        ffn, choice = moe._swiglu(ar, y, p["mlp_gate.w_0"], p["mlp_up.w_0"],
                                  p["mlp_down.w_0"]), None
    else:
        ffn, choice = moe_layer(ar, p, y, sizes, fault)
    return ar.act(h + ffn.reshape(b, s, d)), choice


def _loss_sum(params, buffers, batch, sizes, precision, query_rows, fault):
    """(sum over the block's counted positions of the cross-entropy, the
    routers' choices int32 [expert layers, tokens, top-k])."""
    ar = base._Arithmetic(precision)
    params = {**params, **buffers}
    h = ar.act(params["embed_tokens.w_0"][batch["input_ids"]])
    choices = []
    for i, mixer in enumerate(mixers(sizes)):
        # one layer at a time, its activations made again in the backward
        # pass; the layers are not stacked for a scan (they differ)
        dense = i < sizes["num_dense_layers"]
        block = jax.checkpoint(
            lambda h, p, mixer=mixer, dense=dense: _layer(
                ar, p, h, mixer, dense, sizes, query_rows, fault))
        h, choice = block(h, _of_layer(params, i))
        if choice is not None:
            choices.append(choice)
    choices = jnp.stack(choices) if choices else \
        jnp.zeros((0,) + h.shape[:1], jnp.int32)

    @jax.checkpoint
    def head(h, table):
        x = ar.act(_rms_norm(h, params["final_norm.w_0"],
                             sizes["rms_norm_eps"]))
        logits = ar.act(ar.dot("bsd,vd->bsv", x, table))
        lse = jax.nn.logsumexp(logits, axis=-1)
        l_y = jnp.take_along_axis(
            logits, batch["labels"][..., None], axis=-1)[..., 0]
        ce = lse - l_y
        if fault == "half_positions":
            ce = ce[:, :ce.shape[1] // 2]
        return jnp.sum(ce)
    table = params["embed_tokens.w_0"] if sizes["tie_word_embeddings"] \
        else params["lm_head.w_0"].T
    return head(h, table), choices


@functools.lru_cache(maxsize=16)
def _programs(sizes_items, precision, query_rows, fault):
    sizes = dict(sizes_items)

    @jax.jit
    def block_grad(params, buffers, block):
        return jax.value_and_grad(_loss_sum, has_aux=True)(
            params, buffers, block, sizes, precision, query_rows, fault)

    @functools.partial(jax.jit, static_argnums=(5,),
                       donate_argnums=(0, 2, 3))
    def update(params, grads, m, v, count, t):
        grads = {n: g / count for n, g in grads.items()}
        return base._adam(params, grads, m, v, t, sizes) \
            + (base._leaf_norms(grads),)

    @jax.jit
    def add(a, b):
        return jax.tree_util.tree_map(jnp.add, a, b)

    return block_grad, update, add


def run(sizes, pool, seed, steps=3, precision="f32", rows=None, fault=None,
        rows_per_block=1, query_rows=512):
    """Train `steps` steps from the seed's weights on pool[0..steps-1].
    Returns {"losses": [...], "grad_norms": {leaf: |g_1|},
    "grad_sample": {leaf: g_1 at the seed's sampled elements},
    "delta_norms": {leaf: |p_steps - p_0|}} over the trainable leaves,
    and "first_choices": the routers' choices at step 1, int32 [expert
    layers, tokens, top-k]."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    block_grad, update, add = _programs(
        tuple(sorted((k, v) for k, v in sizes.items()
                     if isinstance(v, (int, float, str, bool)))),
        precision, int(query_rows), fault)
    everything = init_params(sizes, seed)
    buffers = {n: a for n, a in everything.items() if is_buffer(n)}
    params = {n: a for n, a in everything.items() if not is_buffer(n)}
    del everything
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms, choices = [], None, []
    for t in range(1, steps + 1):
        batch = {k: np.asarray(pool[(t - 1) % len(pool)][k])
                 for k in ("input_ids", "labels")}
        if rows is not None:
            batch = {k: a[rows] for k, a in batch.items()}
        n_rows, n_pos = batch["input_ids"].shape
        count = float(n_rows * (n_pos // 2 if fault == "half_positions"
                                else n_pos))
        total, grads = 0.0, None
        for lo in range(0, n_rows, rows_per_block):
            block = {k: a[lo:lo + rows_per_block] for k, a in batch.items()}
            (val, picked), g = block_grad(params, buffers, block)
            if t == 1:
                choices.append(np.asarray(picked))
            total = total + val
            grads = g if grads is None else add(grads, g)
        if t == 1:
            grad_sample = gather_samples(grads, sample_indices(sizes, seed),
                                         1.0 / count)
        params, m, v, norms = update(params, grads, m, v, count, t)
        losses.append(float(total) / count)
        if t == 1:
            grad_norms = {n: float(x) for n, x in norms.items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sample": grad_sample,
            "delta_norms": delta_norms_from_seed(sizes, seed, params),
            "first_choices": np.concatenate(choices, axis=1)}
