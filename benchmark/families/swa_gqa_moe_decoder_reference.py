"""Plain reference of a decoder-only language model's training step whose
attention layers are grouped-query attention over a sliding window or over
the whole causal prefix, each kind with a rotary of its own (plain on the
window layers, YaRN on the full ones), every layer's feed-forward
softmax-routed experts with NO shared expert and an untied head (the
`mellum` block), of which one chip's SHARE is computed — the routed
experts `first_expert .. first_expert + experts_held - 1` of every layer
and `vocab_held` rows of the embedding and of the head.

Straight `jax.numpy` in float32, every matrix product at
`Precision.HIGHEST`, no kernels, no mixed precision; it imports nothing
of `paddle_tpu` and takes nothing the program has made — weights come
from `init_params(sizes, seed)`, batches from the harness, both from the
seed. The float8 arithmetic of the control, Adam and the sampling of
gradient elements are `transformer_encdec_reference`'s; the SwiGLU and
the loop over the held experts are `mla_moe_decoder_reference`'s; the
softmax router is `gqa_dsa_moe_decoder_reference`'s. The band, the two
rotary tables and the attention in query blocks are written here.

The equations (h [B, S, D]); layer i, pre-norm, two parts:
h += Attn_i(RMSNorm(h)); h += MoE(RMSNorm(h)); RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * w. `layers` is a string, one character a layer:
`S` a sliding-window layer, `F` a full (causal) layer.

  Attn, x = RMSNorm(h): q = x W_q -> [S, H, d]; k = x W_k, v = x W_v ->
    [S, Hkv, d]; no bias, no q / k norm. Rotary over all d channels,
    HALF-SPLIT pairs (x_j, x_{j + d/2}) rotated by pos * f_j: on an `S`
    layer f_j = theta^(-2j/d); on an `F` layer the YaRN table (`yarn`):
    low = floor(d ln(L0 / (beta_fast 2 pi)) / (2 ln theta)), high =
    ceil(d ln(L0 / (beta_slow 2 pi)) / (2 ln theta)), r_j = clip((j -
    low) / (high - low), 0, 1), f_j = theta^(-2j/d) (r_j / factor + 1 -
    r_j), and cos and sin both times the attention factor. Query head g
    reads key / value head g // (H / Hkv). Scores q_i . k_j / sqrt(d),
    admitted where j <= i and, on an `S` layer, i - j < window; the
    softmax over the admitted keys; out = concat_h(o) W_o.
  MoE, y = RMSNorm(h): p = softmax(float32(y) W_r^T) over ALL the
    layer's experts; choice = top-k of p; w = p[choice] / sum
    p[choice] (`norm_topk_prob`), routed scaling 1; out = sum over the
    choices HELD HERE of w_e * E_e(y), E_e a SwiGLU of
    `moe_intermediate_size`. A token none of whose choices is held here
    gets a zero feed-forward.
  Head: a final RMSNorm, then `lm_head.w_0` [D, V] (untied) over the
    held vocabulary slice; next-token cross-entropy, mean over
    positions, no auxiliary loss.

Attention goes `query_rows` query rows at a time under `jax.checkpoint`
(at S = 8192 one layer's [32, S, S] scores are 8.6 GB), each layer under
`jax.checkpoint`, the experts one at a time, the layers unrolled.

`precision`: "f32" the reference proper; "fp8" the CONTROL (float8
wherever the program has bfloat16: both operands of every product, every
activation kept in the compute type, the returning gradients; the router
stays float32 as in the program); "fp8_mm" the products alone. `fault`
plants a fault in the reference put in the program's place:
  "window_off_by_one"   the window one key wider (i - j <= window);
  "window_dropped"      the window layers causal over the whole prefix;
  "yarn_dropped"        the full layer's rotary plain (no frequency
                        blend, no attention factor);
  "attention_factor_dropped"  the YaRN blend kept, cos and sin unscaled;
  "qk_norm_added"       q and k RMS-normalised per head (unit scale)
                        before the rotary;
  "unnormalised_topk"   the chosen experts' weights left un-normalised;
  "half_positions"      the second half of every sequence left out of the
                        loss, the mean over the rest.
`rows` restricts every batch to a subset of its rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import gqa_dsa_moe_decoder_reference as gqa
from . import mla_moe_decoder_reference as moe
from . import transformer_encdec_reference as base

_HI = jax.lax.Precision.HIGHEST
_NEG = -1e30
SAMPLE_PER_LEAF = base.SAMPLE_PER_LEAF
gather_samples = base.gather_samples
routed_experts = moe.routed_experts
_rms_norm = moe._rms_norm
_of_layer = moe._of_layer
route = gqa.route

KINDS = {"S": "window", "F": "full"}
FAULTS = ("window_off_by_one", "window_dropped", "yarn_dropped",
          "attention_factor_dropped", "qk_norm_added", "unnormalised_topk",
          "half_positions")


def kinds(sizes):
    """Each layer's attention, from the `layers` string."""
    return [KINDS[ch] for ch in sizes["layers"]]


def count(sizes, part):
    """Layers with the attention `part` ("window", "full"), or with the
    expert layer ("experts": every layer)."""
    if part == "experts":
        return len(sizes["layers"])
    return kinds(sizes).count(part)


def param_specs(sizes):
    """[(name, shape, kind, std)] in the program's parameter names; kind
    is "normal" or "ones". No buffers: the softmax router has no bias."""
    d, std = sizes["hidden_size"], sizes["initializer_range"]
    h, hkv, hd = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"]
    held, f = sizes["experts_held"], sizes["moe_intermediate_size"]
    specs = [("embed_tokens.w_0", (sizes["vocab_held"], d), "normal", std)]

    def add(name, shape, kind="normal"):
        specs.append((name, tuple(shape), kind,
                      std if kind == "normal" else 0.0))

    for i in range(len(sizes["layers"])):
        p = f"layer_{i}"
        add(p + "_attn_norm.w_0", (d,), "ones")
        add(p + "_attn_q.w_0", (d, h * hd))
        add(p + "_attn_k.w_0", (d, hkv * hd))
        add(p + "_attn_v.w_0", (d, hkv * hd))
        add(p + "_attn_o.w_0", (h * hd, d))
        add(p + "_ffn_norm.w_0", (d,), "ones")
        add(p + "_router.w_0", (sizes["router_experts"], d))
        add(p + "_experts_gate.w_0", (held, d, f))
        add(p + "_experts_up.w_0", (held, d, f))
        add(p + "_experts_down.w_0", (held, f, d))
    add("final_norm.w_0", (d,), "ones")
    add("lm_head.w_0", (d, sizes["vocab_held"]))
    return specs


def is_buffer(name):
    return False


def trainable_names(sizes):
    return [n for n, _, _, _ in param_specs(sizes)]


def init_params(sizes, seed):
    """All weights in float32 on the default device, one jitted call
    from the seed."""
    specs = param_specs(sizes)

    @jax.jit
    def make(key):
        return {name: base._init_leaf(key, i, shape, kind, std)
                for i, (name, shape, kind, std) in enumerate(specs)}

    return make(base._seed_key(seed))


def sample_indices(sizes, seed):
    """{leaf: flat indices} — up to SAMPLE_PER_LEAF elements of each,
    drawn from the seed, at which both sides' first gradients are
    read."""
    rng = np.random.default_rng([int(seed), 0x5A4D])
    out = {}
    for name, shape, _, _ in param_specs(sizes):
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
            ps[name] - base._init_leaf(key, i, shape, kind, std))))
            for i, (name, shape, kind, std) in specs}

    return {n: float(x)
            for n, x in norms(base._seed_key(seed), dict(arrays)).items()}


# ---------------------------------------------------------------- rotary

def yarn_bounds(d, theta, original, beta_fast, beta_slow):
    """(low, high) of the YaRN ramp, truncated to whole pairs."""
    def at(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    return max(math.floor(at(beta_fast)), 0), \
        min(math.ceil(at(beta_slow)), d - 1)


def frequencies(d, theta, yarn=None):
    """float64 [d/2]: theta^(-2j/d), or the YaRN blend of it (`yarn` the
    dict of factor, original_max_position_embeddings, beta_fast,
    beta_slow)."""
    j = np.arange(d // 2, dtype=np.float64)
    plain = theta ** (-2.0 * j / d)
    if yarn is None:
        return plain
    low, high = yarn_bounds(d, theta, yarn["original_max_position_embeddings"],
                            yarn["beta_fast"], yarn["beta_slow"])
    r = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain * (r / yarn["factor"] + 1.0 - r)


def rope(x, freq, factor=1.0):
    """x [B, S, H, d]: the half-split pairs (x_j, x_j+d/2) rotated by
    pos * freq_j, cos and sin times `factor`."""
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    angle = pos[:, None] * jnp.asarray(freq, jnp.float32)[None, :]
    cos = (jnp.cos(angle) * factor)[None, :, None]
    sin = (jnp.sin(angle) * factor)[None, :, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def rotary_of_layer(sizes, kind, fault=None):
    """(frequencies, factor on cos and sin) of a layer's rotary."""
    yarn = sizes["yarn"] if kind == "full" and fault != "yarn_dropped" \
        else None
    factor = 1.0 if yarn is None or fault == "attention_factor_dropped" \
        else yarn["attention_factor"]
    theta = sizes["rope_theta_" + kind]
    return frequencies(sizes["head_dim"], theta, yarn), factor


# ---------------------------------------------------------------- forward

def band_attention(ar, q, k, v, window, query_rows):
    """softmax over the admitted keys (j <= i, and i - j < window where
    a window is given) of q k^T / sqrt(d), times v; `query_rows` query
    rows at a time. q [B, S, H, d]; k, v [B, S, H, d]."""
    b, s, h, d = q.shape
    block = min(query_rows, s)
    if s % block:
        raise ValueError(f"{s} query rows in blocks of {block}")
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qb, start = args                                  # [B, block, H, d]
        scores = ar.dot("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        gap = (start + jnp.arange(block))[:, None] - cols[None, :]
        keep = gap >= 0
        if window is not None:
            keep = keep & (gap < window)
        scores = jnp.where(keep[None, None], scores, _NEG)
        probs = ar.act(jax.nn.softmax(scores, axis=-1))
        return ar.act(ar.dot("bhqk,bkhd->bqhd", probs, v))

    qb = jnp.moveaxis(q.reshape(b, s // block, block, h, d), 1, 0)
    out = jax.lax.map(one, (qb, jnp.arange(0, s, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])


def _attention(ar, p, x, kind, sizes, query_rows, fault):
    h, hkv, hd = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"]
    b, s, _ = x.shape
    freq, factor = rotary_of_layer(sizes, kind, fault)

    def heads(part, n):
        return ar.act(ar.dot("bsd,de->bse", x, p[f"attn_{part}.w_0"])
                      ).reshape(b, s, n, hd)

    def placed(part, n):
        t = heads(part, n)
        if fault == "qk_norm_added":
            t = ar.act(_rms_norm(t, jnp.ones((hd,), jnp.float32),
                                 sizes["rms_norm_eps"]))
        return ar.act(rope(t, freq, factor))

    q, k, v = placed("q", h), placed("k", hkv), heads("v", hkv)
    of_head = jnp.arange(h) // (h // hkv)
    window = None
    if kind == "window" and fault != "window_dropped":
        window = sizes["sliding_window"] + (
            1 if fault == "window_off_by_one" else 0)
    ctx = band_attention(ar, q, k[:, :, of_head], v[:, :, of_head], window,
                         query_rows)
    return ar.act(ar.dot("bse,ed->bsd", ctx.reshape(b, s, h * hd),
                         p["attn_o.w_0"]))


def moe_layer(ar, p, y, sizes, fault=None):
    """(the routed experts held here for tokens y [T, D], the router's
    choice). No shared expert."""
    choice, w = route(y, p["router.w_0"], sizes, fault)
    return routed_experts(ar, y, choice, w, p["experts_gate.w_0"],
                          p["experts_up.w_0"], p["experts_down.w_0"],
                          sizes["first_expert"]), choice


def _layer(ar, p, h, kind, sizes, query_rows, fault):
    eps = sizes["rms_norm_eps"]
    x = ar.act(_rms_norm(h, p["attn_norm.w_0"], eps))
    h = ar.act(h + _attention(ar, p, x, kind, sizes, query_rows, fault))
    y = ar.act(_rms_norm(h, p["ffn_norm.w_0"], eps))
    b, s, d = y.shape
    ffn, choice = moe_layer(ar, p, y.reshape(b * s, d), sizes, fault)
    return ar.act(h + ffn.reshape(b, s, d)), choice


def _loss_sum(params, batch, sizes, precision, query_rows, fault):
    """(sum over the block's counted positions of the cross-entropy, the
    routers' choices int32 [layers, tokens, top-k])."""
    ar = base._Arithmetic(precision)
    h = ar.act(params["embed_tokens.w_0"][batch["input_ids"]])
    choices = []
    for i, kind in enumerate(kinds(sizes)):
        # one layer at a time, its activations made again in the backward
        # pass; the layers are not stacked for a scan (they differ)
        block = jax.checkpoint(
            lambda h, p, kind=kind: _layer(ar, p, h, kind, sizes,
                                           query_rows, fault))
        h, choice = block(h, _of_layer(params, i))
        choices.append(choice)

    @jax.checkpoint
    def head(h, w):
        x = ar.act(_rms_norm(h, params["final_norm.w_0"],
                             sizes["rms_norm_eps"]))
        logits = ar.act(ar.dot("bsd,dv->bsv", x, w))
        lse = jax.nn.logsumexp(logits, axis=-1)
        l_y = jnp.take_along_axis(
            logits, batch["labels"][..., None], axis=-1)[..., 0]
        ce = lse - l_y
        if fault == "half_positions":
            ce = ce[:, :ce.shape[1] // 2]
        return jnp.sum(ce)
    return head(h, params["lm_head.w_0"]), jnp.stack(choices)


def _static(sizes):
    """The sizes as a hashable key (the YaRN dict as sorted items)."""
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in sizes.items()
        if isinstance(v, (int, float, str, bool, dict))))


@functools.lru_cache(maxsize=16)
def _programs(sizes_items, precision, query_rows, fault):
    sizes = {k: dict(v) if isinstance(v, tuple) else v
             for k, v in sizes_items}

    @jax.jit
    def block_grad(params, block):
        return jax.value_and_grad(_loss_sum, has_aux=True)(
            params, block, sizes, precision, query_rows, fault)

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
    "delta_norms": {leaf: |p_steps - p_0|}} and "first_choices": the
    routers' choices at step 1, int32 [layers, tokens, top-k]."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    block_grad, update, add = _programs(_static(sizes), precision,
                                        int(query_rows), fault)
    params = init_params(sizes, seed)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms, choices = [], None, []
    for t in range(1, steps + 1):
        batch = {k: np.asarray(pool[(t - 1) % len(pool)][k])
                 for k in ("input_ids", "labels")}
        if rows is not None:
            batch = {k: a[rows] for k, a in batch.items()}
        n_rows, n_pos = batch["input_ids"].shape
        count_ = float(n_rows * (n_pos // 2 if fault == "half_positions"
                                 else n_pos))
        total, grads = 0.0, None
        for lo in range(0, n_rows, rows_per_block):
            block = {k: a[lo:lo + rows_per_block] for k, a in batch.items()}
            (val, picked), g = block_grad(params, block)
            if t == 1:
                choices.append(np.asarray(picked))
            total = total + val
            grads = g if grads is None else add(grads, g)
        if t == 1:
            grad_sample = gather_samples(grads, sample_indices(sizes, seed),
                                         1.0 / count_)
        params, m, v, norms = update(params, grads, m, v, count_, t)
        losses.append(float(total) / count_)
        if t == 1:
            grad_norms = {n: float(x) for n, x in norms.items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sample": grad_sample,
            "delta_norms": delta_norms_from_seed(sizes, seed, params),
            "first_choices": np.concatenate(choices, axis=1)}
