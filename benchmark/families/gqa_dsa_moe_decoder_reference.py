"""Plain reference of a decoder-only language model's training step:
grouped-query attention over the keys a learned sparse-attention indexer
selects, and softmax-routed experts with no shared expert, of which one
chip's SHARE is computed — the routed experts `first_expert ..
first_expert + experts_held - 1` of every layer and `vocab_held` rows of
the embedding and the head.

Straight `jax.numpy` in float32, every matrix product at
`Precision.HIGHEST`, no kernels, no mixed precision; it imports nothing
of `paddle_tpu` and takes nothing the program has made — weights come
from `init_params(sizes, seed)`, batches from the harness, both from the
seed. The float8 arithmetic of the control, Adam and the sampling of
gradient elements are `transformer_encdec_reference`'s; the expert layer
(`routed_experts`) is `mla_moe_decoder_reference`'s.

The equations (h [B, S, hidden]); every layer h += Attn(RMSNorm(h));
h += MoE(RMSNorm(h)); RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w.

  Attn, x = RMSNorm(h): q = x W_q -> [S, H, d]; k = x W_k, v = x W_v ->
    [S, Hkv, d]; no bias. q and k each RMSNorm over the d channels of a
    head with a learned scale, then rotary on all d channels, HALF-SPLIT
    pairs (x_j, x_j+d/2) rotated by pos * theta^(-2j/d). Query head g
    reads key / value head g // (H / Hkv).
    o_t = sum over s in S_t of softmax_{s in S_t}(q_t . k_s / sqrt(d)) v_s;
    out = concat_h(o) W_o.
  Indexer, on x with the gradient stopped: q^I_tj = rot(x_t W^Iq_j) [d_I],
    j = 1..H_I; k^I_s = rot(LayerNorm(x_s W^Ik)) [d_I] (ONE index key
    head, LayerNorm eps 1e-6 with scale and shift; half-split rotary over
    all d_I channels); w_tj = (x_t W^Iw)_j * H_I^-1/2 * d_I^-1/2;
    I_ts = sum_j w_tj relu(q^I_tj . k^I_s); S_t = the keys s <= t with
    I_ts >= the min(top_k, t + 1)-th largest of I_t,:t+1 (the top_k
    best-scored causal keys, every causal key while t < top_k; ties at
    the threshold all kept). The indexer's weights are buffers: no
    gradient, no update.
  MoE, y = RMSNorm(h): p = softmax(float32(y) W_r^T) over ALL the layer's
    experts; choice = top-k of p; w = p[choice] / (sum + 1e-20)
    (`norm_topk_prob`); out = sum over choices held here of w_e * E_e(y),
    E_e a SwiGLU. No bias, no scaling, no groups, no shared expert. What
    the experts held elsewhere would add is left out.
  Head: RMSNorm, untied head over the held vocabulary slice, next-token
    cross-entropy, mean over positions.

Attention and the index scores go `query_rows` query rows at a time under
`jax.checkpoint` (at S=8192 one layer's [32, S, S] scores are 8.6 GB),
each layer under `jax.checkpoint`, the experts one at a time, the layers
unrolled.

`precision`: "f32" the reference proper; "fp8" the CONTROL (float8
wherever the program has bfloat16: both operands of every product, the
indexer's among them, every activation kept in the compute type, the
returning gradients; the router stays float32); "fp8_mm" the products
alone. `fault` plants a fault in the reference put in the program's
place: "dense_attention" drops the selection (every causal key
attended); "half_topk" selects top_k / 2 keys; "unnormalised_topk"
leaves the chosen experts' weights un-normalised; "half_positions"
leaves the second half of every sequence out of the loss and takes the
mean over the rest. `rows` restricts every batch to a subset of its rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import mla_moe_decoder_reference as moe
from . import transformer_encdec_reference as base

_HI = jax.lax.Precision.HIGHEST
_NEG = -1e9
SAMPLE_PER_LEAF = base.SAMPLE_PER_LEAF
gather_samples = base.gather_samples
routed_experts = moe.routed_experts
_rms_norm = moe._rms_norm


def param_specs(sizes):
    """[(name, shape, kind, std)] in the program's parameter names; kind
    is "normal", "ones" or "zeros". The indexer's weights
    (`layer_<i>_attn_index_*`) are buffers: drawn from the seed, never
    updated (`is_buffer`)."""
    d, h, hkv = sizes["hidden_size"], sizes["num_attention_heads"], \
        sizes["num_key_value_heads"]
    hd, std = sizes["head_dim"], sizes["initializer_range"]
    hi, di = sizes["index_heads"], sizes["index_head_dim"]
    held, f = sizes["experts_held"], sizes["moe_intermediate_size"]
    specs = [("embed_tokens.w_0", (sizes["vocab_held"], d), "normal", std)]

    def mat(name, *shape):
        specs.append((name + ".w_0", tuple(shape), "normal", std))

    def norm(name, width):
        specs.append((name + ".w_0", (width,), "ones", 0.0))

    for i in range(sizes["num_hidden_layers"]):
        p = f"layer_{i}"
        norm(p + "_attn_norm", d)
        mat(p + "_attn_q", d, h * hd)
        norm(p + "_attn_q_norm", hd)
        mat(p + "_attn_k", d, hkv * hd)
        norm(p + "_attn_k_norm", hd)
        mat(p + "_attn_v", d, hkv * hd)
        mat(p + "_attn_index_q", d, hi * di)
        mat(p + "_attn_index_k", d, di)
        norm(p + "_attn_index_k_norm", di)
        specs.append((p + "_attn_index_k_norm.b_0", (di,), "zeros", 0.0))
        mat(p + "_attn_index_w", d, hi)
        mat(p + "_attn_o", h * hd, d)
        norm(p + "_ffn_norm", d)
        mat(p + "_router", sizes["router_experts"], d)
        mat(p + "_experts_gate", held, d, f)
        mat(p + "_experts_up", held, d, f)
        mat(p + "_experts_down", held, f, d)
    norm("final_norm", d)
    mat("lm_head", d, sizes["vocab_held"])
    return specs


def is_buffer(name):
    return "_attn_index_" in name


def trainable_names(sizes):
    return [n for n, _, _, _ in param_specs(sizes) if not is_buffer(n)]


def init_params(sizes, seed):
    """All weights (buffers too) in float32 on the default device, one
    jitted call from the seed."""
    specs = param_specs(sizes)

    @jax.jit
    def make(key):
        return {name: base._init_leaf(key, i, shape, kind, std)
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
            ps[name] - base._init_leaf(key, i, shape, kind, std))))
            for i, (name, shape, kind, std) in specs}

    return {n: float(x)
            for n, x in norms(base._seed_key(seed), dict(arrays)).items()}


def kept_pairs_by_hand(batch, seq_len, top_k):
    """sum_t min(t + 1, top_k) a sequence: what the selection keeps when
    no score ties at a threshold."""
    t = np.arange(1, seq_len + 1)
    return int(batch * np.minimum(t, top_k).sum())


# ---------------------------------------------------------------- forward

def _layer_norm(x, w, b, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _rope(x, theta):
    """x [B, S, H, R]: the half-split pairs (x_j, x_j+R/2) rotated by
    pos * theta^(-2j/R)."""
    r = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    angle = pos[:, None] * theta ** (
        -jnp.arange(0, r, 2, dtype=jnp.float32) / r)[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def index_scores(ar, iq, ik, iw):
    """I [B, Q, S] of index queries iq [B, Q, H_I, d_I] (a block of
    them), index keys ik [B, S, d_I] and head weights iw [B, Q, H_I]."""
    prod = ar.dot("bqhd,bkd->bhqk", iq, ik)
    return jnp.einsum("bhqk,bqh->bqk", jnp.maximum(prod, 0.0), iw,
                      precision=_HI)


def select(scores, causal, top_k):
    """keep [B, Q, S] bool: the causal keys whose score is at least the
    row's min(top_k, causal keys)-th largest."""
    scores = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(scores, min(top_k, scores.shape[-1]))[0][..., -1:]
    # a row with fewer than top_k causal keys reads kth = -inf
    return (scores >= kth) & causal


def _sparse_attention(ar, q, k, v, iq, ik, iw, top_k, query_rows, fault):
    """(softmax over the selected keys of q k^T / sqrt(d), times v, the
    pairs kept; `query_rows` query rows at a time). q [B, S, H, D]; k, v
    [B, S, H, D] (already repeated to the query heads); iq [B, S, H_I,
    d_I], ik [B, S, d_I], iw [B, S, H_I]."""
    b, s, h, d = q.shape
    block = min(query_rows, s)
    if s % block:
        raise ValueError(f"{s} query rows in blocks of {block}")
    cols = jnp.arange(s)
    if fault == "half_topk":
        top_k = top_k // 2

    @jax.checkpoint
    def one(args):
        qb, iqb, iwb, start = args
        causal = ((start + jnp.arange(block))[:, None]
                  >= cols[None, :])[None]                   # [1, Q, S]
        keep = causal if fault == "dense_attention" else select(
            index_scores(ar, iqb, ik, iwb), causal, top_k)
        scores = ar.dot("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        scores = jnp.where(keep[:, None], scores, _NEG)
        probs = ar.act(jax.nn.softmax(scores, axis=-1))
        return ar.act(ar.dot("bhqk,bkhd->bqhd", probs, v)), \
            jnp.sum(keep, dtype=jnp.int32)

    def blocks(x):
        return jnp.moveaxis(
            x.reshape((b, s // block, block) + x.shape[2:]), 1, 0)

    out, kept = jax.lax.map(
        one, (blocks(q), blocks(iq), blocks(iw), jnp.arange(0, s, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d), jnp.sum(kept)


def _index_inputs(ar, p, x, sizes):
    """(index queries [B, S, H_I, d_I], index keys [B, S, d_I], head
    weights [B, S, H_I]) from the layer's normalised input. The indexer
    reads x and gives the language-model loss no gradient."""
    hi, di = sizes["index_heads"], sizes["index_head_dim"]
    theta = float(sizes["rope_theta"])
    b, s, _ = x.shape
    x = jax.lax.stop_gradient(x)

    def index(part, n):
        return ar.act(ar.dot("bsd,de->bse", x, p[f"attn_index_{part}.w_0"])
                      ).reshape(b, s, n, -1)

    iq = ar.act(_rope(index("q", hi), theta))
    ik = ar.act(_layer_norm(index("k", 1), p["attn_index_k_norm.w_0"],
                            p["attn_index_k_norm.b_0"]))
    ik = ar.act(_rope(ik, theta))[:, :, 0]
    return iq, ik, index("w", 1)[:, :, 0] * (hi ** -0.5 * di ** -0.5)


def _attention(ar, p, x, sizes, query_rows, fault):
    h, hkv, hd = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"]
    theta, eps = float(sizes["rope_theta"]), sizes["rms_norm_eps"]
    b, s, _ = x.shape

    def heads(part, n, w=p):
        return ar.act(ar.dot("bsd,de->bse", x, w[f"attn_{part}.w_0"])
                      ).reshape(b, s, n, -1)

    def normed(part, n):
        t = ar.act(_rms_norm(heads(part, n), p[f"attn_{part}_norm.w_0"],
                             eps))
        return ar.act(_rope(t, theta))

    q, k, v = normed("q", h), normed("k", hkv), heads("v", hkv)
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))
    iq, ik, iw = _index_inputs(ar, p, x, sizes)
    ctx, kept = _sparse_attention(ar, q, k, v, iq, ik, iw,
                                  sizes["index_topk"], query_rows, fault)
    return ar.act(ar.dot("bse,ed->bsd", ctx.reshape(b, s, h * hd),
                         p["attn_o.w_0"])), kept


def route(y, w_r, sizes, fault=None):
    """(choice int32 [T, k] over all experts, weight float32 [T, k]);
    float32 at the highest precision whatever `precision` is."""
    s = jax.nn.softmax(jnp.einsum("td,ed->te", y, w_r, precision=_HI), -1)
    _, choice = jax.lax.top_k(jax.lax.stop_gradient(s),
                              sizes["num_experts_per_tok"])
    w = jnp.take_along_axis(s, choice, axis=-1)
    if sizes["norm_topk_prob"] and fault != "unnormalised_topk":
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return choice.astype(jnp.int32), w


def moe_layer(ar, p, y, sizes, fault=None):
    """(the routed experts held here for tokens y [T, hidden], the
    router's choice)."""
    choice, w = route(y, p["router.w_0"], sizes, fault)
    return routed_experts(ar, y, choice, w, p["experts_gate.w_0"],
                          p["experts_up.w_0"], p["experts_down.w_0"],
                          sizes["first_expert"]), choice


def _layer(ar, p, h, sizes, query_rows, fault):
    eps = sizes["rms_norm_eps"]
    x = ar.act(_rms_norm(h, p["attn_norm.w_0"], eps))
    attn, kept = _attention(ar, p, x, sizes, query_rows, fault)
    h = ar.act(h + attn)
    y = ar.act(_rms_norm(h, p["ffn_norm.w_0"], eps))
    b, s, d = y.shape
    ffn, choice = moe_layer(ar, p, y.reshape(b * s, d), sizes, fault)
    return ar.act(h + ffn.reshape(b, s, d)), (choice, kept)


def _loss_sum(params, buffers, batch, sizes, precision, query_rows, fault):
    """(sum over the block's counted positions of the cross-entropy,
    (the routers' choices int32 [layers, tokens, top-k], the pairs each
    layer's selection kept int32 [layers]))."""
    ar = base._Arithmetic(precision)
    params = {**params, **buffers}
    h = ar.act(params["embed_tokens.w_0"][batch["input_ids"]])
    choices, kept = [], []
    for i in range(sizes["num_hidden_layers"]):
        # one layer at a time, its activations made again in the backward
        # pass; not stacked for a scan: a stacked copy of the expert
        # matrices and of their gradients would double them
        block = jax.checkpoint(
            lambda h, p: _layer(ar, p, h, sizes, query_rows, fault))
        h, (choice, n) = block(h, moe._of_layer(params, i))
        choices.append(choice)
        kept.append(n)

    @jax.checkpoint
    def head(h):
        x = ar.act(_rms_norm(h, params["final_norm.w_0"],
                             sizes["rms_norm_eps"]))
        logits = ar.act(ar.dot("bsd,dv->bsv", x, params["lm_head.w_0"]))
        lse = jax.nn.logsumexp(logits, axis=-1)
        l_y = jnp.take_along_axis(
            logits, batch["labels"][..., None], axis=-1)[..., 0]
        ce = lse - l_y
        if fault == "half_positions":
            ce = ce[:, :ce.shape[1] // 2]
        return jnp.sum(ce)
    return head(h), (jnp.stack(choices), jnp.stack(kept))


@functools.lru_cache(maxsize=8)
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


def _static(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float, str, bool))))


def run(sizes, pool, seed, steps=3, precision="f32", rows=None, fault=None,
        rows_per_block=1, query_rows=512):
    """Train `steps` steps from the seed's weights on pool[0..steps-1].
    Returns {"losses": [...], "grad_norms": {leaf: |g_1|},
    "grad_sample": {leaf: g_1 at the seed's sampled elements},
    "delta_norms": {leaf: |p_steps - p_0|}} over the trainable leaves,
    "buffer_delta_norms" the same of the buffers (all zero: nothing
    updates them), "first_choices": the routers' choices at step 1, int32
    [layers, tokens, top-k], and "first_kept": the pairs each layer's
    selection kept at step 1, int64 [layers]."""
    block_grad, update, add = _programs(_static(sizes), precision,
                                        int(query_rows), fault)
    everything = init_params(sizes, seed)
    buffers = {n: a for n, a in everything.items() if is_buffer(n)}
    params = {n: a for n, a in everything.items() if not is_buffer(n)}
    del everything
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms, choices, kept = [], None, [], 0
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
            (val, (picked, n)), g = block_grad(params, buffers, block)
            if t == 1:
                choices.append(np.asarray(picked))
                kept = kept + np.asarray(n).astype(np.int64)
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
            "buffer_delta_norms": delta_norms_from_seed(sizes, seed,
                                                        buffers),
            "first_choices": np.concatenate(choices, axis=1),
            "first_kept": kept}


def first_selection(sizes, batch, seed, query_rows=512):
    """The keep masks of step 1 from the seed's weights, bool numpy
    [layers, B, S, S]: the forward pass alone, for counting how a
    program's selection differs (calibration; not part of a run)."""
    ar = base._Arithmetic("f32")
    s = batch["input_ids"].shape[1]
    block = min(query_rows, s)
    cols = jnp.arange(s)

    @jax.jit
    def layer(p, h):
        x = _rms_norm(h, p["attn_norm.w_0"], sizes["rms_norm_eps"])
        iq, ik, iw = _index_inputs(ar, p, x, sizes)

        def one(args):
            iqb, iwb, start = args
            causal = ((start + jnp.arange(block))[:, None]
                      >= cols[None, :])[None]
            return select(index_scores(ar, iqb, ik, iwb), causal,
                          sizes["index_topk"])

        def blocks(t):
            return jnp.moveaxis(
                t.reshape((-1, s // block, block) + t.shape[2:]), 1, 0)

        keep = jax.lax.map(one, (blocks(iq), blocks(iw),
                                 jnp.arange(0, s, block)))
        return _layer(ar, p, h, sizes, query_rows, None)[0], \
            jnp.moveaxis(keep, 0, 1).reshape(-1, s, s)

    params = init_params(sizes, seed)
    h = params["embed_tokens.w_0"][np.asarray(batch["input_ids"])]
    masks = []
    for i in range(sizes["num_hidden_layers"]):
        h, keep = layer(moe._of_layer(params, i), h)
        masks.append(np.asarray(keep))
    return np.stack(masks)
