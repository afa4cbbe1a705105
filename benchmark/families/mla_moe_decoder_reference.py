"""Plain reference of a decoder-only language model's training step:
multi-head latent attention, a leading dense SwiGLU layer, then layers of
sigmoid-routed experts with a shared expert (the `deepseek_v3` block), of
which one chip's SHARE is computed — the routed experts `first_expert ..
first_expert + experts_held - 1` of every layer and `vocab_held` rows of
the embedding and the head.

Straight `jax.numpy` in float32, every matrix product at
`Precision.HIGHEST`, no kernels, no mixed precision; it imports nothing
of `paddle_tpu` and takes nothing the program has made — weights come
from `init_params(sizes, seed)`, batches from the harness, both from the
seed. The float8 arithmetic of the control, Adam and the sampling of
gradient elements are `transformer_encdec_reference`'s.

The equations (T = B*S tokens, h [T, hidden]); every layer
h += Attn(RMSNorm(h)); h += FFN(RMSNorm(h)); RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * w.

  Attn, x = RMSNorm(h): q = x W_q -> [T, H, nope + rope];
    ckv = x W_kva -> c [kv_lora_rank] and ONE rope key [rope] shared by
    all heads; c <- RMSNorm(c; w_kv); kv = c W_kvb -> [T, H, nope + v]
    = k_nope, v. Rotary on q's trailing `rope` channels and on the rope
    key: adjacent pairs (x_2i, x_2i+1) rotated by pos * theta^(-2i/rope)
    (`rope_interleave`; the published code then stores the pairs
    de-interleaved, the same permutation on q and k, so the scores are
    those of the pairwise rotation). q_h = [q_nope_h ; rope(q_rope_h)],
    k_h = [k_nope_h ; rope(k_rope)]; o_h = softmax_causal(q_h k_h^T /
    sqrt(nope + rope)) v_h; out = concat_h(o_h) W_o. No biases.
  Dense FFN (layers < first_k_dense_replace): W_down(silu(y W_gate) *
    (y W_up)).
  MoE FFN, y = RMSNorm(h): s = sigmoid(float32(y) W_r^T) over ALL the
    layer's experts; choice = top-k of (s + b), b a buffer that takes no
    gradient; w = s[choice] (without b); w <- w / (sum w + 1e-20) *
    routed_scaling_factor; routed = sum over choices held here of w_e *
    E_e(y), E_e a SwiGLU; FFN = routed + shared SwiGLU. The router keeps
    its width and its top-k whatever is held; what the experts held
    elsewhere would add is left out.
  Head: RMSNorm, untied head over the held vocabulary slice, next-token
    cross-entropy, mean over positions.

With one sequence a block, attention goes `query_rows` query rows at a
time under `jax.checkpoint`, each layer under `jax.checkpoint`, the
experts one at a time: at S=4096 one layer's f32 score tensor would be
2.1 GB. The step's gradient and Adam's state are 9.2 GB of the chip's
16.9, so the layers are not stacked for a scan either.

`precision` is `transformer_encdec_reference`'s: "f32" the reference
proper; "fp8" the CONTROL (float8 wherever the program has bfloat16:
both operands of every product, every activation kept in the compute
type, the returning gradients; the router stays float32 as it does in
the program); "fp8_mm" the products alone.
`fault` plants a fault in the reference put in the program's place:
"half_positions" leaves the second half of every sequence out of the
loss and takes the mean over the rest (the half-batch fault of a cell
whose batch is one sequence); "unnormalised_topk" leaves the chosen
experts' weights un-normalised. `rows` restricts every batch to a subset
of its rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer_encdec_reference as base

_HI = jax.lax.Precision.HIGHEST
_NEG = -1e9
SAMPLE_PER_LEAF = base.SAMPLE_PER_LEAF
gather_samples = base.gather_samples


def moe_layers(sizes):
    return [i for i in range(sizes["num_hidden_layers"])
            if i >= sizes["first_k_dense_replace"]]


def param_specs(sizes):
    """[(name, shape, kind, std)] in the program's parameter names; kind
    is "normal" or "ones". The router's score correction
    `layer_<i>_router.b_0` is a buffer: drawn from the seed, never
    updated (`is_buffer`)."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    std = sizes["initializer_range"]
    held, f = sizes["experts_held"], sizes["moe_intermediate_size"]
    shared = sizes["n_shared_experts"] * f
    specs = [("embed_tokens.w_0", (sizes["vocab_held"], d), "normal", std)]

    def mat(name, *shape):
        specs.append((name + ".w_0", tuple(shape), "normal", std))

    def norm(name, width):
        specs.append((name + ".w_0", (width,), "ones", 0.0))

    def gated(name, width):
        mat(name + "_gate", d, width)
        mat(name + "_up", d, width)
        mat(name + "_down", width, d)

    for i in range(sizes["num_hidden_layers"]):
        p = f"layer_{i}"
        norm(p + "_attn_norm", d)
        mat(p + "_attn_q", d, h * (nope + rope))
        mat(p + "_attn_kva", d, rank + rope)
        norm(p + "_attn_kv_norm", rank)
        mat(p + "_attn_kvb", rank, h * (nope + dv))
        mat(p + "_attn_o", h * dv, d)
        norm(p + "_ffn_norm", d)
        if i < sizes["first_k_dense_replace"]:
            gated(p + "_mlp", sizes["intermediate_size"])
        else:
            mat(p + "_router", sizes["router_experts"], d)
            specs.append((p + "_router.b_0", (sizes["router_experts"],),
                          "normal", std))
            mat(p + "_experts_gate", held, d, f)
            mat(p + "_experts_up", held, d, f)
            mat(p + "_experts_down", held, f, d)
            gated(p + "_shared", shared)
    norm("final_norm", d)
    mat("lm_head", d, sizes["vocab_held"])
    return specs


def is_buffer(name):
    return name.endswith("_router.b_0")


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


# ---------------------------------------------------------------- forward

def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, S, H, R]: adjacent pairs rotated by pos * theta^(-2i/R)."""
    r = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    angle = pos[:, None] * theta ** (
        -jnp.arange(0, r, 2, dtype=jnp.float32) / r)[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _causal_attention(ar, q, k, v, query_rows):
    """softmax_causal(q k^T / sqrt(d)) v, `query_rows` query rows at a
    time. q, k [B, S, H, D]; v [B, S, H, Dv]."""
    b, s, h, d = q.shape
    block = min(query_rows, s)
    if s % block:
        raise ValueError(f"{s} query rows in blocks of {block}")
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qb, start = args                                  # [B, block, H, D]
        scores = ar.dot("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        keep = (start + jnp.arange(block))[:, None] >= cols[None, :]
        scores = jnp.where(keep[None, None], scores, _NEG)
        probs = ar.act(jax.nn.softmax(scores, axis=-1))
        return ar.act(ar.dot("bhqk,bkhd->bqhd", probs, v))

    qb = jnp.moveaxis(q.reshape(b, s // block, block, h, d), 1, 0)
    out = jax.lax.map(one, (qb, jnp.arange(0, s, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])


def _attention(ar, p, x, sizes, query_rows):
    h = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    theta = float(sizes["rope_theta"])
    b, s, _ = x.shape
    q = ar.act(ar.dot("bsd,de->bse", x, p["attn_q.w_0"])
               ).reshape(b, s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    ckv = ar.act(ar.dot("bsd,de->bse", x, p["attn_kva.w_0"]))
    c = ar.act(_rms_norm(ckv[..., :rank], p["attn_kv_norm.w_0"],
                         sizes["rms_norm_eps"]))
    k_rope = _rope(ckv[..., rank:][:, :, None, :], theta)   # [B, S, 1, R]
    kv = ar.act(ar.dot("bsr,re->bse", c, p["attn_kvb.w_0"])
                ).reshape(b, s, h, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, h, rope))], -1)
    # the rotated channels are rounded again where they are stored
    ctx = _causal_attention(ar, ar.act(q), ar.act(k), kv[..., nope:],
                            query_rows)
    return ar.act(ar.dot("bse,ed->bsd", ctx.reshape(b, s, h * dv),
                         p["attn_o.w_0"]))


def _swiglu(ar, y, w_gate, w_up, w_down):
    hidden = ar.act(jax.nn.silu(ar.act(ar.dot("td,df->tf", y, w_gate)))
                    * ar.act(ar.dot("td,df->tf", y, w_up)))
    return ar.act(ar.dot("tf,fd->td", hidden, w_down))


def route(y, w_r, b, sizes, fault=None):
    """(choice int32 [T, k] over all experts, weight float32 [T, k]);
    float32 at the highest precision whatever `precision` is."""
    s = jax.nn.sigmoid(jnp.einsum("td,ed->te", y, w_r, precision=_HI))
    _, choice = jax.lax.top_k(jax.lax.stop_gradient(s + b[None, :]),
                              sizes["num_experts_per_tok"])
    w = jnp.take_along_axis(s, choice, axis=-1)
    if sizes["norm_topk_prob"] and fault != "unnormalised_topk":
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return choice.astype(jnp.int32), w * sizes["routed_scaling_factor"]


def routed_experts(ar, y, choice, w, w_gate, w_up, w_down, first):
    """sum over the choices held here of w_e * E_e(y): the held experts
    one at a time over every token, each weighted by the routing weight
    the token gave it (zero where it did not choose it)."""
    held = w_gate.shape[0]
    local = choice - first
    dense = jnp.sum(jnp.where(
        local[:, :, None] == jnp.arange(held)[None, None, :],
        w[:, :, None], 0.0), axis=1)                        # [T, held]

    def one(acc, e):
        wg, wu, wd, we = e
        return acc + we[:, None] * _swiglu(ar, y, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          (w_gate, w_up, w_down, dense.T))
    return out


def _moe(ar, p, y, sizes, fault):
    """(routed experts held here + shared expert, the router's choice)."""
    choice, w = route(y, p["router.w_0"], p["router.b_0"], sizes, fault)
    routed = routed_experts(ar, y, choice, w, p["experts_gate.w_0"],
                            p["experts_up.w_0"], p["experts_down.w_0"],
                            sizes["first_expert"])
    return routed + _swiglu(ar, y, p["shared_gate.w_0"],
                            p["shared_up.w_0"], p["shared_down.w_0"]), choice


def _layer(ar, p, h, sizes, query_rows, dense, fault):
    eps = sizes["rms_norm_eps"]
    x = ar.act(_rms_norm(h, p["attn_norm.w_0"], eps))
    h = ar.act(h + _attention(ar, p, x, sizes, query_rows))
    y = ar.act(_rms_norm(h, p["ffn_norm.w_0"], eps))
    b, s, d = y.shape
    y = y.reshape(b * s, d)
    if dense:
        ffn, choice = _swiglu(ar, y, p["mlp_gate.w_0"], p["mlp_up.w_0"],
                              p["mlp_down.w_0"]), None
    else:
        ffn, choice = _moe(ar, p, y, sizes, fault)
    return ar.act(h + ffn.reshape(b, s, d)), choice


def _of_layer(params, i):
    head = f"layer_{i}_"
    return {k[len(head):]: v for k, v in params.items()
            if k.startswith(head)}


def _loss_sum(params, buffers, batch, sizes, precision, query_rows, fault):
    """(sum over the block's counted positions of the cross-entropy, the
    routers' choices int32 [MoE layers, tokens, top-k])."""
    ar = base._Arithmetic(precision)
    params = {**params, **buffers}
    h = ar.act(params["embed_tokens.w_0"][batch["input_ids"]])
    moe = moe_layers(sizes)
    choices = []
    for i in range(sizes["num_hidden_layers"]):
        # one layer at a time, its activations made again in the backward
        # pass; the layers are not stacked for a scan: a stacked copy of
        # the expert matrices and of their gradients is 3.6 GB
        block = jax.checkpoint(
            lambda h, p, dense=i not in moe: _layer(
                ar, p, h, sizes, query_rows, dense, fault))
        h, choice = block(h, _of_layer(params, i))
        if choice is not None:
            choices.append(choice)
    choices = jnp.stack(choices) if choices else \
        jnp.zeros((0,) + h.shape[:1], jnp.int32)

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
    return head(h), choices


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


def run(sizes, pool, seed, steps=3, precision="f32", rows=None, fault=None,
        rows_per_block=1, query_rows=512):
    """Train `steps` steps from the seed's weights on pool[0..steps-1].
    Returns {"losses": [...], "grad_norms": {leaf: |g_1|},
    "grad_sample": {leaf: g_1 at the seed's sampled elements},
    "delta_norms": {leaf: |p_steps - p_0|}} over the trainable leaves,
    and "first_choices": the routers' choices at step 1, int32 [MoE
    layers, tokens, top-k]."""
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
