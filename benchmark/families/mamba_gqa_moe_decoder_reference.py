"""Plain reference of a hybrid decoder-only language model's training
step: every layer ONE part — a Mamba-2 state-space mixer, a
grouped-query attention without positions, an expert layer of
sigmoid-routed UNGATED squared-ReLU experts with a shared expert, or a
dense squared-ReLU feed-forward — by the string `pattern` (`M`, `*`,
`E`, `-`, the `nemotron_h` block). One chip's SHARE is computed: the
routed experts `first_expert .. first_expert + experts_held - 1` of
every expert layer and `vocab_held` rows of the embedding and the head.

Straight `jax.numpy` in float32, every matrix product at
`Precision.HIGHEST`, no kernels, no mixed precision; it imports nothing
of `paddle_tpu` and takes nothing the program has made — weights come
from `init_params(sizes, seed)`, batches from the harness, both from the
seed. The float8 arithmetic of the control, Adam and the sampling of
gradient elements are `transformer_encdec_reference`'s; the router and
the blocked causal attention are `mla_moe_decoder_reference`'s.

The equations (h [B, S, D]); every layer h += part(RMSNorm(h)),
RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w; a final RMSNorm, an untied
head over the held vocabulary slice, next-token cross-entropy, mean over
positions.

  M, x = RMSNorm(h): [z | u | dt] = x W_in (no bias; widths H P | H P +
    2 G N | H); u = silu(conv(u) + b), conv depthwise and causal over
    the last `conv_kernel` tokens: conv(u)_t = sum_j w[:, j] u_{t - (K -
    1) + j}; u splits into x_t [H, P], B_t [G, N], C_t [G, N], head h
    reading group h // (H / G). dt_t = softplus(dt_t + dt_bias) (no
    clamp), A = -exp(A_log) a head. THE RECURRENCE, token by token, the
    state S [P, N] a head from zero:
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
    (`lax.scan` over the tokens, `SCAN_BLOCK` tokens under one
    `jax.checkpoint` so that the backward fits; no chunked identity:
    that is the algorithm under test). Then y = RMSNorm_groups(y *
    silu(z)) * w, the mean square taken within each of the G groups of
    H P / G channels; out = y W_out.
  *, x = RMSNorm(h): q = x W_q -> [S, H_q, d]; k = x W_k, v = x W_v ->
    [S, H_kv, d]; no bias, NO rotary, no q / k norm (the positions come
    from the mixers); query head g reads key / value head g // (H_q /
    H_kv); causal softmax(q k^T / sqrt(d)) v; W_o.
  E, y = RMSNorm(h): s = sigmoid(float32(y) W_r^T) over ALL the layer's
    experts; choice = top-k of (s + b), b a zero buffer; w = s[choice] /
    (sum + 1e-20) * routed_scaling_factor; out = sum over the choices
    held here of w_e * W_down_e relu(W_up_e y)^2 (a plain loop over the
    held experts) + the shared expert, the same form at its own width.
    What the experts held elsewhere would add is left out.
  -: W_down relu(W_up x)^2.

`precision`: "f32" the reference proper; "fp8" the CONTROL (float8
wherever the program has bfloat16: both operands of every product, every
activation kept in the compute type — the scan's x, B, C and y among
them — and the returning gradients; the router, dt, A and the state
stay float32 as in the program); "fp8_mm" the products alone.
`fault` plants a fault in the reference put in the program's place:
"state_dropped" zeroes the scan's state at every chunk boundary
(`chunk_size` tokens); "norm_all_channels" takes the gated norm's mean
square over all H P channels at once; "relu_unsquared" leaves the
experts' (routed and shared) ReLU unsquared; "decay_bf16" computes the
decay exp(dt A) in bfloat16; "unnormalised_topk" and "half_positions"
are `mla_moe_decoder_reference`'s. `rows` restricts every batch to a
subset of its rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import mla_moe_decoder_reference as moe
from . import transformer_encdec_reference as base

SAMPLE_PER_LEAF = base.SAMPLE_PER_LEAF
SCAN_BLOCK = 64            # tokens of the recurrence under one checkpoint
gather_samples = base.gather_samples
route = moe.route
_rms_norm = moe._rms_norm
_of_layer = moe._of_layer

PARTS = {"M": "mixer", "*": "attention", "E": "experts", "-": "mlp"}


def parts(sizes):
    """The part of each layer, from the pattern string."""
    return [PARTS[ch] for ch in sizes["pattern"]]


def count(sizes, part):
    return parts(sizes).count(part)


def mixer_widths(sizes):
    """(inner = H P, the convolution's channels = inner + 2 G N)."""
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    return inner, inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]


def param_specs(sizes):
    """[(name, shape, kind, std)] in the program's parameter names; kind
    is "normal", "ones", "zeros", or a mixer's own: "a_log" = log U(1,
    16), "dt_bias" = softplus^-1 of a log-uniform draw in
    [time_step_min, time_step_max] floored at time_step_floor, "conv" =
    U(-1 / sqrt(taps), 1 / sqrt(taps)), the convolution's weight and
    bias as the published modelling code leaves them (torch's own
    Conv1d default; at std 0.02 the scan's state would be a thousandth
    of the skip path D x and no fault in it could be read). The router's
    score correction `layer_<i>_router.b_0` is a zero buffer, never
    updated (`is_buffer`)."""
    d, std = sizes["hidden_size"], sizes["initializer_range"]
    h, hkv, hd = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"]
    mh = sizes["mamba_num_heads"]
    inner, conv = mixer_widths(sizes)
    held, f = sizes["experts_held"], sizes["moe_intermediate_size"]
    shared = sizes["n_shared_experts"] \
        * sizes["moe_shared_expert_intermediate_size"]
    specs = [("embed_tokens.w_0", (sizes["vocab_held"], d), "normal", std)]

    def add(name, shape, kind="normal"):
        specs.append((name, tuple(shape), kind,
                      std if kind == "normal" else 0.0))

    for i, part in enumerate(parts(sizes)):
        p = f"layer_{i}"
        add(p + "_norm.w_0", (d,), "ones")
        if part == "mixer":
            add(p + "_mixer_in.w_0", (d, inner + conv + mh))
            add(p + "_mixer_conv.w_0", (conv, sizes["conv_kernel"]), "conv")
            add(p + "_mixer_conv.b_0", (conv,), "conv")
            add(p + "_mixer_dt.b_0", (mh,), "dt_bias")
            add(p + "_mixer_a_log.w_0", (mh,), "a_log")
            add(p + "_mixer_d.w_0", (mh,), "ones")
            add(p + "_mixer_norm.w_0", (inner,), "ones")
            add(p + "_mixer_out.w_0", (inner, d))
        elif part == "attention":
            add(p + "_attn_q.w_0", (d, h * hd))
            add(p + "_attn_k.w_0", (d, hkv * hd))
            add(p + "_attn_v.w_0", (d, hkv * hd))
            add(p + "_attn_o.w_0", (h * hd, d))
        elif part == "experts":
            add(p + "_router.w_0", (sizes["router_experts"], d))
            add(p + "_router.b_0", (sizes["router_experts"],), "zeros")
            add(p + "_experts_up.w_0", (held, d, f))
            add(p + "_experts_down.w_0", (held, f, d))
            add(p + "_shared_up.w_0", (d, shared))
            add(p + "_shared_down.w_0", (shared, d))
        else:
            add(p + "_mlp_up.w_0", (d, sizes["intermediate_size"]))
            add(p + "_mlp_down.w_0", (sizes["intermediate_size"], d))
    add("final_norm.w_0", (d,), "ones")
    add("lm_head.w_0", (d, sizes["vocab_held"]))
    return specs


def is_buffer(name):
    return name.endswith("_router.b_0")


def trainable_names(sizes):
    return [n for n, _, _, _ in param_specs(sizes) if not is_buffer(n)]


def _init_leaf(key, i, shape, kind, std, sizes):
    if kind == "a_log":
        return jnp.log(jax.random.uniform(
            jax.random.fold_in(key, i), shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":
        lo, hi = np.log(sizes["time_step_min"]), np.log(sizes["time_step_max"])
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            jax.random.fold_in(key, i), shape, jnp.float32, lo, hi)),
            sizes["time_step_floor"])
        return step + jnp.log(-jnp.expm1(-step))     # softplus^-1
    if kind == "conv":
        bound = sizes["conv_kernel"] ** -0.5
        return jax.random.uniform(jax.random.fold_in(key, i), shape,
                                  jnp.float32, -bound, bound)
    return base._init_leaf(key, i, shape, kind, std)


def init_params(sizes, seed):
    """All weights (the buffer too) in float32 on the default device, one
    jitted call from the seed."""
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

def causal_conv(u, w, b):
    """u [B, S, C], w [C, K], b [C]: b + sum_j w[:, j] u[t - (K - 1) +
    j], tokens before the first zero."""
    k, s = w.shape[1], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(padded[:, j:j + s] * w[:, j] for j in range(k))


def recurrence(x, dt, a, b, c, d, block=SCAN_BLOCK, reset_every=0,
               decay_dtype=None):
    """y [B, S, H, P] of x [B, S, H, P], dt [B, S, H] (> 0), a [H] (< 0),
    b, c [B, S, G, N], d [H]: the state S [B, G, H / G, P, N] from zero,
    one token a step. `reset_every` > 0 zeroes the state before every
    token whose index is a multiple of it (a fault); `decay_dtype`
    rounds dt a and exp(dt a) to that type's precision (a fault)."""
    bs, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    hg = h // g
    block = min(block, s)
    if s % block:
        raise ValueError(f"{s} tokens in blocks of {block}")
    decay = jnp.exp(dt * a)
    if decay_dtype is not None:
        # `reduce_precision`, not `astype`: XLA keeps excess precision
        # through a cast down and up again and the fault would vanish
        bits = jnp.finfo(decay_dtype)
        decay = jax.lax.reduce_precision(
            jnp.exp(jax.lax.reduce_precision(dt * a, bits.nexp, bits.nmant)),
            bits.nexp, bits.nmant)
    keep = jnp.ones((s,), jnp.float32)
    if reset_every:
        keep = (jnp.arange(s) % reset_every != 0).astype(jnp.float32)

    def step(state, tok):
        x_t, dt_t, decay_t, b_t, c_t, keep_t = tok
        x_t = x_t.reshape(bs, g, hg, p)
        scale = (dt_t.reshape(bs, g, hg)[..., None] * x_t)[..., None]
        state = (keep_t * decay_t.reshape(bs, g, hg))[..., None, None] \
            * state + scale * b_t[:, :, None, None, :]
        y_t = jnp.sum(state * c_t[:, :, None, None, :], axis=-1)
        return state, (y_t + d.reshape(g, hg)[None, :, :, None] * x_t
                       ).reshape(bs, h, p)

    def blocks(t):
        return jnp.moveaxis(t, 1, 0).reshape((s // block, block)
                                             + t.shape[:1] + t.shape[2:])

    run_block = jax.checkpoint(lambda state, toks: jax.lax.scan(
        step, state, toks))
    _, y = jax.lax.scan(
        run_block, jnp.zeros((bs, g, hg, p, n), jnp.float32),
        (blocks(x), blocks(dt), blocks(decay), blocks(b), blocks(c),
         keep.reshape(s // block, block)))
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)


def gated_group_norm(y, z, w, groups, eps):
    """RMSNorm(y * silu(z)) * w, the mean square within each of `groups`
    equal groups of the last axis."""
    v = y * jax.nn.silu(z)
    grouped = v.reshape(v.shape[:-1] + (groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + eps)
    return grouped.reshape(v.shape) * w


def _mixer(ar, p, x, sizes, fault):
    mh, mp = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    g, n = sizes["n_groups"], sizes["ssm_state_size"]
    inner, conv = mixer_widths(sizes)
    b, s, _ = x.shape
    proj = ar.act(ar.dot("bsd,de->bse", x, p["mixer_in.w_0"]))
    z, u, dt = proj[..., :inner], proj[..., inner:inner + conv], \
        proj[..., inner + conv:]
    u = ar.act(jax.nn.silu(causal_conv(u, p["mixer_conv.w_0"],
                                       p["mixer_conv.b_0"])))
    xs = u[..., :inner].reshape(b, s, mh, mp)
    bm = u[..., inner:inner + g * n].reshape(b, s, g, n)
    cm = u[..., inner + g * n:].reshape(b, s, g, n)
    y = recurrence(
        xs, jax.nn.softplus(dt + p["mixer_dt.b_0"]),
        -jnp.exp(p["mixer_a_log.w_0"]), bm, cm, p["mixer_d.w_0"],
        reset_every=sizes["chunk_size"] if fault == "state_dropped" else 0,
        decay_dtype=jnp.bfloat16 if fault == "decay_bf16" else None)
    y = ar.act(y).reshape(b, s, inner)
    y = ar.act(gated_group_norm(
        y, z, p["mixer_norm.w_0"],
        1 if fault == "norm_all_channels" else g, sizes["layer_norm_epsilon"]))
    return ar.act(ar.dot("bse,ed->bsd", y, p["mixer_out.w_0"]))


def _attention(ar, p, x, sizes, query_rows):
    h, hkv, hd = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"]
    b, s, _ = x.shape

    def heads(part, n):
        return ar.act(ar.dot("bsd,de->bse", x, p[f"attn_{part}.w_0"])
                      ).reshape(b, s, n, hd)

    q, k, v = heads("q", h), heads("k", hkv), heads("v", hkv)
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))
    ctx = moe._causal_attention(ar, q, k, v, query_rows)
    return ar.act(ar.dot("bse,ed->bsd", ctx.reshape(b, s, h * hd),
                         p["attn_o.w_0"]))


def relu2_ffn(ar, y, w_up, w_down, fault=None):
    """W_down relu(W_up y)^2 for tokens y [T, D]."""
    hidden = jax.nn.relu(ar.act(ar.dot("td,df->tf", y, w_up)))
    if fault != "relu_unsquared":
        hidden = hidden * hidden
    return ar.act(ar.dot("tf,fd->td", ar.act(hidden), w_down))


def routed_experts(ar, y, choice, w, w_up, w_down, first, fault=None):
    """sum over the choices held here of w_e * E_e(y): a plain loop over
    the held experts, each over every token and weighted by the routing
    weight the token gave it (zero where it did not choose it)."""
    out = jnp.zeros_like(y)
    for e in range(w_up.shape[0]):
        w_e = jnp.sum(jnp.where(choice == first + e, w, 0.0), axis=1)
        out = out + w_e[:, None] * relu2_ffn(ar, y, w_up[e], w_down[e],
                                             fault)
    return out


def moe_layer(ar, p, y, sizes, fault=None):
    """(routed experts held here + the shared expert for tokens y [T, D],
    the router's choice)."""
    choice, w = route(y, p["router.w_0"], p["router.b_0"], sizes, fault)
    routed = routed_experts(ar, y, choice, w, p["experts_up.w_0"],
                            p["experts_down.w_0"], sizes["first_expert"],
                            fault)
    return routed + relu2_ffn(ar, y, p["shared_up.w_0"],
                              p["shared_down.w_0"], fault), choice


def _layer(ar, p, h, part, sizes, query_rows, fault):
    x = ar.act(_rms_norm(h, p["norm.w_0"], sizes["layer_norm_epsilon"]))
    b, s, d = x.shape
    choice = None
    if part == "mixer":
        out = _mixer(ar, p, x, sizes, fault)
    elif part == "attention":
        out = _attention(ar, p, x, sizes, query_rows)
    elif part == "experts":
        out, choice = moe_layer(ar, p, x.reshape(b * s, d), sizes, fault)
        out = out.reshape(b, s, d)
    else:
        out = relu2_ffn(ar, x.reshape(b * s, d), p["mlp_up.w_0"],
                        p["mlp_down.w_0"]).reshape(b, s, d)
    return ar.act(h + out), choice


def _loss_sum(params, buffers, batch, sizes, precision, query_rows, fault):
    """(sum over the block's counted positions of the cross-entropy, the
    routers' choices int32 [expert layers, tokens, top-k])."""
    ar = base._Arithmetic(precision)
    params = {**params, **buffers}
    h = ar.act(params["embed_tokens.w_0"][batch["input_ids"]])
    choices = []
    for i, part in enumerate(parts(sizes)):
        # one layer at a time, its activations made again in the backward
        # pass; the layers are not stacked for a scan (they differ)
        block = jax.checkpoint(lambda h, p, part=part: _layer(
            ar, p, h, part, sizes, query_rows, fault))
        h, choice = block(h, _of_layer(params, i))
        if choice is not None:
            choices.append(choice)
    choices = jnp.stack(choices) if choices else \
        jnp.zeros((0,) + h.shape[:1], jnp.int32)

    @jax.checkpoint
    def head(h):
        x = ar.act(_rms_norm(h, params["final_norm.w_0"],
                             sizes["layer_norm_epsilon"]))
        logits = ar.act(ar.dot("bsd,dv->bsv", x, params["lm_head.w_0"]))
        lse = jax.nn.logsumexp(logits, axis=-1)
        l_y = jnp.take_along_axis(
            logits, batch["labels"][..., None], axis=-1)[..., 0]
        ce = lse - l_y
        if fault == "half_positions":
            ce = ce[:, :ce.shape[1] // 2]
        return jnp.sum(ce)
    return head(h), choices


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
