"""Plain reference of a decoder-hybrid-decoder language model's training
step (SambaY, the `phi4flash` block): Mamba-1 mixers and sliding-window
attention in the self-decoder, one full-attention layer, and a
cross-decoder of gated memory units that read the last Mamba layer's
scan readout and cross-attention layers that read the full layer's keys
and values; differential attention on every attention layer, no
positions, LayerNorm, a dense SwiGLU in every layer and a tied head, of
which one chip's SHARE is computed: the layers of one pipeline stage and
`vocab_held` rows of the embedding (which is the head).

Straight `jax.numpy` in float32, every matrix product at
`Precision.HIGHEST`, no kernels, no mixed precision; it imports nothing
of `paddle_tpu` and takes nothing the program has made — weights come
from `init_params(sizes, seed)`, batches from the harness, both from the
seed. The float8 arithmetic of the control, Adam and the sampling of
gradient elements are `transformer_encdec_reference`'s; the attention in
query blocks is `swa_gqa_moe_decoder_reference`'s `band_attention`; the
causal convolution is `mamba_gqa_moe_decoder_reference`'s.

The equations (h [B, S, D]); `layers` is a string, one character a layer:
`M` Mamba, `S` sliding-window attention, `F` full attention, `G` gated
memory unit, `X` cross-attention. Every layer: h += mixer(LN_1(h)); h +=
W_down(silu(y W_gate) * y W_up), y = LN_2(h); LN(x) = (x - mean) /
sqrt(var + eps) * w + b over the last axis.

  M, x = LN_1(h): [u | z] = x W_in (I = expand D each); u = silu(conv(u) +
    b), depthwise and causal over the last `mamba_d_conv` tokens;
    [delta | B | C] = u W_x (R, N, N); dt = softplus(delta W_dt + b_dt),
    A = -exp(A_log) [I, N]. THE RECURRENCE, token by token, a state h [I,
    N] from zero: h_t = exp(dt_t A) * h_{t-1} + (dt_t u_t) (x) B_t; y_t =
    h_t C_t + D * u_t (`lax.scan` over the tokens, `SCAN_BLOCK` tokens
    under one `jax.checkpoint`). The layer's memory m = y, BEFORE the
    gate; out = (y * silu(z)) W_out.
  G, x = LN_1(h): out = (silu(x W_in) * m) W_out, m the last M layer's.
  S / F / X, x = LN_1(h): q = x W_q -> [S, H, d]; on S and F k = x W_k,
    v = x W_v -> [S, Hkv, d], on X the last F layer's k and v. No rotary.
    Query heads (2i, 2i + 1) are (q1_i, q2_i), key heads (2j, 2j + 1)
    (k1_j, k2_j), the value heads read in pairs as heads of 2d:
    v_j = [v_2j | v_2j+1]. Differential head i reads pair j = i // (H /
    Hkv): a_1 = softmax(q1_i k1_j^T / sqrt(d)) v_j, a_2 the same of q2,
    k2; admitted where j <= i and, on S, i - j < window.
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, lambda_init
    = 0.8 - 0.6 exp(-0.3 l) at the layer's PUBLISHED index l;
    o_i = (1 - lambda_init) RMSNorm(a_1 - lambda a_2) * w_sub (eps
    1e-5, over 2d); out = [o_0 .. o_{H/2 - 1}] W_o.
  Head: a final LN, then the embedding's own matrix transposed (tied)
    over the held vocabulary slice; next-token cross-entropy, mean over
    positions.

Attention goes `query_rows` query rows at a time under `jax.checkpoint`,
each layer under `jax.checkpoint`, the layers unrolled.

`precision`: "f32" the reference proper; "fp8" the CONTROL (float8
wherever the program has bfloat16: both operands of every product, every
activation kept in the compute type — the scan's x, B, C and y among
them — and the returning gradients; dt, A and the state stay float32 as
in the program); "fp8_mm" the products alone. `fault` plants a fault in
the reference put in the program's place (FAULTS); `FAULTS_UNSEEN` two
that no limit of the cell sees:
  "scan_state_bf16"    the scan's state rounded to bfloat16 after every
                       token;
  "scan_dt_bf16"       the scan's dt rounded to bfloat16;
  "lambda_a2_dropped"  o = RMSNorm(a_1): the second map left out;
  "window_dropped"     the window layers causal over the whole prefix;
  "memory_gated"       the gated memory units read the Mamba layer's
                       gated output y * silu(z) in place of y;
  "half_positions"     the second half of every sequence left out of the
                       loss, the mean over the rest.
`rows` restricts every batch to a subset of its rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import mamba_gqa_moe_decoder_reference as mamba2
from . import mla_moe_decoder_reference as moe
from . import swa_gqa_moe_decoder_reference as swa
from . import transformer_encdec_reference as base

SAMPLE_PER_LEAF = base.SAMPLE_PER_LEAF
SCAN_BLOCK = 64            # tokens of the recurrence under one checkpoint
gather_samples = base.gather_samples
causal_conv = mamba2.causal_conv
band_attention = swa.band_attention
_of_layer = moe._of_layer

KINDS = {"M": "mamba", "S": "window", "F": "full", "G": "gmu",
         "X": "cross"}
# the faults the cell's limits are there to catch (benchmark/limits/
# phi4flash_s4096.json reads each over a limit on every seed)
FAULTS = ("lambda_a2_dropped", "window_dropped", "memory_gated",
          "half_positions")
# faults the cell's limits cannot see: one Mamba layer of five, each
# within the bf16 rounding of the step's activations (the limits file
# gives their readings); they are here to show how far under that they
# read, and for a comparison of the Mamba layer's readout that would
# see them
FAULTS_UNSEEN = ("scan_state_bf16", "scan_dt_bf16")
SUBLN_EPS = 1e-5


def kinds(sizes):
    """Each layer's mixer, from the `layers` string."""
    return [KINDS[ch] for ch in sizes["layers"]]


def count(sizes, kind):
    """Layers whose mixer is `kind` ("mamba", "window", "full", "gmu",
    "cross"), or every layer ("mlp")."""
    if kind == "mlp":
        return len(sizes["layers"])
    return kinds(sizes).count(kind)


def mamba_widths(sizes):
    """(inner channels I, state N, the step's rank R)."""
    return (sizes["mamba_expand"] * sizes["hidden_size"],
            sizes["mamba_d_state"], sizes["mamba_dt_rank"])


def lambda_init(sizes, i):
    """lambda_init of layer i: 0.8 - 0.6 exp(-0.3 l), l = the published
    index `first_layer` + i."""
    return 0.8 - 0.6 * math.exp(-0.3 * (sizes["first_layer"] + i))


def param_specs(sizes):
    """[(name, shape, kind, std)] in the program's parameter names; kind
    is "normal", "ones", "zeros", or a mixer's own: "a_log" = log(1 ..
    N) each channel, "dt_bias" = softplus^-1 of a log-uniform draw in
    [time_step_min, time_step_max] floored at time_step_floor (the
    published initialiser's draw), "conv" = U(-1 / sqrt(taps), 1 /
    sqrt(taps)), the depthwise Conv1d's own default. The lambda vectors
    are normal at std 0.1."""
    d, std = sizes["hidden_size"], sizes["initializer_range"]
    h, hkv, hd = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"]
    inner, n, rank = mamba_widths(sizes)
    f = sizes["intermediate_size"]
    specs = [("embed_tokens.w_0", (sizes["vocab_held"], d), "normal", std)]

    def add(name, shape, kind="normal", sd=None):
        specs.append((name, tuple(shape), kind,
                      (std if sd is None else sd) if kind == "normal"
                      else 0.0))

    def norm(name, width=d):
        add(name + ".w_0", (width,), "ones")
        add(name + ".b_0", (width,), "zeros")

    for i, kind in enumerate(kinds(sizes)):
        p = f"layer_{i}"
        if kind == "mamba":
            norm(p + "_mamba_norm")
            add(p + "_mamba_in.w_0", (d, 2 * inner))
            add(p + "_mamba_conv.w_0", (inner, sizes["mamba_d_conv"]),
                "conv")
            add(p + "_mamba_conv.b_0", (inner,), "conv")
            add(p + "_mamba_x.w_0", (inner, rank + 2 * n))
            add(p + "_mamba_dt.w_0", (rank, inner))
            add(p + "_mamba_dt.b_0", (inner,), "dt_bias")
            add(p + "_mamba_a_log.w_0", (inner, n), "a_log")
            add(p + "_mamba_d.w_0", (inner,), "ones")
            add(p + "_mamba_out.w_0", (inner, d))
        elif kind == "gmu":
            norm(p + "_gmu_norm")
            add(p + "_gmu_in.w_0", (d, inner))
            add(p + "_gmu_out.w_0", (inner, d))
        else:
            norm(p + "_attn_norm")
            add(p + "_attn_q.w_0", (d, h * hd))
            if kind != "cross":
                add(p + "_attn_k.w_0", (d, hkv * hd))
                add(p + "_attn_v.w_0", (d, hkv * hd))
            for part in ("q1", "k1", "q2", "k2"):
                add(f"{p}_attn_lambda_{part}.w_0", (hd,), sd=0.1)
            add(p + "_attn_subln.w_0", (2 * hd,), "ones")
            add(p + "_attn_o.w_0", (h * hd, d))
        norm(p + "_ffn_norm")
        add(p + "_mlp_gate.w_0", (d, f))
        add(p + "_mlp_up.w_0", (d, f))
        add(p + "_mlp_down.w_0", (f, d))
    norm("final_norm")
    return specs


def is_buffer(name):
    return False


def trainable_names(sizes):
    return [n for n, _, _, _ in param_specs(sizes)]


def _init_leaf(key, i, shape, kind, std, sizes):
    if kind == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1.0, shape[1] + 1.0, dtype=jnp.float32)), shape)
    if kind == "dt_bias":
        lo, hi = np.log(sizes["time_step_min"]), np.log(sizes["time_step_max"])
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            jax.random.fold_in(key, i), shape, jnp.float32, lo, hi)),
            sizes["time_step_floor"])
        return step + jnp.log(-jnp.expm1(-step))     # softplus^-1
    if kind == "conv":
        bound = sizes["mamba_d_conv"] ** -0.5
        return jax.random.uniform(jax.random.fold_in(key, i), shape,
                                  jnp.float32, -bound, bound)
    return base._init_leaf(key, i, shape, kind, std)


def init_params(sizes, seed):
    """All weights in float32 on the default device, one jitted call from
    the seed."""
    specs = param_specs(sizes)

    @jax.jit
    def make(key):
        return {name: _init_leaf(key, i, shape, kind, std, sizes)
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
            ps[name] - _init_leaf(key, i, shape, kind, std, sizes))))
            for i, (name, shape, kind, std) in specs}

    return {n: float(x)
            for n, x in norms(base._seed_key(seed), dict(arrays)).items()}


# ---------------------------------------------------------------- forward

def _bf16(x):
    """x rounded to bfloat16's precision (`reduce_precision`: XLA keeps
    excess precision through a cast down and up again)."""
    bits = jnp.finfo(jnp.bfloat16)
    return jax.lax.reduce_precision(x, bits.nexp, bits.nmant)


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def recurrence(u, dt, a, b, c, d, block=SCAN_BLOCK, state_bf16=False):
    """y [B, S, I] of u, dt [B, S, I] (dt > 0), a [I, N] (< 0), b, c [B,
    S, N], d [I]: the state [B, I, N] from zero, one token a step.
    `state_bf16` rounds the state to bfloat16 after every token (a
    fault)."""
    bs, s, ch = u.shape
    block = min(block, s)
    if s % block:
        raise ValueError(f"{s} tokens in blocks of {block}")

    def step(h, tok):
        u_t, dt_t, b_t, c_t = tok
        h = jnp.exp(dt_t[:, :, None] * a[None]) * h \
            + (dt_t * u_t)[:, :, None] * b_t[:, None, :]
        if state_bf16:
            h = _bf16(h)
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + d * u_t

    def blocks(t):
        return jnp.moveaxis(t, 1, 0).reshape((s // block, block)
                                             + t.shape[:1] + t.shape[2:])

    run_block = jax.checkpoint(lambda h, toks: jax.lax.scan(step, h, toks))
    _, y = jax.lax.scan(run_block, jnp.zeros((bs, ch, a.shape[1]),
                                             jnp.float32),
                        tuple(blocks(t) for t in (u, dt, b, c)))
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)


def _mamba(ar, p, x, sizes, fault):
    """(out, the memory y before the gate, the gated y)."""
    inner, n, rank = mamba_widths(sizes)
    proj = ar.act(ar.dot("bsd,de->bse", x, p["mamba_in.w_0"]))
    u, z = proj[..., :inner], proj[..., inner:]
    u = ar.act(jax.nn.silu(causal_conv(u, p["mamba_conv.w_0"],
                                       p["mamba_conv.b_0"])))
    dbc = ar.act(ar.dot("bse,er->bsr", u, p["mamba_x.w_0"]))
    delta, bm, cm = dbc[..., :rank], dbc[..., rank:rank + n], \
        dbc[..., rank + n:]
    raw = ar.act(ar.dot("bsr,re->bse", delta, p["mamba_dt.w_0"]))
    dt = jax.nn.softplus(raw + p["mamba_dt.b_0"])
    if fault == "scan_dt_bf16":
        dt = _bf16(dt)
    y = ar.act(recurrence(u, dt, -jnp.exp(p["mamba_a_log.w_0"]), bm, cm,
                          p["mamba_d.w_0"],
                          state_bf16=fault == "scan_state_bf16"))
    gated = ar.act(y * jax.nn.silu(z))
    return ar.act(ar.dot("bse,ed->bsd", gated, p["mamba_out.w_0"])), y, \
        gated


def _gmu(ar, p, x, memory):
    gate = ar.act(ar.dot("bsd,de->bse", x, p["gmu_in.w_0"]))
    return ar.act(ar.dot("bse,ed->bsd", ar.act(jax.nn.silu(gate) * memory),
                         p["gmu_out.w_0"]))


def _attention(ar, p, x, kind, i, sizes, kv, query_rows, fault):
    """(out, (k1, k2, v) the layer attended: [B, S, Hkv / 2, d] twice
    and [B, S, Hkv / 2, 2 d])."""
    h, hkv, hd = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"]
    b, s, _ = x.shape
    pairs = hkv // 2

    def proj(part, heads):
        return ar.act(ar.dot("bsd,de->bse", x, p[f"attn_{part}.w_0"])
                      ).reshape(b, s, heads, hd)

    q = proj("q", h)
    if kv is None:
        k = proj("k", hkv)
        kv = (k[:, :, 0::2], k[:, :, 1::2],
              proj("v", hkv).reshape(b, s, pairs, 2 * hd))
    k1, k2, v = kv
    of_pair = jnp.arange(h // 2) // (h // hkv)
    window = None
    if kind == "window" and fault != "window_dropped":
        window = sizes["sliding_window"]
    a1, a2 = (band_attention(ar, q[:, :, m::2], kk[:, :, of_pair],
                             v[:, :, of_pair], window, query_rows)
              for m, kk in ((0, k1), (1, k2)))

    def dot(a, c):
        return jnp.sum(p[f"attn_lambda_{a}.w_0"] * p[f"attn_lambda_{c}.w_0"])
    init = lambda_init(sizes, i)
    lam = jnp.exp(dot("q1", "k1")) - jnp.exp(dot("q2", "k2")) + init
    o = a1 if fault == "lambda_a2_dropped" else a1 - lam * a2
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + SUBLN_EPS)
    o = ar.act(o * p["attn_subln.w_0"] * (1.0 - init))
    return ar.act(ar.dot("bse,ed->bsd", o.reshape(b, s, h * hd),
                         p["attn_o.w_0"])), kv


def _layer(ar, p, h, kind, i, sizes, shared, query_rows, fault):
    """(h, what the layer hands on: a Mamba layer its memory, a full
    layer its keys and values, others None)."""
    eps = sizes["layer_norm_eps"]
    x = ar.act(_layer_norm(h, p[f"{_NORM[kind]}.w_0"],
                           p[f"{_NORM[kind]}.b_0"], eps))
    made = None
    if kind == "mamba":
        out, memory, gated = _mamba(ar, p, x, sizes, fault)
        made = gated if fault == "memory_gated" else memory
    elif kind == "gmu":
        out = _gmu(ar, p, x, shared)
    else:
        out, kv = _attention(ar, p, x, kind, i, sizes,
                             shared if kind == "cross" else None,
                             query_rows, fault)
        made = kv if kind == "full" else None
    h = ar.act(h + out)
    y = ar.act(_layer_norm(h, p["ffn_norm.w_0"], p["ffn_norm.b_0"], eps))
    gate = ar.act(ar.dot("bsd,df->bsf", y, p["mlp_gate.w_0"]))
    up = ar.act(ar.dot("bsd,df->bsf", y, p["mlp_up.w_0"]))
    ffn = ar.dot("bsf,fd->bsd", ar.act(jax.nn.silu(gate) * up),
                 p["mlp_down.w_0"])
    return ar.act(h + ar.act(ffn)), made


_NORM = {"mamba": "mamba_norm", "gmu": "gmu_norm", "window": "attn_norm",
         "full": "attn_norm", "cross": "attn_norm"}


def _loss_sum(params, batch, sizes, precision, query_rows, fault):
    """The sum over the block's counted positions of the cross-entropy."""
    ar = base._Arithmetic(precision)
    table = params["embed_tokens.w_0"]
    h = ar.act(table[batch["input_ids"]])
    memory = kv = None
    for i, kind in enumerate(kinds(sizes)):
        # one layer at a time, its activations made again in the backward
        # pass; the layers are not stacked for a scan (they differ)
        block = jax.checkpoint(
            lambda h, p, shared, kind=kind, i=i: _layer(
                ar, p, h, kind, i, sizes, shared, query_rows, fault))
        h, made = block(h, _of_layer(params, i),
                        memory if kind == "gmu" else kv)
        if kind == "mamba":
            memory = made
        elif kind == "full":
            kv = made

    @jax.checkpoint
    def head(h, w):
        x = ar.act(_layer_norm(h, params["final_norm.w_0"],
                               params["final_norm.b_0"],
                               sizes["layer_norm_eps"]))
        logits = ar.act(ar.dot("bsd,vd->bsv", x, w))
        lse = jax.nn.logsumexp(logits, axis=-1)
        l_y = jnp.take_along_axis(
            logits, batch["labels"][..., None], axis=-1)[..., 0]
        ce = lse - l_y
        if fault == "half_positions":
            ce = ce[:, :ce.shape[1] // 2]
        return jnp.sum(ce)
    return head(h, table)


@functools.lru_cache(maxsize=16)
def _programs(sizes_items, precision, query_rows, fault):
    sizes = dict(sizes_items)

    @jax.jit
    def block_grad(params, block):
        return jax.value_and_grad(_loss_sum)(
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
    "delta_norms": {leaf: |p_steps - p_0|}}."""
    if fault is not None and fault not in FAULTS + FAULTS_UNSEEN:
        raise ValueError(f"unknown fault {fault!r}")
    block_grad, update, add = _programs(
        tuple(sorted((k, v) for k, v in sizes.items()
                     if isinstance(v, (int, float, str, bool)))),
        precision, int(query_rows), fault)
    params = init_params(sizes, seed)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
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
            val, g = block_grad(params, block)
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
            "delta_norms": delta_norms_from_seed(sizes, seed, params)}
