"""Plain reference of the 2017 encoder-decoder Transformer's training step.

Straight `jax.numpy` in float32 with every matrix product at
`Precision.HIGHEST`: both embeddings with the sinusoidal position
encoding, post-norm encoder and decoder blocks (self- and cross-attention,
ReLU feed-forward), the logits projection, the label-smoothed
cross-entropy as a token-weighted mean, its gradient by `jax.grad`, and
Adam with bias correction. No kernels, no mixed precision, no batching
tricks; it imports nothing of `paddle_tpu` and takes nothing the program
has made — weights come from `init_params(sizes, seed)`, batches from the
harness, both from the seed.

Departures from the paper, each because the program under test does the
same and the comparison is of the program's mathematics:
  * the position encoding is [sin(all frequencies) | cos(all
    frequencies)] along the channel axis, not interleaved — a fixed
    permutation of channels (Fluid's add_position_encoding);
  * embeddings are scaled by sqrt(d_model) and are not tied to the output
    projection; the projection has no bias;
  * dropout is 0.0 (the configuration's one `reduced` key);
  * Adam is beta1 0.9, beta2 0.999, epsilon 1e-8 at a constant rate, the
    update p -= lr*sqrt(1-b2^t)/(1-b1^t) * m/(sqrt(v)+eps) (Fluid's
    adam op), not the paper's schedule.

The loss is a mean over tokens with no coupling across examples, so a
step goes `rows_per_block` examples at a time, each layer under
`jax.checkpoint` (one layer's code, scanned over the depth), and the gradients of the blocks' loss SUMS are added:
at S=4096 one f32 score tensor is 0.5 GB per example.

`precision` selects the arithmetic of the matrix products:
  "f32"   the reference proper;
  "fp8"   the CONTROL of the comparison: float8 wherever the program
          under test has bfloat16. Both operands of every product and
          every activation it keeps in its compute type (a product's
          result, a softmax, a norm's result, an embedding, the logits)
          are rounded to float8_e4m3fn in the forward pass, and the
          gradient that comes back through each to float8_e5m2, one scale
          per tensor (amax -> the format's largest finite value);
          products accumulate in float32, as the MXU does. It is the
          nearest precision below the configuration's bfloat16 and has
          to come out as not correct.
  "fp8_mm"  the products' operands and incoming gradients alone (the
          usual float8 matmul recipe); "fp8_fwd" the forward operands
          alone. Both read within a factor of three of bfloat16 on the
          chip (PERF.md) and are kept for those readings.
`rows` restricts every batch to a subset of its rows (the "half of the
batch left out, the mean taken over the rest" fault, planted in the
reference put in the program's place).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
_NEG = -1e9


def param_specs(sizes):
    """[(name, shape, kind, std)] in the program's parameter names; kind
    is "normal", "zeros" or "ones"."""
    d, dff = sizes["d_model"], sizes["d_ff"]
    specs = [("src_word_emb.w_0", (sizes["src_vocab_size"], d), "normal",
              d ** -0.5),
             ("trg_word_emb.w_0", (sizes["trg_vocab_size"], d), "normal",
              d ** -0.5)]

    def linear(name, n_in, n_out):
        specs.append((name + ".w_0", (n_in, n_out), "normal", 0.02))
        specs.append((name + ".b_0", (n_out,), "zeros", 0.0))

    def attn(name):
        for part in "qkvo":
            linear(f"{name}_{part}", d, d)
        norm(name)

    def norm(name):
        specs.append((name + "_ln.w_0", (d,), "ones", 0.0))
        specs.append((name + "_ln.b_0", (d,), "zeros", 0.0))

    def ffn(name):
        linear(name + "_fc1", d, dff)
        linear(name + "_fc2", dff, d)
        norm(name)

    for i in range(sizes["num_encoder_layers"]):
        attn(f"enc_{i}_attn")
        ffn(f"enc_{i}_ffn")
    for i in range(sizes["num_decoder_layers"]):
        attn(f"dec_{i}_self_attn")
        attn(f"dec_{i}_cross_attn")
        ffn(f"dec_{i}_ffn")
    specs.append(("trg_proj.w_0", (d, sizes["trg_vocab_size"]), "normal",
                  0.02))
    return specs


def _seed_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _init_leaf(key, i, shape, kind, std):
    if kind == "normal":
        return std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32)
    return (jnp.ones if kind == "ones" else jnp.zeros)(shape, jnp.float32)


def init_params(sizes, seed):
    """All weights in float32 on the default device, one jitted call from
    the seed (threefry: the same bits on every backend)."""
    specs = param_specs(sizes)

    @jax.jit
    def make(key):
        return {name: _init_leaf(key, i, shape, kind, std)
                for i, (name, shape, kind, std) in enumerate(specs)}

    return make(_seed_key(seed))


SAMPLE_PER_LEAF = 2048


def sample_indices(sizes, seed):
    """{leaf: flat indices} — up to SAMPLE_PER_LEAF elements of each leaf,
    drawn from the seed, at which both sides' first gradients are read."""
    rng = np.random.default_rng([int(seed), 0x5A4D])
    out = {}
    for name, shape, _, _ in param_specs(sizes):
        n = int(np.prod(shape))
        out[name] = np.sort(rng.choice(n, size=min(n, SAMPLE_PER_LEAF),
                                       replace=False)).astype(np.int32)
    return out


def gather_samples(arrays, indices, scale=1.0):
    """{leaf: float32 numpy values at the sampled indices, times scale}."""
    names = sorted(indices)
    got = jax.jit(lambda xs, ix: [x.reshape(-1)[i] * scale
                                  for x, i in zip(xs, ix)])(
        [arrays[n] for n in names], [indices[n] for n in names])
    return {n: np.asarray(g, np.float32) for n, g in zip(names, got)}


def delta_norms_from_seed(sizes, seed, arrays):
    """{leaf: |p - p_0|} for `arrays` {leaf: p}, p_0 drawn again from the
    seed leaf by leaf inside one jitted call, so that no second copy of
    the weights is ever held."""
    specs = param_specs(sizes)

    @jax.jit
    def norms(key, ps):
        return {name: jnp.sqrt(jnp.sum(jnp.square(
            ps[name] - _init_leaf(key, i, shape, kind, std))))
            for i, (name, shape, kind, std) in enumerate(specs)}

    return {n: float(x)
            for n, x in norms(_seed_key(seed), dict(arrays)).items()}


# ---------------------------------------------------------------- forward

def _quant(x, dtype, top):
    """Round to a float8 format with one scale per tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _e4m3(x):
    return _quant(x, jnp.float8_e4m3fn, 448.0)


def _e5m2(x):
    return _quant(x, jnp.float8_e5m2, 57344.0)


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HI)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_dot(spec, a, b):
    return _einsum(spec, _e4m3(a), _e4m3(b))


def _fp8_dot_fwd(spec, a, b):
    qa, qb = _e4m3(a), _e4m3(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _fp8_dot_bwd(spec, saved, g):
    _, vjp = jax.vjp(functools.partial(_einsum, spec), *saved)
    return vjp(_e5m2(g))


_fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


@jax.custom_vjp
def _fp8_act(x):
    return _e4m3(x)


_fp8_act.defvjp(lambda x: (_e4m3(x), None), lambda _, g: (_e5m2(g),))


def _straight_through(x, q):
    return x + jax.lax.stop_gradient(q(x) - x)


class _Arithmetic:
    """`dot`, the matrix product, and `act`, what an activation goes
    through where the program under test keeps it in its compute type (a
    product's result, a softmax, a norm's result, an embedding)."""

    def __init__(self, precision):
        self.act = lambda x: x
        if precision == "f32":
            self.dot = _einsum
        elif precision == "fp8":
            self.dot, self.act = _fp8_dot, _fp8_act
        elif precision == "fp8_mm":
            self.dot = _fp8_dot
        elif precision == "fp8_fwd":
            self.dot = lambda spec, a, b: _einsum(
                spec, _straight_through(a, _e4m3),
                _straight_through(b, _e4m3))
        else:
            raise ValueError(f"unknown precision {precision!r}")


def _position_encoding(length, d):
    pos = np.arange(length)[:, None]
    i = np.arange(d // 2)[None, :]
    angle = pos / np.power(10000.0, 2.0 * i / d)
    return jnp.asarray(np.concatenate([np.sin(angle), np.cos(angle)], -1),
                       jnp.float32)


def _layer_norm(x, w, b):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * w + b


def _attention(ar, p, name, x_q, x_kv, n_head, causal):
    def lin(part, x):
        return ar.act(ar.dot("bsd,de->bse", x, p[f"{name}_{part}.w_0"])
                      + p[f"{name}_{part}.b_0"])
    b, sq, d = x_q.shape
    sk = x_kv.shape[1]
    dh = d // n_head
    q = lin("q", x_q).reshape(b, sq, n_head, dh)
    k = lin("k", x_kv).reshape(b, sk, n_head, dh)
    v = lin("v", x_kv).reshape(b, sk, n_head, dh)
    scores = ar.dot("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
    if causal:
        keep = jnp.tril(jnp.ones((sq, sk), jnp.bool_))
        scores = jnp.where(keep[None, None], scores, _NEG)
    probs = ar.act(jax.nn.softmax(scores, axis=-1))
    ctx = ar.act(ar.dot("bhqk,bkhd->bqhd", probs, v)).reshape(b, sq, d)
    return lin("o", ctx)


def _ffn(ar, p, name, x):
    h = ar.act(jax.nn.relu(ar.dot("bsd,df->bsf", x, p[name + "_fc1.w_0"])
                           + p[name + "_fc1.b_0"]))
    return ar.act(ar.dot("bsf,fd->bsd", h, p[name + "_fc2.w_0"])
                  + p[name + "_fc2.b_0"])


def _residual_norm(ar, p, name, x, sub):
    return ar.act(_layer_norm(x + sub, p[name + "_ln.w_0"],
                              p[name + "_ln.b_0"]))


def _layer_stack(params, prefix, n):
    """{leaf name within a layer: [n, ...] array} of `prefix`_<i>_<leaf>,
    so that one block's code is traced once and scanned over the depth."""
    head = f"{prefix}_0_"
    leaves = sorted(k[len(head):] for k in params if k.startswith(head))
    return {leaf: jnp.stack([params[f"{prefix}_{i}_{leaf}"]
                             for i in range(n)]) for leaf in leaves}


def _loss_sum(params, batch, sizes, precision):
    """Sum over the block's tokens of weight * label-smoothed CE."""
    ar = _Arithmetic(precision)
    d, n_head = sizes["d_model"], sizes["num_heads"]
    eps = sizes["label_smoothing"]

    def embed(table, ids):
        return ar.act(table[ids] * d ** 0.5
                      + _position_encoding(ids.shape[1], d))

    @jax.checkpoint
    def enc_block(x, p):
        x = _residual_norm(ar, p, "attn", x, _attention(
            ar, p, "attn", x, x, n_head, False))
        return _residual_norm(ar, p, "ffn", x, _ffn(ar, p, "ffn", x))

    @jax.checkpoint
    def dec_block(x, mem, p):
        x = _residual_norm(ar, p, "self_attn", x, _attention(
            ar, p, "self_attn", x, x, n_head, True))
        x = _residual_norm(ar, p, "cross_attn", x, _attention(
            ar, p, "cross_attn", x, mem, n_head, False))
        return _residual_norm(ar, p, "ffn", x, _ffn(ar, p, "ffn", x))

    x = embed(params["src_word_emb.w_0"], batch["src_ids"])
    mem, _ = jax.lax.scan(
        lambda x, p: (enc_block(x, p), None), x,
        _layer_stack(params, "enc", sizes["num_encoder_layers"]))
    y = embed(params["trg_word_emb.w_0"], batch["trg_ids"])
    y, _ = jax.lax.scan(
        lambda y, p: (dec_block(y, mem, p), None), y,
        _layer_stack(params, "dec", sizes["num_decoder_layers"]))

    @jax.checkpoint
    def head(y):
        logits = ar.act(ar.dot("bsd,dv->bsv", y, params["trg_proj.w_0"]))
        lse = jax.nn.logsumexp(logits, axis=-1)
        l_y = jnp.take_along_axis(
            logits, batch["lbl_ids"][..., None], axis=-1)[..., 0]
        ce = lse - (1.0 - eps) * l_y - eps * jnp.mean(logits, axis=-1)
        return jnp.sum(ce * batch["lbl_w"])
    return head(y)


def _adam(params, grads, m, v, t, sizes):
    b1, b2 = sizes["adam_beta1"], sizes["adam_beta2"]
    eps, lr = sizes["adam_epsilon"], sizes["learning_rate"]
    lr_t = lr * math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    new_p, new_m, new_v = {}, {}, {}
    for n in params:
        new_m[n] = b1 * m[n] + (1.0 - b1) * grads[n]
        new_v[n] = b2 * v[n] + (1.0 - b2) * grads[n] * grads[n]
        new_p[n] = params[n] - lr_t * new_m[n] / (jnp.sqrt(new_v[n]) + eps)
    return new_p, new_m, new_v


def _leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(a))) for n, a in tree.items()}


@functools.lru_cache(maxsize=8)
def _programs(sizes_items, precision):
    """The reference's jitted pieces for one (sizes, precision)."""
    sizes = dict(sizes_items)

    @jax.jit
    def block_grad(params, block):
        return jax.value_and_grad(_loss_sum)(params, block, sizes,
                                             precision)

    @functools.partial(jax.jit, static_argnums=(5,),
                       donate_argnums=(0, 2, 3))
    def update(params, grads, m, v, count, t):
        grads = {n: g / count for n, g in grads.items()}
        return _adam(params, grads, m, v, t, sizes) + (_leaf_norms(grads),)

    @jax.jit
    def add(a, b):
        return jax.tree_util.tree_map(jnp.add, a, b)

    return block_grad, update, add


def run(sizes, pool, seed, steps=3, precision="f32", rows=None,
        rows_per_block=16):
    """Train `steps` steps from the seed's weights on pool[0..steps-1].
    Returns {"losses": [...], "grad_norms": {leaf: |g_1|},
    "grad_sample": {leaf: g_1 at the seed's sampled elements},
    "delta_norms": {leaf: |p_steps - p_0|}}."""
    keys = ("src_ids", "trg_ids", "lbl_ids", "lbl_w")
    block_grad, update, add = _programs(
        tuple(sorted((k, v) for k, v in sizes.items()
                     if isinstance(v, (int, float, str)))), precision)

    params = init_params(sizes, seed)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t in range(1, steps + 1):
        batch = {k: np.asarray(pool[(t - 1) % len(pool)][k]) for k in keys}
        if rows is not None:
            batch = {k: a[rows] for k, a in batch.items()}
        n_rows = batch["src_ids"].shape[0]
        count = float(batch["lbl_w"].sum())
        total, grads = 0.0, None
        for lo in range(0, n_rows, rows_per_block):
            block = {k: a[lo:lo + rows_per_block] for k, a in batch.items()}
            val, g = block_grad(params, block)
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
            "delta_norms": delta_norms_from_seed(sizes, seed, params)}
