"""Family file: the 2017 encoder-decoder Transformer, trained.

What the harness asks of a family (all it knows of the model lives here):
sizes from a configuration file, the programs built from them through
the framework's own model file, batches from a seed, what an item is,
the FLOPs and bytes a step needs (from shapes, never from
`cost_analysis`), the kernels expected to route, how the first gradient
and the parameters are read out of the program's state, and the plain
reference (`transformer_encdec_reference.py`).
"""
from __future__ import annotations

import numpy as np

from . import transformer_encdec_reference as reference

ITEM = "non-pad target token (sum of lbl_w)"
KIND = "train"

# rehearsal only (CPU, explicit argument): the same code path at sizes an
# interpreter can run. Never a configuration file.
_REHEARSAL = dict(d_model=64, d_ff=128, num_heads=2, d_head=32,
                  num_encoder_layers=2, num_decoder_layers=2,
                  src_vocab_size=512, trg_vocab_size=512)
_REHEARSAL_TRAFFIC = dict(batch=8, src_len=16, trg_len=16,
                          reference_rows_per_block=4)

# fused_adam takes parameters at or over the registry's element floor
ADAM_KERNEL_MIN_NUMEL = 65536
# the flash kernels take an attention whose Sq*Sk reaches the crossover
FLASH_MIN_SEQ_PRODUCT = 1024 * 1024
# f32 p, m, v, g read; p, m, v written
ADAM_BYTES_PER_ELEMENT = 28


def sizes(config, rehearsal=False):
    """Flat sizes the family's functions take, from a configuration."""
    assumed = config["assumed"]
    out = {k: config[k] for k in (
        "d_model", "d_ff", "num_heads", "d_head", "num_encoder_layers",
        "num_decoder_layers", "label_smoothing", "dropout", "adam_beta1",
        "adam_beta2", "adam_epsilon")}
    out.update(src_vocab_size=assumed["src_vocab_size"],
               trg_vocab_size=assumed["trg_vocab_size"],
               learning_rate=assumed["learning_rate"])
    if out["d_model"] != out["num_heads"] * out["d_head"]:
        raise ValueError("d_model != num_heads * d_head")
    if out["num_encoder_layers"] != out["num_decoder_layers"]:
        raise ValueError("the repo's model file builds equal depths")
    if rehearsal:
        out.update(_REHEARSAL)
    return out


def traffic(spec, rehearsal=False):
    out = dict(spec)
    if rehearsal:
        out.update(_REHEARSAL_TRAFFIC)
    return out


def build(fluid, sz, seed):
    """(main, startup, loss variable): the training program every cell of
    this family runs — `models.transformer_train` with fused attention,
    Adam under `mixed_precision.decorate`, every flag at its default."""
    from paddle_tpu import models
    cfg = models.transformer.TransformerConfig(
        src_vocab_size=sz["src_vocab_size"],
        trg_vocab_size=sz["trg_vocab_size"], d_model=sz["d_model"],
        d_inner=sz["d_ff"], n_head=sz["num_heads"],
        n_layer=sz["num_encoder_layers"], dropout=sz["dropout"],
        label_smooth_eps=sz["label_smoothing"], fuse_attention=True)
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    # dropout is 0.0 and the weights are the benchmark's own, so the
    # programs draw nothing: a fixed seed keeps one compiled step per cell
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup):
        cost, _, _ = models.transformer_train(cfg)
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.AdamOptimizer(
                learning_rate=sz["learning_rate"], beta1=sz["adam_beta1"],
                beta2=sz["adam_beta2"], epsilon=sz["adam_epsilon"]))
        opt.minimize(cost)
    return main, startup, cost


def make_pool(sz, tr, seed):
    """`pool` distinct batches from the seed: every sequence full, random
    ids (so all rows differ), the decoder input the target shifted right.
    Feeds are the model file's: ids int32, key-padding biases f32 (all
    zero: nothing is padded), per-token loss weights f32."""
    rng = np.random.default_rng(int(seed))
    b, s_src, s_trg = tr["batch"], tr["src_len"], tr["trg_len"]
    pool = []
    for _ in range(tr["pool"]):
        src = rng.integers(1, sz["src_vocab_size"], (b, s_src),
                           dtype=np.int32)
        tgt = rng.integers(1, sz["trg_vocab_size"], (b, s_trg + 1),
                           dtype=np.int32)
        pool.append({
            "src_ids": src,
            "trg_ids": np.ascontiguousarray(tgt[:, :-1]),
            "lbl_ids": np.ascontiguousarray(tgt[:, 1:]),
            "src_bias": np.zeros((b, 1, 1, s_src), np.float32),
            "trg_bias": np.zeros((b, 1, 1, s_trg), np.float32),
            "lbl_w": np.ones((b, s_trg), np.float32)})
    return pool


def items(batch):
    return float(batch["lbl_w"].sum())


def param_names(sz):
    return [n for n, _, _, _ in reference.param_specs(sz)]


def param_shapes(sz):
    return {n: tuple(s) for n, s, _, _ in reference.param_specs(sz)}


def init_params(sz, seed):
    return reference.init_params(sz, seed)


# ------------------------------------------------- operations and bytes

def dense_params(sz):
    """Parameters of every dense layer a token passes through: projection
    and feed-forward weights and the logits projection; not the two
    embedding tables, biases or norms."""
    d, dff = sz["d_model"], sz["d_ff"]
    enc = sz["num_encoder_layers"] * (4 * d * d + 2 * d * dff)
    dec = sz["num_decoder_layers"] * (8 * d * d + 2 * d * dff)
    return {"encoder": enc, "decoder": dec,
            "logits": d * sz["trg_vocab_size"]}


def attention_flops_forward(sz, tr):
    """4*B*Sq*Sk*d_model per attention (QK^T and PV), the causal decoder
    self-attention at half."""
    b, d = tr["batch"], sz["d_model"]
    ss, st = tr["src_len"], tr["trg_len"]
    enc = sz["num_encoder_layers"] * 4 * b * ss * ss * d
    dec_self = sz["num_decoder_layers"] * 4 * b * st * st * d // 2
    dec_cross = sz["num_decoder_layers"] * 4 * b * st * ss * d
    return enc + dec_self + dec_cross


def flops_per_step(sz, tr):
    """FLOPs the forward and backward passes need for one step: forward =
    2 * tokens * dense parameters (source tokens through the encoder,
    target tokens through the decoder and the logits) + attention; a step
    is three forwards. Recomputed work is not counted."""
    dp = dense_params(sz)
    b = tr["batch"]
    dense = 2 * (b * tr["src_len"] * dp["encoder"]
                 + b * tr["trg_len"] * (dp["decoder"] + dp["logits"]))
    attn = attention_flops_forward(sz, tr)
    return {"step": 3 * (dense + attn), "attention_step": 3 * attn,
            "dense_step": 3 * dense}


def adam_routed_elements(sz):
    return sum(int(np.prod(shape))
               for _, shape, _, _ in reference.param_specs(sz)
               if int(np.prod(shape)) >= ADAM_KERNEL_MIN_NUMEL)


def adam_routed_bytes_per_step(sz):
    return ADAM_BYTES_PER_ELEMENT * adam_routed_elements(sz)


def expected_routing(sz, tr, rehearsal=False):
    """{kernel: the one decision every site of it must have taken}."""
    flash = tr["src_len"] * tr["trg_len"] >= FLASH_MIN_SEQ_PRODUCT
    if rehearsal:      # kernels route only off the CPU
        return {}
    return {"fused_adam": "custom",
            "flash_attention": "custom" if flash else "lowered"}


# ------------------------------------------------ reading the program

def read_first_gradient_norms(get, names, sz):
    """|g_1| per leaf as the optimizer got it, from Adam's first moment
    after ONE step: m_1 = (1 - beta1) * g_1. `get(name)` returns the
    program's array."""
    import jax
    import jax.numpy as jnp
    scale = 1.0 / (1.0 - sz["adam_beta1"])
    norms = jax.jit(lambda ms: [jnp.sqrt(jnp.sum(jnp.square(m))) * scale
                                for m in ms])(
        [get(n + "_moment1_0") for n in names])
    return {n: float(x) for n, x in zip(names, norms)}


def read_first_gradient_sample(get, names, sz, seed):
    """g_1 at the seed's sampled elements of each leaf, from Adam's first
    moment after ONE step."""
    return reference.gather_samples(
        {n: get(n + "_moment1_0") for n in names},
        reference.sample_indices(sz, seed), 1.0 / (1.0 - sz["adam_beta1"]))


def read_delta_norms(get, names, sz, seed):
    """|p - p_0| per leaf, p_0 made again from the seed."""
    return reference.delta_norms_from_seed(sz, seed,
                                           {n: get(n) for n in names})


def fresh_optimizer_state(sz, names):
    """Adam's accumulators as the startup program leaves them."""
    import jax.numpy as jnp
    shapes = {n: s for n, s, _, _ in reference.param_specs(sz)}
    out = {}
    for n in names:
        out[n + "_moment1_0"] = jnp.zeros(shapes[n], jnp.float32)
        out[n + "_moment2_0"] = jnp.zeros(shapes[n], jnp.float32)
        out[n + "_beta1_pow_acc_0"] = jnp.full((1,), sz["adam_beta1"],
                                               jnp.float32)
        out[n + "_beta2_pow_acc_0"] = jnp.full((1,), sz["adam_beta2"],
                                               jnp.float32)
    return out


def run_reference(sz, tr, pool, seed, steps, precision="f32", rows=None):
    return reference.run(sz, pool, seed, steps=steps, precision=precision,
                         rows=rows,
                         rows_per_block=tr["reference_rows_per_block"])


def classify_kernel(results, operands, text):
    """Which kernel a tpu_custom_call event is. The four pallas_calls carry
    no name yet (they trace as `jit(step)/pallas_call`), so once a name is
    in the text it decides, and until then the signature does: fused_adam
    returns three f32 arrays of one shape from f32 operands; the flash
    kernels take bf16 operands."""
    head = text[:text.find("=")] if "=" in text else text
    for kernel, hints in (("fused_adam", ("adam",)),
                          ("flash_attention", ("flash", "kern"))):
        if any(h in head for h in hints):
            return kernel
    arrays = results + operands
    if any(a.startswith(("bf16", "f16")) for a in operands):
        return "flash_attention"
    if len(results) == 3 and len(set(results)) == 1 \
            and results[0].startswith("f32") \
            and all(a.startswith(("f32", "s32")) for a in arrays):
        return "fused_adam"
    return None
