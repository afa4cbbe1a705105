"""Family file: a decoder-hybrid-decoder language model (SambaY, the
`phi4flash` block) — Mamba-1 mixers and sliding-window attention, one
full-attention layer, gated memory units and cross-attention over the
full layer's keys and values, differential attention, LayerNorm, a dense
SwiGLU in every layer and a tied head — trained: ONE PIPELINE STAGE of a
deployment in which several chips share the tied embedding by
vocabulary, the layers of the stage whole, the vocabulary rows held
here.

What the harness asks of a family is what `conv_gqa_moe_decoder.py`
gives: sizes from a configuration file, the programs built from them
through the framework's own model file (`models.decoder_lm`), batches
from a seed, what an item is, the FLOPs and bytes a step needs (from
shapes and from the program's own counters — tokens the selective scans
went over, (query, key) pairs the windows admitted — never from
`cost_analysis`), the kernels expected to route,
how the first gradient and the parameters are read out of the program's
state, and the plain reference (`sambay_decoder_reference.py`).
"""
from __future__ import annotations

import numpy as np

from . import sambay_decoder_reference as reference
from .gqa_dsa_moe_decoder import _read_counter
# what does not depend on the model: an item, the batches (ids uniform
# over the held vocabulary slice, the label the next token) and the
# constants of the shared kernels
from .mla_moe_decoder import (  # noqa: F401
    ADAM_BYTES_PER_ELEMENT, ADAM_KERNEL_MIN_NUMEL, FLASH_MIN_SEQ_PRODUCT,
    ITEM, KIND, items, make_pool)

# rehearsal only (CPU, explicit argument): the same code path at sizes an
# interpreter can run. Never a configuration file. Its matrices are drawn
# at std 0.11 = 0.9 / sqrt(64): a projection of a normalised input then
# has the scale it has at the published width (0.02 * sqrt(2560) = 1.01).
# The window (12 keys) is narrower than the rehearsal's 40 tokens, so the
# band binds there as it does at 4,096.
_REHEARSAL = dict(initializer_range=0.11, hidden_size=64,
                  num_attention_heads=8, num_key_value_heads=4, head_dim=8,
                  intermediate_size=128, mamba_dt_rank=4, vocab_held=512,
                  sliding_window=12)
_REHEARSAL_TRAFFIC = dict(batch=2, seq_len=40, reference_query_rows=20)

WINDOW_PAIRS_VAR = "window_attn_pairs"        # the program's counters
SCAN_TOKENS_VAR = "selective_scan_tokens"
_COUNTERS = {"_window_attn_pairs": WINDOW_PAIRS_VAR,   # where `sizes`
             "_selective_scan_tokens": SCAN_TOKENS_VAR}  # carries them
BYTES_PER_ELEMENT = 2      # the compute type the configuration states
F32_BYTES = 4

_KIND_OF = {"mamba": "M", "sliding_attention": "S", "full_attention": "F",
            "gmu": "G", "cross_attention": "X"}
_LAYER_TYPE_OF = {v: k for k, v in _KIND_OF.items()}

_PUBLISHED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "intermediate_size", "layer_norm_eps", "sliding_window",
              "tie_word_embeddings", "mb_per_layer")
# the assumed keys the model file takes, then those of the optimizer
_MODEL_ASSUMED = ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                  "mamba_dt_rank", "time_step_min", "time_step_max",
                  "time_step_floor", "initializer_range")
_ASSUMED = _MODEL_ASSUMED + ("learning_rate", "adam_beta1", "adam_beta2",
                             "adam_epsilon")


def sizes(config, rehearsal=False):
    """Flat sizes the family's functions take, from a configuration. The
    file's `vocab_size` is what is HELD here; `layers` is its
    `layer_types`, one character a layer (M Mamba, S window, F full, G
    gated memory unit, X cross); `first_layer` the published index of the
    stage's first layer (`layers_held`)."""
    assumed = config["assumed"]
    out = {k: config[k] for k in _PUBLISHED}
    out.update({k: assumed[k] for k in _ASSUMED})
    held = config["deployment"]["layers_held"]
    out.update(
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        layers="".join(_KIND_OF[t] for t in config["layer_types"]),
        first_layer=held[0], vocab_held=config["vocab_size"])
    if len(out["layers"]) != config["num_hidden_layers"] \
            or held != list(range(held[0], held[0] + len(held))) \
            or len(held) != len(out["layers"]) \
            or not out["tie_word_embeddings"]:
        raise ValueError("the family has one entry of layer_types a layer, "
                         "a contiguous stage of published layers and a "
                         "tied head")
    if rehearsal:
        out.update(_REHEARSAL)
    return out


def traffic(spec, rehearsal=False):
    out = dict(spec)
    if rehearsal:
        out.update(_REHEARSAL_TRAFFIC)
    return out


def model_config(sz):
    """The model file's configuration from the published keys."""
    from paddle_tpu import models
    layer_types = [_LAYER_TYPE_OF[ch] for ch in sz["layers"]]
    return models.DecoderLMConfig(
        vocab_size=sz["vocab_held"], num_hidden_layers=len(layer_types),
        layer_types=layer_types,
        layers_held=list(range(sz["first_layer"],
                               sz["first_layer"] + len(layer_types))),
        head_dim=sz["head_dim"], hidden_act="silu", mlp_bias=False,
        **{k: sz[k] for k in _PUBLISHED + _MODEL_ASSUMED})


def build(fluid, sz, seed):
    """(main, startup, loss variable): `models.decoder_lm_train`, Adam
    under `mixed_precision.decorate`, every flag at its default. A
    program from before the mechanism raises here, at once and by name
    (`NotImplementedError: layer_types ['cross_attention', 'gmu',
    'mamba']: ...`), before anything is built or reaches the device."""
    from paddle_tpu import models
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    # no dropout and the weights are the benchmark's own: the programs
    # draw nothing, a fixed seed keeps one compiled step per cell
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup):
        cost, _, _ = models.decoder_lm_train(model_config(sz))
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.AdamOptimizer(
                learning_rate=sz["learning_rate"], beta1=sz["adam_beta1"],
                beta2=sz["adam_beta2"], epsilon=sz["adam_epsilon"]))
        opt.minimize(cost)
    return main, startup, cost


def param_names(sz):
    return [n for n, _, _, _ in reference.param_specs(sz)]


def param_shapes(sz):
    return {n: tuple(s) for n, s, _, _ in reference.param_specs(sz)}


def init_params(sz, seed):
    return reference.init_params(sz, seed)


def trained_parameters(sz):
    """Elements the optimizer updates (the tied table counted once)."""
    return sum(int(np.prod(shape))
               for _, shape, _, _ in reference.param_specs(sz))


# ------------------------------------------------- operations and bytes

def matmul_params(sz):
    """Parameters of the matrices every token passes through, by part:
    one Mamba mixer's four projections, one gated memory unit's two, one
    self-attention's four, one cross-attention's two (q and o), one
    dense feed-forward, the head (the embedding's matrix a second time).
    Not the table's lookup, the norms, the 4-tap filters or the scan."""
    d, hd = sz["hidden_size"], sz["head_dim"]
    h, hkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    inner, n, rank = reference.mamba_widths(sz)
    return {
        "mamba": d * 2 * inner + inner * (rank + 2 * n) + rank * inner
        + inner * d,
        "gmu": 2 * d * inner,
        "attention": 2 * d * h * hd + 2 * d * hkv * hd,
        "cross": 2 * d * h * hd,
        "mlp": 3 * d * sz["intermediate_size"],
        "head": d * sz["vocab_held"]}


def _counted(sz, key, fallback):
    value = sz.get(key)
    return fallback if value is None else float(np.sum(value))


def scanned_tokens_per_step(sz, tr):
    """Tokens the selective scans went over in one step, all Mamba layers
    together: the program's own count where `sizes` carries it (the
    `selective_scan_tokens` counter, which every step overwrites), else
    batch x sequence a layer."""
    return _counted(sz, "_selective_scan_tokens",
                    reference.count(sz, "mamba") * tr["batch"]
                    * tr["seq_len"])


def window_pairs_per_step(sz, tr):
    """(Query, key) pairs the window layers admitted in one step: the
    program's own count (`window_attn_pairs`), else the band's pairs by
    hand: row r keeps min(r + 1, window) keys."""
    s, w = tr["seq_len"], sz["sliding_window"]
    return _counted(sz, "_window_attn_pairs",
                    reference.count(sz, "window") * tr["batch"]
                    * sum(min(r + 1, w) for r in range(s)))


def causal_pairs_per_step(sz, tr, kind):
    """(Query, key) pairs the causal layers of `kind` ("full", or "cross"
    over the full layer's keys) admit in one step: S (S + 1) / 2 a
    sequence a layer."""
    s = tr["seq_len"]
    return float(reference.count(sz, kind) * tr["batch"]
                 * s * (s + 1) // 2)


def attention_flops_forward(sz, pairs):
    """2 * H * pairs * (d + 2 d): every one of the H query heads (two
    maps a differential head) makes q k^T over d channels and p v over
    the 2 d of its value head."""
    return 2.0 * sz["num_attention_heads"] * pairs * 3 * sz["head_dim"]


def attention_pairs_per_step(sz, tr):
    return window_pairs_per_step(sz, tr) \
        + causal_pairs_per_step(sz, tr, "full") \
        + causal_pairs_per_step(sz, tr, "cross")


def flops_per_step(sz, tr):
    """FLOPs the forward and backward passes need for one step: forward =
    2 * tokens * the matrices every token passes + attention over the
    pairs admitted (the window layers' as counted, the full and cross
    layers' causal); a step is three forwards. Recomputed work is not
    counted, nor the scan's elementwise work (no matrix product)."""
    mp = matmul_params(sz)
    tokens = tr["batch"] * tr["seq_len"]
    dense = 2.0 * tokens * (
        sum(reference.count(sz, k) * mp[m] for k, m in (
            ("mamba", "mamba"), ("gmu", "gmu"), ("window", "attention"),
            ("full", "attention"), ("cross", "cross"), ("mlp", "mlp")))
        + mp["head"])
    attn = attention_flops_forward(sz, attention_pairs_per_step(sz, tr))
    return {"step": 3 * (dense + attn), "attention_step": 3 * attn,
            "dense_step": 3 * dense}


def selective_scan_bytes_per_token(sz):
    """HBM bytes a selective scan cannot avoid a token, forward and
    backward: forward reads x (compute type), dt (float32), B and C
    (compute type) and writes y; backward reads those and dy and writes
    dx, d dt (float32), dB and dC."""
    inner, n, _ = reference.mamba_widths(sz)
    c, f = BYTES_PER_ELEMENT, F32_BYTES
    forward = inner * (c + f + c) + 2 * n * c
    backward = inner * (c + f + c + c) + 2 * n * c \
        + inner * (c + f) + 2 * n * c
    return forward + backward


def selective_scan_roofline_seconds_per_step(sz, tr, peaks):
    """The least time the chip could take for the selective scans of one
    step: their HBM bytes at the tokens the program counted over the
    bandwidth (the recurrence has no matrix product)."""
    return scanned_tokens_per_step(sz, tr) \
        * selective_scan_bytes_per_token(sz) / peaks["bytes_per_s"]


def adam_routed_elements(sz):
    return sum(int(np.prod(shape))
               for _, shape, _, _ in reference.param_specs(sz)
               if int(np.prod(shape)) >= ADAM_KERNEL_MIN_NUMEL)


def adam_routed_bytes_per_step(sz):
    return ADAM_BYTES_PER_ELEMENT * adam_routed_elements(sz)


def expected_routing(sz, tr, rehearsal=False):
    """{kernel: the one decision every site of it must have taken}: a run
    in which attention fell to the composed path or the scan to its
    `jax.numpy` recurrence is not `correct`."""
    if rehearsal:      # kernels route only off the CPU
        return {}
    s = tr["seq_len"]
    return {"fused_adam": "custom",
            "flash_attention": "custom"
            if s * s >= FLASH_MIN_SEQ_PRODUCT else "lowered",
            "selective_scan": "custom"}


# ------------------------------------------------ reading the program

def _moments(get, names):
    return {n: get(n + "_moment1_0") for n in names}


def read_first_gradient_norms(get, names, sz):
    """|g_1| per leaf as the optimizer got it, from Adam's first moment
    after ONE step: m_1 = (1 - beta1) * g_1."""
    import jax
    import jax.numpy as jnp
    scale = 1.0 / (1.0 - sz["adam_beta1"])
    ms = _moments(get, names)
    norms = jax.jit(lambda ms: {n: jnp.sqrt(jnp.sum(jnp.square(m))) * scale
                                for n, m in ms.items()})(ms)
    return {n: float(x) for n, x in norms.items()}


def read_first_gradient_sample(get, names, sz, seed):
    return reference.gather_samples(
        _moments(get, names), reference.sample_indices(sz, seed),
        1.0 / (1.0 - sz["adam_beta1"]))


def read_delta_norms(get, names, sz, seed):
    """|p - p_0| per leaf, p_0 made again from the seed. The harness
    calls this after the proof steps, so the program's two counters
    are read here too (through `get`, no fetch) and carried in `sizes`
    to the functions that count operations and bytes."""
    for key, var in _COUNTERS.items():
        value = _read_counter(get, var)
        if value is None:
            sz.pop(key, None)
        else:
            sz[key] = value
    return reference.delta_norms_from_seed(
        sz, seed, {n: get(n) for n in names})


def scanned_tokens(sz):
    """The last proof step's count, int64 [Mamba layers], or None."""
    return sz.get("_selective_scan_tokens")


def window_pairs(sz):
    """The last proof step's count, int64 [window layers], or None."""
    return sz.get("_window_attn_pairs")


def fresh_optimizer_state(sz, names):
    """Adam's accumulators and the two counters as the startup program
    leaves them."""
    import jax.numpy as jnp
    shapes = param_shapes(sz)
    out = {var: jnp.zeros((reference.count(sz, kind),), jnp.int32)
           for var, kind in ((WINDOW_PAIRS_VAR, "window"),
                             (SCAN_TOKENS_VAR, "mamba"))}
    for n in names:
        out[n + "_moment1_0"] = jnp.zeros(shapes[n], jnp.float32)
        out[n + "_moment2_0"] = jnp.zeros(shapes[n], jnp.float32)
        out[n + "_beta1_pow_acc_0"] = jnp.full((1,), sz["adam_beta1"],
                                               jnp.float32)
        out[n + "_beta2_pow_acc_0"] = jnp.full((1,), sz["adam_beta2"],
                                               jnp.float32)
    return out


def run_reference(sz, tr, pool, seed, steps, precision="f32", rows=None,
                  fault=None):
    return reference.run(sz, pool, seed, steps=steps, precision=precision,
                         rows=rows, fault=fault,
                         rows_per_block=tr["reference_rows_per_block"],
                         query_rows=tr["reference_query_rows"])


def classify_kernel(results, operands, text):
    """Which kernel a tpu_custom_call event is, by the name the program
    gives each `pallas_call` (the instruction's own name). The window
    layers' flash kernels (`flash_attention_window_*`) are booked apart
    from the full and cross layers'."""
    head = text[:text.find("=")] if "=" in text else text
    for kernel, hint in (("fused_adam", "adam"),
                         ("flash_attention_window", "flash_attention_window"),
                         ("flash_attention", "flash_attention"),
                         ("selective_scan", "selective_scan")):
        if hint in head:
            return kernel
    return None
