"""Family file: a decoder-only language model whose layers mix tokens by a
gated short convolution or by grouped-query attention (q / k norm,
half-split rotary), with a leading dense SwiGLU layer and then
sigmoid-routed experts with no shared expert (the `lfm2_moe` block), and
a head that is the embedding's own matrix — trained: ONE CHIP'S SHARE of
a deployment in which several chips share each layer: the routed experts
held here, the vocabulary rows held here, everything else (convolution
operators, attention, router, norms) as every chip has it.

What the harness asks of a family is what `mamba_gqa_moe_decoder.py`
gives: sizes from a configuration file, the programs built from them
through the framework's own model file (`models.decoder_lm`), batches
from a seed, what an item is, the FLOPs and bytes a step needs (from
shapes and from the program's own counters — rows routed to held
experts, tokens the convolutions went over — never from
`cost_analysis`), the kernels expected to route, how the first gradient
and the parameters are read out of the program's state, and the plain
reference (`conv_gqa_moe_decoder_reference.py`).
"""
from __future__ import annotations

import numpy as np

from . import conv_gqa_moe_decoder_reference as reference
from .gqa_dsa_moe_decoder import _read_counter
# what does not depend on the model: an item, the batches (ids uniform
# over the held vocabulary slice, the label the next token) and the
# constants of the shared kernels and of the shared counter
from .mla_moe_decoder import (  # noqa: F401
    ADAM_BYTES_PER_ELEMENT, ADAM_KERNEL_MIN_NUMEL, EXPERT_LOAD_VAR,
    FLASH_MIN_SEQ_PRODUCT, ITEM, KIND, PROOF_STEPS, _LOAD_KEY, items,
    make_pool)

# rehearsal only (CPU, explicit argument): the same code path at sizes an
# interpreter can run. Never a configuration file. Its matrices are drawn
# at std 0.11 = 0.9 / sqrt(64): a projection of a normalised input then
# has the scale it has at the published width (0.02 * sqrt(2048) = 0.9).
_REHEARSAL = dict(initializer_range=0.11, hidden_size=64,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  intermediate_size=160, moe_intermediate_size=32,
                  router_experts=16, experts_held=4, vocab_held=512)
_REHEARSAL_TRAFFIC = dict(batch=2, seq_len=40, reference_query_rows=20)

SHORT_CONV_TOKENS_VAR = "short_conv_tokens"   # the program's second counter
_CONVOLVED_KEY = "_short_conv_tokens"   # where `sizes` carries its reading
BYTES_PER_ELEMENT = 2      # the compute type the configuration states

_MIXER_OF = {"conv": "C", "full_attention": "A"}
_LAYER_TYPE_OF = {v: k for k, v in _MIXER_OF.items()}

_PUBLISHED = (
    "hidden_size", "num_attention_heads", "num_key_value_heads",
    "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
    "norm_topk_prob", "routed_scaling_factor", "conv_L_cache", "conv_bias",
    "use_expert_bias", "num_dense_layers")
_ASSUMED = ("tie_word_embeddings", "router_norm_epsilon", "head_dim",
            "initializer_range", "learning_rate", "adam_beta1", "adam_beta2",
            "adam_epsilon")


def sizes(config, rehearsal=False):
    """Flat sizes the family's functions take, from a configuration. The
    file's `num_experts` and `vocab_size` are what is HELD here; the
    router's width is the published count beside them. `layers` is the
    file's `layer_types`, one character a layer (C conv, A attention)."""
    assumed, cut = config["assumed"], config["reduced"]
    out = {k: config[k] for k in _PUBLISHED}
    out.update({k: assumed[k] for k in _ASSUMED})
    rope = config["rope_parameters"]
    out.update(
        layers="".join(_MIXER_OF[t] for t in config["layer_types"]),
        rms_norm_eps=config["norm_eps"], rope_theta=rope["rope_theta"],
        router_experts=cut["num_experts"]["published"],
        experts_held=config["num_experts"],
        first_expert=config["deployment"]["first_expert"],
        vocab_held=config["vocab_size"])
    if len(out["layers"]) != config["num_hidden_layers"] \
            or rope["rope_type"] != "default":
        raise ValueError("the family has one entry of layer_types a layer "
                         "and plain rotary")
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
    return models.DecoderLMConfig(
        vocab_size=sz["vocab_held"], num_experts=sz["router_experts"],
        experts_held=sz["experts_held"], first_expert=sz["first_expert"],
        layer_types=[_LAYER_TYPE_OF[ch] for ch in sz["layers"]],
        norm_eps=sz["rms_norm_eps"],
        rope_parameters={"rope_theta": sz["rope_theta"],
                         "rope_type": "default"},
        **{k: sz[k] for k in _PUBLISHED + (
            "tie_word_embeddings", "router_norm_epsilon", "head_dim",
            "initializer_range")})


def build(fluid, sz, seed):
    """(main, startup, loss variable): `models.decoder_lm_train`, Adam
    under `mixed_precision.decorate`, every flag at its default. A
    program from before the mechanism raises here, at once and by name
    (`NotImplementedError: layer_types: ...`), before anything is built
    or reaches the device."""
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
    """Every parameter the seed sets, the routers' buffers among them."""
    return [n for n, _, _, _ in reference.param_specs(sz)]


def param_shapes(sz):
    return {n: tuple(s) for n, s, _, _ in reference.param_specs(sz)}


def init_params(sz, seed):
    return reference.init_params(sz, seed)


def _trained(names):
    return [n for n in names if not reference.is_buffer(n)]


def trained_parameters(sz):
    """Elements the optimizer updates (the tied table counted once)."""
    return sum(int(np.prod(shape))
               for name, shape, _, _ in reference.param_specs(sz)
               if not reference.is_buffer(name))


# ------------------------------------------------- operations and bytes

def matmul_params(sz):
    """Parameters of the matrices every token passes through, by part:
    one convolution operator's two projections, one attention's four,
    the dense feed-forward, an expert layer's router, ONE routed expert
    (three matrices), the head (the embedding's matrix a second time).
    Not the table's lookup, the norms or the 3-tap filters (6,144
    multiplies a token a layer against 16.8 M)."""
    d, h, hkv, hd = sz["hidden_size"], sz["num_attention_heads"], \
        sz["num_key_value_heads"], sz["head_dim"]
    return {
        "conv": d * 3 * d + d * d,
        "attention": d * h * hd + 2 * d * hkv * hd + h * hd * d,
        "dense_ffn": 3 * d * sz["intermediate_size"],
        "router": sz["router_experts"] * d,
        "routed_expert": 3 * d * sz["moe_intermediate_size"],
        "head": d * sz["vocab_held"]}


def routed_rows_per_step(sz, tr):
    """Rows the routed experts held here take in one step, all expert
    layers together: the program's own count where `sizes` carries it
    (the `moe_expert_load` counter after the proof steps), else what
    uniform routing gives."""
    load = sz.get(_LOAD_KEY)
    if load is not None:
        return float(np.sum(load)) / PROOF_STEPS
    tokens = tr["batch"] * tr["seq_len"]
    return (reference.count(sz, "experts") * tokens
            * sz["num_experts_per_tok"] * sz["experts_held"]
            / sz["router_experts"])


def convolved_tokens_per_step(sz, tr):
    """Tokens the convolution operators went over in one step, all conv
    layers together: the program's own count where `sizes` carries it
    (the `short_conv_tokens` counter, which every step overwrites), else
    batch x sequence a layer."""
    convolved = sz.get(_CONVOLVED_KEY)
    if convolved is not None:
        return float(np.sum(convolved))
    return float(reference.count(sz, "conv") * tr["batch"] * tr["seq_len"])


def short_conv_bytes_forward_per_token(sz):
    """HBM bytes the operator cannot avoid a token a layer, forward: the
    in-projection's output X [3 D] read, the gated convolution [D]
    written, in the compute type."""
    return BYTES_PER_ELEMENT * 4 * sz["hidden_size"]


def short_conv_bytes_backward_per_token(sz):
    """X [3 D] and d out [D] read, dX [3 D] written (the filter's
    gradient is 3 D floats a layer, not a token)."""
    return BYTES_PER_ELEMENT * 7 * sz["hidden_size"]


def short_conv_roofline_seconds_per_step(sz, tr, peaks):
    """The least time the chip could take for the gated convolutions of
    one step: their HBM bytes, forward and backward, at the tokens the
    program counted, over the bandwidth (nothing in them is a matrix
    product: a dozen multiplies a channel against 22 bytes)."""
    return convolved_tokens_per_step(sz, tr) * (
        short_conv_bytes_forward_per_token(sz)
        + short_conv_bytes_backward_per_token(sz)) / peaks["bytes_per_s"]


def attention_flops_forward(sz, tr):
    """2 * B * H * Sq * Sk * (d + d) an attention layer, causal: half."""
    b, s = tr["batch"], tr["seq_len"]
    return reference.count(sz, "attention") * (
        2 * b * sz["num_attention_heads"] * s * s * 2 * sz["head_dim"] // 2)


def routed_flops_forward(sz, tr):
    """2 * rows * hidden * expert width * 3 matrices, at the rows the
    program counted."""
    return 2.0 * routed_rows_per_step(sz, tr) \
        * matmul_params(sz)["routed_expert"]


def flops_per_step(sz, tr):
    """FLOPs the forward and backward passes need for one step: forward =
    2 * tokens * the matrices every token passes + the routed experts at
    the rows counted + attention; a step is three forwards. Recomputed
    work is not counted."""
    mp = matmul_params(sz)
    tokens = tr["batch"] * tr["seq_len"]
    conv = 2 * tokens * reference.count(sz, "conv") * mp["conv"]
    dense = conv + 2 * tokens * (
        reference.count(sz, "attention") * mp["attention"]
        + reference.count(sz, "mlp") * mp["dense_ffn"]
        + reference.count(sz, "experts") * mp["router"] + mp["head"])
    routed = routed_flops_forward(sz, tr)
    attn = attention_flops_forward(sz, tr)
    return {"step": 3 * (dense + routed + attn),
            "attention_step": 3 * attn, "dense_step": 3 * dense,
            "routed_step": 3 * routed, "conv_projections_step": 3 * conv}


def adam_routed_elements(sz):
    return sum(int(np.prod(shape))
               for name, shape, _, _ in reference.param_specs(sz)
               if not reference.is_buffer(name)
               and int(np.prod(shape)) >= ADAM_KERNEL_MIN_NUMEL)


def adam_routed_bytes_per_step(sz):
    return ADAM_BYTES_PER_ELEMENT * adam_routed_elements(sz)


def expected_routing(sz, tr, rehearsal=False):
    """{kernel: the one decision every site of it must have taken}: a run
    in which the convolution fell to its `jax.numpy` lowering, attention
    to the composed path or the experts to the ragged dots is not
    `correct`."""
    if rehearsal:      # kernels route only off the CPU
        return {}
    s = tr["seq_len"]
    return {"fused_adam": "custom",
            "flash_attention": "custom"
            if s * s >= FLASH_MIN_SEQ_PRODUCT else "lowered",
            "moe_grouped_matmul": "custom",
            "gated_short_conv": "custom"}


# ------------------------------------------------ reading the program

def _moments(get, names):
    return {n: get(n + "_moment1_0") for n in _trained(names)}


def read_first_gradient_norms(get, names, sz):
    """|g_1| per trained leaf as the optimizer got it, from Adam's first
    moment after ONE step: m_1 = (1 - beta1) * g_1."""
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
    """|p - p_0| per trained leaf, p_0 made again from the seed. The
    harness calls this after the proof steps, so the program's two
    counters are read here too (through `get`, no fetch) and carried in
    `sizes` to the functions that count operations and bytes."""
    for key, var in ((_LOAD_KEY, EXPERT_LOAD_VAR),
                     (_CONVOLVED_KEY, SHORT_CONV_TOKENS_VAR)):
        value = _read_counter(get, var)
        if value is None:
            sz.pop(key, None)
        else:
            sz[key] = value
    return reference.delta_norms_from_seed(
        sz, seed, {n: get(n) for n in _trained(names)})


def expert_load(sz):
    """The counter as read after the proof steps, int64 [expert layers,
    experts held], or None."""
    return sz.get(_LOAD_KEY)


def convolved_tokens(sz):
    """The last proof step's count, int64 [conv layers], or None."""
    return sz.get(_CONVOLVED_KEY)


def fresh_optimizer_state(sz, names):
    """Adam's accumulators and the two counters as the startup program
    leaves them."""
    import jax.numpy as jnp
    shapes = param_shapes(sz)
    out = {EXPERT_LOAD_VAR: jnp.zeros(
        (reference.count(sz, "experts"), sz["experts_held"]), jnp.int32),
        SHORT_CONV_TOKENS_VAR: jnp.zeros((reference.count(sz, "conv"),),
                                         jnp.int32)}
    for n in _trained(names):
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
    gives each `pallas_call` (the instruction's own name)."""
    head = text[:text.find("=")] if "=" in text else text
    for kernel, hint in (("fused_adam", "adam"),
                         ("flash_attention", "flash_attention"),
                         ("moe_grouped_matmul", "moe_grouped_matmul"),
                         ("moe_combine", "moe_combine"),
                         ("gated_short_conv", "gated_short_conv")):
        if hint in head:
            return kernel
    return None
