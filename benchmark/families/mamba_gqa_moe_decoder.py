"""Family file: a hybrid decoder-only language model whose every layer is
ONE part — a Mamba-2 state-space mixer, a grouped-query attention
without positions, or an expert layer of sigmoid-routed ungated
squared-ReLU experts with a shared expert (the `nemotron_h` block) —
trained: ONE CHIP'S SHARE of a deployment in which several chips share
each layer: the routed experts held here, the vocabulary rows held here,
everything else (mixers, attention, router, shared expert, norms) as
every chip has it.

What the harness asks of a family is what `mla_moe_decoder.py` gives:
sizes from a configuration file, the programs built from them through the
framework's own model file (`models.decoder_lm`), batches from a seed,
what an item is, the FLOPs and bytes a step needs (from shapes and from
the program's own counters — rows routed to held experts, tokens the
mixers scanned — never from `cost_analysis`), the kernels expected to
route, how the first gradient and the parameters are read out of the
program's state, and the plain reference
(`mamba_gqa_moe_decoder_reference.py`).
"""
from __future__ import annotations

import numpy as np

from . import mamba_gqa_moe_decoder_reference as reference
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
# at std 0.13 = 1.04 / sqrt(64): a projection of a normalised input then
# has the scale it has at the published width (0.02 * sqrt(2688) = 1.04);
# at 0.02 the gated norm's epsilon would outweigh what it normalises.
_REHEARSAL = dict(initializer_range=0.13, hidden_size=64, pattern="ME*E", num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
                  mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                  chunk_size=16, moe_intermediate_size=32,
                  moe_shared_expert_intermediate_size=48,
                  router_experts=16, experts_held=4, vocab_held=512)
_REHEARSAL_TRAFFIC = dict(batch=2, seq_len=40, reference_query_rows=20)

SSD_TOKENS_VAR = "mamba_ssd_tokens"    # the program's second counter
_SCANNED_KEY = "_mamba_ssd_tokens"     # where `sizes` carries its reading

_PUBLISHED = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "use_conv_bias", "mamba_hidden_act",
    "mamba_proj_bias", "mlp_hidden_act", "mlp_bias", "attention_bias",
    "use_bias", "layer_norm_epsilon", "intermediate_size",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
    "norm_topk_prob", "n_group", "topk_group", "time_step_min",
    "time_step_max", "time_step_floor", "tie_word_embeddings")


def sizes(config, rehearsal=False):
    """Flat sizes the family's functions take, from a configuration. The
    file's `n_routed_experts` and `vocab_size` are what is HELD here; the
    router's width is the published count beside them. `pattern` is the
    file's `hybrid_override_pattern`, one character a layer."""
    assumed, cut = config["assumed"], config["reduced"]
    out = {k: config[k] for k in _PUBLISHED}
    out.update(
        pattern=config["hybrid_override_pattern"],
        router_experts=cut["n_routed_experts"]["published"],
        experts_held=config["n_routed_experts"],
        first_expert=config["deployment"]["first_expert"],
        vocab_held=config["vocab_size"],
        initializer_range=assumed["initializer_range"],
        learning_rate=assumed["learning_rate"],
        adam_beta1=assumed["adam_beta1"], adam_beta2=assumed["adam_beta2"],
        adam_epsilon=assumed["adam_epsilon"])
    if len(out["pattern"]) != config["num_hidden_layers"] \
            or config["norm_eps"] != config["layer_norm_epsilon"] \
            or tuple(config["time_step_limit"]) != (0, None) \
            or config["sliding_window"] is not None:
        raise ValueError("the family has one part a layer of the pattern, "
                         "one epsilon, no clamp on the step sizes and no "
                         "window")
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
        vocab_size=sz["vocab_held"], n_routed_experts=sz["router_experts"],
        experts_held=sz["experts_held"], first_expert=sz["first_expert"],
        hybrid_override_pattern=sz["pattern"],
        **{k: sz[k] for k in _PUBLISHED + ("initializer_range",)})


def build(fluid, sz, seed):
    """(main, startup, loss variable): `models.decoder_lm_train`, Adam
    under `mixed_precision.decorate`, every flag at its default."""
    from paddle_tpu import layers, models
    if not hasattr(layers, "mamba2_ssd"):
        # a program from before the mechanism: say so at once, before
        # anything is built or reaches the device
        raise SystemExit("this checkout's paddle_tpu has no `mamba2_ssd` "
                         "layer: it cannot build the mamba_gqa_moe_decoder "
                         "family. No result.")
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


# ------------------------------------------------- operations and bytes

def matmul_params(sz):
    """Parameters of the matrices every token passes through, by part:
    one mixer's projections and convolution, one attention's
    projections, an expert layer's router and shared expert, ONE routed
    expert (two matrices: it is ungated), the head. Not the embedding
    table, the norms or the mixers' per-head scalars."""
    d, h, hkv, hd = sz["hidden_size"], sz["num_attention_heads"], \
        sz["num_key_value_heads"], sz["head_dim"]
    inner, conv = reference.mixer_widths(sz)
    return {
        "mixer": d * (inner + conv + sz["mamba_num_heads"]) + inner * d
        + conv * sz["conv_kernel"],
        "attention": d * h * hd + 2 * d * hkv * hd + h * hd * d,
        "router": sz["router_experts"] * d,
        "shared": 2 * d * sz["n_shared_experts"]
        * sz["moe_shared_expert_intermediate_size"],
        "routed_expert": 2 * d * sz["moe_intermediate_size"],
        "dense_ffn": 2 * d * sz["intermediate_size"],
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


def scanned_tokens_per_step(sz, tr):
    """Tokens the mixers' scans went over in one step, all mixers
    together: the program's own count where `sizes` carries it (the
    `mamba_ssd_tokens` counter, which every step overwrites), else
    batch x sequence a mixer."""
    scanned = sz.get(_SCANNED_KEY)
    if scanned is not None:
        return float(np.sum(scanned))
    return float(reference.count(sz, "mixer") * tr["batch"] * tr["seq_len"])


def ssd_flops_forward_per_token(sz):
    """The chunked scan's matrix products a token a mixer, forward:
    2 H (Q P + 2 N P) + 2 G Q N — within the chunk (C B^T . decay) x over
    the chunk's Q tokens, the state read (C S) and written (x^T B), and
    C B^T once a group."""
    h, p = sz["mamba_num_heads"], sz["mamba_head_dim"]
    g, n, q = sz["n_groups"], sz["ssm_state_size"], sz["chunk_size"]
    return 2 * h * (q * p + 2 * n * p) + 2 * g * q * n


def ssd_flops_backward_per_token(sz):
    """Two products for each of the forward's (the gradient of either
    operand); what a backward recomputes of the forward is not counted."""
    return 2 * ssd_flops_forward_per_token(sz)


def ssd_bytes_forward_per_token(sz):
    """HBM bytes the scan cannot avoid a token a mixer, forward: x, B, C
    (2 bytes an element) and dt (float32) read, y written."""
    h, p = sz["mamba_num_heads"], sz["mamba_head_dim"]
    g, n = sz["n_groups"], sz["ssm_state_size"]
    return 2 * (2 * h * p + 2 * g * n) + 4 * h


def ssd_bytes_backward_per_token(sz):
    """x, B, C, dt and dy read; dx, dB, dC and d dt written."""
    h, p = sz["mamba_num_heads"], sz["mamba_head_dim"]
    g, n = sz["n_groups"], sz["ssm_state_size"]
    return 2 * (3 * h * p + 4 * g * n) + 8 * h


def ssd_roofline_seconds_per_step(sz, tr, peaks):
    """The least time the chip could take for the scans of one step: for
    the forward and for the backward the larger of FLOPs over peak and
    HBM bytes over bandwidth, at the tokens the program counted."""
    tokens = scanned_tokens_per_step(sz, tr)
    return tokens * sum(
        max(flops / peaks["flops_per_s"], moved / peaks["bytes_per_s"])
        for flops, moved in (
            (ssd_flops_forward_per_token(sz),
             ssd_bytes_forward_per_token(sz)),
            (ssd_flops_backward_per_token(sz),
             ssd_bytes_backward_per_token(sz))))


def attention_flops_forward(sz, tr):
    """2 * B * H * Sq * Sk * (d + d) an attention layer, causal: half."""
    b, s = tr["batch"], tr["seq_len"]
    return reference.count(sz, "attention") * (
        2 * b * sz["num_attention_heads"] * s * s * 2 * sz["head_dim"] // 2)


def routed_flops_forward(sz, tr):
    """2 * rows * hidden * expert width * 2 matrices, at the rows the
    program counted."""
    return 2.0 * routed_rows_per_step(sz, tr) \
        * matmul_params(sz)["routed_expert"]


def flops_per_step(sz, tr):
    """FLOPs the forward and backward passes need for one step: forward =
    2 * tokens * the matrices every token passes + the routed experts at
    the rows counted + attention + the scans at the tokens counted; a
    step is three forwards. Recomputed work is not counted."""
    mp = matmul_params(sz)
    tokens = tr["batch"] * tr["seq_len"]
    dense = 2 * tokens * (
        reference.count(sz, "mixer") * mp["mixer"]
        + reference.count(sz, "attention") * mp["attention"]
        + reference.count(sz, "experts") * (mp["router"] + mp["shared"])
        + reference.count(sz, "mlp") * mp["dense_ffn"] + mp["head"])
    routed = routed_flops_forward(sz, tr)
    attn = attention_flops_forward(sz, tr)
    scan = scanned_tokens_per_step(sz, tr) * ssd_flops_forward_per_token(sz)
    return {"step": 3 * (dense + routed + attn + scan),
            "attention_step": 3 * attn, "dense_step": 3 * dense,
            "routed_step": 3 * routed, "scan_step": 3 * scan}


def adam_routed_elements(sz):
    return sum(int(np.prod(shape))
               for name, shape, _, _ in reference.param_specs(sz)
               if not reference.is_buffer(name)
               and int(np.prod(shape)) >= ADAM_KERNEL_MIN_NUMEL)


def adam_routed_bytes_per_step(sz):
    return ADAM_BYTES_PER_ELEMENT * adam_routed_elements(sz)


def expected_routing(sz, tr, rehearsal=False):
    """{kernel: the one decision every site of it must have taken}: a run
    in which the scan fell to its `jax.numpy` lowering, attention to the
    composed path or the experts to the ragged dots is not `correct`."""
    if rehearsal:      # kernels route only off the CPU
        return {}
    s = tr["seq_len"]
    return {"fused_adam": "custom",
            "flash_attention": "custom"
            if s * s >= FLASH_MIN_SEQ_PRODUCT else "lowered",
            "moe_grouped_matmul": "custom",
            "mamba2_ssd": "custom"}


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
    `sizes` to the functions that count operations."""
    for key, var in ((_LOAD_KEY, EXPERT_LOAD_VAR),
                     (_SCANNED_KEY, SSD_TOKENS_VAR)):
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


def scanned_tokens(sz):
    """The last proof step's count, int64 [mixer layers], or None."""
    return sz.get(_SCANNED_KEY)


def fresh_optimizer_state(sz, names):
    """Adam's accumulators and the two counters as the startup program
    leaves them."""
    import jax.numpy as jnp
    shapes = param_shapes(sz)
    out = {EXPERT_LOAD_VAR: jnp.zeros(
        (reference.count(sz, "experts"), sz["experts_held"]), jnp.int32),
        SSD_TOKENS_VAR: jnp.zeros((reference.count(sz, "mixer"),),
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
                         ("mamba2_ssd", "mamba2_ssd")):
        if hint in head:
            return kernel
    return None
