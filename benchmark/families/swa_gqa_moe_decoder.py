"""Family file: a decoder-only language model whose attention layers are
grouped-query attention over a sliding window (plain rotary) or over the
whole causal prefix (YaRN rotary), with softmax-routed experts and no
shared expert in every layer and an untied head (the `mellum` block) —
trained: ONE CHIP'S SHARE of a deployment in which several chips share
each layer: the routed experts held here, the vocabulary rows held here,
everything else (attention, router, norms) as every chip has it.

What the harness asks of a family is what `conv_gqa_moe_decoder.py`
gives: sizes from a configuration file, the programs built from them
through the framework's own model file (`models.decoder_lm`), batches
from a seed, what an item is, the FLOPs and bytes a step needs (from
shapes and from the program's own counters — rows routed to held
experts, (query, key) pairs the windows admitted — never from
`cost_analysis`), the kernels expected to route, how the first gradient
and the parameters are read out of the program's state, and the plain
reference (`swa_gqa_moe_decoder_reference.py`).
"""
from __future__ import annotations

import numpy as np

from . import swa_gqa_moe_decoder_reference as reference
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
# has the scale it has at the published width (0.02 * sqrt(2304) = 0.96).
# The window (12 keys) is narrower than the rehearsal's 40 tokens, so the
# band binds there as it does at 8,192.
_REHEARSAL = dict(initializer_range=0.11, hidden_size=64,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  moe_intermediate_size=32, router_experts=16,
                  experts_held=4, vocab_held=512, sliding_window=12)
_REHEARSAL_TRAFFIC = dict(batch=2, seq_len=40, reference_query_rows=20)

WINDOW_PAIRS_VAR = "window_attn_pairs"  # the program's second counter
_PAIRS_KEY = "_window_attn_pairs"       # where `sizes` carries its reading
BYTES_PER_ELEMENT = 2      # the compute type the configuration states

_KIND_OF = {"sliding_attention": "S", "full_attention": "F"}
_LAYER_TYPE_OF = {v: k for k, v in _KIND_OF.items()}

_PUBLISHED = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
    "rms_norm_eps", "sliding_window", "tie_word_embeddings")
_ASSUMED = ("use_qk_norm", "initializer_range", "learning_rate",
            "adam_beta1", "adam_beta2", "adam_epsilon")


def sizes(config, rehearsal=False):
    """Flat sizes the family's functions take, from a configuration. The
    file's `num_experts` and `vocab_size` are what is HELD here; the
    router's width is the published count beside them. `layers` is the
    file's `layer_types`, one character a layer (S window, F full);
    `rope_theta_window` / `rope_theta_full` and `yarn` the two rotaries
    of `rope_parameters`."""
    assumed, cut = config["assumed"], config["reduced"]
    out = {k: config[k] for k in _PUBLISHED}
    out.update({k: assumed[k] for k in _ASSUMED})
    rope = config["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    out.update(
        layers="".join(_KIND_OF[t] for t in config["layer_types"]),
        rope_theta_window=float(window["rope_theta"]),
        rope_theta_full=float(full["rope_theta"]),
        yarn={k: float(full[k]) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor")},
        router_experts=cut["num_experts"]["published"],
        experts_held=config["num_experts"],
        first_expert=config["deployment"]["first_expert"],
        vocab_held=config["vocab_size"])
    if len(out["layers"]) != config["num_hidden_layers"] \
            or set(config["mlp_layer_types"]) != {"sparse"} \
            or window["rope_type"] != "default" \
            or full["rope_type"] != "yarn" or out["tie_word_embeddings"] \
            or out["use_qk_norm"]:
        raise ValueError("the family has one entry of layer_types a layer, "
                         "experts in every layer, plain rotary on the "
                         "window layers and YaRN on the full ones, an "
                         "untied head and no q / k norm")
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
    yarn = dict(sz["yarn"], rope_type="yarn", rope_theta=sz["rope_theta_full"])
    layer_types = [_LAYER_TYPE_OF[ch] for ch in sz["layers"]]
    return models.DecoderLMConfig(
        vocab_size=sz["vocab_held"], num_experts=sz["router_experts"],
        experts_held=sz["experts_held"], first_expert=sz["first_expert"],
        num_hidden_layers=len(layer_types), layer_types=layer_types,
        mlp_layer_types=["sparse"] * len(layer_types),
        rope_parameters={
            "full_attention": yarn,
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": sz["rope_theta_window"]}},
        hidden_act="silu", attention_bias=False,
        **{k: sz[k] for k in _PUBLISHED + ("use_qk_norm",
                                           "initializer_range")})


def build(fluid, sz, seed):
    """(main, startup, loss variable): `models.decoder_lm_train`, Adam
    under `mixed_precision.decorate`, every flag at its default. A
    program from before the mechanism raises here, at once and by name
    (`NotImplementedError: sliding_window: ...`), before anything is
    built or reaches the device."""
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
    """Elements the optimizer updates."""
    return sum(int(np.prod(shape))
               for _, shape, _, _ in reference.param_specs(sz))


# ------------------------------------------------- operations and bytes

def matmul_params(sz):
    """Parameters of the matrices every token passes through, by part:
    one attention's four projections, an expert layer's router, ONE
    routed expert (three matrices), the head. Not the table's lookup or
    the norms."""
    d, h, hkv, hd = sz["hidden_size"], sz["num_attention_heads"], \
        sz["num_key_value_heads"], sz["head_dim"]
    return {
        "attention": d * h * hd + 2 * d * hkv * hd + h * hd * d,
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


def window_pairs_per_step(sz, tr):
    """(Query, key) pairs the window layers admitted in one step, all of
    them together: the program's own count where `sizes` carries it (the
    `window_attn_pairs` counter, which every step overwrites), else the
    band's pairs by hand: row r keeps min(r + 1, window) keys."""
    pairs = sz.get(_PAIRS_KEY)
    if pairs is not None:
        return float(np.sum(pairs))
    s, w = tr["seq_len"], sz["sliding_window"]
    return float(reference.count(sz, "window") * tr["batch"]
                 * sum(min(r + 1, w) for r in range(s)))


def causal_pairs_per_step(sz, tr):
    """(Query, key) pairs the full layers admit in one step: S (S + 1) / 2
    a sequence a layer."""
    s = tr["seq_len"]
    return float(reference.count(sz, "full") * tr["batch"]
                 * s * (s + 1) // 2)


def attention_flops_forward(sz, pairs):
    """2 * H * pairs * (d + d): q k^T and p v over the admitted pairs."""
    return 2.0 * sz["num_attention_heads"] * pairs * 2 * sz["head_dim"]


def routed_flops_forward(sz, tr):
    """2 * rows * hidden * expert width * 3 matrices, at the rows the
    program counted."""
    return 2.0 * routed_rows_per_step(sz, tr) \
        * matmul_params(sz)["routed_expert"]


def flops_per_step(sz, tr):
    """FLOPs the forward and backward passes need for one step: forward =
    2 * tokens * the matrices every token passes + the routed experts at
    the rows counted + attention over the pairs admitted (the window
    layers' as counted, the full layers' causal); a step is three
    forwards. Recomputed work is not counted."""
    mp = matmul_params(sz)
    tokens = tr["batch"] * tr["seq_len"]
    n = reference.count(sz, "experts")
    dense = 2.0 * tokens * (n * (mp["attention"] + mp["router"])
                            + mp["head"])
    routed = routed_flops_forward(sz, tr)
    window = attention_flops_forward(sz, window_pairs_per_step(sz, tr))
    full = attention_flops_forward(sz, causal_pairs_per_step(sz, tr))
    return {"step": 3 * (dense + routed + window + full),
            "attention_step": 3 * (window + full),
            "window_attention_step": 3 * window,
            "full_attention_step": 3 * full,
            "dense_step": 3 * dense, "routed_step": 3 * routed}


def adam_routed_elements(sz):
    return sum(int(np.prod(shape))
               for _, shape, _, _ in reference.param_specs(sz)
               if int(np.prod(shape)) >= ADAM_KERNEL_MIN_NUMEL)


def adam_routed_bytes_per_step(sz):
    return ADAM_BYTES_PER_ELEMENT * adam_routed_elements(sz)


def expected_routing(sz, tr, rehearsal=False):
    """{kernel: the one decision every site of it must have taken}: a run
    in which attention fell to the composed path or the experts to the
    ragged dots is not `correct`."""
    if rehearsal:      # kernels route only off the CPU
        return {}
    s = tr["seq_len"]
    return {"fused_adam": "custom",
            "flash_attention": "custom"
            if s * s >= FLASH_MIN_SEQ_PRODUCT else "lowered",
            "moe_grouped_matmul": "custom"}


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
    calls this after the proof steps, so the program's two counters are
    read here too (through `get`, no fetch) and carried in `sizes` to the
    functions that count operations."""
    for key, var in ((_LOAD_KEY, EXPERT_LOAD_VAR),
                     (_PAIRS_KEY, WINDOW_PAIRS_VAR)):
        value = _read_counter(get, var)
        if value is None:
            sz.pop(key, None)
        else:
            sz[key] = value
    return reference.delta_norms_from_seed(
        sz, seed, {n: get(n) for n in names})


def expert_load(sz):
    """The counter as read after the proof steps, int64 [expert layers,
    experts held], or None."""
    return sz.get(_LOAD_KEY)


def window_pairs(sz):
    """The last proof step's count, int64 [window layers], or None."""
    return sz.get(_PAIRS_KEY)


def fresh_optimizer_state(sz, names):
    """Adam's accumulators and the two counters as the startup program
    leaves them."""
    import jax.numpy as jnp
    shapes = param_shapes(sz)
    out = {EXPERT_LOAD_VAR: jnp.zeros(
        (reference.count(sz, "experts"), sz["experts_held"]), jnp.int32),
        WINDOW_PAIRS_VAR: jnp.zeros((reference.count(sz, "window"),),
                                    jnp.int32)}
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
    layers' flash kernels (`flash_attention_window_fwd` / `_bwd` /
    `_dq`) are booked apart from the full layer's."""
    head = text[:text.find("=")] if "=" in text else text
    for kernel, hint in (("fused_adam", "adam"),
                         ("flash_attention_window", "flash_attention_window"),
                         ("flash_attention", "flash_attention"),
                         ("moe_grouped_matmul", "moe_grouped_matmul"),
                         ("moe_combine", "moe_combine")):
        if hint in head:
            return kernel
    return None
