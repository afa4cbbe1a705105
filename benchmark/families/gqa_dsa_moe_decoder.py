"""Family file: a decoder-only language model with grouped-query
attention over the keys a learned sparse-attention indexer selects and
softmax-routed experts without a shared expert, trained — ONE CHIP'S
SHARE of a deployment in which several chips share each layer: the
routed experts held here, the vocabulary rows held here, everything else
(attention, indexer, router, norms) as every chip has it.

What the harness asks of a family is what `mla_moe_decoder.py` gives:
sizes from a configuration file, the programs built from them through the
framework's own model file (`models.decoder_lm`), batches from a seed,
what an item is, the FLOPs and bytes a step needs (from shapes and from
the program's own counters — rows routed to held experts, (query, key)
pairs the selection kept — never from `cost_analysis`), the kernels
expected to route, how the first gradient and the parameters are read out
of the program's state, and the plain reference
(`gqa_dsa_moe_decoder_reference.py`).
"""
from __future__ import annotations

import numpy as np

from . import gqa_dsa_moe_decoder_reference as reference
# what does not depend on the model: an item, the batches (ids uniform
# over the held vocabulary slice, the label the next token) and the
# constants of the shared kernels and of the shared counter
from .mla_moe_decoder import (  # noqa: F401
    ADAM_BYTES_PER_ELEMENT, ADAM_KERNEL_MIN_NUMEL, EXPERT_LOAD_VAR,
    FLASH_MIN_SEQ_PRODUCT, ITEM, KIND, PROOF_STEPS, _LOAD_KEY, items,
    make_pool)

# rehearsal only (CPU, explicit argument): the same code path at sizes an
# interpreter can run. Never a configuration file.
_REHEARSAL = dict(hidden_size=64, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  index_heads=4, index_head_dim=16, index_topk=12,
                  moe_intermediate_size=32, router_experts=16,
                  experts_held=4, vocab_held=512)
_REHEARSAL_TRAFFIC = dict(batch=2, seq_len=32, reference_query_rows=16)

INDEX_KERNEL_TILE = 1024   # sequences the index kernels tile: whole
#                            [512, 512] score tiles and 1,024-column chunks
KEPT_PAIRS_VAR = "sparse_attn_kept"    # the program's second counter
_KEPT_KEY = "_sparse_attn_kept"        # where `sizes` carries its reading

_PUBLISHED = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
    "intermediate_size", "num_experts_per_tok", "moe_intermediate_size",
    "norm_topk_prob", "decoder_sparse_step")


def sizes(config, rehearsal=False):
    """Flat sizes the family's functions take, from a configuration. The
    file's `num_experts` and `vocab_size` are what is HELD here; the
    router's width is the published count beside them."""
    assumed, cut, sa = config["assumed"], config["reduced"], \
        config["sa_config"]
    out = {k: config[k] for k in _PUBLISHED}
    out.update(
        router_experts=cut["num_experts"]["published"],
        experts_held=config["num_experts"],
        first_expert=config["deployment"]["first_expert"],
        vocab_held=config["vocab_size"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        initializer_range=assumed["initializer_range"],
        learning_rate=assumed["learning_rate"],
        adam_beta1=assumed["adam_beta1"], adam_beta2=assumed["adam_beta2"],
        adam_epsilon=assumed["adam_epsilon"])
    if sa["indexer_num_kv_heads"] != 1 or config["mlp_only_layers"] \
            or config["decoder_sparse_step"] != 1 \
            or config["rope_scaling"]["rope_type"] != "default" \
            or config["use_sliding_window"]:
        raise ValueError("the family has one index key head, experts in "
                         "every layer, plain rotary and no window")
    if rehearsal:
        out.update(_REHEARSAL)
    return out


def traffic(spec, rehearsal=False):
    out = dict(spec)
    if rehearsal:
        out.update(_REHEARSAL_TRAFFIC)
    return out


def model_config(sz):
    """The model file's configuration from the published keys, the
    indexer's as the `sa_config` group they come in."""
    from paddle_tpu import models
    return models.DecoderLMConfig(
        vocab_size=sz["vocab_held"], num_experts=sz["router_experts"],
        experts_held=sz["experts_held"], first_expert=sz["first_expert"],
        sa_config={"indexer_num_heads": sz["index_heads"],
                   "indexer_head_dim": sz["index_head_dim"],
                   "indexer_num_kv_heads": 1, "topk": sz["index_topk"]},
        **{k: sz[k] for k in _PUBLISHED + ("initializer_range",)})


def build(fluid, sz, seed):
    """(main, startup, loss variable): `models.decoder_lm_train`, Adam
    under `mixed_precision.decorate`, every flag at its default."""
    from paddle_tpu import layers, models
    if not hasattr(layers, "sparse_attention_index"):
        # a program from before the mechanism: say so at once, before
        # anything is built or reaches the device
        raise SystemExit("this checkout's paddle_tpu has no "
                         "`sparse_attention_index` layer: it cannot build "
                         "the gqa_dsa_moe_decoder family. No result.")
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
    """Every parameter the seed sets, the indexer's buffers among them."""
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
    one layer's attention projections (q, k, v, o), its indexer's three
    projections (forward only: they take no gradient), its router, ONE
    routed expert, the head. Not the embedding table or the norms."""
    d, h, hkv, hd = sz["hidden_size"], sz["num_attention_heads"], \
        sz["num_key_value_heads"], sz["head_dim"]
    hi, di = sz["index_heads"], sz["index_head_dim"]
    return {
        "attention": d * h * hd + 2 * d * hkv * hd + h * hd * d,
        "indexer": d * (hi * di + di + hi),
        "router": sz["router_experts"] * d,
        "routed_expert": 3 * d * sz["moe_intermediate_size"],
        "head": d * sz["vocab_held"]}


def causal_pairs(tr):
    """(query, key) pairs with key <= query, a layer a step."""
    s = tr["seq_len"]
    return tr["batch"] * s * (s + 1) // 2


def kept_pairs_per_step(sz, tr):
    """(query, key) pairs the selection kept in one step, all layers
    together: the program's own count where `sizes` carries it (the
    `sparse_attn_kept` counter, which every step overwrites), else
    sum_t min(t + 1, top_k) a sequence a layer."""
    kept = sz.get(_KEPT_KEY)
    if kept is not None:
        return float(np.sum(kept))
    return float(sz["num_hidden_layers"] * reference.kept_pairs_by_hand(
        tr["batch"], tr["seq_len"], sz["index_topk"]))


def routed_rows_per_step(sz, tr):
    """Rows the routed experts held here take in one step, all layers
    together: the program's own count where `sizes` carries it (the
    `moe_expert_load` counter after the proof steps), else what uniform
    routing gives."""
    load = sz.get(_LOAD_KEY)
    if load is not None:
        return float(np.sum(load)) / PROOF_STEPS
    tokens = tr["batch"] * tr["seq_len"]
    return (sz["num_hidden_layers"] * tokens * sz["num_experts_per_tok"]
            * sz["experts_held"] / sz["router_experts"])


def attention_flops_forward(sz, tr):
    """2 * H * kept pairs * (d + d): QK^T and PV over the pairs the
    selection KEPT — the work the mathematics needs whatever implements
    it (a masked dense kernel does more, a gathering one this much)."""
    return 2.0 * sz["num_attention_heads"] * kept_pairs_per_step(sz, tr) \
        * 2 * sz["head_dim"]


def index_flops_forward(sz, tr):
    """2 * H_I * d_I a causal pair: the indexer's q.k products over every
    pair with key <= query (forward only; it takes no gradient)."""
    return 2.0 * sz["index_heads"] * sz["index_head_dim"] \
        * causal_pairs(tr) * sz["num_hidden_layers"]


def index_select_bytes(sz, tr):
    """HBM bytes the selection cannot avoid a step: the float32 scores
    [S, S] read once and the int8 mask written once, a layer a sequence."""
    s = tr["seq_len"]
    return sz["num_hidden_layers"] * tr["batch"] * s * s * (4 + 1)


def routed_flops_forward(sz, tr):
    """2 * rows * hidden * expert width * 3 matrices, at the rows the
    program counted."""
    return 2.0 * routed_rows_per_step(sz, tr) \
        * matmul_params(sz)["routed_expert"]


def flops_per_step(sz, tr):
    """FLOPs the forward and backward passes need for one step: forward =
    2 * tokens * the matrices every token passes + the routed experts at
    the rows counted + attention over the pairs kept; a step is three
    forwards, plus ONE forward of the indexer (projections and scores:
    nothing of it is differentiated). Recomputed work is not counted."""
    mp = matmul_params(sz)
    layers = sz["num_hidden_layers"]
    tokens = tr["batch"] * tr["seq_len"]
    dense = 2 * tokens * (layers * (mp["attention"] + mp["router"])
                          + mp["head"])
    indexer = 2 * tokens * layers * mp["indexer"]
    index = index_flops_forward(sz, tr)
    routed = routed_flops_forward(sz, tr)
    attn = attention_flops_forward(sz, tr)
    return {"step": 3 * (dense + routed + attn) + indexer + index,
            "attention_step": 3 * attn, "dense_step": 3 * dense + indexer,
            "routed_step": 3 * routed, "index_step": index}


def adam_routed_elements(sz):
    return sum(int(np.prod(shape))
               for name, shape, _, _ in reference.param_specs(sz)
               if not reference.is_buffer(name)
               and int(np.prod(shape)) >= ADAM_KERNEL_MIN_NUMEL)


def adam_routed_bytes_per_step(sz):
    return ADAM_BYTES_PER_ELEMENT * adam_routed_elements(sz)


def expected_routing(sz, tr, rehearsal=False):
    """{kernel: the one decision every site of it must have taken}: a run
    in which attention fell to the composed path, the experts to the
    ragged dots or the index to its `jax.numpy` lowering is not
    `correct`."""
    if rehearsal:      # kernels route only off the CPU
        return {}
    s = tr["seq_len"]
    return {"fused_adam": "custom",
            "flash_attention": "custom"
            if s * s >= FLASH_MIN_SEQ_PRODUCT else "lowered",
            "moe_grouped_matmul": "custom",
            "sparse_index_scores": "custom"
            if s % INDEX_KERNEL_TILE == 0 else "lowered"}


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


def _read_counter(get, name):
    try:
        return np.asarray(get(name)).astype(np.int64)
    except (AttributeError, KeyError):       # a program without it
        return None


def read_delta_norms(get, names, sz, seed):
    """|p - p_0| per trained leaf, p_0 made again from the seed. The
    harness calls this after the proof steps, so the program's two
    counters are read here too (through `get`, no fetch) and carried in
    `sizes` to the functions that count operations."""
    for key, var in ((_LOAD_KEY, EXPERT_LOAD_VAR),
                     (_KEPT_KEY, KEPT_PAIRS_VAR)):
        value = _read_counter(get, var)
        if value is None:
            sz.pop(key, None)
        else:
            sz[key] = value
    return reference.delta_norms_from_seed(
        sz, seed, {n: get(n) for n in _trained(names)})


def expert_load(sz):
    """The counter as read after the proof steps, int64 [layers, experts
    held], or None."""
    return sz.get(_LOAD_KEY)


def kept_pairs(sz):
    """The last proof step's count, int64 [layers], or None."""
    return sz.get(_KEPT_KEY)


def fresh_optimizer_state(sz, names):
    """Adam's accumulators and the two counters as the startup program
    leaves them."""
    import jax.numpy as jnp
    shapes = param_shapes(sz)
    layers = sz["num_hidden_layers"]
    out = {EXPERT_LOAD_VAR: jnp.zeros((layers, sz["experts_held"]),
                                      jnp.int32),
           KEPT_PAIRS_VAR: jnp.zeros((layers,), jnp.int32)}
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
                         ("sparse_index_scores", "sparse_index_scores"),
                         ("sparse_index_select", "sparse_index_select")):
        if hint in head:
            return kernel
    return None
