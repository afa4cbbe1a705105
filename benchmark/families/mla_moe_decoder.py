"""Family file: a decoder-only language model with multi-head latent
attention and sigmoid-routed experts plus shared experts (the
`deepseek_v3` block), trained — ONE CHIP'S SHARE of a deployment in which
several chips share each layer: the routed experts held here, the
vocabulary rows held here, everything else (attention, router, shared
expert, norms) as every chip has it.

What the harness asks of a family is what `transformer_encdec.py` gives:
sizes from a configuration file, the programs built from them through
the framework's own model file (`models.decoder_lm`), batches from a
seed, what an item is, the FLOPs and bytes a step needs (from shapes and
from the program's own count of routed rows, never from
`cost_analysis`), the kernels expected to route, how the first gradient
and the parameters are read out of the program's state, and the plain
reference (`mla_moe_decoder_reference.py`).
"""
from __future__ import annotations

import numpy as np

from . import mla_moe_decoder_reference as reference

ITEM = "token trained (one position of one sequence)"
KIND = "train"

# rehearsal only (CPU, explicit argument): the same code path at sizes an
# interpreter can run. Never a configuration file.
_REHEARSAL = dict(hidden_size=64, num_hidden_layers=3,
                  num_attention_heads=2, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  intermediate_size=128, moe_intermediate_size=32,
                  router_experts=16, experts_held=4, vocab_held=512)
_REHEARSAL_TRAFFIC = dict(batch=2, seq_len=32, reference_query_rows=16)

ADAM_KERNEL_MIN_NUMEL = 65536          # the registry's element floor
FLASH_MIN_SEQ_PRODUCT = 1024 * 1024    # the flash kernels' crossover
ADAM_BYTES_PER_ELEMENT = 28            # f32 p, m, v, g read; p, m, v written
EXPERT_LOAD_VAR = "moe_expert_load"    # the program's counter
_LOAD_KEY = "_moe_expert_load"         # where `sizes` carries its reading
PROOF_STEPS = 3                        # steps the counter has seen when read

_PUBLISHED = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "rope_theta", "rms_norm_eps", "intermediate_size",
    "first_k_dense_replace", "num_experts_per_tok", "n_shared_experts",
    "moe_intermediate_size", "routed_scaling_factor", "norm_topk_prob",
    "scoring_func", "n_group", "topk_group")


def sizes(config, rehearsal=False):
    """Flat sizes the family's functions take, from a configuration. The
    file's `n_routed_experts` and `vocab_size` are what is HELD here;
    the router's width is the published count beside them."""
    assumed, cut = config["assumed"], config["reduced"]
    out = {k: config[k] for k in _PUBLISHED}
    out.update(
        router_experts=cut["n_routed_experts"]["published"],
        experts_held=config["n_routed_experts"],
        first_expert=config["deployment"]["first_expert"],
        vocab_held=config["vocab_size"],
        initializer_range=assumed["initializer_range"],
        learning_rate=assumed["learning_rate"],
        adam_beta1=assumed["adam_beta1"], adam_beta2=assumed["adam_beta2"],
        adam_epsilon=assumed["adam_epsilon"])
    if config["q_lora_rank"] is not None or config["rope_scaling"] \
            or not config["rope_interleave"]:
        raise ValueError("the family has no query compression, no rope "
                         "scaling and interleaved pairs only")
    if rehearsal:
        out.update(_REHEARSAL)
    return out


def traffic(spec, rehearsal=False):
    out = dict(spec)
    if rehearsal:
        out.update(_REHEARSAL_TRAFFIC)
    return out


def model_config(sz):
    from paddle_tpu import models
    return models.DecoderLMConfig(
        vocab_size=sz["vocab_held"], n_routed_experts=sz["router_experts"],
        experts_held=sz["experts_held"], first_expert=sz["first_expert"],
        **{k: sz[k] for k in _PUBLISHED + ("initializer_range",)})


def build(fluid, sz, seed):
    """(main, startup, loss variable): `models.decoder_lm_train`, Adam
    under `mixed_precision.decorate`, every flag at its default."""
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


def make_pool(sz, tr, seed):
    """`pool` distinct batches from the seed: full sequences of ids
    uniform over the held vocabulary slice, the label of a position the
    next token."""
    rng = np.random.default_rng(int(seed))
    pool = []
    for _ in range(tr["pool"]):
        ids = rng.integers(0, sz["vocab_held"],
                           (tr["batch"], tr["seq_len"] + 1), dtype=np.int32)
        pool.append({"input_ids": np.ascontiguousarray(ids[:, :-1]),
                     "labels": np.ascontiguousarray(ids[:, 1:])})
    return pool


def items(batch):
    return float(batch["labels"].size)


def param_names(sz):
    """Every parameter the seed sets, the router's buffers among them."""
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
    one layer's attention projections, the dense layer's feed-forward,
    a MoE layer's router and shared expert, ONE routed expert, the head.
    Not the embedding table or the norms."""
    d, h = sz["hidden_size"], sz["num_attention_heads"]
    nope, rope = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"]
    dv, rank, f = sz["v_head_dim"], sz["kv_lora_rank"], \
        sz["moe_intermediate_size"]
    return {
        "attention": d * h * (nope + rope) + d * (rank + rope)
        + rank * h * (nope + dv) + h * dv * d,
        "dense_ffn": 3 * d * sz["intermediate_size"],
        "router": sz["router_experts"] * d,
        "shared": 3 * d * sz["n_shared_experts"] * f,
        "routed_expert": 3 * d * f,
        "head": d * sz["vocab_held"]}


def routed_rows_per_step(sz, tr):
    """Rows the routed experts held here take in one step, all MoE
    layers together: the program's own count where `sizes` carries it
    (the `moe_expert_load` counter after the proof steps), else what
    uniform routing gives."""
    load = sz.get(_LOAD_KEY)
    if load is not None:
        return float(np.sum(load)) / PROOF_STEPS
    tokens = tr["batch"] * tr["seq_len"]
    return (len(reference.moe_layers(sz)) * tokens
            * sz["num_experts_per_tok"] * sz["experts_held"]
            / sz["router_experts"])


def attention_flops_forward(sz, tr):
    """2*B*H*Sq*Sk*(d_qk + d_v) an attention (QK^T at the q/k width, PV
    at the v width), causal: half."""
    b, s = tr["batch"], tr["seq_len"]
    d_qk = sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"]
    return sz["num_hidden_layers"] * (
        2 * b * sz["num_attention_heads"] * s * s
        * (d_qk + sz["v_head_dim"]) // 2)


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
    n_moe = len(reference.moe_layers(sz))
    n_dense = sz["num_hidden_layers"] - n_moe
    tokens = tr["batch"] * tr["seq_len"]
    dense = 2 * tokens * (
        sz["num_hidden_layers"] * mp["attention"]
        + n_dense * mp["dense_ffn"]
        + n_moe * (mp["router"] + mp["shared"]) + mp["head"])
    routed = routed_flops_forward(sz, tr)
    attn = attention_flops_forward(sz, tr)
    return {"step": 3 * (dense + routed + attn), "attention_step": 3 * attn,
            "dense_step": 3 * dense, "routed_step": 3 * routed}


def grouped_matmul_bytes_per_step(sz, tr):
    """HBM bytes the nine grouped matmuls of every MoE layer cannot avoid
    in one step, every held expert routed to: each expert's bf16 matrix
    read once a call in the six forward and dx calls, its float32
    gradient written once in the three dw calls, and the bf16 rows read
    and written (a [rows, hidden] and a [rows, width] side a call)."""
    n_moe = len(reference.moe_layers(sz))
    d, f = sz["hidden_size"], sz["moe_intermediate_size"]
    rows = routed_rows_per_step(sz, tr)
    weights = sz["experts_held"] * d * f * (6 * 2 + 3 * 4)
    return n_moe * weights + 9 * rows * (d + f) * 2


def adam_routed_elements(sz):
    return sum(int(np.prod(shape))
               for name, shape, _, _ in reference.param_specs(sz)
               if not reference.is_buffer(name)
               and int(np.prod(shape)) >= ADAM_KERNEL_MIN_NUMEL)


def adam_routed_bytes_per_step(sz):
    return ADAM_BYTES_PER_ELEMENT * adam_routed_elements(sz)


def expected_routing(sz, tr, rehearsal=False):
    """{kernel: the one decision every site of it must have taken}: a run
    in which latent attention fell to the composed path, or the experts
    to the ragged dots, is not `correct`."""
    if rehearsal:      # kernels route only off the CPU
        return {}
    flash = tr["seq_len"] * tr["seq_len"] >= FLASH_MIN_SEQ_PRODUCT
    return {"fused_adam": "custom",
            "flash_attention": "custom" if flash else "lowered",
            "moe_grouped_matmul": "custom"}


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
    harness calls this after the proof steps, so the program's count of
    routed rows is read here too (through `get`, no fetch) and carried
    in `sizes` to the functions that count operations."""
    try:
        sz[_LOAD_KEY] = np.asarray(get(EXPERT_LOAD_VAR)).astype(np.int64)
    except (AttributeError, KeyError):       # a program without it
        sz.pop(_LOAD_KEY, None)
    return reference.delta_norms_from_seed(
        sz, seed, {n: get(n) for n in _trained(names)})


def expert_load(sz):
    """The counter as read after the proof steps, int64 [MoE layers,
    experts held], or None."""
    return sz.get(_LOAD_KEY)


def fresh_optimizer_state(sz, names):
    """Adam's accumulators and the expert-load counter as the startup
    program leaves them."""
    import jax.numpy as jnp
    shapes = param_shapes(sz)
    out = {EXPERT_LOAD_VAR: jnp.zeros(
        (len(reference.moe_layers(sz)), sz["experts_held"]), jnp.int32)}
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
                         ("moe_grouped_matmul", "moe_grouped_matmul")):
        if hint in head:
            return kernel
    return None
