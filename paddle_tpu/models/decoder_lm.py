"""Causal decoder-only language model: multi-head latent attention and a
mixture of sigmoid-routed experts with shared experts (the block of the
`deepseek_v3` model type), built with paddle_tpu.layers.

The configuration takes the published `config.json` keys as they are,
plus three that say which share of each layer THIS chip holds under
expert and vocabulary parallelism:

  experts_held, first_expert   the routed experts whose weights live
      here (default: all). The router keeps its published width and its
      top-k whatever is held; a choice of an expert held elsewhere adds
      nothing here. On one chip the expert layer runs without its
      exchange and nothing stands in for the absent chips.
  vocab_held   rows of the embedding and of the head held here (default:
      all); ids, logits and the loss are over that slice.

Every layer: h += Attn(RMSNorm(h)); h += FFN(RMSNorm(h)). The first
`first_k_dense_replace` layers have a dense SwiGLU feed-forward of
`intermediate_size`, the rest `n_routed_experts` routed SwiGLU experts
of `moe_intermediate_size` (top `num_experts_per_tok`) plus one shared
SwiGLU of `n_shared_experts * moe_intermediate_size`.

Published keys that say nothing about the shapes built here
(`max_position_embeddings`, `model_type`, `head_dim`, ...) are accepted
and ignored, so a `config.json` can be passed whole.

Every parameter has an explicit, stable name (`layer_<i>_attn_q.w_0`,
`layer_<i>_experts_gate.w_0`, ...). The persistable int32
`moe_expert_load` [MoE layers, experts held] accumulates, inside the
step, the tokens the router sent to each held expert
(observability/moe.py reads it).
"""
from __future__ import annotations

from .. import layers
from ..framework import name_scope
from ..initializer import Constant, Normal
from ..observability.moe import EXPERT_LOAD_VAR
from ..param_attr import ParamAttr


class DecoderLMConfig:
    def __init__(self, vocab_size=32000, hidden_size=2048,
                 num_hidden_layers=4, num_attention_heads=32,
                 kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000.0,
                 rope_interleave=True, rope_scaling=None,
                 rms_norm_eps=1e-6, intermediate_size=6144,
                 first_k_dense_replace=1, moe_layer_freq=1,
                 n_routed_experts=128, num_experts_per_tok=6,
                 n_shared_experts=2, moe_intermediate_size=768,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
                 topk_group=1, hidden_act="silu", attention_bias=False,
                 tie_word_embeddings=False, initializer_range=0.02,
                 experts_held=None, first_expert=0, vocab_held=None,
                 **unused):
        if q_lora_rank is not None:
            raise NotImplementedError("query compression (q_lora_rank)")
        if rope_scaling is not None:
            raise NotImplementedError("rope scaling")
        if not rope_interleave:
            raise NotImplementedError("half-split rotary pairs")
        if hidden_act != "silu" or attention_bias or tie_word_embeddings:
            raise NotImplementedError(
                "silu, no attention bias, untied head only")
        if moe_layer_freq != 1:
            raise NotImplementedError("moe_layer_freq other than 1")
        self.vocab_size = int(vocab_held or vocab_size)
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.qk_head_dim = qk_nope_head_dim + qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = rms_norm_eps
        self.intermediate_size = intermediate_size
        self.first_k_dense_replace = first_k_dense_replace
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.moe_intermediate_size = moe_intermediate_size
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.scoring_func = scoring_func
        self.topk_method = topk_method
        self.n_group, self.topk_group = n_group, topk_group
        self.initializer_range = initializer_range
        self.experts_held = int(experts_held or n_routed_experts)
        self.first_expert = int(first_expert)

    @property
    def moe_layers(self):
        return [i for i in range(self.num_hidden_layers)
                if i >= self.first_k_dense_replace]


def _w(name, cfg):
    return ParamAttr(name=name,
                     initializer=Normal(0.0, cfg.initializer_range))


def _linear(x, size, name, cfg):
    return layers.fc(x, size, num_flatten_dims=2,
                     param_attr=_w(name + ".w_0", cfg), bias_attr=False)


def _norm(x, name, cfg):
    return layers.rms_norm(
        x, epsilon=cfg.rms_norm_eps,
        param_attr=ParamAttr(name=name + ".w_0",
                             initializer=Constant(1.0)))


def latent_attention(x, cfg, name):
    """Multi-head latent attention without query compression, causal.
    q [.., H, nope + rope]; one compressed kv row of kv_lora_rank and ONE
    rope key shared by all heads; k = [k_nope ; rope(k_rope)] per head,
    v of its own width: the flash kernels take the two widths apart."""
    h = cfg.num_attention_heads
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    q = _linear(x, h * (nope + rope), name + "_q", cfg)
    q = layers.reshape(q, [0, 0, h, nope + rope])
    q = layers.rotary_embedding(q, theta=cfg.rope_theta, rotary_dim=rope)
    ckv = _linear(x, cfg.kv_lora_rank + rope, name + "_kva", cfg)
    c, k_rope = layers.split(ckv, [cfg.kv_lora_rank, rope], dim=-1)
    c = _norm(c, name + "_kv_norm", cfg)
    kv = _linear(c, h * (nope + dv), name + "_kvb", cfg)
    kv = layers.reshape(kv, [0, 0, h, nope + dv])
    k_nope, v = layers.split(kv, [nope, dv], dim=-1)
    k_rope = layers.rotary_embedding(
        layers.reshape(k_rope, [0, 0, 1, rope]), theta=cfg.rope_theta)
    k = layers.concat([k_nope, layers.expand(k_rope, [1, 1, h, 1])],
                      axis=3)
    ctx = layers.fused_attention(q, k, v, None,
                                 scale=(nope + rope) ** -0.5,
                                 layout="bshd", causal=True)
    ctx = layers.reshape(ctx, [0, 0, h * dv])
    return _linear(ctx, cfg.hidden_size, name + "_o", cfg)


def gated_ffn(x, width, cfg, name):
    hidden = layers.swiglu(_linear(x, width, name + "_gate", cfg),
                           _linear(x, width, name + "_up", cfg))
    return _linear(hidden, cfg.hidden_size, name + "_down", cfg)


def moe_ffn(x, cfg, name):
    """Routed experts held here plus the shared expert. Returns (output,
    the router's count of tokens per held expert)."""
    choice, weight, counts = layers.moe_router(
        x, cfg.n_routed_experts, cfg.num_experts_per_tok,
        experts_held=cfg.experts_held, first_expert=cfg.first_expert,
        scoring_func=cfg.scoring_func, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        param_attr=_w(name + "_router.w_0", cfg),
        bias_attr=ParamAttr(name=name + "_router.b_0",
                            initializer=Constant(0.0)))
    routed = layers.moe_experts(
        x, choice, weight, cfg.n_routed_experts,
        cfg.moe_intermediate_size, experts_held=cfg.experts_held,
        first_expert=cfg.first_expert,
        gate_attr=_w(name + "_experts_gate.w_0", cfg),
        up_attr=_w(name + "_experts_up.w_0", cfg),
        down_attr=_w(name + "_experts_down.w_0", cfg))
    if cfg.n_shared_experts:
        shared = gated_ffn(
            x, cfg.n_shared_experts * cfg.moe_intermediate_size, cfg,
            name + "_shared")
        routed = layers.elementwise_add(routed, shared)
    return routed, counts


def decoder_lm_train(cfg: DecoderLMConfig):
    """Build the training graph. Feeds: `input_ids` int32 [B, S] and
    `labels` int32 [B, S] (the next token of each position, prepared by
    the host), both over the held vocabulary slice. Returns (avg_cost,
    logits, feed names): the mean over positions of the cross-entropy."""
    ids = layers.data("input_ids", [-1, -1], append_batch_size=False,
                      dtype="int32")
    labels = layers.data("labels", [-1, -1], append_batch_size=False,
                         dtype="int32")
    with name_scope("embed"):
        h = layers.embedding(
            ids, size=[cfg.vocab_size, cfg.hidden_size],
            param_attr=_w("embed_tokens.w_0", cfg))
    counts = []
    for i in range(cfg.num_hidden_layers):
        p = f"layer_{i}"
        with name_scope(p):
            with name_scope("attn"):
                attn = latent_attention(_norm(h, p + "_attn_norm", cfg),
                                        cfg, p + "_attn")
                h = layers.elementwise_add(h, attn)
            if i < cfg.first_k_dense_replace:
                with name_scope("mlp"):
                    ffn = gated_ffn(_norm(h, p + "_ffn_norm", cfg),
                                    cfg.intermediate_size, cfg, p + "_mlp")
                    h = layers.elementwise_add(h, ffn)
            else:
                with name_scope("moe"):
                    ffn, c = moe_ffn(_norm(h, p + "_ffn_norm", cfg), cfg, p)
                    counts.append(c)
                    h = layers.elementwise_add(h, ffn)
    if counts:
        with name_scope("moe_expert_load"):
            load = layers.create_global_var(
                [len(counts), cfg.experts_held], 0, "int32",
                persistable=True, name=EXPERT_LOAD_VAR)
            layers.sums([load, layers.stack(counts, axis=0)], out=load)
    with name_scope("head"):
        logits = _linear(_norm(h, "final_norm", cfg), cfg.vocab_size,
                         "lm_head", cfg)
    with name_scope("loss"):
        cost = layers.softmax_with_cross_entropy(
            logits, layers.unsqueeze(labels, axes=[2]))
        avg_cost = layers.mean(cost)
    return avg_cost, logits, ["input_ids", "labels"]
