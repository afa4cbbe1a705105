"""Causal decoder-only language model with a mixture of routed experts,
built with paddle_tpu.layers. ONE model file: which attention, which
router and which feed-forwards a layer gets follow from the published
`config.json` keys the configuration is given, not from a model's name.

  attention   `kv_lora_rank` -> multi-head latent attention (the
      `deepseek_v3` block: q/k of nope + rope channels, v of its own
      width, one shared rope key); else grouped-query attention with
      `num_key_value_heads` key / value heads of `head_dim`, q and k
      normalised per head (RMS) before the rotary, and, with
      `sa_config`, a learned sparse attention: an indexer picks the
      `topk` keys each query attends (`sparse_attention_index`).
      `use_qk_norm` false: no q / k norm (absent: the norm, as always).
  rotary      adjacent pairs with `rope_interleave` (latent attention's
      default), half-split pairs (channel i with i + d/2) without; a
      `rope_scaling` of type
      "default" is plain rotary (text: the sections of a multimodal
      rotary all carry the same position), of type "yarn" the YaRN
      blend of transformers' `_compute_yarn_parameters` (truncating):
      `factor`, `original_max_position_embeddings`, `beta_fast`,
      `beta_slow`, `attention_factor` (ops/decoder.py `yarn_scale`).
      `rope_parameters` keyed by layer type ({"full_attention": {...},
      "sliding_attention": {...}}) gives each attention layer its type's
      own rotary; any other `rope_type` raises by name.
  experts     `n_routed_experts` (that key family scores by
      `scoring_func`, sigmoid with a selection-only bias unless given)
      or `num_experts` (that family scores by softmax, no bias);
      `n_shared_experts` shared SwiGLU experts beside them (0: none).
  dense layers  the first `first_k_dense_replace`, those listed in
      `mlp_only_layers`, and with `decoder_sparse_step` n every layer
      whose number (from 1) is no multiple of n: a dense SwiGLU of
      `intermediate_size` in place of the experts.
  feed-forwards  gated SwiGLU, three matrices (`hidden_act` "silu"), or
      with `mlp_hidden_act` "relu2" ungated, down(relu(up(x))^2), two:
      the routed experts, the shared expert (of
      `moe_shared_expert_intermediate_size` where that key is given)
      and the dense layers alike.
  hybrid      `hybrid_override_pattern` (the `nemotron_h` key): ONE part
      a layer, one character each — `M` a Mamba-2 mixer
      (`mamba_num_heads` heads of `mamba_head_dim`, `n_groups` groups of
      B and C, state `ssm_state_size`, a causal depthwise convolution of
      `conv_kernel` taps, scan chunks of `chunk_size`), `*` attention,
      `E` the expert layer, `-` a dense feed-forward. Such a model's
      attention has NO rotary and no q / k norm: its modelling code has
      neither, the positions come from the mixers.
  conv layers  `layer_types` (the `lfm2_moe` key): a list of "conv" and
      "full_attention", which token mixer each two-part layer has. A
      "conv" layer's is the gated short convolution: [Bg | Cg | x] = x
      W_in [D, 3 D]; Cg * conv(Bg * x), depthwise and causal over
      `conv_L_cache` taps, no bias (`conv_bias` true raises), no
      activation; W_out [D, D] (`gated_short_conv`). The first
      `num_dense_layers` layers (the same count as
      `first_k_dense_replace`) have the dense feed-forward. That key
      family's router scores by sigmoid with a selection-only bias where
      `use_expert_bias`, and adds `router_norm_epsilon` (1e-6, its
      modelling code's) to the chosen scores' sum. Any other entry of
      `layer_types` ("linear_attention", ...) raises by name.
      `rope_parameters` {rope_theta, rope_type} is read as `rope_theta` /
      `rope_scaling` are.
  window layers  a "sliding_attention" entry of `layer_types`: grouped-
      query attention whose query at position r attends the keys r -
      `sliding_window` < c <= r, the window an argument of the flash
      kernels (their grids step over the band's blocks alone), op scope
      `layer_<i>/swa`; "full_attention" the causal layer. `sliding_window`
      is admitted where `layer_types` places it and raises without;
      `use_sliding_window` / `max_window_layers` are accepted and ignored:
      `layer_types` governs, as in transformers.
  feed-forward by layer  `mlp_layer_types`, one entry a layer: "sparse"
      the expert layer, "dense" the dense SwiGLU of `intermediate_size`;
      any other entry raises by name.
  head        untied (`lm_head.w_0` [D, V]) unless `tie_word_embeddings`:
      then the logits are the final norm's output times the embedding's
      own matrix transposed — one parameter, one optimizer state, its
      gradient the sum of both uses.

Three more keys say which share of each layer THIS chip holds under
expert and vocabulary parallelism:

  experts_held, first_expert   the routed experts whose weights live
      here (default: all). The router keeps its published width and its
      top-k whatever is held; a choice of an expert held elsewhere adds
      nothing here. On one chip the expert layer runs without its
      exchange and nothing stands in for the absent chips.
  vocab_held   rows of the embedding and of the head held here (default:
      all); ids, logits and the loss are over that slice.

Every layer: h += Attn(RMSNorm(h)); h += FFN(RMSNorm(h)); with a
pattern h += part(RMSNorm(h)), one part.

Published keys that say nothing about the shapes built here
(`max_position_embeddings`, `model_type`, ...) are accepted and ignored,
so a `config.json` can be passed whole. Keys that change a layer's
equations and are not built here raise NotImplementedError by name
(`moe_latent_size`, `num_nextn_predict_layers`, a `sliding_window`
that no `layer_types` places, a `layer_types` entry that is not "conv",
"full_attention" or "sliding_attention", `conv_bias`, ...): a file that
carries one is never built as some other model.

Every parameter has an explicit, stable name (`layer_<i>_attn_q.w_0`,
`layer_<i>_experts_gate.w_0`, ...). The indexer's weights
(`layer_<i>_attn_index_*`) are buffers: not trainable, and the indexer
reads the layer's input with no gradient (its own alignment loss is a
training recipe's, not the language model's). Three persistable int32
counters are written inside the step: `moe_expert_load` [MoE layers,
experts held] ACCUMULATES the tokens the router sent to each held
expert and `moe_rows_worked` [MoE layers, 2] the rows of its row buffer
each expert layer worked over and the rows of it in use
(observability/moe.py reads both); `sparse_attn_kept` [layers] is
OVERWRITTEN with the (query, key) pairs each layer's selection kept
(observability/sparse_attention.py). A model with mixers keeps a fourth,
`mamba_ssd_tokens` [mixer layers], OVERWRITTEN with the tokens each
mixer's scan went over (observability/mamba.py); one with conv layers a
fifth, `short_conv_tokens` [conv layers], OVERWRITTEN with the tokens
each layer's convolution went over (observability/short_conv.py); one
with window layers a sixth, `window_attn_pairs` [window layers],
OVERWRITTEN with the (query, key) pairs each window layer's band
admitted (observability/window_attention.py).

A conv layer's parameters: `layer_<i>_conv_norm.w_0`, `layer_<i>_conv_in
.w_0` [D, 3 D], `layer_<i>_conv.w_0` [D, taps] drawn from U(-1 /
sqrt(taps), 1 / sqrt(taps)) (a depthwise Conv1d's own default),
`layer_<i>_conv_out.w_0` [D, D].

A mixer's parameters (`layer_<i>_mixer_*`): `in.w_0` [D, 2 H P + 2 G N
+ H] (the gate z, the convolution's input x | B | C, the step sizes dt),
`conv.w_0` [H P + 2 G N, taps] and `conv.b_0`, `dt.b_0`, `a_log.w_0`,
`d.w_0` [H], `norm.w_0` [H P], `out.w_0` [H P, D]. The startup program
draws `a_log` as the log of values spread evenly over [1, 16] and
`dt.b_0` as the inverse softplus of steps spread evenly in the log over
[`time_step_min`, `time_step_max`] (the published initialiser draws both
at random from those ranges), `d` at one, the convolution's weight and
bias from U(-1 / sqrt(taps), 1 / sqrt(taps)) (a depthwise Conv1d's own
default, which the published initialiser leaves as it is).
"""
from __future__ import annotations

from .. import layers
from ..framework import name_scope
from ..layers.decoder import YARN_KEYS
import numpy as np

from ..initializer import (Constant, Normal, NumpyArrayInitializer,
                           Uniform)
from ..observability.mamba import SSD_TOKENS_VAR
from ..observability.moe import EXPERT_LOAD_VAR, ROWS_WORKED_VAR
from ..observability.short_conv import SHORT_CONV_TOKENS_VAR
from ..observability.sparse_attention import KEPT_PAIRS_VAR
from ..observability.window_attention import WINDOW_PAIRS_VAR
from ..param_attr import ParamAttr


# one character of `hybrid_override_pattern` -> the layer's one part (and
# its op scope)
PATTERN_PARTS = {"M": "mamba", "*": "attn", "E": "moe", "-": "mlp"}
# an entry of `layer_types` -> a two-part layer's token mixer (and its op
# scope)
LAYER_TYPES = {"conv": "conv", "full_attention": "attn",
               "sliding_attention": "swa"}
# an entry of `mlp_layer_types` -> whether the layer's feed-forward is dense
MLP_LAYER_TYPES = {"sparse": False, "dense": True}
_YARN_DEFAULTS = {"beta_fast": 32.0, "beta_slow": 1.0}


def rotary_of(params, theta):
    """(theta, YaRN dict or None) of one rotary's parameters (a
    `rope_scaling` or an entry of `rope_parameters`); a `rope_type` that
    is neither "default" nor "yarn" raises by name."""
    params = params or {}
    kind = params.get("rope_type", params.get("type", "default"))
    theta = float(params.get("rope_theta", theta))
    if kind == "default":
        return theta, None
    if kind != "yarn":
        raise NotImplementedError(f"rope scaling {kind!r}")
    yarn = dict(_YARN_DEFAULTS, **{k: params[k] for k in YARN_KEYS
                                   if params.get(k) is not None})
    yarn.setdefault("attention_factor",
                    0.1 * np.log(float(yarn["factor"])) + 1.0)
    return theta, {k: float(yarn[k]) for k in YARN_KEYS}


def parse_pattern(pattern):
    """`hybrid_override_pattern` -> the parts of its layers, in order."""
    bad = sorted(set(pattern) - set(PATTERN_PARTS))
    if bad or not pattern:
        raise ValueError(f"hybrid_override_pattern {pattern!r}: a layer is "
                         f"one of {sorted(PATTERN_PARTS)}, not {bad}")
    return [PATTERN_PARTS[ch] for ch in pattern]


class DecoderLMConfig:
    def __init__(self, vocab_size=32000, hidden_size=2048,
                 num_hidden_layers=None, num_attention_heads=32,
                 kv_lora_rank=None, q_lora_rank=None, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 num_key_value_heads=None, head_dim=None, sa_config=None,
                 rope_theta=10000.0, rope_interleave=None,
                 rope_scaling=None, rms_norm_eps=None,
                 layer_norm_epsilon=None, norm_eps=None,
                 intermediate_size=6144, first_k_dense_replace=0,
                 mlp_only_layers=(), decoder_sparse_step=1,
                 moe_layer_freq=1, n_routed_experts=None, num_experts=None,
                 num_experts_per_tok=6, n_shared_experts=0,
                 moe_intermediate_size=768,
                 moe_shared_expert_intermediate_size=None,
                 routed_scaling_factor=1.0,
                 norm_topk_prob=True, scoring_func=None,
                 topk_method="noaux_tc", n_group=1, topk_group=1,
                 hidden_act="silu", mlp_hidden_act=None,
                 attention_bias=False,
                 tie_word_embeddings=False, initializer_range=0.02,
                 hybrid_override_pattern=None, mamba_num_heads=None,
                 mamba_head_dim=None, n_groups=1, ssm_state_size=128,
                 conv_kernel=4, chunk_size=128, use_conv_bias=True,
                 mamba_hidden_act="silu", mamba_proj_bias=False,
                 mlp_bias=False, use_bias=False, time_step_min=0.001,
                 time_step_max=0.1, time_step_floor=1e-4,
                 time_step_limit=None, moe_latent_size=None,
                 num_nextn_predict_layers=0, sliding_window=None,
                 layer_types=None, num_dense_layers=None, conv_L_cache=3,
                 conv_bias=False, use_expert_bias=None,
                 router_norm_epsilon=None, rope_parameters=None,
                 mlp_layer_types=None, use_qk_norm=None,
                 experts_held=None, first_expert=0, vocab_held=None,
                 **unused):
        if q_lora_rank is not None:
            raise NotImplementedError("query compression (q_lora_rank)")
        # keys that change a layer's equations and are not built here: a
        # file that carries one must not build as some other model
        windowed = "sliding_attention" in (layer_types or ())
        for key, given in (
                ("moe_latent_size", moe_latent_size is not None),
                ("num_nextn_predict_layers", bool(num_nextn_predict_layers)),
                ("sliding_window", sliding_window is not None
                 and not windowed),
                ("conv_bias", bool(conv_bias))):
            if given:
                raise NotImplementedError(
                    f"{key}: the model file builds no such layer")
        if layer_types is not None:
            bad = sorted(set(layer_types) - set(LAYER_TYPES))
            if bad:
                raise NotImplementedError(
                    f"layer_types {bad}: the model file builds no such "
                    f"layer (it builds {sorted(LAYER_TYPES)})")
            if hybrid_override_pattern is not None or kv_lora_rank:
                raise ValueError("layer_types beside a pattern or latent "
                                 "attention")
            if num_hidden_layers not in (None, len(layer_types)):
                raise ValueError(
                    f"num_hidden_layers {num_hidden_layers} against "
                    f"{len(layer_types)} layer_types")
            num_hidden_layers = len(layer_types)
            if windowed and not sliding_window:
                raise ValueError("sliding_attention layers without a "
                                 "sliding_window")
        if num_dense_layers is not None:
            if first_k_dense_replace not in (0, num_dense_layers):
                raise ValueError("num_dense_layers and first_k_dense_replace "
                                 "differ")
            first_k_dense_replace = num_dense_layers
        # `rope_parameters` keyed by layer type: each attention layer its
        # type's own rotary
        by_type = None
        if rope_parameters and all(isinstance(v, dict)
                                   for v in rope_parameters.values()):
            by_type = {t: rotary_of(p, rope_theta)
                       for t, p in rope_parameters.items()}
        elif rope_parameters:
            rope_theta = rope_parameters.get("rope_theta", rope_theta)
            rope_scaling = rope_scaling or rope_parameters
        rope_theta, self.yarn = rotary_of(rope_scaling, rope_theta)
        act = mlp_hidden_act or hidden_act
        if act not in ("silu", "relu2") or attention_bias \
                or mlp_bias or use_bias:
            raise NotImplementedError(
                "silu (gated) or relu2 (ungated) feed-forwards, no bias")
        if moe_layer_freq != 1:
            raise NotImplementedError("moe_layer_freq other than 1")
        self.parts = None if hybrid_override_pattern is None \
            else parse_pattern(hybrid_override_pattern)
        if self.parts is None:
            num_hidden_layers = 4 if num_hidden_layers is None \
                else num_hidden_layers
        elif num_hidden_layers not in (None, len(self.parts)):
            raise ValueError(
                f"num_hidden_layers {num_hidden_layers} against a pattern "
                f"of {len(self.parts)} layers")
        else:
            num_hidden_layers = len(self.parts)
        has_experts = self.parts is None or "moe" in self.parts
        if has_experts and \
                (n_routed_experts is None) == (num_experts is None):
            raise ValueError("one of n_routed_experts and num_experts")
        self.vocab_size = int(vocab_held or vocab_size)
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        # a two-part layer's token mixer, by layer (None: attention)
        self.mixers = None if layer_types is None \
            else [LAYER_TYPES[t] for t in layer_types]
        self.sliding_window = int(sliding_window) if windowed else None
        self.rotary_by_type = by_type
        self.layer_types = list(layer_types) if layer_types else None
        if by_type is not None and self.layer_types and any(
                t not in by_type for t in self.layer_types if t != "conv"):
            raise ValueError(f"rope_parameters {sorted(by_type)} against "
                             f"layer_types {sorted(set(layer_types))}")
        self.conv_taps = int(conv_L_cache)
        self.tie_word_embeddings = bool(tie_word_embeddings)
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.qk_head_dim = qk_nope_head_dim + qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.num_key_value_heads = num_key_value_heads \
            or num_attention_heads
        self.head_dim = head_dim or hidden_size // num_attention_heads
        self.sa_config = dict(sa_config) if sa_config else None
        if self.sa_config and self.sa_config["indexer_num_kv_heads"] != 1:
            raise NotImplementedError("one index key head")
        self.rope_theta = float(rope_theta)
        # absent: the latent block's own default (adjacent pairs), the
        # half-split pairs of every other decoder
        self.rope_interleave = bool(kv_lora_rank) \
            if rope_interleave is None else bool(rope_interleave)
        # a hybrid's attention: no rotary, no q / k norm
        self.attention_positions = self.parts is None
        self.qk_norm = self.attention_positions if use_qk_norm is None \
            else bool(use_qk_norm) and self.attention_positions
        eps = {e for e in (rms_norm_eps, layer_norm_epsilon, norm_eps)
               if e is not None}
        if len(eps) > 1:
            raise ValueError(f"the norm's epsilon given as {sorted(eps)}")
        self.rms_norm_eps = eps.pop() if eps else 1e-6
        self.intermediate_size = intermediate_size
        self.dense_layers = {
            i for i in range(num_hidden_layers)
            if i < first_k_dense_replace or i in set(mlp_only_layers)
            or (i + 1) % decoder_sparse_step}
        if mlp_layer_types is not None:
            bad = sorted(set(mlp_layer_types) - set(MLP_LAYER_TYPES))
            if bad:
                raise NotImplementedError(
                    f"mlp_layer_types {bad}: the model file builds "
                    f"{sorted(MLP_LAYER_TYPES)} feed-forwards")
            if len(mlp_layer_types) != num_hidden_layers:
                raise ValueError(f"{len(mlp_layer_types)} mlp_layer_types "
                                 f"for {num_hidden_layers} layers")
            self.dense_layers = {i for i, t in enumerate(mlp_layer_types)
                                 if MLP_LAYER_TYPES[t]}
        self.n_routed_experts = n_routed_experts or num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_width = n_shared_experts * (
            moe_shared_expert_intermediate_size or moe_intermediate_size)
        self.gated_ffn = act == "silu"
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        # the two key families' own modelling code: sigmoid scores with
        # a selection-only correction bias, or softmax over all experts
        # ... or, where `use_expert_bias` is a key (`lfm2_moe`), sigmoid
        # with that bias and 1e-6 under the chosen scores' sum
        lfm = use_expert_bias is not None
        self.scoring_func = scoring_func or (
            "sigmoid" if n_routed_experts or lfm else "softmax")
        self.router_bias = bool(use_expert_bias) if lfm else (
            bool(n_routed_experts) and topk_method == "noaux_tc")
        # None: the router op's own 1e-20
        self.router_norm_epsilon = router_norm_epsilon if \
            router_norm_epsilon is not None else (1e-6 if lfm else None)
        self.n_group, self.topk_group = n_group, topk_group
        self.initializer_range = initializer_range
        self.experts_held = int(experts_held or self.n_routed_experts or 0)
        self.first_expert = int(first_expert)
        if self.parts and "mamba" in self.parts:
            if mamba_hidden_act != "silu" or mamba_proj_bias:
                raise NotImplementedError(
                    "a mixer with silu and no projection bias only")
            if time_step_limit is not None and (
                    time_step_limit[0] or time_step_limit[1]
                    not in (None, float("inf"))):
                raise NotImplementedError("a clamp on the step sizes "
                                          "(time_step_limit)")
            if not mamba_num_heads or not mamba_head_dim \
                    or mamba_num_heads % n_groups:
                raise ValueError("mamba_num_heads and mamba_head_dim, the "
                                 "heads a multiple of n_groups")
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.n_groups, self.ssm_state_size = n_groups, ssm_state_size
        self.conv_kernel, self.chunk_size = conv_kernel, chunk_size
        self.use_conv_bias = bool(use_conv_bias)
        self.time_step = (time_step_min, time_step_max, time_step_floor)

    def rotary_at(self, i):
        """(theta, YaRN dict or None) of layer i's attention."""
        if self.rotary_by_type is not None:
            return self.rotary_by_type[self.layer_types[i]]
        return self.rope_theta, self.yarn

    def window_at(self, i):
        """Layer i's sliding window, or None (a causal layer)."""
        if self.mixers is not None and self.mixers[i] == "swa":
            return self.sliding_window
        return None

    @property
    def moe_layers(self):
        if self.parts is not None:
            return [i for i, part in enumerate(self.parts) if part == "moe"]
        return [i for i in range(self.num_hidden_layers)
                if i not in self.dense_layers]


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
    q = layers.rotary_embedding(q, theta=cfg.rope_theta, rotary_dim=rope,
                                interleaved=cfg.rope_interleave)
    ckv = _linear(x, cfg.kv_lora_rank + rope, name + "_kva", cfg)
    c, k_rope = layers.split(ckv, [cfg.kv_lora_rank, rope], dim=-1)
    c = _norm(c, name + "_kv_norm", cfg)
    kv = _linear(c, h * (nope + dv), name + "_kvb", cfg)
    kv = layers.reshape(kv, [0, 0, h, nope + dv])
    k_nope, v = layers.split(kv, [nope, dv], dim=-1)
    k_rope = layers.rotary_embedding(
        layers.reshape(k_rope, [0, 0, 1, rope]), theta=cfg.rope_theta,
        interleaved=cfg.rope_interleave)
    k = layers.concat([k_nope, layers.expand(k_rope, [1, 1, h, 1])],
                      axis=3)
    ctx = layers.fused_attention(q, k, v, None,
                                 scale=(nope + rope) ** -0.5,
                                 layout="bshd", causal=True)
    ctx = layers.reshape(ctx, [0, 0, h * dv])
    return _linear(ctx, cfg.hidden_size, name + "_o", cfg)


def _buffer(name, cfg, init=None):
    """A weight the optimizer never sees: drawn once, then held."""
    return ParamAttr(name=name, trainable=False,
                     initializer=init or Normal(0.0, cfg.initializer_range))


def sparse_index(x, cfg, name):
    """The learned sparse attention's indexer on the layer's normalised
    input: `indexer_num_heads` index queries and ONE index key of
    `indexer_head_dim` a token (the key layer-normalised, both rotated),
    a weight a head, and from them the keep mask of the `topk` keys each
    query attends. Returns (mask int8 [B, 1, S, S], pairs kept int32
    [1]). Every weight is a buffer and nothing here takes a gradient."""
    sa = cfg.sa_config
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]

    def project(width, part):
        return layers.fc(x, width, num_flatten_dims=2, bias_attr=False,
                         param_attr=_buffer(f"{name}_{part}.w_0", cfg))

    def rotate(t):
        return layers.rotary_embedding(t, theta=cfg.rope_theta,
                                       interleaved=cfg.rope_interleave)

    q = rotate(layers.reshape(project(heads * dim, "q"), [0, 0, heads, dim]))
    k = layers.layer_norm(
        project(dim, "k"), begin_norm_axis=2, epsilon=1e-6,
        param_attr=_buffer(name + "_k_norm.w_0", cfg, Constant(1.0)),
        bias_attr=_buffer(name + "_k_norm.b_0", cfg, Constant(0.0)))
    k = layers.reshape(rotate(layers.reshape(k, [0, 0, 1, dim])),
                       [0, 0, dim])
    return layers.sparse_attention_index(
        q, k, project(heads, "w"), sa["topk"],
        scale=heads ** -0.5 * dim ** -0.5)


def grouped_query_attention(x, cfg, name, layer=0):
    """Causal attention of `num_attention_heads` query heads over
    `num_key_value_heads` key / value heads of `head_dim` (k and v go to
    the op at their own head count), q and k RMS-normalised per head
    (`qk_norm`) and then rotated by the layer's rotary (neither in a
    hybrid: `attention_positions`); with `sa_config` over the keys the
    indexer keeps; in a window layer over the layer's band.
    Returns (output, pairs kept int32 [1] or None, pairs the window
    admitted int32 [1] or None)."""
    h, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    theta, yarn = cfg.rotary_at(layer)

    def heads(part, n, normed):
        t = layers.reshape(_linear(x, n * d, f"{name}_{part}", cfg),
                           [0, 0, n, d])
        if not (normed and cfg.attention_positions):
            return t
        if cfg.qk_norm:
            t = _norm(t, f"{name}_{part}_norm", cfg)
        return layers.rotary_embedding(t, theta=theta,
                                       interleaved=cfg.rope_interleave,
                                       yarn=yarn)

    q, k, v = heads("q", h, True), heads("k", hkv, True), \
        heads("v", hkv, False)
    mask = kept = admitted = None
    if cfg.sa_config:
        with name_scope("index"):
            mask, kept = sparse_index(x, cfg, name + "_index")
    window = cfg.window_at(layer)
    ctx = layers.fused_attention(q, k, v, mask, scale=d ** -0.5,
                                 layout="bshd", causal=True, window=window)
    if window is not None:
        admitted = ctx.block.var(ctx.op.output("WindowPairs")[0])
    ctx = layers.reshape(ctx, [0, 0, h * d])
    return _linear(ctx, cfg.hidden_size, name + "_o", cfg), kept, admitted


def gated_ffn(x, width, cfg, name):
    hidden = layers.swiglu(_linear(x, width, name + "_gate", cfg),
                           _linear(x, width, name + "_up", cfg))
    return _linear(hidden, cfg.hidden_size, name + "_down", cfg)


def ffn(x, width, cfg, name):
    """The configuration's feed-forward: gated SwiGLU, or ungated with a
    squared ReLU."""
    if cfg.gated_ffn:
        return gated_ffn(x, width, cfg, name)
    hidden = layers.relu2(_linear(x, width, name + "_up", cfg))
    return _linear(hidden, cfg.hidden_size, name + "_down", cfg)


def moe_ffn(x, cfg, name):
    """Routed experts held here plus the shared expert where there is
    one. Returns (output, the router's count of tokens per held
    expert, the expert layer's rows worked and in use)."""
    choice, weight, counts = layers.moe_router(
        x, cfg.n_routed_experts, cfg.num_experts_per_tok,
        experts_held=cfg.experts_held, first_expert=cfg.first_expert,
        scoring_func=cfg.scoring_func, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        param_attr=_w(name + "_router.w_0", cfg),
        bias_attr=ParamAttr(name=name + "_router.b_0",
                            initializer=Constant(0.0))
        if cfg.router_bias else False,
        norm_epsilon=cfg.router_norm_epsilon)
    routed = layers.moe_experts(
        x, choice, weight, cfg.n_routed_experts,
        cfg.moe_intermediate_size, experts_held=cfg.experts_held,
        first_expert=cfg.first_expert,
        gate_attr=_w(name + "_experts_gate.w_0", cfg),
        up_attr=_w(name + "_experts_up.w_0", cfg),
        down_attr=_w(name + "_experts_down.w_0", cfg),
        activation="swiglu" if cfg.gated_ffn else "relu2")
    worked = routed.block.var(routed.op.output("RowsWorked")[0])
    if cfg.n_shared_experts:
        shared = ffn(x, cfg.shared_expert_width, cfg, name + "_shared")
        routed = layers.elementwise_add(routed, shared)
    return routed, counts, worked


def _per_head(name, values):
    return ParamAttr(name=name, initializer=NumpyArrayInitializer(
        np.asarray(values, np.float32)))


def mamba_mixer(x, cfg, name):
    """A Mamba-2 mixer on the layer's normalised input: [z | x B C | dt]
    = x W_in; x B C through the causal depthwise convolution and SiLU;
    the state-space scan; the group-wise RMS norm gated by z; W_out.
    Returns (output, tokens scanned int32 [1])."""
    h, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, n = cfg.n_groups, cfg.ssm_state_size
    inner, groups = h * p, g * n
    z, xbc, dt = layers.split(
        _linear(x, 2 * inner + 2 * groups + h, name + "_in", cfg),
        [inner, inner + 2 * groups, h], dim=-1)
    taps = Uniform(-cfg.conv_kernel ** -0.5, cfg.conv_kernel ** -0.5)
    xbc = layers.causal_conv1d(
        xbc, cfg.conv_kernel, act="silu",
        param_attr=ParamAttr(name=name + "_conv.w_0", initializer=taps),
        bias_attr=ParamAttr(name=name + "_conv.b_0", initializer=taps)
        if cfg.use_conv_bias else False)
    xs, b, c = layers.split(xbc, [inner, groups, groups], dim=-1)
    lo, hi, floor = cfg.time_step
    step = np.maximum(np.exp(np.linspace(np.log(lo), np.log(hi), h)), floor)
    y, tokens = layers.mamba2_ssd(
        layers.reshape(xs, [0, 0, h, p]), dt,
        layers.reshape(b, [0, 0, g, n]), layers.reshape(c, [0, 0, g, n]),
        chunk_size=cfg.chunk_size,
        # softplus^-1(step) = step + log(1 - exp(-step))
        dt_bias_attr=_per_head(name + "_dt.b_0",
                               step + np.log(-np.expm1(-step))),
        a_log_attr=_per_head(name + "_a_log.w_0",
                             np.log(np.linspace(1.0, 16.0, h))),
        d_attr=ParamAttr(name=name + "_d.w_0", initializer=Constant(1.0)))
    y = layers.gated_rms_norm(
        layers.reshape(y, [0, 0, inner]), z, groups=g,
        epsilon=cfg.rms_norm_eps,
        param_attr=ParamAttr(name=name + "_norm.w_0",
                             initializer=Constant(1.0)))
    return _linear(y, cfg.hidden_size, name + "_out", cfg), tokens


def short_conv(x, cfg, name):
    """The gated short convolution operator on the layer's normalised
    input: [Bg | Cg | x] = x W_in; Cg * conv(Bg * x) over `conv_taps`
    tokens back; W_out. Returns (output, tokens convolved int32 [1])."""
    taps = Uniform(-cfg.conv_taps ** -0.5, cfg.conv_taps ** -0.5)
    y, tokens = layers.gated_short_conv(
        _linear(x, 3 * cfg.hidden_size, name + "_in", cfg), cfg.conv_taps,
        param_attr=ParamAttr(name=name + ".w_0", initializer=taps))
    return _linear(y, cfg.hidden_size, name + "_out", cfg), tokens


class _Counts:
    """What the layers hand to the step's counters."""

    def __init__(self):
        self.load, self.worked, self.kept, self.scanned = [], [], [], []
        self.convolved, self.admitted = [], []


def _attention(x, cfg, name, counts, layer=0):
    if cfg.kv_lora_rank:
        return latent_attention(x, cfg, name)
    attn, n, admitted = grouped_query_attention(x, cfg, name, layer)
    if n is not None:
        counts.kept.append(n)
    if admitted is not None:
        counts.admitted.append(admitted)
    return attn


def _experts(x, cfg, p, counts):
    out, c, w = moe_ffn(x, cfg, p)
    counts.load.append(c)
    counts.worked.append(w)
    return out


def _two_part_layer(h, i, cfg, counts):
    """h += Mixer(RMSNorm(h)); h += FFN(RMSNorm(h)), the mixer attention
    (causal, or in a window layer over its band) or, by `layer_types`,
    the gated short convolution."""
    p = f"layer_{i}"
    if cfg.mixers is not None and cfg.mixers[i] == "conv":
        with name_scope("conv"):
            out, n = short_conv(_norm(h, p + "_conv_norm", cfg), cfg,
                                p + "_conv")
            counts.convolved.append(n)
            h = layers.elementwise_add(h, out)
    else:
        with name_scope("attn" if cfg.window_at(i) is None else "swa"):
            attn = _attention(_norm(h, p + "_attn_norm", cfg), cfg,
                              p + "_attn", counts, i)
            h = layers.elementwise_add(h, attn)
    if i in cfg.dense_layers:
        with name_scope("mlp"):
            out = gated_ffn(_norm(h, p + "_ffn_norm", cfg),
                            cfg.intermediate_size, cfg, p + "_mlp")
            return layers.elementwise_add(h, out)
    with name_scope("moe"):
        out = _experts(_norm(h, p + "_ffn_norm", cfg), cfg, p, counts)
        return layers.elementwise_add(h, out)


def _one_part_layer(h, i, part, cfg, counts):
    """h += part(RMSNorm(h)), the part one of `PATTERN_PARTS`' names."""
    p = f"layer_{i}"
    with name_scope(part):
        x = _norm(h, p + "_norm", cfg)
        if part == "mamba":
            out, n = mamba_mixer(x, cfg, p + "_mixer")
            counts.scanned.append(n)
        elif part == "attn":
            out = _attention(x, cfg, p + "_attn", counts, i)
        elif part == "moe":
            out = _experts(x, cfg, p, counts)
        else:
            out = ffn(x, cfg.intermediate_size, cfg, p + "_mlp")
        return layers.elementwise_add(h, out)


def _overwritten_counter(name, parts):
    """A persistable int32 [len(parts)] every step overwrites (a sum
    would overflow an int32 within minutes: 14.7 M pairs a layer a step
    at 8,192 tokens)."""
    with name_scope(name):
        layers.assign(layers.concat(parts, axis=0),
                      output=layers.create_global_var(
                          [len(parts)], 0, "int32", persistable=True,
                          name=name))


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
    counts = _Counts()
    for i in range(cfg.num_hidden_layers):
        with name_scope(f"layer_{i}"):
            if cfg.parts is None:
                h = _two_part_layer(h, i, cfg, counts)
            else:
                h = _one_part_layer(h, i, cfg.parts[i], cfg, counts)
    if counts.load:
        with name_scope("moe_expert_load"):
            load = layers.create_global_var(
                [len(counts.load), cfg.experts_held], 0, "int32",
                persistable=True, name=EXPERT_LOAD_VAR)
            layers.sums([load, layers.stack(counts.load, axis=0)], out=load)
        with name_scope("moe_rows_worked"):
            rows = layers.create_global_var(
                [len(counts.worked), 2], 0, "int32", persistable=True,
                name=ROWS_WORKED_VAR)
            layers.sums([rows, layers.stack(counts.worked, axis=0)],
                        out=rows)
    if counts.kept:
        _overwritten_counter(KEPT_PAIRS_VAR, counts.kept)
    if counts.scanned:
        _overwritten_counter(SSD_TOKENS_VAR, counts.scanned)
    if counts.convolved:
        _overwritten_counter(SHORT_CONV_TOKENS_VAR, counts.convolved)
    if counts.admitted:
        _overwritten_counter(WINDOW_PAIRS_VAR, counts.admitted)
    with name_scope("head"):
        x = _norm(h, "final_norm", cfg)
        if cfg.tie_word_embeddings:
            # the embedding's own parameter: one state, and its gradient
            # the sum of the lookup's and the head's
            table = x.block.program.global_block().var("embed_tokens.w_0")
            logits = layers.matmul(x, table, transpose_y=True)
        else:
            logits = _linear(x, cfg.vocab_size, "lm_head", cfg)
    with name_scope("loss"):
        cost = layers.softmax_with_cross_entropy(
            logits, layers.unsqueeze(labels, axes=[2]))
        avg_cost = layers.mean(cost)
    return avg_cost, logits, ["input_ids", "labels"]
