"""Layers of a current decoder-only language model block (ops in
ops/decoder.py): RMS normalisation, rotary positions, the feed-forward's
activation (gated, or a squared ReLU), the top-k router, the expert layer
that is told which experts this chip holds, a learned sparse attention's
index, a Mamba-2 mixer's three ops: the short causal convolution,
the state-space scan and the gated group-wise RMS norm, a Mamba-1
mixer's selective scan, and the gated short convolution that mixes
tokens on its own."""
from __future__ import annotations

from ..initializer import Constant, Normal, Uniform
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = ["rms_norm", "rotary_embedding", "swiglu", "relu2", "moe_router",
           "moe_experts", "sparse_attention_index", "causal_conv1d",
           "gated_rms_norm", "mamba2_ssd", "selective_scan",
           "gated_short_conv"]


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None):
    """x / sqrt(mean(x^2, last axis) + epsilon) * w, w [D] from ones."""
    helper = LayerHelper("rms_norm", name=name)
    scale = helper.create_parameter(param_attr, [int(input.shape[-1])],
                                    input.dtype,
                                    default_initializer=Constant(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("rms_norm", inputs={"X": input, "Scale": scale},
                     outputs={"Y": out}, attrs={"epsilon": float(epsilon)})
    return out


YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "attention_factor")


def rotary_embedding(input, theta=10000.0, rotary_dim=None,
                     position_offset=0, interleaved=True, name=None,
                     yarn=None):
    """Rotary positions on input [B, S, H, D]: the trailing `rotary_dim`
    channels of every head (all of them by default), pair i rotated by
    pos * theta^(-2i / rotary_dim). The pairs are adjacent channels, or
    with `interleaved=False` channel i and channel i + rotary_dim / 2.
    yarn: a dict of `YARN_KEYS` (the `rope_parameters` of a "yarn"
    rotary): each frequency blended toward theta^(-2i/d) / factor over
    the ramp beta_fast .. beta_slow turns of the original context, cos
    and sin times the attention factor (ops/decoder.py `yarn_scale`)."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"theta": float(theta), "rotary_dim": int(rotary_dim or 0),
             "position_offset": int(position_offset),
             "interleaved": bool(interleaved)}
    if yarn is not None:
        attrs["yarn"] = [float(yarn[k]) for k in YARN_KEYS]
    helper.append_op("rotary_embedding", inputs={"X": input},
                     outputs={"Out": out}, attrs=attrs)
    return out


def swiglu(gate, up, name=None):
    """silu(gate) * up."""
    helper = LayerHelper("swiglu", name=name)
    out = helper.create_variable_for_type_inference(gate.dtype)
    helper.append_op("swiglu", inputs={"X": gate, "Y": up},
                     outputs={"Out": out})
    return out


def relu2(x, name=None):
    """max(x, 0)^2."""
    helper = LayerHelper("relu2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("relu2", inputs={"X": x}, outputs={"Out": out})
    return out


def moe_router(input, num_experts, top_k, experts_held=None, first_expert=0,
               scoring_func="sigmoid", norm_topk_prob=True,
               routed_scaling_factor=1.0, n_group=1, topk_group=1,
               param_attr=None, bias_attr=None, name=None,
               norm_epsilon=None):
    """Top-k router over `num_experts`, scoring by `scoring_func`
    ("sigmoid" or "softmax" over all experts; float32 under AMP).
    Returns (choice int32 [T, top_k], weight float32 [T, top_k], count
    of tokens for each of the `experts_held` experts from
    `first_expert`). `bias_attr` names the selection-only score
    correction, a parameter that takes no gradient (False: none).
    `norm_epsilon` is what `norm_topk_prob` adds to the chosen scores'
    sum (None: the op's own 1e-20)."""
    helper = LayerHelper("moe_router", name=name)
    d = int(input.shape[-1])
    weight = helper.create_parameter(param_attr, [num_experts, d],
                                     "float32",
                                     default_initializer=Normal(0.0, 0.02))
    inputs = {"X": input, "Weight": weight}
    if bias_attr is not False:
        attr = ParamAttr._to_attr(bias_attr)
        attr.trainable = False
        inputs["Bias"] = helper.create_parameter(
            attr, [num_experts], "float32", is_bias=True)
    choice = helper.create_variable_for_type_inference("int32", True)
    probs = helper.create_variable_for_type_inference("float32")
    counts = helper.create_variable_for_type_inference("int32", True)
    attrs = {"top_k": int(top_k), "scoring_func": scoring_func,
             "norm_topk_prob": bool(norm_topk_prob),
             "routed_scaling_factor": float(routed_scaling_factor),
             "n_group": int(n_group), "topk_group": int(topk_group),
             "experts_held": int(experts_held or num_experts),
             "first_expert": int(first_expert)}
    if norm_epsilon is not None:
        attrs["norm_epsilon"] = float(norm_epsilon)
    helper.append_op(
        "moe_router", inputs=inputs,
        outputs={"TopkIdx": choice, "TopkWeight": probs, "Counts": counts},
        attrs=attrs)
    return choice, probs, counts


def moe_experts(input, choice, weight, num_experts, expert_width,
                experts_held=None, first_expert=0, gate_attr=None,
                up_attr=None, down_attr=None, activation="swiglu",
                name=None):
    """The routed experts `first_expert .. first_expert + experts_held
    - 1` of a layer of `num_experts`, dropless. `activation` "swiglu":
    gated experts, down(silu(gate(x)) * up(x)), three stacked parameters
    [experts_held, D, F], [experts_held, D, F], [experts_held, F, D];
    "relu2": ungated experts, down(relu(up(x))^2), the last two alone. A
    choice of an expert held elsewhere adds nothing here. The op's
    `RowsWorked` output (int32 [2]: rows of the buffer the layer worked
    over, rows in use) is found through `<result>.op`."""
    if activation not in ("swiglu", "relu2"):
        raise ValueError(f"moe_experts activation {activation!r}")
    helper = LayerHelper("moe_experts", name=name)
    held = int(experts_held or num_experts)
    d, f = int(input.shape[-1]), int(expert_width)
    init = Normal(0.0, 0.02)
    inputs = {"X": input, "TopkIdx": choice, "TopkWeight": weight}
    out = helper.create_variable_for_type_inference(input.dtype)
    outputs = {"Out": out}
    attrs = {"num_experts": int(num_experts), "experts_held": held,
             "first_expert": int(first_expert)}
    if activation == "swiglu":
        inputs["WGate"] = helper.create_parameter(
            gate_attr, [held, d, f], "float32", default_initializer=init)
    else:
        attrs["activation"] = activation
    inputs["WUp"] = helper.create_parameter(
        up_attr, [held, d, f], "float32", default_initializer=init)
    inputs["WDown"] = helper.create_parameter(
        down_attr, [held, f, d], "float32", default_initializer=init)
    if activation == "swiglu":
        outputs["GateAct"] = helper.create_variable_for_type_inference(
            input.dtype, True)
    outputs["UpAct"] = helper.create_variable_for_type_inference(
        input.dtype, True)
    outputs["RowsWorked"] = helper.create_variable_for_type_inference(
        "int32", True)
    helper.append_op("moe_experts", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return out


def sparse_attention_index(index_q, index_k, index_w, top_k, scale=1.0,
                           name=None):
    """The keep mask of a learned sparse attention from its indexer's
    projections: index_q [B, S, heads, d], index_k [B, S, d], index_w
    [B, S, heads]. Query t keeps the `top_k` keys s <= t of largest
    sum_j scale * w[t, j] * relu(q[t, j] . k[s]) (all of them while
    t < top_k). Returns (mask int8 [B, 1, S, S] for `fused_attention`'s
    bias, pairs kept int32 [1]); neither takes a gradient."""
    helper = LayerHelper("sparse_attention_index", name=name)
    mask = helper.create_variable_for_type_inference("int8", True)
    kept = helper.create_variable_for_type_inference("int32", True)
    helper.append_op(
        "sparse_attention_index",
        inputs={"IndexQ": index_q, "IndexK": index_k, "IndexW": index_w},
        outputs={"Mask": mask, "Kept": kept},
        attrs={"top_k": int(top_k), "scale": float(scale)})
    return mask, kept


def causal_conv1d(input, kernel_size, act=None, param_attr=None,
                  bias_attr=None, name=None):
    """A depthwise convolution along the sequence of input [B, T, C]
    that looks back only: out[t] = act(b + sum_j w[:, j] * input[t -
    (kernel_size - 1) + j]), w [C, kernel_size], b [C] (`bias_attr`
    False: none); `act` None or "silu"."""
    helper = LayerHelper("causal_conv1d", name=name)
    c = int(input.shape[-1])
    inputs = {"X": input, "Weight": helper.create_parameter(
        param_attr, [c, int(kernel_size)], "float32",
        default_initializer=Normal(0.0, 0.02))}
    if bias_attr is not False:
        inputs["Bias"] = helper.create_parameter(
            ParamAttr._to_attr(bias_attr), [c], "float32", is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("causal_conv1d", inputs=inputs, outputs={"Out": out},
                     attrs={"activation": act or ""})
    return out


def gated_rms_norm(input, gate, groups=1, epsilon=1e-5, param_attr=None,
                   name=None):
    """RMSNorm(input * silu(gate)) * w, the mean square taken within
    each of `groups` equal groups of the last axis; w [C] from ones."""
    helper = LayerHelper("gated_rms_norm", name=name)
    scale = helper.create_parameter(param_attr, [int(input.shape[-1])],
                                    "float32",
                                    default_initializer=Constant(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("gated_rms_norm",
                     inputs={"X": input, "Gate": gate, "Scale": scale},
                     outputs={"Y": out},
                     attrs={"groups": int(groups),
                            "epsilon": float(epsilon)})
    return out


def mamba2_ssd(x, dt, b, c, chunk_size=128, dt_bias_attr=None,
               a_log_attr=None, d_attr=None, name=None):
    """Mamba-2's state-space scan over x [B, T, H, P] with step sizes
    dt [B, T, H] (before their bias and softplus) and the groups' b, c
    [B, T, G, N]; three parameters a head: the step's bias, log(-A) and
    the skip weight D. Returns (y [B, T, H, P], tokens scanned int32
    [1])."""
    helper = LayerHelper("mamba2_ssd", name=name)
    h = int(x.shape[2])

    def per_head(attr, value):
        return helper.create_parameter(attr, [h], "float32",
                                       default_initializer=Constant(value))

    inputs = {"X": x, "Dt": dt, "B": b, "C": c,
              "DtBias": per_head(dt_bias_attr, 0.0),
              "ALog": per_head(a_log_attr, 0.0),
              "D": per_head(d_attr, 1.0)}
    y = helper.create_variable_for_type_inference(x.dtype)
    states = helper.create_variable_for_type_inference("float32", True)
    tokens = helper.create_variable_for_type_inference("int32", True)
    helper.append_op("mamba2_ssd", inputs=inputs,
                     outputs={"Y": y, "States": states, "Tokens": tokens},
                     attrs={"chunk_size": int(chunk_size)})
    return y, tokens


def selective_scan(x, dt, b, c, state_size, dt_bias_attr=None,
                   a_log_attr=None, d_attr=None, name=None):
    """Mamba-1's selective scan over x [B, T, C] with step sizes dt
    [B, T, C] (before their bias and softplus) and b, c [B, T, N] shared
    by the channels; parameters the step's bias [C], log(-A) [C, N] and
    the skip weight D [C]. Returns (y [B, T, C], tokens scanned int32
    [1])."""
    helper = LayerHelper("selective_scan", name=name)
    ch, n = int(x.shape[-1]), int(state_size)

    def param(attr, shape, value):
        return helper.create_parameter(attr, shape, "float32",
                                       default_initializer=Constant(value))

    inputs = {"X": x, "Dt": dt, "B": b, "C": c,
              "DtBias": param(dt_bias_attr, [ch], 0.0),
              "ALog": param(a_log_attr, [ch, n], 0.0),
              "D": param(d_attr, [ch], 1.0)}
    y = helper.create_variable_for_type_inference(x.dtype)
    states = helper.create_variable_for_type_inference("float32", True)
    tokens = helper.create_variable_for_type_inference("int32", True)
    helper.append_op("selective_scan", inputs=inputs,
                     outputs={"Y": y, "States": states, "Tokens": tokens})
    return y, tokens


def gated_short_conv(input, kernel_size, param_attr=None, name=None):
    """The gated short convolution of input [B, T, 3 D] = [Bg | Cg | x]
    (an in-projection's output): Cg * conv(Bg * x), conv depthwise and
    causal over the last `kernel_size` tokens with w [D, kernel_size],
    no bias, no activation. Returns (out [B, T, D], tokens convolved
    int32 [1])."""
    helper = LayerHelper("gated_short_conv", name=name)
    d, k = int(input.shape[-1]) // 3, int(kernel_size)
    weight = helper.create_parameter(
        param_attr, [d, k], "float32",
        default_initializer=Uniform(-k ** -0.5, k ** -0.5))
    out = helper.create_variable_for_type_inference(input.dtype)
    tokens = helper.create_variable_for_type_inference("int32", True)
    helper.append_op("gated_short_conv",
                     inputs={"X": input, "Weight": weight},
                     outputs={"Out": out, "Tokens": tokens})
    return out, tokens
