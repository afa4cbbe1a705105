"""Automatic mixed precision state (TPU-native bf16-first).

Parity: reference contrib/mixed_precision (decorator.py:27
OptimizerWithMixedPrecison — fp16 compute + fp32 master weights + loss
scaling; white/black op lists in fp16_lists.py). TPU-first differences:
bf16 shares fp32's exponent range, so no loss scaling is needed and
master weights are simply the fp32 params the engine already holds.

Precision policy (applied centrally by ExecContext, core/registry.py —
the trace-time analog of the reference's cast-insertion pass,
contrib/mixed_precision/fp16_utils.py:103 find_true_prev_op/insert_cast):

* WHITE (MXU ops: matmul/conv family): f32 float inputs are cast to the
  amp dtype at read time. Because lowerings derive their result dtype
  from their (already-cast) inputs, outputs STAY in the amp dtype — the
  activation stream between MXU ops travels through HBM at 2 bytes, not
  4. Accumulation still happens in f32 via preferred_element_type.
* GRAY (elementwise/activation/shape ops): follow their inputs — if any
  float input is already the amp dtype, remaining f32 float inputs are
  cast down so type promotion cannot silently re-widen the chain (a
  single f32 bias would otherwise upcast every downstream tensor).
  Pure-f32 gray ops (e.g. LR arithmetic in the optimizer section) are
  untouched.
* BLACK (loss/softmax reductions): reduced-precision float inputs are
  cast UP to f32. The cast fuses into the consuming reduction, so this
  costs registers, not HBM.
* NORM ops (layer_norm/rms_norm/batch_norm/group_norm/data_norm) opt out of
  input casting entirely: their lowerings read bf16 activations, compute
  statistics in f32 internally (see ops/nn.py), emit Y in the input's
  dtype, and keep f32 running-stat persistables f32 — context casting
  would corrupt the stat state dtype.
* OUT_CAST (lookup_table): inputs untouched (casting a vocab-sized
  embedding table would materialize a full-table copy); the gathered
  rows are cast to the amp dtype on output.

Everything else sees values exactly as the env holds them.
"""
from __future__ import annotations

import contextlib
import threading

import jax.numpy as jnp

_state = threading.local()

WHITE_OPS = frozenset({
    "matmul", "mul", "conv2d", "depthwise_conv2d", "conv2d_transpose",
    "conv3d", "fused_attention",
})

GRAY_OPS = frozenset({
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "sum",
    "relu", "relu6", "gelu", "tanh", "sigmoid", "leaky_relu", "elu",
    "swish", "softplus", "softsign", "brelu", "soft_relu",
    "hard_sigmoid", "selu", "stanh", "logsigmoid", "sqrt", "rsqrt",
    "abs", "pow", "scale", "clip", "dropout",
    "pool2d", "pad", "pad2d", "concat", "split", "stack", "slice",
    "reshape2", "reshape", "transpose2", "transpose", "squeeze2",
    "squeeze", "unsqueeze2", "unsqueeze", "expand", "flatten2",
    "flatten", "add_position_encoding", "rotary_embedding", "swiglu",
    "relu2",
})

# numerically sensitive: always f32 compute (extended per-config via the
# decorator's AutoMixedPrecisionLists.black_list).
# label_smoothed_softmax_xent is NOT here although it is loss math: its
# lowering upcasts internally per consumer fusion — a context-level black
# cast would materialize a multi-consumer f32 [B,S,vocab] convert of the
# logits (measured 1.6 GB/step on transformer-base), whereas the internal
# casts fuse into each reduction.
BLACK_OPS = frozenset({
    "softmax", "log_softmax", "softmax_with_cross_entropy",
    "cross_entropy", "cross_entropy2",
    "sigmoid_cross_entropy_with_logits",
    "mean", "reduce_mean", "reduce_sum", "exp", "log", "square",
    "cos_sim",
    # the router's logits are float32 in the published modelling code: in
    # bf16 near-tied scores flip the top-k choice
    "moe_router",
})

NORM_OPS = frozenset({
    "layer_norm", "batch_norm", "group_norm", "data_norm", "rms_norm",
})

OUT_CAST_OPS = frozenset({"lookup_table", "lookup_table_v2"})

_REDUCED = (jnp.bfloat16, jnp.float16)


def _st():
    if not hasattr(_state, "cfg"):
        _state.cfg = {"enabled": False, "dtype": jnp.bfloat16,
                      "black": frozenset(), "white": frozenset()}
    return _state.cfg


def amp_enabled() -> bool:
    return _st()["enabled"]


def amp_dtype():
    return _st()["dtype"]


def amp_black_ops():
    return _st()["black"]


@contextlib.contextmanager
def amp_guard(enabled=True, dtype=jnp.bfloat16, black_ops=(),
              white_ops=()):
    old = dict(_st())
    _st().update(enabled=enabled, dtype=dtype,
                 black=frozenset(black_ops),
                 white=frozenset(white_ops))
    try:
        yield
    finally:
        _st().update(old)


def op_mode(op_type: str):
    """Policy mode for an op type under the active amp config, or None
    when amp is off / the op is unlisted. Explicit user lists (from the
    decorator's AutoMixedPrecisionLists) override the defaults."""
    cfg = _st()
    if not cfg["enabled"]:
        return None
    if op_type in cfg["white"] and op_type not in cfg["black"]:
        return "white"
    if op_type in cfg["black"] or op_type in BLACK_OPS:
        return "black"
    if op_type in NORM_OPS:
        return "norm"
    if op_type in WHITE_OPS:
        return "white"
    if op_type in OUT_CAST_OPS:
        return "out_cast"
    if op_type in GRAY_OPS:
        return "gray"
    return None


def cast_in(mode, value, follow: bool):
    """Apply the input-side policy to one value. `follow` = some float
    input of this op already carries the amp dtype (gray activation)."""
    dt = getattr(value, "dtype", None)
    if dt is None:
        return value
    cfg = _st()
    if mode == "white":
        if dt == jnp.float32:
            return value.astype(cfg["dtype"])
    elif mode == "gray":
        if follow and dt == jnp.float32:
            return value.astype(cfg["dtype"])
    elif mode == "black":
        if dt in _REDUCED:
            return value.astype(jnp.float32)
    return value


def cast_out(mode, value):
    dt = getattr(value, "dtype", None)
    if mode == "out_cast" and dt == jnp.float32:
        return value.astype(_st()["dtype"])
    return value


def amp_cast(op_type, *vals):
    """Cast fp32 operands of an MXU op to the amp dtype (no-op when amp
    is off or the op is black-listed). Kept for lowerings that cast
    explicitly (e.g. inside fused kernels); idempotent with the
    ExecContext-level white cast."""
    cfg = _st()
    if not cfg["enabled"] or op_type in cfg["black"]:
        return vals
    dt = cfg["dtype"]
    out = []
    for v in vals:
        if v is not None and jnp.result_type(v) == jnp.float32:
            v = v.astype(dt)
        out.append(v)
    return tuple(out)
