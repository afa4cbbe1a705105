"""Parametrized check_grad sweep over the grad-registered op population
(VERDICT r3 #7; reference pattern: ~400 per-op unittests each calling
check_grad, python/paddle/fluid/tests/unittests/op_test.py:532).

Every op in GRAD.spec whose gradient is registered is accounted for:
* RECIPES  — built as a one-op program and checked numeric-vs-analytic
             right here (central-difference vs append_backward);
* COVERED  — ops whose grads need structured inputs (LoD, anchors,
             RNN state, ...) and already have a dedicated check_grad /
             parity test; the entry names it;
* SKIP     — genuinely not numerically checkable, with the reason
             (integer/zero gradients by definition, eager-only hosts,
             stochastic forwards, ...).

A completeness assertion fails the suite when a new grad op lands
without being classified, which is the sweep's real job: gradient
coverage can no longer drift silently.
"""
import os

import numpy as np
import pytest

from op_test import OpTest

_HERE = os.path.dirname(os.path.abspath(__file__))


def _grad_ops():
    ops = []
    with open(os.path.join(_HERE, "..", "GRAD.spec")) as f:
        for line in f:
            parts = line.split()
            if len(parts) >= 2 and parts[1] != "no_grad":
                ops.append(parts[0])
    return ops


def _rng(seed=0):
    return np.random.default_rng(seed)


def _f(shape, lo=-1.0, hi=1.0, seed=0):
    return (_rng(seed).uniform(lo, hi, shape)).astype(np.float32)


def _pos(shape, seed=0):
    return (_rng(seed).uniform(0.3, 1.7, shape)).astype(np.float32)


def _away_from(x, pts, eps=0.05):
    """Nudge entries within eps of any non-smooth point."""
    for p in pts:
        x = np.where(np.abs(x - p) < eps, x + 2 * eps, x)
    return x.astype(np.float32)


def _unary(data=None, attrs=None, out="Out", tol=0.01):
    return {"inputs": {"X": _f((2, 6)) if data is None else data},
            "attrs": attrs or {}, "out": out, "check": ["x"],
            "tol": tol}


def _binary(x=None, y=None, attrs=None, tol=0.01):
    return {"inputs": {"X": _f((2, 6)) if x is None else x,
                       "Y": _f((2, 6), seed=1) if y is None else y},
            "attrs": attrs or {}, "out": "Out", "check": ["x", "y"],
            "tol": tol}


_smooth = _away_from(_f((2, 6)), [0.0])
_img = _f((2, 3, 6, 6), seed=2)
_lbl2 = _rng(3).integers(0, 4, (3, 1)).astype(np.int64)

RECIPES = {
    # ---- smooth unary activations / math --------------------------------
    "abs": _unary(_smooth),
    "acos": _unary(_f((2, 6), -0.8, 0.8)),
    "asin": _unary(_f((2, 6), -0.8, 0.8)),
    "atan": _unary(),
    "brelu": _unary(_away_from(_f((2, 6), -4, 4), [-1.0, 1.0]),
                    {"t_min": -1.0, "t_max": 1.0}),
    "clip": _unary(_away_from(_f((2, 6)), [-0.5, 0.5]),
                   {"min": -0.5, "max": 0.5}),
    "cos": _unary(),
    "cumsum": _unary(),
    "elu": _unary(_smooth),
    "exp": _unary(),
    "gelu": _unary(),
    "hard_shrink": _unary(_away_from(_f((2, 6), -3, 3), [-0.5, 0.5]),
                          {"threshold": 0.5}),
    "hard_sigmoid": _unary(_away_from(_f((2, 6)), [-3.0, 3.0]),
                           {"slope": 0.2, "offset": 0.5}),
    "leaky_relu": _unary(_smooth, {"alpha": 0.1}),
    "log": _unary(_pos((2, 6))),
    "logsigmoid": _unary(),
    "reciprocal": _unary(_pos((2, 6))),
    "relu": _unary(_smooth),
    "relu6": _unary(_away_from(_f((2, 6), -2, 8), [0.0, 6.0])),
    "rsqrt": _unary(_pos((2, 6))),
    "scale": _unary(attrs={"scale": 2.5, "bias": 0.3}),
    "selu": _unary(_smooth),
    "sigmoid": _unary(),
    "sin": _unary(),
    "soft_relu": _unary(attrs={"threshold": 40.0}),
    "softplus": _unary(),
    "softshrink": _unary(_away_from(_f((2, 6), -3, 3), [-0.5, 0.5]),
                         {"lambda": 0.5}),
    "softsign": _unary(),
    "sqrt": _unary(_pos((2, 6))),
    "square": _unary(),
    "stanh": _unary(),
    "swish": _unary(attrs={"beta": 1.0}),
    "tanh": _unary(),
    "tanh_shrink": _unary(),
    "thresholded_relu": _unary(_away_from(_f((2, 6), -2, 2), [1.0]),
                               {"threshold": 1.0}),
    "pow": _unary(_pos((2, 6)), {"factor": 2.3}),
    "mean": _unary(out="Out"),
    "l1_norm": _unary(_smooth, out="Out"),
    "squared_l2_norm": _unary(out="Out"),
    "frobenius_norm": _unary(_pos((2, 6)), {"dim": [0, 1],
                                            "keep_dim": False},
                             out="Out"),
    "log_softmax": _unary(),
    "softmax": _unary(),
    "sequence_softmax": {
        "inputs": {"X": (_f((6, 1)), [[0, 2, 6]])},
        "attrs": {}, "out": "Out", "check": ["x"], "tol": 0.01},
    # ---- shape / movement ----------------------------------------------
    "cast": _unary(attrs={"in_dtype": 9, "out_dtype": 9}),  # DT_FLOAT32
    "assign": _unary(),
    "flatten": _unary(_f((2, 3, 4)), {"axis": 1}),
    "flatten2": _unary(_f((2, 3, 4)), {"axis": 1}),
    "reshape": _unary(_f((2, 6)), {"shape": [3, 4]}),
    "reshape2": _unary(_f((2, 6)), {"shape": [3, 4]}),
    "squeeze": _unary(_f((2, 1, 6)), {"axes": [1]}),
    "squeeze2": _unary(_f((2, 1, 6)), {"axes": [1]}),
    "unsqueeze": _unary(_f((2, 6)), {"axes": [1]}),
    "unsqueeze2": _unary(_f((2, 6)), {"axes": [1]}),
    "transpose": _unary(_f((2, 3, 4)), {"axis": [2, 0, 1]}),
    "transpose2": _unary(_f((2, 3, 4)), {"axis": [2, 0, 1]}),
    "expand": _unary(_f((2, 3)), {"expand_times": [2, 2]}),
    "slice": {"inputs": {"Input": _f((4, 6))},
              "attrs": {"axes": [0, 1], "starts": [1, 2],
                        "ends": [3, 5]},
              "out": "Out", "check": ["input"], "tol": 0.01},
    "strided_slice": {"inputs": {"Input": _f((6, 6))},
                      "attrs": {"axes": [0], "starts": [1],
                                "ends": [6], "strides": [2]},
                      "out": "Out", "check": ["input"], "tol": 0.01},
    "reverse": _unary(_f((3, 4)), {"axis": [0]}),
    "crop": _unary(_f((4, 6)), {"offsets": [1, 2], "shape": [2, 3]}),
    "pad": _unary(_f((2, 3)), {"paddings": [1, 1, 0, 2],
                               "pad_value": 0.0}),
    "pad2d": _unary(_img, {"paddings": [1, 1, 2, 0],
                           "mode": "constant", "pad_value": 0.0}),
    "pad_constant_like": {
        # X is the shape reference (no_grad slot); only Y flows grads
        "inputs": {"X": _f((4, 6)), "Y": _f((2, 3), seed=1)},
        "attrs": {"pad_value": 0.0}, "out": "Out", "check": ["y"],
        "tol": 0.01},
    "space_to_depth": _unary(_f((2, 3, 4, 4)), {"blocksize": 2}),
    "pixel_shuffle": _unary(_f((2, 8, 3, 3)), {"upscale_factor": 2}),
    "shuffle_channel": _unary(_img, {"group": 3}),
    "temporal_shift": _unary(_f((4, 4, 3, 3)),
                             {"seg_num": 2, "shift_ratio": 0.25}),
    "im2sequence": _unary(_img, {"kernels": [2, 2], "strides": [1, 1],
                                 "paddings": [0, 0, 0, 0]}),
    "unfold": _unary(_img, {"kernel_sizes": [2, 2], "strides": [1, 1],
                            "paddings": [0, 0, 0, 0],
                            "dilations": [1, 1]}, out="Y"),
    # ---- reductions ------------------------------------------------------
    "reduce_sum": _unary(attrs={"dim": [1], "keep_dim": False}),
    "reduce_mean": _unary(attrs={"dim": [1], "keep_dim": False}),
    "reduce_prod": _unary(_pos((2, 4)), {"dim": [1],
                                         "keep_dim": False}),
    "reduce_max": {
        # ties break the subgradient: use distinct values
        "inputs": {"X": np.arange(8, dtype=np.float32).reshape(2, 4)
                   * 0.37 + 0.1},
        "attrs": {"dim": [1], "keep_dim": False}, "out": "Out",
        "check": ["x"], "tol": 0.01},
    "reduce_min": {
        "inputs": {"X": np.arange(8, dtype=np.float32).reshape(2, 4)
                   * -0.29 + 3.0},
        "attrs": {"dim": [1], "keep_dim": False}, "out": "Out",
        "check": ["x"], "tol": 0.01},
    # ---- binary / n-ary --------------------------------------------------
    "elementwise_add": _binary(),
    "elementwise_sub": _binary(),
    "elementwise_mul": _binary(),
    "elementwise_div": _binary(y=_pos((2, 6), seed=1)),
    "elementwise_max": _binary(x=_f((2, 6)),
                               y=_f((2, 6), seed=1) + 0.11),
    "elementwise_min": _binary(x=_f((2, 6)),
                               y=_f((2, 6), seed=1) + 0.11),
    "elementwise_pow": _binary(x=_pos((2, 6)), y=_pos((2, 6), seed=1)),
    "minus": _binary(),
    "matmul": _binary(x=_f((2, 4)), y=_f((4, 3), seed=1)),
    "mul": _binary(x=_f((2, 4)), y=_f((4, 3), seed=1)),
    "cos_sim": _binary(x=_f((3, 5)), y=_f((3, 5), seed=1)),
    "sum": {"inputs": {"X": [("sum_a", _f((2, 3))),
                             ("sum_b", _f((2, 3), seed=1))]},
            "attrs": {}, "out": "Out", "check": ["sum_a", "sum_b"],
            "tol": 0.01},
    "concat": {"inputs": {"X": [("cc_a", _f((2, 3))),
                                ("cc_b", _f((2, 4), seed=1))]},
               "attrs": {"axis": 1}, "out": "Out",
               "check": ["cc_a", "cc_b"], "tol": 0.01},
    "stack": {"inputs": {"X": [("st_a", _f((2, 3))),
                               ("st_b", _f((2, 3), seed=1))]},
              "attrs": {"axis": 0}, "out": "Y",
              "check": ["st_a", "st_b"], "tol": 0.01},
    "unstack": {"inputs": {"X": _f((2, 3))},
                "attrs": {"axis": 0, "num": 2}, "out": "Y",
                "out_names": [("uns_a", np.zeros((1,), np.float32)),
                              ("uns_b", np.zeros((1,), np.float32))],
                "check": ["x"], "tol": 0.01},
    "multiplex": {
        "inputs": {"Ids": np.array([[0], [1], [0]], np.int32),
                   "X": [("mx_a", _f((3, 4))),
                         ("mx_b", _f((3, 4), seed=1))]},
        "attrs": {}, "out": "Out", "check": ["mx_a", "mx_b"],
        "tol": 0.01},
    "bilinear_tensor_product": {
        "inputs": {"X": _f((3, 4)), "Y": _f((3, 5), seed=1),
                   "Weight": _f((2, 4, 5), seed=2)},
        "attrs": {}, "out": "Out", "check": ["x", "y", "weight"],
        "tol": 0.02},
    "conv_shift": _binary(x=_f((3, 8)), y=_f((3, 3), seed=1)),
    "fsp": {"inputs": {"X": _f((2, 3, 4, 4)),
                       "Y": _f((2, 2, 4, 4), seed=1)},
            "attrs": {}, "out": "Out", "check": ["x", "y"],
            "tol": 0.02},
    # ---- losses ----------------------------------------------------------
    "cross_entropy": {
        "inputs": {"X": (_pos((3, 4)) /
                         _pos((3, 4)).sum(1, keepdims=True)),
                   "Label": _lbl2},
        "attrs": {"soft_label": False}, "out": "Y", "check": ["x"],
        "tol": 0.02},
    "cross_entropy2": {
        "inputs": {"X": (_pos((3, 4)) /
                         _pos((3, 4)).sum(1, keepdims=True)),
                   "Label": _lbl2},
        "attrs": {}, "out": "Y", "check": ["x"], "tol": 0.02},
    "softmax_with_cross_entropy": {
        "inputs": {"Logits": _f((3, 4)), "Label": _lbl2},
        "attrs": {"soft_label": False}, "out": "Loss",
        "check": ["logits"], "tol": 0.01},
    "label_smoothed_softmax_xent": {
        "inputs": {"Logits": _f((3, 4)),
                   "Label": _lbl2.reshape(3)},
        "attrs": {"epsilon": 0.1}, "out": "Loss",
        "check": ["logits"], "tol": 0.01},
    "sigmoid_cross_entropy_with_logits": {
        "inputs": {"X": _f((3, 4)),
                   "Label": _rng(4).integers(0, 2, (3, 4))
                   .astype(np.float32)},
        "attrs": {}, "out": "Out", "check": ["x"], "tol": 0.01},
    "bpr_loss": {
        "inputs": {"X": _f((3, 4)), "Label": _lbl2},
        "attrs": {}, "out": "Y", "check": ["x"], "tol": 0.02},
    "log_loss": {
        "inputs": {"Predicted": _f((4, 1), 0.1, 0.9),
                   "Labels": _rng(5).integers(0, 2, (4, 1))
                   .astype(np.float32)},
        "attrs": {"epsilon": 1e-4}, "out": "Loss",
        "check": ["predicted"], "tol": 0.02},
    "huber_loss": {
        "inputs": {"X": _f((4, 1)), "Y": _f((4, 1), seed=1)},
        "attrs": {"delta": 0.5}, "out": "Out", "check": ["x"],
        "tol": 0.02},
    "hinge_loss": {
        "inputs": {"Logits": _f((4, 1)) + 0.05,
                   "Labels": _rng(6).integers(0, 2, (4, 1))
                   .astype(np.float32)},
        "attrs": {}, "out": "Loss", "check": ["logits"], "tol": 0.02},
    "rank_loss": {
        "inputs": {"Label": _rng(7).integers(0, 2, (4, 1))
                   .astype(np.float32),
                   "Left": _f((4, 1)), "Right": _f((4, 1), seed=1)},
        "attrs": {}, "out": "Out", "check": ["left", "right"],
        "tol": 0.02},
    "margin_rank_loss": {
        "inputs": {"Label": (_rng(8).integers(0, 2, (4, 1)) * 2 - 1)
                   .astype(np.float32),
                   "X1": _f((4, 1)), "X2": _f((4, 1), seed=1)},
        "attrs": {"margin": 0.1}, "out": "Out", "check": ["x1", "x2"],
        "tol": 0.05},
    "modified_huber_loss": {
        "inputs": {"X": _f((4, 1), -0.8, 0.8),
                   "Y": _rng(9).integers(0, 2, (4, 1))
                   .astype(np.float32)},
        "attrs": {}, "out": "Out", "check": ["x"], "tol": 0.05},
    "smooth_l1_loss": {
        "inputs": {"X": _f((3, 4)), "Y": _f((3, 4), seed=1)},
        "attrs": {"sigma": 1.0}, "out": "Out", "check": ["x"],
        "tol": 0.02},
    "kldiv_loss": {
        "inputs": {"X": _f((3, 4), 0.1, 1.0),
                   "Target": _pos((3, 4), seed=1)},
        "attrs": {"reduction": "mean"}, "out": "Loss",
        "check": ["x"], "tol": 0.02},
    "squared_l2_distance": {
        "inputs": {"X": _f((3, 4)), "Y": _f((3, 4), seed=1)},
        "attrs": {}, "out": "Out", "check": ["x"], "tol": 0.02},
    "teacher_student_sigmoid_loss": {
        "inputs": {"X": _f((4, 1)),
                   "Label": _f((4, 1), 0.1, 0.9, seed=1)},
        "attrs": {}, "out": "Y", "check": ["x"], "tol": 0.05},
    "sigmoid_focal_loss": {
        "inputs": {"X": _f((3, 4)),
                   "Label": _rng(10).integers(0, 4, (3, 1))
                   .astype(np.int64),
                   "FgNum": np.array([2], np.int32)},
        "attrs": {"gamma": 2.0, "alpha": 0.25}, "out": "Out",
        "check": ["x"], "tol": 0.05},
    "center_loss": {
        "inputs": {"X": _f((3, 4)),
                   "Label": _rng(11).integers(0, 3, (3, 1))
                   .astype(np.int64),
                   "Centers": _f((5, 4), seed=1),
                   "CenterUpdateRate": np.array([0.1], np.float32)},
        "attrs": {"cluster_num": 5, "need_update": False},
        "out": "Loss", "check": ["x"], "tol": 0.05},
    "cvm": {
        "inputs": {"X": _pos((3, 6)),
                   "CVM": _pos((3, 2), seed=1)},
        "attrs": {"use_cvm": True}, "out": "Y", "check": ["x"],
        "tol": 0.05},
    # ---- normalization ---------------------------------------------------
    "layer_norm": {
        "inputs": {"X": _f((3, 6)), "Scale": _pos((6,), seed=1),
                   "Bias": _f((6,), seed=2)},
        "attrs": {"begin_norm_axis": 1, "epsilon": 1e-5}, "out": "Y",
        "check": ["x", "scale", "bias"], "tol": 0.02},
    "rms_norm": {
        "inputs": {"X": _f((3, 6)), "Scale": _pos((6,), seed=1)},
        "attrs": {"epsilon": 1e-6}, "out": "Y",
        "check": ["x", "scale"], "tol": 0.02},
    "rotary_embedding": {
        "inputs": {"X": _f((2, 3, 2, 8))},
        "attrs": {"theta": 100.0, "rotary_dim": 4, "position_offset": 0},
        "out": "Out", "check": ["x"], "tol": 0.01},
    "swiglu": _binary(),
    "batch_norm": {
        "inputs": {"X": _f((3, 4, 2, 2)), "Scale": _pos((4,), seed=1),
                   "Bias": _f((4,), seed=2),
                   "Mean": np.zeros(4, np.float32),
                   "Variance": np.ones(4, np.float32)},
        "attrs": {"is_test": False, "epsilon": 1e-5},
        "out": "Y", "check": ["x", "scale", "bias"], "tol": 0.03},
    "group_norm": {
        "inputs": {"X": _f((2, 4, 3, 3)), "Scale": _pos((4,), seed=1),
                   "Bias": _f((4,), seed=2)},
        "attrs": {"groups": 2, "epsilon": 1e-5}, "out": "Y",
        "check": ["x", "scale", "bias"], "tol": 0.03},
    "instance_norm": {
        "inputs": {"X": _f((2, 3, 4, 4)), "Scale": _pos((3,), seed=1),
                   "Bias": _f((3,), seed=2)},
        "attrs": {"epsilon": 1e-5}, "out": "Y",
        "check": ["x", "scale", "bias"], "tol": 0.03},
    "data_norm": {
        "inputs": {"X": _f((3, 4)),
                   "BatchSize": np.full((4,), 8.0, np.float32),
                   "BatchSum": _f((4,), seed=1),
                   "BatchSquareSum": _pos((4,), seed=2) + 4.0},
        "attrs": {}, "out": "Y", "check": ["x"], "tol": 0.03},
    "l2_normalize": _unary(_f((3, 4)) + 0.2, {"axis": 1,
                                              "epsilon": 1e-10}),
    "norm": _unary(_f((3, 4)) + 0.2, {"axis": 1, "epsilon": 1e-10}),
    "lrn": {"inputs": {"X": _f((2, 4, 3, 3))},
            "attrs": {"n": 2, "k": 1.0, "alpha": 1e-4, "beta": 0.75},
            "out": "Out", "check": ["x"], "tol": 0.03},
    "clip_by_norm": _unary(_f((3, 4)), {"max_norm": 0.7}),
    "spectral_norm": {
        "inputs": {"Weight": _f((4, 5)), "U": _f((4,), seed=1),
                   "V": _f((5,), seed=2)},
        "attrs": {"power_iters": 0, "dim": 0, "eps": 1e-12},
        "out": "Out", "check": ["weight"], "tol": 0.05},
    # ---- conv / pool family ---------------------------------------------
    "conv2d": {
        "inputs": {"Input": _f((2, 3, 5, 5)),
                   "Filter": _f((4, 3, 3, 3), seed=1)},
        "attrs": {"strides": [1, 1], "paddings": [1, 1],
                  "dilations": [1, 1], "groups": 1},
        "out": "Output", "check": ["input", "filter"], "tol": 0.03},
    "depthwise_conv2d": {
        "inputs": {"Input": _f((2, 3, 5, 5)),
                   "Filter": _f((3, 1, 3, 3), seed=1)},
        "attrs": {"strides": [1, 1], "paddings": [1, 1],
                  "dilations": [1, 1], "groups": 3},
        "out": "Output", "check": ["input", "filter"], "tol": 0.03},
    "conv2d_transpose": {
        "inputs": {"Input": _f((2, 3, 4, 4)),
                   "Filter": _f((3, 2, 3, 3), seed=1)},
        "attrs": {"strides": [1, 1], "paddings": [0, 0],
                  "dilations": [1, 1], "groups": 1},
        "out": "Output", "check": ["input", "filter"], "tol": 0.03},
    "depthwise_conv2d_transpose": {
        "inputs": {"Input": _f((2, 3, 4, 4)),
                   "Filter": _f((3, 1, 3, 3), seed=1)},
        "attrs": {"strides": [1, 1], "paddings": [0, 0],
                  "dilations": [1, 1], "groups": 3},
        "out": "Output", "check": ["input", "filter"], "tol": 0.03},
    "conv3d": {
        "inputs": {"Input": _f((1, 2, 4, 4, 4)),
                   "Filter": _f((3, 2, 2, 2, 2), seed=1)},
        "attrs": {"strides": [1, 1, 1], "paddings": [0, 0, 0],
                  "dilations": [1, 1, 1], "groups": 1},
        "out": "Output", "check": ["input", "filter"], "tol": 0.03},
    "conv3d_transpose": {
        "inputs": {"Input": _f((1, 2, 3, 3, 3)),
                   "Filter": _f((2, 2, 2, 2, 2), seed=1)},
        "attrs": {"strides": [1, 1, 1], "paddings": [0, 0, 0],
                  "dilations": [1, 1, 1], "groups": 1},
        "out": "Output", "check": ["input", "filter"], "tol": 0.03},
    "pool2d": {
        "inputs": {"X": _f((2, 2, 4, 4))},
        "attrs": {"pooling_type": "avg", "ksize": [2, 2],
                  "strides": [2, 2], "paddings": [0, 0]},
        "out": "Out", "check": ["x"], "tol": 0.02},
    "pool3d": {
        "inputs": {"X": _f((1, 2, 4, 4, 4))},
        "attrs": {"pooling_type": "avg", "ksize": [2, 2, 2],
                  "strides": [2, 2, 2], "paddings": [0, 0, 0]},
        "out": "Out", "check": ["x"], "tol": 0.02},
    "max_pool2d_with_index": {
        "inputs": {"X": _f((2, 2, 4, 4)) +
                   np.arange(64, dtype=np.float32).reshape(
                       2, 2, 4, 4) * 0.01},
        "attrs": {"ksize": [2, 2], "strides": [2, 2],
                  "paddings": [0, 0]},
        "out": "Out", "check": ["x"], "tol": 0.02},
    "max_pool3d_with_index": {
        "inputs": {"X": _f((1, 1, 4, 4, 4)) +
                   np.arange(64, dtype=np.float32).reshape(
                       1, 1, 4, 4, 4) * 0.01},
        "attrs": {"ksize": [2, 2, 2], "strides": [2, 2, 2],
                  "paddings": [0, 0, 0]},
        "out": "Out", "check": ["x"], "tol": 0.02},
    "maxout": _unary(_f((2, 4, 3, 3)) + np.arange(72, dtype=np.float32)
                     .reshape(2, 4, 3, 3) * 0.01, {"groups": 2}),
    "spp": {"inputs": {"X": _f((1, 2, 4, 4))},
            "attrs": {"pyramid_height": 2, "pooling_type": "avg"},
            "out": "Out", "check": ["x"], "tol": 0.03},
    "unpool": {
        "inputs": {"X": _f((1, 2, 2, 2)),
                   "Indices": np.array(
                       [[[[0, 3], [8, 11]], [[0, 3], [8, 11]]]],
                       np.int32)},
        "attrs": {"unpooling_type": "max", "ksize": [2, 2],
                  "strides": [2, 2], "paddings": [0, 0]},
        "out": "Out", "check": ["x"], "tol": 0.02},
    # ---- gather / scatter / indexing ------------------------------------
    "gather": {
        "inputs": {"X": _f((5, 3)),
                   "Index": np.array([0, 2, 4], np.int32)},
        "attrs": {}, "out": "Out", "check": ["x"], "tol": 0.01},
    "gather_nd": {
        "inputs": {"X": _f((3, 4)),
                   "Index": np.array([[0, 1], [2, 3]], np.int32)},
        "attrs": {}, "out": "Out", "check": ["x"], "tol": 0.01},
    "scatter": {
        "inputs": {"X": _f((5, 3)),
                   "Ids": np.array([1, 3], np.int32),
                   "Updates": _f((2, 3), seed=1)},
        "attrs": {"overwrite": True}, "out": "Out",
        "check": ["updates"], "tol": 0.01},
    "lookup_table": {
        "inputs": {"W": _f((6, 3)),
                   "Ids": _rng(12).integers(0, 6, (4, 1))
                   .astype(np.int64)},
        "attrs": {"is_sparse": False}, "out": "Out", "check": ["w"],
        "tol": 0.01},
    "top_k": {
        "inputs": {"X": np.arange(12, dtype=np.float32)
                   .reshape(3, 4) * 0.73 + 0.1},
        "attrs": {"k": 2}, "out": "Out", "check": ["x"], "tol": 0.01},
    "where_op_select": {
        "inputs": {"Condition": np.array([[True, False, True]] * 2),
                   "X": _f((2, 3)), "Y": _f((2, 3), seed=1)},
        "attrs": {}, "out": "Out", "check": ["x", "y"], "tol": 0.01},
    "label_smooth": {
        "inputs": {"X": _f((3, 4), 0.0, 1.0)},
        "attrs": {"epsilon": 0.1}, "out": "Out", "check": ["x"],
        "tol": 0.01},
    "affine_channel": {
        "inputs": {"X": _f((2, 3, 4, 4)), "Scale": _pos((3,), seed=1),
                   "Bias": _f((3,), seed=2)},
        "attrs": {"data_layout": "NCHW"}, "out": "Out",
        "check": ["x", "scale", "bias"], "tol": 0.02},
    "prelu": {
        "inputs": {"X": _smooth, "Alpha": _pos((1,), seed=1)},
        "attrs": {"mode": "all"}, "out": "Out",
        "check": ["x", "alpha"], "tol": 0.02},
    "bilinear_interp": {
        "inputs": {"X": _f((2, 2, 3, 3))},
        "attrs": {"out_h": 6, "out_w": 6, "align_corners": False,
                  "interp_method": "bilinear"},
        "out": "Out", "check": ["x"], "tol": 0.03},
    "nearest_interp": {
        "inputs": {"X": _f((2, 2, 3, 3))},
        "attrs": {"out_h": 6, "out_w": 6, "align_corners": False,
                  "interp_method": "nearest"},
        "out": "Out", "check": ["x"], "tol": 0.02},
    "grid_sampler": {
        "inputs": {"X": _f((1, 2, 4, 4)),
                   "Grid": _f((1, 3, 3, 2), -0.7, 0.7, seed=1)},
        "attrs": {}, "out": "Output", "check": ["x"], "tol": 0.05},
    "affine_grid": {
        "inputs": {"Theta": _f((1, 2, 3))},
        "attrs": {"output_shape": [1, 1, 3, 3]}, "out": "Output",
        "check": ["theta"], "tol": 0.03},
}


# Ops whose gradient IS exercised, but by a dedicated test that builds
# the structured inputs (LoD offsets, RNN state, anchors, ...) the
# generic one-op builder here cannot: entry -> where the coverage lives.
COVERED = {
    "causal_conv1d": "tests/test_decoder_lm.py (forward and the three gradients against the reference's convolution, with and without bias; causality)",
    "gated_short_conv": "tests/test_decoder_lm.py (hand-written grad: the output, dX over its three thirds and dWeight against jax.grad of the reference's function, lowered and through the two Pallas kernels across block boundaries; causality)",
    "gated_rms_norm": "tests/test_decoder_lm.py (forward and the three gradients against the reference's group norm, one group and four)",
    "mamba2_ssd": "tests/test_decoder_lm.py, tests/test_kernels.py (hand-written grad: y and all seven gradients against the token-by-token recurrence, lowered and through the Pallas kernels, a ragged tail, B > 1)",
    "relu2": "tests/test_decoder_lm.py (forward and gradient by hand)",
    "moe_experts": "tests/test_decoder_lm.py (forward and every gradient against the plain reference, lowered and through the grouped-matmul kernels; the share test; dropless under imbalance)",
    "moe_router": "tests/test_decoder_lm.py (choices, weights, counts and gradients against the plain reference; the top-k choice is piecewise constant, so central differences straddle its jumps)",
    "add_position_encoding": "tests/test_nlp_ops.py (position encoding parity incl. grad via transformer training)",
    "array_to_lod_tensor": "tests/test_rnn_control_flow.py (dynamic RNN beam pipeline differentiates through the array ops)",
    "attention_lstm": "tests/test_rnn_control_flow.py TestAttentionLSTM",
    "box_clip": "tests/test_detection_ops.py (detection grads)",
    "box_coder": "tests/test_detection_ops.py",
    "conv2d_fusion": "tests/test_conv_pool_ops.py (fused conv parity vs conv2d whose grad is swept here)",
    "conv2d_inception_fusion": "tests/test_conv_pool_ops.py TestInceptionFusion",
    "cudnn_lstm": "tests/test_rnn_control_flow.py (lstm family)",
    "deformable_conv": "tests/test_detection_ops.py TestDeformableConv",
    "deformable_psroi_pooling": "tests/test_detection_ops.py",
    "dense_lstm": "tests/test_rnn_control_flow.py",
    "dropout": "tests/test_loss_norm_ops.py TestDropout (mask determinism + scale; stochastic fwd excludes central differences)",
    "expand_to_rank_table_batch": "tests/test_rnn_control_flow.py (rank-table pipeline)",
    "fc": "composite of mul+elementwise_add, both swept here; tests/test_executor_mnist.py trains through it",
    "fused_attention": "tests/test_flash_attention_bwd.py (kernel vs composed grads, both layouts); tests/test_flash_attention_window.py (a sliding window: the banded kernels' dQ / dK / dV, fused and split, vs the composed band)",
    "fused_elemwise_activation": "tests/test_elementwise_ops.py (compositions swept individually)",
    "fused_embedding_fc_lstm": "tests/test_rnn_control_flow.py (lstm family)",
    "fused_embedding_seq_pool": "tests/test_sequence_ops.py (embedding+pool composition)",
    "fusion_gru": "tests/test_rnn_control_flow.py TestGRU (same math as gru, swept there)",
    "fusion_lstm": "tests/test_rnn_control_flow.py TestLSTM",
    "fusion_repeated_fc_relu": "composition of mul/relu swept here",
    "fusion_seqconv_eltadd_relu": "tests/test_sequence_ops.py (sequence_conv grad)",
    "fusion_seqexpand_concat_fc": "tests/test_sequence_ops.py",
    "fusion_seqpool_concat": "tests/test_sequence_ops.py (sequence_pool grad)",
    "fusion_seqpool_cvm_concat": "tests/test_sequence_ops.py",
    "fusion_squared_mat_sub": "tests/test_matmul_ops.py (matmul/square swept here)",
    "fusion_transpose_flatten_concat": "transpose/flatten/concat all swept here",
    "gru": "tests/test_rnn_control_flow.py TestGRU",
    "gru_unit": "tests/test_rnn_control_flow.py",
    "hierarchical_sigmoid": "tests/test_nlp_ops.py TestHSigmoid (grad check)",
    "linear_chain_crf": "tests/test_nlp_ops.py TestLinearChainCRF (grad vs brute-force likelihood)",
    "lod_tensor_to_array": "tests/test_rnn_control_flow.py",
    "lookup_sparse_table": "tests/test_selected_rows.py (sparse grad path)",
    "lstm": "tests/test_rnn_control_flow.py TestLSTM",
    "lstm_unit": "tests/test_rnn_control_flow.py",
    "lstmp": "tests/test_rnn_control_flow.py TestLSTMP",
    "merge_lod_tensor": "tests/test_rnn_control_flow.py (switch/merge pipeline)",
    "nce": "tests/test_nlp_ops.py TestNCE (stochastic sampling fwd; grad vs full-softmax reference)",
    "psroi_pool": "tests/test_detection_ops.py",
    "py_func": "tests/test_eager_islands.py (host op; backward runs the registered python backward)",
    "read_from_array": "tests/test_rnn_control_flow.py",
    "recurrent": "tests/test_rnn_control_flow.py TestRecurrent (vjp through lax.scan)",
    "reorder_lod_tensor_by_rank": "tests/test_rnn_control_flow.py",
    "roi_align": "tests/test_detection_ops.py",
    "roi_perspective_transform": "tests/test_detection_ops.py",
    "roi_pool": "tests/test_detection_ops.py",
    "row_conv": "tests/test_sequence_ops.py (LoD input)",
    "sample_logits": "tests/test_nlp_ops.py (stochastic sampling forward)",
    "sequence_concat": "tests/test_sequence_ops.py",
    "sequence_conv": "tests/test_sequence_ops.py",
    "sequence_expand": "tests/test_sequence_ops.py",
    "sequence_expand_as": "tests/test_sequence_ops.py",
    "sequence_pad": "tests/test_sequence_ops.py",
    "sequence_pool": "tests/test_sequence_ops.py",
    "sequence_reshape": "tests/test_sequence_ops.py",
    "sequence_reverse": "tests/test_sequence_ops.py",
    "sequence_scatter": "tests/test_sequence_ops.py",
    "sequence_slice": "tests/test_sequence_ops.py",
    "sequence_unpad": "tests/test_sequence_ops.py",
    "shrink_rnn_memory": "tests/test_rnn_control_flow.py",
    "similarity_focus": "tests/test_misc_ops.py",
    "split": "tests/test_reduce_shape_ops.py TestSplit (multi-output slot binding)",
    "split_lod_tensor": "tests/test_rnn_control_flow.py",
    "sync_batch_norm": "alias of batch_norm under SPMD (tests/test_parallel_sharding.py); batch_norm swept here",
    "tree_conv": "tests/test_misc_ops.py",
    "warpctc": "tests/test_nlp_ops.py TestWarpCTC (grad vs brute-force alignment sum)",
    "yolov3_loss": "tests/test_detection_ops.py",
}

# Genuinely not numeric-checkable, with the reason.
SKIP = {
    "ceil": "piecewise-constant: analytic grad is 0 everywhere, numeric diff is 0 a.e. — nothing to compare",
    "floor": "piecewise-constant (grad identically 0)",
    "round": "piecewise-constant (grad identically 0)",
    "sign": "piecewise-constant (grad identically 0)",
    "elementwise_floordiv": "integer-valued output; grad identically 0",
    "elementwise_mod": "grad wrt divisor is 0/undefined at wraps; x-grad covered by elementwise_sub sweep",
    "fake_channel_wise_dequantize_max_abs": "straight-through estimator: grad is defined as identity, not the true derivative of the quantized fwd (tests/test_quantization.py)",
    "fake_channel_wise_quantize_abs_max": "straight-through estimator (tests/test_quantization.py)",
    "fake_dequantize_max_abs": "straight-through estimator (tests/test_quantization.py)",
    "fake_quantize_abs_max": "straight-through estimator (tests/test_quantization.py)",
    "fake_quantize_dequantize_abs_max": "straight-through estimator (tests/test_quantization.py)",
    "fake_quantize_dequantize_moving_average_abs_max": "straight-through estimator (tests/test_quantization.py)",
    "fake_quantize_moving_average_abs_max": "straight-through estimator (tests/test_quantization.py)",
    "fake_quantize_range_abs_max": "straight-through estimator (tests/test_quantization.py)",
    "moving_average_abs_max_scale": "stat-tracking identity; straight-through (tests/test_quantization.py)",
}


_ALL = _grad_ops()


def test_every_grad_op_is_classified():
    """The sweep's contract: nothing in GRAD.spec escapes accounting."""
    classified = set(RECIPES) | set(COVERED) | set(SKIP)
    missing = [op for op in _ALL if op not in classified]
    stale = sorted(classified - set(_ALL))
    assert not missing, f"unclassified grad ops: {missing}"
    assert not stale, f"stale sweep entries: {stale}"


class _Case(OpTest):
    def runTest(self):  # pragma: no cover - parametrization shim
        pass


@pytest.mark.parametrize("op", sorted(RECIPES))
def test_numeric_vs_analytic(op):
    r = RECIPES[op]
    case = _Case()
    case.op_type = op
    case.inputs = r["inputs"]
    out_slot = r["out"]
    if "out_names" in r:
        case.outputs = {out_slot: r["out_names"]}
        out_names = [n for n, _ in r["out_names"]]
    else:
        case.outputs = {out_slot: np.zeros((1,), np.float32)}
        out_names = out_slot.lower() + "_out"
    case.attrs = r["attrs"]
    case.check_grad(r["check"], out_names,
                    max_relative_error=r["tol"])
