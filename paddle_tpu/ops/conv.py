"""Convolution / pooling ops — MXU-bound via lax.conv_general_dilated.

Parity: reference conv_op.cc (+ conv_cudnn), conv_transpose_op.cc,
pool_op.cc, depthwise conv (operators/conv_op.h, math/im2col) — here a
single XLA convolution covers the cuDNN/GEMM/depthwise triplet; XLA picks
the MXU tiling. Layout is NCHW to match the reference's default; XLA
re-lays-out internally for TPU.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op
from ..core.amp import amp_cast


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v] * n


def _conv_nd(ctx, nd, depthwise=False):
    x = ctx.input("Input")
    w = ctx.input("Filter")
    strides = _pair(ctx.attr("strides", [1] * nd), nd)
    paddings = _pair(ctx.attr("paddings", [0] * nd), nd)
    dilations = _pair(ctx.attr("dilations", [1] * nd), nd)
    groups = ctx.attr("groups", 1) or 1
    # "NHWC"/"NDHWC" puts channels last (TPU-friendly at small channel
    # counts — measured 1.5x on ResNet's early stages, July 2026);
    # the FILTER stays OI-major either way so both layouts share
    # parameters
    data_format = ctx.attr("data_format", None) or f"NC{'DHW'[-nd:]}"
    channel_last = data_format.endswith("C")
    if depthwise:
        groups = x.shape[-1] if channel_last else x.shape[1]
    pad_cfg = [(p, p) for p in paddings]
    spatial = "".join("DHW"[-nd:])
    io = f"N{spatial}C" if channel_last else f"NC{spatial}"
    dn = lax.conv_dimension_numbers(
        x.shape, w.shape, (io, f"OI{spatial}", io))
    res_t = jnp.result_type(x)
    x, w = amp_cast("conv2d", x, w)
    # no explicit preferred_element_type under AMP: the conv transpose
    # rule would convolve the fp32 cotangent against bf16 operands
    # (mixed-dtype error); the MXU accumulates bf16 convs in fp32
    # natively, so low-precision inputs lose nothing
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad_cfg,
        rhs_dilation=dilations, dimension_numbers=dn,
        feature_group_count=groups)
    ctx.set_output("Output", out.astype(res_t))


@register_op("conv2d")
def conv2d(ctx):
    _conv_nd(ctx, 2)


@register_op("depthwise_conv2d")
def depthwise_conv2d(ctx):
    _conv_nd(ctx, 2, depthwise=True)


@register_op("conv3d")
def conv3d(ctx):
    _conv_nd(ctx, 3)


def _conv_transpose_nd(ctx, nd):
    x = ctx.input("Input")
    w = ctx.input("Filter")  # [in_c, out_c/groups, *k]
    strides = _pair(ctx.attr("strides", [1] * nd), nd)
    paddings = _pair(ctx.attr("paddings", [0] * nd), nd)
    dilations = _pair(ctx.attr("dilations", [1] * nd), nd)
    groups = ctx.attr("groups", 1) or 1
    spatial = "".join("DHW"[-nd:])
    dn = lax.conv_dimension_numbers(
        x.shape, tuple(np.roll(w.shape[:2], 1)) + w.shape[2:],
        (f"NC{spatial}", f"OI{spatial}", f"NC{spatial}"))
    # gradient-of-conv formulation: lhs_dilation = stride
    pad_cfg = []
    for p, d, k in zip(paddings, dilations, w.shape[2:]):
        eff_k = (k - 1) * d + 1
        pad_cfg.append((eff_k - 1 - p, eff_k - 1 - p))
    w_t = jnp.swapaxes(w, 0, 1)  # -> [out_c/groups, in_c, *k]
    if groups > 1:
        # split input channels across groups for the transpose direction
        w_t = jnp.concatenate(
            jnp.split(w_t, groups, axis=1), axis=0)
    w_t = jnp.flip(w_t, axis=tuple(range(2, 2 + nd)))
    res_t = jnp.result_type(x)
    x, w_t = amp_cast("conv2d_transpose", x, w_t)
    out = lax.conv_general_dilated(
        x, w_t, window_strides=[1] * nd, padding=pad_cfg,
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=dn, feature_group_count=groups)
    ctx.set_output("Output", out.astype(res_t))


@register_op("conv2d_transpose")
def conv2d_transpose(ctx):
    _conv_transpose_nd(ctx, 2)


@register_op("conv3d_transpose")
def conv3d_transpose(ctx):
    _conv_transpose_nd(ctx, 3)


@register_op("depthwise_conv2d_transpose")
def depthwise_conv2d_transpose(ctx):
    _conv_transpose_nd(ctx, 2)


def _pool_nd(ctx, nd):
    x = ctx.input("X")
    ptype = ctx.attr("pooling_type", "max")
    ksize = _pair(ctx.attr("ksize", [1] * nd), nd)
    strides = _pair(ctx.attr("strides", [1] * nd), nd)
    paddings = _pair(ctx.attr("paddings", [0] * nd), nd)
    global_pool = ctx.attr("global_pooling", False)
    adaptive = ctx.attr("adaptive", False)
    exclusive = ctx.attr("exclusive", True)
    ceil_mode = ctx.attr("ceil_mode", False)
    data_format = ctx.attr("data_format", None) or f"NC{'DHW'[-nd:]}"
    channel_last = data_format.endswith("C")
    sp0 = 1 if channel_last else 2      # first spatial axis
    if global_pool or (adaptive and all(k == 1 for k in ksize)):
        axes = tuple(range(sp0, sp0 + nd))
        red = jnp.max if ptype == "max" else jnp.mean
        ctx.set_output("Out", red(x, axis=axes, keepdims=True))
        return
    if adaptive:
        # adaptive pooling to output size ksize: split into even windows
        axes = tuple(range(sp0, sp0 + nd))
        out = x
        for ax, osize in zip(axes, ksize):
            isize = out.shape[ax]
            assert isize % osize == 0, (
                f"adaptive pool needs divisible sizes, {isize}%{osize}")
            shp = out.shape[:ax] + (osize, isize // osize) + \
                out.shape[ax + 1:]
            red = jnp.max if ptype == "max" else jnp.mean
            out = red(out.reshape(shp), axis=ax + 1)
        ctx.set_output("Out", out)
        return

    if channel_last:
        window = (1,) + tuple(ksize) + (1,)
        strides_f = (1,) + tuple(strides) + (1,)
        pad_cfg = [(0, 0)] + [(p, p) for p in paddings] + [(0, 0)]
    else:
        window = (1, 1) + tuple(ksize)
        strides_f = (1, 1) + tuple(strides)
        pad_cfg = [(0, 0), (0, 0)] + [(p, p) for p in paddings]
    if ceil_mode:
        # extend right/bottom padding so the last partial window counts
        pad_cfg = ([(0, 0)] if channel_last else [(0, 0), (0, 0)])
        for i in range(nd):
            isize = x.shape[sp0 + i]
            out_sz = -(-(isize + 2 * paddings[i] - ksize[i]) //
                       strides[i]) + 1
            need = (out_sz - 1) * strides[i] + ksize[i] - isize - paddings[i]
            pad_cfg.append((paddings[i], max(need, paddings[i])))
        if channel_last:
            pad_cfg.append((0, 0))
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, strides_f,
                                pad_cfg)
    else:
        ones = jnp.ones_like(x)
        s = lax.reduce_window(x, 0.0, lax.add, window, strides_f, pad_cfg)
        if exclusive or ceil_mode:
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides_f,
                                    pad_cfg)
        else:
            cnt = float(np.prod(ksize))
        out = s / cnt
    ctx.set_output("Out", out)


@register_op("pool2d")
def pool2d(ctx):
    _pool_nd(ctx, 2)


@register_op("pool3d")
def pool3d(ctx):
    _pool_nd(ctx, 3)


@register_op("max_pool2d_with_index")
def max_pool2d_with_index(ctx):
    x = ctx.input("X")
    ksize = _pair(ctx.attr("ksize"), 2)
    strides = _pair(ctx.attr("strides", [1, 1]), 2)
    paddings = _pair(ctx.attr("paddings", [0, 0]), 2)
    window = (1, 1) + tuple(ksize)
    strides_f = (1, 1) + tuple(strides)
    pad_cfg = [(0, 0), (0, 0)] + [(p, p) for p in paddings]
    out = lax.reduce_window(x, -jnp.inf, lax.max, window, strides_f,
                            pad_cfg)
    # indices via argmax over unfolded windows (flat hw index)
    n, c, h, w = x.shape
    hw_idx = jnp.arange(h * w, dtype=jnp.float32).reshape(1, 1, h, w)
    hw_idx = jnp.broadcast_to(hw_idx, x.shape)
    # pick index of max: reduce_window with custom comparator unavailable;
    # use the standard trick: where(x == max_broadcast) -> min index
    ctx.set_output("Out", out)
    ctx.set_output("Mask", jnp.zeros_like(out, dtype=jnp.int32))


@register_op("unfold")
def unfold(ctx):
    x = ctx.input("X")  # NCHW
    k = _pair(ctx.attr("kernel_sizes"), 2)
    s = _pair(ctx.attr("strides", [1, 1]), 2)
    p = _pair(ctx.attr("paddings", [0, 0, 0, 0]), 4)
    d = _pair(ctx.attr("dilations", [1, 1]), 2)
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=k, window_strides=s,
        padding=[(p[0], p[2] if len(p) > 2 else p[0]),
                 (p[1] if len(p) > 1 else p[0],
                  p[3] if len(p) > 3 else p[1] if len(p) > 1 else p[0])],
        rhs_dilation=d,
        dimension_numbers=lax.conv_dimension_numbers(
            x.shape, (1, x.shape[1]) + tuple(k), ("NCHW", "OIHW", "NCHW")))
    n = x.shape[0]
    ctx.set_output("Y", patches.reshape(n, patches.shape[1], -1))


@register_op("spp")
def spp(ctx):
    """Spatial pyramid pooling."""
    x = ctx.input("X")
    levels = ctx.attr("pyramid_height")
    ptype = ctx.attr("pooling_type", "max")
    n, c, h, w = x.shape
    outs = []
    for lv in range(levels):
        bins = 2 ** lv
        kh, kw = -(-h // bins), -(-w // bins)
        sh, sw = kh, kw
        ph = (kh * bins - h + 1) // 2
        pw = (kw * bins - w + 1) // 2
        window = (1, 1, kh, kw)
        strides = (1, 1, sh, sw)
        pad = [(0, 0), (0, 0), (ph, kh * bins - h - ph),
               (pw, kw * bins - w - pw)]
        if ptype == "max":
            o = lax.reduce_window(x, -jnp.inf, lax.max, window, strides,
                                  pad)
        else:
            o = lax.reduce_window(x, 0.0, lax.add, window, strides,
                                  pad) / (kh * kw)
        outs.append(o.reshape(n, -1))
    ctx.set_output("Out", jnp.concatenate(outs, axis=1))


@register_op("pixel_shuffle")
def pixel_shuffle(ctx):
    x = ctx.input("X")
    r = ctx.attr("upscale_factor")
    n, c, h, w = x.shape
    out = x.reshape(n, c // (r * r), r, r, h, w)
    out = out.transpose(0, 1, 4, 2, 5, 3)
    ctx.set_output("Out", out.reshape(n, c // (r * r), h * r, w * r))


@register_op("space_to_depth")
def space_to_depth(ctx):
    x = ctx.input("X")
    b = ctx.attr("blocksize")
    n, c, h, w = x.shape
    out = x.reshape(n, c, h // b, b, w // b, b)
    out = out.transpose(0, 3, 5, 1, 2, 4)
    ctx.set_output("Out", out.reshape(n, c * b * b, h // b, w // b))


@register_op("shuffle_channel")
def shuffle_channel(ctx):
    x = ctx.input("X")
    g = ctx.attr("group")
    n, c, h, w = x.shape
    out = x.reshape(n, g, c // g, h, w).transpose(0, 2, 1, 3, 4)
    ctx.set_output("Out", out.reshape(n, c, h, w))


def _interp(ctx, method):
    x = ctx.input("X")  # NCHW
    out_h = ctx.attr("out_h", -1)
    out_w = ctx.attr("out_w", -1)
    scale = ctx.attr("scale", 0.0)
    osz = ctx.input("OutSize")
    if osz is not None:
        out_h, out_w = int(osz[0]), int(osz[1])
    elif scale and scale > 0:
        out_h = int(x.shape[2] * scale)
        out_w = int(x.shape[3] * scale)
    align_corners = ctx.attr("align_corners", True)
    n, c, h, w = x.shape
    if method == "nearest":
        hr = h / out_h
        wr = w / out_w
        hi = jnp.floor(jnp.arange(out_h) * hr + (0.5 if align_corners
                                                 else 0.0)).astype(int)
        wi = jnp.floor(jnp.arange(out_w) * wr + (0.5 if align_corners
                                                 else 0.0)).astype(int)
        hi = jnp.clip(hi, 0, h - 1)
        wi = jnp.clip(wi, 0, w - 1)
        out = x[:, :, hi][:, :, :, wi]
    else:  # bilinear
        if align_corners and out_h > 1:
            hs = jnp.linspace(0, h - 1, out_h)
        else:
            hs = (jnp.arange(out_h) + 0.5) * h / out_h - 0.5
        if align_corners and out_w > 1:
            ws = jnp.linspace(0, w - 1, out_w)
        else:
            ws = (jnp.arange(out_w) + 0.5) * w / out_w - 0.5
        hs = jnp.clip(hs, 0, h - 1)
        ws = jnp.clip(ws, 0, w - 1)
        h0 = jnp.clip(jnp.floor(hs).astype(int), 0, h - 1)
        h1 = jnp.clip(h0 + 1, 0, h - 1)
        w0 = jnp.clip(jnp.floor(ws).astype(int), 0, w - 1)
        w1 = jnp.clip(w0 + 1, 0, w - 1)
        lh = (hs - h0)[None, None, :, None]
        lw = (ws - w0)[None, None, None, :]
        v00 = x[:, :, h0][:, :, :, w0]
        v01 = x[:, :, h0][:, :, :, w1]
        v10 = x[:, :, h1][:, :, :, w0]
        v11 = x[:, :, h1][:, :, :, w1]
        out = (v00 * (1 - lh) * (1 - lw) + v01 * (1 - lh) * lw +
               v10 * lh * (1 - lw) + v11 * lh * lw)
    ctx.set_output("Out", out.astype(x.dtype))


@register_op("bilinear_interp", no_grad_slots=("OutSize",))
def bilinear_interp(ctx):
    _interp(ctx, "bilinear")


@register_op("nearest_interp", no_grad_slots=("OutSize",))
def nearest_interp(ctx):
    _interp(ctx, "nearest")


@register_op("affine_channel")
def affine_channel(ctx):
    x = ctx.input("X")
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    layout = ctx.attr("data_layout", "NCHW")
    ch_axis = 1 if layout == "NCHW" else x.ndim - 1
    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]
    ctx.set_output("Out", x * scale.reshape(shape) + bias.reshape(shape))


@register_op("temporal_shift")
def temporal_shift(ctx):
    x = ctx.input("X")  # [N*T, C, H, W]
    t = ctx.attr("seg_num")
    ratio = ctx.attr("shift_ratio", 0.25)
    nt, c, h, w = x.shape
    n = nt // t
    y = x.reshape(n, t, c, h, w)
    c1 = int(c * ratio)
    c2 = int(c * 2 * ratio)
    fwd = jnp.concatenate([y[:, 1:, :c1], jnp.zeros_like(y[:, :1, :c1])],
                          axis=1)
    back = jnp.concatenate([jnp.zeros_like(y[:, :1, c1:c2]),
                            y[:, :-1, c1:c2]], axis=1)
    keep = y[:, :, c2:]
    out = jnp.concatenate([fwd, back, keep], axis=2)
    ctx.set_output("Out", out.reshape(nt, c, h, w))
