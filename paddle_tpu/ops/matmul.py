"""MXU ops: mul / matmul / bmm — the FLOPs live here.

Parity: reference mul_op (flatten-to-2D semantics via x_num_col_dims /
y_num_col_dims, operators/mul_op.cc) and matmul_op (transpose_X/Y, alpha,
batched, operators/matmul_op.cc). Lowered to lax.dot_general so XLA tiles
straight onto the MXU; accumulation happens in f32 via
preferred_element_type when inputs are bf16.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op, shard_hint
from ..core.amp import amp_cast


def _flat2d(x, num_col_dims):
    lead = 1
    for d in x.shape[:num_col_dims]:
        lead *= d
    tail = 1
    for d in x.shape[num_col_dims:]:
        tail *= d
    return x.reshape(lead, tail)


def _acc_type(x, y):
    dt = jnp.result_type(x, y)
    if dt in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return None


@register_op("mul")
def mul(ctx):
    """Out = X . Y over X's trailing ``rank - x_num_col_dims`` and Y's
    leading ``y_num_col_dims`` dimensions.

    One dot_general contracts X in its own rank: X's trailing dimensions
    merge into one K (one already in every ``fc``: X ``[B, S, D]``
    against Y ``[D, N]``) and its leading ones stay free dimensions of
    the dot. The token dimensions are never merged into ``[B*S, D]`` and
    split again, which XLA could only meet with a relayout of the
    activation. Unit leading dimensions are dropped first, a bitcast: a
    decoder's ``[1, S, D]`` contracts as ``[S, D]``. A routed kernel
    takes the reference's 2-D operands.
    """
    x, y = ctx.input("X"), ctx.input("Y")
    xn = ctx.attr("x_num_col_dims", 1)
    yn = ctx.attr("y_num_col_dims", 1)
    out_shape = tuple(x.shape[:xn]) + tuple(y.shape[yn:])
    res_t = jnp.result_type(x, y)
    x, y = amp_cast("mul", x, y)
    y2 = _flat2d(y, yn)
    from ..kernels import registry as kreg
    sel = None
    if kreg.routable("mul"):
        x2 = _flat2d(x, xn)
        sel = kreg.select("mul", kreg.signature("mul", x2, y2))
    if sel is not None:
        out = sel.run(x2, y2, out_dtype=res_t)
    else:
        kreg.count("mul", "in_rank")
        lead = tuple(d for d in x.shape[:xn] if d != 1)
        x = x.reshape(lead + (y2.shape[0],))
        out = lax.dot_general(
            x, y2, (((len(lead),), (0,)), ((), ())),
            preferred_element_type=_acc_type(x, y2) or res_t)
        out = out.astype(res_t)
    out = out.reshape(out_shape)
    # tp-sharded matmul: under an active multi-axis activation scope
    # the output is pinned per Y's PartitionSpec (Megatron dispatch)
    out = shard_hint(ctx, "Out", out, weight_slot="Y")
    ctx.set_output("Out", out)


@register_op("matmul")
def matmul(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    tx = ctx.attr("transpose_X", False)
    ty = ctx.attr("transpose_Y", False)
    alpha = ctx.attr("alpha", 1.0)
    if x.ndim == 1:
        x = x[None, :] if not tx else x[:, None]
    if y.ndim == 1:
        y = y[:, None] if not ty else y[None, :]
    if tx:
        x = jnp.swapaxes(x, -1, -2)
    if ty:
        y = jnp.swapaxes(y, -1, -2)
    res_t = jnp.result_type(x, y)
    x, y = amp_cast("matmul", x, y)
    sel = None
    if x.ndim == 2 and y.ndim == 2 and alpha == 1.0:
        from ..kernels import registry as kreg
        if kreg.routable("matmul"):
            sel = kreg.select("matmul",
                              kreg.signature("matmul", x, y))
    if sel is not None:
        out = sel.run(x, y, out_dtype=res_t)
    else:
        out = jnp.matmul(
            x, y, preferred_element_type=_acc_type(x, y) or res_t)
        out = out.astype(res_t)
        if alpha != 1.0:
            out = out * alpha
    out = shard_hint(ctx, "Out", out, weight_slot="Y")
    ctx.set_output("Out", out)


@register_op("bilinear_tensor_product")
def bilinear_tensor_product(ctx):
    x, y, w = ctx.input("X"), ctx.input("Y"), ctx.input("Weight")
    # w: [out, dx, dy]; out[b,o] = x[b,:] @ w[o] @ y[b,:]
    out = jnp.einsum("bi,oij,bj->bo", x, w, y)
    b = ctx.input("Bias")
    if b is not None:
        out = out + b
    ctx.set_output("Out", out)
