"""Ops of a current decoder-only language model block: RMS
normalisation, rotary positions, the feed-forward's activation (gated
SwiGLU, or a squared ReLU), the top-k router (sigmoid or softmax
scores), the expert layer of a mixture of experts of which this chip
holds a share, the index of a learned sparse attention (which keys each
query attends), the three ops of a Mamba-2 mixer: the short causal
convolution, the state-space scan and the gated group-wise RMS norm, and
the gated short convolution that is a token mixer of its own.

The expert layer is dropless and knows which experts it holds:
`moe_experts` gathers the rows routed to experts `first_expert ..
first_expert + experts_held - 1` into a worst-case row buffer, runs
the three grouped matmuls of kernels/grouped_matmul.py over the part of
it that is in use and sums each token's rows back out of that part; what
the experts held elsewhere would add is left out
(on one chip there is no exchange, and nothing stands in for the absent
chips).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import numpy as np

from ..core.amp import amp_cast
from ..core.registry import (register_op, register_no_grad_op,
                             override_grad_lowering)

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


@register_op("rms_norm")
def rms_norm(ctx):
    """Y = X / sqrt(mean(X^2, last axis) + epsilon) * Scale. Statistics
    in float32 whatever X's type (a NORM op of core/amp.py: it reads
    bf16 activations as they are and answers in their type)."""
    x, scale = ctx.input("X"), ctx.input("Scale")
    eps = ctx.attr("epsilon", 1e-6)
    xf = x.astype(_F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if scale is not None:
        y = y * scale.astype(_F32)
    ctx.set_output("Y", y.astype(x.dtype))


def yarn_scale(d, theta, factor, original, beta_fast, beta_slow):
    """YaRN's factor on each of the d/2 rotary frequencies theta^(-2i/d),
    as transformers' `_compute_yarn_parameters` makes it (truncating):
    the pairs that turn fewer than `beta_slow` times over the `original`
    context are interpolated (1 / factor), those that turn more than
    `beta_fast` times kept (1), a linear ramp r_i between the two
    (r_i / factor + 1 - r_i). float64 [d/2], a constant of the trace."""
    def turning(rotations):      # the pair that turns so often over L0
        return d * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(turning(beta_fast)), 0)
    high = min(math.ceil(turning(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    r = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return r / factor + 1.0 - r


def _rotate_pairs(x, theta, offset, interleaved, yarn=None):
    """Rotate pair i of the last axis of x [B, S, H, D] by the angle
    pos * theta^(-2i/D), pos = offset + s. The pairs are the adjacent
    channels (x[2i], x[2i+1]) when `interleaved`, else the half-split
    ones (x[i], x[i + D/2]). yarn (factor, original context, beta_fast,
    beta_slow, attention factor): each frequency times `yarn_scale`,
    cos and sin times the attention factor."""
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=_F32) + offset
    freq = theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d)
    if yarn is not None:
        freq = freq * jnp.asarray(yarn_scale(d, theta, *yarn[:4]), _F32)
    angle = pos[:, None] * freq[None, :]                    # [S, D/2]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    if yarn is not None:
        cos, sin = cos * yarn[4], sin * yarn[4]
    xf = x.astype(_F32)
    if interleaved:
        pairs = xf.reshape(x.shape[:-1] + (d // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    else:
        a, b = xf[..., :d // 2], xf[..., d // 2:]
        out = jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


@register_op("rotary_embedding")
def rotary_embedding(ctx):
    """Rotary positions on the trailing `rotary_dim` channels of
    X [B, S, H, D] (all of D when 0). attrs: theta, rotary_dim,
    position_offset, interleaved (the pairs are adjacent channels; False:
    channel i pairs with channel i + rotary_dim/2), and where the
    rotary is YaRN-scaled `yarn` = [factor, original context, beta_fast,
    beta_slow, attention factor] (`_rotate_pairs`)."""
    x = ctx.input("X")
    theta = float(ctx.attr("theta", 10000.0))
    n = int(ctx.attr("rotary_dim", 0) or 0) or x.shape[-1]
    offset = float(ctx.attr("position_offset", 0))
    interleaved = bool(ctx.attr("interleaved", True))
    yarn = ctx.attr("yarn", None) or None
    if n % 2 or n > x.shape[-1]:
        raise ValueError(f"rotary_dim {n} of a head of {x.shape[-1]}")
    keep = x.shape[-1] - n
    if keep == 0:
        ctx.set_output("Out", _rotate_pairs(x, theta, offset, interleaved,
                                            yarn))
    else:
        ctx.set_output("Out", jnp.concatenate(
            [x[..., :keep],
             _rotate_pairs(x[..., keep:], theta, offset, interleaved,
                           yarn)],
            axis=-1))


@register_op("swiglu")
def swiglu(ctx):
    """Out = silu(X) * Y, the gated feed-forward's activation (X the
    gate projection, Y the up projection)."""
    x, y = ctx.input("X"), ctx.input("Y")
    xf = x.astype(_F32)
    ctx.set_output("Out", (xf * jax.nn.sigmoid(xf) * y.astype(_F32)
                           ).astype(jnp.result_type(x, y)))


@register_op("relu2")
def relu2(ctx):
    """Out = max(X, 0)^2, the ungated feed-forward's activation."""
    x = ctx.input("X")
    r = jnp.maximum(x.astype(_F32), 0.0)
    ctx.set_output("Out", (r * r).astype(x.dtype))


# ---------------------------------------------------------------- router

_SCORING = {"sigmoid": jax.nn.sigmoid,
            "softmax": lambda logits: jax.nn.softmax(logits, axis=-1)}


def _router_scores(x, w, scoring):
    """scoring(float32(x) . w^T) over all experts, the matrix product in
    float32 on every backend."""
    logits = jnp.einsum("td,ed->te", x.astype(_F32), w.astype(_F32),
                        precision=_HIGHEST)
    return _SCORING[scoring](logits)


def _router(ctx, x, w, bias):
    k = int(ctx.attr("top_k", 1))
    scoring = ctx.attr("scoring_func", "sigmoid")
    if scoring not in _SCORING:
        raise NotImplementedError(
            f"moe_router scores by {sorted(_SCORING)}, not {scoring!r}")
    if int(ctx.attr("n_group", 1)) != 1 or \
            int(ctx.attr("topk_group", 1)) != 1:
        raise NotImplementedError("moe_router selects within one group")
    s = _router_scores(x, w, scoring)
    pick = s if bias is None else s + bias.astype(_F32)[None, :]
    _, choice = jax.lax.top_k(jax.lax.stop_gradient(pick), k)
    weight = jnp.take_along_axis(s, choice, axis=-1)
    if ctx.attr("norm_topk_prob", True):
        weight = weight / (jnp.sum(weight, -1, keepdims=True)
                           + float(ctx.attr("norm_epsilon", 1e-20)))
    return choice.astype(jnp.int32), \
        weight * float(ctx.attr("routed_scaling_factor", 1.0))


@register_op("moe_router", no_grad_slots=("Bias",))
def moe_router(ctx):
    """X [T, D], Weight [num_experts, D], optional Bias [num_experts]
    (the selection-only score correction; takes no gradient).
    TopkIdx int32 [T, top_k]: the top_k of s + bias over all experts,
    s = sigmoid(x.w^T) or softmax(x.w^T) (attr scoring_func);
    TopkWeight float32 [T, top_k]: the chosen scores
    WITHOUT the bias, normalised to sum 1 (norm_topk_prob: over their
    sum + attr norm_epsilon, 1e-20 unless given) and scaled
    by routed_scaling_factor; Counts int32 [experts_held]: tokens routed
    to each expert held here (first_expert ..). float32 whatever AMP
    says (a BLACK op of core/amp.py)."""
    x = ctx.input("X")
    choice, weight = _router(ctx, x.reshape(-1, x.shape[-1]),
                             ctx.input("Weight"), ctx.input("Bias"))
    held = int(ctx.attr("experts_held", 0)) or ctx.input("Weight").shape[0]
    local = choice - int(ctx.attr("first_expert", 0))
    counts = jnp.sum(
        local.reshape(-1, 1) == jnp.arange(held, dtype=jnp.int32)[None],
        axis=0, dtype=jnp.int32)
    ctx.set_output("TopkIdx", choice)
    ctx.set_output("TopkWeight", weight)
    ctx.set_output("Counts", counts)


@override_grad_lowering("moe_router")
def moe_router_grad(ctx):
    """Gradients reach X and Weight through TopkWeight only: the choice
    is piecewise constant."""
    op = ctx.op
    x, w = ctx.input("X"), ctx.input("Weight")
    bias = ctx.input("Bias")
    g = ctx.env.get((op.input("TopkWeight@GRAD") or [""])[0])
    if g is None:
        return
    x2 = x.reshape(-1, x.shape[-1])
    _, vjp = jax.vjp(lambda a, b: _router(ctx, a, b, bias)[1], x2, w)
    dx, dw = vjp(g.astype(_F32))
    for slot, grad, like in (("X", dx.reshape(x.shape), x),
                             ("Weight", dw, w)):
        names = op.output(slot + "@GRAD")
        if names and names[0]:
            ctx.env[names[0]] = grad.astype(like.dtype)


# --------------------------------------------------------------- experts

def _silu(x):
    return x * jax.nn.sigmoid(x)


def _gather_rows(table, plan, top_k):
    """The token each row carries: table [T, D] -> [rows, D], padding
    rows zero."""
    rows = table[plan["choice_of_row"] // top_k]
    return jnp.where(plan["valid"][:, None], rows, 0)


def _combine(buf, plan, t, top_k):
    """Sum each token's held choices out of the buffer, choice by
    choice: [rows, D] -> float32 [T, D]. Every one of the T * top_k
    choices gathers a row, so this is the needed work where the rows are
    as many (the worst-case prefix, a layer that holds every expert).
    Rows the kernels never wrote are masked, not multiplied."""
    picked = buf[plan["row_of_choice"]]                  # [T*k, D]
    picked = jnp.where(plan["held"][:, None], picked, 0)
    return jnp.sum(picked.reshape(t, top_k, -1).astype(_F32), axis=1)


class _Prefix:
    """The first `rows` rows of the row buffer, which hold every tile in
    use: the plan cut to them, the rows gathered into them, each row's
    routing weight, the grouped matmuls over them and the combine out
    of them."""

    def __init__(self, x, weight, plan, rows, held, kernels):
        from ..kernels import grouped_matmul as gm
        self.gm, self.held, self.kernels = gm, held, kernels
        self.tokens, self.top_k = weight.shape
        self.plan = gm.prefix_plan(plan, rows)
        self.by_rows = kernels and gm.combine_by_rows(rows, weight.size)
        self.valid = self.plan["valid"][:, None]
        self.xs = _gather_rows(x, self.plan, self.top_k)
        self.w_row = jnp.where(
            self.plan["valid"],
            weight.reshape(-1)[self.plan["choice_of_row"]], 0.0)

    def gmm(self, lhs, rhs):
        return self.gm.gmm(lhs, rhs, self.plan, self.kernels)

    def gmm_dx(self, dout, rhs):
        return self.gm.gmm_dx(dout, rhs, self.plan, self.kernels)

    def gmm_dw(self, lhs, dout):
        return self.gm.gmm_dw(lhs, dout, self.plan, self.held, self.kernels)

    def combine(self, buf):
        """[rows, D] -> float32 [T, D], each token's held rows summed in
        float32 from the buffer's own type. Where the layer's kernels
        run and the prefix is short (`grouped_matmul.combine_by_rows`)
        the sum goes over the prefix's ROWS (kernel `moe_combine`: a
        token's held rows added in row, that is expert, order), else
        over every choice of every token (`_combine`, in choice order):
        the two differ by the float32 reassociation of at most top_k
        terms and are bit-equal where a token holds one choice or two.
        Which one a traced body took is counted under `moe_combine`
        (`prefix_rows` / `all_choices`) where the kernels run."""
        from ..kernels import registry
        if self.kernels:
            registry.count("moe_combine",
                           "prefix_rows" if self.by_rows else "all_choices")
        if self.by_rows:
            return self.gm.combine(buf, self.plan, self.tokens, self.top_k)
        return _combine(buf, self.plan, self.tokens, self.top_k)


def _experts_forward(x, weight, wg, wu, wd, plan, *, rows, held, kernels,
                     out_dtype):
    """`moe_experts` over the buffer's first `rows` rows. Returns (out
    [T, D], GateAct, UpAct): the two at the whole buffer's length, their
    rows past `rows` zero. Without a gate matrix (`wg` None) the expert
    is the ungated one, down(relu(up(x))^2), and GateAct is None."""
    p = _Prefix(x, weight, plan, rows, held, kernels)
    up = p.gmm(p.xs, wu)
    if wg is None:
        gate = None
        hidden = jnp.square(jnp.maximum(up.astype(_F32), 0.0))
    else:
        gate = p.gmm(p.xs, wg)
        hidden = _silu(gate.astype(_F32)) * up.astype(_F32)
    # the routing weight goes in before the down projection (it is
    # linear), so the backward needs no expert output kept or recomputed
    hw = jnp.where(p.valid, hidden * p.w_row[:, None], 0)
    out = p.combine(p.gmm(hw.astype(x.dtype), wd))
    beyond = plan["valid"].shape[0] - rows
    if beyond:
        gate, up = (None if a is None else jnp.pad(a, ((0, beyond), (0, 0)))
                    for a in (gate, up))
    return out.astype(out_dtype), gate, up


def _experts_backward(x, weight, wg, wu, wd, plan, gate, up, dout, *, rows,
                      held, kernels, shape):
    """`moe_experts_grad` over the buffer's first `rows` rows, the rows
    of GateAct and UpAct the forward wrote. Returns float32 (dX, dWeight,
    dWGate, dWUp, dWDown); dWGate is None for the ungated expert."""
    p = _Prefix(x, weight, plan, rows, held, kernels)
    dtype, valid = x.dtype, p.valid
    if up is None:          # a hand-built op desc without the two slots
        up = p.gmm(p.xs, wu)
        gate = None if wg is None else p.gmm(p.xs, wg)
    elif rows < up.shape[0]:
        up = up[:rows]
        gate = None if gate is None else gate[:rows]
    dy = _gather_rows(dout.reshape(x.shape[0], -1).astype(dtype), p.plan,
                      p.top_k)

    up32 = jnp.where(valid, up.astype(_F32), 0)
    if wg is None:
        act = jnp.maximum(up32, 0.0)
        hidden = act * act
    else:
        gate32 = jnp.where(valid, gate.astype(_F32), 0)
        sig = jax.nn.sigmoid(gate32)
        act = gate32 * sig
        hidden = act * up32
    hw = (hidden * p.w_row[:, None]).astype(dtype)
    dhw = jnp.where(valid, p.gmm_dx(dy, wd).astype(_F32), 0)
    d_wd = p.gmm_dw(hw, dy)
    dw_row = jnp.sum(dhw * hidden, axis=-1)
    dh = dhw * p.w_row[:, None]
    if wg is None:
        dup = (dh * 2.0 * act).astype(dtype)
        dxs = p.gmm_dx(dup, wu).astype(_F32)
        d_wg = None
    else:
        dgate = (dh * up32 * (sig + act * (1.0 - sig))).astype(dtype)
        dup = (dh * act).astype(dtype)
        dxs = p.gmm_dx(dgate, wg).astype(_F32) \
            + p.gmm_dx(dup, wu).astype(_F32)
        d_wg = p.gmm_dw(p.xs, dgate)
    d_wu = p.gmm_dw(p.xs, dup)
    dx = p.combine(dxs).reshape(shape)
    dweight = jnp.where(plan["held"], dw_row[plan["row_of_choice"]],
                        0.0).reshape(weight.shape)
    return dx, dweight, d_wg, d_wu, d_wd


# A layer's branches have the same shapes in every layer and in both
# traces the engine makes of a step: jitted at module level, a body is
# traced once a prefix and found again. Everything that reads a setting
# of the process (AMP's casts, the kernel decision) stays outside them.
_BODIES = {
    _experts_forward: jax.jit(_experts_forward, static_argnames=(
        "rows", "held", "kernels", "out_dtype")),
    _experts_backward: jax.jit(_experts_backward, static_argnames=(
        "rows", "held", "kernels", "shape")),
}


class _Experts:
    """What the forward and the grad op of `moe_experts` share: the
    operands as the kernels take them, the row buffer's plan, the kernel
    decision, and the prefixes of the buffer the layer may run over
    (`grouped_matmul.prefix_rows`): the share of the experts held here
    sets them when the op is traced, the plan's `n_active` picks one
    when it runs."""

    def __init__(self, ctx):
        from ..kernels import grouped_matmul as gm
        self.gm = gm
        x, choice = ctx.input("X"), ctx.input("TopkIdx")
        self.weight = ctx.input("TopkWeight").astype(_F32)
        self.shape = x.shape
        x = x.reshape(-1, x.shape[-1])
        # neither op is on an AMP list: cast here, so that both
        # differentiate the same function and the weights stay float32
        activation = ctx.attr("activation", "swiglu")
        gated = activation == "swiglu"
        if activation not in ("swiglu", "relu2") \
                or gated != ctx.has_input("WGate"):
            raise ValueError("moe_experts is swiglu with a gate matrix or "
                             "relu2 without one")
        x, wg, wu, wd = amp_cast(
            "moe_experts", x, ctx.input("WGate") if gated else None,
            ctx.input("WUp"), ctx.input("WDown"))
        self.x = x
        self.wg, self.wu, self.wd = (
            None if w is None else w.astype(x.dtype) for w in (wg, wu, wd))
        self.held = int(ctx.attr("experts_held"))
        first = int(ctx.attr("first_expert", 0))
        num_experts = int(ctx.attr("num_experts"))
        if first < 0 or first + self.held > num_experts:
            raise ValueError("experts held lie outside the layer's experts")
        self.plan = gm.plan_rows(choice.reshape(-1) - first, self.held)
        self.kernels = gm.use_kernels(x, self.wu)
        self.ladder = gm.prefix_rows(choice.size, self.held, num_experts)

    def run(self, body, *more, **static):
        """`body` over the shortest prefix that holds every tile in use.
        One prefix (every expert held): the body itself, no branch."""
        operands = (self.x, self.weight, self.wg, self.wu, self.wd,
                    self.plan) + more
        static.update(held=self.held, kernels=self.kernels)
        if len(self.ladder) == 1:
            return body(*operands, rows=self.ladder[0], **static)
        return jax.lax.switch(
            self.taken,
            [functools.partial(_BODIES[body], rows=rows, **static)
             for rows in self.ladder], *operands)

    @functools.cached_property
    def taken(self):
        """int32 scalar: which prefix of the ladder this routing takes."""
        return self.gm.prefix_index(self.plan, self.ladder)

    def rows_worked(self):
        """int32 [2]: rows of the prefix taken, rows in use (the tiles
        in use, whole)."""
        return jnp.stack([
            jnp.asarray(self.ladder, jnp.int32)[self.taken],
            self.plan["n_active"][0] * self.gm.TILE_ROWS])


@register_op("moe_experts", no_grad_slots=("TopkIdx",),
             intermediate_outputs=("GateAct", "UpAct"))
def moe_experts(ctx):
    """The routed experts held here, dropless. X [.., D]; TopkIdx int32
    [T, k] over all `num_experts`; TopkWeight [T, k]; WGate, WUp
    [experts_held, D, F]; WDown [experts_held, F, D].
    Out[t] = sum over k with TopkIdx[t, k] held here of TopkWeight[t, k]
    * WDown_e(silu(x_t WGate_e) * (x_t WUp_e)); with attr activation
    "relu2" the expert is ungated, WDown_e(relu(x_t WUp_e)^2), and the op
    has no WGate and no GateAct. GateAct and UpAct
    (intermediate, [buffer rows, F]) carry the two projections to the
    grad op, so the backward runs no forward kernel again.

    Where only a share of the experts is held, the whole layer runs over
    a static prefix of the worst-case buffer that holds every tile in
    use (`_Experts`); the worst case stays one of the prefixes, so no
    routing drops a token; a short prefix also sums its rows back to the
    tokens row by row (`_Prefix.combine`). GateAct and UpAct keep the
    worst-case length:
    a prefix's rows are written, the rows past it are zero, and nothing
    reads them (the grad op takes the same prefix). RowsWorked (optional,
    int32 [2]): rows of the prefix taken and rows in use, for the
    `moe_rows_worked` counter (observability/moe.py)."""
    e = _Experts(ctx)
    out, gate, up = e.run(_experts_forward,
                          out_dtype=jnp.result_type(ctx.input("X")))
    ctx.set_output("Out", out.reshape(e.shape))
    if gate is not None:
        ctx.set_output("GateAct", gate)
    ctx.set_output("UpAct", up)
    if ctx.has_output("RowsWorked"):
        ctx.set_output("RowsWorked", e.rows_worked())


@override_grad_lowering("moe_experts")
def moe_experts_grad(ctx):
    """Hand-written: three dx and three dw grouped matmuls over the
    forward's prefix of the row buffer (the same `n_active` picks it),
    reading the forward's GateAct and UpAct. With
    hw = silu(gate) * up * w_row and y = hw WDown:
      d hw = dy WDown^T;  dWDown = hw^T dy;  dw_row = sum(d hw * hidden)
      d gate = d hw * w_row * up * silu'(gate);  d up = d hw * w_row *
      silu(gate);  dx = d gate WGate^T + d up WUp^T;  dWGate = x^T
      d gate;  dWUp = x^T d up."""
    op = ctx.op
    e = _Experts(ctx)
    gate = up = None
    if ctx.has_input("UpAct") and (e.wg is None
                                   or ctx.has_input("GateAct")):
        up = ctx.env[op.input("UpAct")[0]]
        if e.wg is not None:
            gate = ctx.env[op.input("GateAct")[0]]
    grads = e.run(_experts_backward, gate, up,
                  ctx.env[op.input("Out@GRAD")[0]], shape=e.shape)
    for slot, grad in zip(("X", "TopkWeight", "WGate", "WUp", "WDown"),
                          grads):
        names = op.output(slot + "@GRAD")
        if names and names[0] and grad is not None:
            primal = ctx.env[op.input(slot)[0]]
            ctx.env[names[0]] = grad.astype(primal.dtype)


# ------------------------------------------------ sparse attention's index

@register_no_grad_op("sparse_attention_index")
def sparse_attention_index(ctx):
    """Which keys each query of a learned sparse attention attends.
    IndexQ [B, S, heads, d], IndexK [B, S, d] (one index key head),
    IndexW [B, S, heads]; attrs top_k, scale (on IndexW).
    I[t, s] = sum_j scale * w[t, j] * relu(q[t, j] . k[s]), float32 from
    products in the inputs' type. Mask int8 [B, 1, S, S]: 1 where s <= t
    and I[t, s] is among the top_k largest of I[t, :t + 1] (every key
    while t < top_k; ties at the threshold all kept), the keep mask
    `fused_attention` takes as BiasQK. Kept int32 [1]: the pairs kept.
    No gradient: the selection is piecewise constant."""
    from ..kernels import sparse_index
    q, k = ctx.input("IndexQ"), ctx.input("IndexK")
    w = ctx.input("IndexW").astype(_F32) * float(ctx.attr("scale", 1.0))
    k = k.astype(q.dtype)
    keep, kept = sparse_index.index_mask(
        q, k, w, int(ctx.attr("top_k")), sparse_index.use_kernels(q, k))
    ctx.set_output("Mask", keep[:, None])
    ctx.set_output("Kept", kept)


# ------------------------------------------------------ the Mamba-2 mixer

@register_op("causal_conv1d")
def causal_conv1d(ctx):
    """A short depthwise convolution along the sequence that looks back
    only: X [B, T, C], Weight [C, K], optional Bias [C].
    Out[t, c] = act(Bias[c] + sum_j Weight[c, j] X[t - (K - 1) + j, c]),
    tokens before the first read as zero; attr activation "silu" or "".
    Float32 inside, X's type out."""
    x, w = ctx.input("X"), ctx.input("Weight").astype(_F32)
    bias = ctx.input("Bias") if ctx.has_input("Bias") else None
    k, t = w.shape[1], x.shape[1]
    xp = jnp.pad(x.astype(_F32), ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + t] * w[None, None, :, j] for j in range(k))
    if bias is not None:
        y = y + bias.astype(_F32)
    act = ctx.attr("activation", "")
    if act == "silu":
        y = _silu(y)
    elif act:
        raise NotImplementedError(f"causal_conv1d activation {act!r}")
    ctx.set_output("Out", y.astype(x.dtype))


@register_op("gated_rms_norm")
def gated_rms_norm(ctx):
    """Y = RMSNorm(X * silu(Gate)) * Scale, the mean square taken within
    each of `groups` equal groups of the last axis' channels (the gate
    goes in BEFORE the norm). Float32 inside, X's type out."""
    x, gate, scale = ctx.input("X"), ctx.input("Gate"), ctx.input("Scale")
    groups, eps = int(ctx.attr("groups", 1)), ctx.attr("epsilon", 1e-5)
    if x.shape[-1] % groups:
        raise ValueError(f"{x.shape[-1]} channels in {groups} groups")
    v = x.astype(_F32) * _silu(gate.astype(_F32))
    g = v.reshape(v.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    ctx.set_output("Y", (g.reshape(v.shape) * scale.astype(_F32)
                         ).astype(x.dtype))


class _Scan:
    """What the forward and the grad op of `mamba2_ssd` share: the step
    sizes dt = softplus(Dt + DtBias) and the decay rates A = -exp(ALog)
    in float32, B and C in X's type, the kernel decision."""

    def __init__(self, ctx):
        from ..kernels import mamba2_ssd
        self.ssd = mamba2_ssd
        self.x = ctx.input("X")
        self.b, self.c = (ctx.input(s).astype(self.x.dtype)
                          for s in ("B", "C"))
        self.raw = ctx.input("Dt").astype(_F32) \
            + ctx.input("DtBias").astype(_F32)
        self.dt = jax.nn.softplus(self.raw)
        self.a = -jnp.exp(ctx.input("ALog").astype(_F32))
        self.d = ctx.input("D").astype(_F32)
        self.chunk = int(ctx.attr("chunk_size", mamba2_ssd.CHUNK))
        self.kernels = mamba2_ssd.use_kernels(self.x, self.b)


@register_op("mamba2_ssd", intermediate_outputs=("States",))
def mamba2_ssd(ctx):
    """Mamba-2's state-space scan. X [B, T, H, P]; Dt [B, T, H]; DtBias,
    ALog, D [H]; B, C [B, T, G, N] (head h reads group h // (H / G)).
    With dt = softplus(Dt + DtBias) and A = -exp(ALog), a head's state
    S [P, N] from zero: S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,
    Y_t = S_t C_t + D x_t, computed in chunks of attr chunk_size tokens
    (kernels/mamba2_ssd.py). States (intermediate, float32 [B, chunks,
    H, P, N]) carries the state at each chunk's start to the grad op.
    Tokens (optional, int32 [1]): the tokens scanned, for the
    `mamba_ssd_tokens` counter (observability/mamba.py)."""
    s = _Scan(ctx)
    y, states = s.ssd.ssd(s.x, s.dt, s.a, s.b, s.c, s.d, s.kernels, s.chunk)
    ctx.set_output("Y", y)
    ctx.set_output("States", states)
    if ctx.has_output("Tokens"):
        ctx.set_output("Tokens", jnp.full(
            (1,), s.x.shape[0] * s.x.shape[1], jnp.int32))


@override_grad_lowering("mamba2_ssd")
def mamba2_ssd_grad(ctx):
    """Hand-written: the backward kernel reads the forward's States (a
    forward recomputed here would be a second custom call); then
    d Dt = d DtBias = d dt * sigmoid(Dt + DtBias), d ALog = dA * A."""
    op = ctx.op
    s = _Scan(ctx)
    dy = ctx.env[op.input("Y@GRAD")[0]].astype(s.x.dtype)
    if ctx.has_input("States"):
        states = ctx.env[op.input("States")[0]]
    else:
        states = s.ssd.ssd(s.x, s.dt, s.a, s.b, s.c, s.d, s.kernels,
                           s.chunk)[1]
    dx, ddt, da, db, dc, dd = s.ssd.ssd_grad(
        s.x, s.dt, s.a, s.b, s.c, s.d, states, dy, s.kernels, s.chunk)
    draw = ddt * jax.nn.sigmoid(s.raw)
    for slot, grad in (("X", dx), ("Dt", draw),
                       ("DtBias", jnp.sum(draw, axis=(0, 1))),
                       ("ALog", da * s.a), ("B", db), ("C", dc), ("D", dd)):
        names = op.output(slot + "@GRAD")
        if names and names[0]:
            primal = ctx.env[op.input(slot)[0]]
            ctx.env[names[0]] = grad.astype(primal.dtype)


# ------------------------------------------- the gated short convolution

def _short_conv_operands(ctx):
    from ..kernels import short_conv
    x, w = ctx.input("X"), ctx.input("Weight")
    if x.ndim != 3 or x.shape[-1] != 3 * w.shape[0]:
        raise ValueError(f"gated_short_conv: X {x.shape} is not [B, T, 3 D] "
                         f"of a filter {w.shape}")
    return short_conv, x, w, short_conv.use_kernels(x, w)


@register_op("gated_short_conv")
def gated_short_conv(ctx):
    """A gated short convolution, the token mixer between an in- and an
    out-projection. X [B, T, 3 D] = [Bg | Cg | x], the in-projection's
    output; Weight [D, K], a depthwise filter.
    Out[t] = Cg[t] * sum_j Weight[:, j] * (Bg * x)[t - (K - 1) + j],
    tokens before the first read as zero; no bias, no activation.
    Float32 inside, X's type out (kernels/short_conv.py). Tokens
    (optional, int32 [1]): the tokens convolved, for the
    `short_conv_tokens` counter (observability/short_conv.py)."""
    sc, x, w, kernels = _short_conv_operands(ctx)
    ctx.set_output("Out", sc.conv(x, w, kernels))
    if ctx.has_output("Tokens"):
        ctx.set_output("Tokens", jnp.full(
            (1,), x.shape[0] * x.shape[1], jnp.int32))


@override_grad_lowering("gated_short_conv")
def gated_short_conv_grad(ctx):
    """Hand-written: one backward kernel gives dX (its three thirds are
    d Bg = du * x, d Cg = dOut * c, dx = du * Bg, with du the filter run
    the other way over dOut * Cg) and dWeight (float32)."""
    op = ctx.op
    sc, x, w, kernels = _short_conv_operands(ctx)
    dx, dw = sc.conv_grad(x, w, ctx.env[op.input("Out@GRAD")[0]], kernels)
    for slot, grad, like in (("X", dx, x), ("Weight", dw, w)):
        names = op.output(slot + "@GRAD")
        if names and names[0]:
            ctx.env[names[0]] = grad.astype(like.dtype)
