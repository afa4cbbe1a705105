"""A gated short convolution as a token mixer (Pallas), forward and
backward.

The operator between a layer's in- and out-projection: with
[Bg | Cg | x] the three D-wide thirds of the in-projection's output
X [B, T, 3 D] and w [D, K] a depthwise filter of K taps,

  u = Bg * x;  c[t] = sum_j w[:, j] * u[t - (K - 1) + j];  out = Cg * c

causal (positions before the first read as zero), no bias, no
activation. Nothing here is a matrix product: a token is 8 D bytes of
HBM forward (X read, out written) and 14 D backward (X and d out read,
dX written) in bfloat16, against a dozen multiplies a channel, so both
kernels are bound by HBM and are one pass each.

  gated_short_conv_fwd   grid (batch, time blocks), the blocks in order:
      a [bt, 3 D] block of X in, [bt, D] out, the last rows of the
      block's u carried to the next block in a float32 VMEM scratch.
  gated_short_conv_bwd   the same grid, stateless along the sequence but
      for dw: with dc = d out * Cg the taps run the other way,
      du[t] = sum_j w[:, j] * dc[t + (K - 1) - j], so a block needs the
      rows of u just BEFORE it (for c, which dCg = d out * c reads) and
      the rows of dc just AFTER it: both are recomputed from a
      `_HALO`-row block of X (and of d out) on either side, 6% more
      reads at 256-row blocks. dX [B, T, 3 D] is written whole, and dw
      [K, D] accumulates in float32 in an output block that stays in
      VMEM over a batch row's blocks.

Everything inside is float32; X's type comes back out.

Routing. `select()`-governed like the scan (kernels/registry.py), ONE
decision an op counted under `gated_short_conv`: off the CPU, when not
denied and where D is whole lane blocks, the kernels run; otherwise the
`lowered` path computes the same in `jax.numpy` (its backward by
`jax.vjp`), which XLA can partition and a CPU can run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

_F32 = jnp.float32
_HALO = 16          # rows: a bfloat16 sublane tile, and at least K - 1
_BLOCK_ROWS = 256
_LANES = 512        # channels worked at a time inside a block
_VMEM_LIMIT = 64 << 20

__all__ = ["conv", "conv_grad", "use_kernels"]


# ---------------------------------------------------------------------------
# lowered path
# ---------------------------------------------------------------------------

def _lowered(x, w):
    """x [B, T, 3 D], w [D, K] -> out [B, T, D] in x's type."""
    d, k = w.shape
    t = x.shape[1]
    xf, wf = x.astype(_F32), w.astype(_F32)
    u = xf[..., :d] * xf[..., 2 * d:]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    c = sum(up[:, j:j + t] * wf[None, None, :, j] for j in range(k))
    return (xf[..., d:2 * d] * c).astype(x.dtype)


def _lowered_grad(x, w, dout):
    _, vjp = jax.vjp(_lowered, x, w.astype(_F32))
    return vjp(dout.astype(x.dtype))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _roll(a, shift):
    """Rows of `a` moved down by `shift` (cyclically)."""
    shift %= a.shape[0]
    if not shift:
        return a
    if registry.interpret():
        return jnp.roll(a, shift, axis=0)
    return pltpu.roll(a, shift, 0)


def _delayed(a, before, delay):
    """a[t - delay] for the rows t of a block: `a` moved down by
    `delay` rows, its first rows filled from the LAST rows of `before`
    (the `_HALO` rows ahead of the block)."""
    if not delay:
        return a
    out = _roll(a, delay)
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    for r in range(delay):
        src = before.shape[0] - delay + r
        out = jnp.where(row == r, before[src:src + 1, :], out)
    return out


def _advanced(a, after, delay):
    """a[t + delay]: `a` moved up, its last rows filled from the FIRST
    rows of `after` (the `_HALO` rows behind the block)."""
    if not delay:
        return a
    n = a.shape[0]
    out = _roll(a, -delay)
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    for r in range(delay):
        out = jnp.where(row == n - delay + r, after[r:r + 1, :], out)
    return out


def _chunks(d):
    """Static lane slices of a D-wide third, `_LANES` channels each."""
    return [(lo, min(_LANES, d - lo)) for lo in range(0, d, _LANES)]


_BG, _CG, _XS = range(3)       # the thirds of X, in order


def _third(ref, part, lo, width, d):
    """float32 [rows, width] of one third of a [rows, 3 D] ref."""
    return ref[:, part * d + lo:part * d + lo + width].astype(_F32)


def _fwd_kernel(x_ref, w_ref, o_ref, tail_scr, *, d, k):
    @pl.when(pl.program_id(1) == 0)
    def _start():
        tail_scr[...] = jnp.zeros_like(tail_scr)

    rows = x_ref.shape[0]
    for lo, width in _chunks(d):
        bg, cg, xs = (_third(x_ref, part, lo, width, d)
                      for part in (_BG, _CG, _XS))
        u = bg * xs
        before = tail_scr[:, lo:lo + width]
        c = sum(w_ref[j:j + 1, lo:lo + width]
                * _delayed(u, before, k - 1 - j) for j in range(k))
        o_ref[:, lo:lo + width] = (cg * c).astype(o_ref.dtype)
        tail_scr[:, lo:lo + width] = u[rows - _HALO:, :]


def _bwd_kernel(x_ref, prev_ref, next_ref, g_ref, gnext_ref, w_ref,
                dx_ref, dw_ref, *, d, k, n_blocks):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _start():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    first = (i > 0).astype(_F32)               # no token before the first
    last = (i < n_blocks - 1).astype(_F32)     # none after the last
    for lo, width in _chunks(d):
        ch = slice(lo, lo + width)
        bg, cg, xs = (_third(x_ref, part, lo, width, d)
                      for part in (_BG, _CG, _XS))
        g = g_ref[:, ch].astype(_F32)
        u, dc = bg * xs, g * cg
        before = _third(prev_ref, _BG, lo, width, d) \
            * _third(prev_ref, _XS, lo, width, d) * first
        after = gnext_ref[:, ch].astype(_F32) \
            * _third(next_ref, _CG, lo, width, d) * last
        c = jnp.zeros_like(u)
        du = jnp.zeros_like(u)
        for j in range(k):
            wj = w_ref[j:j + 1, ch]
            uj = _delayed(u, before, k - 1 - j)
            c = c + wj * uj
            du = du + wj * _advanced(dc, after, k - 1 - j)
            dw_ref[j:j + 1, ch] += jnp.sum(dc * uj, axis=0, keepdims=True)
        for part, val in enumerate((du * xs, g * c, du * bg)):
            dx_ref[:, part * d + lo:part * d + lo + width] = \
                val.astype(dx_ref.dtype)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _block_rows(t, rows):
    """(rows a block, the sequence's padded length): blocks of whole
    `_HALO`-row tiles, the length a multiple of the block."""
    bt = min(rows, -(-t // _HALO) * _HALO)
    return bt, -(-t // bt) * bt


def _pad_rows(a, t_pad):
    pad = t_pad - a.shape[1]
    return jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if pad else a


@functools.partial(jax.jit, static_argnames=("rows",))
def _fwd_call(x, w, rows=_BLOCK_ROWS):
    b, t, d3 = x.shape
    d, k = w.shape
    bt, t_pad = _block_rows(t, rows)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, k=k),
        name="gated_short_conv_fwd",
        grid=(b, t_pad // bt),
        in_specs=[pl.BlockSpec((None, bt, d3), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((k, d), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((None, bt, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t_pad, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO, d), _F32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=registry.interpret(),
    )(_pad_rows(x, t_pad), w.astype(_F32).T)
    return out[:, :t]


@functools.partial(jax.jit, static_argnames=("rows",))
def _bwd_call(x, w, dout, rows=_BLOCK_ROWS):
    b, t, d3 = x.shape
    d, k = w.shape
    bt, t_pad = _block_rows(t, rows)
    n, per = t_pad // bt, bt // _HALO
    x, dout = _pad_rows(x, t_pad), _pad_rows(dout.astype(x.dtype), t_pad)

    def block(width):
        return pl.BlockSpec((None, bt, width), lambda i, j: (i, j, 0))

    def halo(width, side):
        """The `_HALO` rows before (side -1) or after the block, clamped
        to the sequence: the kernel zeroes what a clamp brought in."""
        def index(i, j):
            at = j * per - 1 if side < 0 else (j + 1) * per
            return (i, jnp.clip(at, 0, n * per - 1), 0)
        return pl.BlockSpec((None, _HALO, width), index)

    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, k=k, n_blocks=n),
        name="gated_short_conv_bwd",
        grid=(b, n),
        in_specs=[block(d3), halo(d3, -1), halo(d3, 1), block(d),
                  halo(d, 1), pl.BlockSpec((k, d), lambda i, j: (0, 0))],
        out_specs=[block(d3),
                   pl.BlockSpec((None, k, d), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, t_pad, d3), x.dtype),
                   jax.ShapeDtypeStruct((b, k, d), _F32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=registry.interpret(),
    )(x, x, x, dout, dout, w.astype(_F32).T)
    return dx[:, :t], jnp.sum(dw, axis=0).T


# ---------------------------------------------------------------------------
# entry points and routing
# ---------------------------------------------------------------------------

def use_kernels(x, w) -> bool:
    """One decision an op, counted under `gated_short_conv`: the two
    Pallas kernels (`custom`) or the `jax.numpy` form (`lowered`)."""
    if not registry.routable("gated_short_conv"):
        return False
    return registry.select(
        "gated_short_conv",
        registry.signature("gated_short_conv", x, w)) is not None


def conv(x, w, kernels):
    """x [B, T, 3 D] (the in-projection's output, [Bg | Cg | x]), w
    [D, K] -> Cg * conv(Bg * x) [B, T, D] in x's type."""
    return _fwd_call(x, w) if kernels else _lowered(x, w)


def conv_grad(x, w, dout, kernels):
    """(dX [B, T, 3 D] in x's type, dw [D, K] float32)."""
    return _bwd_call(x, w, dout) if kernels else _lowered_grad(x, w, dout)


def _eligible(sig: registry.Signature) -> bool:
    """Thirds of whole lane blocks (the interpreter takes any width);
    taps that fit the halo."""
    (_, _, d3), (d, k) = sig.shapes[0], sig.shapes[1]
    if sig.dtypes[0] not in ("bfloat16", "float32") or d3 != 3 * d \
            or not 1 <= k <= _HALO + 1:
        return False
    return registry._INTERPRET or d % 128 == 0


registry.register_kernel(
    "gated_short_conv", op_types=("gated_short_conv",), eligible=_eligible,
    run=conv,
    doc="Cg * causal depthwise conv(Bg * x) over the thirds of an "
        "in-projection's output (fwd, bwd): one HBM pass each, the "
        "block's halo carried in VMEM (fwd) or recomputed (bwd)")
