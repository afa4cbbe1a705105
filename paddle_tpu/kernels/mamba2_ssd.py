"""Mamba-2's state-space scan, chunked (Pallas), forward and backward.

A head h of a Mamba-2 mixer carries a state S [P, N] along the sequence:

  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t          (S_0 = 0)
  y_t = S_t C_t + D x_t

x_t [P] the head's channels, B_t and C_t [N] those of the head's GROUP
(head h reads group h // (H / G)), dt_t > 0 and A < 0 scalars of the head.
Token by token that is T dependent steps of almost no work. In chunks of
Q tokens it is matrix products: with cum_t the sum of dt_s A over the
chunk's tokens up to t,

  y_t = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s      (within)
        + exp(cum_t) S_prev C_t + D x_t                           (carried)
  S_end = exp(cum_Q) S_prev + sum_s exp(cum_Q - cum_s) dt_s x_s (x) B_s

so one chunk is `C B^T` once a group, `(C B^T . decay) x`, `C S_prev^T`
and `x^T B` a head, and the only sequential part is one [P, N] state a
head handed from chunk to chunk. Every exponent is <= 0. No array of a
state a TOKEN ever exists: the forward keeps the state at each chunk's
START ([T / Q, H, P, N] float32), which is what the backward needs.

Two kernels, one grid step a (batch, group, chunk), the chunks in order
and the group's states in VMEM in float32:

  mamba2_ssd_fwd   y and the chunk-start states.
  mamba2_ssd_bwd   the chunks in REVERSE, carrying the gradient of the
      state: dx, dB, dC (summed over the group's heads), and a row a
      token a head of d dt, d cum and dy . x, from which the entry point
      makes d dt, dA and dD (a reverse cumulative sum within each chunk:
      cum is a cumulative sum).

dt, cum and every exp are float32; the products' operands are in x's
type (bfloat16 under mixed precision) and accumulate in float32.

Routing. `select()`-governed like the grouped matmul
(kernels/registry.py), ONE decision an op counted under `mamba2_ssd`:
off the CPU, when not denied and where the shapes tile, the kernels run;
otherwise the `lowered` path computes the same chunked form with
`jax.numpy` (its backward by `jax.vjp`), which XLA can partition and a
CPU can run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

CHUNK = 128
_F32 = jnp.float32

__all__ = ["CHUNK", "ssd", "ssd_grad", "use_kernels"]


# ---------------------------------------------------------------------------
# what both paths share: chunks, the cumulative log-decay
# ---------------------------------------------------------------------------

def _pad_tokens(a, pad):
    """Zero tokens at the end of axis 1: with dt = 0 a token neither
    decays the state nor adds to it."""
    if not pad:
        return a
    return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))


def _chunk_cumsum(dt, a, q):
    """cum [B, T, H] float32: the sum of dt * A over the tokens of the
    same chunk up to and including each (T a multiple of q)."""
    b, t, h = dt.shape
    da = (dt * a[None, None, :]).reshape(b, t // q, q, h)
    return jnp.cumsum(da, axis=2).reshape(b, t, h)


def _chunk_rev_cumsum(g, q):
    """The transpose of `_chunk_cumsum`'s sum: g [B, T, H] summed over
    the tokens of the same chunk from each token on."""
    b, t, h = g.shape
    g = g.reshape(b, t // q, q, h)
    return jnp.flip(jnp.cumsum(jnp.flip(g, 2), axis=2), 2).reshape(b, t, h)


# ---------------------------------------------------------------------------
# lowered path: the same chunked form by XLA
# ---------------------------------------------------------------------------

def _lowered(x, dt, a, b, c, d, q):
    """(y [B, T, H, P] in x's type, chunk-start states [B, T/q, H, P, N]
    float32); T a multiple of q."""
    bs, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    hg, nc, dtype = h // g, t // q, x.dtype
    cum = _chunk_cumsum(dt, a, q).reshape(bs, nc, q, g, hg)
    dtc = dt.reshape(bs, nc, q, g, hg)
    xc = x.reshape(bs, nc, q, g, hg, p)
    bc, cc = b.reshape(bs, nc, q, g, n), c.reshape(bs, nc, q, g, n)

    gm = jnp.einsum("bctgn,bcsgn->bcgts", cc, bc,
                    preferred_element_type=_F32)
    seg = cum[:, :, :, None] - cum[:, :, None, :]         # [.., t, s, g, j]
    lower = (jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
             )[None, None, :, :, None, None]
    decay = jnp.where(lower, jnp.exp(jnp.minimum(seg, 0.0)), 0.0)
    m = gm.transpose(0, 1, 3, 4, 2)[..., None] * decay * dtc[:, :, None]
    y = jnp.einsum("bctsgj,bcsgjp->bctgjp", m.astype(dtype), xc,
                   preferred_element_type=_F32)

    cum_q = cum[:, :, -1]                                 # [B, nc, g, j]
    u = jnp.exp(cum_q[:, :, None] - cum) * dtc
    add = jnp.einsum("bcsgjp,bcsgn->bcgjpn",
                     (xc.astype(_F32) * u[..., None]).astype(dtype), bc,
                     preferred_element_type=_F32)
    keep = jnp.exp(cum_q)[..., None, None]

    def carry(s, inp):
        k, ad = inp
        return k * s + ad, s

    _, states = lax.scan(
        carry, jnp.zeros((bs, g, hg, p, n), _F32),
        (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(add, 1, 0)))
    states = jnp.moveaxis(states, 0, 1)                   # chunk-START
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bctgn,bcgjpn->bctgjp", cc, states.astype(dtype),
        preferred_element_type=_F32)
    y = y + d.reshape(g, hg)[None, None, None, :, :, None] * xc.astype(_F32)
    return y.reshape(bs, t, h, p).astype(dtype), \
        states.reshape(bs, nc, h, p, n)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _to_col(row, eye):
    """[1, Q] -> [Q, 1] without a transpose: the diagonal of the row
    broadcast down the square."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col, eye):
    """[Q, 1] -> [1, Q]."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=_F32)


_NT = ((1,), (1,))      # a b^T
_TN = ((0,), (0,))      # a^T b
_NN = ((1,), (0,))      # a b


def _last(rows, q):
    """[rows, q] mask of the last column."""
    return lax.broadcasted_iota(jnp.int32, (rows, q), 1) == q - 1


def _head_terms(dt_ref, cum_ref, j, q, p):
    """The per-head scalars of a chunk in both orientations, the
    within-chunk decay L[t, s] = exp(cum_t - cum_s) for s <= t, each
    token's decay to the chunk's end, and the chunk's whole decay
    exp(cum_Q) as a [p, 1] column and as [1, 1] (Mosaic broadcasts a
    [1, 1] along one axis only, and slices no column)."""
    rows = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    eye = rows == cols
    cum_row, dt_row = cum_ref[j:j + 1, :], dt_ref[j:j + 1, :]
    cum_col, dt_col = _to_col(cum_row, eye), _to_col(dt_row, eye)
    decay = jnp.where(rows >= cols,
                      jnp.exp(jnp.minimum(cum_col - cum_row, 0.0)), 0.0)

    def cum_q(n):
        return jnp.sum(jnp.where(_last(n, q), cum_row, 0.0), axis=1,
                       keepdims=True)
    return eye, dt_row, cum_col, dt_col, decay, \
        jnp.exp(cum_q(q) - cum_col), jnp.exp(cum_q(p)), jnp.exp(cum_q(1))


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, y_ref, st_ref,
                s_scr, cs_scr, *, hg, p, q):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_scr[...] = jnp.zeros_like(s_scr)

    st_ref[...] = s_scr[...]
    cm, bm = c_ref[...], b_ref[...]
    dtype = cm.dtype
    gm = _dot(cm, bm, _NT)                                    # [Q, Q]
    cs_scr[...] = _dot(cm, s_scr[...].astype(dtype), _NT)     # [Q, hg*P]
    for j in range(hg):
        ch = slice(j * p, (j + 1) * p)
        eye, dt_row, cum_col, dt_col, decay, to_end, keep, keep1 = \
            _head_terms(dt_ref, cum_ref, j, q, p)
        xh = x_ref[:, ch]
        y = _dot((gm * decay * dt_row).astype(dtype), xh, _NN)
        y = y + jnp.exp(cum_col) * cs_scr[:, ch]
        y = y + d_ref[j:j + 1, :p] * xh.astype(_F32)
        y_ref[:, ch] = y.astype(y_ref.dtype)
        xs = (xh.astype(_F32) * (to_end * dt_col)).astype(dtype)
        s_scr[ch, :] = keep * s_scr[ch, :] + _dot(xs, bm, _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, st_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, ddx_ref,
                ds_scr, ds_new, cs_scr, bds_scr, dyd_scr, xs_scr, *,
                hg, p, q):
    """One chunk of the backward. `ds_scr` carries the gradient of the
    state at this chunk's END (zero after the last chunk); the gradient
    of its START goes to `ds_new` head by head and becomes `ds_scr` when
    every product that needs the end's has been made."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    cm, bm = c_ref[...], b_ref[...]
    dtype = cm.dtype
    gm = _dot(cm, bm, _NT)
    cs_scr[...] = _dot(cm, st_ref[...].astype(dtype), _NT)    # C S_prev^T
    bds_scr[...] = _dot(bm, ds_scr[...].astype(dtype), _NT)   # B dS_end^T
    dgm = jnp.zeros((q, q), _F32)
    for j in range(hg):
        ch = slice(j * p, (j + 1) * p)
        eye, dt_row, cum_col, dt_col, decay, to_end, keep, keep1 = \
            _head_terms(dt_ref, cum_ref, j, q, p)
        xh, dyh = x_ref[:, ch], dy_ref[:, ch]
        xf, dyf = xh.astype(_F32), dyh.astype(_F32)
        mp = gm * decay
        dm = _dot(dyh, xh, _NT)                               # dy x^T
        dgm = dgm + dm * decay * dt_row
        zp = dm * mp
        z = zp * dt_row
        dx = _dot((mp * dt_row).astype(dtype), dyh, _TN)      # M^T dy
        # carried in: y += exp(cum_t) C_t S_prev
        out = jnp.exp(cum_col)
        dcum_col = out * jnp.sum(dyf * cs_scr[:, ch], axis=1, keepdims=True)
        dyd = (dyf * out).astype(dtype)
        dyd_scr[:, ch] = dyd
        # handed on: S_end = keep S_prev + (x u)^T B, u = to_end dt
        u = to_end * dt_col
        bds = bds_scr[:, ch]
        dx = dx + u * bds
        du = jnp.sum(xf * bds, axis=1, keepdims=True)
        xs_scr[:, ch] = (xf * u).astype(dtype)
        dcum_col = dcum_col - du * u
        at_end = jnp.sum(du * u, axis=0, keepdims=True) + keep1 * jnp.sum(
            jnp.sum(ds_scr[ch, :] * st_ref[ch, :], axis=1, keepdims=True),
            axis=0, keepdims=True)                            # [1, 1]
        dx = dx + d_ref[j:j + 1, :p] * dyf
        dx_ref[:, ch] = dx.astype(dx_ref.dtype)
        ddt_ref[j:j + 1, :] = jnp.sum(zp, axis=0, keepdims=True) \
            + _to_row(du * to_end, eye)
        dcum_ref[j:j + 1, :] = _to_row(
            jnp.sum(z, axis=1, keepdims=True) + dcum_col, eye) \
            - jnp.sum(z, axis=0, keepdims=True) \
            + jnp.where(_last(1, q), at_end, 0.0)
        ddx_ref[j:j + 1, :] = _to_row(
            jnp.sum(dyf * xf, axis=1, keepdims=True), eye)
        ds_new[ch, :] = keep * ds_scr[ch, :] + _dot(dyd, cm, _TN)
    dgm = dgm.astype(dtype)
    dc_ref[...] = _dot(dgm, bm, _NN) + _dot(
        dyd_scr[...], st_ref[...].astype(dtype), _NN)
    db_ref[...] = _dot(dgm, cm, _TN) + _dot(
        xs_scr[...], ds_scr[...].astype(dtype), _NN)
    ds_scr[...] = ds_new[...]


def _specs(bs, t, h, p, g, n, q, reverse):
    """Block specs of the operands both kernels read, on the grid
    (batch, group, chunk); `reverse` walks the chunks from the last."""
    hg, nc = h // g, t // q
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    tokens = pl.BlockSpec((None, q, hg * p),
                          lambda bi, gi, ci: (bi, at(ci), gi))
    group = pl.BlockSpec((None, q, n), lambda bi, gi, ci: (bi, at(ci), gi))
    heads = pl.BlockSpec((None, hg, q), lambda bi, gi, ci: (bi, gi, at(ci)))
    skip = pl.BlockSpec((hg, 128), lambda bi, gi, ci: (gi, 0))
    state = pl.BlockSpec((None, None, hg * p, n),
                         lambda bi, gi, ci: (bi, at(ci), gi, 0))
    return tokens, group, heads, skip, state


def _rows(x, dt, cum, b, c, d):
    """The operands as the kernels take them: tokens by channels, the
    per-head scalars with the tokens on the lanes."""
    bs, t, h, p = x.shape
    return (x.reshape(bs, t, h * p), b.reshape(bs, t, -1),
            c.reshape(bs, t, -1), jnp.swapaxes(dt, 1, 2),
            jnp.swapaxes(cum, 1, 2),
            jnp.broadcast_to(d.astype(_F32)[:, None], (h, 128)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd_call(x, dt, cum, b, c, d, q):
    bs, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    hg, nc = h // g, t // q
    tokens, group, heads, skip, state = _specs(bs, t, h, p, g, n, q, False)
    y, states = pl.pallas_call(
        functools.partial(_fwd_kernel, hg=hg, p=p, q=q),
        name="mamba2_ssd_fwd", grid=(bs, g, nc),
        in_specs=[tokens, group, group, heads, heads, skip],
        out_specs=[tokens, state],
        out_shape=[jax.ShapeDtypeStruct((bs, t, h * p), x.dtype),
                   jax.ShapeDtypeStruct((bs, nc, h * p, n), _F32)],
        scratch_shapes=[pltpu.VMEM((hg * p, n), _F32),
                        pltpu.VMEM((q, hg * p), _F32)],
        compiler_params=_PARAMS, interpret=registry.interpret(),
    )(*_rows(x, dt, cum, b, c, d))
    return y.reshape(x.shape), states.reshape(bs, nc, h, p, n)


def _bwd_call(x, dt, cum, b, c, d, states, dy, q):
    bs, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    hg, nc = h // g, t // q
    tokens, group, heads, skip, state = _specs(bs, t, h, p, g, n, q, True)
    wide = functools.partial(pltpu.VMEM, (q, hg * p))
    per_head = jax.ShapeDtypeStruct((bs, h, t), _F32)
    per_group = jax.ShapeDtypeStruct((bs, t, g * n), _F32)
    dx, db, dc, ddt, dcum, ddx = pl.pallas_call(
        functools.partial(_bwd_kernel, hg=hg, p=p, q=q),
        name="mamba2_ssd_bwd", grid=(bs, g, nc),
        in_specs=[tokens, group, group, heads, heads, skip, state, tokens],
        out_specs=[tokens, group, group, heads, heads, heads],
        out_shape=[jax.ShapeDtypeStruct((bs, t, h * p), x.dtype),
                   per_group, per_group, per_head, per_head, per_head],
        scratch_shapes=[pltpu.VMEM((hg * p, n), _F32),
                        pltpu.VMEM((hg * p, n), _F32),
                        wide(_F32), wide(_F32), wide(x.dtype),
                        wide(x.dtype)],
        compiler_params=_PARAMS, interpret=registry.interpret(),
    )(*_rows(x, dt, cum, b, c, d), states.reshape(bs, nc, h * p, n),
      dy.reshape(bs, t, h * p))
    return (dx.reshape(x.shape), db.reshape(b.shape), dc.reshape(c.shape),
            jnp.swapaxes(ddt, 1, 2), jnp.swapaxes(dcum, 1, 2),
            jnp.swapaxes(ddx, 1, 2))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def use_kernels(x, b) -> bool:
    """One decision an op, counted under `mamba2_ssd`: the two Pallas
    kernels (`custom`) or the `jax.numpy` chunked form (`lowered`)."""
    if not registry.routable("mamba2_ssd"):
        return False
    return registry.select(
        "mamba2_ssd", registry.signature("mamba2_ssd", x, b)) is not None


def _padded(q, *arrays):
    t = arrays[0].shape[1]
    pad = -t % q
    return t, tuple(_pad_tokens(a, pad) for a in arrays)


def ssd(x, dt, a, b, c, d, kernels, chunk=CHUNK):
    """x [B, T, H, P]; dt [B, T, H] float32 (> 0); a [H] float32 (< 0);
    b, c [B, T, G, N] in x's type; d [H] float32. Returns (y [B, T, H, P]
    in x's type, the states at each chunk's start float32 [B, ceil(T /
    chunk), H, P, N], which `ssd_grad` takes). A sequence that is no
    multiple of the chunk is padded with tokens of dt = 0."""
    t, (x, dt, b, c) = _padded(chunk, x, dt.astype(_F32), b, c)
    a, d = a.astype(_F32), d.astype(_F32)
    if kernels:
        y, states = _fwd_call(x, dt, _chunk_cumsum(dt, a, chunk), b, c, d,
                              chunk)
    else:
        y, states = _lowered(x, dt, a, b, c, d, chunk)
    return y[:, :t], states


def ssd_grad(x, dt, a, b, c, d, states, dy, kernels, chunk=CHUNK):
    """The gradients of `ssd`'s y for (x, dt, a, b, c, d), float32 but dx,
    which is in x's type."""
    a, d = a.astype(_F32), d.astype(_F32)
    if not kernels:
        def f(x, dt, a, b, c, d):
            return ssd(x, dt, a, b, c, d, False, chunk)[0]
        return jax.vjp(f, x, dt.astype(_F32), a, b, c, d)[1](dy)
    t, (x, dt, b, c, dy) = _padded(chunk, x, dt.astype(_F32), b, c, dy)
    dx, db, dc, ddt, dcum, ddx = _bwd_call(
        x, dt, _chunk_cumsum(dt, a, chunk), b, c, d, states, dy, chunk)
    # cum is a cumulative sum of dt * a within the chunk
    dda = _chunk_rev_cumsum(dcum, chunk)
    ddt = ddt + dda * a[None, None, :]
    return (dx[:, :t], ddt[:, :t], jnp.sum(dda * dt, axis=(0, 1)),
            db[:, :t], dc[:, :t], jnp.sum(ddx, axis=(0, 1)))


def _eligible(sig: registry.Signature) -> bool:
    """Shapes Mosaic can tile: a group's heads a whole sublane tile and
    their channels whole lane blocks, the state's width a lane block
    (the interpreter takes any); a head no wider than the lane block its
    skip weight D arrives in."""
    (_, _, h, p), (_, _, g, n) = sig.shapes[0], sig.shapes[1]
    if sig.dtypes[0] not in ("bfloat16", "float32") \
            or sig.dtypes[0] != sig.dtypes[1] or h % g or p > 128:
        return False
    hg = h // g
    return registry._INTERPRET or (
        (hg % 8 == 0 or g == 1) and (hg * p) % 128 == 0 and n % 128 == 0
        and p % 8 == 0)


registry.register_kernel(
    "mamba2_ssd", op_types=("mamba2_ssd",), eligible=_eligible, run=ssd,
    doc="Mamba-2's state-space scan in chunks of 128 tokens (fwd, bwd): "
        "matrix products within a chunk, one [P, N] float32 state a head "
        "in VMEM from chunk to chunk")
