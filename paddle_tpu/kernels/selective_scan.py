"""Mamba-1's selective scan (Pallas), forward and backward.

A Mamba-1 mixer carries a state h [C, N] along the sequence, one row of
N states a channel:

  h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) (x) B_t          (h_0 = 0)
  y_t = h_t C_t + D * x_t

x_t and dt_t [C] (dt > 0, one step a CHANNEL), A [C, N] (< 0, one decay
rate a channel AND a state), B_t and C_t [N] shared by every channel, D
[C]. Mamba-2's chunked matrix form (kernels/mamba2_ssd.py) rests on one
scalar decay a head: the decay between two tokens is then one number,
and a chunk's outputs are a masked matrix product. Here the decay
differs along the state axis too, so no such product exists; the scan
is an elementwise recurrence, and the work of a token is O(C N) on the
VPU. XLA's `associative_scan` of it would write a [T, C, N] float32
state at each of its log T levels (1.34 GB at T = 4,096, C = 5,120,
N = 16).

Layout: the state is float32 [N, channels] in VMEM, the channels on the
lanes and the N states on the sublanes. A grid step is (batch, a block
of channels, a chunk of CHUNK tokens), the chunks in order; within a
chunk the tokens go GROUP at a time (a bfloat16 sublane tile of x, dt
and y loaded and stored whole), one token after another inside a group.
B_t and C_t arrive as rows [1, N] and are turned into columns [N, 1]
against an identity mask, so nothing is transposed.

  selective_scan_fwd   y and the state at each chunk's START ([T /
      CHUNK, N, C] float32 a sequence), which is all the backward needs.
  selective_scan_bwd   the chunks in REVERSE: the chunk's states made
      again from its start into VMEM, then its tokens backwards carrying
      the gradient of the state, g_t = C_t dy_t + exp(dt_{t+1} A) *
      g_{t+1}: dx, d dt, dB and dC (each summed over its channel block
      here, over the blocks by the entry point), dA and dD.

dt, A, B, C, the state and every exp are float32; x and y are in x's
type (bfloat16 under mixed precision).

Routing. `select()`-governed like the other kernels (kernels/
registry.py), ONE decision an op counted under `selective_scan`: off
the CPU, when not denied and where the channels tile, the kernels run;
otherwise the `lowered` path computes the same recurrence with
`jax.numpy`, a `lax.scan` over the chunks whose body (a `lax.scan` over
the chunk's tokens) is checkpointed, so that neither path ever holds a
state for every token; its backward is `jax.vjp`'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

CHUNK = 128         # tokens a grid step
GROUP = 16          # tokens loaded and stored together
FWD_CHANNELS = 512  # channels a forward grid step
BWD_CHANNELS = 256  # channels a backward grid step (more live state)
_F32 = jnp.float32

__all__ = ["CHUNK", "scan", "scan_grad", "use_kernels"]


def _pad_tokens(a, pad):
    """Zero tokens at the end of axis 1: with dt = 0 a token neither
    decays the state nor adds to it."""
    if not pad:
        return a
    return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))


def _padded(q, *arrays):
    t = arrays[0].shape[1]
    return t, tuple(_pad_tokens(a, -t % q) for a in arrays)


def _channel_block(c, want):
    """The widest of want, want / 2, .., 128 channels that divides c; c
    itself where none does (the interpreter's small shapes)."""
    while want >= 128:
        if c % want == 0:
            return want
        want //= 2
    return c


# ---------------------------------------------------------------------------
# lowered path: the same recurrence by XLA, chunk by chunk
# ---------------------------------------------------------------------------

def _lowered(x, dt, a, b, c, d, q):
    """(y [B, T, C] in x's type, chunk-start states [B, T / q, N, C]
    float32); T a multiple of q."""
    bs, t, ch = x.shape
    nc = t // q

    def chunks(v):
        return jnp.moveaxis(v.reshape((bs, nc, q) + v.shape[2:]), 1, 0)

    def token(h, z):
        x_t, dt_t, b_t, c_t = z
        h = jnp.exp(dt_t[:, :, None] * a[None]) * h \
            + (dt_t * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def chunk(h, z):
        h_end, y = lax.scan(token, h, tuple(jnp.moveaxis(v, 1, 0)
                                            for v in z))
        return h_end, (jnp.moveaxis(y, 0, 1), h)

    _, (y, states) = lax.scan(
        chunk, jnp.zeros((bs, ch, a.shape[1]), _F32),
        tuple(chunks(v) for v in (x.astype(_F32), dt, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bs, t, ch) + d * x.astype(_F32)
    return y.astype(x.dtype), jnp.swapaxes(jnp.moveaxis(states, 0, 1), 2, 3)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _eye(n):
    return lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == lax.broadcasted_iota(jnp.int32, (n, n), 1)


def _col(row, eye):
    """[1, N] -> [N, 1] without a transpose."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """[N, 1] -> [1, N]."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _group(ref, base):
    return ref[pl.ds(base, GROUP), :].astype(_F32)


def _fwd_kernel(x_ref, dt_ref, b_ref, c_ref, at_ref, d_ref, y_ref, st_ref,
                h_scr, ys_scr, *, q, n):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr)

    st_ref[...] = h_scr[...]
    at, eye = at_ref[...], _eye(n)

    def group(gi, h):
        base = pl.multiple_of(gi * GROUP, GROUP)
        xg, dg = _group(x_ref, base), _group(dt_ref, base)
        bg, cg = _group(b_ref, base), _group(c_ref, base)
        ug = dg * xg
        for u in range(GROUP):
            h = jnp.exp(dg[u:u + 1] * at) * h \
                + ug[u:u + 1] * _col(bg[u:u + 1], eye)
            ys_scr[u:u + 1, :] = jnp.sum(h * _col(cg[u:u + 1], eye),
                                         axis=0, keepdims=True)
        y_ref[pl.ds(base, GROUP), :] = (
            ys_scr[...] + d_ref[...] * xg).astype(y_ref.dtype)
        return h

    h_scr[...] = lax.fori_loop(0, q // GROUP, group, h_scr[...])


def _bwd_kernel(x_ref, dt_ref, b_ref, c_ref, at_ref, d_ref, st_ref, dy_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                g_scr, hs_scr, gx_scr, gd_scr, gb_scr, gc_scr, *, q, n):
    """One chunk of the backward. `g_scr` carries the gradient that
    flows into the state at this chunk's END from the chunks after it
    (zero after the last); `hs_scr[s]` holds the state after the chunk's
    s-th token, `hs_scr[0]` the chunk's start. dA and dD accumulate in
    their output blocks over the chunks."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    at, eye = at_ref[...], _eye(n)
    hs_scr[0] = st_ref[...]

    def forward(gi, h):
        base = pl.multiple_of(gi * GROUP, GROUP)
        xg, dg, bg = (_group(r, base) for r in (x_ref, dt_ref, b_ref))
        ug = dg * xg
        for u in range(GROUP):
            h = jnp.exp(dg[u:u + 1] * at) * h \
                + ug[u:u + 1] * _col(bg[u:u + 1], eye)
            hs_scr[base + u + 1] = h
        return h

    lax.fori_loop(0, q // GROUP, forward, st_ref[...])

    def backward(k, carry):
        g, da, dd = carry
        base = pl.multiple_of((q // GROUP - 1 - k) * GROUP, GROUP)
        xg, dg, bg, cg, dyg = (_group(r, base) for r in (
            x_ref, dt_ref, b_ref, c_ref, dy_ref))
        ug = dg * xg
        for u in reversed(range(GROUP)):
            dyr, dr = dyg[u:u + 1], dg[u:u + 1]
            h, hp = hs_scr[base + u + 1], hs_scr[base + u]
            g = g + _col(cg[u:u + 1], eye) * dyr        # dL / dh_t
            gc_scr[u:u + 1, :] = _row(
                jnp.sum(h * dyr, axis=1, keepdims=True), eye)
            gb_scr[u:u + 1, :] = _row(
                jnp.sum(g * ug[u:u + 1], axis=1, keepdims=True), eye)
            du = jnp.sum(g * _col(bg[u:u + 1], eye), axis=0, keepdims=True)
            a = jnp.exp(dr * at)
            ga = g * hp * a                             # dL / d(dt A)
            gd_scr[u:u + 1, :] = du * xg[u:u + 1] \
                + jnp.sum(ga * at, axis=0, keepdims=True)
            gx_scr[u:u + 1, :] = du * dr
            da = da + ga * dr
            g = a * g
        dx_ref[pl.ds(base, GROUP), :] = (
            gx_scr[...] + d_ref[...] * dyg).astype(dx_ref.dtype)
        ddt_ref[pl.ds(base, GROUP), :] = gd_scr[...]
        db_ref[pl.ds(base, GROUP), :] = gb_scr[...]
        dc_ref[pl.ds(base, GROUP), :] = gc_scr[...]
        return g, da, dd + jnp.sum(dyg * xg, axis=0, keepdims=True)

    g, da, dd = lax.fori_loop(
        0, q // GROUP, backward,
        (g_scr[...], jnp.zeros(g_scr.shape, _F32),
         jnp.zeros(dd_ref.shape, _F32)))
    g_scr[...] = g
    da_ref[...] += da
    dd_ref[...] += dd


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _specs(t, n, cb, q, reverse):
    """Block specs on the grid (batch, channel block, chunk); `reverse`
    walks the chunks from the last."""
    nc = t // q
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    tokens = pl.BlockSpec((None, q, cb), lambda bi, j, ci: (bi, at(ci), j))
    shared = pl.BlockSpec((None, q, n), lambda bi, j, ci: (bi, at(ci), 0))
    rate = pl.BlockSpec((n, cb), lambda bi, j, ci: (0, j))
    skip = pl.BlockSpec((1, cb), lambda bi, j, ci: (0, j))
    state = pl.BlockSpec((None, None, n, cb),
                         lambda bi, j, ci: (bi, at(ci), 0, j))
    return tokens, shared, rate, skip, state


def _fwd_call(x, dt, a, b, c, d, q):
    bs, t, ch = x.shape
    n = a.shape[1]
    cb = _channel_block(ch, FWD_CHANNELS)
    tokens, shared, rate, skip, state = _specs(t, n, cb, q, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, q=q, n=n),
        name="selective_scan_fwd", grid=(bs, ch // cb, t // q),
        in_specs=[tokens, tokens, shared, shared, rate, skip],
        out_specs=[tokens, state],
        out_shape=[jax.ShapeDtypeStruct((bs, t, ch), x.dtype),
                   jax.ShapeDtypeStruct((bs, t // q, n, ch), _F32)],
        scratch_shapes=[pltpu.VMEM((n, cb), _F32),
                        pltpu.VMEM((GROUP, cb), _F32)],
        compiler_params=_PARAMS, interpret=registry.interpret(),
    )(x, dt, b, c, a.T, d.reshape(1, ch))


def _bwd_call(x, dt, a, b, c, d, states, dy, q):
    bs, t, ch = x.shape
    n = a.shape[1]
    cb = _channel_block(ch, BWD_CHANNELS)
    nb, nc = ch // cb, t // q
    tokens, shared, rate, skip, state = _specs(t, n, cb, q, True)
    partial = pl.BlockSpec((None, None, q, n),
                           lambda bi, j, ci: (bi, j, nc - 1 - ci, 0))
    acc = pl.BlockSpec((None, n, cb), lambda bi, j, ci: (bi, 0, j))
    acc1 = pl.BlockSpec((None, 1, cb), lambda bi, j, ci: (bi, 0, j))
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=_F32)
    dx, ddt, db, dc, da, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, q=q, n=n),
        name="selective_scan_bwd", grid=(bs, nb, nc),
        in_specs=[tokens, tokens, shared, shared, rate, skip, state, tokens],
        out_specs=[tokens, tokens, partial, partial, acc, acc1],
        out_shape=[jax.ShapeDtypeStruct((bs, t, ch), x.dtype),
                   f32((bs, t, ch)), f32((bs, nb, t, n)),
                   f32((bs, nb, t, n)), f32((bs, n, ch)), f32((bs, 1, ch))],
        scratch_shapes=[pltpu.VMEM((n, cb), _F32),
                        pltpu.VMEM((q + 1, n, cb), _F32),
                        pltpu.VMEM((GROUP, cb), _F32),
                        pltpu.VMEM((GROUP, cb), _F32),
                        pltpu.VMEM((GROUP, n), _F32),
                        pltpu.VMEM((GROUP, n), _F32)],
        compiler_params=_PARAMS, interpret=registry.interpret(),
    )(x, dt, b, c, a.T, d.reshape(1, ch), states, dy)
    return (dx, ddt, jnp.sum(da, axis=0).T, jnp.sum(db, axis=1),
            jnp.sum(dc, axis=1), jnp.sum(dd, axis=(0, 1)))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def use_kernels(x, a) -> bool:
    """One decision an op, counted under `selective_scan`: the two
    Pallas kernels (`custom`) or the `jax.numpy` recurrence
    (`lowered`)."""
    if not registry.routable("selective_scan"):
        return False
    return registry.select(
        "selective_scan",
        registry.signature("selective_scan", x, a)) is not None


def _check_chunk(chunk):
    if chunk % GROUP:
        raise ValueError(f"the kernels take chunks of a multiple of {GROUP} "
                         f"tokens, not {chunk}")


def scan(x, dt, a, b, c, d, kernels, chunk=CHUNK):
    """x [B, T, C]; dt [B, T, C] float32 (> 0); a [C, N] float32 (< 0);
    b, c [B, T, N]; d [C]. Returns (y [B, T, C] in x's type, the states
    at each chunk's start float32 [B, ceil(T / chunk), N, C], which
    `scan_grad` takes). A sequence that is no multiple of the chunk is
    padded with tokens of dt = 0."""
    if kernels:
        _check_chunk(chunk)
    t, (x, dt, b, c) = _padded(chunk, x, dt.astype(_F32), b.astype(_F32),
                               c.astype(_F32))
    call = _fwd_call if kernels else _lowered
    y, states = call(x, dt, a.astype(_F32), b, c, d.astype(_F32), chunk)
    return y[:, :t], states


def scan_grad(x, dt, a, b, c, d, states, dy, kernels, chunk=CHUNK):
    """The gradients of `scan`'s y for (x, dt, a, b, c, d): dx in x's
    type, the others float32."""
    a, d = a.astype(_F32), d.astype(_F32)
    if not kernels:
        def f(x, dt, a, b, c, d):
            return scan(x, dt, a, b, c, d, False, chunk)[0]
        return jax.vjp(f, x, dt.astype(_F32), a, b.astype(_F32),
                       c.astype(_F32), d)[1](dy)
    _check_chunk(chunk)
    t, (x, dt, b, c, dy) = _padded(chunk, x, dt.astype(_F32),
                                   b.astype(_F32), c.astype(_F32),
                                   dy.astype(x.dtype))
    dx, ddt, da, db, dc, dd = _bwd_call(x, dt, a, b, c, d, states, dy, chunk)
    return dx[:, :t], ddt[:, :t], da, db[:, :t], dc[:, :t], dd


def _eligible(sig: registry.Signature) -> bool:
    """Shapes Mosaic can tile: the channels whole lane blocks and the
    states a whole sublane tile (the interpreter takes any)."""
    (_, _, ch), (_, n) = sig.shapes[0], sig.shapes[1]
    if sig.dtypes[0] not in ("bfloat16", "float32"):
        return False
    return registry._INTERPRET or (ch % 128 == 0 and n % 8 == 0)


registry.register_kernel(
    "selective_scan", op_types=("selective_scan",), eligible=_eligible,
    run=scan,
    doc="Mamba-1's selective scan in chunks of 128 tokens (fwd, bwd): an "
        "elementwise recurrence, one [N, channels] float32 state a channel "
        "block in VMEM from token to token")
