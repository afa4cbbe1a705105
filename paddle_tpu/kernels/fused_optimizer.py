"""Fused optimizer-update Pallas kernels (Adam / SGD).

The lowered optimizer path materializes every intermediate of the Adam
recurrence (m', v', sqrt, quotient, ...) as its own HLO op; XLA fuses
most of it, but each parameter still costs one loop over HBM per fusion
root and the moments round-trip at f32.  This kernel does the whole
update — moment EMAs, bias-corrected step, decoupled weight decay, the
stability-guard gate, and the ZeRO-1 shard mask — in a single VMEM pass
per (block_rows, 128) tile: read p/g/m/v once, write p'/m'/v' once.

Two entry surfaces:

* per-op (:func:`fused_adam` / :func:`fused_sgd`) — registered in the
  kernel registry under the ``adam``/``sgd`` op types, selected inside
  ``ops/optimizer_ops.py`` lowerings.  Math is element-for-element the
  host lowering's (same operation order), so parity holds at a few ulp.
  The stability guard composes untouched: its gate runs *after* op
  lowerings, over the env's updated values (stability/guard.py).
* bucket (:func:`bucket_sweep`) — sweeps a comm-scheduler
  ``GradBucket`` flat view and optionally applies the guard gate and a
  ZeRO-1 shard mask in-kernel.  The shard mask keys off traced
  ``(shard_index, num_shards)`` scalars in SMEM, so the same compiled
  kernel serves every replica of a ``sharded_update_spec`` layout: each
  replica writes only its row slice, rows outside pass old values
  through unchanged (a replica-local no-op, like the sharded host
  update).

Update formulas (must match ops/optimizer_ops.py exactly, see
kernels/parity.py):

  adam:  lr_t  = lr * sqrt(1 - b2^t) / (1 - b1^t)        (host-side)
         m'    = b1*m + (1-b1)*g
         v'    = b2*v + (1-b2)*g*g
         p'    = p - (lr_t * m' / (sqrt(v') + eps) + lr_t*wd*p)
  sgd:   p'    = p - lr * (g + wd*p)

(wd = decoupled weight decay, 0 on the host ops — kept for the bucket
surface.)  Guard gate (must match stability/guard.py:_gate_value):

  gated = where(nonfinite, old,
          where(spike, old + (new - old)*damp, new))

Padding tail (flat size -> rows of 128 lanes) runs the same math on
zeros — finite, and masked rows always rewrite old values — so no
NaN/garbage ever lands in the output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

_LANES = 128
_BLOCK_ROWS = 256  # 256x128 f32 = 128 KiB per operand block in VMEM

__all__ = ["fused_adam", "fused_sgd", "bucket_sweep"]


# ---------------------------------------------------------------------------
# flat <-> (rows, 128) padding
# ---------------------------------------------------------------------------

def _rows_padded(n: int) -> int:
    rows = -(-n // _LANES)
    return -(-rows // _BLOCK_ROWS) * _BLOCK_ROWS


def _to2d(flat):
    n = flat.shape[0]
    rows = _rows_padded(n)
    pad = rows * _LANES - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, _LANES)


def _from2d(x2d, n: int):
    return x2d.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _gate(new, old, nf, sp, damp):
    """stability/guard.py _gate_value, elementwise in-kernel."""
    damped = old + (new - old) * damp
    return jnp.where(nf, old, jnp.where(sp, damped, new))


def _row_mask(bounds_ref, block_rows):
    i = pl.program_id(0)
    rows = i * block_rows + jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, _LANES), 0)
    return (rows >= bounds_ref[0, 0]) & (rows < bounds_ref[0, 1])


def _adam_block(hyper_ref, bounds_ref, p_ref, g_ref, m_ref, v_ref,
                po_ref, mo_ref, vo_ref, *, b1, b2, eps, wd,
                block_rows, gated):
    p, g, m, v = p_ref[:], g_ref[:], m_ref[:], v_ref[:]
    lr_t = hyper_ref[0, 0]
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    # grouping matches ops/optimizer_ops.py adam: (lr_t*m') / (...)
    upd = lr_t * m_new / (jnp.sqrt(v_new) + eps)
    if wd:
        upd = upd + lr_t * wd * p
    p_new = p - upd
    if gated:
        nf = hyper_ref[0, 1] > 0.0
        sp = hyper_ref[0, 2] > 0.0
        damp = hyper_ref[0, 3]
        p_new = _gate(p_new, p, nf, sp, damp)
        m_new = _gate(m_new, m, nf, sp, damp)
        v_new = _gate(v_new, v, nf, sp, damp)
    inside = _row_mask(bounds_ref, block_rows)
    po_ref[:] = jnp.where(inside, p_new, p)
    mo_ref[:] = jnp.where(inside, m_new, m)
    vo_ref[:] = jnp.where(inside, v_new, v)


def _sgd_block(hyper_ref, bounds_ref, p_ref, g_ref, po_ref, *, wd,
               block_rows, gated):
    p, g = p_ref[:], g_ref[:]
    lr = hyper_ref[0, 0]
    if wd:
        g = g + wd * p
    p_new = p - lr * g
    if gated:
        p_new = _gate(p_new, p, hyper_ref[0, 1] > 0.0,
                      hyper_ref[0, 2] > 0.0, hyper_ref[0, 3])
    inside = _row_mask(bounds_ref, block_rows)
    po_ref[:] = jnp.where(inside, p_new, p)


def _call(name, body, hyper, bounds, bufs, n_out, block_rows):
    rows = bufs[0].shape[0]
    grid = (rows // block_rows,)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        body,
        name=name,
        grid=grid,
        in_specs=[smem, smem] + [tile] * len(bufs),
        out_specs=[tile] * n_out if n_out > 1 else tile,
        out_shape=([jax.ShapeDtypeStruct(bufs[0].shape, bufs[0].dtype)]
                   * n_out if n_out > 1
                   else jax.ShapeDtypeStruct(bufs[0].shape,
                                             bufs[0].dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=registry.interpret(),
    )(hyper, bounds, *bufs)
    return out if n_out > 1 else (out,)


def _hyper(lr_t, guard):
    if guard is None:
        nf = sp = damp = 0.0
    else:
        nf, sp, damp = guard
    return jnp.stack([
        jnp.asarray(lr_t, jnp.float32).reshape(()),
        jnp.asarray(nf, jnp.float32).reshape(()),
        jnp.asarray(sp, jnp.float32).reshape(()),
        jnp.asarray(damp, jnp.float32).reshape(()),
    ]).reshape(1, 4)


def _bounds(rows: int, shard):
    if shard is None:
        lo = jnp.int32(0)
        hi = jnp.int32(rows)
    else:
        idx, num = shard
        if rows % num:
            raise ValueError(
                "bucket rows (%d) not divisible by num_shards (%d); pad "
                "the bucket to num_shards*128 elements" % (rows, num))
        per = rows // num
        lo = (jnp.asarray(idx, jnp.int32) * per).reshape(())
        hi = lo + per
    return jnp.stack([lo, hi]).reshape(1, 2)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def fused_adam(p, g, m, v, lr_t, *, beta1=0.9, beta2=0.999,
               epsilon=1e-8, weight_decay=0.0):
    """One-shot Adam update on one parameter; shapes/dtypes preserved.

    ``lr_t`` is the bias-corrected rate (host side keeps the
    lr*sqrt(1-b2^t)/(1-b1^t) fold so the beta-pow recurrence stays in
    the lowering).  Returns (p', m', v').
    """
    shape = p.shape
    n = p.size
    bufs = [_to2d(x.reshape(-1)) for x in (p, g, m, v)]
    body = functools.partial(_adam_block, b1=float(beta1),
                             b2=float(beta2), eps=float(epsilon),
                             wd=float(weight_decay),
                             block_rows=_BLOCK_ROWS, gated=False)
    po, mo, vo = _call("fused_adam", body, _hyper(lr_t, None),
                       _bounds(bufs[0].shape[0], None), bufs, 3,
                       _BLOCK_ROWS)
    return (_from2d(po, n).reshape(shape),
            _from2d(mo, n).reshape(shape),
            _from2d(vo, n).reshape(shape))


def fused_sgd(p, g, lr, *, weight_decay=0.0):
    """One-shot SGD update on one parameter; shape/dtype preserved."""
    shape = p.shape
    n = p.size
    bufs = [_to2d(x.reshape(-1)) for x in (p, g)]
    body = functools.partial(_sgd_block, wd=float(weight_decay),
                             block_rows=_BLOCK_ROWS, gated=False)
    (po,) = _call("fused_sgd", body, _hyper(lr, None),
                  _bounds(bufs[0].shape[0], None), bufs, 1,
                  _BLOCK_ROWS)
    return _from2d(po, n).reshape(shape)


def bucket_sweep(kind, flat_param, flat_grad, flat_m=None, flat_v=None,
                 *, lr, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 beta1_pow=None, beta2_pow=None, weight_decay=0.0,
                 shard=None, guard=None):
    """Apply one optimizer step over a bucketed flat view.

    kind        "adam" | "sgd".
    flat_*      1-D f32 views, the comm scheduler's ``GradBucket``
                concatenation order (param/grad, plus m/v for adam).
    lr          learning rate; for adam the bias correction is folded
                here when ``beta{1,2}_pow`` are given.
    shard       optional ``(shard_index, num_shards)`` — traced scalars
                are fine.  Each replica updates only rows
                [idx*rows/num, (idx+1)*rows/num); rows outside pass old
                values through (the ZeRO-1 replica-local no-op).  The
                padded row count must divide by num_shards.
    guard       optional ``(nonfinite, spike, damp)`` traced scalars;
                the in-kernel gate is stability/guard.py _gate_value
                (pass damp=0.0 for the skip/rollback revert policies).

    Returns p' for sgd, (p', m', v') for adam.
    """
    gated = guard is not None
    n = flat_param.shape[0]
    if kind == "adam":
        lr_t = lr
        if beta1_pow is not None and beta2_pow is not None:
            b1p = jnp.asarray(beta1_pow, jnp.float32).reshape(())
            b2p = jnp.asarray(beta2_pow, jnp.float32).reshape(())
            lr_t = lr * jnp.sqrt(1.0 - b2p) / (1.0 - b1p)
        bufs = [_to2d(x) for x in (flat_param, flat_grad, flat_m,
                                   flat_v)]
        body = functools.partial(_adam_block, b1=float(beta1),
                                 b2=float(beta2), eps=float(epsilon),
                                 wd=float(weight_decay),
                                 block_rows=_BLOCK_ROWS, gated=gated)
        po, mo, vo = _call("fused_adam", body, _hyper(lr_t, guard),
                           _bounds(bufs[0].shape[0], shard), bufs, 3,
                           _BLOCK_ROWS)
        return _from2d(po, n), _from2d(mo, n), _from2d(vo, n)
    if kind == "sgd":
        bufs = [_to2d(x) for x in (flat_param, flat_grad)]
        body = functools.partial(_sgd_block, wd=float(weight_decay),
                                 block_rows=_BLOCK_ROWS, gated=gated)
        (po,) = _call("fused_sgd", body, _hyper(lr, guard),
                      _bounds(bufs[0].shape[0], shard), bufs, 1,
                      _BLOCK_ROWS)
        return _from2d(po, n)
    raise ValueError("bucket_sweep kind must be adam|sgd, got %r"
                     % (kind,))


# ---------------------------------------------------------------------------
# registry entries
# ---------------------------------------------------------------------------

def _dense_f32(sig: registry.Signature) -> bool:
    return (all(dt == "float32" for dt in sig.dtypes)
            and sig.numel >= registry.min_numel())


registry.register_kernel(
    "fused_adam", op_types=("adam",), eligible=_dense_f32,
    run=fused_adam, source_tag="fused_optimizer.py",
    doc="single-pass Adam update (m/v EMAs + bias-corrected step) per "
        "VMEM tile; dense f32, >= PT_KERNEL_MIN_NUMEL elements")

registry.register_kernel(
    "fused_sgd", op_types=("sgd",), eligible=_dense_f32,
    run=fused_sgd, source_tag="fused_optimizer.py",
    doc="single-pass SGD update per VMEM tile; dense f32, >= "
        "PT_KERNEL_MIN_NUMEL elements")
