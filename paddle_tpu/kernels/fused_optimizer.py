"""Fused optimizer-update Pallas kernels (Adam / SGD).

The lowered optimizer path materializes every intermediate of the Adam
recurrence (m', v', sqrt, quotient, ...) as its own HLO op; XLA fuses
most of it, but each parameter still costs one loop over HBM per fusion
root and the moments round-trip at f32.  This kernel does the whole
update — moment EMAs, bias-corrected step, decoupled weight decay, the
stability-guard gate, and the ZeRO-1 shard mask — in a single VMEM pass
per block: read p/g/m/v once, write p'/m'/v' once, the outputs aliased
onto p/m/v so a donated parameter is updated in place.

Two views of an operand, chosen from its shape (docs/KERNELS.md):

* native — a ``[.., K, N]`` operand whose two minor dimensions fill an
  (8, 128) tile is blocked as it lies: viewed ``[L, K, N]`` (collapsing
  leading dimensions leaves the tiled layout alone, so the view is a
  bitcast) under a grid ``(L, K blocks, N blocks)``; Pallas masks the
  partial edge blocks. Nothing is padded, sliced or relaid out.
* flat — ``[rows, 128]``, padded to whole blocks. Free for rank 1 and
  for the bucket surface, which are flat already; for a tiled
  ``[K, N]`` it is a physical permutation that XLA runs as a standalone
  ``reshape`` of every operand and result (seven a parameter), so only
  shapes too narrow to block natively still take it.

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

The flat view's padding tail runs the same math on zeros — finite, and
masked rows always rewrite old values; what a native edge block reads
past the array is never written back (the update is elementwise) — so
no NaN/garbage ever lands in the output.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

_LANES = 128
_SUBLANES = 8
_BLOCK_ROWS = 256  # flat view: 256x128 f32 = 128 KiB per operand block
# native view: 512 KiB per operand block; Adam's 7 blocks x 2 buffers
# are 7 MiB, under the default scoped VMEM limit
_BLOCK_ELEMS = 256 * 512
_WHOLE_ROW_COLS = 1024

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
# native view: blocks over the operand's own [.., K, N] layout
# ---------------------------------------------------------------------------

def _fills_a_tile(shape) -> bool:
    """Whether the two minor dimensions hold at least one (8, 128)
    tile. Under that (a conv filter ``[O, I, 3, 3]``, a ``[K, 7]``) a
    native block would be a few elements and the grid one step each."""
    return (len(shape) >= 2 and shape[-2] >= _SUBLANES
            and shape[-1] >= _LANES)


def _lies_k_minor(k: int, n: int) -> bool:
    """Whether XLA keeps a ``[.., K, N]`` f32 array K-minor on the TPU:
    it does where N is off the 128 lanes and K is on them (no lane is
    padded that way; read off the compiled steps: ``[2048, 576]`` and
    ``[2048, 16032]`` lie ``{0,1}``, ``[16032, 2048]`` ``{1,0}``). The
    kernel then blocks the transpose, which is a bitcast of such an
    array; where the guess is wrong XLA transposes, what the flat view
    cost every operand."""
    return n % _LANES != 0 and k % _LANES == 0


def _native_block(k: int, n: int):
    """(block_rows, block_cols) for a ``[.., K, N]`` operand: columns
    the whole of N while a block of whole rows stays contiguous and
    small, else 512; rows the power of two that keeps the block within
    ``_BLOCK_ELEMS`` (most K divide by it), or the whole of K."""
    cols = n if n <= _WHOLE_ROW_COLS else 512
    lanes = -(-cols // _LANES) * _LANES
    rows = 1 << ((_BLOCK_ELEMS // lanes).bit_length() - 1)
    return (k if k <= rows else rows), cols


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _gate(new, old, nf, sp, damp):
    """stability/guard.py _gate_value, elementwise in-kernel."""
    damped = old + (new - old) * damp
    return jnp.where(nf, old, jnp.where(sp, damped, new))


def _row_mask(bounds_ref):
    i = pl.program_id(0)
    rows = i * _BLOCK_ROWS + jax.lax.broadcasted_iota(
        jnp.int32, (_BLOCK_ROWS, _LANES), 0)
    return (rows >= bounds_ref[0, 0]) & (rows < bounds_ref[0, 1])


def _adam_block(hyper_ref, *refs, b1, b2, eps, wd, gated, sharded):
    if sharded:
        inside = _row_mask(refs[0])
        refs = refs[1:]
    p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref = refs
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
    if sharded:
        p_new = jnp.where(inside, p_new, p)
        m_new = jnp.where(inside, m_new, m)
        v_new = jnp.where(inside, v_new, v)
    po_ref[:] = p_new
    mo_ref[:] = m_new
    vo_ref[:] = v_new


def _sgd_block(hyper_ref, *refs, wd, gated, sharded):
    if sharded:
        inside = _row_mask(refs[0])
        refs = refs[1:]
    p_ref, g_ref, po_ref = refs
    p, g = p_ref[:], g_ref[:]
    lr = hyper_ref[0, 0]
    if wd:
        g = g + wd * p
    p_new = p - lr * g
    if gated:
        p_new = _gate(p_new, p, hyper_ref[0, 1] > 0.0,
                      hyper_ref[0, 2] > 0.0, hyper_ref[0, 3])
    if sharded:
        p_new = jnp.where(inside, p_new, p)
    po_ref[:] = p_new


def _call(name, body, scalars, bufs, updated, grid, block, index_map):
    """One pallas_call over *bufs* (all one shape), blocked as *block*.
    ``updated`` lists the bufs the kernel rewrites, in output order;
    each output aliases its input, so XLA hands a donated buffer to the
    kernel to update in place and copies only an input that stays live
    (eager callers, tests)."""
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)
    out = jax.ShapeDtypeStruct(bufs[0].shape, bufs[0].dtype)
    return pl.pallas_call(
        body,
        name=name,
        grid=grid,
        in_specs=[smem] * len(scalars) + [tile] * len(bufs),
        out_specs=[tile] * len(updated),
        out_shape=[out] * len(updated),
        input_output_aliases={len(scalars) + i: o
                              for o, i in enumerate(updated)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        interpret=registry.interpret(),
    )(*scalars, *bufs)


def _flat_call(name, body, scalars, flats, updated):
    """The kernel over 1-D *flats* through the ``[rows, 128]`` view."""
    n = flats[0].shape[0]
    bufs = [_to2d(x) for x in flats]
    outs = _call(name, body, scalars, bufs, updated,
                 (bufs[0].shape[0] // _BLOCK_ROWS,),
                 (_BLOCK_ROWS, _LANES), lambda i: (i, 0))
    return [_from2d(o, n) for o in outs]


def _per_op(name, body, hyper, operands, updated):
    """One parameter's update in the view its shape allows; which one
    each call site took is counted beside the registry's decision."""
    shape = operands[0].shape
    if not _fills_a_tile(shape):
        registry.count(name, "flat_view")
        outs = _flat_call(name, body, [hyper],
                          [x.reshape(-1) for x in operands], updated)
        return [o.reshape(shape) for o in outs]
    registry.count(name, "native_view")
    k, n = shape[-2:]
    swapped = _lies_k_minor(k, n)
    if swapped:
        operands = [jnp.swapaxes(x, -1, -2) for x in operands]
        k, n = n, k
    lead = math.prod(shape[:-2])
    rows, cols = _native_block(k, n)
    outs = _call(name, body, [hyper],
                 [x.reshape(lead, k, n) for x in operands], updated,
                 (lead, pl.cdiv(k, rows), pl.cdiv(n, cols)),
                 (None, rows, cols), lambda l, i, j: (l, i, j))
    if swapped:
        outs = [jnp.swapaxes(o, -1, -2) for o in outs]
    return [o.reshape(shape) for o in outs]


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
    idx, num = shard
    if rows % num:
        raise ValueError(
            "bucket rows (%d) not divisible by num_shards (%d); pad "
            "the bucket to num_shards*128 elements" % (rows, num))
    per = rows // num
    lo = (jnp.asarray(idx, jnp.int32) * per).reshape(())
    return jnp.stack([lo, lo + per]).reshape(1, 2)


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
    body = functools.partial(_adam_block, b1=float(beta1),
                             b2=float(beta2), eps=float(epsilon),
                             wd=float(weight_decay), gated=False,
                             sharded=False)
    return tuple(_per_op("fused_adam", body, _hyper(lr_t, None),
                         (p, g, m, v), (0, 2, 3)))


def fused_sgd(p, g, lr, *, weight_decay=0.0):
    """One-shot SGD update on one parameter; shape/dtype preserved."""
    body = functools.partial(_sgd_block, wd=float(weight_decay),
                             gated=False, sharded=False)
    return _per_op("fused_sgd", body, _hyper(lr, None), (p, g), (0,))[0]


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
    if kind not in ("adam", "sgd"):
        raise ValueError("bucket_sweep kind must be adam|sgd, got %r"
                         % (kind,))
    opts = dict(gated=guard is not None, sharded=shard is not None)
    if kind == "adam" and beta1_pow is not None and beta2_pow is not None:
        b1p = jnp.asarray(beta1_pow, jnp.float32).reshape(())
        b2p = jnp.asarray(beta2_pow, jnp.float32).reshape(())
        lr = lr * jnp.sqrt(1.0 - b2p) / (1.0 - b1p)
    scalars = [_hyper(lr, guard)]
    if shard is not None:
        scalars.append(
            _bounds(_rows_padded(flat_param.shape[0]), shard))
    if kind == "adam":
        body = functools.partial(_adam_block, b1=float(beta1),
                                 b2=float(beta2), eps=float(epsilon),
                                 wd=float(weight_decay), **opts)
        return tuple(_flat_call(
            "fused_adam", body, scalars,
            [flat_param, flat_grad, flat_m, flat_v], (0, 2, 3)))
    body = functools.partial(_sgd_block, wd=float(weight_decay), **opts)
    return _flat_call("fused_sgd", body, scalars,
                      [flat_param, flat_grad], (0,))[0]


# ---------------------------------------------------------------------------
# registry entries
# ---------------------------------------------------------------------------

def _dense_f32(sig: registry.Signature) -> bool:
    return (all(dt == "float32" for dt in sig.dtypes)
            and sig.numel >= registry.min_numel())


registry.register_kernel(
    "fused_adam", op_types=("adam",), eligible=_dense_f32,
    run=fused_adam,
    doc="single-pass Adam update (m/v EMAs + bias-corrected step) per "
        "VMEM tile; dense f32, >= PT_KERNEL_MIN_NUMEL elements")

registry.register_kernel(
    "fused_sgd", op_types=("sgd",), eligible=_dense_f32,
    run=fused_sgd,
    doc="single-pass SGD update per VMEM tile; dense f32, >= "
        "PT_KERNEL_MIN_NUMEL elements")
