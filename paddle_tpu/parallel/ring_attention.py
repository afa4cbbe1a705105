"""Ring attention: exact attention over a sequence-sharded (sp/cp) axis.

The reference has NO sequence/context parallelism (SURVEY §2.3 item 9 —
2019 snapshot); this is the TPU-native long-context capability the build
treats as first-class: q/k/v sharded along the sequence dim over the
"sp" mesh axis, K/V blocks rotated around the ring with lax.ppermute
(ICI neighbor exchange) while each device accumulates its queries'
attention over every block with online-softmax (logsumexp) merging.

Memory is O(S/n) per chip END-TO-END: the custom_vjp saves only the
local q/k/v blocks plus the [S_local] out/lse residuals, and the
backward RE-ROTATES K/V around the ring a second time, recomputing each
block's probabilities from the saved global logsumexp:

    p_i = exp(q @ k_i^T * scale - lse_global)

is the true global softmax weight for block i, so each step's
dq/dk/dv/dbias contribution is exact; dk/dv accumulators travel around
the ring WITH their K/V block (n rotations total returns every block —
now carrying gradient contributions from all devices — to its owner).
Per-block compute uses the Pallas flash kernels where shapes allow, so
the [Sq, Sk] score matrix never materializes in either pass.

Use under shard_map with q/k/v PartitionSpec'd as [B, H, S/sp, D] (and
batch over dp): `ring_attention(q, k, v, bias, axis_name="sp")`.
bias is [B, 1|H, Sq_local, Sk_GLOBAL] (query rows local, key columns
global). Pass `check_vma=False` to shard_map when the Pallas kernel
path is active (jax 0.9's vma tracking doesn't thread through
pallas_call + ppermute compositions yet).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax



def _block_attn(q, k, v, bias, scale):
    from ..kernels.flash_attention import flash_attention_lse
    return flash_attention_lse(q, k, v, bias, scale, 128, 128)


def _block_bwd(q, k, v, bias, out, lse, di, g, scale):
    """One K/V block's backward against the GLOBAL (out, lse, di)
    residuals. Kernel path when shapes tile onto the MXU, composed
    otherwise. Returns (dq, dk, dv, dbias?) — all f32."""
    from ..kernels.flash_attention import _kernel_ok, _fa_backward
    if _kernel_ok(q, k, 128, 128):
        dq, dk, dv, dbias = _fa_backward(
            q, k, v, bias, out, lse, g, scale, 128, 128)
        return (dq.astype(jnp.float32), dk.astype(jnp.float32),
                dv.astype(jnp.float32), dbias)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    p = jnp.exp(s - lse[..., None])                 # [B,H,Sq,Sk_blk]
    g32 = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, g32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g32, v.astype(jnp.float32))
    ds = p * (dp - di[..., None])
    dq = scale * jnp.einsum("bhqk,bhkd->bhqd", ds,
                            k.astype(jnp.float32))
    dk = scale * jnp.einsum("bhqk,bhqd->bhkd", ds,
                            q.astype(jnp.float32))
    dbias = None
    if bias is not None:
        dbias = ds
        if bias.shape[1] == 1:
            dbias = dbias.sum(axis=1, keepdims=True)
        if bias.shape[2] == 1:
            dbias = dbias.sum(axis=2, keepdims=True)
        dbias = dbias.astype(bias.dtype)
    return dq, dk, dv, dbias


def _ring_forward(q, k, v, bias, axis_name, scale):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_local = k.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]

    out = None
    lse = None
    for step in range(n):
        src = (my - step) % n  # whose K/V block we currently hold
        if bias is not None:
            b = lax.dynamic_slice_in_dim(bias, src * s_local, s_local,
                                         axis=3)
        else:
            b = None
        o_i, lse_i = _block_attn(q, k, v, b, scale)
        if out is None:
            out, lse = o_i.astype(jnp.float32), lse_i
        else:
            new_lse = jnp.logaddexp(lse, lse_i)
            w_old = jnp.exp(lse - new_lse)[..., None]
            w_new = jnp.exp(lse_i - new_lse)[..., None]
            out = out * w_old + o_i.astype(jnp.float32) * w_new
            lse = new_lse
        if step != n - 1:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ring_attention(q, k, v, bias, axis_name, scale):
    out, _ = _ring_forward(q, k, v, bias, axis_name, scale)
    return out.astype(q.dtype)


def _ring_fwd(q, k, v, bias, axis_name, scale):
    out, lse = _ring_forward(q, k, v, bias, axis_name, scale)
    primal = out.astype(q.dtype)
    # O(S/n) residuals: local blocks + per-row out/lse only — no
    # rotated K/V copies survive the forward
    return primal, (q, k, v, bias, primal, lse)


def _ring_bwd(axis_name, scale, res, g):
    q, k, v, bias, out, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_local = k.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]

    di = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                 axis=-1)                            # [B,H,Sq_local]
    dq = jnp.zeros(q.shape, jnp.float32)
    dk_acc = jnp.zeros(k.shape, jnp.float32)
    dv_acc = jnp.zeros(v.shape, jnp.float32)
    dbias = None if bias is None else jnp.zeros(bias.shape, jnp.float32)

    for step in range(n):
        src = (my - step) % n
        if bias is not None:
            b = lax.dynamic_slice_in_dim(bias, src * s_local, s_local,
                                         axis=3)
        else:
            b = None
        dq_i, dk_i, dv_i, db_i = _block_bwd(q, k, v, b, out, lse, di,
                                            g, scale)
        dq = dq + dq_i
        dk_acc = dk_acc + dk_i
        dv_acc = dv_acc + dv_i
        if bias is not None:
            dbias = lax.dynamic_update_slice_in_dim(
                dbias, db_i.astype(jnp.float32), src * s_local, axis=3)
        # rotate the block AND its accumulated gradient; after n
        # rotations every block is home with all devices' contributions
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
    return (dq.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype),
            None if bias is None else dbias.astype(bias.dtype))


_ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(q, k, v, bias=None, axis_name="sp", scale=None):
    """q, k, v: per-device blocks [B, H, S_local, D] of a sequence
    sharded over `axis_name`. bias: [B, 1|H, Sq_local, Sk_GLOBAL]
    additive mask or None. Returns the exact global attention output
    for the local queries, with O(S/n) memory through training."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    return _ring_attention(q, k, v, bias, axis_name, scale)
