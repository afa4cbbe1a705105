"""The index of a learned sparse attention (Pallas): which keys each
query attends.

A small indexer scores every (query, key) pair and each query attends
only its `top_k` best-scored keys among those not after it:

  I[t, s] = sum over index heads j of w[t, j] * relu(q[t, j] . k[s])
  keep[t, s] = s <= t and I[t, s] >= the min(top_k, t + 1)-th largest of
               I[t, :t + 1]

(ties at the threshold are all kept; a query with at most `top_k` keys
before it keeps every one). Two kernels:

  sparse_index_scores  one [tile, tile] block of I a grid step: the
      heads' [tile, d] x [d, tile] products on the MXU, relu, the
      w-weighted sum over heads; blocks above the diagonal are not
      computed, and every pair with s > t reads -inf. The [heads, S, S]
      products never exist.
  sparse_index_select  a block of query rows at a time, the rows' scores
      in VMEM: the exact threshold of every row by bisection over the 32
      bits of an order-preserving integer key (one counting pass over the
      causal part of the rows a bit), then keep = key >= threshold as
      int8, and beside it each row's count of kept keys (the last
      accepted pass's count: the mask is never read again to count it).
      Exact: no sort, no approximate top-k.

Routing. `select()`-governed like the grouped matmul
(kernels/registry.py), ONE decision an op counted under
`sparse_index_scores`: off the CPU, when not denied and where the shapes
tile, both kernels run; otherwise the `lowered` path computes the same
mask with `jax.numpy`, the scores a block of query rows at a time and
the threshold with `lax.top_k`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

SCORE_TILE = 512           # the published q_chunk_size / kv_chunk_size
SELECT_ROWS = 128          # query rows a step of the selection holds
_SELECT_CHUNK = 1024       # columns a counting step reads
_VMEM_DEFAULT_LIMIT = 16 << 20
_INT_MIN = -2 ** 31

__all__ = ["SCORE_TILE", "SELECT_ROWS", "index_mask", "index_scores",
           "select_mask", "use_kernels"]


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _scores_kernel(q_ref, k_ref, w_ref, o_ref, *, heads, dim, tile):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki <= qi)
    def _run():
        k = k_ref[...]                                      # [tile, dim]
        w = w_ref[...].astype(jnp.float32)                  # [tile, heads]
        acc = jnp.zeros((tile, tile), jnp.float32)
        for j in range(heads):
            s = lax.dot_general(
                q_ref[:, j * dim:(j + 1) * dim], k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
        rows = qi * tile + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        cols = ki * tile + lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        o_ref[...] = jnp.where(rows >= cols, acc, -jnp.inf)

    @pl.when(ki > qi)
    def _fill():
        o_ref[...] = jnp.full((tile, tile), -jnp.inf, jnp.float32)


def _scores_call(q, k, w, tile):
    b, s, heads, dim = q.shape
    # a key block above the diagonal is not read: a repeated block index
    # elides its DMA
    below = lambda bi, i, j: (bi, jnp.minimum(i, j), 0)
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=heads, dim=dim, tile=tile),
        name="sparse_index_scores",
        grid=(b, s // tile, s // tile),
        in_specs=[
            pl.BlockSpec((None, tile, heads * dim),
                         lambda bi, i, j: (bi, i, 0)),
            pl.BlockSpec((None, tile, dim), below),
            pl.BlockSpec((None, tile, heads), lambda bi, i, j: (bi, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, tile, tile),
                               lambda bi, i, j: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=registry.interpret(),
    )(q.reshape(b, s, heads * dim), k, w)


def _ordered_key(x):
    """float32 -> int32 whose signed order is the floats' order."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _select_kernel(s_ref, o_ref, n_ref, key_scr, *, top_k, rows, chunk):
    i = pl.program_id(1)
    key_scr[...] = _ordered_key(s_ref[...])
    t = i * rows + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    want = jnp.minimum(top_k, t + 1)                        # [rows, 1]
    # columns past the block's last row are all -inf: not counted
    n_chunks = ((i + 1) * rows + chunk - 1) // chunk

    lanes = min(128, chunk)

    def count_at_least(cand):
        def body(c, acc):
            blk = key_scr[:, pl.ds(pl.multiple_of(c * chunk, chunk), chunk)]
            hit = (blk >= cand).astype(jnp.int32)
            # whole vregs added lane by lane; one cross-lane sum a pass
            for j in range(chunk // lanes):
                acc = acc + hit[:, j * lanes:(j + 1) * lanes]
            return acc
        acc = lax.fori_loop(0, n_chunks, body,
                            jnp.zeros((rows, lanes), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    # the largest key T with count(key >= T) >= want, bit by bit from the
    # top, and that count beside it. Clearing the sign bit of INT_MIN
    # gives 0; setting a lower bit of any int32 makes it larger; every
    # counted column is >= INT_MIN
    zero = jnp.zeros((rows, 1), jnp.int32)
    n_zero = count_at_least(zero)
    thr = jnp.where(n_zero >= want, zero,
                    jnp.full((rows, 1), _INT_MIN, jnp.int32))
    kept = jnp.where(n_zero >= want, n_zero, n_chunks * chunk)

    def bit(n, carry):
        thr, kept = carry
        cand = thr | (1 << (30 - n))
        n_cand = count_at_least(cand)
        ok = n_cand >= want
        return jnp.where(ok, cand, thr), jnp.where(ok, n_cand, kept)

    thr, kept = lax.fori_loop(0, 31, bit, (thr, kept))
    o_ref[...] = (key_scr[...] >= thr).astype(jnp.int8)
    n_ref[...] = kept


def _select_call(scores, top_k, rows, chunk):
    b, s, _ = scores.shape
    block = rows * s
    return pl.pallas_call(
        functools.partial(_select_kernel, top_k=top_k, rows=rows,
                          chunk=chunk),
        name="sparse_index_select",
        grid=(b, s // rows),
        in_specs=[pl.BlockSpec((None, rows, s), lambda bi, i: (bi, i, 0))],
        out_specs=[pl.BlockSpec((None, rows, s), lambda bi, i: (bi, i, 0)),
                   pl.BlockSpec((None, rows, 1), lambda bi, i: (bi, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, s, s), jnp.int8),
                   jax.ShapeDtypeStruct((b, s, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the scores block twice (double-buffered), its keys, the
            # mask block twice, beside Mosaic's default for the rest
            vmem_limit_bytes=_VMEM_DEFAULT_LIMIT + block * (8 + 4 + 2)),
        interpret=registry.interpret(),
    )(scores)


# ---------------------------------------------------------------------------
# lowered path: the same mask by XLA
# ---------------------------------------------------------------------------

def _lowered_scores(q, k, w, block):
    """I [B, S, S] float32, -inf where s > t, `block` query rows at a
    time: [B, heads, block, S] products at most."""
    b, s, heads, dim = q.shape
    block = min(block, s)
    if s % block:
        block = s
    cols = jnp.arange(s)

    def one(args):
        qb, wb, start = args                 # [B, block, H, D], [B, block, H]
        prod = jnp.einsum("bqhd,bkd->bhqk", qb, k,
                          preferred_element_type=jnp.float32)
        score = jnp.einsum("bhqk,bqh->bqk", jnp.maximum(prod, 0.0),
                           wb.astype(jnp.float32))
        causal = (start + jnp.arange(block))[:, None] >= cols[None, :]
        return jnp.where(causal[None], score, -jnp.inf)

    n = s // block
    out = lax.map(one, (
        jnp.moveaxis(q.reshape(b, n, block, heads, dim), 1, 0),
        jnp.moveaxis(w.reshape(b, n, block, heads), 1, 0),
        jnp.arange(0, s, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, s)


def _lowered_select(scores, top_k):
    s = scores.shape[-1]
    kth = lax.top_k(scores, min(top_k, s))[0][..., -1:]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    # a row with fewer than top_k keys before it reads kth = -inf, which
    # the pairs with s > t equal
    keep = (scores >= kth) & causal[None]
    return keep.astype(jnp.int8), \
        jnp.sum(keep, axis=-1, keepdims=True, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def use_kernels(q, k) -> bool:
    """One decision an op, counted under `sparse_index_scores`: the two
    Pallas kernels (`custom`) or the `jax.numpy` lowering (`lowered`)."""
    if not registry.routable("sparse_attention_index"):
        return False
    return registry.select(
        "sparse_attention_index",
        registry.signature("sparse_attention_index", q, k)) is not None


def _tiles(s):
    """(score tile, selection rows, counting chunk) for a sequence of s:
    the kernels' own where s is a multiple of them, else (the
    interpreter's small shapes) the largest power of two that divides
    s."""
    def fit(n):
        while s % n:
            n //= 2
        return n
    return fit(SCORE_TILE), fit(SELECT_ROWS), fit(_SELECT_CHUNK)


def index_scores(q, k, w, kernels, tile=None):
    """q [B, S, heads, d], k [B, S, d], w [B, S, heads] -> I [B, S, S]
    float32 with -inf where s > t."""
    tile = tile or _tiles(q.shape[1])[0]
    if kernels:
        return _scores_call(q, k, w, tile)
    return _lowered_scores(q, k, w, tile)


def select_mask(scores, top_k, kernels, rows=None, chunk=None):
    """I [B, S, S] (-inf where s > t) -> (keep int8 [B, S, S], keys kept
    a row int32 [B, S, 1])."""
    if kernels:
        _, r, c = _tiles(scores.shape[1])
        return _select_call(scores, int(top_k), rows or r, chunk or c)
    return _lowered_select(scores, int(top_k))


def index_mask(q, k, w, top_k, kernels):
    """(keep int8 [B, S, S], pairs kept int32 [1])."""
    keep, rows_kept = select_mask(index_scores(q, k, w, kernels), top_k,
                                  kernels)
    return keep, jnp.sum(rows_kept, dtype=jnp.int32).reshape(1)


def _eligible(sig: registry.Signature) -> bool:
    """Sequences the kernels tile (whole score tiles, selection blocks
    and counting chunks, so 1,024 at the least), the heads' channels
    filling whole lane blocks; the interpreter takes any sequence of
    whole 8-row tiles."""
    (_, s, heads, dim), k_shape = sig.shapes[0], sig.shapes[1]
    if sig.dtypes[0] not in ("bfloat16", "float32") \
            or sig.dtypes[0] != sig.dtypes[1] \
            or k_shape != (sig.shapes[0][0], s, dim):
        return False
    if registry._INTERPRET:
        return s % 8 == 0
    return (s % SCORE_TILE == 0 and s % _SELECT_CHUNK == 0
            and (heads * dim) % 128 == 0)


registry.register_kernel(
    "sparse_index_scores", op_types=("sparse_attention_index",),
    eligible=_eligible, run=index_mask,
    doc="learned sparse attention's index: relu-gated head-weighted "
        "scores a [512, 512] tile a step, causal tiles skipped, then "
        "(sparse_index_select) the exact top-k threshold of every row by "
        "bisection over the bits of an ordered key")
