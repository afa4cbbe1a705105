"""Blockwise (flash) attention forward + backward kernels in Pallas.

The reference composes attention from matmul/softmax primitives (no fused
attention kernel exists in the 2019 snapshot — SURVEY §5 "long-context");
this kernel pair is the TPU-native upgrade for that hot path, filling the
custom-kernel slot the reference's Xbyak JIT tier fills on x86
(/root/reference/paddle/fluid/operators/jit/README.md):

* forward: online-softmax over KV blocks so the [Sq, Sk] score matrix
  never materializes in HBM — O(S) memory, QK^T and PV on the MXU from
  VMEM tiles; optionally emits logsumexp, one float32 a row (lane-
  broadcast only inside VMEM: `_Plan.lse_spec`).
* backward: one fused kernel that consumes the saved (out, lse)
  residuals, recomputes the probability tile p = exp(s - lse) per block
  — the [Sq, Sk] matrix again never hits HBM — and writes dq, dk and dv
  from that single pass over the score tiles, the whole dq of one
  (batch, head group) resident in VMEM. di = sum(dO*O) is recomputed
  per block from the out/do streams (VPU work) instead of a second
  per-row HBM tensor. A dq kernel and a dk/dv kernel run as a
  split pair only where the fused one cannot (_fa_backward decides, by
  shape): with an additive bias that needs a gradient, where the dq
  kernel also emits the ds tile (dbias IS ds summed over broadcast
  dims), and where the sequence's dq does not fit the VMEM budget.

Layouts — the same kernel bodies serve two HBM layouts:

* "bhsd" — q/k/v [B, H, S, D] (the classic layout; ring attention uses
  this along the sequence axis). Blocks are [block, D] tiles of the
  [B*H, S, D] view; one head per grid step.
* "bshd" — q/k/v [B, S, H, D], i.e. a free reshape of the [B, S, H*D]
  projection output. This kills the head-split transposes entirely:
  XLA cannot fuse layout changes into a custom call, so the bhsd path's
  pre/post-kernel transposes materialize (~8 GB/step of HBM copies on
  transformer-base at B=96). Mosaic requires lane blocks of 128 (or the
  full minor dim), so with D < 128 the kernel PACKS hpb = 128 // D
  heads into each 128-wide lane block of the [B, S, H*D] view and
  slices per-head tiles in VMEM (static lane slices) — grid
  (B, H/hpb, n_q, n_kv), an unrolled hpb-iteration loop per step.

Grad identities (standard flash attention backward):
  di = sum(dO * O, -1);  p = exp(s - lse)
  dv = p^T @ dO;  dp = dO @ V^T;  ds = p * (dp - di)
  dq = (ds @ K) * scale;  dk = (ds^T @ Q) * scale;  dbias = ds
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _out_struct(shape, dtype, like):
    """pallas_call out_shape carrying ``like``'s varying-mesh-axes set:
    under shard_map outputs inherit the inputs' vma, and JAX checks it
    on pallas_call out_shapes."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)

# test hook: run pallas_call in interpreter mode (CPU correctness tests)
_INTERPRET = False


# ---------------------------------------------------------------------------
# attention-weights dropout (reference dist_transformer.py:1043-1044 —
# layers.dropout applied to the softmax WEIGHTS) inside the kernels
# ---------------------------------------------------------------------------

def _mix32(h):
    """murmur3 finalizer on uint32 (works on jnp arrays in and out of
    kernels)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _hash_keep(s0, s1, bh, q_start, k_start, bq, bk, Sk, t):
    """u8-threshold keep mask for one [bq, bk] score tile, as a pure
    function of (seed, head, absolute row, absolute col) — block-
    geometry-independent, so the fwd and bwd kernels regenerate
    bit-identical masks, and it runs under the Pallas interpreter
    (pltpu.prng_* has no interpreter lowering in this JAX). Compiled
    kernels use the hardware PRNG instead (_tile_keep): the ~12
    int-ops/element here would rival the block's MXU time."""
    rows = (jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 0)
            + q_start.astype(jnp.uint32))
    cols = (jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 1)
            + k_start.astype(jnp.uint32))
    pos = rows * jnp.uint32(Sk) + cols
    seed = (s0.astype(jnp.uint32)
            ^ _mix32(s1.astype(jnp.uint32)
                     ^ bh.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)))
    return (_mix32(pos ^ seed) & jnp.uint32(255)) < jnp.uint32(t)


def dropout_keep_mask(seed, B, H, Sq, Sk, t):
    """[B, H, Sq, Sk] keep mask exactly as the INTERPRET-mode kernels
    realize it (test/debug helper). seed: int32[2] (bitcast of the op's
    uint32 PRNG key). Compiled kernels draw from the TPU hardware PRNG
    instead; their masks share the seeding contract but not the bits."""
    rows = jnp.arange(Sq, dtype=jnp.uint32)[:, None]
    cols = jnp.arange(Sk, dtype=jnp.uint32)[None, :]
    pos = rows * jnp.uint32(Sk) + cols
    bh = jnp.arange(B * H, dtype=jnp.uint32).reshape(B, H, 1, 1)
    sd = (seed[0].astype(jnp.uint32)
          ^ _mix32(seed[1].astype(jnp.uint32)
                   ^ bh * jnp.uint32(0x9E3779B1)))
    return (_mix32(pos[None, None] ^ sd)
            & jnp.uint32(255)) < jnp.uint32(t)


def _tile_keep(plan, seed_ref, bh, q_idx, kv_idx, t):
    """Keep mask for a local head's [bq, bk] tile at grid step
    (q_idx, kv_idx). bh = the head's global batch*H+head id (computed
    at kernel top — pl.program_id can't sit inside a pl.when body in
    the interpreter). Seeded per (key, global head, q block, kv block)
    — the same tuple in the forward and the backward kernels, so the
    recomputed masks agree."""
    bq, bk = plan.bq, plan.bk
    if _INTERPRET:
        return _hash_keep(seed_ref[0], seed_ref[1], bh,
                          q_idx * bq, kv_idx * bk, bq, bk, plan.Sk, t)
    # Mosaic's PRNG takes at most TWO seed words: fold the 5-tuple
    # down with scalar mixes (once per block, scalar core)
    a = _mix32(seed_ref[0].astype(jnp.uint32)
               ^ bh.astype(jnp.uint32) * jnp.uint32(0x9E3779B1))
    b = _mix32(seed_ref[1].astype(jnp.uint32)
               ^ q_idx.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
               ^ kv_idx.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35))
    pltpu.prng_seed(a, b)
    if bq % 4 == 0:
        # the threshold only needs 8 bits: draw a QUARTER tile of u32s
        # and bitcast to u8 (tpu.bitcast expands the sublane dim 4x) —
        # the PRNG draw is the dominant dropout cost in the kernels.
        # The target has no i8 vector compare; widen to i32 first
        # (cheap relative to 3/4 of the draws saved).
        bits = pltpu.bitcast(pltpu.prng_random_bits((bq // 4, bk)),
                             jnp.uint8)
        return bits.astype(jnp.int32) < t
    bits = pltpu.prng_random_bits((bq, bk))
    return (bits & 255) < t


def _dims(q, layout):
    """(B, H, S, D) of a q / k / v in either layout."""
    a, b, c, d = q.shape
    return (a, c, b, d) if layout == "bshd" else (a, b, c, d)


def _seq_len(x, layout):
    return x.shape[1] if layout == "bshd" else x.shape[2]


def _heads(x, layout):
    return x.shape[2] if layout == "bshd" else x.shape[1]


def _heads_per_block(H, D, Dv=None):
    """bshd lane packing: how many heads share one lane block. Aims for
    128 lanes (the Mosaic minimum for a strict lane block); interpret
    mode and _kernel_ok tolerate smaller when H is small. Where q/k and
    v differ in width (Dv), or a width over 128 is no multiple of it,
    the least number of heads that makes BOTH blocks whole multiples of
    128 lanes: 2 for 192/128 (blocks of 384 and 256)."""
    if Dv in (None, D) and (D < 128 or D % 128 == 0):
        hpb = max(1, 128 // D) if D < 128 else 1
    else:
        a, b = (128 // math.gcd(w, 128) for w in (D, Dv or D))
        hpb = a * b // math.gcd(a, b)
    hpb = min(hpb, H)
    while H % hpb:
        hpb -= 1
    return hpb


class _Plan:
    """Geometry for one (layout, shape, block) configuration.

    bhsd: grid (B*H, i, j);    rows [B*H, S, D];    hpb=1
    bshd: grid (B, Hg, i, j);  rows [B, S, H*D];    hpb=128//D heads
          per lane block (Hg = H // hpb)
    `order` maps the q/k sequence grid axes for the active kernel
    (dq-style grids put q before kv; dkv-style grids swap them).

    Grouped queries: k and v may have Hkv < H heads, query head g
    reading key head g // (H // Hkv). The grid stays the query heads';
    only the k / v index maps change (`row_spec(shared=True)`), so k and
    v are never expanded. With one head a lane block (D >= 128) a query
    block's k / v block is simply its key head's. With PACKED heads
    (D < 128, hpb > 1) the group is a multiple of hpb (`_kernel_ok`), so
    the hpb query heads of a lane block all read ONE key head, which is
    one D-wide slice of a key lane block of hpb key heads: the index map
    picks the key block (query block // group) and the kernels pick the
    slice by the grid step (`shared_kv`)."""

    def __init__(self, layout, B, H, Sq, Sk, D, bq, bk, Dv=None,
                 Hkv=None):
        self.layout = layout
        self.B, self.H, self.Sq, self.Sk, self.D = B, H, Sq, Sk, D
        # query heads that share one key / value head
        self.group = H // (Hkv or H)
        # q and k are D wide, v (and so out, do, dv) Dv wide
        self.Dv = D if Dv is None else Dv
        self.bq, self.bk = bq, bk
        self.bqp = -(-bq // 128) * 128     # a q block's lanes of an lse
        if layout == "bshd":
            self.hpb = _heads_per_block(H, D, self.Dv)
            self.Hg = H // self.hpb
        else:
            self.hpb = 1
            self.Hg = None
        # grouped queries with packed heads: a lane block's query heads
        # share one key head, a slice of its key lane block
        self.packed_shared = self.group > 1 and self.hpb > 1

    def rows(self, x):
        """HBM view handed to pallas_call."""
        if self.layout == "bshd":
            B, S = x.shape[0], x.shape[1]
            return x.reshape(B, S, x.shape[2] * x.shape[3])
        B, H, S, D = x.shape
        return x.reshape(B * H, S, D)

    def grid(self, n_i, n_j):
        if self.layout == "bshd":
            return (self.B, self.Hg, n_i, n_j)
        return (self.B * self.H, n_i, n_j)

    def seq_axes(self, swap):
        """(q_axis, k_axis) grid positions; swap=True for dkv grids."""
        base = 2 if self.layout == "bshd" else 1
        return (base + 1, base) if swap else (base, base + 1)

    def bh(self, i):
        """Global batch*H + head index of local head i at this grid
        step — the per-head dropout stream id (identical across the
        fwd/dq/dkv grids)."""
        if self.layout == "bshd":
            return (pl.program_id(0) * self.H
                    + pl.program_id(1) * self.hpb + i)
        return pl.program_id(0)

    def row_spec(self, blk, width_per_head, which_axis, idx=None,
                 shared=False):
        """Spec for a q/k/v/out/do tensor: [blk rows x
        hpb*width_per_head lanes]. which_axis = grid position of the
        sequence index; idx (callable(g) -> index) overrides it — the
        causal path clamps the masked-out tail of a sequential axis to
        its last live block, so Mosaic sees a repeated block index and
        elides the DMA for skipped steps. shared: a k / v INPUT, whose
        head is the query head's over `group`."""
        get = (lambda g: g[which_axis]) if idx is None else idx
        per = self.group if shared else 1
        if self.layout == "bshd":
            def index_map(*g):
                return (g[0], get(g), g[1] // per if per > 1 else g[1])
            return pl.BlockSpec(
                (None, blk, self.hpb * width_per_head), index_map)

        def index_map(*g):
            return (g[0] // per if per > 1 else g[0], get(g), 0)
        return pl.BlockSpec((None, blk, width_per_head), index_map)

    # A site's per-row float32 vectors (lse, its cotangent) cross the
    # kernel boundary with ONE number a lane: [B*H/hpb, hpb, Sq], a free
    # reshape of [B, H, Sq], in blocks of (hpb, bq) whose second-minor
    # dimension is the array's whole one (Mosaic's (8, 128) block rule);
    # a bq off the 128 lanes pads each q block to whole tiles (bqp).

    def lse_shape(self):
        return (self.B * self.H // self.hpb, self.hpb,
                self.Sq // self.bq * self.bqp)

    def lse_rows(self, x):
        """[B, H, Sq] -> the form handed to pallas_call."""
        x = jnp.pad(x.astype(jnp.float32).reshape(-1, self.bq),
                    ((0, 0), (0, self.bqp - self.bq)))
        return x.reshape(self.lse_shape())

    def lse_spec(self, which_axis, idx=None):
        """The ref is [hpb, bqp]: local head i's numbers along row i."""
        get = (lambda g: g[which_axis]) if idx is None else idx
        bshd = self.layout == "bshd"
        return pl.BlockSpec(
            (None, self.hpb, self.bqp),
            lambda *g: (g[0] * self.Hg + g[1] if bshd else g[0], 0, get(g)))

    def bias_info(self, bias):
        """Returns (reshaped_bias, spec_factory, per_head, per_q).
        spec_factory(q_axis, k_axis, q_idx=, k_idx=) -> BlockSpec whose
        ref is [hpb, bqs, bk] for packed per-head bias, else [bqs, bk];
        the optional idx callables clamp a sequential axis (causal DMA
        elision, see row_spec)."""
        B, H, Sq = self.B, self.H, self.Sq
        bq, bk, hpb = self.bq, self.bk, self.hpb
        per_head = bias.shape[1] != 1
        per_q = bias.shape[2] != 1
        bqs = bq if per_q else 1
        if self.layout == "bshd":
            if per_head:
                br = bias.reshape(B, self.Hg, hpb,
                                  Sq if per_q else 1, bias.shape[3])

                def factory(q_axis, k_axis, q_idx=None, k_idx=None):
                    qg = (lambda g: g[q_axis]) if q_idx is None else q_idx
                    kg = (lambda g: g[k_axis]) if k_idx is None else k_idx

                    def index_map(*g):
                        return (g[0], g[1], 0,
                                qg(g) if per_q else 0, kg(g))
                    return pl.BlockSpec((None, None, hpb, bqs, bk),
                                        index_map)
            else:
                br = bias.reshape(B, Sq if per_q else 1, bias.shape[3])

                def factory(q_axis, k_axis, q_idx=None, k_idx=None):
                    qg = (lambda g: g[q_axis]) if q_idx is None else q_idx
                    kg = (lambda g: g[k_axis]) if k_idx is None else k_idx

                    def index_map(*g):
                        return (g[0], qg(g) if per_q else 0, kg(g))
                    return pl.BlockSpec((None, bqs, bk), index_map)
            return br, factory, per_head, per_q
        br = bias.reshape((B * H if per_head else B,
                           Sq if per_q else 1, bias.shape[3]))

        def factory(q_axis, k_axis, q_idx=None, k_idx=None):
            qg = (lambda g: g[q_axis]) if q_idx is None else q_idx
            kg = (lambda g: g[k_axis]) if k_idx is None else k_idx

            def index_map(*g):
                return (g[0] if per_head else g[0] // H,
                        qg(g) if per_q else 0, kg(g))
            return pl.BlockSpec((None, bqs, bk), index_map)
        return br, factory, per_head, per_q

    def bias_tile(self, bias_ref, i, transpose=False):
        """Per-local-head [bqs, bk] tile from the bias ref ([bk, bqs]
        transposed): f32 to add to the scores, or (an integer bias is a
        keep MASK) bool."""
        if bias_ref is None:
            return None
        # packed per-head [hpb, bqs, bk], else [bqs, bk]
        tile = bias_ref[i] if bias_ref.ndim == 3 else bias_ref[...]
        mask = _is_mask(tile)
        # the target has no i8 vector compare: widen first
        tile = tile.astype(jnp.int32 if mask else jnp.float32)
        if transpose:
            tile = tile.T
        return tile != 0 if mask else tile

    def ds_shape(self):
        if self.layout == "bshd":
            return (self.B, self.Hg, self.hpb, self.Sq, self.Sk)
        return (self.B * self.H, self.Sq, self.Sk)

    def ds_spec(self, q_axis, k_axis):
        if self.layout == "bshd":
            def index_map(*g):
                return (g[0], g[1], 0, g[q_axis], g[k_axis])
            return pl.BlockSpec(
                (None, None, self.hpb, self.bq, self.bk), index_map)

        def index_map(*g):
            return (g[0], g[q_axis], g[k_axis])
        return pl.BlockSpec((None, self.bq, self.bk), index_map)

    def ds_store(self, ds_ref, i, tile):
        if self.layout == "bshd":
            ds_ref[i] = tile
        else:
            ds_ref[...] = tile

    def lanes(self, ref, i, width):
        """Local head i's [rows, width] slice of a packed ref."""
        if self.hpb == 1 and self.layout != "bshd":
            return ref[...]
        return ref[:, i * width:(i + 1) * width]

    def kv_slot(self):
        """Which slice of its key lane block this grid step's query
        block reads (grouped queries with packed heads; None otherwise):
        query block g1 holds heads g1 * hpb .., whose key head is g1 //
        (group / hpb), the (that % hpb)-th of its lane block. Taken at
        the kernel's top (a `pl.program_id` cannot sit inside a
        `pl.when` body under the interpreter)."""
        if not self.packed_shared:
            return None
        return (pl.program_id(1) // (self.group // self.hpb)) % self.hpb

    def shared_kv(self, slot, ref, width):
        """The one key (or value) tile every query head of this block
        reads: the `slot`-th [rows, width] slice of the packed ref,
        picked among the static lane slices."""
        tile = ref[:, :width]
        for h in range(1, self.hpb):
            tile = jnp.where(slot == h, ref[:, h * width:(h + 1) * width],
                             tile)
        return tile

    def store_lanes(self, ref, i, width, val):
        if self.hpb == 1 and self.layout != "bshd":
            ref[...] = val
        else:
            ref[:, i * width:(i + 1) * width] = val


class _Band:
    """Where a sliding window's band lies on the block grid: query row r
    admits the keys c with r - window < c <= r (absolute positions, as
    the causal mask). The rows of query block i meet the keys
    [i bq - window + 1, i bq + bq - 1], one contiguous run of key blocks
    kv_first(i) .. kv_last(i), and key block j meets the query blocks
    q_first(j) .. q_last(j). A banded grid axis has `kv_steps` (or
    `q_steps`) steps, the most blocks any block meets: step t of query
    block i is key block kv_first(i) + t, and the steps past kv_last(i)
    repeat its index (Mosaic elides their DMA) and do no work. Every
    block a query block's band meets holds at least one admitted pair;
    a block it does not meet takes no grid step.

    The methods take a grid index, traced inside a kernel or an index
    map; `static` evaluates them on Python ints."""

    def __init__(self, window, bq, bk, n_q, n_kv):
        self.window, self.bq, self.bk = int(window), bq, bk
        self.n_q, self.n_kv = n_q, n_kv
        self.kv_steps = max(self.kv_last(i, min) - self.kv_first(i, max) + 1
                            for i in range(n_q))
        self.q_steps = max(self.q_last(j, min) - self.q_first(j) + 1
                           for j in range(n_kv))

    def kv_first(self, i, mx=jnp.maximum):
        # clamp before dividing: the division stays on non-negative ints
        return mx(i * self.bq - (self.window - 1), 0) // self.bk

    def kv_last(self, i, mn=jnp.minimum):
        return mn((i * self.bq + self.bq - 1) // self.bk, self.n_kv - 1)

    def q_first(self, j):
        return j * self.bk // self.bq

    def q_last(self, j, mn=jnp.minimum):
        return mn((j * self.bk + self.bk + self.window - 2) // self.bq,
                  self.n_q - 1)

    def kv_block(self, i, t):
        """Key block of step t of query block i (index maps: clamped)."""
        return jnp.minimum(self.kv_first(i) + t, self.kv_last(i))

    def q_block(self, j, t):
        """Query block of step t of key block j (index maps: clamped)."""
        return jnp.minimum(self.q_first(j) + t, self.q_last(j))


def admitted_pairs(Sq, Sk, window):
    """(query, key) pairs a sliding window admits in one head of one
    sequence: row r keeps keys max(0, r - window + 1) .. min(r, Sk - 1)."""
    return sum(max(0, min(r, Sk - 1) - max(0, r - window + 1) + 1)
               for r in range(Sq))


# ---------------------------------------------------------------------------
# kernel bodies (shared by both layouts via the plan's lane slicing)
# ---------------------------------------------------------------------------

def _is_mask(bias):
    """An integer (or bool) bias is a keep mask: scores where it is 0
    are masked out. A float bias is added to the scores."""
    return not jnp.issubdtype(bias.dtype, jnp.floating)


def _biased(s, bt):
    """Scores s under a bias tile from `_Plan.bias_tile` (or None)."""
    if bt is None:
        return s
    if bt.dtype == jnp.bool_:
        return jnp.where(bt, s, _NEG_INF)
    return s + bt


def _causal_mask(s, q_idx, kv_idx, bq, bk):
    """Mask s to the causal triangle (absolute positions; fully-visible
    blocks get an all-true compare, masked-out blocks never run)."""
    rows = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows >= cols, s, _NEG_INF)


def _causal_mask_dense(s):
    """Whole-matrix sibling of _causal_mask for the composed paths
    (s [..., Sq, Sk], absolute rows >= cols convention)."""
    rows = jnp.arange(s.shape[-2])[:, None]
    cols = jnp.arange(s.shape[-1])[None, :]
    return jnp.where(rows >= cols, s, _NEG_INF)


def _band_mask(s, q_blk, kv_blk, bq, bk, window):
    """Mask s to a sliding window's band, r - window < c <= r, for the
    tile of query block q_blk and key block kv_blk (absolute positions;
    a tile inside the band gets an all-true compare)."""
    rows = q_blk * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = kv_blk * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    d = rows - cols
    return jnp.where((d >= 0) & (d < window), s, _NEG_INF)


def _band_mask_dense(s, window):
    """Whole-matrix sibling of _band_mask for the composed paths."""
    d = jnp.arange(s.shape[-2])[:, None] - jnp.arange(s.shape[-1])[None, :]
    return jnp.where((d >= 0) & (d < window), s, _NEG_INF)


def _eye(n):
    """The first n rows of the [128, 128] diagonal mask: what keeps only
    it, summed over lanes, is each row's own number; exact, the sum adds
    zeros to one number (as `kernels/mamba2_ssd.py` `_to_col`)."""
    return jax.lax.broadcasted_iota(jnp.int32, (n, 128), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, 128), 1)


def _to_lanes(ref, i, col):
    """Store a [bq, 128] lane-broadcast per-row value as row i of a
    `_Plan.lse_spec` ref. Every lane holds its row's number, so lanes
    8j .. 8j+7 of a 128-row chunk's [8, 128] pick come from its j-th 8
    rows, and sublane l % 8 of lane l is row l's (selects and one sum
    with zeros: exact; lanes past a short chunk's rows are padding)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    mine = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0) == lane % 8
    group = lane // 8
    for c in range(0, col.shape[0], 128):
        pick = col[c:c + 8]
        for r in range(c + 8, min(c + 128, col.shape[0]), 8):
            pick = jnp.where(group == (r - c) // 8, col[r:r + 8], pick)
        ref[i:i + 1, c:c + 128] = jnp.sum(jnp.where(mine, pick, 0.0),
                                          axis=0, keepdims=True)


def _to_rows(ref, i, bq):
    """Row i of a `_Plan.lse_spec` ref as a [bq, 1] column."""
    wide = jnp.concatenate(
        [jnp.where(_eye(min(128, bq - c)), ref[i:i + 1, c:c + 128], 0.0)
         for c in range(0, bq, 128)], axis=0)
    return jnp.sum(wide, axis=1, keepdims=True)


def _fa_kernel(plan, seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
               lse_ref, m_scr, l_scr, acc_scr, *, scale, n_kv, q_axis,
               kv_axis, causal, drop_t, band=None):
    """The forward on the TRANSPOSED tile, s^T = k q^T [bk, bq]: keys on
    sublanes, queries on lanes. The softmax's max and sum run down the
    sublanes (no cross-lane reduction), its running max, sum and
    rescale are [1, bq] rows (bq / 128 vregs, not bq / 8), and the
    accumulator is o^T = v^T p^T [Dv, bq], lane-dense at any head width
    (Mosaic turns the [bk, Dv] v tile on the XLU); `_finish` turns o^T
    back once a query block. A bias is a [1, bk] row here (a key's);
    a [bq, bk] bias tile, or dropout, takes `_fa_kernel_rows`.

    band (a `_Band`): the kv axis steps over the key blocks of the
    query block's band, kv_idx counting steps, kv_blk the key block."""
    assert drop_t is None       # dropout takes `_fa_kernel_rows`
    kv_idx = pl.program_id(kv_axis)
    q_idx = pl.program_id(q_axis)
    D, Dv, bq, bk = plan.D, plan.Dv, plan.bq, plan.bk
    slot = plan.kv_slot()
    kv_blk = kv_idx if band is None else band.kv_first(q_idx) + kv_idx
    window = None if band is None else band.window

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        if slot is not None:
            k_all = plan.shared_kv(slot, k_ref, D)
            v_all = plan.shared_kv(slot, v_ref, Dv)

        def scores(i):
            st = jax.lax.dot_general(
                plan.lanes(k_ref, i, D) if slot is None else k_all,
                plan.lanes(q_ref, i, D), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bk, bq]
            st = _biased(st, plan.bias_tile(bias_ref, i, transpose=True))
            if causal:
                # query - key of each pair, absolute
                d = q_idx * bq - kv_blk * bk + (
                    jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
                    - jax.lax.broadcasted_iota(jnp.int32, st.shape, 0))
                keep = d >= 0 if window is None else (d >= 0) & (d < window)
                st = jnp.where(keep, st, _NEG_INF)
            return st

        # the heads of a lane block in turn, the next head's k q^T issued
        # before this head's softmax: the MXU works under the VPU
        s_next = scores(0)
        for i in range(plan.hpb):
            st = s_next
            if i + 1 < plan.hpb:
                s_next = scores(i + 1)
            m_prev = m_scr[i][:1]                          # [1, bq]
            m_next = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            corr = jnp.exp(m_prev - m_next)
            p = jnp.exp(st - m_next)                       # [bk, bq]
            l_next = l_scr[i][:1] * corr + jnp.sum(p, axis=0, keepdims=True)
            acc_scr[i] = acc_scr[i] * corr + jax.lax.dot_general(
                plan.lanes(v_ref, i, Dv) if slot is None else v_all,
                p.astype(v_ref.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [Dv, bq]
            m_scr[i] = jnp.broadcast_to(m_next, m_scr[i].shape)
            l_scr[i] = jnp.broadcast_to(l_next, l_scr[i].shape)

    if band is not None:
        # the steps past the band's last key block: no work, and the
        # clamped index maps already elided their DMA
        pl.when(kv_blk <= band.kv_last(q_idx))(_body)
    elif causal:
        # skip fully-masked KV blocks (everything strictly above the
        # block diagonal): no MXU work, and the clamped index maps
        # already elided their DMA
        pl.when(q_idx * bq + bq > kv_idx * bk)(_body)
    else:
        _body()

    @pl.when(kv_idx == n_kv - 1)
    def _finish():
        for i in range(plan.hpb):
            l = jnp.maximum(l_scr[i][:1], 1e-30)
            plan.store_lanes(o_ref, i, Dv,
                             (acc_scr[i] / l).T.astype(o_ref.dtype))
            if lse_ref is not None:
                lse_ref[i:i + 1, :bq] = m_scr[i][:1] + jnp.log(l)


def _fa_kernel_rows(plan, seed_ref, q_ref, k_ref, v_ref, bias_ref,
                    o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, n_kv,
                    q_axis, kv_axis, causal, drop_t, band=None):
    """The forward on the [bq, bk] tile itself (keys on lanes), for a
    site whose bias is a [bq, bk] tile a query block (a keep mask, a
    float bias a query) or whose weights drop out: the tile and the keep
    mask come in that form, and the backward draws the mask in it.
    band: as `_fa_kernel`'s."""
    kv_idx = pl.program_id(kv_axis)
    q_idx = pl.program_id(q_axis)
    D, Dv, bq, bk = plan.D, plan.Dv, plan.bq, plan.bk
    bhs = [plan.bh(i) for i in range(plan.hpb)] \
        if drop_t is not None else None
    slot = plan.kv_slot()
    kv_blk = kv_idx if band is None else band.kv_first(q_idx) + kv_idx

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        if slot is not None:
            k_all = plan.shared_kv(slot, k_ref, D)
            v_all = plan.shared_kv(slot, v_ref, Dv)
        for i in range(plan.hpb):
            q = plan.lanes(q_ref, i, D)                # [bq, D]
            k = plan.lanes(k_ref, i, D) if slot is None else k_all
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bq, bk]
            s = _biased(s, plan.bias_tile(bias_ref, i))
            if band is not None:
                s = _band_mask(s, q_idx, kv_blk, bq, bk, band.window)
            elif causal:
                s = _causal_mask(s, q_idx, kv_idx, bq, bk)

            m_prev = m_scr[i][:, :1]                   # [bq, 1]
            l_prev = l_scr[i][:, :1]
            m_curr = jnp.max(s, axis=-1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_curr)
            corr = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)                    # [bq, bk]
            l_next = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            # dropout hits the WEIGHTS (numerator) only: the softmax
            # denominator l comes from the undropped p, matching
            # dropout(softmax(s)) @ v semantics
            p_v = p
            if drop_t is not None:
                keep = _tile_keep(plan, seed_ref, bhs[i], q_idx, kv_blk,
                                  drop_t)
                p_v = jnp.where(keep, p * (256.0 / drop_t), 0.0)
            acc_scr[i] = acc_scr[i] * corr + jax.lax.dot_general(
                p_v.astype(v_ref.dtype),
                plan.lanes(v_ref, i, Dv) if slot is None else v_all,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[i] = jnp.broadcast_to(m_next, m_scr[i].shape)
            l_scr[i] = jnp.broadcast_to(l_next, l_scr[i].shape)

    if band is not None:
        # the steps past the band's last key block: no work, and the
        # clamped index maps already elided their DMA
        @pl.when(kv_blk <= band.kv_last(q_idx))
        def _run_band():
            _body()
    elif causal:
        # skip fully-masked KV blocks (everything strictly above the
        # block diagonal): no MXU work, and the clamped index maps
        # already elided their DMA
        @pl.when(q_idx * bq + bq > kv_idx * bk)
        def _run():
            _body()
    else:
        _body()

    @pl.when(kv_idx == n_kv - 1)
    def _finish():
        for i in range(plan.hpb):
            denom = jnp.maximum(l_scr[i][:, :1], 1e-30)
            plan.store_lanes(o_ref, i, Dv,
                             (acc_scr[i] / denom).astype(o_ref.dtype))
            if lse_ref is not None:
                _to_lanes(lse_ref, i, m_scr[i] + jnp.log(
                    jnp.maximum(l_scr[i], 1e-30)))


def _bwd_tile(plan, i, q_idx, kv_idx, bh, seed_ref, q_ref, k_ref, v_ref,
              lse_ref, out_ref, do_ref, glse_ref, bias_ref, *, scale,
              causal, drop_t, slot=None, window=None):
    """Local head i's (q block, kv block) tile of the backward, built
    once for whichever products the calling kernel feeds from it:
    (q, k, p_v, ds) with p_v the DROPPED weights dv consumes
    (out = p_drop @ v) and ds = p * (dp - di). `slot`: `_Plan.kv_slot`;
    q_idx / kv_idx are the tile's blocks, `window` a band's width."""
    D, Dv, bq, bk = plan.D, plan.Dv, plan.bq, plan.bk
    q = plan.lanes(q_ref, i, D)                     # [bq, D]
    k = plan.lanes(k_ref, i, D) if slot is None \
        else plan.shared_kv(slot, k_ref, D)         # [bk, D]
    do = plan.lanes(do_ref, i, Dv)                  # [bq, Dv]
    lse = _to_rows(lse_ref, i, bq)                  # [bq, 1]
    di = jnp.sum(plan.lanes(out_ref, i, Dv).astype(jnp.float32)
                 * do.astype(jnp.float32), axis=-1, keepdims=True)
    if glse_ref is not None:
        di = di - _to_rows(glse_ref, i, bq)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = _biased(s, plan.bias_tile(bias_ref, i))
    if window is not None:
        s = _band_mask(s, q_idx, kv_idx, bq, bk, window)
    elif causal:
        s = _causal_mask(s, q_idx, kv_idx, bq, bk)
    p = jnp.exp(s - lse)                            # [bq, bk]
    # dO goes to the MXU in the stream's own dtype, as q, k and v do:
    # a float32 copy of a bf16 dO is the same numbers in twice the
    # bytes
    dp = jax.lax.dot_general(
        do, plan.lanes(v_ref, i, Dv) if slot is None
        else plan.shared_kv(slot, v_ref, Dv), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    p_v = p
    if drop_t is not None:
        # chain rule through p_drop = keep * p * 256/t: dp flows only
        # through kept weights (di already equals sum(p_drop * dp)
        # because out was computed with p_drop)
        keep = _tile_keep(plan, seed_ref, bh, q_idx, kv_idx, drop_t)
        p_v = jnp.where(keep, p * (256.0 / drop_t), 0.0)
        dp = jnp.where(keep, dp * (256.0 / drop_t), 0.0)
    return q, k, p_v, p * (dp - di)


def _fa_bwd_dq_kernel(plan, seed_ref, q_ref, k_ref, v_ref, lse_ref,
                      out_ref, do_ref, glse_ref, bias_ref, dq_ref,
                      ds_ref, dq_scr, *, scale, n_kv, q_axis, kv_axis,
                      causal, drop_t, band=None):
    kv_idx = pl.program_id(kv_axis)
    q_idx = pl.program_id(q_axis)
    D, bq, bk = plan.D, plan.bq, plan.bk
    bhs = [plan.bh(i) if drop_t is not None else None
           for i in range(plan.hpb)]
    slot = plan.kv_slot()
    kv_blk = kv_idx if band is None else band.kv_first(q_idx) + kv_idx
    window = None if band is None else band.window

    @pl.when(kv_idx == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body():
        for i in range(plan.hpb):
            _, k, _, ds = _bwd_tile(
                plan, i, q_idx, kv_blk, bhs[i], seed_ref, q_ref, k_ref,
                v_ref, lse_ref, out_ref, do_ref, glse_ref, bias_ref,
                scale=scale, causal=causal, drop_t=drop_t, slot=slot,
                window=window)
            if ds_ref is not None:
                plan.ds_store(ds_ref, i, ds.astype(ds_ref.dtype))
            dq_scr[i] += scale * jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if band is not None or causal:
        run = kv_blk <= band.kv_last(q_idx) if band is not None \
            else q_idx * bq + bq > kv_idx * bk

        @pl.when(run)
        def _run():
            _body()

        if ds_ref is not None:
            # the ds OUTPUT block for a skipped step is never written
            # by _body — zero it so dbias sums clean tiles
            @pl.when(jnp.logical_not(run))
            def _zero_ds():
                for i in range(plan.hpb):
                    plan.ds_store(ds_ref, i,
                                  jnp.zeros((bq, bk), ds_ref.dtype))
    else:
        _body()

    @pl.when(kv_idx == n_kv - 1)
    def _finish():
        for i in range(plan.hpb):
            plan.store_lanes(dq_ref, i, D,
                             dq_scr[i].astype(dq_ref.dtype))


def _fa_bwd_dkv_kernel(plan, seed_ref, q_ref, k_ref, v_ref, lse_ref,
                       out_ref, do_ref, glse_ref, bias_ref, dk_ref,
                       dv_ref, dq_ref, dk_scr, dv_scr, dq_scr, *, scale,
                       n_q, n_kv, q_axis, kv_axis, causal, drop_t,
                       band=None):
    """dk and dv of one kv block, accumulated over the inner q axis.
    With dq_ref (the fused backward) the same ds also feeds dq: the
    whole [Sq, hpb*D] dq of this (batch, head group) stays in dq_scr
    over both sequence axes, each q block's rows accumulating over the
    outer kv axis, ascending as in the dq kernel. With a band the q axis
    steps over the query blocks of the key block's band (q_idx counting
    steps, q_blk the block), and a query block's dq rows start at the
    first key block of its own band and end at its last."""
    q_idx = pl.program_id(q_axis)
    kv_idx = pl.program_id(kv_axis)
    D, Dv, bq, bk = plan.D, plan.Dv, plan.bq, plan.bk
    bhs = [plan.bh(i) if drop_t is not None else None
           for i in range(plan.hpb)]
    slot = plan.kv_slot()
    q_blk, window = q_idx, None
    if band is not None:
        q_blk, window = band.q_first(kv_idx) + q_idx, band.window
        live = q_blk <= band.q_last(kv_idx)

    @pl.when(q_idx == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if dq_ref is not None and band is not None:
        rows = pl.ds(pl.multiple_of(
            jnp.minimum(q_blk, band.q_last(kv_idx)) * bq, bq), bq)

        @pl.when(live & (kv_idx == band.kv_first(q_blk)))
        def _init_band_dq():
            dq_scr[rows, :] = jnp.zeros((bq, dq_scr.shape[1]),
                                        jnp.float32)
    elif dq_ref is not None:
        rows = pl.ds(pl.multiple_of(q_idx * bq, bq), bq)

        @pl.when(kv_idx == 0)
        def _init_dq():
            dq_scr[rows, :] = jnp.zeros((bq, dq_scr.shape[1]),
                                        jnp.float32)

    def _body():
        for i in range(plan.hpb):
            q, k, p_v, ds = _bwd_tile(
                plan, i, q_blk, kv_idx, bhs[i], seed_ref, q_ref, k_ref,
                v_ref, lse_ref, out_ref, do_ref, glse_ref, bias_ref,
                scale=scale, causal=causal, drop_t=drop_t, slot=slot,
                window=window)
            dv_scr[i] += jax.lax.dot_general(
                p_v.astype(do_ref.dtype), plan.lanes(do_ref, i, Dv),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = ds.astype(q.dtype)
            dk_scr[i] += scale * jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dq_ref is not None:
                dq_scr[rows, i * D:(i + 1) * D] += \
                    scale * jax.lax.dot_general(
                        ds, k, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)

    if band is not None:
        @pl.when(live)
        def _run_band():
            _body()
    elif causal:
        @pl.when(q_idx * bq + bq > kv_idx * bk)
        def _run():
            _body()
    else:
        _body()

    @pl.when(q_idx == n_q - 1)
    def _finish():
        for i in range(plan.hpb):
            plan.store_lanes(dk_ref, i, D,
                             dk_scr[i].astype(dk_ref.dtype))
            plan.store_lanes(dv_ref, i, Dv,
                             dv_scr[i].astype(dv_ref.dtype))

    if dq_ref is not None:
        # this q block has met its last kv block (a causally skipped
        # one adds nothing): its rows of the held output are final
        @pl.when(kv_idx == n_kv - 1 if band is None
                 else live & (kv_idx == band.kv_last(q_blk)))
        def _finish_dq():
            dq_ref[rows, :] = dq_scr[rows, :].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _seed_i32(dropout):
    """(uint32 key, t) -> (int32[2] SMEM seed, static t)."""
    if dropout is None:
        return None, None
    key, t = dropout
    if int(t) <= 0:
        # the kernels upscale by 256/t; the drop-everything edge must
        # be handled by the CALLER emitting zeros (ops/fused.py does)
        raise ValueError(
            "flash kernels cannot realize t<=0 (drop everything); "
            "emit zeros at the call site instead")
    return jax.lax.bitcast_convert_type(key, jnp.int32).reshape(2), \
        int(t)


def _window_band(window, causal, bq, bk, Sq, Sk):
    """The `_Band` of a windowed site (None without a window)."""
    if window is None:
        return None
    if not causal or int(window) < 1:
        raise ValueError(f"a sliding window ({window}) is causal and at "
                         f"least one key wide")
    return _Band(window, bq, bk, Sq // bq, Sk // bk)


def _fa_forward(q, k, v, bias, scale, block_q, block_k,
                return_lse=False, layout="bhsd", causal=False,
                dropout=None, window=None):
    """window: a causal site's sliding window, r - window < c <= r; its
    kv grid axis steps over the key blocks of each query block's band
    only (`_Band`), under the kernel's own name."""
    B, H, Sq, D = _dims(q, layout)
    Dv = v.shape[3]
    Sk = _seq_len(k, layout)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    n_kv = Sk // bk
    plan = _Plan(layout, B, H, Sq, Sk, D, bq, bk, Dv, _heads(k, layout))
    band = _window_band(window, causal, bq, bk, Sq, Sk)
    if band is not None:
        _kreg.count("flash_attention", "window")
        n_kv = band.kv_steps
    # a [bq, bk] bias tile a query block, or dropout's keep mask, keeps
    # the tile's own form (`_fa_kernel_rows`); every other site runs on
    # the transposed tile, where a lane block of more than one head
    # issues the next head's k q^T under this head's softmax
    rows = dropout is not None or (bias is not None and bias.shape[2] != 1)
    _kreg.count("flash_attention", "pipelined_fwd"
                if plan.hpb > 1 and not rows else "single_fwd")

    def _sds(shape, dtype):
        return _out_struct(shape, dtype, like=q)

    grid = plan.grid(Sq // bq, n_kv)
    qa, ka = plan.seq_axes(swap=False)
    kv_axis = len(grid) - 1
    seed, drop_t = _seed_i32(dropout)
    has_drop = seed is not None

    k_idx = None
    if band is not None:
        def k_idx(g):
            return band.kv_block(g[qa], g[ka])
    elif causal:
        # clamp the (sequential) kv axis to the diagonal block for
        # masked-out steps: repeated block index -> Mosaic elides the
        # k/v/bias DMA for the skipped upper triangle
        def k_idx(g):
            return jnp.minimum(g[ka], (g[qa] * bq + bq - 1) // bk)

    in_specs = [
        plan.row_spec(bq, D, qa),
        plan.row_spec(bk, D, ka, idx=k_idx, shared=True),
        plan.row_spec(bk, Dv, ka, idx=k_idx, shared=True),
    ]
    args = [plan.rows(q), plan.rows(k), plan.rows(v)]
    has_bias = bias is not None
    if has_bias:
        br, bfac, _, _ = plan.bias_info(bias)
        in_specs.append(bfac(qa, ka, k_idx=k_idx))
        args.append(br)
    if has_drop:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)

    out_rows = ((B, Sq, H * Dv) if layout == "bshd"
                else (B * H, Sq, Dv))
    out_specs = [plan.row_spec(bq, Dv, qa)]
    out_shape = [_sds(out_rows, q.dtype)]
    if return_lse:
        out_specs.append(plan.lse_spec(qa))
        out_shape.append(_sds(plan.lse_shape(), jnp.float32))

    def kern(*refs):
        i = 3
        b_ref = refs[i] if has_bias else None
        i += has_bias
        seed_ref = refs[i] if has_drop else None
        i += has_drop
        o_ref = refs[i]
        i += 1
        lse_ref = refs[i] if return_lse else None
        i += return_lse
        m, l, a = refs[i:i + 3]
        return (_fa_kernel_rows if rows else _fa_kernel)(
            plan, seed_ref, refs[0], refs[1], refs[2], b_ref, o_ref,
            lse_ref, m, l, a, scale=scale, n_kv=n_kv, q_axis=qa,
            kv_axis=kv_axis, causal=causal, drop_t=drop_t, band=band)

    res = pl.pallas_call(
        kern,
        name="flash_attention_fwd" if band is None
        else "flash_attention_window_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if return_lse else out_specs[0],
        out_shape=out_shape if return_lse else out_shape[0],
        # running max and sum ([bq, 128] lane-broadcast columns, or [1,
        # bq] rows in 8 sublanes transposed) and the accumulator ([bq,
        # Dv], or [Dv, bq] transposed)
        scratch_shapes=[
            pltpu.VMEM((plan.hpb, bq, 128) if rows else (plan.hpb, 8, bq),
                       jnp.float32),
            pltpu.VMEM((plan.hpb, bq, 128) if rows else (plan.hpb, 8, bq),
                       jnp.float32),
            pltpu.VMEM((plan.hpb, bq, Dv) if rows else (plan.hpb, Dv, bq),
                       jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * kv_axis
            + ("arbitrary",)),
        interpret=_INTERPRET,
    )(*args)

    shape = (B, Sq, H, Dv) if layout == "bshd" else (B, H, Sq, Dv)
    if return_lse:
        # float32 [B, H, Sq] in either layout
        return res[0].reshape(shape), res[1].reshape(
            -1, plan.bqp)[:, :bq].reshape(B, H, Sq)
    return res.reshape(shape)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

# The fused backward holds one (batch, head group)'s whole dq in VMEM.
# A v5e core has 128 MiB of it, of which Mosaic gives one kernel 16 MiB
# unless the call asks for more: the fused call asks for that plus what
# its resident dq takes, and a dq over the budget (under a third of the
# core's VMEM) falls back to the split pair.
_VMEM_DEFAULT_LIMIT = 16 << 20
_FUSED_DQ_VMEM_BUDGET = 40 << 20


def _resident_dq_bytes(plan, dtype):
    """VMEM the fused backward's dq holds: the f32 accumulator
    [Sq, hpb*D] and the output block of the same shape in the stream's
    dtype, which Pallas double-buffers; lanes padded to 128."""
    lanes = -(-plan.hpb * plan.D // 128) * 128
    return plan.Sq * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)


def _fa_backward(q, k, v, bias, out, lse, g, scale, block_q, block_k,
                 g_lse=None, layout="bhsd", want_dbias=None,
                 causal=False, dropout=None, window=None):
    """Kernel-path backward: returns (dq, dk, dv, dbias?).

    One fused kernel builds each (q block, kv block) tile's s, p, dp and
    ds once and writes dq, dk and dv from it. The split pair (a dq
    kernel over a (q, kv) grid, then the dk/dv kernel) runs only where
    the fused one cannot: a demanded dbias (the ds output follows the
    dq-style grid) or a dq too long to stay in VMEM. Which of the two a
    call site took is counted as `fused_bwd` / `split_bwd`, and every
    call as `narrow_lse`.

    lse is the forward's float32 [B, H, Sq]; g_lse (per-row lse
    cotangent, likewise) folds into the di term inside the kernels:
    ds = p*(dp - (di - g_lse)).

    want_dbias=False suppresses the ds OUTPUT while still adding the
    bias into the recomputed scores: ds is an O(B*H*Sq*Sk) f32 buffer a
    multi-output custom call cannot DCE (measured 2.1 GB/site at B=4
    S=4096), and a padding/causal-mask bias never needs a gradient.

    window: as `_fa_forward`'s. The dk/dv kernel's q axis steps over
    the query blocks of each key block's band, the dq kernel's kv axis
    over the key blocks of each query block's, under the kernels' own
    names (`flash_attention_window_bwd`, `_window_dq`)."""
    B, H, Sq, D = _dims(q, layout)
    Dv = v.shape[3]
    Sk = _seq_len(k, layout)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    n_q = Sq // bq
    n_kv = Sk // bk
    plan = _Plan(layout, B, H, Sq, Sk, D, bq, bk, Dv, _heads(k, layout))
    band = _window_band(window, causal, bq, bk, Sq, Sk)
    args = [plan.rows(q), plan.rows(k), plan.rows(v), plan.lse_rows(lse),
            plan.rows(out), plan.rows(g)]
    has_glse = g_lse is not None
    if has_glse:
        args.append(plan.lse_rows(g_lse))
    has_bias = bias is not None
    if has_bias:
        # bias always feeds the score recompute; ds is emitted ONLY
        # when a bias gradient is actually demanded
        br, bfac, per_head, per_q = plan.bias_info(bias)
        args.append(br)
    seed, drop_t = _seed_i32(dropout)
    has_drop = seed is not None
    if has_drop:
        args.append(seed)
    want_dbias = has_bias and not _is_mask(bias) \
        and (want_dbias is None or bool(want_dbias))
    if want_dbias and band is not None:
        raise NotImplementedError("a bias gradient under a sliding window")
    resident = _resident_dq_bytes(plan, q.dtype)
    fused = not want_dbias and resident <= _FUSED_DQ_VMEM_BUDGET
    _kreg.count("flash_attention", "fused_bwd" if fused else "split_bwd")
    _kreg.count("flash_attention", "narrow_lse")

    def _sds(shape, dtype):
        return _out_struct(shape, dtype, like=q)

    def out_rows(S, W=D):
        return ((B, S, H * W) if layout == "bshd" else (B * H, S, W))

    def _unrows(o, S, W=D):
        if layout == "bshd":
            return o.reshape(B, S, H, W)
        return o.reshape(B, H, S, W)

    def in_specs(qa, ka, q_idx=None, k_idx=None):
        """Specs of `args` on a grid whose q / kv block indices sit at
        positions qa / ka; q_idx / k_idx clamp a sequential axis."""
        specs = [
            plan.row_spec(bq, D, qa, idx=q_idx),
            plan.row_spec(bk, D, ka, idx=k_idx, shared=True),
            plan.row_spec(bk, Dv, ka, idx=k_idx, shared=True),
            plan.lse_spec(qa, idx=q_idx),
            plan.row_spec(bq, Dv, qa, idx=q_idx),
            plan.row_spec(bq, Dv, qa, idx=q_idx),
        ]
        if has_glse:
            specs.append(plan.lse_spec(qa, idx=q_idx))
        if has_bias:
            specs.append(bfac(qa, ka, q_idx=q_idx, k_idx=k_idx))
        if has_drop:
            specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        return specs

    def split_refs(refs):
        """(the six streams, glse, bias, seed, outputs and scratch)."""
        i = 6
        gl_r = refs[i] if has_glse else None
        i += has_glse
        b_r = refs[i] if has_bias else None
        i += has_bias
        seed_r = refs[i] if has_drop else None
        i += has_drop
        return refs[:6], gl_r, b_r, seed_r, refs[i:]

    dq = dbias = None
    if not fused:
        # ---- dq (+ds when dbias is needed): reduction over kv --------
        grid = plan.grid(n_q, n_kv if band is None else band.kv_steps)
        qa, ka = plan.seq_axes(swap=False)
        kv_axis = len(grid) - 1

        k_idx = None
        if band is not None:
            def k_idx(g):
                return band.kv_block(g[qa], g[ka])
        elif causal:
            def k_idx(g):
                return jnp.minimum(g[ka], (g[qa] * bq + bq - 1) // bk)

        out_specs = [plan.row_spec(bq, D, qa)]
        out_shape = [_sds(out_rows(Sq), q.dtype)]
        if want_dbias:
            out_specs.append(plan.ds_spec(qa, ka))
            out_shape.append(_sds(plan.ds_shape(), jnp.float32))

        def kern_dq(*refs):
            streams, gl_r, b_r, seed_r, (dq_r, *ds_r, scr) = \
                split_refs(refs)
            return _fa_bwd_dq_kernel(plan, seed_r, *streams, gl_r, b_r,
                                     dq_r, ds_r[0] if ds_r else None,
                                     scr, scale=scale, n_kv=grid[-1],
                                     q_axis=qa, kv_axis=kv_axis,
                                     causal=causal, drop_t=drop_t,
                                     band=band)

        dq, *ds = pl.pallas_call(
            kern_dq,
            name="flash_attention_dq" if band is None
            else "flash_attention_window_dq",
            grid=grid,
            in_specs=in_specs(qa, ka, k_idx=k_idx),
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((plan.hpb, bq, D), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * kv_axis
                + ("arbitrary",)),
            interpret=_INTERPRET,
        )(*args)
        if want_dbias:
            dbias = ds[0].reshape(B, H, Sq, Sk)
            if not per_head:
                dbias = dbias.sum(axis=1, keepdims=True)
            if not per_q:
                dbias = dbias.sum(axis=2, keepdims=True)
            dbias = dbias.astype(bias.dtype)

    # ---- dk/dv (+dq when fused): reduction over q --------------------
    n_q_steps = n_q if band is None else band.q_steps
    grid = plan.grid(n_kv, n_q_steps)
    qa, ka = plan.seq_axes(swap=True)
    q_axis = len(grid) - 1

    q_idx_f = None
    if band is not None:
        def q_idx_f(g):
            return band.q_block(g[ka], g[qa])
    elif causal:
        # the q stream's masked-out HEAD (q blocks strictly above the
        # diagonal) clamps forward to the diagonal block
        def q_idx_f(g):
            return jnp.maximum(g[qa], (g[ka] * bk) // bq)

    out_specs = [plan.row_spec(bk, D, ka), plan.row_spec(bk, Dv, ka)]
    out_shape = [_sds(out_rows(Sk), k.dtype),
                 _sds(out_rows(Sk, Dv), v.dtype)]
    scratch = [pltpu.VMEM((plan.hpb, bk, D), jnp.float32),
               pltpu.VMEM((plan.hpb, bk, Dv), jnp.float32)]
    semantics = ("parallel",) * q_axis + ("arbitrary",)
    vmem_limit = None
    if fused:
        # one block the length of the sequence whose index follows
        # neither sequence axis: Pallas holds it from a (batch, head
        # group)'s first step to its last and writes it back once
        out_specs.append(plan.row_spec(Sq, D, None, idx=lambda g: 0))
        out_shape.append(_sds(out_rows(Sq), q.dtype))
        scratch.append(pltpu.VMEM((Sq, plan.hpb * D), jnp.float32))
        semantics = ("parallel",) * ka + ("arbitrary", "arbitrary")
        vmem_limit = _VMEM_DEFAULT_LIMIT + resident

    def kern_dkv(*refs):
        streams, gl_r, b_r, seed_r, rest = split_refs(refs)
        if fused:
            dk_r, dv_r, dq_r, ks, vs, qs = rest
        else:
            (dk_r, dv_r, ks, vs), dq_r, qs = rest, None, None
        return _fa_bwd_dkv_kernel(plan, seed_r, *streams, gl_r, b_r,
                                  dk_r, dv_r, dq_r, ks, vs, qs,
                                  scale=scale, n_q=n_q_steps, n_kv=n_kv,
                                  q_axis=q_axis, kv_axis=ka,
                                  causal=causal, drop_t=drop_t, band=band)

    res = pl.pallas_call(
        kern_dkv,
        # The fused kernel keeps the dk/dv kernel's name: it is that
        # kernel, which now also accumulates dq, and the benchmark's
        # readers (mla_flash_roofline_pct sums the events named
        # flash_attention_fwd / _dq / _dkv) find a kernel by its name.
        # Under a new name its time would drop out of that sum.
        name="flash_attention_dkv" if band is None
        else "flash_attention_window_bwd",
        grid=grid,
        in_specs=in_specs(qa, ka, q_idx=q_idx_f),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=vmem_limit),
        interpret=_INTERPRET,
    )(*args)
    dk, dv = res[:2]
    if fused:
        dq = res[2]
    return (_unrows(dq, Sq), _group_sum(_unrows(dk, Sk), plan),
            _group_sum(_unrows(dv, Sk, Dv), plan), dbias)


def _group_sum(dx, plan):
    """dk or dv as the kernels write it, one slice a QUERY head, summed
    over the query heads that share each key / value head (float32
    accumulation): [B, S, H, D] -> [B, S, Hkv, D] (bshd; bhsd alike)."""
    if plan.group == 1:
        return dx
    axis = 2 if plan.layout == "bshd" else 1
    split = dx.shape[:axis] + (plan.H // plan.group, plan.group) \
        + dx.shape[axis + 1:]
    return jnp.sum(dx.reshape(split).astype(jnp.float32),
                   axis=axis + 1).astype(dx.dtype)


def _kernel_ok(q, k, block_q, block_k, layout="bhsd", v=None):
    import os
    if os.environ.get("PT_FORCE_COMPOSED"):   # A/B-measurement knob
        return False
    Sq, Sk = _seq_len(q, layout), _seq_len(k, layout)
    D = q.shape[3]
    Dv = D if v is None else v.shape[3]
    H, Hkv = _heads(q, layout), _heads(k, layout)
    if H % Hkv:
        return False
    if layout == "bshd":
        hpb = _heads_per_block(H, D, Dv)
        # real Mosaic requires strict 128-lane (or full-minor) blocks;
        # the interpreter does not care, which lets CPU tests cover
        # small shapes
        if not _INTERPRET and ((hpb * D) % 128 or (hpb * Dv) % 128):
            return False
        # grouped queries: a lane block is ONE head, so that a query
        # head's k / v block is simply its key head's, or hpb packed
        # heads that all read one key head (the group a multiple of
        # hpb) out of a key lane block of hpb whole key heads
        if Hkv != H and hpb != 1 and ((H // Hkv) % hpb or Hkv % hpb):
            return False
    return (Sq % min(block_q, Sq) == 0 and Sk % min(block_k, Sk) == 0
            and D % 8 == 0 and Dv % 8 == 0
            and (_INTERPRET or jax.default_backend() != "cpu"))


# Kernel-vs-composed dispatch. r5 measured the crossover IN THE MIDDLE
# of the range (VERDICT r4 #2) with whole-model bench A/Bs
# (transformer-base, bf16 stream, bshd, causal decoder + attention
# dropout; PT_FORCE_{KERNEL,COMPOSED} at every point — tokens/s):
#
#   S=128  B=96: composed 204.6k  kernel 157.6k   -> composed
#   S=512  B=16: composed 116.2k  kernel  78.2k   -> composed
#   S=512  B=32: composed 112.3k  kernel  80.2k   -> composed
#   S=1024 B=4 : composed  76.4k  kernel 135.6k   -> KERNEL 1.8x
#   S=1024 B=8 : composed  72.9k  kernel 145.8k   -> KERNEL 2.0x
#   S=2048 B=4 : composed  41.0k  kernel 101.7k   -> KERNEL 2.5x
#   S=4096 B=4 : composed thrash  kernel  67.7k   -> KERNEL
#
# The crossover is SEQUENCE-keyed, not score-element-keyed: S=512 B=32
# and S=1024 B=8 have identical B*H*Sq*Sk yet opposite winners (r4's
# 2^28-element rule measured only the endpoints and missed this —
# mid-range users sat on the wrong path up to 2x). Two reasons the
# sequence length decides: (a) the block policy only reaches the big
# 512/1024 tiles the kernels need at S >= 1024, and (b) the composed
# path's per-site [B,H,S,S] temporaries grow quadratically in S but
# XLA keeps them fused/tiled acceptably while S^2 is small regardless
# of batch. Interpret mode always uses the kernels so CPU tests cover
# them.
_KERNEL_MIN_SEQ_PRODUCT = 1024 * 1024      # Sq * Sk


def use_kernel_path(q, k, block_q=128, block_k=128, layout="bhsd",
                    v=None):
    """True when the fused-attention op should route through the Pallas
    kernels rather than the composed einsum formulation. `v` is given
    where its head width may differ from q's and k's.

    Registry-governed: FLAGS_use_custom_kernels off (or
    "flash_attention" in PT_KERNEL_DENY) forces the composed path, and
    every trace-time decision lands in the dispatch stats /
    pt_kernel_dispatch_total, like registry-selected kernels."""
    import os
    from . import registry as _kreg
    if not _kreg.allowed("flash_attention"):
        _kreg.count("flash_attention", "denied")
        return False
    ok = _kernel_ok(q, k, block_q, block_k, layout, v) \
        and not _kreg.in_auto_partitioned_trace()
    if ok and not _INTERPRET \
            and not os.environ.get("PT_FORCE_KERNEL"):
        ok = (_seq_len(q, layout) * _seq_len(k, layout)
              >= _KERNEL_MIN_SEQ_PRODUCT)
    _kreg.count("flash_attention", "custom" if ok else "lowered")
    return ok


def _attn_reference(q, k, v, bias, scale, layout="bhsd",
                    dropout=None, causal=False, window=None):
    """Composed attention. dropout = (key, t) applies u8-threshold
    attention-weights dropout with exact-realized-probability upscale
    (same contract as the dropout op, ops/nn.py). causal masks to the
    lower triangle in ABSOLUTE positions (rows >= cols), matching the
    kernels' block mask; a `window` to the band rows - window < cols <=
    rows."""
    eq = "bqhd,bkhd->bhqk" if layout == "bshd" else "bhqd,bhkd->bhqk"
    group = _heads(q, layout) // _heads(k, layout)
    if group > 1:       # grouped queries: each key / value head `group` times
        axis = 2 if layout == "bshd" else 1
        k, v = (jnp.repeat(x, group, axis=axis) for x in (k, v))
    s = jnp.einsum(eq, q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = jnp.where(bias != 0, s, _NEG_INF) if _is_mask(bias) \
            else s + bias.astype(jnp.float32)
    if window is not None:
        s = _band_mask_dense(s, window)
    elif causal:
        s = _causal_mask_dense(s)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    if dropout is not None:
        key, t = dropout
        state = jax.lax.bitcast_convert_type(
            jnp.concatenate([key, key ^ jnp.uint32(0x9E3779B9)]),
            jnp.uint32).reshape(4)
        _, bits = jax.lax.rng_bit_generator(state, p.shape,
                                            dtype=jnp.uint8)
        p = jnp.where(bits < jnp.uint8(t), p / (t / 256.0), 0.0)
    eo = "bhqk,bkhd->bqhd" if layout == "bshd" else "bhqk,bhkd->bhqd"
    return jnp.einsum(eo, p, v)


def _attn_reference_lse(q, k, v, bias, scale, causal=False, window=None):
    """Composed attention ([B,H,S,D] only) that also returns logsumexp
    over keys — the CPU/odd-shape counterpart of return_lse mode. Bias,
    grouped key heads and `window` as `_attn_reference`'s."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = jnp.where(bias != 0, s, _NEG_INF) if _is_mask(bias) \
            else s + bias.astype(jnp.float32)
    if window is not None:
        s = _band_mask_dense(s, window)
    elif causal:
        s = _causal_mask_dense(s)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = (e / jnp.maximum(l, 1e-30)).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    return out, lse


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def flash_attention(q, k, v, bias=None, scale=1.0, block_q=128,
                    block_k=128, layout="bhsd", causal=False,
                    need_dbias=None, window=None):
    """q [B,H,Sq,D] (bhsd) or [B,Sq,H,D] (bshd); k/v likewise;
    bias [B,1|H,Sq|1,Sk] additive in either layout; causal masks to
    rows >= cols and SKIPS fully-masked KV blocks in the kernels.
    need_dbias (static): False suppresses the ds/dbias backward output
    entirely — a multi-output Pallas call cannot DCE the ds tile, so
    callers that never read the bias gradient must say so here; None
    (default) keeps the historical behavior (dbias iff bias given).
    window (static, causal only): a sliding window, rows - window <
    cols <= rows; the kernels' grids cover the band's blocks alone."""
    if _kernel_ok(q, k, block_q, block_k, layout, v):
        return _fa_forward(q, k, v, bias, scale, block_q, block_k,
                           layout=layout, causal=causal, window=window)
    qb, kb, vb = q, k, v
    if layout == "bshd":
        qb, kb, vb = (jnp.moveaxis(x, 2, 1) for x in (q, k, v))
    out = _attn_reference(qb, kb, vb, bias, scale, causal=causal,
                          window=window)
    return jnp.moveaxis(out, 1, 2) if layout == "bshd" else out


def _fa_fwd(q, k, v, bias, scale, block_q, block_k, layout, causal,
            need_dbias, window):
    if _kernel_ok(q, k, block_q, block_k, layout, v):
        out, lse = _fa_forward(q, k, v, bias, scale, block_q, block_k,
                               return_lse=True, layout=layout,
                               causal=causal, window=window)
    else:
        qb, kb, vb = q, k, v
        if layout == "bshd":
            qb, kb, vb = (jnp.moveaxis(x, 2, 1) for x in (q, k, v))
        if window is None:
            out, lse = _attn_reference_lse(qb, kb, vb, bias, scale,
                                           causal=causal)
        else:
            # the composed backward recomputes from (q, k, v): no lse
            out, lse = _attn_reference(qb, kb, vb, bias, scale,
                                       causal=causal, window=window), None
        if layout == "bshd":
            out = jnp.moveaxis(out, 1, 2)
    return out, (q, k, v, bias, out, lse)


def _fa_bwd(scale, block_q, block_k, layout, causal, need_dbias, window,
            res, g):
    q, k, v, bias, out, lse = res
    want_dbias = (bias is not None) if need_dbias is None \
        else bool(need_dbias)
    if use_kernel_path(q, k, block_q, block_k, layout, v):
        dq, dk, dv, dbias = _fa_backward(
            q, k, v, bias, out, lse, g, scale, block_q, block_k,
            layout=layout, causal=causal, want_dbias=want_dbias,
            window=window)
        return dq, dk, dv, dbias if want_dbias else None

    def f(q, k, v, bias):
        return _attn_reference(q, k, v, bias, scale, layout=layout,
                               causal=causal, window=window)
    _, vjp = jax.vjp(f, q, k, v, bias)
    dq, dk, dv, dbias = vjp(g)
    return dq, dk, dv, dbias if want_dbias and bias is not None \
        else None


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def _lse_dispatch(q, k, v, bias, scale, block_q, block_k):
    """Kernel when the shapes tile onto the MXU (or interpret mode is
    forced for CPU tests), composed formulation otherwise."""
    if _kernel_ok(q, k, block_q, block_k):
        return _fa_forward(q, k, v, bias, scale, block_q, block_k,
                           return_lse=True)
    return _attn_reference_lse(q, k, v, bias, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention_lse(q, k, v, bias=None, scale=1.0, block_q=128,
                        block_k=128):
    """Flash attention ([B,H,S,D]) returning (out, lse) — the block
    primitive for ring attention's online-softmax merge. Differentiable
    on every backend: the backward recomputes through the composed
    lse-emitting formulation (handles nonzero cotangents on BOTH
    outputs, since the ring merge arithmetic uses lse downstream)."""
    return _lse_dispatch(q, k, v, bias, scale, block_q, block_k)


def _fal_fwd(q, k, v, bias, scale, block_q, block_k):
    out, lse = _lse_dispatch(q, k, v, bias, scale, block_q, block_k)
    return (out, lse), (q, k, v, bias, out, lse)


def _fal_bwd(scale, block_q, block_k, res, g):
    q, k, v, bias, out, lse = res
    g_out, g_lse = g
    if use_kernel_path(q, k, block_q, block_k):
        # the lse cotangent folds into the per-row correction term:
        # dlse/ds = p, so ds = p*(dp - di + g_lse) — the kernels
        # subtract g_lse from di
        dq, dk, dv, dbias = _fa_backward(
            q, k, v, bias, out, lse, g_out, scale, block_q, block_k,
            g_lse=g_lse)
        return dq, dk, dv, dbias

    def f(q, k, v, bias):
        return _attn_reference_lse(q, k, v, bias, scale)

    _, vjp = jax.vjp(f, q, k, v, bias)
    dq, dk, dv, dbias = vjp((g_out, g_lse))
    return dq, dk, dv, None if bias is None else dbias


flash_attention_lse.defvjp(_fal_fwd, _fal_bwd)


# ---------------------------------------------------------------------------
# registry entry — dispatch itself stays in use_kernel_path (the
# sequence-keyed crossover above needs more context than a Signature
# carries), but registering here puts flash attention in the same
# deny/flag/stats/parity surface as every other custom kernel.
# ---------------------------------------------------------------------------
from . import registry as _kreg  # noqa: E402


def _fa_eligible(sig):
    # shape-keyed dispatch lives in use_kernel_path/_kernel_ok; the
    # registry entry exists for governance (flag/deny), attribution,
    # and parity completeness.
    return True


_kreg.register_kernel(
    "flash_attention", op_types=("fused_attention",),
    eligible=_fa_eligible, run=flash_attention,
    doc="online-softmax attention fwd + fused dq/dk/dv bwd (O(S) "
        "memory); sequence-keyed crossover vs the composed path in "
        "use_kernel_path")
