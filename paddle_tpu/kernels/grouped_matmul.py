"""Grouped matmul over the experts a chip holds (Pallas), dropless.

A mixture-of-experts layer sends each token to `top_k` of `num_experts`
experts; this chip holds `experts_held` of them. The rows routed to held
experts are gathered into ONE row buffer, expert by expert, three
kernels do every matrix product of the expert feed-forward over it and
a fourth sums the result back to the tokens:

  moe_grouped_matmul_fwd   out[r] = lhs[r] @ rhs[expert(r)]
  moe_grouped_matmul_dx    dlhs[r] = dout[r] @ rhs[expert(r)]^T
  moe_grouped_matmul_dw    drhs[e] = sum over rows r of e: lhs[r]^T dout[r]
  moe_combine              out[token(r)] += buf[r]   (short prefixes)

The row buffer (`plan_rows`). Shapes are static, token counts are not,
and no token is ever dropped: the buffer has room for the worst case,
every choice of every token held here (`top_k * T` rows), plus one tile
a held expert so that every group can start on a tile boundary. A
group's rows are padded up to whole tiles of `TILE_ROWS`; an expert no
token chose keeps one (all-padding) tile, so the dw kernel visits, and
so writes, every expert's block. Each tile therefore belongs to exactly
one expert (`tile_expert`, scalar-prefetched), and the tiles in use are
the first `n_active` of the buffer. Work is done only for those: the
index maps clamp a later grid step to the last tile in use, so Mosaic
sees a repeated block index and elides its DMA, and `pl.when` skips its
body. Rows past the last tile in use are never written: whatever reads
the buffer masks them (`valid`), it does not multiply them by zero.

Prefixes (`prefix_rows`, `prefix_plan`, `prefix_index`). The kernels skip
the tiles past `n_active`; what XLA does around them (the gathers into
the buffer, the activation, the casts) cannot, its shapes are static. So
the caller runs the whole layer over a static PREFIX of the buffer that
holds every tile in use, picked on the device from `n_active` among a
short ladder of sizes: a chip that holds `experts_held` of `num_experts`
sees about that share of the choices, so the ladder is the buffer that
share, twice and four times that share would need, then the worst case.
The worst case stays: the layer is dropless whatever the router does,
only slower.

The combine (`combine`, `combine_by_rows`; kernel `moe_combine`). The
layer's result is each token's held rows summed out of the buffer,
`[rows, D] -> [T, D]` float32. Over a short prefix a fourth kernel does
it from the ROWS: grid (D blocks, row tiles), the `[T, D block]` output
resident in VMEM and zeroed at the first tile, each tile streamed once
(the same clamp past `n_active`), each valid row added into its token's
row, `out[tok] += buf[r]`, the token ids scalar-prefetched. It reads only
tiles in use and moves `rows in use x D + T x D` where the gather over
every choice of every token (ops/decoder.py `_combine`) moves `T x top_k
x D` three times; its time grows with the rows, so the rule is static in
the prefix's rows (`COMBINE_MAX_SHARE`) and the worst case keeps the
gather. It belongs to the layer's one kernel decision, not a second one.

Routing. `select()`-governed like fused_adam (kernels/registry.py): off
the CPU and when not denied the three kernels run; otherwise the
`lowered` path computes the same three products over the same buffer
with `lax.ragged_dot_general`, which XLA can partition and a CPU can
run. Decisions land in `dispatch_stats()` under `moe_grouped_matmul`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

TILE_ROWS = 128
# the dw kernel's output block [bk, n] f32 is held to this many bytes
_DW_BLOCK_BYTES = 2 * 1024 * 1024
# Mosaic's scoped VMEM unless a call asks for more; a call whose blocks,
# double-buffered, do not fit asks for what they take and this much beside
_VMEM_DEFAULT_LIMIT = 16 << 20
_VMEM_MARGIN = 4 << 20
# the combine kernel's resident [T, bd] f32 output block is held to this
_COMBINE_BLOCK_BYTES = 16 << 20
# The combine goes over a prefix's rows (`combine`) where they are at most
# this share of all the choices, T * top_k, and over every choice (the
# gather) past it. tools/bench_moe_combine.py on one v5e (PR 38; ms a
# call, bf16 rows + float32 rows, kernel against gather), by prefix:
# kanana2_s4096 0.42 / 0.43 / 0.57 against 2.35 / 2.36 / 2.52 at 0.21 /
# 0.33 / 0.58 of the choices; keye2_s8192 0.75 / 1.03 / 1.70 against 2.67 /
# 4.86 / 6.91 at 0.16 / 0.28 / 0.53; twotower_s4096 0.44 / 0.42 / 0.50
# against 3.20 / 3.21 / 3.30 at 0.10 / 0.17 / 0.29. The kernel's time grows
# with the rows in use (0.015-0.03 us each), the gather's hardly at all;
# the kernel still read less with 0.67-0.83 of the choices in use (the
# worst-case prefix: 0.92 against 3.14, 3.04 against 6.37, 1.13 against
# 4.94), which is as far as it was measured: 0.75. The worst case, whose
# rows pass T * top_k, and a layer that holds every expert keep the
# gather, which is the needed work when every choice is held.
COMBINE_MAX_SHARE = 0.75
# the kernel's token table is scalar-prefetched: 4 bytes a row of the
# chip's 1 MiB of SMEM (a described v5e refuses 262,144 rows)
_COMBINE_MAX_ROWS = 196608
# rows of a tile the combine kernel adds between two loop tests
_COMBINE_UNROLL = 8

__all__ = ["TILE_ROWS", "plan_rows", "buffer_rows", "prefix_rows",
           "prefix_plan", "prefix_index", "gmm", "gmm_dx", "gmm_dw",
           "combine", "combine_by_rows", "COMBINE_MAX_SHARE", "use_kernels"]


# ---------------------------------------------------------------------------
# the row buffer
# ---------------------------------------------------------------------------

def buffer_rows(n_choices: int, experts_held: int,
                tile: int = TILE_ROWS) -> int:
    """Rows of the worst-case buffer: every choice held here, each group
    padded to whole tiles (at most one tile of padding a group)."""
    return (-(-n_choices // tile) + experts_held) * tile


def plan_rows(local_expert, experts_held: int, tile: int = TILE_ROWS):
    """Lay the choices routed to held experts out in the row buffer.

    local_expert  int32 [n_choices]: the held expert's index in
                  [0, experts_held) or anything else for a choice that
                  is routed to an expert another chip holds.
    Returns a dict of int32/bool arrays:
      row_of_choice [n_choices]  the choice's row (only where `held`)
      held          [n_choices]  the choice is routed to a held expert
      choice_of_row [rows]       the choice a row carries (0 for padding)
      valid         [rows]       the row carries a choice
      tile_expert   [rows/tile]  the expert each tile belongs to
      n_active      [1]          tiles in use: the first n_active
      sizes         [experts_held]  rows routed to each held expert
    """
    n = local_expert.shape[0]
    e = experts_held
    rows = buffer_rows(n, e, tile)
    n_tiles = rows // tile
    held = (local_expert >= 0) & (local_expert < e)
    key = jnp.where(held, local_expert, e).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(e, dtype=jnp.int32)[None],
                    axis=0, dtype=jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    first_sorted = jnp.cumsum(sizes) - sizes        # group's first, sorted
    tiles = jnp.maximum(1, -(-sizes // tile))
    tile_end = jnp.cumsum(tiles)
    first_row = (tile_end - tiles) * tile           # group's first row
    n_active = tile_end[-1:]

    # rows -> choices, by gathers alone
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles, dtype=jnp.int32),
                         side="right").astype(jnp.int32), e - 1)
    row = jnp.arange(rows, dtype=jnp.int32)
    row_expert = tile_expert[row // tile]
    offset = row - first_row[row_expert]
    valid = (offset < sizes[row_expert]) & (row // tile < n_active[0])
    src = jnp.clip(first_sorted[row_expert] + offset, 0, n - 1)
    choice_of_row = jnp.where(valid, order[src], 0)

    # choices -> rows: a choice's rank in the sorted order
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    safe = jnp.minimum(key, e - 1)
    row_of_choice = jnp.where(
        held, first_row[safe] + rank - first_sorted[safe], 0)
    return {"row_of_choice": row_of_choice, "held": held,
            "choice_of_row": choice_of_row, "valid": valid,
            "tile_expert": tile_expert, "n_active": n_active,
            "sizes": sizes}


def prefix_rows(n_choices: int, experts_held: int, num_experts: int,
                tile: int = TILE_ROWS):
    """The prefixes of the worst-case buffer a layer may run over, in
    rows, ascending, the worst case last: the buffers of a share s, 2s
    and 4s of the choices (those under all of them), s = experts_held /
    num_experts being the share uniform routing sends here. One entry,
    the worst case, where every expert is held."""
    worst = buffer_rows(n_choices, experts_held, tile)
    rows = {buffer_rows(-(-n_choices * experts_held * m // num_experts),
                        experts_held, tile)
            for m in (1, 2, 4) if experts_held * m < num_experts}
    return sorted(r for r in rows if r < worst) + [worst]


def prefix_plan(plan, rows: int, tile: int = TILE_ROWS):
    """The plan over the buffer's first `rows` rows. It is the same
    function of the routing wherever n_active * tile <= rows: the tiles
    in use are the buffer's first, and `row_of_choice` of a held choice
    lies among them."""
    if rows == plan["valid"].shape[0]:
        return plan
    return dict(plan, choice_of_row=plan["choice_of_row"][:rows],
                valid=plan["valid"][:rows],
                tile_expert=plan["tile_expert"][:rows // tile])


def prefix_index(plan, ladder, tile: int = TILE_ROWS):
    """int32 scalar: the first prefix of `ladder` (`prefix_rows`) that
    holds every tile in use, i.e. how many of the shorter ones n_active
    exceeds."""
    edges = jnp.asarray([r // tile for r in ladder[:-1]], jnp.int32)
    return jnp.sum(plan["n_active"][0] > edges, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _in_use(t, n_active):
    """Clamp a grid step past the tiles in use to the last of them: a
    repeated block index, so no DMA."""
    return jnp.minimum(t, n_active[0] - 1)


def _gmm_kernel(te_ref, na_ref, lhs_ref, rhs_ref, out_ref, *, transpose):
    @pl.when(pl.program_id(0) < na_ref[0])
    def _run():
        dims = (((1,), (1,)), ((), ())) if transpose \
            else (((1,), (0,)), ((), ()))
        out_ref[...] = lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _params(semantics, *blocks):
    """Compiler parameters of a call whose double-buffered `blocks`
    ((shape, dtype) each) may pass Mosaic's default VMEM: one expert's
    [2688, 1856] matrix is 10 MB. A call that fits the default gets the
    parameters it always had."""
    need = 2 * sum(jnp.dtype(dtype).itemsize * math.prod(shape)
                   for shape, dtype in blocks) + _VMEM_MARGIN
    if need <= _VMEM_DEFAULT_LIMIT:
        return pltpu.CompilerParams(dimension_semantics=semantics)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=int(need))


def _gmm_call(name, lhs, rhs, plan, transpose, tile):
    rows, k = lhs.shape
    n_out = rhs.shape[1] if transpose else rhs.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(rows // tile,),
        in_specs=[
            pl.BlockSpec((tile, k), lambda t, te, na: (_in_use(t, na), 0)),
            pl.BlockSpec((None,) + rhs.shape[1:],
                         lambda t, te, na: (te[_in_use(t, na)], 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, n_out),
                               lambda t, te, na: (_in_use(t, na), 0)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose=transpose),
        name=name, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n_out), lhs.dtype),
        compiler_params=_params(
            ("arbitrary",), ((tile, k), lhs.dtype),
            (rhs.shape[1:], rhs.dtype), ((tile, n_out), lhs.dtype)),
        interpret=registry.interpret(),
    )(plan["tile_expert"], plan["n_active"], lhs, rhs)


def _dw_kernel(te_ref, na_ref, lhs_ref, dout_ref, out_ref):
    t = pl.program_id(1)
    live = t < na_ref[0]
    prev = te_ref[jnp.maximum(t, 1) - 1]
    first = jnp.logical_or(t == 0, te_ref[t] != prev)

    @pl.when(jnp.logical_and(live, first))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live)
    def _run():
        out_ref[...] += lax.dot_general(
            lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _dw_block(k, n, limit=_DW_BLOCK_BYTES):
    """Largest 128-multiple divisor of k whose [bk, n] f32 block stays
    within `limit` bytes (k itself when k is no multiple of 128)."""
    if k % 128:
        return k
    best = 128
    for bk in range(128, k + 1, 128):
        if k % bk == 0 and bk * n * 4 <= limit:
            best = bk
    return best


def _dw_call(lhs, dout, plan, experts_held, tile):
    rows, k = lhs.shape
    n = dout.shape[1]
    # the output is blocked along k, the lhs block's lanes; a k that is
    # no multiple of 128 (an expert width of 1,856) stays whole there and
    # the output is blocked along n instead
    if k % 128 and n % 128 == 0:
        bk, bn = k, _dw_block(n, k)
    else:
        bk, bn = _dw_block(k, n), n

    def block(j):
        """(k block, n block) of the grid's first axis."""
        return (j, 0) if bn == n else (0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(k // bk * (n // bn), rows // tile),
        in_specs=[
            pl.BlockSpec((tile, bk),
                         lambda j, t, te, na: (_in_use(t, na), block(j)[0])),
            pl.BlockSpec((tile, bn),
                         lambda j, t, te, na: (_in_use(t, na), block(j)[1])),
        ],
        out_specs=pl.BlockSpec(
            (None, bk, bn),
            lambda j, t, te, na: (te[_in_use(t, na)],) + block(j)))
    return pl.pallas_call(
        _dw_kernel, name="moe_grouped_matmul_dw", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((experts_held, k, n), jnp.float32),
        compiler_params=_params(
            ("parallel", "arbitrary"), ((tile, bk), lhs.dtype),
            ((tile, bn), dout.dtype), ((bk, bn), jnp.float32)),
        interpret=registry.interpret(),
    )(plan["tile_expert"], plan["n_active"], lhs, dout)


def _combine_kernel(na_ref, count_ref, tok_ref, buf_ref, out_ref, *wide):
    tile = buf_ref.shape[0]
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(t < na_ref[0])
    def _run():
        if wide:            # packed rows: widened once a tile, read by row
            rows_ref, = wide
            rows_ref[...] = buf_ref[...].astype(jnp.float32)
        else:
            rows_ref = buf_ref

        def add(r):
            tok = tok_ref[t * tile + r]
            out_ref[pl.ds(tok, 1), :] += rows_ref[pl.ds(r, 1), :]

        def several(c, carry):
            for j in range(_COMBINE_UNROLL):
                add(c * _COMBINE_UNROLL + j)
            return carry

        def one(r, carry):
            add(r)
            return carry
        # a group's padding rows are its last, so a tile's valid rows are
        # its first `count`; rows of one tile may share a token (a
        # caller's choices need not be distinct), so they add in turn
        count = count_ref[t]
        whole = count // _COMBINE_UNROLL
        lax.fori_loop(0, whole, several, 0)
        lax.fori_loop(whole * _COMBINE_UNROLL, count, one, 0)


def combine(buf, plan, t, top_k, tile=TILE_ROWS):
    """[rows, d] -> float32 [t, d]: each token's rows summed, row r going
    to token `choice_of_row[r] // top_k` (kernel `moe_combine`). Only
    valid rows of tiles in use are read; the sum is float32 from `buf`'s
    own type, a token's rows added in row (expert) order. The output is
    blocked along d like the dw kernel's along k, a block of at most
    _COMBINE_BLOCK_BYTES."""
    rows, d = buf.shape
    bd = _dw_block(d, t, _COMBINE_BLOCK_BYTES)
    tok = plan["choice_of_row"] // top_k
    count = jnp.sum(plan["valid"].reshape(rows // tile, tile), axis=1,
                    dtype=jnp.int32)
    wide = [((tile, bd), jnp.float32)] * (buf.dtype != jnp.float32)
    # grid (D blocks, row tiles): the [t, bd] float32 output block stays
    # in VMEM while the row tiles stream past it
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(d // bd, rows // tile),
        in_specs=[pl.BlockSpec(
            (tile, bd), lambda j, r, na, n, tok: (_in_use(r, na), j))],
        out_specs=pl.BlockSpec((t, bd), lambda j, r, na, n, tok: (0, j)),
        scratch_shapes=[pltpu.VMEM(*block) for block in wide])
    return pl.pallas_call(
        _combine_kernel, name="moe_combine", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=_params(
            ("parallel", "arbitrary"), ((tile, bd), buf.dtype),
            ((t, bd), jnp.float32), *wide),
        interpret=registry.interpret(),
    )(plan["n_active"], count, tok, buf)


# ---------------------------------------------------------------------------
# lowered path: the same products over the same buffer, by XLA
# ---------------------------------------------------------------------------

def _padded_sizes(plan, tile):
    """Rows each group takes in the buffer (whole tiles), as the ragged
    dot's group sizes: their sum is the rows in use, the rest is left
    zero."""
    return jnp.maximum(1, -(-plan["sizes"] // tile)) * tile


def _lowered_gmm(lhs, rhs, plan, transpose, tile):
    if transpose:
        rhs = jnp.swapaxes(rhs, 1, 2)
    return lax.ragged_dot(lhs, rhs, _padded_sizes(plan, tile),
                          preferred_element_type=jnp.float32
                          ).astype(lhs.dtype)


def _lowered_dw(lhs, dout, plan, experts_held, tile):
    # the rows are the contraction here, and the dense dot a backend
    # without a ragged one makes of it sums over all of them in an order
    # that depends on how many there are: a prefix is padded back to the
    # worst case (zero rows, as the worst case's own are past the tiles
    # in use), so that its result is the worst case's to the bit
    beyond = buffer_rows(plan["held"].shape[0], experts_held, tile) \
        - lhs.shape[0]
    if beyond:
        lhs, dout = (jnp.pad(a, ((0, beyond), (0, 0))) for a in (lhs, dout))
    dims = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return lax.ragged_dot_general(
        lhs, dout, _padded_sizes(plan, tile), dims,
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def use_kernels(lhs, rhs) -> bool:
    """One decision a layer, counted under `moe_grouped_matmul`: the
    three Pallas kernels (`custom`) or the ragged dots (`lowered`)."""
    if not registry.routable("moe_experts"):
        return False
    return registry.select(
        "moe_experts", registry.signature("moe_experts", lhs, rhs)) \
        is not None


def gmm(lhs, rhs, plan, kernels, tile=TILE_ROWS):
    """[rows, k] x [experts, k, n] -> [rows, n], each row with its own
    tile's expert. Rows past the tiles in use are not written on the
    kernel path and zero on the lowered one."""
    if kernels:
        return _gmm_call("moe_grouped_matmul_fwd", lhs, rhs, plan, False,
                         tile)
    return _lowered_gmm(lhs, rhs, plan, False, tile)


def gmm_dx(dout, rhs, plan, kernels, tile=TILE_ROWS):
    """[rows, n] x [experts, k, n]^T -> [rows, k]: gmm's gradient for
    its rows."""
    if kernels:
        return _gmm_call("moe_grouped_matmul_dx", dout, rhs, plan, True,
                         tile)
    return _lowered_gmm(dout, rhs, plan, True, tile)


def gmm_dw(lhs, dout, plan, experts_held, kernels, tile=TILE_ROWS):
    """[rows, k]^T x [rows, n] summed within each group -> float32
    [experts, k, n]: gmm's gradient for its weights. Padding rows must
    be zero in `lhs` or in `dout`."""
    if kernels:
        return _dw_call(lhs, dout, plan, experts_held, tile)
    return _lowered_dw(lhs, dout, plan, experts_held, tile)


def combine_by_rows(rows: int, n_choices: int) -> bool:
    """Whether the combine out of a prefix of `rows` rows goes over its
    rows (`combine`) or over all `n_choices` = T * top_k choices (the
    gather, ops/decoder.py `_combine`). Static: the prefix's own length
    against `COMBINE_MAX_SHARE` of the choices, so the worst-case prefix
    and a layer that holds every expert (rows >= n_choices) never do,
    nor does a prefix whose token table SMEM cannot hold."""
    return rows <= min(COMBINE_MAX_SHARE * n_choices, _COMBINE_MAX_ROWS)


def _eligible(sig: registry.Signature) -> bool:
    """bf16 or f32 rows whose widths Mosaic can tile: one matrix
    dimension a multiple of 128 and the other of 64 (an expert width of
    1,856 = 29 x 64 stays whole in every block and Mosaic pads its
    lanes; the interpreter takes any)."""
    (rows, k), (_, k2, n) = sig.shapes[0], sig.shapes[1]
    return (sig.dtypes[0] in ("bfloat16", "float32")
            and sig.dtypes[0] == sig.dtypes[1] and k == k2
            and (registry._INTERPRET
                 or (k % 64 == 0 and n % 64 == 0
                     and (k % 128 == 0 or n % 128 == 0))))


registry.register_kernel(
    "moe_grouped_matmul", op_types=("moe_experts",), eligible=_eligible,
    run=gmm,
    doc="dropless grouped matmul over the held experts' row buffer "
        "(fwd, dx, dw); tiles past the last routed row are skipped")
