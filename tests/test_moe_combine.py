"""The expert layer's combine over the rows of the prefix it took
(kernels/grouped_matmul.py `combine`, Pallas kernel `moe_combine`, under
the interpreter here) against the gather over every choice of every
token (ops/decoder.py `_combine`), which stays in the program for the
long prefixes and is the reference here; the static rule that says which
prefix takes which; `moe_experts` and its grad op through both."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels import registry as kreg
from paddle_tpu.ops import decoder

from test_decoder_lm import (_experts_case, _experts_program, _routed,
                             interp)  # noqa: F401  (a fixture)

EPS = 2.0 ** -24        # float32's unit roundoff

# the three decoder cells: tokens, top_k, experts held, experts, width
CELLS = {"kanana2_s4096": (4096, 6, 16, 128, 2048),
         "keye2_s8192": (8192, 8, 16, 128, 2048),
         "twotower_s4096": (4096, 6, 8, 128, 2688)}


def _distinct_choice(rng, t, k, experts):
    return np.stack([rng.choice(experts, k, replace=False)
                     for _ in range(t)]).astype(np.int32)


def _routing(name, rng, t, k, held, experts, first):
    """int32 [t, k] over all `experts`; `first .. first + held - 1` are
    held here."""
    if name == "uniform":
        return _distinct_choice(rng, t, k, experts)
    if name == "one_held_expert_takes_every_choice":
        return np.full((t, k), first + held // 2, np.int32)
    if name == "no_choice_held":
        elsewhere = np.setdiff1d(np.arange(experts),
                                 np.arange(first, first + held))
        return elsewhere[_distinct_choice(rng, t, k, len(elsewhere))]
    raise KeyError(name)


def _bound(buf, plan, t, top_k):
    """What float32 reassociation allows between two orders of one sum
    of m terms: each is within (m - 1) EPS sum|x| of the exact sum, so
    they are within twice that of each other; 0 where a token holds one
    choice, and two terms add to the same float in either order."""
    held = np.asarray(plan["held"]).reshape(t, top_k)
    terms = np.abs(np.asarray(buf, np.float64))[
        np.asarray(plan["row_of_choice"])].reshape(t, top_k, -1)
    terms = np.where(held[:, :, None], terms, 0.0)
    m = held.sum(axis=1)
    return (m > 2)[:, None] * 2.0 * (m[:, None] - 1) * EPS * terms.sum(1)


def _check_combine(buf, plan, rows, t, top_k):
    """The kernel over the first `rows` rows against the gather; the
    rows past the tiles in use are NaN in what both read."""
    in_use = int(plan["n_active"][0]) * gm.TILE_ROWS
    assert in_use <= rows
    clean = np.array(buf.astype(jnp.float32))[:rows]
    clean[in_use:] = 0.0
    buf = buf[:rows].at[in_use:].set(jnp.nan)
    want = decoder._combine(buf, plan, t, top_k)
    got = gm.combine(buf, gm.prefix_plan(plan, rows), t, top_k)
    assert got.dtype == jnp.float32 and got.shape == (t, buf.shape[1])
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all() and np.isfinite(want).all()
    bound = _bound(clean, plan, t, top_k)
    assert (np.abs(got.astype(np.float64) - want) <= bound).all(), \
        np.max(np.abs(got - want))
    one = np.asarray(plan["held"]).reshape(t, top_k).sum(1) <= 2
    np.testing.assert_array_equal(got[one], want[one])
    # and both are the exact sum to float32's rounding of at most k terms
    exact = np.zeros((t, clean.shape[1]))
    valid = np.asarray(plan["valid"])[:rows]
    tok = np.asarray(plan["choice_of_row"])[:rows] // top_k
    np.add.at(exact, tok[valid], clean[valid].astype(np.float64))
    slack = top_k * EPS * np.abs(exact).max() * top_k
    assert np.abs(got - exact).max() <= slack
    return got


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16_forward", "f32_backward"])
@pytest.mark.parametrize("d", [256, 384])
@pytest.mark.parametrize("routing", ["uniform",
                                     "one_held_expert_takes_every_choice",
                                     "no_choice_held"])
def test_combine_over_rows_equals_the_gather(routing, d, dtype):
    """128 tokens, top-4, experts 4..7 of 16 held: over every prefix of
    the ladder that holds the tiles in use, the worst case included."""
    t, k, held, experts, first = 128, 4, 4, 16, 4
    rng = np.random.default_rng(7)
    choice = _routing(routing, rng, t, k, held, experts, first)
    plan = gm.plan_rows(jnp.asarray(choice.reshape(-1)) - first, held)
    ladder = gm.prefix_rows(t * k, held, experts)
    in_use = int(plan["n_active"][0]) * gm.TILE_ROWS
    buf = jnp.asarray(rng.standard_normal((ladder[-1], d)), dtype)
    ran = 0
    for rows in ladder:
        if in_use <= rows:
            got = _check_combine(buf, plan, rows, t, k)
            ran += 1
            if routing == "no_choice_held":
                assert not got.any()
    assert ran >= 1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16_forward", "f32_backward"])
def test_combine_where_every_expert_is_held(dtype):
    """Every choice carries a row: top_k terms a token, the widest sum
    the bound has to hold for."""
    t, k, experts, d = 96, 6, 8, 256
    rng = np.random.default_rng(8)
    choice = _distinct_choice(rng, t, k, experts)
    plan = gm.plan_rows(jnp.asarray(choice.reshape(-1)), experts)
    rows, = gm.prefix_rows(t * k, experts, experts)
    assert bool(np.asarray(plan["held"]).all())
    buf = jnp.asarray(rng.standard_normal((rows, d)), dtype)
    _check_combine(buf, plan, rows, t, k)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_combine_over_every_rung_of_a_cells_ladder_cut_to_few_tokens(cell):
    """A cell's top_k, held and total experts at 512 tokens (the ladder
    keeps its four rungs) and a narrow width: each rung with a routing
    that fills it (one busy expert, the others empty), bf16 and float32
    rows."""
    _, k, held, experts, width = CELLS[cell]
    t, d = 512, 256 if width == 2048 else 384
    ladder = gm.prefix_rows(t * k, held, experts)
    assert len(ladder) == 4
    rng = np.random.default_rng(9)
    edge = 0
    for rows in ladder:
        # the rung's rows less a tile for each other expert, to one
        n = min(t * k, rows - (held - 1) * gm.TILE_ROWS)
        local = np.full(t * k, -1, np.int32)
        local[:n] = held // 2
        plan = gm.plan_rows(jnp.asarray(rng.permutation(local)), held)
        in_use = int(plan["n_active"][0]) * gm.TILE_ROWS
        assert edge < in_use <= rows, (cell, rows, in_use)
        edge = rows
        for dtype in (jnp.bfloat16, jnp.float32):
            buf = jnp.asarray(rng.standard_normal((rows, d)), dtype)
            _check_combine(buf, plan, rows, t, k)


def test_a_tiles_valid_rows_are_its_first():
    """What the kernel's loop counts on: a group's padding rows are its
    last, so in every tile the valid rows come first."""
    rng = np.random.default_rng(10)
    local = rng.integers(-3, 5, 3000).astype(np.int32)
    plan = gm.plan_rows(jnp.asarray(local), 5)
    valid = np.asarray(plan["valid"]).reshape(-1, gm.TILE_ROWS)
    count = valid.sum(axis=1)
    assert (valid == (np.arange(gm.TILE_ROWS)[None] < count[:, None])).all()
    assert count.sum() == ((local >= 0) & (local < 5)).sum()


# which rungs of its ladder a cell's expert layers combine by rows
_BY_ROWS = {"kanana2_s4096": ([5120, 8192, 14336, 26624],
                              [True, True, True, False]),
            "keye2_s8192": ([10240, 18432, 34816, 67584],
                            [True, True, True, False]),
            "twotower_s4096": ([2560, 4096, 7168, 25600],
                               [True, True, True, False])}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_rule_at_the_three_cells_sizes(cell):
    """The rule is static and reads the rung's rows against T * top_k
    alone: the short rungs go by rows, the worst case never does, nor
    does a layer that holds every expert."""
    t, k, held, experts, _ = CELLS[cell]
    ladder, want = _BY_ROWS[cell]
    assert gm.prefix_rows(t * k, held, experts) == ladder
    assert [gm.combine_by_rows(rows, t * k) for rows in ladder] == want
    assert not gm.combine_by_rows(gm.buffer_rows(t * k, held), t * k)
    all_held, = gm.prefix_rows(t * k, experts, experts)
    assert not gm.combine_by_rows(all_held, t * k)
    assert 0 < gm.COMBINE_MAX_SHARE < 1
    # a prefix whose token table would not fit SMEM keeps the gather
    assert gm.combine_by_rows(196608, 2 ** 20)
    assert not gm.combine_by_rows(196608 + gm.TILE_ROWS, 2 ** 20)


# 512 tokens x 4 choices, experts 2..3 of 16 held: prefixes of 512, 768
# and 1,280 rows under the worst case's 2,304, all three under
# COMBINE_MAX_SHARE of the 2,048 choices. A routing is the rows sent to
# each held expert.
_ROUTINGS = {
    "first_rung": ((130, 120), 512, True),
    "second_rung": ((300, 200), 768, True),
    "third_rung": ((700, 300), 1280, True),
    "worst_case": ((2048, 0), 2304, False),
}


@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
@pytest.mark.parametrize("routing", sorted(_ROUTINGS))
def test_moe_experts_by_rows_against_the_worst_case_body(
        routing, activation, interp, monkeypatch):
    """`moe_experts` and its grad op through the kernels: whatever rung
    the routing takes and whichever combine that rung holds, Out and
    every gradient equal the one body over the whole worst-case buffer
    (the gather) to float32 reassociation of top_k terms, and to the bit
    where the rung keeps the gather; `routing` counts one outcome a
    traced body."""
    sizes, prefix, by_rows = _ROUTINGS[routing]
    t, k, held, experts, first = 512, 4, 2, 16, 2
    assert gm.prefix_rows(t * k, held, experts) == [512, 768, 1280, 2304]
    assert gm.combine_by_rows(prefix, t * k) == by_rows
    c = _experts_case(t, 16, 8, held, _routed(sizes, t, k, first, seed=62),
                      seed=63)
    for body in decoder._BODIES.values():
        body.clear_cache()      # so that this run's bodies are traced
    out, got = _experts_program(c, experts, held, first,
                                slots=["RowsWorked"], activation=activation)
    assert got.pop().tolist()[0] == prefix
    took = kreg.dispatch_stats()["per_kernel"]["moe_combine"]
    # eight bodies (four rungs, forward and backward), a count each
    assert took == {"prefix_rows": 6, "all_choices": 2}
    monkeypatch.setattr(
        gm, "prefix_rows", lambda n, held, num: [gm.buffer_rows(n, held)])
    whole, want = _experts_program(c, experts, held, first,
                                   slots=["RowsWorked"],
                                   activation=activation)
    assert want.pop().tolist()[0] == 2304
    assert np.abs(whole).max() > 0
    pairs = [("out", out, whole)] + [
        (f"grad {i}", g, w) for i, (g, w) in enumerate(zip(got, want))]
    assert len(got) == len(want) == (5 if activation == "swiglu" else 4)
    for name, g, w in pairs:
        if by_rows:
            np.testing.assert_allclose(
                g, w, rtol=0, atol=2 * (k - 1) * EPS * k * np.abs(w).max(),
                err_msg=name)
        else:
            np.testing.assert_array_equal(g, w, err_msg=name)


def test_lowered_layer_keeps_the_gather():
    """Where the layer's kernels do not run (this CPU, a mesh, the deny
    list) every rung combines by the gather: no kernel in the body,
    nothing counted."""
    kreg.reset_stats()
    sizes, prefix, _ = _ROUTINGS["first_rung"]
    c = _experts_case(512, 16, 8, 2, _routed(sizes, 512, 4, 2, seed=62),
                      seed=63)
    plan = gm.plan_rows(jnp.asarray(c["choice"].reshape(-1)) - 2, 2)
    text = str(jax.make_jaxpr(functools.partial(
        decoder._experts_forward, rows=prefix, held=2, kernels=False,
        out_dtype=jnp.float32))(
        c["x"], c["weight"], c["wg"], c["wu"], c["wd"], plan))
    assert "pallas_call" not in text and "gather" in text
    assert "moe_combine" not in kreg.dispatch_stats()["per_kernel"]


def test_combine_stats_reader():
    """`observability.moe.combine_stats` from the `moe_rows_worked`
    counter's increases over single steps, at kanana2_s4096's ladder:
    two layers, three steps."""
    from paddle_tpu.observability import moe
    ladder, n = [5120, 8192, 14336, 26624], 4096 * 6
    readings = np.cumsum([[[5120, 4992], [8192, 5632]],
                          [[5120, 5120], [14336, 9000]],
                          [[8192, 5248], [26624, 20000]]], axis=0)
    steps = np.diff(readings, axis=0, prepend=0)
    stats = moe.combine_stats(steps, ladder, n)
    assert stats["by_rows_share"] == pytest.approx(5 / 6)
    assert stats["by_rows_share_per_layer"] == pytest.approx([1.0, 2 / 3])
    assert stats["rows_over_choices"] == pytest.approx(
        (5120 + 5120 + 8192 + 8192 + 14336 + n) / (6 * n))
    one = moe.combine_stats(steps[0], ladder, n)
    assert one["by_rows_share"] == 1.0
    assert moe.combine_stats(np.zeros((0, 2, 2)), ladder, n) is None
    with pytest.raises(ValueError, match="no rung"):
        moe.combine_stats(readings[1], ladder, n)   # two steps' sum
