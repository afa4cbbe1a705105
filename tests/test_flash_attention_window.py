"""The flash kernels' sliding window: the forward, the fused backward and
the split pair (dq kernel, then dk/dv) against the composed path with the
band, under the Pallas interpreter on the CPU; the banded grid's steps
against the tiles the band meets; the kernels' own names; and that a site
without a window keeps its grid and its name."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import registry as kreg

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


@pytest.fixture
def interp(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    kreg.reset_stats()
    yield


def _r(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _close(a, b, tol, scale=1.0):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape, (a.shape, b.shape)
    err = np.max(np.abs(a - b))
    assert err <= tol * max(scale, np.max(np.abs(b))), err


def _qkv(layout, s, h, hkv, d, seed):
    def shape(n):
        return (2, s, n, d) if layout == "bshd" else (2, n, s, d)
    return _r(shape(h), seed), _r(shape(hkv), seed + 1), \
        _r(shape(hkv), seed + 2)


# windows of one key, of one block, of no multiple of the block, of two
# and a third blocks, and wider than the sequence (which is causal)
WINDOWS = [1, 128, 100, 300, 10_000]
# (layout, query heads, key heads, head width, block_q, block_k):
# grouped 8/1 at 128, one head a lane block; packed 64 x 2 over shared
# key heads (two query heads a lane block, both reading one key head);
# the same in bhsd, one head a grid step; unequal blocks
SITES = [("bshd", 8, 1, 128, 128, 128), ("bhsd", 8, 1, 128, 128, 128),
         ("bshd", 4, 2, 64, 128, 128), ("bhsd", 4, 2, 64, 128, 128),
         ("bshd", 2, 2, 64, 128, 256), ("bshd", 8, 1, 128, 256, 128)]
# the split pair on the two bshd shapes of the model file's sites
CASES = [(site, False) for site in SITES] + [(SITES[0], True),
                                             (SITES[2], True)]


@pytest.mark.parametrize("site,split", CASES,
                         ids=lambda c: f"{c[0]}_{c[1]}x{c[2]}x{c[3]}"
                         f"_b{c[4]}x{c[5]}" if isinstance(c, tuple)
                         else ("split" if c else "fused"))
@pytest.mark.parametrize("window", WINDOWS)
def test_window_kernels_equal_the_composed_band(window, site, split,
                                                interp, monkeypatch):
    """Out, dQ, dK and dV of a windowed site through the kernels against
    `_attn_reference` with the same band, float32 on both sides (2e-6 of
    the largest element forward, 5e-6 of the largest of the three
    gradients backward: the same sums in another order; a window of one
    key has dQ = dK = 0 exactly, its rounding of the size of dV's).
    Split: the dq kernel and the dk/dv kernel, which run where the fused
    backward's dq would not fit its VMEM budget."""
    layout, h, hkv, d, bq, bk = site
    s = 512
    if split:
        monkeypatch.setattr(fa, "_FUSED_DQ_VMEM_BUDGET", 0)
    q, k, v = _qkv(layout, s, h, hkv, d, 11)
    sc = d ** -0.5

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, None, sc, bq, bk, layout, True,
                                  None, window)

    def composed(q, k, v):
        return fa._attn_reference(q, k, v, None, sc, layout=layout,
                                  causal=True, window=window)
    with jax.default_matmul_precision("highest"):
        _close(kernel(q, k, v), composed(q, k, v), 2e-6)
        cot = _r(kernel(q, k, v).shape, 5)
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) * cot), (0, 1, 2))(
            q, k, v)
        want = jax.grad(lambda *a: jnp.sum(composed(*a) * cot), (0, 1, 2))(
            q, k, v)
    largest = max(float(np.max(np.abs(w))) for w in want)
    for a, b in zip(got, want):
        _close(a, b, 5e-6, largest)
    took = kreg.dispatch_stats()["per_kernel"]["flash_attention"]
    assert took["split_bwd" if split else "fused_bwd"] >= 1
    assert took["window"] >= 1


@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
def test_a_window_as_wide_as_the_sequence_is_causal(layout, interp):
    """window >= S admits every causal pair: the windowed kernels give
    the causal kernels' numbers (the same tiles, the same order)."""
    q, k, v = _qkv(layout, 256, 4, 2, 64, 21)
    sc = 64 ** -0.5
    with jax.default_matmul_precision("highest"):
        wide = fa.flash_attention(q, k, v, None, sc, 128, 128, layout,
                                  True, None, 256)
        causal = fa.flash_attention(q, k, v, None, sc, 128, 128, layout,
                                    True, None, None)
    _close(wide, causal, 1e-6)


def _pallas_calls(f, *args):
    """[(name, grid)] of the pallas_calls in f's jaxpr, in order."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append((eqn.params["name"],
                            tuple(eqn.params["grid_mapping"].grid)))
            for sub in eqn.params.values():
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    walk(sub)
    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return out


@pytest.mark.parametrize("window,names", [
    (None, ("flash_attention_fwd", "flash_attention_dkv")),
    (1024, ("flash_attention_window_fwd", "flash_attention_window_bwd"))])
def test_the_band_grid_at_the_cells_shape(window, names, interp):
    """S = 8,192 in 512 x 1,024 blocks (the block policy at that length),
    32 query heads over 4 key heads of 128: the causal forward steps 8
    key blocks a query block and its dk/dv kernel 16 query blocks a key
    block; a window of 1,024 steps 2 and 4, under the window kernels'
    names. Only the shapes are traced."""
    s = 8192
    q = jax.ShapeDtypeStruct((1, s, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, s, 4, 128), jnp.bfloat16)

    def f(q, k, v, g):
        out, lse = fa._fa_forward(q, k, v, None, 128 ** -0.5, 512, 1024,
                                  return_lse=True, layout="bshd",
                                  causal=True, window=window)
        return fa._fa_backward(q, k, v, None, out, lse, g, 128 ** -0.5,
                               512, 1024, layout="bshd", causal=True,
                               window=window)[:3]
    calls = _pallas_calls(f, q, kv, kv, q)
    assert tuple(n for n, _ in calls) == names
    (_, fwd), (_, bwd) = calls
    assert fwd == (1, 32, 16, 8 if window is None else 2)
    assert bwd == (1, 32, 8, 16 if window is None else 4)


def _tiles_met(s, bq, bk, window):
    """(query block, key block) tiles holding at least one admitted pair,
    from the dense band."""
    r = np.arange(s)[:, None]
    c = np.arange(s)[None, :]
    keep = (r >= c) & (r - c < window)
    return {(i, j) for i in range(s // bq) for j in range(s // bk)
            if keep[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()}


@pytest.mark.parametrize("s,bq,bk,window", [
    (8192, 512, 1024, 1024), (512, 128, 128, 1), (512, 128, 128, 100),
    (512, 128, 256, 300), (1024, 256, 128, 129), (512, 128, 128, 10_000)])
def test_the_grid_visits_the_band_and_nothing_else(s, bq, bk, window):
    """Every grid step's block, as the index maps give it, over the steps
    that work: the forward's (query block, key block) pairs and the dk/dv
    kernel's are exactly the tiles that hold an admitted pair, each once,
    and the idle steps repeat a working step's block (no DMA). At the
    cell's shape the band works 30 of the causal kernel's 72 tiles."""
    band = fa._Band(window, bq, bk, s // bq, s // bk)
    met = _tiles_met(s, bq, bk, window)
    fwd, bwd = [], []
    for i in range(s // bq):
        for t in range(band.kv_steps):
            j = int(band.kv_block(i, t))
            if band.kv_first(i, max) + t <= band.kv_last(i, min):
                fwd.append((i, j))
            else:
                assert j == band.kv_last(i, min)
    for j in range(s // bk):
        for t in range(band.q_steps):
            i = int(band.q_block(j, t))
            if band.q_first(j) + t <= band.q_last(j, min):
                bwd.append((i, j))
            else:
                assert i == band.q_last(j, min)
    assert len(fwd) == len(set(fwd)) == len(bwd) == len(set(bwd))
    assert set(fwd) == set(bwd) == met
    if (s, bq, bk, window) == (8192, 512, 1024, 1024):
        assert len(met) == 30 and len(_tiles_met(s, bq, bk, s)) == 72
        assert band.kv_steps == 2 and band.q_steps == 4


def test_admitted_pairs_by_hand():
    """Row r admits min(r + 1, window) keys: at S = 8,192 and a window of
    1,024, 1,024 x 1,025 / 2 + 7,168 x 1,024 = 7,864,832 pairs, 23.4% of
    the causal square's 33,558,528."""
    assert fa.admitted_pairs(8192, 8192, 1024) == 7_864_832
    assert fa.admitted_pairs(8192, 8192, 8192) == 8192 * 8193 // 2
    assert fa.admitted_pairs(5, 5, 1) == 5


def test_a_window_is_causal_and_takes_no_bias_gradient(interp):
    q, k, v = _qkv("bshd", 256, 2, 2, 64, 31)
    with pytest.raises(ValueError, match="causal"):
        fa._fa_forward(q, k, v, None, 1.0, 128, 128, layout="bshd",
                       causal=False, window=64)
    out, lse = fa._fa_forward(q, k, v, None, 1.0, 128, 128,
                              return_lse=True, layout="bshd", causal=True,
                              window=64)
    bias = jnp.zeros((2, 1, 256, 256), jnp.float32)
    with pytest.raises(NotImplementedError, match="bias gradient"):
        fa._fa_backward(q, k, v, bias, out, lse, out, 1.0, 128, 128,
                        layout="bshd", causal=True, window=64)
