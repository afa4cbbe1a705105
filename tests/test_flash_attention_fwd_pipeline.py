"""The flash forward on its transposed tile (s^T = k q^T: keys on
sublanes, queries on lanes; PR 42), and on the [bq, bk] tile where a
site's bias is a tile a query block or its weights drop out: Out and lse
against the composed `_attn_reference_lse` at every form a cell's site
takes — full with a key-padding bias, causal, a band, an int8 keep mask
over grouped key heads, packed heads over shared key heads at 64, 192 /
128 widths, a per-head float bias, a query block off the 128 lanes — the
fused backward run on that lse against the composed gradients, and the
`pipelined_fwd` / `single_fwd` outcomes the forward counts. Under the
Pallas interpreter on the CPU."""
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


def _bhsd(x, layout):
    return jnp.moveaxis(x, 1, 2) if layout == "bshd" else x


# (layout, B, S, query heads, key heads, d_qk, d_v, causal, window, bias,
#  block_q, block_k); bias: None, "key" [B,1,1,S] f32, "head" [B,H,S,S]
# f32, "keep" [B,1,S,S] int8 with the diagonal kept
SITES = {
    "full_key_bias": ("bshd", 2, 256, 4, 4, 64, 64, False, None, "key",
                      128, 256),
    "causal": ("bshd", 1, 512, 4, 4, 64, 64, True, None, None, 128, 256),
    "band": ("bshd", 1, 512, 4, 4, 64, 64, True, 100, None, 128, 256),
    "keep_mask_gqa": ("bshd", 1, 256, 4, 1, 128, 128, True, None, "keep",
                      128, 128),
    "packed_shared_gqa64": ("bshd", 1, 256, 4, 2, 64, 64, True, None,
                            None, 128, 128),
    "mla_192_128": ("bshd", 1, 256, 2, 2, 192, 128, True, None, None,
                    128, 256),
    "per_head_bias_bhsd": ("bhsd", 2, 256, 2, 2, 32, 32, False, None,
                           "head", 128, 128),
    "query_block_of_64": ("bshd", 1, 256, 2, 2, 64, 64, True, None, None,
                          64, 128),
}


def _site(name, seed=3):
    layout, b, s, h, hkv, d, dv, causal, window, bias, bq, bk = \
        SITES[name]

    def shape(n, w):
        return (b, s, n, w) if layout == "bshd" else (b, n, s, w)
    q, k, v = _r(shape(h, d), seed), _r(shape(hkv, d), seed + 1), \
        _r(shape(hkv, dv), seed + 2)
    bt = None
    if bias == "key":
        bt = _r((b, 1, 1, s), seed + 3)
    elif bias == "head":
        bt = _r((b, h, s, s), seed + 3)
    elif bias == "keep":
        rng = np.random.default_rng(seed + 3)
        bt = jnp.asarray((rng.random((b, 1, s, s)) < 0.4)
                         | np.eye(s, dtype=bool), jnp.int8)
    return q, k, v, bt, dict(layout=layout, causal=causal, window=window,
                             block_q=bq, block_k=bk, scale=d ** -0.5)


def _forward(q, k, v, bias, cfg):
    return fa._fa_forward(q, k, v, bias, cfg["scale"], cfg["block_q"],
                          cfg["block_k"], return_lse=True,
                          layout=cfg["layout"], causal=cfg["causal"],
                          window=cfg["window"])


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape, (a.shape, b.shape)
    err = np.max(np.abs(a - b))
    assert err <= tol * max(1.0, np.max(np.abs(b))), err


@pytest.mark.parametrize("name", list(SITES))
def test_forward_equals_the_composed_attention(name, interp):
    """Out and lse of every site form against `_attn_reference_lse`,
    float32 on both sides (the same sums in another order)."""
    q, k, v, bias, cfg = _site(name)
    out, lse = _forward(q, k, v, bias, cfg)
    lay = cfg["layout"]
    want, want_lse = fa._attn_reference_lse(
        _bhsd(q, lay), _bhsd(k, lay), _bhsd(v, lay), bias, cfg["scale"],
        causal=cfg["causal"], window=cfg["window"])
    _close(_bhsd(out, lay), want, 2e-6)
    _close(lse, want_lse, 2e-6)


@pytest.mark.parametrize("name", ["causal", "band", "keep_mask_gqa",
                                  "packed_shared_gqa64", "mla_192_128"])
def test_fused_backward_on_the_forward_lse(name, interp):
    """dQ, dK and dV of the fused backward, fed the new forward's Out and
    lse, against the composed attention's vjp."""
    q, k, v, bias, cfg = _site(name, seed=11)
    lay = cfg["layout"]
    g = _r(q.shape[:-1] + (v.shape[-1],), 17)
    out, lse = _forward(q, k, v, bias, cfg)
    kreg.reset_stats()
    dq, dk, dv, _ = fa._fa_backward(
        q, k, v, bias, out, lse, g, cfg["scale"], cfg["block_q"],
        cfg["block_k"], layout=lay, causal=cfg["causal"],
        window=cfg["window"])
    assert kreg.dispatch_stats()["per_kernel"]["flash_attention"].get(
        "fused_bwd") == 1
    _, vjp = jax.vjp(lambda q, k, v: fa._attn_reference(
        q, k, v, bias, cfg["scale"], layout=lay, causal=cfg["causal"],
        window=cfg["window"]), q, k, v)
    for got, want in zip((dq, dk, dv), vjp(g)):
        _close(got, want, 5e-6)


@pytest.mark.parametrize("name,outcome,body", [
    ("full_key_bias", "pipelined_fwd", "_fa_kernel"),
    ("causal", "pipelined_fwd", "_fa_kernel"),
    ("packed_shared_gqa64", "pipelined_fwd", "_fa_kernel"),
    ("mla_192_128", "pipelined_fwd", "_fa_kernel"),
    ("keep_mask_gqa", "single_fwd", "_fa_kernel_rows"),
    ("per_head_bias_bhsd", "single_fwd", "_fa_kernel_rows"),
    ("dropout", "single_fwd", "_fa_kernel_rows")])
def test_forward_counts_its_outcome(name, outcome, body, interp,
                                    monkeypatch):
    """One outcome a forward trace: `pipelined_fwd` where the transposed
    tile runs and a lane block holds more than one head (the next head's
    k q^T runs under this head's softmax), `single_fwd` otherwise. A
    bias that is a [bq, bk] tile a query block, or dropout's keep mask,
    keeps the [bq, bk] tile (`_fa_kernel_rows`)."""
    ran = []
    for kern in ("_fa_kernel", "_fa_kernel_rows"):
        monkeypatch.setattr(fa, kern, lambda *a, _k=kern, _f=getattr(
            fa, kern), **kw: ran.append(_k) or _f(*a, **kw))
    drop = None
    if name == "dropout":
        name, drop = "causal", (jax.random.key_data(
            jax.random.PRNGKey(5)).astype(jnp.uint32), 205)
    q, k, v, bias, cfg = _site(name)
    jax.eval_shape(lambda *a: fa._fa_forward(
        *a, cfg["scale"], cfg["block_q"], cfg["block_k"], return_lse=True,
        layout=cfg["layout"], causal=cfg["causal"], window=cfg["window"],
        dropout=drop), q, k, v, bias)
    took = kreg.dispatch_stats()["per_kernel"]["flash_attention"]
    assert took == {outcome: 1}, took
    assert set(ran) == {body}, ran


def test_a_site_under_the_crossover_counts_no_forward_outcome():
    """S = 128 (the two S=128 cells' sites) routes to the composed path:
    the site counts `lowered` and no forward outcome."""
    kreg.reset_stats()
    q = jax.ShapeDtypeStruct((4, 128, 8, 64), jnp.bfloat16)
    assert not fa.use_kernel_path(q, q, 128, 128, "bshd")
    took = kreg.dispatch_stats()["per_kernel"]["flash_attention"]
    assert took == {"lowered": 1}, took
