"""Ring attention (sequence/context parallel) correctness vs full
attention, on the 8-device virtual CPU mesh."""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from paddle_tpu.kernels.flash_attention import _attn_reference
from paddle_tpu.parallel.ring_attention import ring_attention


def test_ring_attention_matches_full():
    rng = np.random.default_rng(0)
    B, H, S, D = 2, 2, 64, 16
    n_sp = 4
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    lens = np.array([50, 64])
    mask = np.arange(S)[None, :] < lens[:, None]
    causal = np.tril(np.ones((S, S), bool))
    bias = jnp.asarray(np.where(
        causal[None, None] & mask[:, None, None, :], 0.0,
        -1e9).astype(np.float32))

    scale = float(D) ** -0.5
    ref = _attn_reference(q, k, v, bias, scale)

    mesh = Mesh(np.array(jax.devices()[:n_sp]), ("sp",))
    seq_sh = NamedSharding(mesh, P(None, None, "sp", None))
    bias_sh = NamedSharding(mesh, P(None, None, "sp", None))

    def f(q, k, v, bias):
        return ring_attention(q, k, v, bias, axis_name="sp",
                              scale=scale)

    fm = shard_map(f, mesh=mesh,
                   in_specs=(P(None, None, "sp", None),) * 3 +
                   (P(None, None, "sp", None),),
                   out_specs=P(None, None, "sp", None))
    out = jax.jit(fm)(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grads_match():
    rng = np.random.default_rng(1)
    B, H, S, D = 1, 2, 32, 8
    n_sp = 4
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    scale = float(D) ** -0.5

    mesh = Mesh(np.array(jax.devices()[:n_sp]), ("sp",))
    fm = shard_map(
        lambda q, k, v: ring_attention(q, k, v, None, "sp", scale),
        mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None))

    def loss_ring(q, k, v):
        return (fm(q, k, v) ** 2).sum()

    def loss_ref(q, k, v):
        return (_attn_reference(q, k, v, None, scale) ** 2).sum()

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_ring_attention_grads_kernel_path(monkeypatch):
    """Gradients flow through the PALLAS kernel forward (interpret
    mode stands in for TPU): the custom_vjp recompute backward must
    engage on exactly the path training uses on hardware."""
    import importlib
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    monkeypatch.setattr(fa, "_INTERPRET", True)

    rng = np.random.default_rng(2)
    B, H, S, D = 1, 1, 256, 8  # local blocks 128 -> kernel path
    n_sp = 2
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    scale = float(D) ** -0.5

    mesh = Mesh(np.array(jax.devices()[:n_sp]), ("sp",))
    fm = shard_map(
        lambda q, k, v: ring_attention(q, k, v, None, "sp", scale),
        mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None), check_vma=False)

    def loss_ring(q, k, v):
        return (fm(q, k, v) ** 2).sum()

    def loss_ref(q, k, v):
        return (_attn_reference(q, k, v, None, scale) ** 2).sum()

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_ring_attention_long_context_training_step():
    """Long-context stress: a 8192-token causal sequence sharded over
    sp=8 trains one attention-layer step; grads match the full-attention
    computation (the first-class long-context claim, SURVEY section 5)."""
    rng = np.random.default_rng(7)
    B, H, S, D = 1, 2, 8192, 16
    n_sp = 8
    q = jnp.asarray(rng.standard_normal((B, H, S, D)) * 0.05,
                    jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)) * 0.05,
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)) * 0.05,
                    jnp.float32)
    causal = np.tril(np.ones((S, S), bool))
    bias = jnp.asarray(np.where(causal[None, None], 0.0,
                                -1e9).astype(np.float32))
    scale = float(D) ** -0.5

    mesh = Mesh(np.array(jax.devices()[:n_sp]), ("sp",))
    specs = (P(None, None, "sp", None),) * 4

    def loss_ring(q, k, v, bias):
        def f(q, k, v, bias):
            o = ring_attention(q, k, v, bias, axis_name="sp",
                               scale=scale)
            # partial sums live per sp shard: reduce across the ring
            return jax.lax.psum(jnp.sum(jnp.square(o)), "sp")
        part = shard_map(f, mesh=mesh, in_specs=specs,
                         out_specs=P(), check_vma=False)
        return part(q, k, v, bias)

    ring_val, ring_grads = jax.value_and_grad(
        loss_ring, argnums=(0, 1, 2))(q, k, v, bias)

    def loss_ref(q, k, v):
        o = _attn_reference(q, k, v, bias, scale)
        return jnp.sum(jnp.square(o))

    ref_val, ref_grads = jax.value_and_grad(
        loss_ref, argnums=(0, 1, 2))(q, k, v)

    np.testing.assert_allclose(float(ring_val), float(ref_val),
                               rtol=2e-4)
    for rg, fg in zip(ring_grads, ref_grads):
        np.testing.assert_allclose(np.asarray(rg), np.asarray(fg),
                                   rtol=5e-3, atol=5e-5)


def test_ring_backward_residuals_scale_inverse_with_sp():
    """O(S/n) end-to-end memory (round-2 verdict item 4): the custom_vjp
    residuals saved between forward and backward are per-device local
    blocks only — total residual bytes must scale ~1/n with the sp size —
    and the backward re-rotates K/V (ppermute count grows with n) instead
    of saving every rotated block."""
    import importlib
    ra = importlib.import_module("paddle_tpu.parallel.ring_attention")

    B, H, S, D = 1, 2, 8192, 16
    scale = float(D) ** -0.5

    def residual_bytes(n_sp):
        sizes = {}
        mesh = Mesh(np.array(jax.devices()[:n_sp]), ("sp",))

        def f(q, k, v):
            primal, res = ra._ring_fwd(q, k, v, None, "sp", scale)
            sizes["bytes"] = sum(
                int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(res))
            return primal

        fm = shard_map(f, mesh=mesh,
                       in_specs=(P(None, None, "sp", None),) * 3,
                       out_specs=P(None, None, "sp", None))
        q = jax.ShapeDtypeStruct((B, H, S, D), jnp.float32)
        jax.eval_shape(fm, q, q, q)
        return sizes["bytes"]

    b2 = residual_bytes(2)
    b8 = residual_bytes(8)
    # residuals are (q, k, v, out, lse) local blocks: exactly 1/n each
    assert b8 <= b2 / 3.5, (b2, b8)

    # backward re-rotates: the grad jaxpr holds ~4n ppermutes (k, v,
    # dk_acc, dv_acc per step) on top of the forward's 2(n-1)
    def pcount(n_sp):
        mesh = Mesh(np.array(jax.devices()[:n_sp]), ("sp",))
        fm = shard_map(
            lambda q, k, v: ra.ring_attention(q, k, v, None, "sp",
                                              scale),
            mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None))
        q = jax.ShapeDtypeStruct((B, H, 512, D), jnp.float32)
        jaxpr = jax.make_jaxpr(
            jax.grad(lambda q, k, v: (fm(q, k, v) ** 2).sum(),
                     (0, 1, 2)))(q, q, q)
        return str(jaxpr).count("ppermute")

    n = 4
    assert pcount(n) >= 6 * n - 6, pcount(n)
