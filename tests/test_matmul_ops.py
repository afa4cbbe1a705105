"""matmul / mul / fc-substrate tests (reference test_matmul_op.py,
test_mul_op.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.amp import amp_guard
from paddle_tpu.core.registry import OPS, ExecContext, _SlotView
from paddle_tpu.kernels import registry as kreg
from paddle_tpu.ops.matmul import _flat2d

from op_test import OpTest


class TestMatmul(OpTest):
    def setUp(self):
        self.op_type = "matmul"
        rng = np.random.default_rng(0)
        x = rng.standard_normal((3, 4)).astype(np.float32)
        y = rng.standard_normal((4, 5)).astype(np.float32)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": x @ y}
        self.attrs = {"transpose_X": False, "transpose_Y": False,
                      "alpha": 1.0}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x", "y"], "out_out", max_relative_error=0.01)


class TestMatmulTransY(OpTest):
    def setUp(self):
        self.op_type = "matmul"
        rng = np.random.default_rng(1)
        x = rng.standard_normal((3, 4)).astype(np.float32)
        y = rng.standard_normal((5, 4)).astype(np.float32)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": x @ y.T}
        self.attrs = {"transpose_X": False, "transpose_Y": True,
                      "alpha": 1.0}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x", "y"], "out_out", max_relative_error=0.01)


class TestMatmulBatchedAlpha(OpTest):
    def setUp(self):
        self.op_type = "matmul"
        rng = np.random.default_rng(2)
        x = rng.standard_normal((2, 3, 4)).astype(np.float32)
        y = rng.standard_normal((2, 4, 2)).astype(np.float32)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": 0.5 * np.matmul(x, y)}
        self.attrs = {"transpose_X": False, "transpose_Y": False,
                      "alpha": 0.5}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x", "y"], "out_out", max_relative_error=0.01)


class TestMul(OpTest):
    def setUp(self):
        self.op_type = "mul"
        rng = np.random.default_rng(3)
        x = rng.standard_normal((3, 2, 2)).astype(np.float32)
        y = rng.standard_normal((4, 5)).astype(np.float32)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": x.reshape(3, 4) @ y}
        self.attrs = {"x_num_col_dims": 1, "y_num_col_dims": 1}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x", "y"], "out_out", max_relative_error=0.01)


class TestSum(OpTest):
    def setUp(self):
        self.op_type = "sum"
        rng = np.random.default_rng(4)
        a = rng.standard_normal((3, 4)).astype(np.float32)
        b = rng.standard_normal((3, 4)).astype(np.float32)
        c = rng.standard_normal((3, 4)).astype(np.float32)
        self.inputs = {"X": [("a", a), ("b", b), ("c", c)]}
        self.outputs = {"Out": a + b + c}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["a", "b", "c"], "out_out")


class TestBilinearTensorProduct(OpTest):
    def setUp(self):
        self.op_type = "bilinear_tensor_product"
        rng = np.random.default_rng(5)
        B, M, N, K = 3, 4, 3, 5
        x = rng.standard_normal((B, M)).astype(np.float32)
        y = rng.standard_normal((B, N)).astype(np.float32)
        w = rng.standard_normal((K, M, N)).astype(np.float32)
        bias = rng.standard_normal((1, K)).astype(np.float32)
        out = np.einsum("bm,kmn,bn->bk", x, w, y) + bias
        self.inputs = {"X": x, "Y": y, "Weight": w, "Bias": bias}
        self.outputs = {"Out": out.astype(np.float32)}

    def test_output(self):
        self.check_output(atol=1e-4)


# ------------------------------------------- mul in X's own rank


def _lowered_mul(x, y, xn, yn):
    """Out of the registered `mul` lowering, as the engine runs it."""
    env = {"x": x, "y": y}
    op = _SlotView("mul", {"X": ["x"], "Y": ["y"]}, {"Out": ["out"]},
                   {"x_num_col_dims": xn, "y_num_col_dims": yn})
    OPS.get("mul").lowering(ExecContext(op, env))
    return env["out"]


def _flat_mul(x, y, xn, yn):
    """The reference's form: X and Y flattened to 2-D, the product
    reshaped back."""
    x2, y2 = _flat2d(x, xn), _flat2d(y, yn)
    out = jnp.matmul(x2, y2, preferred_element_type=jnp.float32)
    return out.astype(x.dtype).reshape(x.shape[:xn] + y.shape[yn:])


# (X shape, Y shape, x_num_col_dims, y_num_col_dims): X's contracted
# extents are Y's (every `fc`), or match them only as products
MUL_CASES = [
    ((6, 24), (24, 5), 1, 1),
    ((6, 24), (4, 6, 5), 1, 2),
    ((3, 4, 24), (24, 5), 2, 1),
    ((3, 4, 24), (24, 5, 2), 2, 1),
    ((3, 4, 24), (4, 6, 5), 2, 2),
    ((3, 4, 24), (4, 24, 5), 1, 2),
    ((3, 4, 24), (96, 5), 1, 1),
    ((1, 6, 24), (24, 5), 2, 1),
    ((1, 1, 24), (24, 5, 2), 2, 1),
    ((2, 3, 4, 24), (24, 5), 3, 1),
    ((2, 1, 4, 24), (24, 5), 3, 1),
    ((2, 3, 4, 24), (4, 6, 5), 3, 2),
    ((2, 3, 4, 24), (288, 5), 1, 1),
    ((2, 3, 4, 24), (12, 24, 5), 1, 2),
]


@pytest.mark.parametrize("xs,ys,xn,yn", MUL_CASES,
                         ids=lambda v: "x".join(map(str, v))
                         if isinstance(v, tuple) else str(v))
def test_mul_in_rank_equals_the_flat_form(xs, ys, xn, yn):
    """Forward and the generic grad (jax.vjp of the lowering, as
    `mul_grad` takes it) against the flattened form in float32 to a few
    ulp, and the bf16 product under AMP within bf16 rounding; the
    `routing` counter counts the call once as `in_rank`."""
    rng = np.random.default_rng(len(xs) * 10 + xn * 3 + yn)
    x = jnp.asarray(rng.standard_normal(xs), jnp.float32)
    y = jnp.asarray(rng.standard_normal(ys), jnp.float32)
    g = jnp.asarray(rng.standard_normal(xs[:xn] + ys[yn:]), jnp.float32)
    kreg.reset_stats()
    out, vjp = jax.vjp(lambda a, b: _lowered_mul(a, b, xn, yn), x, y)
    took = kreg.dispatch_stats()["per_kernel"]["mul"]
    assert took == {"in_rank": 1}, took
    ref, ref_vjp = jax.vjp(lambda a, b: _flat_mul(a, b, xn, yn), x, y)
    assert out.shape == ref.shape and out.dtype == jnp.float32
    k = int(np.prod(xs[xn:]))
    tol = 4 * np.finfo(np.float32).eps * np.sqrt(k)
    for got, want in zip((out,) + vjp(g), (ref,) + ref_vjp(g)):
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol * scale)
    with amp_guard(True):
        half = jax.jit(lambda a, b: _lowered_mul(a, b, xn, yn))(x, y)
    assert half.dtype == jnp.bfloat16
    want = _flat_mul(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16), xn, yn)
    np.testing.assert_allclose(
        np.asarray(half, np.float32), np.asarray(want, np.float32),
        rtol=2 ** -7, atol=2 ** -7 * float(jnp.max(jnp.abs(
            want.astype(jnp.float32)))))


def _flat_lowering(ctx):
    """`mul` as the reference writes it: flatten to 2-D, multiply,
    reshape back."""
    x, y = ctx.input("X"), ctx.input("Y")
    xn = ctx.attr("x_num_col_dims", 1)
    yn = ctx.attr("y_num_col_dims", 1)
    ctx.set_output("Out", _flat_mul(x, y, xn, yn))


def _transformer_losses(steps=3):
    from paddle_tpu import models
    from paddle_tpu.core.scope import Scope
    cfg = models.transformer.TransformerConfig(
        src_vocab_size=64, trg_vocab_size=64, d_model=32, d_inner=64,
        n_head=4, n_layer=2, dropout=0.0, fuse_attention=True)
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        cost, _, _ = models.transformer_train(cfg)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(cost)
    feed = models.transformer.make_batch(cfg, 4, 8, 8)
    with fluid.scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return [float(np.asarray(exe.run(main, feed=feed,
                                         fetch_list=[cost])[0]))
                for _ in range(steps)]


def test_transformer_trains_as_with_the_flat_mul(monkeypatch):
    """Three Adam steps of the Transformer program in float32: every
    `fc` in X's own rank gives the flattened form's losses within f32
    rounding."""
    kreg.reset_stats()
    in_rank = _transformer_losses()
    took = kreg.dispatch_stats()["per_kernel"]["mul"]
    assert set(took) == {"in_rank"}, took
    monkeypatch.setattr(OPS.get("mul"), "lowering", _flat_lowering)
    flat = _transformer_losses()
    assert in_rank[-1] < in_rank[0]
    np.testing.assert_allclose(in_rank, flat, rtol=1e-5)
