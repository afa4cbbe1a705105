"""Numerics-parity harness: every registered kernel vs its lowered op.

The generate-and-verify loop (PAPERS.md "Agentic Operator Generation
for ML ASICs"): a custom kernel is only trusted while a parity case
demonstrates, on every test run, that it matches the lowered-op
baseline it replaces.  The harness

* runs each case's BASELINE through the real op lowering
  (``core.registry.OPS``) with the kernel registry force-disabled, so
  the reference really is the path users get with kernels off;
* runs the kernel directly: compiled by Mosaic on a TPU
  (``chip_smoke.py`` leg C), under the Pallas interpreter on CPU — see
  ``registry.interpret()`` — so this also gates in tier-1 CI under
  ``JAX_PLATFORMS=cpu``;
* compares under a per-dtype tolerance: **ulp** bounds for
  value-preserving kernels (fused optimizer: same math, same
  operation order, tolerance a handful of ulp), **relative-error**
  bounds for value-approximating kernels (quantized matmul, flash
  attention's online softmax). Where two correct programs legitimately
  differ (FMA contraction on a cancelling sum; the MXU's bf16 passes at
  default precision) kernel and baseline are each measured against a
  float64 evaluation and the kernel may not be worse — the bound
  follows what the backend does, it is not widened to cover it.

``tools/lint_program.py --check-kernels`` fails the build when a
registered kernel has no parity case (:func:`missing_parity`);
``tests/test_kernels.py`` runs :func:`run_all` case by case.

Tolerance policy (docs/KERNELS.md): f32 value-preserving <= 4 ulp
(fused_sgd), or <= 8 ulp of each sum's largest addend from a float64
evaluation (fused_adam, whose sums cancel); rel-error kernels get
per-mode bounds (int8 5e-2, bf16 1e-2; flash attention 1e-5 from float64 at precision "highest", and at
the backend's default precision no further from float64 than the
composed path) measured on unit-scale random data with a fixed seed —
loosening a bound is a reviewed change, not a test edit.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, List

import numpy as np

import jax.numpy as jnp

from . import registry

__all__ = ["cases", "run_case", "run_all", "missing_parity",
           "max_ulp", "adam_f64", "ADAM_TOL", "rel_err",
           "dropout_mask_identity"]


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def max_ulp(ref, got, scale=None) -> float:
    """Largest elementwise |got - ref| in units of ref's last place —
    or of *scale*'s, where the caller knows the largest addend of the
    sum that made ref: a rounded sum is good to the last place of its
    largest addend, not of a result that cancelled, so on cancelling
    elements two correct f32 programs sit hundreds of ulp of the RESULT
    apart and within one or two of the addend's."""
    ref = np.asarray(ref)
    got = np.asarray(got)
    dt = ref.dtype if ref.dtype.kind == "f" else np.dtype(np.float32)
    if ref.size == 0:
        return 0.0
    unit = np.abs(ref if scale is None else scale)
    spacing = np.spacing(
        np.maximum(unit, np.finfo(dt).tiny).astype(dt)
    ).astype(np.float64)
    diff = np.abs(ref.astype(np.float64) - got.astype(np.float64))
    return float(np.max(diff / spacing))


def adam_f64(p, g, m, v, lr_t, b1=0.9, b2=0.999, eps=1e-8):
    """The Adam recurrence in float64 over f32 inputs, with the f32
    constants the programs use: ``[(ref, scale)]`` for p', m', v', each
    ref rounded to f32 beside the largest addend of its last sum (for
    p' the update's own addends carried through the quotient), the
    unit :func:`max_ulp` measures a correct f32 program by."""
    f64, f32 = np.float64, np.float32
    p, g, m, v = (np.asarray(x).astype(f64) for x in (p, g, m, v))
    m_old, m_grad = f64(f32(b1)) * m, f64(f32(1.0 - b1)) * g
    v64 = f64(f32(b2)) * v + f64(f32(1.0 - b2)) * g * g
    step = f64(f32(lr_t)) / (np.sqrt(v64) + f64(f32(eps)))
    m64 = m_old + m_grad
    m_scale = np.maximum(np.abs(m_old), np.abs(m_grad))
    return [((p - step * m64).astype(f32),
             np.maximum(np.abs(p), step * m_scale).astype(f32)),
            (m64.astype(f32), m_scale.astype(f32)),
            (v64.astype(f32), v64.astype(f32))]


def rel_err(ref, got) -> float:
    ref = np.asarray(ref, np.float64)
    got = np.asarray(got, np.float64)
    denom = np.linalg.norm(ref.ravel())
    return float(np.linalg.norm((got - ref).ravel())
                 / max(denom, 1e-30))


@contextlib.contextmanager
def _kernels_disabled():
    """Run the baseline with registry selection off, so the lowered
    path is the real lowered path even when a test armed the
    interpret-mode hook."""
    from ..core.flags import FLAGS, set_flags
    prev = bool(FLAGS.use_custom_kernels)
    set_flags({"FLAGS_use_custom_kernels": False})
    try:
        yield
    finally:
        set_flags({"FLAGS_use_custom_kernels": prev})


def _run_lowered(op_type: str, inputs: Dict[str, List[str]],
                 outputs: Dict[str, List[str]],
                 attrs: Dict[str, Any], env: Dict[str, Any]):
    """Execute one op through its registered lowering; returns env.

    The lowering runs under jax.jit, like it does inside the engine's
    whole-block trace — XLA's instruction contraction (FMA) is part of
    the baseline numerics, and eager op-by-op execution would misstate
    them (cancellation-heavy terms land tens of ulp away)."""
    import jax
    from ..core.registry import OPS, ExecContext, _SlotView
    names = sorted(env)
    out_names = [n for ns in outputs.values() for n in ns]

    def f(vals):
        local = dict(zip(names, vals))
        op = _SlotView(op_type, inputs, outputs, attrs)
        OPS.get(op_type).lowering(ExecContext(op, local))
        return {n: local[n] for n in out_names}

    with _kernels_disabled():
        env.update(jax.jit(f)([env[n] for n in names]))
    return env


# ---------------------------------------------------------------------------
# cases
# ---------------------------------------------------------------------------

class Case:
    """One (kernel, configuration) parity check."""

    __slots__ = ("kernel", "label", "runner")

    def __init__(self, kernel: str, label: str,
                 runner: Callable[[], Dict[str, Any]]):
        self.kernel = kernel      # registered kernel name
        self.label = label
        self.runner = runner

    def __repr__(self):
        return "Case(%s)" % (self.label,)


def _rng(seed=0):
    return np.random.default_rng(seed)


# a correct f32 Adam reads up to 4.4 addend-ulp from float64 on a million
# elements (the quotient's roundings ride on p'); a wrong block, operand
# or constant reads thousands
ADAM_TOL = 8.0


def _adam_case(shape):
    def run():
        r = _rng(7)
        p = r.standard_normal(shape, dtype=np.float32)
        g = r.standard_normal(shape, dtype=np.float32)
        m = 0.1 * r.standard_normal(shape, dtype=np.float32)
        v = np.abs(0.01 * r.standard_normal(shape, dtype=np.float32))
        lr = np.float32(1e-3)
        b1p, b2p = np.float32(0.9 ** 3), np.float32(0.999 ** 3)
        env = {"p": jnp.asarray(p), "g": jnp.asarray(g),
               "m": jnp.asarray(m), "v": jnp.asarray(v),
               "lr": jnp.asarray(lr).reshape(1),
               "b1p": jnp.asarray(b1p).reshape(1),
               "b2p": jnp.asarray(b2p).reshape(1)}
        _run_lowered(
            "adam",
            {"Param": ["p"], "Grad": ["g"], "Moment1": ["m"],
             "Moment2": ["v"], "LearningRate": ["lr"],
             "Beta1Pow": ["b1p"], "Beta2Pow": ["b2p"]},
            {"ParamOut": ["po"], "Moment1Out": ["mo"],
             "Moment2Out": ["vo"], "Beta1PowOut": [],
             "Beta2PowOut": []},
            {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}, env)
        from .fused_optimizer import fused_adam
        lr_t = lr * np.sqrt(1 - b2p) / (1 - b1p)
        po, mo, vo = fused_adam(jnp.asarray(p), jnp.asarray(g),
                                jnp.asarray(m), jnp.asarray(v),
                                jnp.asarray(lr_t), beta1=0.9,
                                beta2=0.999, epsilon=1e-8)
        # m' = b1*m + (1-b1)*g and p' = p - upd cancel on some
        # elements, and there two correct f32 programs that differ in
        # FMA contraction sit hundreds of ulp of the result apart
        # (XLA:CPU vs the Pallas interpreter, whose contraction follows
        # the block's shape: 32,733 at (16, 128); compiled on the v5e:
        # 0). So both are measured against the same recurrence in
        # float64, in units of the largest addend's last place, where a
        # correct program reads 1 to 5 (adam_f64).
        refs = adam_f64(p, g, m, v, lr_t)
        outs = (po, mo, vo)
        lows = (env["po"], env["mo"], env["vo"])
        return {"metric": "addend_ulp_from_f64", "tol": ADAM_TOL,
                "value": max(max_ulp(r, k, s)
                             for (r, s), k in zip(refs, outs)),
                "note": "lowering %.3g from float64; kernel vs lowering "
                        "%g ulp of the result"
                        % (max(max_ulp(r, lo, s)
                               for (r, s), lo in zip(refs, lows)),
                           max(map(max_ulp, lows, outs)))}
    return Case("fused_adam", "fused_adam/f32/%s" % (shape,), run)


def _sgd_case(shape):
    def run():
        r = _rng(11)
        p = r.standard_normal(shape, dtype=np.float32)
        g = r.standard_normal(shape, dtype=np.float32)
        lr = np.float32(0.05)
        env = {"p": jnp.asarray(p), "g": jnp.asarray(g),
               "lr": jnp.asarray(lr).reshape(1)}
        _run_lowered(
            "sgd",
            {"Param": ["p"], "Grad": ["g"], "LearningRate": ["lr"]},
            {"ParamOut": ["po"]}, {}, env)
        from .fused_optimizer import fused_sgd
        po = fused_sgd(jnp.asarray(p), jnp.asarray(g),
                       jnp.asarray(lr))
        return {"metric": "ulp", "tol": 4.0,
                "value": max_ulp(env["po"], po)}
    return Case("fused_sgd", "fused_sgd/f32/%s" % (shape,), run)


def _qmm_case(mode, tol):
    def run():
        r = _rng(13)
        x = r.standard_normal((256, 384), dtype=np.float32)
        y = r.standard_normal((384, 128), dtype=np.float32)
        env = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
        _run_lowered("mul", {"X": ["x"], "Y": ["y"]},
                     {"Out": ["out"]},
                     {"x_num_col_dims": 1, "y_num_col_dims": 1}, env)
        from .quantized_matmul import quantized_matmul
        got = quantized_matmul(jnp.asarray(x), jnp.asarray(y),
                               mode=mode)
        return {"metric": "rel", "tol": tol,
                "value": rel_err(env["out"], got)}
    return Case("quantized_matmul",
                "quantized_matmul/%s/256x384x128" % mode, run)


def _fa_module():
    # the package re-exports the flash_attention FUNCTION under the
    # module's name; go through importlib for the module itself
    import importlib
    return importlib.import_module("paddle_tpu.kernels.flash_attention")


@contextlib.contextmanager
def _fa_kernels_live(fa):
    """Flash kernels run as the backend runs them: compiled by Mosaic
    on a TPU, under the Pallas interpreter on a CPU host."""
    prev = fa._INTERPRET
    fa._INTERPRET = registry.interpret()
    try:
        yield
    finally:
        fa._INTERPRET = prev


# flash attention, f32 data, against a float64 evaluation. At precision
# "highest" the kernel is held to an f32 bound on every backend
# (measured: 3.6e-7 interpreted, 1.3e-6 compiled on the v5e). At the
# backend's DEFAULT precision a TPU feeds the MXU bf16 passes in the
# kernel and in the composed path alike (3.18e-3 and 3.34e-3 from
# float64 on the v5e), so no flat bound separates a correct kernel from
# a broken one: the kernel may be at most _FA_SLACK further from
# float64 than the composed path it replaces, measured in the same run.
_FA_HIGHEST_TOL = 1e-5
_FA_SLACK = 1.25


def _fa_case(precision):
    def run():
        import jax
        fa = _fa_module()
        r = _rng(17)
        q = r.standard_normal((1, 2, 256, 64), dtype=np.float32)
        k = r.standard_normal((1, 2, 256, 64), dtype=np.float32)
        v = r.standard_normal((1, 2, 256, 64), dtype=np.float32)
        scale = 0.125
        s64 = np.einsum("bhqd,bhkd->bhqk", q.astype(np.float64),
                        k.astype(np.float64)) * scale
        p64 = np.exp(s64 - s64.max(-1, keepdims=True))
        p64 /= p64.sum(-1, keepdims=True)
        ref = np.einsum("bhqk,bhkd->bhqd", p64, v.astype(np.float64))
        scope = (jax.default_matmul_precision("highest")
                 if precision == "highest" else contextlib.nullcontext())
        with scope, _fa_kernels_live(fa):
            got = fa.flash_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), None, scale,
                                     128, 128)
            composed = fa._attn_reference(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), None,
                scale)
        k_err, c_err = rel_err(ref, got), rel_err(ref, composed)
        tol = _FA_HIGHEST_TOL
        if precision != "highest":
            tol = max(tol, _FA_SLACK * c_err)
        return {"metric": "rel_vs_f64", "tol": tol, "value": k_err,
                "note": "composed path %.3g from float64" % c_err}
    return Case("flash_attention",
                "flash_attention/f32/%s/1x2x256x64" % precision, run)


# The flash backward as the long-sequence cells run it: bf16 streams in
# the bshd layout, causal, d_head 64 (tbase_s4096) and q/k 192 against
# v 128 (kanana2_s4096), compiled at S=4096 in blocks of 512 x 1024 on a
# chip and interpreted at S=256 in blocks of 128 on a CPU host. The
# fused kernel's dq, dk and dv are held to the composed formulation's
# float32 vjp at precision "highest" (2.5e-3 compiled on the v5e: the
# bf16 rounding of p, ds and the results) and, bit for bit, to the split
# pair's, whose accumulation order the fused kernel keeps.
_FA_BWD_BF16_TOL = 1e-2


def _fa_bwd_case(heads, d, dv):
    def run():
        import jax
        fa = _fa_module()
        seq, bq, bk = (256, 128, 128) if registry.interpret() \
            else (4096, 512, 1024)
        r = _rng(31)
        q, k, v, g = (jnp.asarray(r.standard_normal((1, seq, heads, w),
                                                    dtype=np.float32),
                                  jnp.bfloat16)
                      for w in (d, d, dv, dv))
        scale = d ** -0.5

        def backward(q, k, v, g):
            out, lse = fa._fa_forward(q, k, v, None, scale, bq, bk,
                                      return_lse=True, layout="bshd",
                                      causal=True)
            return fa._fa_backward(q, k, v, None, out, lse, g, scale, bq,
                                   bk, layout="bshd", causal=True)[:3]

        with _fa_kernels_live(fa):
            fused = jax.jit(backward)(q, k, v, g)
            budget, fa._FUSED_DQ_VMEM_BUDGET = fa._FUSED_DQ_VMEM_BUDGET, 0
            try:
                split = jax.jit(backward)(q, k, v, g)
            finally:
                fa._FUSED_DQ_VMEM_BUDGET = budget
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(
                lambda q, k, v: fa._attn_reference(
                    q, k, v, None, scale, layout="bshd", causal=True),
                *(x.astype(jnp.float32) for x in (q, k, v)))
            want = vjp(g.astype(jnp.float32))
        apart = sum(int((np.asarray(a, np.float32)
                         != np.asarray(b, np.float32)).sum())
                    for a, b in zip(fused, split))
        err = max(rel_err(w, np.asarray(f, np.float32))
                  for f, w in zip(fused, want))
        return {"metric": "rel_vs_composed_vjp", "tol": _FA_BWD_BF16_TOL,
                "value": float("inf") if apart else err,
                "note": "%d elements apart from the split pair's" % apart}
    return Case("flash_attention",
                "flash_attention/bwd_fused/bf16/%dx%d" % (d, dv), run)


def dropout_mask_identity() -> Dict[str, Any]:
    """Do the forward and the backward flash kernels realize the SAME
    attention-dropout mask? Exact-extraction probe: q = k = 0 makes p
    uniform, so one-hot v / dO read the keep mask out elementwise from
    the forward output and from dv (the fused backward kernel), and a
    per-head zero bias whose gradient is demanded reads it from ds (the
    split pair's dq kernel). Compiled kernels draw the mask from the
    TPU hardware PRNG (``_tile_keep``), which no CPU run reaches; under
    the interpreter the same probe checks the hash path. Returns ``value`` = mask
    positions where a backward kernel disagrees with the forward
    (``tol`` 0) and ``keep_frac``, the realized keep rate."""
    import jax
    fa = _fa_module()
    B, H, S, D = 1, 4, 256, 64
    bq = bk = 128
    key = jax.random.PRNGKey(9)
    t = 205
    c = 256.0 / t
    z = jnp.zeros((B, S, H, D), jnp.float32)
    drop = dict(layout="bshd", dropout=(key, t))

    # jitted once each: the one-hot sweeps reuse one compiled kernel
    @jax.jit
    def fwd(v, bias):
        return fa._fa_forward(z, z, v, bias, 1.0, bq, bk,
                              return_lse=True, **drop)

    @jax.jit
    def dv_of(out, lse, do):
        return fa._fa_backward(z, z, z, None, out, lse, do, 1.0, bq,
                               bk, **drop)[2]

    with _fa_kernels_live(fa):
        m_fwd = np.zeros((H, S, S))
        for r in range(S // 64):
            v = np.zeros((B, S, H, D), np.float32)
            for j in range(64):
                v[0, r * 64 + j, :, j] = 1.0
            out, _ = fwd(jnp.asarray(v), None)
            m_fwd[:, :, r * 64:(r + 1) * 64] = \
                np.moveaxis(np.asarray(out)[0], 1, 0) * (S / c)
        m_fwd = m_fwd > 0.5

        out, lse = fwd(z, None)
        m_dkv = np.zeros((H, S, S))
        for r in range(S // 64):
            do = np.zeros((B, S, H, D), np.float32)
            for i in range(64):
                do[0, r * 64 + i, :, i] = 1.0
            dv = dv_of(out, lse, jnp.asarray(do))
            m_dkv[:, r * 64:(r + 1) * 64, :] = \
                np.transpose(np.asarray(dv)[0], (1, 2, 0)) * (S / c)

        v = jnp.asarray(
            _rng(0).standard_normal((B, S, H, D)) * 0.3 + 1.0,
            jnp.float32)
        bias_h = jnp.zeros((B, H, S, S), jnp.float32)
        out, lse = fwd(v, bias_h)
        _, _, _, dbias = fa._fa_backward(
            z, z, v, bias_h, out, lse, jnp.ones_like(v), 1.0, bq, bk,
            want_dbias=True, **drop)
    ds = np.asarray(dbias)[0]
    w = np.asarray(v.sum(-1))[0]
    di = np.asarray(out.sum(-1))[0]
    m_dq = np.zeros((H, S, S))
    for h in range(H):
        m_dq[h] = (S * ds[h] + di[:, h:h + 1]) / (c * w[:, h][None, :])
    return {"metric": "mask_mismatch", "tol": 0,
            "value": int((m_fwd != (m_dkv > 0.5)).sum()
                         + (m_fwd != (m_dq > 0.5)).sum()),
            "keep_frac": float(m_fwd.mean())}


def _gmm_case(which):
    """The grouped matmul over a row buffer with uneven groups (one
    expert without a row, one over two tiles), f32 at precision
    "highest": each kernel against the lowered ragged dot over the same
    buffer."""
    def run():
        import jax
        from . import grouped_matmul as gm
        r = _rng(23)
        experts, k, n = 4, 128, 256
        local = np.concatenate([np.full(300, 2), np.full(40, 0),
                                np.full(90, 3), np.full(50, 7)])
        plan = gm.plan_rows(jnp.asarray(r.permutation(local), jnp.int32),
                            experts)
        rows = plan["valid"].shape[0]
        valid = np.asarray(plan["valid"])[:, None]
        lhs = jnp.asarray(r.standard_normal((rows, k), dtype=np.float32)
                          * valid)
        dout = jnp.asarray(r.standard_normal((rows, n), dtype=np.float32)
                           * valid)
        rhs = jnp.asarray(r.standard_normal((experts, k, n),
                                            dtype=np.float32))
        used = int(plan["n_active"][0]) * gm.TILE_ROWS
        with jax.default_matmul_precision("highest"):
            if which == "fwd":
                got, ref = (gm.gmm(lhs, rhs, plan, kern)[:used]
                            for kern in (True, False))
            elif which == "dx":
                got, ref = (gm.gmm_dx(dout, rhs, plan, kern)[:used]
                            for kern in (True, False))
            else:
                got, ref = (gm.gmm_dw(lhs, dout, plan, experts, kern)
                            for kern in (True, False))
        return {"metric": "rel_vs_lowered", "tol": 1e-5,
                "value": rel_err(ref, got)}
    return Case("moe_grouped_matmul",
                "moe_grouped_matmul/%s/f32/4x128x256" % which, run)


def _combine_case(label, dtype):
    """The expert layer's combine out of a prefix of the row buffer
    (kernel `moe_combine`, part of the `moe_grouped_matmul` decision):
    64 tokens x top-4 over 16 experts of which 0..3 are held, so a token
    holds up to four rows; against the gather over every choice
    (ops/decoder.py `_combine`), float32 sums of the same terms in
    another order. Rows past the tiles in use are NaN."""
    def run():
        from . import grouped_matmul as gm
        from ..ops.decoder import _combine
        r = _rng(29)
        t, top_k, held, d = 64, 4, 4, 256
        choice = np.stack([r.choice(16, top_k, replace=False)
                           for _ in range(t)]).astype(np.int32)
        choice[:8] = np.arange(4)[::-1]     # eight tokens hold all four,
        # chosen in the order the rows do not lie in
        plan = gm.plan_rows(jnp.asarray(choice.reshape(-1)), held)
        rows = gm.prefix_rows(t * top_k, held, 16)[0]
        used = int(plan["n_active"][0]) * gm.TILE_ROWS
        assert used <= rows
        buf = jnp.asarray(r.standard_normal((rows, d), dtype=np.float32),
                          dtype).at[used:].set(jnp.nan)
        got = gm.combine(buf, gm.prefix_plan(plan, rows), t, top_k)
        ref = _combine(buf, plan, t, top_k)
        return {"metric": "rel_vs_gather", "tol": 1e-6,
                "value": rel_err(ref, got)}
    return Case("moe_grouped_matmul", "moe_combine/" + label, run)


def _sparse_index_case(which):
    """A learned sparse attention's index at S=256 in 128-wide tiles:
    the score kernel against the `jax.numpy` lowering (f32, "highest"),
    and the selection kernel against `lax.top_k`'s threshold on the same
    scores, which must agree in every pair."""
    def run():
        import jax
        from . import sparse_index as si
        r = _rng(29)
        b, s, heads, dim = 2, 256, 4, 32
        q = jnp.asarray(r.standard_normal((b, s, heads, dim),
                                          dtype=np.float32))
        k = jnp.asarray(r.standard_normal((b, s, dim), dtype=np.float32))
        w = jnp.asarray(r.standard_normal((b, s, heads), dtype=np.float32))
        with jax.default_matmul_precision("highest"):
            ref = si.index_scores(q, k, w, False)
            if which == "scores":
                got = si.index_scores(q, k, w, True, tile=128)
                live = np.isfinite(np.asarray(ref))
                return {"metric": "rel_vs_lowered", "tol": 1e-5,
                        "value": rel_err(np.where(live, ref, 0),
                                         np.where(live, got, 0))
                        + float((np.isfinite(np.asarray(got))
                                 != live).sum())}
        got, counted = si.select_mask(ref, 48, True, rows=32, chunk=128)
        want, summed = si.select_mask(ref, 48, False)
        return {"metric": "mask_mismatch", "tol": 0,
                "value": int((np.asarray(got) != np.asarray(want)).sum()
                             + (np.asarray(counted)
                                != np.asarray(summed)).sum())}
    return Case("sparse_index_scores",
                "sparse_index_%s/f32/2x256x4x32" % which, run)


def _ssd_case(which):
    """Mamba-2's scan at 2 x 296 tokens (no multiple of the chunk), 8
    heads of 16 in 2 groups, state 32: the forward kernel's y and
    chunk-start states, and the backward kernel's six gradients, against
    the `jax.numpy` chunked form and its `jax.vjp` (f32, "highest")."""
    def run():
        import jax
        from . import mamba2_ssd as ssd
        r = _rng(31)
        b, t, h, p, g, n = 2, 296, 8, 16, 2, 32

        def draw(*shape):
            return jnp.asarray(r.standard_normal(shape, dtype=np.float32))
        x, bm, cm, dy = draw(b, t, h, p), draw(b, t, g, n), \
            draw(b, t, g, n), draw(b, t, h, p)
        dt = jax.nn.softplus(draw(b, t, h) - 1.0)
        a, d = -jnp.exp(draw(h) * 0.5), draw(h)
        with jax.default_matmul_precision("highest"):
            got, ref = (ssd.ssd(x, dt, a, bm, cm, d, kern)
                        for kern in (True, False))
            if which == "bwd":
                got, ref = (ssd.ssd_grad(x, dt, a, bm, cm, d, ref[1], dy,
                                         kern) for kern in (True, False))
        return {"metric": "rel_vs_lowered", "tol": 1e-5,
                "value": max(rel_err(w, v) for v, w in zip(got, ref))}
    return Case("mamba2_ssd", "mamba2_ssd/%s/f32/2x296x8x16" % which, run)


def _short_conv_case(which):
    """The gated short convolution at 2 x 296 tokens (no multiple of the
    32-row blocks: the halo crosses nine boundaries), D 64, three taps:
    the forward kernel's output, and the backward kernel's dX and
    dWeight, against the `jax.numpy` lowering and its `jax.vjp` (f32)."""
    def run():
        from . import short_conv as sc
        r = _rng(37)
        x = jnp.asarray(r.standard_normal((2, 296, 192), dtype=np.float32))
        w = jnp.asarray(r.standard_normal((64, 3), dtype=np.float32))
        g = jnp.asarray(r.standard_normal((2, 296, 64), dtype=np.float32))
        if which == "fwd":
            got, ref = (sc._fwd_call(x, w, rows=32),), (sc._lowered(x, w),)
        else:
            got, ref = sc._bwd_call(x, w, g, rows=32), \
                sc._lowered_grad(x, w, g)
        return {"metric": "rel_vs_lowered", "tol": 1e-5,
                "value": max(rel_err(v, u) for u, v in zip(got, ref))}
    return Case("gated_short_conv",
                "gated_short_conv/%s/f32/2x296x64x3" % which, run)


# shapes the fused optimizer blocks over their own layout
# (fused_optimizer._native_block): one whole block; N off the 128 lanes
# in a whole-row block; N over whole-row width and off the 512-column
# block (partial lane edge); K off the row block too (partial edges both
# ways); N off the lanes and K on them (blocked as its transpose);
# leading dimensions collapsed into the grid
_NATIVE_VIEW_SHAPES = ((64, 256), (40, 200), (16, 1100), (300, 1536),
                       (256, 200), (4, 16, 256))


def cases() -> List[Case]:
    """Every parity case; keyed to registered kernel names."""
    # import for side effect: ensure all kernels are registered before
    # completeness is judged
    import importlib
    from . import fused_optimizer, grouped_matmul  # noqa: F401
    from . import quantized_matmul, sparse_index  # noqa: F401
    from . import mamba2_ssd, short_conv  # noqa: F401
    importlib.import_module("paddle_tpu.kernels.flash_attention")
    return [
        _adam_case((4096,)),        # rank 1: the flat view
        _adam_case((513, 7)),       # under a tile: flat, padding tail
        _sgd_case((2048,)),
        _sgd_case((129, 5)),
        *(case(shape) for case in (_adam_case, _sgd_case)
          for shape in _NATIVE_VIEW_SHAPES),
        _qmm_case("int8", 5e-2),
        _qmm_case("bf16", 1e-2),
        _fa_case("highest"),
        _fa_case("default"),
        _fa_bwd_case(4, 64, 64),
        _fa_bwd_case(4, 192, 128),
        _gmm_case("fwd"),
        _gmm_case("dx"),
        _gmm_case("dw"),
        _combine_case("bf16", jnp.bfloat16),
        _combine_case("f32", jnp.float32),
        _sparse_index_case("scores"),
        _sparse_index_case("select"),
        _ssd_case("fwd"),
        _ssd_case("bwd"),
        _short_conv_case("fwd"),
        _short_conv_case("bwd"),
    ]


def run_case(case: Case) -> Dict[str, Any]:
    res = case.runner()
    res.update(kernel=case.kernel, label=case.label,
               passed=bool(res["value"] <= res["tol"]))
    return res


def run_all() -> List[Dict[str, Any]]:
    return [run_case(c) for c in cases()]


def missing_parity() -> List[str]:
    """Registered kernels with no parity case (lint surface)."""
    covered = {c.kernel for c in cases()}
    return [n for n in registry.kernel_names() if n not in covered]
