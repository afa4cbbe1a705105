"""Kernel variant search: generate-and-verify over block shapes and
epilogue fusions, ranked by measured time (docs/TUNING.md).

This extends the ``kernels/parity.py`` generate-and-verify loop from
"one hand-written kernel, one parity case" into a *search* (PAPERS.md
"Agentic Operator Generation for ML ASICs"): enumerate a family of
Pallas GEMM variants — tile shapes (bm, bn, bk) crossed with fused
epilogues (none, layer_norm, dropout+residual) — admit ONLY variants
whose parity case passes against the composed XLA baseline, then rank
the admitted set by the median of repeated timed calls. Winners persist in the tuning cache next to the
knob config and are re-registered on later runs by the driver.

The variant kernel follows quantized_matmul's structure: a
(M/bm, N/bn, K/bk) grid with K innermost ("arbitrary" = sequential),
an f32 VMEM accumulator across K steps, epilogue applied at the flush.
``layer_norm`` requires bn == N (the row statistics need the full
feature axis in the output tile — epilogue choice CONSTRAINS legal
blockings, which is exactly why this is a joint search). Dropout is
fused as mask-scale (the mask is an operand, so parity against the
composed baseline is exact modulo f32 reassociation).

On CPU the kernels run under the Pallas interpreter: parity gating is
real (tier-1 proves the loop), timings are marked ``interpret_mode``
and not treated as hardware truth.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["Variant", "enumerate_variants", "variant_cases",
           "verify_variant", "search_variants", "tuned_matmul",
           "register_winner"]

_LN_EPS = 1e-5
_KEEP = 0.9          # dropout keep probability for the fused epilogue
_REL_TOL = 1e-4      # f32 reassociation only (blocked-K accumulation)


class Variant:
    """One (block shape, epilogue) point of the search space."""

    __slots__ = ("bm", "bn", "bk", "epilogue")

    def __init__(self, bm: int, bn: int, bk: int, epilogue: str):
        self.bm, self.bn, self.bk = bm, bn, bk
        self.epilogue = epilogue

    @property
    def label(self) -> str:
        return (f"tuned_matmul/{self.epilogue}/"
                f"{self.bm}x{self.bn}x{self.bk}")

    def as_dict(self) -> Dict[str, Any]:
        return {"bm": self.bm, "bn": self.bn, "bk": self.bk,
                "epilogue": self.epilogue}

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"Variant({self.label})"


# ---------------------------------------------------------------------------
# the parameterized Pallas kernel
# ---------------------------------------------------------------------------

def _mm_block(x_ref, y_ref, o_ref, acc_ref, *, n_k):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot(x_ref[:], y_ref[:],
                              preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[:] = acc_ref[:]


def _mm_ln_block(x_ref, y_ref, g_ref, b_ref, o_ref, acc_ref, *, n_k):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot(x_ref[:], y_ref[:],
                              preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        acc = acc_ref[:]
        mu = jnp.mean(acc, axis=1, keepdims=True)
        var = jnp.mean((acc - mu) * (acc - mu), axis=1, keepdims=True)
        normed = (acc - mu) * jax.lax.rsqrt(var + _LN_EPS)
        o_ref[:] = normed * g_ref[:][None, :] + b_ref[:][None, :]


def _mm_dr_block(x_ref, y_ref, m_ref, r_ref, o_ref, acc_ref, *, n_k):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot(x_ref[:], y_ref[:],
                              preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[:] = (acc_ref[:] * m_ref[:] * (1.0 / _KEEP)
                    + r_ref[:])


def tuned_matmul(x, y, *, variant: Variant, gamma=None, beta=None,
                 mask=None, residual=None):
    """C = epilogue(x @ y) under ``variant``'s blocking.

    x: [M, K], y: [K, N], dims divisible by the variant's blocks;
    layer_norm additionally requires bn == N.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..kernels import registry as kreg

    bm, bn, bk = variant.bm, variant.bn, variant.bk
    M, K = x.shape
    K2, N = y.shape
    assert K == K2, (x.shape, y.shape)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (
        (M, N, K), (bm, bn, bk))
    if variant.epilogue == "layer_norm":
        assert bn == N, ("layer_norm epilogue needs full rows", bn, N)
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    xy_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j),
                     memory_space=pltpu.VMEM),
    ]
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j),
                            memory_space=pltpu.VMEM)
    common = dict(
        grid=grid,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=kreg.interpret(),
        name=f"tuned_matmul_{variant.epilogue}_{bm}x{bn}x{bk}",
    )
    if variant.epilogue == "none":
        return pl.pallas_call(
            functools.partial(_mm_block, n_k=n_k),
            in_specs=xy_specs, **common)(x, y)
    if variant.epilogue == "layer_norm":
        vec = pl.BlockSpec((bn,), lambda i, j, k: (j,),
                           memory_space=pltpu.VMEM)
        return pl.pallas_call(
            functools.partial(_mm_ln_block, n_k=n_k),
            in_specs=xy_specs + [vec, vec], **common)(
                x, y, gamma, beta)
    if variant.epilogue == "dropout_residual":
        tile = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j),
                            memory_space=pltpu.VMEM)
        return pl.pallas_call(
            functools.partial(_mm_dr_block, n_k=n_k),
            in_specs=xy_specs + [tile, tile], **common)(
                x, y, mask, residual)
    raise ValueError(f"unknown epilogue {variant.epilogue!r}")


# ---------------------------------------------------------------------------
# enumerate -> verify -> rank
# ---------------------------------------------------------------------------

_BLOCKS = ((64, 128, 128), (128, 128, 128), (128, 256, 128),
           (256, 256, 256))
_EPILOGUES = ("none", "layer_norm", "dropout_residual")


def enumerate_variants(M: int = 256, N: int = 256, K: int = 256
                       ) -> List[Variant]:
    """Legal (block, epilogue) points for an MxNxK problem."""
    out = []
    for ep in _EPILOGUES:
        for bm, bn, bk in _BLOCKS:
            if M % bm or N % bn or K % bk:
                continue
            if ep == "layer_norm" and bn != N:
                continue
            out.append(Variant(bm, bn, bk, ep))
    return out


def _problem(M, N, K, seed=23):
    import jax.numpy as jnp
    r = np.random.default_rng(seed)
    data = {
        "x": jnp.asarray(r.standard_normal((M, K), dtype=np.float32)),
        "y": jnp.asarray(r.standard_normal((K, N), dtype=np.float32)),
        "gamma": jnp.asarray(
            1.0 + 0.1 * r.standard_normal(N, dtype=np.float32)),
        "beta": jnp.asarray(
            0.1 * r.standard_normal(N, dtype=np.float32)),
        "mask": jnp.asarray(
            (r.random((M, N)) < _KEEP).astype(np.float32)),
        "residual": jnp.asarray(
            r.standard_normal((M, N), dtype=np.float32)),
    }
    return data


def _reference(epilogue: str, d):
    """Composed XLA baseline the variant must match (jitted, like the
    lowered path inside the engine trace — parity.py's discipline)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, y, gamma, beta, mask, residual):
        out = jnp.matmul(x, y)
        if epilogue == "layer_norm":
            mu = jnp.mean(out, axis=1, keepdims=True)
            var = jnp.mean((out - mu) ** 2, axis=1, keepdims=True)
            out = (out - mu) * jax.lax.rsqrt(var + _LN_EPS)
            out = out * gamma[None, :] + beta[None, :]
        elif epilogue == "dropout_residual":
            out = out * mask * (1.0 / _KEEP) + residual
        return out

    return f(d["x"], d["y"], d["gamma"], d["beta"], d["mask"],
             d["residual"])


def _run_variant(v: Variant, d):
    kw = {}
    if v.epilogue == "layer_norm":
        kw = {"gamma": d["gamma"], "beta": d["beta"]}
    elif v.epilogue == "dropout_residual":
        kw = {"mask": d["mask"], "residual": d["residual"]}
    return tuned_matmul(d["x"], d["y"], variant=v, **kw)


def variant_cases(M: int = 256, N: int = 256, K: int = 256):
    """The enumerated space as ``kernels/parity.py`` Case objects —
    the same generate-and-verify loop, generated instead of
    hand-listed."""
    from ..kernels.parity import Case, rel_err

    def make(v):
        def run():
            d = _problem(M, N, K)
            ref = _reference(v.epilogue, d)
            got = _run_variant(v, d)
            return {"metric": "rel", "tol": _REL_TOL,
                    "value": rel_err(ref, got)}
        return Case("tuned_matmul", v.label, run)

    return [(v, make(v)) for v in enumerate_variants(M, N, K)]


def verify_variant(v: Variant, M=256, N=256, K=256) -> Dict[str, Any]:
    from ..kernels.parity import run_case
    for vv, case in variant_cases(M, N, K):
        if vv.label == v.label:
            return run_case(case)
    raise KeyError(v.label)


def search_variants(M: int = 256, N: int = 256, K: int = 256,
                    iters: int = 3) -> Dict[str, Any]:
    """Full loop: enumerate -> parity-admit -> rank by median ms.

    Returns {"interpret_mode", "considered", "admitted": [...],
    "winners": {epilogue: {bm,bn,bk,ms,rel_err}}} — the shape persisted
    under "kernel_variants" in the tuning cache.
    """
    from ..kernels import registry as kreg
    from ..kernels.parity import run_case
    considered = 0
    admitted: List[Dict[str, Any]] = []
    for v, case in variant_cases(M, N, K):
        considered += 1
        try:
            res = run_case(case)
        except Exception as exc:
            res = {"passed": False,
                   "error": f"{type(exc).__name__}: {exc}"[:200]}
        if not res.get("passed"):
            continue
        d = _problem(M, N, K)

        def fn(v=v, d=d):
            np.asarray(_run_variant(v, d))

        fn()  # warmup / compile
        ts = []
        for _ in range(max(1, iters)):
            t0 = time.perf_counter()
            fn()
            ts.append((time.perf_counter() - t0) * 1e3)
        admitted.append({**v.as_dict(),
                         "rel_err": res["value"],
                         "ms": round(sorted(ts)[len(ts) // 2], 3)})
    winners: Dict[str, Any] = {}
    for row in sorted(admitted, key=lambda r: (r["ms"], r["bm"],
                                               r["bn"], r["bk"])):
        winners.setdefault(row["epilogue"], row)
    return {"interpret_mode": kreg.interpret(),
            "problem": [M, N, K],
            "considered": considered,
            "admitted": admitted,
            "winners": winners}


def register_winner(winners: Dict[str, Any]) -> Optional[str]:
    """Make the plain-GEMM winner live in the kernel registry.

    Only the "none" epilogue is routable today (the op lowerings
    dispatch single ops; fused-epilogue routing needs the one-pipeline
    refactor, ROADMAP item 5) — layer_norm / dropout+residual winners
    stay recorded in the cache for direct callers. Returns the
    registered kernel name, or None when nothing is routable.
    """
    row = (winners or {}).get("none")
    if not row:
        return None
    from ..kernels import registry as kreg
    v = Variant(int(row["bm"]), int(row["bn"]), int(row["bk"]), "none")

    def run(x, y, **_kw):
        return tuned_matmul(x, y, variant=v)

    def eligible(sig: "kreg.Signature") -> bool:
        if len(sig.shapes) != 2:
            return False
        a, b = sig.shapes
        if len(a) != 2 or len(b) != 2 or a[1] != b[0]:
            return False
        if a[0] % v.bm or a[1] % v.bk or b[1] % v.bn:
            return False
        if sig.numel < kreg.min_numel():
            return False
        return all(dt == "float32" for dt in sig.dtypes)

    kreg.register_kernel(
        "tuned_matmul", op_types=("mul", "matmul"),
        eligible=eligible, run=run,
        doc=f"autotuned f32 GEMM, blocks {v.bm}x{v.bn}x{v.bk} "
            f"(winner from the tuning-cache variant search)")
    return "tuned_matmul"
