"""Quantized matmul Pallas kernel (int8 / bf16) with per-tile scales.

Inference-shaped programs spend their FLOPs in ``mul``/``matmul`` GEMMs
whose weights tolerate reduced precision.  This kernel computes
C = A @ B on a (M/128, N/128, K/128) grid with the K axis innermost
("arbitrary" = sequential), quantizing each 128x128 operand tile
on the fly:

* mode "int8": per-tile symmetric scale s = max|tile| / 127, tiles
  rounded to int8, int8 x int8 -> int32 on the MXU, accumulated as
  f32 * (s_a * s_b).  Per-TILE scales (not per-tensor) keep the error
  local: one outlier only coarsens its own 128x128 block.
* mode "bf16": tiles cast to bf16, MXU dot with
  preferred_element_type=f32 — zero quantization bookkeeping, ~half
  the HBM traffic of the f32 path.

The f32 accumulator lives in VMEM scratch across K steps and is
flushed to the output block on the last K step.

Opt-in: this kernel changes numerics, so registry eligibility requires
``PT_KERNEL_QUANT_MATMUL=int8|bf16`` in the environment on top of the
usual gates (the env var is part of the engine trace cache key).
Shape eligibility: both operands 2-D f32/bf16 with M, K, N all
multiples of 128 — the op lowerings only consult the registry after
their own flattening/transposes have produced a plain 2-D GEMM.

Tolerance policy (kernels/parity.py): relative error vs the f32
baseline, 5e-2 for int8 and 1e-2 for bf16 on unit-scale data.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

_TILE = 128

__all__ = ["quantized_matmul", "quant_mode"]


def quant_mode() -> str:
    """Requested quantization mode ("" = kernel disabled). Read via
    the knob registry (a LOSSY knob — the autotuner only searches it
    under PT_TUNE_ALLOW_LOSSY=1)."""
    try:
        from ..tuning import knobs
        mode = str(knobs.value("kernel_quant_matmul") or "")
    except Exception:
        mode = os.environ.get("PT_KERNEL_QUANT_MATMUL", "")
    mode = mode.strip().lower()
    return mode if mode in ("int8", "bf16") else ""


def _qmm_block(x_ref, y_ref, o_ref, acc_ref, *, n_k, mode):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    xb = x_ref[:].astype(jnp.float32)
    yb = y_ref[:].astype(jnp.float32)
    if mode == "int8":
        sx = jnp.maximum(jnp.max(jnp.abs(xb)), 1e-30) / 127.0
        sy = jnp.maximum(jnp.max(jnp.abs(yb)), 1e-30) / 127.0
        xq = jnp.clip(jnp.round(xb / sx), -127, 127).astype(jnp.int8)
        yq = jnp.clip(jnp.round(yb / sy), -127, 127).astype(jnp.int8)
        prod = jax.lax.dot(xq, yq,
                           preferred_element_type=jnp.int32)
        acc_ref[:] += prod.astype(jnp.float32) * (sx * sy)
    else:  # bf16
        acc_ref[:] += jax.lax.dot(xb.astype(jnp.bfloat16),
                                  yb.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[:] = acc_ref[:]


def quantized_matmul(x, y, *, mode=None, out_dtype=None):
    """C = x @ y with on-the-fly per-tile quantization.

    x: [M, K], y: [K, N], M/K/N multiples of 128.  Returns f32 unless
    ``out_dtype`` is given.
    """
    mode = mode or quant_mode() or "bf16"
    M, K = x.shape
    K2, N = y.shape
    assert K == K2, (x.shape, y.shape)
    assert M % _TILE == 0 and K % _TILE == 0 and N % _TILE == 0, (
        x.shape, y.shape)
    n_k = K // _TILE
    out = pl.pallas_call(
        functools.partial(_qmm_block, n_k=n_k, mode=mode),
        name="quantized_matmul",
        grid=(M // _TILE, N // _TILE, n_k),
        in_specs=[
            pl.BlockSpec((_TILE, _TILE), lambda i, j, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE, _TILE), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_TILE, _TILE),
                               lambda i, j, k: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_TILE, _TILE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=registry.interpret(),
    )(x, y)
    if out_dtype is not None and out.dtype != out_dtype:
        out = out.astype(out_dtype)
    return out


def _qmm_eligible(sig: registry.Signature) -> bool:
    if not quant_mode():
        return False
    if len(sig.shapes) != 2:
        return False
    (a, b) = sig.shapes
    if len(a) != 2 or len(b) != 2 or a[1] != b[0]:
        return False
    if any(d % _TILE for d in (a[0], a[1], b[1])):
        return False
    return all(dt in ("float32", "bfloat16") for dt in sig.dtypes)


registry.register_kernel(
    "quantized_matmul", op_types=("mul", "matmul"),
    eligible=_qmm_eligible, run=quantized_matmul,
    doc="per-tile int8/bf16 GEMM for inference-shaped programs; "
        "opt-in via PT_KERNEL_QUANT_MATMUL=int8|bf16, 2-D operands "
        "with 128-multiple dims")
