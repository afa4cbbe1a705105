"""Micro-benchmark of the gated short convolution alone, on the chip: the
two Pallas kernels (`gated_short_conv_fwd` / `_bwd`,
kernels/short_conv.py) against their `jax.numpy` lowering at a cell's
shape — X [B, T, 3 D] bfloat16, a 3-tap filter — and how far the two are
apart. ms a call, the least of three batches of back-to-back calls.

    chiprun -- python tools/bench_short_conv.py [out.json]
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
from paddle_tpu.kernels import short_conv as sc  # noqa: E402

# cell: batch, tokens, hidden width, taps
CELLS = {"lfm2_s8192": (1, 8192, 2048, 3)}
ROWS = (128, 256, 512)
CALLS, BATCHES = 20, 3


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / CALLS)
    return best * 1e3


def gap(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def main(argv):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {jax.devices()}", file=sys.stderr)
        return 3
    rows_out = []
    for cell, (b, t, d, k) in CELLS.items():
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((b, t, 3 * d)), jnp.bfloat16)
        g = jnp.asarray(rng.standard_normal((b, t, d)), jnp.bfloat16)
        w = jnp.asarray(rng.uniform(-k ** -0.5, k ** -0.5, (d, k)),
                        jnp.float32)
        low_f, low_b = jax.jit(sc._lowered), jax.jit(sc._lowered_grad)
        row = {"cell": cell, "device_kind": dev.device_kind,
               "lowered_fwd_ms": timed(low_f, x, w),
               "lowered_bwd_ms": timed(low_b, x, w, g)}
        ref_f, (ref_dx, ref_dw) = low_f(x, w), low_b(x, w, g)
        for rows in ROWS:
            fwd = lambda x, w: sc._fwd_call(x, w, rows=rows)  # noqa: E731
            bwd = lambda x, w, g: sc._bwd_call(x, w, g, rows=rows)  # noqa: E731
            dx, dw = bwd(x, w, g)
            row[f"rows_{rows}"] = {
                "fwd_ms": timed(fwd, x, w), "bwd_ms": timed(bwd, x, w, g),
                "fwd_gap": gap(fwd(x, w), ref_f), "dx_gap": gap(dx, ref_dx),
                "dw_gap": gap(dw, ref_dw)}
        print(json.dumps(row), flush=True)
        rows_out.append(row)
    if len(argv) > 1:
        os.makedirs(os.path.dirname(os.path.abspath(argv[1])), exist_ok=True)
        with open(argv[1], "w") as f:
            json.dump(rows_out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
