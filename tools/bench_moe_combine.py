"""Micro-benchmark of the expert layer's combine alone, on the chip: the
`[rows, D]` row buffer -> the `[T, D]` float32 sum of each token's held
rows, at the three decoder cells' shapes and every rung of their prefix
ladders, bf16 rows (the forward's) and float32 rows (the backward's).

Forms: `gather` (ops/decoder.py `_combine`: every choice of every token
gathers a row), `kernel` (kernels/grouped_matmul.py `combine`: the Pallas
kernel `moe_combine` over the prefix's rows), `scatter` and
`scatter_drop` (XLA's own scatter-add from the prefix, padding rows
masked to zero or sent out of bounds; measured, not kept:
docs/KERNELS.md). ms a call, the least of three batches of back-to-back
calls.

    chiprun -- python tools/bench_moe_combine.py [out.json]
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
from paddle_tpu.kernels import grouped_matmul as gm  # noqa: E402
from paddle_tpu.ops import decoder  # noqa: E402

# cell: tokens, top_k, experts held, experts, width
CELLS = {"kanana2_s4096": (4096, 6, 16, 128, 2048),
         "keye2_s8192": (8192, 8, 16, 128, 2048),
         "twotower_s4096": (4096, 6, 8, 128, 2688)}
CALLS, BATCHES = 20, 3


def routing(rng, n_choices, held, rows_in_use):
    """int32 [n_choices]: `rows_in_use` choices spread over the held
    experts, the rest held elsewhere (-1), shuffled."""
    local = np.full(n_choices, -1, np.int32)
    local[:rows_in_use] = rng.integers(0, held, rows_in_use)
    return jnp.asarray(rng.permutation(local))


def scatter(buf, plan, t, top_k, drop):
    tok = plan["choice_of_row"] // top_k
    if drop:
        tok = jnp.where(plan["valid"], tok, t)
        return jnp.zeros((t, buf.shape[1]), jnp.float32).at[tok].add(
            buf.astype(jnp.float32), mode="drop")
    rows = jnp.where(plan["valid"][:, None], buf, 0).astype(jnp.float32)
    return jnp.zeros((t, buf.shape[1]), jnp.float32).at[tok].add(rows)


def forms(t, top_k, rows):
    def prefix(fn):
        return lambda buf, plan: fn(buf, gm.prefix_plan(plan, rows))
    return {
        "gather": lambda buf, plan: decoder._combine(buf, plan, t, top_k),
        "kernel": prefix(lambda buf, p: gm.combine(buf, p, t, top_k)),
        "scatter": prefix(lambda buf, p: scatter(buf, p, t, top_k, False)),
        "scatter_drop": prefix(
            lambda buf, p: scatter(buf, p, t, top_k, True)),
    }


def ms_a_call(fn, *args):
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(BATCHES):
        start = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        out.block_until_ready()
        best = min(best, (time.perf_counter() - start) / CALLS)
    return best * 1e3


def main(argv):
    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)
    rng = np.random.default_rng(38)
    results = []
    for cell, (t, top_k, held, experts, d) in CELLS.items():
        ladder = gm.prefix_rows(t * top_k, held, experts)
        edge = 0
        for rows in ladder:
            # uniform routing at the first rung; past it, the middle of
            # the rows only this rung holds
            in_use = t * top_k * held // experts if not edge \
                else min((edge + rows) // 2 - held * gm.TILE_ROWS // 2,
                         t * top_k)
            plan = gm.plan_rows(routing(rng, t * top_k, held, in_use), held)
            tiles = int(plan["n_active"][0])
            assert edge < tiles * gm.TILE_ROWS <= rows, (cell, rows, tiles)
            edge = rows
            for dtype in (jnp.bfloat16, jnp.float32):
                buf = jnp.asarray(rng.standard_normal((rows, d)), dtype)
                want = None
                for form, fn in forms(t, top_k, rows).items():
                    fn = jax.jit(fn)
                    try:
                        got = fn(buf, plan)
                        ms = ms_a_call(fn, buf, plan)
                    except Exception as e:  # a form the chip refuses
                        print(cell, rows, form, "FAILED", str(e)[:300],
                              flush=True)
                        continue
                    want = got if want is None else want
                    line = {"cell": cell, "T*k": t * top_k, "rows": rows,
                            "rows_in_use": tiles * gm.TILE_ROWS,
                            "dtype": jnp.dtype(dtype).name, "form": form,
                            "ms": round(ms, 4), "max_abs_from_gather":
                            float(jnp.max(jnp.abs(got - want)))}
                    results.append(line)
                    print(json.dumps(line), flush=True)
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            json.dump({"device": device.device_kind, "results": results}, f,
                      indent=1)


if __name__ == "__main__":
    main(sys.argv)
