"""Per-op microbenchmark harness.

Parity: reference config-driven single-op timer
(/root/reference/paddle/fluid/operators/benchmark/op_tester.cc,
op_tester_config.cc) — time any registered op's lowering standalone.
TPU-native: the op is compiled as a one-op XLA executable through the
normal engine path and timed with bench.py's fetch-fenced,
overhead-cancelling discipline (close every window with a host fetch,
difference two window sizes to cancel the constant overhead). Reports
steps/s, analytical FLOPs from the compiled executable's cost analysis,
implied TFLOP/s and, on a TPU, MFU against the chip's peak.

Usage:
    python -m paddle_tpu.tools.op_bench --op softmax --shape 96,128,512
    python -m paddle_tpu.tools.op_bench --op matmul \\
        --inputs "X=512,512;Y=512,512"
    python -m paddle_tpu.tools.op_bench --op fused_attention \\
        --inputs "Q=4,8,512,64;K=4,8,512,64;V=4,8,512,64" \\
        --attrs "scale=0.125"
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _parse_shape(s):
    return [int(v) for v in s.split(",") if v]


def _parse_inputs(spec):
    out = {}
    for part in spec.split(";"):
        if not part:
            continue
        name, shape = part.split("=")
        out[name] = _parse_shape(shape)
    return out


def _parse_attrs(spec):
    attrs = {}
    for part in (spec or "").split(";"):
        if not part:
            continue
        k, v = part.split("=", 1)
        for cast in (int, float):
            try:
                attrs[k] = cast(v)
                break
            except ValueError:
                continue
        else:
            attrs[k] = {"true": True, "false": False}.get(v.lower(), v)
    return attrs


def _rand(shape, dtype, rng):
    if np.issubdtype(np.dtype(dtype), np.integer):
        return rng.randint(0, 8, shape).astype(dtype)
    return rng.standard_normal(shape).astype(dtype)


_IN_CANDIDATES = (("X",), ("Input",), ("X", "Y"))
_OUT_CANDIDATES = ("Out", "Output", "Y", "Loss")


def bench_op(op_type, inputs=None, shape=None, attrs=None,
             dtype="float32", out_slot=None, iters=30, warmup=3):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core.engine import Engine
    from paddle_tpu.core.scope import Scope

    rng = np.random.RandomState(0)
    attrs = attrs or {}

    def build(slot_shapes, out_name):
        fluid.framework.unique_name.reset()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            b = main.global_block()
            feeds = {}
            in_map = {}
            for slot, shp in slot_shapes.items():
                var = f"in_{slot}"
                b.create_var(name=var, shape=list(shp), dtype=dtype)
                feeds[var] = _rand(shp, dtype, rng)
                in_map[slot] = [var]
            b.create_var(name="bench_out", shape=[1], dtype=dtype)
            b.append_op(type=op_type, inputs=in_map,
                        outputs={out_name: ["bench_out"]},
                        attrs=dict(attrs), infer_shape=False)
        return main, startup, feeds

    trials = []
    if inputs:
        trials = [(inputs, o) for o in
                  ([out_slot] if out_slot else _OUT_CANDIDATES)]
    else:
        assert shape, "--shape or --inputs required"
        for slots in _IN_CANDIDATES:
            slot_shapes = {s: shape for s in slots}
            for o in ([out_slot] if out_slot else _OUT_CANDIDATES):
                trials.append((slot_shapes, o))

    last_err = None
    for slot_shapes, out_name in trials:
        main, startup, feeds = build(slot_shapes, out_name)
        scope = Scope()
        try:
            with fluid.scope_guard(scope):
                exe = fluid.Executor()
                exe.run(startup)
                eng = Engine()
                out = eng.run(main, scope, None, feeds,
                              ["bench_out"], return_numpy=False)
            break
        except Exception as exc:  # try the next slot layout
            last_err = exc
    else:
        raise SystemExit(
            f"op_bench: could not run op {op_type!r} with any candidate "
            f"slot layout; pass --inputs/--out explicitly. Last error: "
            f"{last_err}")

    def _arr(o):
        return o.array if hasattr(o, "array") else o

    with fluid.scope_guard(scope):
        feeds_dev = {k: jax.device_put(np.asarray(v))
                     for k, v in feeds.items()}
        for _ in range(warmup):
            out = eng.run(main, scope, None, feeds_dev, ["bench_out"],
                          return_numpy=False)
        np.asarray(_arr(out[0]))

        def window(n):
            t0 = time.perf_counter()
            for _ in range(n):
                o = eng.run(main, scope, None, feeds_dev,
                            ["bench_out"], return_numpy=False)
            np.asarray(_arr(o[0]))  # fetch fence
            return time.perf_counter() - t0

        t1 = window(iters)
        t2 = window(2 * iters)
        if t2 - t1 > 0.02 * t2:
            sps = iters / (t2 - t1)
        else:
            sps = 3 * iters / (t1 + t2)
        stats = eng.compiled_stats(main, scope, feeds_dev,
                                   ["bench_out"])

    flops = float(stats["flops"]) if stats else 0.0
    if flops < 0:
        # XLA reports unknown costs (e.g. Pallas custom calls) as -1/-2
        flops = 0.0
    tflops = flops * sps / 1e12
    dev = jax.devices()[0]
    kind = dev.device_kind
    rec = {
        "op": op_type,
        # slot_shapes/out_name are the candidate layout that actually
        # SUCCEEDED in the trial loop (an earlier candidate may have
        # failed), so the record names what was really benchmarked
        "inputs": {k: list(v) for k, v in slot_shapes.items()},
        "out_slot": out_name,
        "dtype": dtype,
        "steps_per_sec": round(sps, 2),
        "flops_per_step": flops,
        "implied_tflops": round(tflops, 3),
        "platform": dev.platform,
        "device": kind,
    }
    if dev.platform == "tpu":
        # a fraction of the MXU peak means something on a TPU only; an
        # unlisted device_kind raises
        from ..observability.attribution import peak_tflops
        rec["mfu_pct"] = round(100.0 * tflops / peak_tflops(kind), 2)
    if stats and "bytes_accessed" in stats:
        rec["bytes_accessed"] = stats["bytes_accessed"]
    return rec


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--op", required=True)
    p.add_argument("--shape", type=_parse_shape, default=None,
                   help="comma-separated dims for the primary input")
    p.add_argument("--inputs", type=_parse_inputs, default=None,
                   help='explicit slots: "X=2,3;Y=3,4"')
    p.add_argument("--attrs", type=_parse_attrs, default=None,
                   help='op attrs: "axis=-1;use_cudnn=false"')
    p.add_argument("--dtype", default="float32")
    p.add_argument("--out", dest="out_slot", default=None)
    p.add_argument("--iters", type=int, default=30)
    args = p.parse_args(argv)
    rec = bench_op(args.op, inputs=args.inputs, shape=args.shape,
                   attrs=args.attrs, dtype=args.dtype,
                   out_slot=args.out_slot, iters=args.iters)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
