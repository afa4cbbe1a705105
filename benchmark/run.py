"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. A cell is a name in BENCHMARK.json; its
configuration, traffic, limits, family and per-layer readers are files
found by name (benchmark/lib/cells.py). Without a TPU, or with fewer chips
than the cell asks for, the command exits non-zero and prints no result.
`--rehearse-cpu` is the explicit rehearsal: the family's tiny sizes on the
CPU backend, every line labelled, nothing it prints a device result.

Every line names platform, device_kind and the device count. The last
line of standard output is the result object; the numbers compared, each
beside its limit, are its last key and the last lines of standard error.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()          # set-up is counted from process start

import argparse                    # noqa: E402
import importlib                   # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import sys                         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny sizes on the CPU backend; labelled, never a "
                        "device result")
    return p.parse_args(argv)


def run_cell(cell, seed, seconds, trace, rehearsal, t0, session_hook=None,
             out=sys.stdout, err=sys.stderr):
    """Drive one cell on whatever devices JAX has (the caller has looked
    for the chip). Returns the result object after printing it."""
    import jax
    from benchmark.lib import cells as cells_lib
    from benchmark.lib import peaks as peaks_lib
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips}
    tag = (f"[platform={dev.platform} device_kind={dev.device_kind!r} "
           f"devices={len(jax.devices())}]"
           + (" [CPU REHEARSAL - not a device result]" if rehearsal else ""))

    def say(msg, file=out):
        print(f"{tag} t+{time.perf_counter() - t0:.1f}s {msg}", file=file,
              flush=True)

    kind = importlib.import_module(
        "benchmark.lib.kind_" + cell.family.KIND)
    say(f"cell {cell.name}: config {cell.row['config']} traffic "
        f"{cell.row['traffic']} seed {seed} seconds {seconds} trace {trace}")
    res = kind.run(cell, seed, seconds, trace, rehearsal, t0, say,
                   session_hook=session_hook)

    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    metrics, breakdown = {}, None
    if trace:
        from benchmark.lib import trace as trace_lib
        tr = res["traced"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        ctx = {"trace": tr, "steps": tr["steps"], "window_s": tr["window_s"],
               "family": cell.family, "sizes": res["sizes"],
               "traffic": res["traffic"], "chips": cell.chips,
               "routing": res["routing"], "trace_lib": trace_lib,
               "peaks": None if rehearsal else peaks_lib.peaks(
                   dev.device_kind)}
        for m in cell.per_layer():
            value = cells_lib.layer_metric_reader(m["name"])(ctx)
            if value is None:
                say(f"per-layer {m['name']}: nothing to read here")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {
            "device_ops": trace_lib.top(tr["by_category_s"]),
            "idle_gaps": trace_lib.top(tr["idle_gaps_s"])}
        say(f"traced {tr['steps']} steps: window {tr['window_s']:.4f} s, "
            f"device busy {tr['busy_s']:.4f} s")
    else:
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": res["end_to_end"][m["name"]],
                                  "unit": m["unit"]}

    result = {"correct": res["correct"], "attempted": res["attempted"],
              "failed": res["failed"], "metrics": metrics, "device": device}
    if rehearsal:
        result["rehearsal"] = "CPU REHEARSAL - not a device result"
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = res["compared"]
    for name, c in res["compared"].items():
        say(f"compared {name} {c['value']:.6g} limit {c['limit']:.6g} "
            f"{'ok' if c['value'] <= c['limit'] else 'OVER'}", file=err)
    say(f"correct {result['correct']}", file=err)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None):
    args = _parse(argv)
    from benchmark.lib.cells import Cell
    cell = Cell(args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    found = [(d.platform, d.device_kind) for d in jax.devices()]
    if not args.rehearse_cpu and (
            jax.default_backend() != "tpu" or len(found) < cell.chips):
        print(f"benchmark/run.py: cell {cell.name!r} needs {cell.chips} TPU "
              f"chip(s); JAX found backend {jax.default_backend()!r} with "
              f"devices {found}. No result. (--rehearse-cpu rehearses the "
              f"harness on the CPU.)", file=sys.stderr)
        return 3
    run_cell(cell, args.seed, args.seconds, args.trace, args.rehearse_cpu,
             _T0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
