"""Read the comparison's numbers on many seeds in one process: the
program's (the lower reading of each limit), the control's — the reference
in float8 — and the planted faults' (the upper readings).

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 3]

Not part of a benchmark run. One compiled step serves every seed: the
session is re-seeded, not rebuilt. Set-up is long, so the program's dozen
seeds and the control's are read in one process; no measured window is
needed for a training cell's readings. Writes one JSON line per seed to
standard output and to chiprun_out/calibrate/<cell>.jsonl.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3,
                   help="how many of the seeds also read the control and "
                        "the faults")
    p.add_argument("--control-only", action="store_true",
                   help="read the control and the faults alone, without "
                        "the program")
    p.add_argument("--dump", action="store_true",
                   help="also write every leaf's readings of the control "
                        "seeds")
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from benchmark.lib import compare
    from benchmark.lib.cells import Cell
    from benchmark.lib.kind_train import PROOF_STEPS, Session
    cell = Cell(args.workload)
    if not args.rehearse_cpu and jax.default_backend() != "tpu":
        print(f"no TPU: JAX found {jax.devices()}", file=sys.stderr)
        return 3
    dev = jax.devices()[0]
    fam = cell.family
    sz = fam.sizes(cell.config, args.rehearse_cpu)
    tr = fam.traffic(cell.traffic, args.rehearse_cpu)
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.join(ROOT, "chiprun_out", "calibrate"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", "calibrate",
                        cell.name + ".jsonl")
    sess = None if args.control_only else \
        Session(fam, sz, tr, seeds[0], args.rehearse_cpu)
    half = list(range(tr["batch"] // 2, tr["batch"]))
    with open(path, "a") as log:
        for k, seed in enumerate(seeds):
            pool = fam.make_pool(sz, tr, seed)
            t = time.perf_counter()
            ref = fam.run_reference(sz, tr, pool, seed, PROOF_STEPS)
            row = {"cell": cell.name, "seed": seed,
                   "platform": dev.platform, "kind": dev.device_kind,
                   "rehearsal": bool(args.rehearse_cpu),
                   "ref_losses": ref["losses"],
                   "reference_s": time.perf_counter() - t}
            if sess is not None:
                t = time.perf_counter()
                if k:
                    sess.reseed(seed)
                got = sess.prove()
                row.update(losses=got["losses"],
                           program_s=time.perf_counter() - t)
                row["program"], row["program_where"] = compare.gaps(got, ref)
            if k < args.control_seeds:
                t = time.perf_counter()
                ctl = fam.run_reference(sz, tr, pool, seed, PROOF_STEPS,
                                        precision="fp8")
                row["control_s"] = time.perf_counter() - t
                row["control_fp8"], _ = compare.gaps(ctl, ref)
                fwd = fam.run_reference(sz, tr, pool, seed, PROOF_STEPS,
                                        precision="fp8_mm")
                row["control_fp8_mm"], _ = compare.gaps(fwd, ref)
                bad = fam.run_reference(sz, tr, pool, seed, PROOF_STEPS,
                                        rows=half)
                row["fault_half_batch"], _ = compare.gaps(bad, ref)
                if args.dump:
                    with open(path[:-6] + f".readings.{seed}.json",
                              "w") as f:
                        json.dump({k: {a: b for a, b in v.items()
                                       if a != "grad_sample"}
                                   for k, v in (("reference", ref),
                                                ("fp8", ctl),
                                                ("fp8_mm", fwd),
                                                ("half_batch", bad))}, f)
            line = json.dumps(row)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
