"""Read the comparison's numbers on many seeds in one process, the
program FIRST and the reference AFTER the session is closed.

    python3 benchmark/calibrate_after.py --workload <cell> --seeds 1,2,3 [--control-seeds 3]

`calibrate.py` holds the session and the reference on the chip together
and plants its fault by dropping half of the batch's rows. A cell whose
state and reference do not fit the chip side by side (kanana2_s4096: 9.2
GB of weights, gradients and Adam state a side), or whose batch is ONE
sequence, is read here instead: every seed's three proof steps through
one session (re-seeded, not rebuilt), the readings taken to the host,
the session closed; then every seed's reference, and on the first
`--control-seeds` the control (the reference in float8) and the faults a
family's `run_reference` takes by name (`fault=`): half of every
sequence's positions left out of the loss, the mean taken over the rest;
the chosen experts' weights left un-normalised.

Where the family's reference returns the routers' choices of step 1
(`first_choices`) the program's are fetched too, once, on the first seed
(`--choices`: a second session after the first is closed, whose step has
the choices among its fetches and so compiles again; it is asked for),
and the (token, expert) choices that differ are counted. Not part of a
benchmark run. Writes one JSON line per seed to standard output and to
chiprun_out/calibrate/<cell>.jsonl.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FAULTS = ("half_positions", "unnormalised_topk")


def _program_choices(sess):
    """The routers' choices of the session's first step from the seed's
    weights, int32 [MoE layers, tokens, top-k], or None where the program
    has no router: the training step with the choices among its
    fetches."""
    import numpy as np
    block = sess.main.global_block()
    names = [op.output("TopkIdx")[0] for op in block.ops
             if op.type == "moe_router"]
    if not names:
        return None
    with sess.fluid.scope_guard(sess.scope):
        out = sess.exe.run(sess.main, feed=sess.pool[0],
                           fetch_list=[sess.cost]
                           + [block.var(n) for n in names])
    return np.stack([np.asarray(a) for a in out[1:]])


def _reseed(sess, seed):
    """`Session.reseed`, the old state freed first: the new seed's
    weights and optimizer state beside the old would be twice the state
    (13.8 GB in kanana2_s4096) and leave no room for the step's scratch."""
    import jax.numpy as jnp
    # the engine holds the last step's updated arrays until it is asked
    # to wait for them
    sess.exe.synchronize()
    for n in list(sess.family.fresh_optimizer_state(sess.sz, sess.names)) \
            + list(sess.names):
        sess.scope.find_var(n).set_value(jnp.zeros((), jnp.float32))
    sess.reseed(seed)


def _differing(got, ref):
    """(token, expert) choices of `got` that `ref` did not make, and how
    many choices there are."""
    import numpy as np
    missing = ~(got[..., :, None] == ref[..., None, :]).any(-1)
    return int(missing.sum()), int(np.prod(got.shape))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--choices", action="store_true",
                   help="also fetch the program's step-1 choices on the "
                        "first seed (compiles the step a second time)")
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

    t = time.perf_counter()
    sess = Session(fam, sz, tr, seeds[0], args.rehearse_cpu)
    program, seconds, choices = {}, {}, None
    for k, seed in enumerate(seeds):
        t = time.perf_counter()
        if k:
            _reseed(sess, seed)
        program[seed] = sess.prove()
        seconds[seed] = time.perf_counter() - t
        print(f"program seed {seed}: losses {program[seed]['losses']} in "
              f"{seconds[seed]:.1f} s", file=sys.stderr, flush=True)
    sess.close()
    del sess
    gc.collect()
    if args.choices:
        sess = Session(fam, sz, tr, seeds[0], args.rehearse_cpu)
        choices = _program_choices(sess)
        sess.close()
        del sess
        gc.collect()

    with open(path, "a") as log:
        for k, seed in enumerate(seeds):
            pool = fam.make_pool(sz, tr, seed)
            t = time.perf_counter()
            ref = fam.run_reference(sz, tr, pool, seed, PROOF_STEPS)
            row = {"cell": cell.name, "seed": seed,
                   "platform": dev.platform, "kind": dev.device_kind,
                   "rehearsal": bool(args.rehearse_cpu),
                   "ref_losses": ref["losses"],
                   "reference_s": time.perf_counter() - t,
                   "losses": program[seed]["losses"],
                   "program_s": seconds[seed]}
            row["program"], row["program_where"] = compare.gaps(
                program[seed], ref)
            if k == 0 and choices is not None and "first_choices" in ref:
                row["choices_differing"], row["choices"] = _differing(
                    choices, ref["first_choices"])
            if k < args.control_seeds:
                for name in ("fp8", "fp8_mm"):
                    t = time.perf_counter()
                    ctl = fam.run_reference(sz, tr, pool, seed, PROOF_STEPS,
                                            precision=name)
                    row[f"control_{name}"], _ = compare.gaps(ctl, ref)
                    row[f"control_{name}_s"] = time.perf_counter() - t
                    if "first_choices" in ctl:
                        row[f"control_{name}_choices_differing"] = \
                            _differing(ctl["first_choices"],
                                       ref["first_choices"])[0]
                for fault in FAULTS:
                    bad = fam.run_reference(sz, tr, pool, seed, PROOF_STEPS,
                                            fault=fault)
                    row[f"fault_{fault}"], _ = compare.gaps(bad, ref)
            line = json.dumps(row)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
