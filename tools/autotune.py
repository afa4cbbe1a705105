"""Offline feedback-directed autotune CLI (docs/TUNING.md).

Runs the same cache-or-search loop ``FLAGS_autotune`` runs at a
program's first training step — but ahead of time, so production jobs
start from a warm tuning cache and pay ZERO trials::

    # search on the built-in training-step model (lint_program's
    # book MLP), persist the winner
    python tools/autotune.py --cache-dir /ckpt/tuning

    # tune a serialized inference model (save_inference_model dir)
    python tools/autotune.py --model /path/to/model_dir

    # include lossy knobs, custom search shape, machine-readable out
    python tools/autotune.py --allow-lossy --budgets 2,6 --rounds 2 \
        --knobs sched_lanes,allreduce_bucket_mb --json

A second invocation against the same cache dir reports the pure cache
hit (``--force`` deletes the entry first to re-search). ``--variants``
additionally runs the Pallas kernel variant search (parity-gated block
shapes + epilogue fusions, tuning/variants.py) and persists the
winners alongside the knob config.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _synth_feed(program, batch):
    """Random feed dicts for a loaded model's data vars (batch dim -1
    resolved to --batch)."""
    from paddle_tpu.core.types import dtype_to_np
    rng = np.random.RandomState(0)
    feed = {}
    for var in program.global_block().vars.values():
        if not getattr(var, "is_data", False):
            continue
        shape = [batch if int(d) < 0 else int(d) for d in var.shape]
        np_dt = dtype_to_np(var.dtype)
        if np.issubdtype(np_dt, np.floating):
            feed[var.name] = rng.rand(*shape).astype(np_dt)
        else:
            feed[var.name] = rng.randint(0, 2, shape).astype(np_dt)
    return feed


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", default=None, metavar="DIR",
                   help="serialized inference-model dir "
                        "(save_inference_model); default: the built-in "
                        "MLP training step")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--seed", type=int, default=None,
                   help="search seed (default PT_TUNE_SEED or 0)")
    p.add_argument("--budgets", default=None, metavar="N,N",
                   help="successive-halving step budgets "
                        "(default PT_TUNE_BUDGETS or 2,5)")
    p.add_argument("--rounds", type=int, default=None,
                   help="coordinate-descent rounds "
                        "(default PT_TUNE_ROUNDS or 2)")
    p.add_argument("--knobs", default=None, metavar="NAME,NAME",
                   help="restrict the searched knob axes")
    p.add_argument("--allow-lossy", action="store_true",
                   help="search lossy knobs too (quantized allreduce, "
                        "quantized matmul) — changes numerics")
    p.add_argument("--cache-dir", default=None, metavar="DIR",
                   help="tuning cache dir (default PT_TUNING_CACHE_DIR "
                        "or ~/.cache/paddle_tpu/tuning)")
    p.add_argument("--variants", action="store_true",
                   help="also run the Pallas kernel variant search and "
                        "persist the parity-gated winners")
    p.add_argument("--force", action="store_true",
                   help="drop any existing cache entry first (re-search)")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)

    for opt, env in ((args.cache_dir, "PT_TUNING_CACHE_DIR"),
                     (args.budgets, "PT_TUNE_BUDGETS"),
                     (args.knobs, "PT_TUNE_KNOBS")):
        if opt is not None:
            os.environ[env] = str(opt)
    if args.rounds is not None:
        os.environ["PT_TUNE_ROUNDS"] = str(args.rounds)
    if args.seed is not None:
        os.environ["PT_TUNE_SEED"] = str(args.seed)
    if args.allow_lossy:
        os.environ["PT_TUNE_ALLOW_LOSSY"] = "1"
    if args.variants:
        os.environ["PT_TUNE_VARIANTS"] = "1"

    import paddle_tpu as fluid
    from paddle_tpu.core.engine import Engine
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.tuning import cache, driver, state

    if args.model:
        scope = Scope()
        with fluid.scope_guard(scope):
            program, feed_names, fetch_vars = \
                fluid.io.load_inference_model(args.model,
                                              fluid.Executor())
        feed = _synth_feed(program, args.batch)
        missing = [n for n in feed_names if n not in feed]
        if missing:
            print(f"autotune: no data-var shape for feed {missing}",
                  file=sys.stderr)
            return 2
        fetch = [v.name for v in fetch_vars]
        eng = Engine()
    else:
        from tools.lint_program import build_model
        program, startup, _, loss = build_model("mlp")
        scope = Scope()
        with fluid.scope_guard(scope):
            fluid.Executor().run(startup)
        feed = _synth_feed(program, args.batch)
        fetch = [loss.name]
        eng = Engine()

    if args.force:
        path = cache.path_for(
            cache.cache_key(cache.content_fingerprint(program)))
        if os.path.exists(path):
            os.remove(path)

    with fluid.scope_guard(scope):
        info = driver.autotune_for_run(eng, program, scope, None,
                                       feed, fetch)
    info["applied_token"] = state.applied_token()
    info["cache_dir"] = cache.cache_dir()
    if args.json:
        print(json.dumps(info, sort_keys=True))
    else:
        print(f"# autotune[{info['source']}]: {info['trials']} trial(s)"
              f", objective "
              f"{info['objective_ms'] if info['objective_ms'] is None else round(info['objective_ms'], 3)}"
              f" ms, config {info['config']}")
        print(f"# entry: {info['path']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
