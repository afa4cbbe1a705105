"""Compile a BASELINE bench config's training step and print its
HBM-traffic-by-source table (paddle_tpu.tools.hbm_breakdown).

Usage: python tools/traffic_report.py [transformer|resnet50] [--dump FILE]

This is the auditable input behind a traffic-by-category table
(VERDICT r3 #1): it compiles the exact step bench.py times, asks
XLA for cost/memory analysis, and attributes the optimized HLO's bytes
to framework source lines.
"""
from __future__ import annotations

import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_transformer(batch=96, s=128, vocab=32000):
    import paddle_tpu as fluid
    from paddle_tpu import models

    cfg = models.transformer.transformer_base(
        src_vocab_size=vocab, trg_vocab_size=vocab, dropout=0.1,
        fuse_attention=True)
    fluid.framework.unique_name.reset()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        cost, logits, feed_names = models.transformer_train(cfg)
        opt = fluid.optimizer.AdamOptimizer(learning_rate=2e-4)
        opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(cost)
    batch_d = models.transformer.make_batch(cfg, batch, s, s)
    return main_prog, startup, batch_d, [cost.name]


def build_resnet50(batch=None):
    batch = batch or int(os.environ.get("RN_BATCH", "128"))
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.framework.unique_name.reset()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        cost, acc, feeds = models.resnet_train(depth=50)
        opt = fluid.optimizer.MomentumOptimizer(0.1, 0.9)
        opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(cost)
    rng = np.random.RandomState(0)
    batch_d = {"image": rng.rand(batch, 3, 224, 224).astype(np.float32),
               "label": rng.randint(0, 1000, (batch, 1)).astype(np.int64)}
    return main_prog, startup, batch_d, [cost.name]


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "transformer"
    import paddle_tpu as fluid
    from paddle_tpu.core.engine import Engine
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.tools import hbm_breakdown

    if which == "transformer":
        prog, startup, batch, fetch = build_transformer()
    else:
        prog, startup, batch, fetch = build_resnet50()
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        eng = Engine()
        eng.run(prog, scope, None, batch, fetch, return_numpy=False)
        stats = eng.compiled_stats(prog, scope, batch, fetch)
        compiled = eng.compiled_step(prog, scope, batch, fetch)
        if compiled is None:
            print("# nothing compiled (eager-interpreter "
                  "fallback) — no report", file=sys.stderr)
            return
        hlo = compiled.as_text()
        if "--dump" in sys.argv:
            path = sys.argv[sys.argv.index("--dump") + 1]
            with open(path, "w") as f:
                f.write(hlo)
            print(f"# HLO dumped to {path}", file=sys.stderr)
        print(f"# cost_analysis: flops={stats['flops']/1e12:.3f} T  "
              f"bytes={stats['bytes_accessed']/1e9:.2f} GB  "
              f"temp={stats.get('temp_bytes', 0)/1e9:.2f} GB",
              file=sys.stderr)
        hbm_breakdown.report(hlo, stats.get("bytes_accessed"),
                             label=which, top=30)


if __name__ == "__main__":
    main()
