"""Per-source device-TIME breakdown of a compiled step (VERDICT r4 #3).

Companion to hbm_breakdown (bytes): the traffic table proves what the
step READS/WRITES; this one proves where the step's device time GOES.
jax.profiler's chrome trace carries real per-fusion events on the
`/device:TPU:N` lane; each
event name is an HLO instruction name in the optimized module, whose
`metadata={source_file=..., source_line=...}` attributes it to the
framework source line that emitted it — the same mapping
hbm_breakdown uses for bytes, so the two tables share categories and
can be read side by side.

The reference's analogue is the per-op timeline of its profiler
(/root/reference/paddle/fluid/platform/profiler.cc) — here the unit is
the XLA fusion, the true unit of device scheduling on TPU.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os

from .hbm_breakdown import parse_entry_computation, categorize


def trace_step(run_step, steps=3, trace_dir="/tmp/paddle_tpu_timerep"):
    """Run `run_step()` under a jax profiler trace and return the path
    of the newest trace.json.gz produced."""
    import jax

    run_step()                      # warm (compile outside the trace)
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(steps):
            run_step()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no trace produced under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def device_events(trace_path):
    """[(name, total_us, count)] of complete events on the device
    "XLA Ops" lanes — the per-HLO-op level. The other device lanes
    ("Steps", "XLA Modules") are parent spans that would double-count,
    and "Steps" additionally includes host/dispatch idle gaps."""
    with gzip.open(trace_path) as f:
        tr = json.load(f)
    ev = tr["traceEvents"]
    dev_pids = {e["pid"] for e in ev
                if e.get("ph") == "M" and e.get("name") == "process_name"
                and "device:" in (e.get("args") or {}).get("name", "")
                and "CPU" not in e["args"]["name"]}
    op_lanes = {(e["pid"], e["tid"]) for e in ev
                if e.get("ph") == "M" and e.get("name") == "thread_name"
                and e["pid"] in dev_pids
                and e["args"].get("name") == "XLA Ops"}
    agg = collections.defaultdict(lambda: [0.0, 0])
    for e in ev:
        if e.get("ph") != "X" or \
                (e.get("pid"), e.get("tid")) not in op_lanes:
            continue
        a = agg[e.get("name", "")]
        a[0] += float(e.get("dur", 0.0))
        a[1] += 1
    return [(n, us, c) for n, (us, c) in agg.items()]


def breakdown(trace_path, hlo_text, steps, top=25):
    """Rows (category, ms_per_step, n_events, example) sorted desc, plus
    total device ms/step. Event names are matched to entry-computation
    instruction names; unmatched events (copies, infeed, ...) keep
    their raw name as the category."""
    instrs = {i.name: i for i in parse_entry_computation(hlo_text)}
    agg = collections.defaultdict(lambda: [0.0, 0, None])
    total_us = 0.0
    for name, us, count in device_events(trace_path):
        base = name.lstrip("%")
        instr = instrs.get(base)
        if instr is None:
            # fusion names sometimes carry a ".N" dedup suffix
            instr = instrs.get(base.rsplit(".", 1)[0])
        if instr is not None:
            cat = categorize(instr)
            example = instr.src or base
        else:
            cat = f"device:{base.split('.')[0]}"
            example = base
        a = agg[cat]
        a[0] += us
        a[1] += count
        if a[2] is None:
            a[2] = example
        total_us += us
    rows = sorted(((c, us / steps / 1e3, n, ex)
                   for c, (us, n, ex) in agg.items()),
                  key=lambda r: -r[1])
    return rows[:top], total_us / steps / 1e3


def report(trace_path, hlo_text, steps, label="step", top=25,
           file=None):
    import sys
    file = file or sys.stderr
    rows, total_ms = breakdown(trace_path, hlo_text, steps, top)
    print(f"# device-time breakdown — {label} "
          f"(sum of device-lane events: {total_ms:.1f} ms/step)",
          file=file)
    print(f"# {'category':<48} {'ms/step':>8} {'%':>6} {'#ev':>5}  "
          f"example", file=file)
    for cat, ms, n, ex in rows:
        pct = 100.0 * ms / total_ms if total_ms else 0.0
        print(f"# {cat:<48} {ms:8.2f} {pct:5.1f}% {n:5d}  "
              f"{(ex or '')[-58:]}", file=file)
    return rows, total_ms
