"""Per-step host-overhead micro-benchmark for the Engine hot loop.

Reports how much of the synchronous 1-step wall time is HOST/dispatch
overhead rather than device work: overhead = sync 1-step latency minus
the device-pipeline bound (1 / pipelined steps-per-second, measured with
bench.py's overhead-cancelling double-window method). This is the number
the async dispatch pipeline (docs/ASYNC_DISPATCH.md) exists to shrink:
a perfectly overlapped loop pays ~0 ms of it.

CLI::

    python tools/step_overhead_bench.py [--json] [--async-dispatch]
        [--batch N] [--steps N] [--threshold-ms X] [--telemetry]
        [--compare-telemetry] [--compare-scheduler] [--compare-guard]
        [--compare-tuned] [--compare-memory] [--compare-integrity]
        [--compare-multistep] [--multistep-k K] [--compare-pipeline]

exits non-zero when measured host overhead exceeds ``--threshold-ms``
(the CI regression gate). ``overhead_report()`` is imported by bench.py
to emit the same accounting line alongside tokens/sec.

This bench is also the proof for the observability one-boolean
contract (docs/OBSERVABILITY.md): without ``--telemetry`` every
observability gate is forced OFF first, so the default run measures
the disabled path — ``tools/metrics_report.py --threshold-ms`` gates
on that number. ``--compare-telemetry`` measures both and reports the
enabled-path delta.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def overhead_report(name, sync_ms, sps, stats=None, counters=None):
    """One '#'-prefixed accounting line: host overhead per step =
    sync latency - pipelined bound. Returns None when inputs missing."""
    if not sync_ms or not sps:
        return None
    bound_ms = 1e3 / sps
    overhead = sync_ms - bound_ms
    line = (f"# {name}: per-step host overhead {overhead:.1f} ms "
            f"(sync {sync_ms:.1f} - pipelined bound {bound_ms:.1f})")
    if counters:
        line += (f"; steady-state counters: device_puts="
                 f"{counters.get('device_puts', 0)} "
                 f"sig_builds={counters.get('sig_builds', 0)} "
                 f"traces={counters.get('traces', 0)}")
    return line


def scheduler_overlap_report(sched):
    """(dict, '#'-line) for the bench JSON tail from a scheduler A/B
    probe result ({sync_ms_off, sync_ms_on, counters...}); (None, None)
    when the probe did not run or errored before measuring."""
    if not sched or "sync_ms_on" not in sched:
        return (sched or None), None
    off, on = sched["sync_ms_off"], sched["sync_ms_on"]
    pct = (1 - on / off) * 100 if off else 0.0
    c = sched.get("counters", {})
    line = (f"# scheduler_overlap: sync {off:.1f} -> {on:.1f} ms/step "
            f"({pct:+.0f}% vs scheduler-off); islands_concurrent="
            f"{c.get('islands_concurrent', 0)} pipeline_fill_frac="
            f"{c.get('pipeline_fill_frac', 0)} lane_idle="
            f"{c.get('lane_idle_ms', 0):.1f} ms")
    return sched, line


def guard_overhead_report(guard):
    """(dict, '#'-line) for the bench JSON tail from a stability-guard
    A/B probe result ({sync_ms_off, sync_ms_on, ...}); (None, None)
    when the probe did not run or errored before measuring."""
    if not guard or "sync_ms_on" not in guard:
        return (guard or None), None
    off, on = guard["sync_ms_off"], guard["sync_ms_on"]
    line = (f"# stability_guard: sync {off:.2f} -> {on:.2f} ms/step "
            f"(delta {on - off:+.3f} ms); host guard overhead "
            f"{guard.get('guard_host_ms_per_step', 0.0):.3f} ms/step, "
            f"ghosts={guard.get('ghost_snapshots', 0)} "
            f"anomalies={guard.get('anomalies', 0)}")
    return guard, line


def integrity_report(integ):
    """(dict, '#'-line) for the bench JSON tail from an integrity-
    sentinel A/B probe result ({sync_ms_off, sync_ms_on, ...});
    (None, None) when the probe did not run or errored before
    measuring."""
    if not integ or "sync_ms_on" not in integ:
        return (integ or None), None
    off, on = integ["sync_ms_off"], integ["sync_ms_on"]
    line = (f"# integrity_sentinel: sync {off:.2f} -> {on:.2f} ms/step "
            f"(delta {on - off:+.3f} ms); checks="
            f"{integ.get('integrity_checks', 0)} mismatches="
            f"{integ.get('integrity_mismatches', 0)}")
    return integ, line


def tuning_report(tun):
    """(dict, '#'-line) for the bench JSON tail from an autotune probe
    result; (None, None) when the probe did not run or errored before
    measuring."""
    if not tun or "source" not in tun:
        return (tun or None), None
    obj = tun.get("objective_ms")
    line = (f"# autotune[{tun['source']}]: {tun.get('trials', 0)} "
            f"trial(s), objective "
            f"{obj if obj is None else format(obj, '.3f')} ms/step, "
            f"tuned-vs-default delta "
            f"{tun.get('delta_ms') or 0.0:+.3f} ms")
    if "cache_hit_second_run" in tun:
        line += (f"; second run cache_hit="
                 f"{tun['cache_hit_second_run']}")
    return tun, line


def memory_report(mem):
    """(dict, '#'-line) for the bench JSON tail from a memory-census
    A/B probe result ({sync_ms_off, sync_ms_on, censuses, ...});
    (None, None) when the probe did not run or errored before
    measuring."""
    if not mem or "sync_ms_on" not in mem:
        return (mem or None), None
    off, on = mem["sync_ms_off"], mem["sync_ms_on"]
    cov = mem.get("coverage_frac")
    line = (f"# memory_observatory: sync {off:.2f} -> {on:.2f} ms/step "
            f"(delta {on - off:+.3f} ms); censuses="
            f"{mem.get('censuses', 0)} coverage="
            f"{cov if cov is None else format(cov, '.2f')} live="
            f"{mem.get('live_bytes', 0)} B")
    return mem, line


def mesh_report(mesh):
    """(dict, '#'-line) for the bench JSON tail from a named-mesh A/B
    probe result ({sync_ms_off, sync_ms_on, mesh}); (None, None) when
    the probe did not run or errored before measuring."""
    if not mesh or "sync_ms_on" not in mesh:
        return (mesh or None), None
    off, on = mesh["sync_ms_off"], mesh["sync_ms_on"]
    line = (f"# mesh_spmd: sync {off:.2f} -> {on:.2f} ms/step "
            f"(delta {on - off:+.3f} ms) over mesh {mesh.get('mesh')}")
    return mesh, line


def pipeline_report(pl):
    """(dict, '#'-line) for the bench JSON tail from a pipeline
    schedule A/B probe result ({sync_ms_gpipe, sync_ms_1f1b, ...});
    (None, None) when the probe did not run or errored before
    measuring."""
    if not pl or "sync_ms_1f1b" not in pl:
        return (pl or None), None
    g, f = pl["sync_ms_gpipe"], pl["sync_ms_1f1b"]
    bg = pl.get("gpipe", {}).get("bubble_frac")
    bf = pl.get("1f1b", {}).get("bubble_frac")
    bub = (f"; bubble {bg:.3f} -> {bf:.3f}"
           if bg is not None and bf is not None else "")
    line = (f"# pipeline_1f1b: sync {g:.2f} (gpipe) -> {f:.2f} ms/step "
            f"(delta {f - g:+.3f} ms) at M={pl.get('micro_batches')} "
            f"S={pl.get('n_stages')}{bub}")
    return pl, line


def multistep_report(ms):
    """(dict, '#'-line) for the bench JSON tail from a multi-step
    dispatch A/B probe result ({k, sync_ms_k1, amortized_ms_per_step,
    counters...}); (None, None) when the probe did not run or errored
    before measuring."""
    if not ms or "amortized_ms_per_step" not in ms:
        return (ms or None), None
    off, on = ms["sync_ms_k1"], ms["amortized_ms_per_step"]
    pct = (1 - on / off) * 100 if off else 0.0
    c = ms.get("counters", {})
    line = (f"# multistep: sync {off:.2f} -> amortized {on:.2f} "
            f"ms/step at K={ms.get('k')} ({pct:+.0f}% vs K=1); "
            f"host share {ms.get('host_share_before', 1.0):.2f} -> "
            f"{ms.get('host_share_after') or 0.0:.2f} "
            f"dispatches/substep; dispatches="
            f"{c.get('multistep_dispatches', 0)} substeps="
            f"{c.get('multistep_substeps', 0)} early_exits="
            f"{c.get('multistep_early_exits', 0)}")
    return ms, line


def _build_model(batch, strategy=None):
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core.engine import Engine
    from paddle_tpu.core.scope import Scope

    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[256], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="int64")
        h = layers.fc(x, size=512, act="relu")
        h = layers.fc(h, size=512, act="relu")
        pred = layers.fc(h, size=10, act="softmax")
        loss = layers.mean(layers.cross_entropy(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    scope = Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(batch, 256).astype(np.float32),
            "y": rng.randint(0, 10, (batch, 1)).astype(np.int64)}
    return Engine(strategy=strategy), main, scope, feed, [loss.name]


def measure_step_overhead(eng, prog, scope, batch, fetch_names,
                          steps=30, warmup=5):
    """(sync_ms, pipelined_ms, host_overhead_ms, counters-delta) for one
    engine/program pair, fetch-fenced per bench.py's discipline (a host
    fetch closes every window)."""
    import jax

    def _np(o):
        return np.asarray(o.array if hasattr(o, "array") else o)

    batch = {k: jax.device_put(np.asarray(v)) for k, v in batch.items()}
    for _ in range(warmup):
        out = eng.run(prog, scope, None, batch, fetch_names,
                      return_numpy=False)
    _np(out[0])
    c0 = dict(eng.counters)

    def window(n):
        t0 = time.perf_counter()
        last = None
        for _ in range(n):
            last = eng.run(prog, scope, None, batch, fetch_names,
                           return_numpy=False)[0]
        float(_np(last))   # fetch fence
        return time.perf_counter() - t0

    t1, t2 = window(steps), window(2 * steps)
    sps = steps / (t2 - t1) if t2 - t1 > 0.02 * t2 \
        else 3 * steps / (t1 + t2)
    ts = []
    for _ in range(7):
        t0 = time.perf_counter()
        float(_np(eng.run(prog, scope, None, batch, fetch_names,
                          return_numpy=False)[0]))
        ts.append(time.perf_counter() - t0)
    sync_ms = sorted(ts)[len(ts) // 2] * 1e3
    pipelined_ms = 1e3 / sps
    counters = {k: eng.counters[k] - c0.get(k, 0)
                for k in eng.counters}
    return {"sync_ms": sync_ms,
            "pipelined_ms": pipelined_ms,
            "host_overhead_ms": sync_ms - pipelined_ms,
            "steps_per_sec": sps,
            "counters": counters}


def set_telemetry(enabled):
    """Force every observability hot-path gate to a known state so the
    measurement is attributable: disabled means metrics + recorder +
    watchdog-arming + fault-arming all off (``_HOT[0]`` False)."""
    from paddle_tpu.observability import metrics, recorder
    from paddle_tpu.distributed import faults
    faults.uninstall()
    recorder.set_watchdog_active(False)
    recorder.enable(bool(enabled))
    metrics.enable_telemetry(bool(enabled))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--threshold-ms", type=float, default=None,
                   help="exit 1 when host overhead/step exceeds this")
    p.add_argument("--async-dispatch", action="store_true",
                   help="measure with FLAGS_async_dispatch on")
    p.add_argument("--telemetry", action="store_true",
                   help="measure with FLAGS_telemetry + flight "
                        "recorder ON (default: forced off)")
    p.add_argument("--compare-telemetry", action="store_true",
                   help="measure disabled then enabled, report both "
                        "and the enabled-path delta")
    p.add_argument("--compare-scheduler", action="store_true",
                   help="A/B FLAGS_op_scheduler: measure off (the "
                        "default path, proving its overhead is "
                        "unchanged) then on; --threshold-ms gates "
                        "BOTH measurements")
    p.add_argument("--compare-guard", action="store_true",
                   help="A/B FLAGS_stability_guard: measure off then "
                        "on (verdict compiled into the step, ONE "
                        "scalar fetch); --threshold-ms gates the "
                        "guard-on DELTA, the number "
                        "docs/STABILITY.md promises stays small")
    p.add_argument("--compare-integrity", action="store_true",
                   help="A/B FLAGS_integrity_sentinel: measure off "
                        "then on (per-bucket fingerprints compiled "
                        "into the step, host verdict every "
                        "PT_INTEGRITY_EVERY steps); --threshold-ms "
                        "gates the sentinel-on sync DELTA, the number "
                        "docs/RESILIENCE.md promises stays small")
    p.add_argument("--compare-tuned", action="store_true",
                   help="run the feedback-directed autotuner on a "
                        "fresh engine/model (docs/TUNING.md), measure "
                        "with the winner applied, report the tuned-vs-"
                        "default search delta (<= 0 by construction); "
                        "--threshold-ms gates that delta. Search shape "
                        "via PT_TUNE_KNOBS/PT_TUNE_BUDGETS (default: "
                        "host-side knobs only, so the probe stays "
                        "cheap); cache dir: PT_TUNING_CACHE_DIR "
                        "(a throwaway dir when unset)")
    p.add_argument("--compare-mesh", action="store_true",
                   help="A/B the named-mesh SPMD path "
                        "(docs/PARALLELISM.md): measure the plain "
                        "single-engine step, then the SAME model under "
                        "a data-only MeshSpec over every host device "
                        "(bit-identical math, GSPMD-partitioned); "
                        "--threshold-ms gates the mesh-on sync DELTA")
    p.add_argument("--compare-pipeline", action="store_true",
                   help="A/B the MPMD pipeline schedules "
                        "(docs/PARALLELISM.md): auto-cut a fresh "
                        "2-stage model (parallel/auto_cut.py, no "
                        "manual cut_vars) and run the SAME program "
                        "under the gpipe fill/drain baseline and the "
                        "interleaved 1F1B schedule; --threshold-ms "
                        "gates the 1F1B-minus-gpipe sync DELTA "
                        "(<= 0 expected: 1F1B only reorders "
                        "micro-batches, it must not be slower)")
    p.add_argument("--compare-multistep", action="store_true",
                   help="A/B multi-step dispatch (PT_MULTI_STEP, "
                        "docs/ASYNC_DISPATCH.md): stack K copies of "
                        "the batch into one FeedSlab and dispatch the "
                        "K-substep scanned executable; --threshold-ms "
                        "gates the amortized-per-substep-minus-K=1 "
                        "sync DELTA (negative = the fused dispatch "
                        "amortizes the per-dispatch host cost as promised)")
    p.add_argument("--multistep-k", type=int, default=4,
                   help="substeps per fused dispatch for "
                        "--compare-multistep (default 4)")
    p.add_argument("--compare-memory", action="store_true",
                   help="A/B the HBM memory-observatory census "
                        "(docs/MEMORY.md): measure with the census "
                        "disabled (the default path above, proving "
                        "the one-boolean gate does zero work) then "
                        "with memory.enable(True); --threshold-ms "
                        "gates the census-on sync DELTA. Census "
                        "cadence via PT_HBM_CENSUS_EVERY")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)

    from paddle_tpu.core.flags import set_flags
    if args.async_dispatch:
        set_flags({"FLAGS_async_dispatch": True})

    eng, prog, scope, feed, fetch = _build_model(args.batch)
    import paddle_tpu as fluid
    set_telemetry(args.telemetry)
    with fluid.scope_guard(scope):
        r = measure_step_overhead(eng, prog, scope, feed, fetch,
                                  steps=args.steps)
        if args.compare_telemetry and not args.telemetry:
            set_telemetry(True)
            r_on = measure_step_overhead(eng, prog, scope, feed, fetch,
                                         steps=args.steps)
            set_telemetry(False)
            r["telemetry_on"] = {k: r_on[k] for k in
                                 ("sync_ms", "pipelined_ms",
                                  "host_overhead_ms", "steps_per_sec")}
            r["telemetry_delta_ms"] = (r_on["sync_ms"] - r["sync_ms"])
        if args.compare_scheduler:
            # A/B the op scheduler on a FRESH engine/model (flag-aware
            # cache keys would retrace anyway; a fresh scope keeps the
            # two measurements starting from identical params)
            set_flags({"FLAGS_op_scheduler": True})
            try:
                eng2, prog2, scope2, feed2, fetch2 = \
                    _build_model(args.batch)
                with fluid.scope_guard(scope2):
                    r_s = measure_step_overhead(
                        eng2, prog2, scope2, feed2, fetch2,
                        steps=args.steps)
                r["scheduler_on"] = {
                    **{k: r_s[k] for k in
                       ("sync_ms", "pipelined_ms", "host_overhead_ms",
                        "steps_per_sec")},
                    # gauges read absolute (a steady-state delta of a
                    # gauge is always 0); cumulative keys as deltas
                    "counters": {
                        "scheduled_steps":
                            r_s["counters"].get("scheduled_steps", 0),
                        "islands_concurrent":
                            eng2.counters["islands_concurrent"],
                        "pipeline_fill_frac":
                            eng2.counters["pipeline_fill_frac"],
                        "lane_idle_ms": round(
                            r_s["counters"].get("lane_idle_ms", 0.0),
                            2)}}
                r["scheduler_delta_ms"] = (r_s["sync_ms"]
                                           - r["sync_ms"])
            finally:
                set_flags({"FLAGS_op_scheduler": False})
        if args.compare_guard:
            # A/B the stability guard on a FRESH engine/model so both
            # measurements start from identical params and the
            # guard-off numbers above stay uncontaminated
            set_flags({"FLAGS_stability_guard": True})
            try:
                eng3, prog3, scope3, feed3, fetch3 = \
                    _build_model(args.batch)
                with fluid.scope_guard(scope3):
                    r_g = measure_step_overhead(
                        eng3, prog3, scope3, feed3, fetch3,
                        steps=args.steps)
                n_steps = max(1, r_g["counters"].get("runs", 0))
                r["guard_on"] = {
                    **{k: r_g[k] for k in
                       ("sync_ms", "pipelined_ms", "host_overhead_ms",
                        "steps_per_sec")},
                    "guard_host_ms_per_step": round(
                        r_g["counters"].get("guard_overhead_ms", 0.0)
                        / n_steps, 4),
                    "ghost_snapshots":
                        r_g["counters"].get("ghost_snapshots", 0),
                    "anomalies": r_g["counters"].get("anomalies", 0)}
                r["guard_delta_ms"] = r_g["sync_ms"] - r["sync_ms"]
            finally:
                set_flags({"FLAGS_stability_guard": False})
        if args.compare_integrity:
            # A/B the integrity sentinel on a FRESH engine/model (the
            # sentinel flag is part of the trace cache key; a fresh
            # scope keeps both measurements starting from identical
            # params and the sentinel-off numbers uncontaminated)
            set_flags({"FLAGS_integrity_sentinel": True})
            try:
                eng6, prog6, scope6, feed6, fetch6 = \
                    _build_model(args.batch)
                with fluid.scope_guard(scope6):
                    r_i = measure_step_overhead(
                        eng6, prog6, scope6, feed6, fetch6,
                        steps=args.steps)
                r["integrity_on"] = {
                    **{k: r_i[k] for k in
                       ("sync_ms", "pipelined_ms", "host_overhead_ms",
                        "steps_per_sec")},
                    "integrity_checks":
                        r_i["counters"].get("integrity_checks", 0),
                    "integrity_mismatches":
                        r_i["counters"].get("integrity_mismatches", 0)}
                r["integrity_delta_ms"] = r_i["sync_ms"] - r["sync_ms"]
            finally:
                set_flags({"FLAGS_integrity_sentinel": False})
        if args.compare_multistep:
            # A/B multi-step dispatch on a FRESH engine/model (the
            # K=1 numbers above stay uncontaminated; PT_MULTI_STEP is
            # part of the trace cache key so the slab compiles its own
            # scanned executable)
            import jax
            from paddle_tpu.reader.prefetcher import FeedSlab
            k = max(1, args.multistep_k)
            eng8, prog8, scope8, feed8, fetch8 = \
                _build_model(args.batch)
            with fluid.scope_guard(scope8):
                def _np8(o):
                    return np.asarray(
                        o.array if hasattr(o, "array") else o)
                b8 = {kk: jax.device_put(np.asarray(v))
                      for kk, v in feed8.items()}
                slab = FeedSlab.stack([b8] * k)
                for _ in range(3):
                    rows = eng8.run_multi(prog8, scope8, None, slab,
                                          fetch8, return_numpy=False)
                float(_np8(rows[-1][0]))
                ts8 = []
                for _ in range(7):
                    t0 = time.perf_counter()
                    rows = eng8.run_multi(prog8, scope8, None, slab,
                                          fetch8, return_numpy=False)
                    float(_np8(rows[-1][0]))
                    ts8.append(time.perf_counter() - t0)
                slab_ms = sorted(ts8)[len(ts8) // 2] * 1e3
                d8 = eng8.counters["multistep_dispatches"]
                s8 = eng8.counters["multistep_substeps"]
                r["multistep_on"] = {
                    "k": k,
                    "sync_ms_k1": r["sync_ms"],
                    "slab_ms": slab_ms,
                    "amortized_ms_per_step": slab_ms / k,
                    "host_share_before": 1.0,
                    "host_share_after":
                        round(d8 / s8, 3) if s8 else None,
                    "counters": {
                        "multistep_dispatches": d8,
                        "multistep_substeps": s8,
                        "multistep_early_exits":
                            eng8.counters["multistep_early_exits"]}}
                r["multistep_delta_ms"] = slab_ms / k - r["sync_ms"]
        if args.compare_tuned:
            # autotune a FRESH engine/model, then measure with the
            # winner applied; knob + applied state restored after, so
            # the probe never leaks tuning into the caller's process
            import shutil
            import tempfile
            from paddle_tpu.tuning import driver as tdriver
            from paddle_tpu.tuning import knobs as tknobs
            from paddle_tpu.tuning import state as tstate
            snap = tknobs.snapshot()
            own_cache = None
            if not os.environ.get("PT_TUNING_CACHE_DIR"):
                own_cache = tempfile.mkdtemp(prefix="pt_tune_bench_")
                os.environ["PT_TUNING_CACHE_DIR"] = own_cache
            os.environ.setdefault("PT_TUNE_KNOBS",
                                  "prefetch_depth,ghost_every")
            os.environ.setdefault("PT_TUNE_BUDGETS", "1,3")
            try:
                eng4, prog4, scope4, feed4, fetch4 = \
                    _build_model(args.batch)
                with fluid.scope_guard(scope4):
                    info = tdriver.autotune_for_run(
                        eng4, prog4, scope4, None, feed4, fetch4)
                    r_t = measure_step_overhead(
                        eng4, prog4, scope4, feed4, fetch4,
                        steps=args.steps)
                r["tuning"] = {
                    "source": info["source"],
                    "trials": info["trials"],
                    "config": info["config"],
                    "objective_ms": info["objective_ms"],
                    "delta_ms": info.get("delta_ms"),
                    "tuned": {k: r_t[k] for k in
                              ("sync_ms", "pipelined_ms",
                               "host_overhead_ms", "steps_per_sec")}}
                r["tuned_delta_ms"] = info.get("delta_ms") or 0.0
            finally:
                tknobs.restore(snap)
                tstate.clear_applied()
                if own_cache:
                    os.environ.pop("PT_TUNING_CACHE_DIR", None)
                    shutil.rmtree(own_cache, ignore_errors=True)
        if args.compare_mesh:
            # A/B the named mesh on a FRESH engine/model: the data-only
            # MeshSpec is the bit-identity layout (test_mesh_spmd.py),
            # so any sync delta is pure partitioner/dispatch overhead
            import jax
            from paddle_tpu.parallel import DistributedStrategy, MeshSpec
            n = len(jax.devices())
            if n < 2:
                r["mesh_on"] = {"skipped": "single-device host"}
            else:
                strat = DistributedStrategy.from_mesh_spec(
                    MeshSpec(data=n))
                eng7, prog7, scope7, feed7, fetch7 = \
                    _build_model(args.batch, strategy=strat)
                with fluid.scope_guard(scope7):
                    r_x = measure_step_overhead(
                        eng7, prog7, scope7, feed7, fetch7,
                        steps=args.steps)
                r["mesh_on"] = {
                    **{k: r_x[k] for k in
                       ("sync_ms", "pipelined_ms", "host_overhead_ms",
                        "steps_per_sec")},
                    "mesh": {"data": n}}
                r["mesh_delta_ms"] = r_x["sync_ms"] - r["sync_ms"]
        if args.compare_pipeline:
            # A/B the two schedules on a FRESH auto-cut 2-stage model:
            # both runs execute the identical per-stage executables on
            # the identical micro-batches, so any delta is pure
            # schedule (dispatch order + stash pressure)
            import paddle_tpu as fluid
            from paddle_tpu.core.scope import Scope
            from paddle_tpu.parallel.mpmd_pipeline import \
                MPMDPipelineEngine

            def _pipe_model():
                fluid.framework.unique_name.reset()
                main, startup = fluid.Program(), fluid.Program()
                with fluid.program_guard(main, startup):
                    from paddle_tpu import layers
                    x = layers.data("px", [64], dtype="float32")
                    y = layers.data("py", [1], dtype="int64")
                    h = layers.fc(x, size=128, act="relu")
                    h = layers.fc(h, size=128, act="relu")
                    h = layers.fc(h, size=128, act="relu")
                    pred = layers.fc(h, size=10, act="softmax")
                    loss = layers.mean(
                        layers.cross_entropy(input=pred, label=y))
                return main, startup, loss

            rng = np.random.RandomState(0)
            n_micro = 4
            b = max(n_micro, (args.batch // n_micro) * n_micro)
            feed_p = {"px": rng.rand(b, 64).astype(np.float32),
                      "py": rng.randint(0, 10, (b, 1)).astype(np.int64)}
            pl = {}
            for kind in ("gpipe", "1f1b"):
                main_p, startup_p, loss_p = _pipe_model()
                scope_p = Scope()
                with fluid.scope_guard(scope_p):
                    fluid.Executor().run(startup_p)
                    eng_p = MPMDPipelineEngine(
                        main_p, loss_p.name, None, n_stages=2,
                        num_microbatches=n_micro, schedule=kind)
                    for _ in range(2):
                        eng_p.run(scope_p, feed_p)
                    ts = []
                    for _ in range(max(5, args.steps // 4)):
                        t0 = time.perf_counter()
                        eng_p.run(scope_p, feed_p)
                        ts.append(time.perf_counter() - t0)
                st = eng_p.last_stats or {}
                pl[kind] = {
                    "sync_ms": sorted(ts)[len(ts) // 2] * 1e3,
                    "bubble_frac": st.get("bubble_frac"),
                    "stash_peak": st.get("stash_peak"),
                    "cut_vars": list(eng_p.cut_vars)}
            r["pipeline_ab"] = {
                "micro_batches": n_micro,
                "n_stages": 2,
                "gpipe": pl["gpipe"], "1f1b": pl["1f1b"],
                "sync_ms_gpipe": pl["gpipe"]["sync_ms"],
                "sync_ms_1f1b": pl["1f1b"]["sync_ms"]}
            r["pipeline_delta_ms"] = (pl["1f1b"]["sync_ms"]
                                      - pl["gpipe"]["sync_ms"])
        if args.compare_memory:
            # A/B the live-buffer census on a FRESH engine/model; the
            # census-off numbers above stay uncontaminated, and the
            # baseline census count proves the disabled path did no
            # census work at all
            from paddle_tpu.observability import memory as obs_memory
            censuses_off = obs_memory.stats()["censuses"]
            obs_memory.reset()
            obs_memory.enable(True)
            try:
                eng5, prog5, scope5, feed5, fetch5 = \
                    _build_model(args.batch)
                with fluid.scope_guard(scope5):
                    r_m = measure_step_overhead(
                        eng5, prog5, scope5, feed5, fetch5,
                        steps=args.steps)
                c = obs_memory.last_census() or {}
                r["memory_on"] = {
                    **{k: r_m[k] for k in
                       ("sync_ms", "pipelined_ms", "host_overhead_ms",
                        "steps_per_sec")},
                    "censuses": obs_memory.stats()["censuses"],
                    "censuses_disabled_baseline": censuses_off,
                    "coverage_frac": c.get("coverage_frac"),
                    "live_bytes": c.get("live_bytes"),
                    "orphan_bytes": c.get("orphan_bytes"),
                    "owners": {o: rec.get("bytes", 0) for o, rec in
                               (c.get("owners") or {}).items()}}
                r["memory_delta_ms"] = r_m["sync_ms"] - r["sync_ms"]
            finally:
                obs_memory.enable(False)
                obs_memory.reset()
    r["async_dispatch"] = bool(args.async_dispatch)
    r["telemetry"] = bool(args.telemetry)
    if args.json:
        print(json.dumps(r))
    else:
        print(overhead_report("step_overhead_bench", r["sync_ms"],
                              r["steps_per_sec"],
                              counters=r["counters"]))
        if "telemetry_delta_ms" in r:
            print(f"# telemetry-enabled sync "
                  f"{r['telemetry_on']['sync_ms']:.2f} ms/step "
                  f"(delta {r['telemetry_delta_ms']:+.3f} ms vs "
                  f"disabled {r['sync_ms']:.2f})")
        if "scheduler_on" in r:
            _, line = scheduler_overlap_report(
                {"sync_ms_off": r["sync_ms"],
                 "sync_ms_on": r["scheduler_on"]["sync_ms"],
                 "counters": r["scheduler_on"]["counters"]})
            if line:
                print(line)
        if "guard_on" in r:
            _, line = guard_overhead_report(
                {"sync_ms_off": r["sync_ms"],
                 "sync_ms_on": r["guard_on"]["sync_ms"],
                 "guard_host_ms_per_step":
                     r["guard_on"]["guard_host_ms_per_step"],
                 "ghost_snapshots": r["guard_on"]["ghost_snapshots"],
                 "anomalies": r["guard_on"]["anomalies"]})
            if line:
                print(line)
        if "integrity_on" in r:
            _, line = integrity_report(
                {"sync_ms_off": r["sync_ms"],
                 "sync_ms_on": r["integrity_on"]["sync_ms"],
                 "integrity_checks":
                     r["integrity_on"]["integrity_checks"],
                 "integrity_mismatches":
                     r["integrity_on"]["integrity_mismatches"]})
            if line:
                print(line)
        if "multistep_on" in r:
            _, line = multistep_report(r["multistep_on"])
            if line:
                print(line)
        if "tuning" in r:
            _, line = tuning_report(r["tuning"])
            if line:
                print(line)
        if "pipeline_ab" in r:
            _, line = pipeline_report(r["pipeline_ab"])
            if line:
                print(line)
        if "mesh_on" in r and "sync_ms" in r.get("mesh_on", {}):
            _, line = mesh_report(
                {"sync_ms_off": r["sync_ms"],
                 "sync_ms_on": r["mesh_on"]["sync_ms"],
                 "mesh": r["mesh_on"]["mesh"]})
            if line:
                print(line)
        if "memory_on" in r:
            _, line = memory_report(
                {"sync_ms_off": r["sync_ms"],
                 "sync_ms_on": r["memory_on"]["sync_ms"],
                 "censuses": r["memory_on"]["censuses"],
                 "coverage_frac": r["memory_on"]["coverage_frac"],
                 "live_bytes": r["memory_on"]["live_bytes"]})
            if line:
                print(line)
    bad = []
    if r["counters"].get("traces"):
        bad.append(f"steady state re-traced "
                   f"{r['counters']['traces']}x")
    if args.threshold_ms is not None and \
            r["host_overhead_ms"] > args.threshold_ms:
        bad.append(f"host overhead {r['host_overhead_ms']:.1f} ms > "
                   f"threshold {args.threshold_ms:.1f} ms")
    if args.threshold_ms is not None and "scheduler_on" in r and \
            r["scheduler_on"]["host_overhead_ms"] > args.threshold_ms:
        bad.append(
            f"scheduler-on host overhead "
            f"{r['scheduler_on']['host_overhead_ms']:.1f} ms > "
            f"threshold {args.threshold_ms:.1f} ms")
    if args.threshold_ms is not None and "guard_delta_ms" in r and \
            r["guard_delta_ms"] > args.threshold_ms:
        bad.append(
            f"stability-guard sync delta "
            f"{r['guard_delta_ms']:.2f} ms > threshold "
            f"{args.threshold_ms:.1f} ms")
    if args.threshold_ms is not None and "integrity_delta_ms" in r \
            and r["integrity_delta_ms"] > args.threshold_ms:
        bad.append(
            f"integrity-sentinel sync delta "
            f"{r['integrity_delta_ms']:.2f} ms > threshold "
            f"{args.threshold_ms:.1f} ms")
    if args.threshold_ms is not None and "multistep_delta_ms" in r \
            and r["multistep_delta_ms"] > args.threshold_ms:
        bad.append(
            f"multistep amortized-vs-K=1 sync delta "
            f"{r['multistep_delta_ms']:.2f} ms > threshold "
            f"{args.threshold_ms:.1f} ms")
    if args.threshold_ms is not None and "tuned_delta_ms" in r and \
            r["tuned_delta_ms"] > args.threshold_ms:
        bad.append(
            f"tuned-vs-default sync delta "
            f"{r['tuned_delta_ms']:.3f} ms > threshold "
            f"{args.threshold_ms:.1f} ms")
    if args.threshold_ms is not None and "pipeline_delta_ms" in r \
            and r["pipeline_delta_ms"] > args.threshold_ms:
        bad.append(
            f"pipeline 1F1B-vs-gpipe delta "
            f"{r['pipeline_delta_ms']:.1f} ms > threshold "
            f"{args.threshold_ms:.1f} ms")
    if args.threshold_ms is not None and "memory_delta_ms" in r and \
            r["memory_delta_ms"] > args.threshold_ms:
        bad.append(
            f"memory-census sync delta "
            f"{r['memory_delta_ms']:.2f} ms > threshold "
            f"{args.threshold_ms:.1f} ms")
    if args.threshold_ms is not None and "mesh_delta_ms" in r and \
            r["mesh_delta_ms"] > args.threshold_ms:
        bad.append(
            f"mesh-on sync delta "
            f"{r['mesh_delta_ms']:.2f} ms > threshold "
            f"{args.threshold_ms:.1f} ms")
    if bad:
        print("REGRESSION: " + "; ".join(bad), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
