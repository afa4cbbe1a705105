"""Benchmarks for the 5 BASELINE.json configs on one TPU chip.

Runs on the TPU only: every benched path takes fluid.TPUPlace(0) and
the process exits non-zero when jax.default_backend() is not "tpu".
Nothing has been measured with this file on the present installation
(jax 0.9.0 / libtpu 0.0.34); see PERF.md.

Headline metric per BASELINE.json: "Transformer-base tokens/sec" with
the north-star target of >= 0.8x the reference CUDA path per chip on
V100. The reference snapshot publishes no numbers, so the comparison
constant is the public V100 FP32 Transformer-base training throughput
ballpark (~15k tokens/sec, fairseq/tensor2tensor-era reports);
vs_baseline = measured / 15000 (1.0 == V100 parity, 0.8 == the
north-star bar).

Measurement discipline:

  Every timing window closes with a HOST FETCH of the final scalar
  loss (a fence that holds on any backend; on the v5e
  `block_until_ready` fences too — PERF.md "Findings", PR 21), and the
  window-constant overhead (dispatch + fetch) cancels by differencing
  two window sizes: steps/s = N / (T(2N) - T(N)).
  Cross-checks emitted per config:
    * analytical FLOPs/step from the compiled executable's XLA
      cost_analysis() (Engine.compiled_stats),
    * implied TFLOP/s = FLOPs/step * steps/s and implied MFU vs the
      chip's dense bf16 peak (observability/attribution.py's table; an
      unknown device_kind is an error) — any value > 100% of peak is a
      measurement bug by definition and is flagged loudly,
    * a synchronous single-step latency (dispatch + fetch each step,
      so it upper-bounds true step time).

Execution proof: donated params chain step N's input to step N-1's
update, so the fixed-batch loss at steps {0, mid, last} being pairwise
distinct proves every timed step really executed (no dedup/skip).

Default prints ONE JSON line for the driver:
  {"metric", "value", "unit", "vs_baseline"}.
`python bench.py --all` additionally measures the other BASELINE.json
configs (MNIST LeNet, ResNet-50, Wide&Deep CTR, dygraph) to stderr, each
in a fresh process, and exits non-zero if any of them failed.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

V100_TOKENS_PER_SEC = 15000.0

BATCH = 96
SRC_LEN = 128
TRG_LEN = 128
WARMUP = 3
ITERS = 30


def _tpu_place():
    """fluid.TPUPlace(0), or exit: the bench never falls back to
    whatever backend JAX happened to find."""
    import jax
    import paddle_tpu as fluid
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU; jax.default_backend() is "
            f"{jax.default_backend()!r} (devices {jax.devices()})")
    return fluid.TPUPlace(0)


def _device_peak():
    """(device_kind, dense bf16 peak TFLOP/s) of chip 0; an unknown
    device_kind raises."""
    from paddle_tpu.observability.attribution import peak_tflops
    kind = _tpu_place().jax_device().device_kind
    return kind, peak_tflops(kind)


def _loop(eng, prog, scope, place, batch, fetch, iters, warmup=WARMUP,
          iterations=1):
    """Fetch-fenced, overhead-cancelling timing loop on `place`.

    Returns (steps/sec, (l0, lm, ln), sync_ms). See module docstring
    for the fence. `iterations` =
    ExecutionStrategy.num_iteration_per_run: K steps compile into one
    lax.scan executable, amortizing the per-dispatch host cost for
    small (dispatch-bound) models; fetched losses come from each run's
    LAST step, so the trajectory proof still holds.
    """
    import jax

    def _arr(o):
        return o.array if hasattr(o, "array") else o

    # device-resident feeds: measure the chip, not the host->device
    # link (a real input pipeline overlaps transfers)
    batch = {k: jax.device_put(v, place.jax_device())
             for k, v in batch.items()}
    for _ in range(warmup):
        out = eng.run(prog, scope, place, batch, fetch,
                      return_numpy=False, iterations=iterations)
    np.asarray(_arr(out[0]))  # completion fence

    def window(n):
        t0 = time.perf_counter()
        ls = [eng.run(prog, scope, place, batch, fetch,
                      return_numpy=False,
                      iterations=iterations)[0] for _ in range(n)]
        float(np.asarray(_arr(ls[-1])))  # fence: host fetch
        return time.perf_counter() - t0, ls

    t1, la = window(iters)
    t2, lb = window(2 * iters)
    if t2 - t1 > 0.02 * t2:
        sps = iters * iterations / (t2 - t1)
    else:
        # window noise swallowed the difference; fall back to the
        # conservative upper-bound-inclusive estimate (overhead counted)
        sps = 3 * iters * iterations / (t1 + t2)
    losses = la + lb
    l0 = float(np.asarray(_arr(losses[0])))
    lm = float(np.asarray(_arr(losses[len(losses) // 2])))
    ln = float(np.asarray(_arr(losses[-1])))
    # execution proof (see module docstring); all three finite (NaNs are
    # pairwise-"distinct" in a set) and pairwise distinct
    assert all(np.isfinite(v) for v in (l0, lm, ln)), (l0, lm, ln)
    assert len({l0, lm, ln}) == 3, (l0, lm, ln)
    # synchronous single-step latency: includes dispatch + fetch per
    # step, upper-bounds the true device step time
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        o = eng.run(prog, scope, place, batch, fetch, return_numpy=False,
                    iterations=iterations)
        float(np.asarray(_arr(o[0])))
        ts.append(time.perf_counter() - t0)
    sync_ms = sorted(ts)[len(ts) // 2] * 1e3 / iterations
    return sps, (l0, lm, ln), sync_ms


def _mfu_lines(name, sps, sync_ms, stats):
    """MFU/roofline accounting lines for stderr (VERDICT r2 #1)."""
    kind, peak = _device_peak()
    lines = []
    if stats and stats.get("flops"):
        # XLA cost_analysis counts a while/scan body ONCE, so
        # stats["flops"] is ~per-substep even for scanned executables
        # (num_iteration_per_run / PT_MULTI_STEP); `sps` counts
        # substeps too. Scale both to per-DISPATCH with the trip count
        # so every substep is counted exactly once and the scanned
        # path can't report impossibly low MFU.
        trip = float(stats.get("trip_count") or 1.0)
        fl = stats["flops"] * trip
        tfs = fl * (sps / trip) / 1e12
        if trip > 1:
            line = (f"# {name}: roofline: {fl/1e12:.3f} "
                    f"TFLOPs/dispatch ({stats['flops']/1e12:.3f} "
                    f"body x trip {trip:.0f}) x {sps/trip:.2f} "
                    f"dispatches/s = {tfs:.1f} TFLOP/s")
        else:
            line = (f"# {name}: roofline: {fl/1e12:.3f} TFLOPs/step x "
                    f"{sps:.2f} steps/s = {tfs:.1f} TFLOP/s")
        mfu = tfs / peak
        line += f" -> MFU {mfu*100:.1f}% of {kind} peak {peak:.0f}"
        if mfu > 1.0:
            line += (" *** IMPOSSIBLE (>100% of peak): measurement"
                     " bug, do not trust this row ***")
        lines.append(line)
    if sync_ms:
        lines.append(
            f"# {name}: sync 1-step latency {sync_ms:.1f} ms "
            f"(incl. dispatch + fetch; device-only bound "
            f"{1e3/sps:.1f} ms/step)")
        try:
            from tools.step_overhead_bench import overhead_report
            line = overhead_report(name, sync_ms, sps, stats)
            if line:
                lines.append(line)
        except Exception:
            pass   # accounting line only; never fail the bench on it
    return lines


def _bench_checkpoint(exe, scope, main_prog):
    """Checkpoint round-trip timing (docs/CHECKPOINTING.md acceptance:
    async ``save()`` must return in <10% of the synchronous
    ``save_persistables`` wall time — the step loop pays only the
    snapshot, not the D2H + serialization + fsync)."""
    import shutil
    import tempfile
    import paddle_tpu as fluid
    from paddle_tpu.checkpoint import CheckpointManager

    root = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        t0 = time.perf_counter()
        fluid.io.save_persistables(exe, os.path.join(root, "legacy"),
                                   main_prog)
        sync_s = time.perf_counter() - t0
        m = CheckpointManager(os.path.join(root, "async"))
        t0 = time.perf_counter()
        m.save(1, scope=scope, program=main_prog,
               raise_on_missing=False)
        ret_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        m.wait_all()
        drain_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        m.restore(step=1, scope=scope, program=main_prog)
        rest_s = time.perf_counter() - t0
        m.close()
        print(f"# checkpoint: sync save {sync_s*1e3:.0f} ms; async "
              f"save() returned in {ret_s*1e3:.1f} ms "
              f"({ret_s/sync_s*100:.1f}% of sync), background drain "
              f"{drain_s*1e3:.0f} ms, restore {rest_s*1e3:.0f} ms",
              file=sys.stderr)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _probe_scheduler(eng, prog, scope, feed, fetch, sync_off_ms):
    """A/B the op scheduler (FLAGS_op_scheduler, docs/SCHEDULING.md) on
    the already-built transformer: flag on (flag-aware cache keys force
    a fresh scheduled trace), 3 warmups, median of 5 fetch-fenced sync
    steps. The scheduler's headline win is exactly this number: the
    loss is a forward-island output, so its fetch completes while the
    backward/optimizer islands still run — the whole-block executable
    makes the same fetch wait for the optimizer."""
    import jax
    from paddle_tpu.core.flags import FLAGS, set_flags
    prev = bool(FLAGS.op_scheduler)
    out = {"sync_ms_off": round(sync_off_ms, 2)}

    def _np(o):
        return np.asarray(o.array if hasattr(o, "array") else o)

    try:
        set_flags({"FLAGS_op_scheduler": True})
        batch = {k: jax.device_put(np.asarray(v))
                 for k, v in feed.items()}
        for _ in range(3):
            o = eng.run(prog, scope, None, batch, fetch,
                        return_numpy=False)
        float(_np(o[0]))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            float(_np(eng.run(prog, scope, None, batch, fetch,
                              return_numpy=False)[0]))
            ts.append(time.perf_counter() - t0)
        out["sync_ms_on"] = round(sorted(ts)[len(ts) // 2] * 1e3, 2)
        out["counters"] = {
            "scheduled_steps": eng.counters["scheduled_steps"],
            "islands_concurrent": eng.counters["islands_concurrent"],
            "pipeline_fill_frac": eng.counters["pipeline_fill_frac"],
            "lane_idle_ms": round(eng.counters["lane_idle_ms"], 2)}
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    finally:
        set_flags({"FLAGS_op_scheduler": prev})
    return out


def _probe_multistep(eng, prog, scope, feed, fetch, sync_ms_k1):
    """A/B multi-step dispatch (PT_MULTI_STEP, docs/ASYNC_DISPATCH.md
    "Multi-step dispatch"): stack K copies of the batch into one
    FeedSlab, dispatch the K-substep scanned executable, and compare
    the amortized per-substep fetch-fenced latency against the K=1
    sync step above. The host-phase share (host dispatches per device
    substep) before/after says where the win comes from: K substeps
    now pay ONE dispatch + fetch round trip."""
    import jax
    from paddle_tpu.reader.prefetcher import FeedSlab
    k = int(os.environ.get("PT_BENCH_MULTISTEP_K", "4"))
    out = {"k": k, "sync_ms_k1": round(sync_ms_k1, 2)}

    def _np(o):
        return np.asarray(o.array if hasattr(o, "array") else o)

    try:
        batch = {kk: jax.device_put(np.asarray(v))
                 for kk, v in feed.items()}
        slab = FeedSlab.stack([batch] * k)
        d0 = eng.counters["multistep_dispatches"]
        s0 = eng.counters["multistep_substeps"]
        for _ in range(3):
            rows = eng.run_multi(prog, scope, None, slab, fetch,
                                 return_numpy=False)
        float(_np(rows[-1][0]))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            rows = eng.run_multi(prog, scope, None, slab, fetch,
                                 return_numpy=False)
            float(_np(rows[-1][0]))
            ts.append(time.perf_counter() - t0)
        slab_ms = sorted(ts)[len(ts) // 2] * 1e3
        d = eng.counters["multistep_dispatches"] - d0
        s = eng.counters["multistep_substeps"] - s0
        out["slab_ms"] = round(slab_ms, 2)
        out["amortized_ms_per_step"] = round(slab_ms / k, 2)
        if sync_ms_k1:
            out["improvement_frac"] = round(
                1.0 - (slab_ms / k) / sync_ms_k1, 3)
        # host-phase share: dispatches per substep (K=1 pays one host
        # dispatch EVERY substep by definition)
        out["host_share_before"] = 1.0
        out["host_share_after"] = round(d / s, 3) if s else None
        out["counters"] = {
            "multistep_dispatches": d,
            "multistep_substeps": s,
            "multistep_early_exits":
                eng.counters["multistep_early_exits"]}
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    return out


def _probe_guard(eng, prog, scope, feed, fetch, sync_off_ms):
    """A/B the stability guard (FLAGS_stability_guard,
    docs/STABILITY.md) on the already-built transformer: the verdict +
    gate compile into the traced step, so the promised cost is one
    fused reduction plus elementwise selects — this probe measures the
    realized sync-step delta and the host-side controller overhead."""
    import jax
    from paddle_tpu.core.flags import FLAGS, set_flags
    prev = bool(FLAGS.stability_guard)
    out = {"sync_ms_off": round(sync_off_ms, 2)}

    def _np(o):
        return np.asarray(o.array if hasattr(o, "array") else o)

    try:
        set_flags({"FLAGS_stability_guard": True})
        c0 = {k: eng.counters.get(k, 0)
              for k in ("runs", "guard_overhead_ms",
                        "ghost_snapshots", "anomalies")}
        batch = {k: jax.device_put(np.asarray(v))
                 for k, v in feed.items()}
        for _ in range(3):
            o = eng.run(prog, scope, None, batch, fetch,
                        return_numpy=False)
        float(_np(o[0]))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            float(_np(eng.run(prog, scope, None, batch, fetch,
                              return_numpy=False)[0]))
            ts.append(time.perf_counter() - t0)
        out["sync_ms_on"] = round(sorted(ts)[len(ts) // 2] * 1e3, 2)
        n = max(1, eng.counters["runs"] - c0["runs"])
        out["guard_host_ms_per_step"] = round(
            (eng.counters["guard_overhead_ms"]
             - c0["guard_overhead_ms"]) / n, 4)
        out["ghost_snapshots"] = (eng.counters["ghost_snapshots"]
                                  - c0["ghost_snapshots"])
        out["anomalies"] = eng.counters["anomalies"] - c0["anomalies"]
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    finally:
        set_flags({"FLAGS_stability_guard": prev})
    return out


def _probe_kernels(eng, prog, scope, feed, fetch, sync_on_ms):
    """A/B the custom-kernel registry (FLAGS_use_custom_kernels,
    docs/KERNELS.md) on the already-built transformer. The headline
    sync step already ran with kernels ON (the flag defaults on); this
    re-times the same step with the registry forced off — flag-aware
    cache keys force a fresh all-lowered trace — so the delta is the
    kernels' step-time contribution (dominated by the fused optimizer
    sweep on TPU). Also snapshots the registry's trace-time dispatch
    stats, after an interpret-mode dispatch self-check: one eligible
    adam signature selected and executed through the registry on the
    current backend, so the hit-rate is live even on CPU hosts where
    the engine trace itself keeps the lowered paths."""
    import jax
    from paddle_tpu.core.flags import FLAGS, set_flags
    from paddle_tpu.kernels import registry as kreg
    prev = bool(FLAGS.use_custom_kernels)
    out = {"sync_ms_on": round(sync_on_ms, 2)}

    def _np(o):
        return np.asarray(o.array if hasattr(o, "array") else o)

    try:
        prev_hook = kreg._INTERPRET
        kreg._INTERPRET = True
        try:
            n = max(65536 * 2, kreg.min_numel())
            z = jax.numpy.zeros((n,), jax.numpy.float32)
            sel = kreg.select("adam",
                              kreg.signature("adam", z, z, z, z))
            if sel is not None:
                sel.run(z, z, z, z + 1.0, 1e-3)[0].block_until_ready()
        finally:
            kreg._INTERPRET = prev_hook
        out["dispatch"] = kreg.dispatch_stats()
        set_flags({"FLAGS_use_custom_kernels": False})
        batch = {k: jax.device_put(np.asarray(v))
                 for k, v in feed.items()}
        for _ in range(3):
            o = eng.run(prog, scope, None, batch, fetch,
                        return_numpy=False)
        float(_np(o[0]))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            float(_np(eng.run(prog, scope, None, batch, fetch,
                              return_numpy=False)[0]))
            ts.append(time.perf_counter() - t0)
        out["sync_ms_off"] = round(sorted(ts)[len(ts) // 2] * 1e3, 2)
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    finally:
        set_flags({"FLAGS_use_custom_kernels": prev})
    return out


def _probe_tracing(eng, prog, scope, feed, fetch, sync_ms):
    """Device-time attribution probe (docs/TRACING.md) on the
    already-built transformer: compiled cost_analysis() FLOPs/bytes,
    HBM peak, a short jax.profiler device capture, per-island
    apportionment — the bench's first MEASURED MFU number (the
    existing MFU line is analytic, from host steps/s). Device fields
    are None on CPU hosts; mfu_estimate then falls back to host wall
    time (labeled via mfu_basis)."""
    out = {"sync_ms": round(sync_ms, 2)}
    try:
        from paddle_tpu.observability import attribution, tracing
        rep = attribution.attribute(eng, prog, scope, feed, fetch,
                                    profile_steps=3)
        cost = rep.get("cost") or {}
        dev = rep.get("device") or {}
        out.update({
            "flops_per_step": cost.get("flops"),
            "hbm_peak_bytes": rep.get("hbm_peak_bytes"),
            "device_ms_per_step": dev.get("device_ms_per_step"),
            "host_ms_per_step": dev.get("host_ms_per_step"),
            "islands": rep.get("islands") or None,
            "mfu_estimate": rep.get("mfu_estimate"),
            "mfu_basis": rep.get("mfu_basis"),
            "skew": tracing.skew_snapshot(),
        })
        if rep.get("error"):
            out["error"] = str(rep["error"])[:200]
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    return out


def _probe_tuning(eng, prog, scope, feed, fetch, sync_ms):
    """Feedback-directed autotune probe (FLAGS_autotune path,
    docs/TUNING.md) on the already-built transformer: run the
    cache-or-search driver (scope-snapshotted trials, so the bench's
    params are untouched), report trials run + winning config +
    tuned-vs-default search delta (<= 0 by construction), then prove
    the persistence loop by re-running on a FRESH engine — the second
    run must be a pure cache hit with zero trials. Knob + applied
    state are restored after; a throwaway cache dir is used unless
    PT_TUNING_CACHE_DIR is set. Search shape via PT_TUNE_KNOBS /
    PT_TUNE_BUDGETS (default: host-side knobs, cheap)."""
    import shutil
    import tempfile
    from paddle_tpu.core.engine import Engine
    from paddle_tpu.tuning import driver as tdriver
    from paddle_tpu.tuning import knobs as tknobs
    from paddle_tpu.tuning import state as tstate
    out = {"sync_ms_default": round(sync_ms, 2)}
    snap = tknobs.snapshot()
    own_cache = None
    if not os.environ.get("PT_TUNING_CACHE_DIR"):
        own_cache = tempfile.mkdtemp(prefix="pt_tune_bench_")
        os.environ["PT_TUNING_CACHE_DIR"] = own_cache
    os.environ.setdefault("PT_TUNE_KNOBS", "prefetch_depth,ghost_every")
    os.environ.setdefault("PT_TUNE_BUDGETS", "1,3")
    try:
        info = tdriver.autotune_for_run(eng, prog, scope, None, feed,
                                        fetch)
        out.update({
            "source": info["source"],
            "trials": info["trials"],
            "config": info["config"],
            "objective_ms": None if info["objective_ms"] is None
            else round(info["objective_ms"], 3),
            "delta_ms": None if info.get("delta_ms") is None
            else round(info["delta_ms"], 3)})
        # persistence proof: ambient baseline back, fresh engine, the
        # stored winner must replay with ZERO trials
        tknobs.restore(snap)
        tstate.clear_applied()
        info2 = tdriver.autotune_for_run(Engine(), prog, scope, None,
                                         feed, fetch)
        out["cache_hit_second_run"] = (info2["source"] == "cache"
                                       and info2["trials"] == 0)
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    finally:
        tknobs.restore(snap)
        tstate.clear_applied()
        if own_cache:
            os.environ.pop("PT_TUNING_CACHE_DIR", None)
            shutil.rmtree(own_cache, ignore_errors=True)
    return out


def _probe_memory(eng, prog, scope, feed, fetch, sync_ms):
    """HBM memory-observatory probe (docs/MEMORY.md) on the
    already-built transformer: one owner-attributed live-buffer
    census — coverage vs jax.live_arrays() is the acceptance number
    (the census must see >=95% of live bytes) — plus donation
    effectiveness over the compiled entries and the per-island peak
    rows when the scheduler split the step. Census enablement is
    restored after, so the bench's telemetry-off numbers stay
    uncontaminated."""
    out = {"sync_ms": round(sync_ms, 2)}
    try:
        from paddle_tpu.observability import memory as obs_memory
        was = obs_memory.census_enabled()
        obs_memory.enable(True)
        try:
            c = obs_memory.census()
        finally:
            obs_memory.enable(was)
        out.update({
            "live_bytes": c["live_bytes"],
            "tagged_bytes": c["tagged_bytes"],
            "orphan_bytes": c["orphan_bytes"],
            "coverage_frac": round(c["coverage_frac"], 4),
            "census_ms": round(c["census_ms"], 3),
            "owners": {o: r.get("bytes", 0)
                       for o, r in c["owners"].items()},
            "donation": obs_memory.donation_stats()})
        rows = obs_memory.island_attribution()
        if rows:
            out["island_peak_bytes"] = max(
                int(r.get("peak_bytes", 0) or 0) for r in rows)
            out["islands"] = len(rows)
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    return out


def _probe_parallelism(eng, prog, scope, feed, fetch, sync_ms):
    """Multi-axis placement-search probe (docs/PARALLELISM.md) on the
    already-built transformer: run the cost-driven placement search
    (purely static — nothing executes), report the chosen mesh +
    reduction strategy, the per-axis collective-bytes breakdown, the
    search wall time, and the static-vs-measured step-cost ratio (the
    measured headline step calibrates the cost model). Then prove the
    persistence loop: a second plan_for_program on the same program
    must replay from the tuning cache with ZERO search trials. A
    throwaway cache dir is used unless PT_TUNING_CACHE_DIR is set."""
    import shutil
    import tempfile
    out = {"sync_ms": round(sync_ms, 2)}
    own_cache = None
    if not os.environ.get("PT_TUNING_CACHE_DIR"):
        own_cache = tempfile.mkdtemp(prefix="pt_place_bench_")
        os.environ["PT_TUNING_CACHE_DIR"] = own_cache
    try:
        import jax
        from paddle_tpu.analysis import placement
        # search an 8-way mesh even on smaller hosts: the plan is
        # static, and 8 is the smallest count where data/fsdp/tp all
        # have room to trade off
        n = max(8, len(jax.devices()))
        t0 = time.perf_counter()
        plan = placement.plan_for_program(
            prog, n_devices=n, measured={"step_ms": sync_ms})
        search_ms = (time.perf_counter() - t0) * 1e3
        out.update({
            "n_devices": n,
            "mesh": plan.spec.to_dict(),
            "reduction": plan.reduction,
            "multi_axis": plan.multi_axis,
            "predicted_ms": round(plan.predicted_ms, 3),
            "baseline_data_parallel_ms": round(plan.baseline_ms, 3),
            "per_axis_collective_bytes": dict(plan.per_axis_bytes),
            "hbm_bytes": plan.hbm_bytes,
            "placement_search_ms": round(search_ms, 2),
            # uncalibrated pure-data prediction over the measured
            # step: how honest the static cost model is on this host
            "static_vs_measured_ratio": round(
                1.0 / plan.calibration, 4) if plan.calibration > 0
            else None})
        plan2 = placement.plan_for_program(prog, n_devices=n)
        out["cache_hit_second_run"] = bool(plan2.cached and
                                           plan2.trials == 0)
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    finally:
        if own_cache:
            os.environ.pop("PT_TUNING_CACHE_DIR", None)
            shutil.rmtree(own_cache, ignore_errors=True)
    return out


def _probe_pipeline(batch):
    """MPMD pipeline probe (docs/PARALLELISM.md) for the pipeline JSON
    tail: auto-cut a compact forward model into 2 stages (no manual
    cut_vars — parallel/auto_cut.py), run the interleaved 1F1B
    schedule, and report the slot table's measured bubble fraction
    against the analytic gpipe fill/drain bubble at the same
    microbatch count, the static per-stage HBM estimates, and the
    predicted-vs-measured step time (predicted = per-device busy time
    inflated by the measured bubble — how honest the schedule model is
    about the step it just dispatched)."""
    out = {}
    try:
        import paddle_tpu as fluid
        from paddle_tpu.core.scope import Scope
        from paddle_tpu.parallel.mpmd_pipeline import MPMDPipelineEngine

        fluid.framework.unique_name.reset()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("bx", [64], dtype="float32")
            y = fluid.layers.data("by", [1], dtype="int64")
            h = fluid.layers.fc(x, size=128, act="relu")
            h = fluid.layers.fc(h, size=128, act="relu")
            h = fluid.layers.fc(h, size=128, act="relu")
            pred = fluid.layers.fc(h, size=10, act="softmax")
            loss = fluid.layers.mean(
                fluid.layers.cross_entropy(input=pred, label=y))
        n_micro = 4
        b = max(n_micro, (min(batch, 32) // n_micro) * n_micro)
        rng = np.random.RandomState(0)
        feed = {"bx": rng.rand(b, 64).astype(np.float32),
                "by": rng.randint(0, 10, (b, 1)).astype(np.int64)}
        scope = Scope()
        with fluid.scope_guard(scope):
            fluid.Executor().run(startup)
            eng = MPMDPipelineEngine(main, loss.name, None, n_stages=2,
                                     num_microbatches=n_micro)
            eng.run(scope, feed)      # warmup: trace both stages
            ts = []
            for _ in range(5):
                t0 = time.perf_counter()
                eng.run(scope, feed)
                ts.append((time.perf_counter() - t0) * 1e3)
        st = eng.last_stats or {}
        measured_ms = sorted(ts)[len(ts) // 2]
        busy_ms = sum(s["dur_ms"] for s in st.get("spans") or ())
        bub = float(st.get("bubble_frac") or 0.0)
        nd = max(1, int(st.get("n_devices") or 1))
        predicted = (busy_ms / nd) / (1.0 - bub) if bub < 1.0 else None
        out.update({
            "n_stages": st.get("n_stages"),
            "n_devices": nd,
            "schedule": st.get("schedule"),
            "micro_batches": st.get("micro_batches"),
            "cut_vars": list(eng.cut_vars),
            "bubble_frac": bub,
            "bubble_frac_gpipe": st.get("bubble_frac_gpipe"),
            "pipeline_fill_frac": round(
                float(st.get("pipeline_fill_frac") or 0.0), 4),
            "stage_hbm_bytes": st.get("stage_hbm_bytes"),
            "activation_exchange_bytes":
                st.get("activation_exchange_bytes"),
            "step_ms": round(measured_ms, 3),
            "predicted_step_ms":
                round(predicted, 3) if predicted is not None else None,
            "predicted_vs_measured_ratio":
                round(predicted / measured_ms, 4)
                if predicted is not None and measured_ms > 0 else None})
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    return out


def _probe_analysis(eng, prog, scope, feed, fetch, stats, batch):
    """Program-verifier calibration probe (docs/STATIC_ANALYSIS.md) on
    the already-built transformer: the liveness-based static HBM plan
    reconciled against the measured owner census and per-island
    ``memory_analysis`` rows (``*_error_ratio`` is the acceptance
    number — the static plan must land within 25% of the measured
    census), the static cost model correlated against per-island
    dispatch spans and XLA's own flops figure, and the verifier's own
    wall time (it runs pre-compile, so it must stay cheap)."""
    out = {}
    try:
        from paddle_tpu.analysis import (analyze_program, plan_memory,
                                         reconcile)
        from paddle_tpu.observability import attribution as obs_attr
        from paddle_tpu.observability import memory as obs_memory

        t0 = time.perf_counter()
        diags = analyze_program(prog, feed_names=sorted(feed),
                                fetch_names=fetch)
        out["verifier_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        out["diagnostics"] = len(diags)

        plan = plan_memory(prog, feed_names=sorted(feed),
                           fetch_names=fetch, dynamic_dim=batch)
        was = obs_memory.census_enabled()
        obs_memory.enable(True)
        try:
            c = obs_memory.census()
        finally:
            obs_memory.enable(was)
        rec = reconcile(plan, census=c,
                        island_rows=obs_attr.island_memory_rows(eng)
                        or None,
                        measured_step=stats)
        out["static_peak_bytes"] = plan.peak_bytes
        for k in ("resident_error_ratio", "island_mean_error_ratio",
                  "temp_error_ratio"):
            if k in rec:
                out[k] = rec[k]

        cal = obs_attr.cost_calibration(eng, prog, dynamic_dim=batch,
                                        compiled_stats=stats)
        for k in ("static_total_flops", "flop_time_correlation",
                  "flops_ratio", "islands_matched"):
            if cal.get(k) is not None:
                out[k] = cal[k]
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    return out


def _probe_conformance(prog, fetch, batch):
    """Cross-path lowering conformance probe (docs/STATIC_ANALYSIS.md):
    extract the canonical lowering trace of the bench model on all four
    execution paths and diff them against the declared support matrix.
    The acceptance number is ``undeclared_divergences == 0`` — any
    undeclared drift between engine / scheduler / transpiled / dygraph
    lowering is a regression; ``verify_ms`` keeps the verifier honest
    about its pre-compile cost."""
    out = {}
    try:
        from paddle_tpu.analysis import (conformance_summary,
                                         extract_traces,
                                         verify_conformance)
        from paddle_tpu.analysis.conformance import TraceConfig

        cfg = TraceConfig.capability(dynamic_dim=batch)
        t0 = time.perf_counter()
        traces = extract_traces(prog, fetch_names=fetch, config=cfg)
        diags = verify_conformance(prog, fetch_names=fetch, config=cfg,
                                   traces=traces, label="bench")
        out["verify_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        out["paths"] = sorted(traces)
        s = conformance_summary(diags)
        out["declared_divergences"] = s["declared"]
        out["undeclared_divergences"] = s["undeclared"]
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    return out


def _probe_serving():
    """Continuous-batching serving probe for the serving JSON tail
    (docs/SERVING.md): export the book LM, warm every declared
    (batch, bucket) signature, then push a burst of mixed-length
    requests through the engine. The acceptance numbers are
    ``occupancy_mean > 1`` (requests actually share decode steps),
    ``parity_ok`` (tokens bit-identical to the solo baseline) and
    ``kv_pages_leaked == 0``; tools/serve_bench.py runs the same
    engine against a Poisson arrival process with a p99 CI gate."""
    out = {}
    try:
        import tempfile
        import paddle_tpu as fluid
        from paddle_tpu.inference.serving import (
            BucketSpec, ServingEngine, build_book_lm,
            export_serving_model, load_serving_model,
            reference_generate)
        d = os.path.join(tempfile.mkdtemp(prefix="bench_serve_"),
                         "model")
        fluid.framework.unique_name.reset()
        prefill, decode, startup, meta = build_book_lm(
            vocab=64, hidden=16, num_layers=2, max_len=64)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        bk = BucketSpec(batch=4, prefill_lens=(8,), cache_lens=(24,))
        export_serving_model(d, exe, prefill, decode, meta,
                             buckets=bk)
        model = load_serving_model(d)
        t0 = time.perf_counter()
        out["warmup_signatures"] = model.warmup()
        out["warmup_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(1, 64, size=rng.randint(2, 8)))
                   for _ in range(8)]
        eng = ServingEngine(model)
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        while eng.pending():
            eng.step()
        dt = time.perf_counter() - t0
        occ = eng.occupancy_history or [0]
        out["requests"] = len(reqs)
        out["completed"] = sum(1 for r in reqs if r.status == "ok")
        out["tokens_per_sec"] = round(
            sum(len(r.tokens) for r in reqs) / dt, 1)
        out["occupancy_mean"] = round(sum(occ) / len(occ), 2)
        out["occupancy_max"] = max(occ)
        out["kv_pages_leaked"] = eng.kv.pages_in_use
        out["parity_ok"] = all(
            r.tokens == reference_generate(model, p, 6)
            for r, p in zip(reqs[:3], prompts[:3]))
    except Exception as exc:   # accounting only; never fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"[:200]
    return out


def bench_transformer(batch=BATCH, seq=None, measure_ckpt=False):
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.core.engine import Engine
    from paddle_tpu.core.scope import Scope

    s_src = s_trg = seq or SRC_LEN
    # TF_HEADS: head-count knob at fixed d_model (d_head = 512/H).
    # H=4 -> d_head=128 fills full MXU tiles in the attention matmuls:
    # 108.9k tokens/s / 20.2% MFU at S=4096 vs 67.7k / 12.6% for the
    # reference-parity H=8/d_head=64 (July 2026, previous installation;
    # git history)
    cfg = models.transformer.transformer_base(
        src_vocab_size=32000, trg_vocab_size=32000, dropout=0.1,
        fuse_attention=True,
        n_head=int(os.environ.get("TF_HEADS", "8")))
    fluid.framework.unique_name.reset()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        cost, logits, feed_names = models.transformer_train(cfg)
        opt = fluid.optimizer.AdamOptimizer(learning_rate=2e-4)
        # bf16 MXU compute with fp32 master weights (the production
        # recipe; reference trains transformer fp16 on V100 similarly)
        opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(cost)
    scope = Scope()
    place = _tpu_place()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(startup)
        eng = Engine()
        feed = models.transformer.make_batch(cfg, batch, s_src, s_trg)
        K = int(os.environ.get("TF_ITERS", "1"))
        sps, traj, sync_ms = _loop(eng, main_prog, scope, place, feed,
                                   [cost.name], ITERS, iterations=K)
        stats = eng.compiled_stats(main_prog, scope, feed,
                                   [cost.name], iterations=K)
        if stats is not None:
            # comm-scheduler accounting for the BENCH json tail
            # (zeros on a single-device mesh — no grad collectives)
            stats["comm"] = dict(eng.counters)
        if measure_ckpt:
            _bench_checkpoint(exe, scope, main_prog)
            # headline run only: scheduler-on sync A/B for the
            # scheduler_overlap JSON tail (ROADMAP open item 4)
            stats = stats or {}
            stats["scheduler"] = _probe_scheduler(
                eng, main_prog, scope, feed, [cost.name], sync_ms)
            # K-substep fused-dispatch A/B for the multistep JSON
            # tail (PT_MULTI_STEP, docs/ASYNC_DISPATCH.md)
            stats["multistep"] = _probe_multistep(
                eng, main_prog, scope, feed, [cost.name], sync_ms)
            # guard-on sync A/B for the stability JSON tail
            stats["stability"] = _probe_guard(
                eng, main_prog, scope, feed, [cost.name], sync_ms)
            # kernels-off sync A/B + registry hit rates for the
            # kernels JSON tail (ROADMAP open item 3)
            stats["kernels"] = _probe_kernels(
                eng, main_prog, scope, feed, [cost.name], sync_ms)
            # measured device-time attribution + measured MFU for the
            # tracing JSON tail (docs/TRACING.md)
            stats["tracing"] = _probe_tracing(
                eng, main_prog, scope, feed, [cost.name], sync_ms)
            # feedback-directed autotune loop (search -> persist ->
            # cache hit) for the tuning JSON tail (docs/TUNING.md)
            stats["tuning"] = _probe_tuning(
                eng, main_prog, scope, feed, [cost.name], sync_ms)
            # owner-attributed live-buffer census + donation
            # effectiveness for the memory JSON tail (docs/MEMORY.md)
            stats["memory"] = _probe_memory(
                eng, main_prog, scope, feed, [cost.name], sync_ms)
            # static-vs-measured verifier calibration for the analysis
            # JSON tail (docs/STATIC_ANALYSIS.md)
            stats["analysis"] = _probe_analysis(
                eng, main_prog, scope, feed, [cost.name], stats, batch)
            # cross-path lowering conformance for the conformance
            # JSON tail (docs/STATIC_ANALYSIS.md)
            stats["conformance"] = _probe_conformance(
                main_prog, [cost.name], batch)
            # cost-driven multi-axis placement search for the
            # parallelism JSON tail (docs/PARALLELISM.md)
            stats["parallelism"] = _probe_parallelism(
                eng, main_prog, scope, feed, [cost.name], sync_ms)
            # auto-cut 1F1B pipeline schedule accounting for the
            # pipeline JSON tail (docs/PARALLELISM.md)
            stats["pipeline"] = _probe_pipeline(batch)
            # continuous-batching serving engine probe for the
            # serving JSON tail (docs/SERVING.md)
            stats["serving"] = _probe_serving()
    return sps * batch * s_trg, sps, traj, sync_ms, stats


def bench_transformer_longctx():
    """Long-context regime (S=4096): attention runs on the Pallas flash
    kernels (fwd + dq/dkv backward, in-kernel dropout, causal decoder
    block-skipping) — the composed path's [B,H,S,S] tensors would need
    ~4.3 GB temp HBM per layer pair."""
    return bench_transformer(
        batch=int(os.environ.get("TF_BATCH", "4")),
        seq=int(os.environ.get("TF_SEQ", "4096")))


def bench_transformer_s1024():
    """Mid-range shape guarding the measured kernel/composed dispatch
    crossover (VERDICT r4 #2): S=1024 sits just ABOVE the
    sequence-keyed threshold (Sq*Sk >= 1024^2), where the kernels beat
    composed ~2x (dispatch table in kernels/flash_attention.py)."""
    return bench_transformer(
        batch=int(os.environ.get("TF_BATCH", "8")),
        seq=int(os.environ.get("TF_SEQ", "1024")))


def bench_transformer_canonical():
    """Reference-era canonical shape (VERDICT r3 #3): S=256, 32k vocab,
    batch chosen by sweep (B in 16/24/32/48/64/96 -> 32 best: 186.5k
    tokens/s at 37.4% MFU; attention's S^2 term punishes larger B)."""
    return bench_transformer(
        batch=int(os.environ.get("TF_BATCH", "32")),
        seq=int(os.environ.get("TF_SEQ", "256")))


def bench_lenet():
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.core.engine import Engine
    from paddle_tpu.core.scope import Scope

    B = 512
    fluid.framework.unique_name.reset()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        cost, acc, feeds = models.lenet_train()
        fluid.optimizer.AdamOptimizer(3e-4).minimize(cost)
    rng = np.random.RandomState(0)
    batch = {"img": rng.rand(B, 1, 28, 28).astype(np.float32),
             "label": rng.randint(0, 10, (B, 1)).astype(np.int64)}
    scope = Scope()
    place = _tpu_place()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(startup)
        eng = Engine()
        # the sub-ms LeNet step is DISPATCH-bound: even amortized over
        # iterations=16 the per-dispatch launch floor is a large share
        # of the measured step. Policy: co-measure the floor each
        # window, repeat until 3 CONSECUTIVE windows agree within 15%,
        # publish that stable median + the all-window IQR + the floor
        # correlation so the number describes the chip, not the host's
        # dispatch jitter.
        def _floor_probe(n=8):
            import jax
            import jax.numpy as jnp
            x = jnp.ones((8, 128), jnp.float32)
            f = jax.jit(lambda x: x * 2.0 + 1.0)
            float(f(x)[0, 0])
            t0 = time.time()
            for _ in range(n):
                r = f(x)
            float(r[0, 0])
            return (time.time() - t0) / n * 1e3

        runs, floors = [], []
        stable = None
        for w in range(15):
            floors.append(_floor_probe())
            sps_i, traj, sync_ms = _loop(eng, main_prog, scope, place,
                                         batch, [cost.name], 20,
                                         iterations=16)
            runs.append(sps_i)
            if len(runs) >= 3:
                last3 = runs[-3:]
                if max(last3) / min(last3) <= 1.15:
                    stable = sorted(last3)[1]
                    break
        srt = sorted(runs)
        q1 = srt[len(srt) // 4]
        q3 = srt[(3 * len(srt)) // 4]
        sps = stable if stable is not None else srt[len(srt) // 2]
        corr = float(np.corrcoef(
            np.array(floors), 1.0 / np.array(runs))[0, 1]) \
            if len(runs) >= 3 else float("nan")
        print(f"# mnist_lenet: {'STABLE' if stable else 'UNSTABLE'} "
              f"after {len(runs)} windows "
              f"(policy: 3 consecutive within 15%); "
              f"IQR {q1 * B:.0f}..{q3 * B:.0f} img/s; "
              f"co-measured launch floor "
              f"{min(floors):.1f}-{max(floors):.1f} ms "
              f"(corr with step time {corr:.2f}, n={len(runs)} — "
              f"noisy; the dispatch-bound diagnosis rests on sync "
              f"latency vs device-only below)", file=sys.stderr)
        stats = eng.compiled_stats(main_prog, scope, batch, [cost.name], iterations=16)
        if stats is not None:
            # static-vs-measured verifier calibration (second model
            # class for the acceptance bar: MLP/conv alongside the
            # headline transformer)
            stats["analysis"] = _probe_analysis(
                eng, main_prog, scope, batch, [cost.name], stats, B)
            stats["conformance"] = _probe_conformance(
                main_prog, [cost.name], B)
    return sps * B, sps, traj, sync_ms, stats


def bench_resnet50():
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.core.engine import Engine
    from paddle_tpu.core.scope import Scope

    B = int(os.environ.get("RN_BATCH", "128"))
    # RN_LAYOUT=NHWC: channels-last convs
    layout = os.environ.get("RN_LAYOUT", "NCHW")
    fluid.framework.unique_name.reset()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        cost, acc, feeds = models.resnet_train(depth=50, layout=layout)
        opt = fluid.optimizer.MomentumOptimizer(0.1, 0.9)
        opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(cost)
    rng = np.random.RandomState(0)
    img_shape = (B, 224, 224, 3) if layout == "NHWC" else \
        (B, 3, 224, 224)
    batch = {"image": rng.rand(*img_shape).astype(np.float32),
             "label": rng.randint(0, 1000, (B, 1)).astype(np.int64)}
    scope = Scope()
    place = _tpu_place()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(startup)
        eng = Engine()
        K = int(os.environ.get("RN_ITERS", "4"))
        sps, traj, sync_ms = _loop(eng, main_prog, scope, place, batch,
                                   [cost.name], 20, iterations=K)
        stats = eng.compiled_stats(main_prog, scope, batch, [cost.name], iterations=K)
    return sps * B, sps, traj, sync_ms, stats


def bench_ctr():
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.core.engine import Engine
    from paddle_tpu.core.scope import Scope

    B = int(os.environ.get("CTR_BATCH", "4096"))
    num_slots, num_dense = 26, 13
    fluid.framework.unique_name.reset()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        cost, prob, feeds = models.ctr_train(vocab_size=1000001)
        fluid.optimizer.AdagradOptimizer(0.01).minimize(cost)
    rng = np.random.RandomState(0)
    batch = {
        "slot_ids": rng.randint(0, 1000001,
                                (B, num_slots)).astype(np.int32),
        "dense_feat": rng.rand(B, num_dense).astype(np.float32),
        "ctr_label": rng.randint(0, 2, (B, 1)).astype(np.float32)}
    scope = Scope()
    place = _tpu_place()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(startup)
        eng = Engine()
        sps, traj, sync_ms = _loop(eng, main_prog, scope, place, batch,
                                   [cost.name], 30)
        stats = eng.compiled_stats(main_prog, scope, batch, [cost.name])
    return sps * B, sps, traj, sync_ms, stats


class _DyBottleneck:
    """ResNet-50 bottleneck as a dygraph Layer factory."""

    def __new__(cls, name, ch, stride, shortcut):
        import paddle_tpu as fluid
        from paddle_tpu import dygraph

        class Block(dygraph.Layer):
            def __init__(self):
                super().__init__(name)
                self.c1 = dygraph.nn.Conv2D(name + "_1", ch, 1,
                                            bias_attr=False)
                self.b1 = dygraph.nn.BatchNorm(name + "_b1", act="relu")
                self.c2 = dygraph.nn.Conv2D(name + "_2", ch, 3,
                                            stride=stride, padding=1,
                                            bias_attr=False)
                self.b2 = dygraph.nn.BatchNorm(name + "_b2", act="relu")
                self.c3 = dygraph.nn.Conv2D(name + "_3", ch * 4, 1,
                                            bias_attr=False)
                self.b3 = dygraph.nn.BatchNorm(name + "_b3")
                self.shortcut = shortcut
                if not shortcut:
                    self.cs = dygraph.nn.Conv2D(name + "_s", ch * 4, 1,
                                                stride=stride,
                                                bias_attr=False)
                    self.bs = dygraph.nn.BatchNorm(name + "_bs")

            def forward(self, x):
                y = self.b3(self.c3(self.b2(self.c2(
                    self.b1(self.c1(x))))))
                sc = x if self.shortcut else self.bs(self.cs(x))
                return fluid.layers.relu(
                    fluid.layers.elementwise_add(sc, y))

        return Block()


def _dygraph_resnet50():
    """Full ResNet-50 (bottleneck [3,4,6,3]) as a dygraph Layer — the
    model BASELINE.json config 5 names (parity with models/resnet.py)."""
    import paddle_tpu as fluid
    from paddle_tpu import dygraph

    class ResNet50(dygraph.Layer):
        def __init__(self):
            super().__init__("dyres")
            self.stem = dygraph.nn.Conv2D("stem", 64, 7, stride=2,
                                          padding=3, bias_attr=False)
            self.bn = dygraph.nn.BatchNorm("stem_bn", act="relu")
            self.pool = dygraph.nn.Pool2D("pool", 3, "max", 2, 1)
            self.blocks = []
            in_stage = [(64, 3, 1), (128, 4, 2), (256, 6, 2),
                        (512, 3, 2)]
            for si, (ch, n, stride) in enumerate(in_stage):
                for bi in range(n):
                    blk = _DyBottleneck(f"s{si}b{bi}", ch,
                                        stride if bi == 0 else 1,
                                        shortcut=bi != 0)
                    setattr(self, f"blk_{si}_{bi}", blk)
                    self.blocks.append(blk)
            self.gap = dygraph.nn.Pool2D("gap", global_pooling=True,
                                         pool_type="avg")
            self.fc = dygraph.nn.FC("fc", 1000)

        def forward(self, x):
            h = self.pool(self.bn(self.stem(x)))
            for blk in self.blocks:
                h = blk(h)
            return self.fc(self.gap(h))

    return ResNet50()


def bench_dygraph():
    """BASELINE.json config 5: dygraph ResNet-50 under
    dygraph.jit.capture with amp=True (bf16 activation stream, fp32
    master params) — one compiled executable per step; eager per-op
    dispatch compiles ~500 unique op shapes before the first step
    finishes."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import dygraph

    B = int(os.environ.get("DY_BATCH", "128"))
    rng = np.random.RandomState(0)
    xs = rng.rand(B, 3, 224, 224).astype(np.float32)
    ys = rng.randint(0, 1000, (B, 1)).astype(np.int64)
    # Eager per-op dispatch pays a compile per op shape (~500 unique
    # shapes), which is the whole point of the capture. The capture's
    # discovery pass is host-only (abstract), so NO eager step ever
    # runs: params materialize on the chip and every real step is one
    # compiled dispatch.
    tpu_dev = _tpu_place().jax_device()
    with dygraph.guard(fluid.CPUPlace()):
        net = _dygraph_resnet50()
        opt = fluid.optimizer.MomentumOptimizer(0.1, 0.9)

        def step(x, y):
            logits = net(x)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            loss.backward()
            opt.minimize(loss)
            net.clear_gradients()
            return loss

        captured = dygraph.jit.capture(step, optimizer=opt,
                                       device=tpu_dev, amp=True)
        # device-resident feeds: measure the chip, not the host link
        # (same discipline as _loop)
        xs_d = jax.device_put(xs, tpu_dev)
        ys_d = jax.device_put(ys, tpu_dev)
        for _ in range(2):
            l = captured(xs_d, ys_d)
        float(np.asarray(l.numpy()))

        def window(n):
            t0 = time.perf_counter()
            for _ in range(n):
                l = captured(xs_d, ys_d)
            float(np.asarray(l.numpy()))   # fetch fence
            return time.perf_counter() - t0

        t1, t2 = window(10), window(20)
        sps = 10 / (t2 - t1) if t2 - t1 > 0.02 * t2 else 30 / (t1 + t2)
        final = float(np.asarray(l.numpy()))
    print(f"# dygraph resnet50 under jit.capture: {sps * B:.0f} img/s "
          f"at 224x224", file=sys.stderr)
    return sps * B, sps, final, None, None


def _config_table():
    return {
        "transformer_s256": (bench_transformer_canonical, "tokens/sec"),
        "transformer_s1024": (bench_transformer_s1024, "tokens/sec"),
        "transformer_s4096": (bench_transformer_longctx, "tokens/sec"),
        "mnist_lenet": (bench_lenet, "images/sec"),
        "resnet50": (bench_resnet50, "images/sec"),
        "wide_deep_ctr": (bench_ctr, "examples/sec"),
        "dygraph_resnet50": (bench_dygraph, "images/sec"),
    }


def _run_one(name):
    table = _config_table()
    if name not in table:
        raise SystemExit(f"unknown --config {name!r}; valid: "
                         f"{sorted(table)}")
    fn, unit = table[name]
    _tpu_place()    # fail before building anything when there is no TPU
    rate, sps, traj, sync_ms, stats = fn()
    if isinstance(traj, tuple):
        tr = "->".join(f"{v:.4f}" for v in traj)
    else:
        tr = f"{traj:.4f}"
    print(f"# {name}: {rate:.0f} {unit} "
          f"(steps/s={sps:.2f} loss {tr})", file=sys.stderr)
    for line in _mfu_lines(name, sps, sync_ms, stats):
        print(line, file=sys.stderr)


def main():
    if "--config" in sys.argv:
        idx = sys.argv.index("--config") + 1
        if idx >= len(sys.argv):
            raise SystemExit(
                f"--config needs a name; valid: "
                f"{sorted(_config_table())}")
        _run_one(sys.argv[idx])
        return
    if "--all" in sys.argv:
        # EVERY config (headline included) in a FRESH process: a
        # previous model's live scope keeps HBM occupied and can slow
        # a later config >20x
        import subprocess
        # a chip belongs to one process: this parent must never have
        # brought a JAX backend up, or every child fails to open it
        xb = sys.modules.get("jax._src.xla_bridge")
        assert xb is None or not xb._backends, \
            "bench.py --all parent initialised a JAX backend"
        me = os.path.abspath(__file__)
        failed = []
        r = subprocess.run([sys.executable, me],
                           capture_output=True, text=True)
        if r.returncode == 0:
            sys.stdout.write(r.stdout)      # the driver's JSON line
            for line in r.stderr.splitlines():
                if line.startswith("#"):
                    print(line, file=sys.stderr)
        else:
            failed.append("transformer")
            print(f"# headline transformer: FAILED\n{r.stderr[-500:]}",
                  file=sys.stderr)
        for name in _config_table():
            r = subprocess.run([sys.executable, me, "--config", name],
                               capture_output=True, text=True)
            if r.returncode == 0:
                for line in r.stderr.splitlines():
                    if line.startswith("#"):
                        print(line, file=sys.stderr)
            else:
                failed.append(name)
                print(f"# {name}: FAILED\n{r.stderr[-500:]}",
                      file=sys.stderr)
        if failed:
            sys.exit(f"bench.py --all: failed configs: {failed}")
        return
    _tpu_place()    # fail before building anything when there is no TPU
    tokens_per_sec, sps, traj, sync_ms, stats = bench_transformer(
        measure_ckpt=True)
    comm, comm_line = {}, None
    try:
        from tools.comm_bench import comm_overlap_report
        comm, comm_line = comm_overlap_report(
            (stats or {}).get("comm"))
    except Exception:
        pass   # accounting only; never fail the bench on it
    sched, sched_line = {}, None
    try:
        from tools.step_overhead_bench import scheduler_overlap_report
        sched, sched_line = scheduler_overlap_report(
            (stats or {}).get("scheduler"))
    except Exception:
        pass   # accounting only; never fail the bench on it
    mstep, mstep_line = {}, None
    try:
        from tools.step_overhead_bench import multistep_report
        mstep, mstep_line = multistep_report(
            (stats or {}).get("multistep"))
    except Exception:
        pass   # accounting only; never fail the bench on it
    stab, stab_line = {}, None
    try:
        from tools.step_overhead_bench import guard_overhead_report
        stab, stab_line = guard_overhead_report(
            (stats or {}).get("stability"))
    except Exception:
        pass   # accounting only; never fail the bench on it
    kern, kern_line = {}, None
    try:
        from tools.kernel_bench import kernels_report
        kern, kern_line = kernels_report((stats or {}).get("kernels"))
    except Exception:
        pass   # accounting only; never fail the bench on it
    trac, trac_line = (stats or {}).get("tracing") or {}, None
    if trac:
        mfu = trac.get("mfu_estimate")
        dev = trac.get("device_ms_per_step")
        trac_line = (f"# tracing: device_ms="
                     f"{dev if dev is not None else 'n/a'} "
                     f"mfu_estimate={mfu if mfu is not None else 'n/a'}"
                     f" ({trac.get('mfu_basis') or 'n/a'}) "
                     f"hbm_peak={trac.get('hbm_peak_bytes') or 'n/a'}")
    tun, tun_line = {}, None
    try:
        from tools.step_overhead_bench import tuning_report
        tun, tun_line = tuning_report((stats or {}).get("tuning"))
    except Exception:
        pass   # accounting only; never fail the bench on it
    memr, mem_line = (stats or {}).get("memory") or {}, None
    if memr and "coverage_frac" in memr:
        don = memr.get("donation") or {}
        eff = don.get("effectiveness_frac")
        mem_line = (f"# memory: census coverage="
                    f"{memr['coverage_frac']:.2f} live="
                    f"{memr['live_bytes']} B orphan="
                    f"{memr['orphan_bytes']} B in "
                    f"{memr['census_ms']:.1f} ms; donation "
                    f"effectiveness="
                    f"{eff if eff is None else format(eff, '.2f')} "
                    f"({don.get('donated_names', 0)} donated vars)")
    chaos, chaos_line = {}, None
    if os.environ.get("PT_BENCH_CHAOS"):
        # opt-in: spawns a 2-trainer PS job twice (clean + faulted),
        # ~1 min on CPU — too slow for the default bench path
        try:
            from tools.chaos_report import chaos_report_line
            chaos, chaos_line = chaos_report_line()
        except Exception:
            pass   # survival accounting only; never fail the bench
    metrics_tail = None
    try:
        # fleet-view tail: everything the run's registry accumulated
        # (step-phase histograms, ckpt timings, rpc/heartbeat counters)
        # so one BENCH json line carries the full telemetry snapshot
        # for tools/metrics_report.py to diff across runs
        from paddle_tpu.observability.export import metrics_snapshot
        snap = metrics_snapshot()
        metrics_tail = {name: fam for name, fam in snap.items()
                        if any(s.get("count") or s.get("value")
                               for s in fam.get("samples", []))}
    except Exception:
        pass   # accounting only; never fail the bench on it
    print(json.dumps({
        "metric": "transformer_base_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tokens_per_sec / V100_TOKENS_PER_SEC, 3),
        "comm_overlap": comm or None,
        "scheduler_overlap": sched or None,
        "multistep": mstep or None,
        "stability": stab or None,
        "kernels": kern or None,
        "tracing": trac or None,
        "tuning": tun or None,
        "memory": memr or None,
        "chaos": chaos or None,
        "metrics": metrics_tail or None,
    }))
    if comm_line:
        print(comm_line, file=sys.stderr)
    if sched_line:
        print(sched_line, file=sys.stderr)
    if mstep_line:
        print(mstep_line, file=sys.stderr)
    if stab_line:
        print(stab_line, file=sys.stderr)
    if kern_line:
        print(kern_line, file=sys.stderr)
    if trac_line:
        print(trac_line, file=sys.stderr)
    if tun_line:
        print(tun_line, file=sys.stderr)
    if mem_line:
        print(mem_line, file=sys.stderr)
    if chaos_line:
        print(chaos_line, file=sys.stderr)
    print(f"# transformer: steps/s={sps:.2f} "
          f"loss {traj[0]:.4f}->{traj[1]:.4f}->{traj[2]:.4f}",
          file=sys.stderr)
    for line in _mfu_lines("transformer", sps, sync_ms, stats):
        print(line, file=sys.stderr)


if __name__ == "__main__":
    main()
