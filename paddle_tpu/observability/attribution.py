"""Device-time and HBM attribution for compiled steps.

The reference Fluid's CUPTI ``DeviceTracer`` tied kernel time back to
framework ops; on the TPU the equivalents are the compiled
executable's ``cost_analysis()`` / ``memory_analysis()`` (analytic,
always available) and ``jax.profiler`` device events (measured,
captured on demand). This module joins the two with the artifacts the
rebuild already has:

* **HLO source-tag parsing** (``tools/hbm_breakdown``): every entry
  instruction carries ``metadata={source_file, source_line, op_name}``
  pointing into our op lowerings, so traffic and instruction counts
  attribute to framework op categories — including the registry's
  ``kernel:<name>`` categories for custom Pallas kernels (PR 9).
* **ProgramDesc ops**: the block's op list gives the framework-side
  inventory the HLO categories map onto.
* **Scheduler islands**: the op scheduler's per-island host dispatch
  spans apportion the measured device total per island (labeled
  estimate — XLA device events carry no island tag, so the split uses
  each island's share of host dispatch time).
* **Measured MFU**: analytic FLOPs per step over the *measured* device
  seconds per step (``tools/time_breakdown.device_events``) against
  the chip's dense peak — the first measured-MFU number in the bench
  trajectory (the bench's existing MFU line is analytic, derived from
  host steps/s).

Live gauges: ``pt_island_device_seconds{island=...}``,
``pt_hbm_peak_bytes``, ``pt_mfu_estimate``.

**Deep profile trigger.** ``PT_DEEP_PROFILE_EVERY=N`` (or an explicit
:func:`request_deep_profile` call) makes the engine's obs-finish hook
capture K = ``PT_DEEP_PROFILE_STEPS`` steps under ``jax.profiler`` and
then emit ONE merged chrome timeline — device events + this process's
span and flight dumps + any other worker's dumps sharing the flight
directory — via :func:`observability.export.merge_chrome_traces`, as
``timeline_<pid>_<seq>.json`` next to the dumps. Everything here runs
at analysis/dump time except the per-step :func:`deep_profile_tick`
counter, which sits behind the ``_HOT`` gate.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

from . import metrics as _metrics
from . import recorder as _recorder
from . import tracing as _tracing

__all__ = ["attribute", "measure_device_time", "mfu_estimate",
           "PEAK_TFLOPS", "peak_tflops",
           "island_rows", "island_memory_rows", "program_ops",
           "hlo_text", "request_deep_profile", "deep_profile_tick",
           "deep_profile_active", "cost_calibration"]

# Dense bf16 matmul peak TFLOP/s per chip, keyed by the device_kind
# JAX reports (Google Cloud TPU documentation, per-generation system
# architecture pages). The ONE peak table in the tree: bench.py,
# tools/kernel_roofline.py and tools/op_bench.py read it through
# peak_tflops(); a device that is not listed is an error, never a
# default.
PEAK_TFLOPS = {
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v5": 459.0,
    "TPU v4": 275.0,
    "TPU v3": 123.0,
    "TPU v2": 46.0,
}


def peak_tflops(device_kind: str) -> float:
    """Dense bf16 peak of one chip of *device_kind*; unknown raises."""
    try:
        return PEAK_TFLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak TFLOP/s entry for device_kind {device_kind!r}; "
            f"known: {sorted(PEAK_TFLOPS)} — add the chip to "
            f"observability/attribution.py PEAK_TFLOPS with its "
            f"source") from None


def mfu_estimate(flops, seconds_per_step) -> Optional[float]:
    """Measured MFU: analytic FLOPs per step over measured seconds per
    step against the chip's dense peak. None when JAX is not running on
    a TPU (a host backend has no MXU peak to be a fraction of); an
    unlisted TPU device_kind raises."""
    import jax
    if jax.default_backend() != "tpu":
        return None
    peak = peak_tflops(jax.devices()[0].device_kind)
    if not flops or not seconds_per_step:
        return None
    return float(flops) / float(seconds_per_step) / (peak * 1e12)


# ---------------------------------------------------------------------------
# static attribution: HLO categories + ProgramDesc ops
# ---------------------------------------------------------------------------

def hlo_text(engine, program, scope, feed, fetch_names,
             block_idx: int = 0, iterations: int = 1) -> Optional[str]:
    """Optimized HLO of the already-run step (None on the eager
    fallback)."""
    try:
        compiled = engine.compiled_step(program, scope, feed,
                                        fetch_names,
                                        block_idx=block_idx,
                                        iterations=iterations)
        return compiled.as_text() if compiled is not None else None
    except Exception:
        return None


def program_ops(program, block_idx: int = 0) -> Dict[str, int]:
    """ProgramDesc op inventory: {op type -> count} for the block the
    HLO categories attribute onto."""
    out: Dict[str, int] = {}
    try:
        for op in program.blocks[block_idx].ops:
            t = getattr(op, "type", None) or "?"
            out[t] = out.get(t, 0) + 1
    except Exception:
        pass
    return out


def island_rows(engine, device_ms_total: Optional[float] = None
                ) -> List[Dict]:
    """Per-island attribution from the op scheduler's last dispatch:
    island index, phase, op count, host dispatch span, and — when a
    measured device total is available — the island's device-time
    estimate apportioned by host-span share (sets the
    ``pt_island_device_seconds`` gauge)."""
    rows: List[Dict] = []
    for traced in list(getattr(engine, "_cache", {}).values()):
        sched = getattr(traced, "op_sched", None)
        if sched is None or not getattr(sched, "last_stats", None):
            continue
        spans = sched.last_stats.get("spans") or []
        host_total = sum(float(s.get("dur_ms") or 0.0) for s in spans)
        for s in spans:
            idx = s.get("i", s.get("lane", s.get("micro_batch")))
            row = {"island": idx, "phase": s.get("phase"),
                   "ops": s.get("ops"),
                   "host_ms": s.get("dur_ms")}
            if device_ms_total and host_total > 0:
                dev_ms = (device_ms_total
                          * float(s.get("dur_ms") or 0.0) / host_total)
                row["device_ms_est"] = round(dev_ms, 3)
                try:
                    _metrics.gauge("pt_island_device_seconds").set(
                        dev_ms / 1e3, island=str(idx))
                except Exception:
                    pass
            rows.append(row)
        if rows:
            break  # one scheduled trace is the step being attributed
    return rows


def island_memory_rows(engine) -> List[Dict]:
    """Per-island compiled-memory attribution: lower each scheduler
    island's own executable against the signatures recorded by the
    build pass and read its ``memory_analysis()`` —
    argument/temp/output byte split plus the island peak (argument +
    temp), exported as ``pt_island_hbm_peak_bytes{island}`` on the
    same global island index the device-time rows use. Rows are cached
    on the scheduled step (island signatures are fixed after build, so
    the lowering cost is paid once) and pushed to the memory
    observatory so postmortem dumps carry them. Empty when no
    scheduler-split trace exists (whole-step ``pt_hbm_peak_bytes``
    covers that case)."""
    for traced in list(getattr(engine, "_cache", {}).values()):
        sched = getattr(traced, "op_sched", None)
        if sched is None or not getattr(sched, "phases", None):
            continue
        rows = getattr(sched, "_mem_rows", None)
        if rows is None:
            rows = _island_memory_rows(sched)
            sched._mem_rows = rows
        if not rows:
            continue
        for r in rows:
            try:
                _metrics.gauge("pt_island_hbm_peak_bytes").set(
                    float(r["peak_bytes"]), island=str(r["island"]))
            except Exception:
                pass
        try:
            from . import memory as _memory
            _memory.set_island_attribution(rows)
        except Exception:
            pass
        return [dict(r) for r in rows]
    return []


def _island_memory_rows(sched) -> List[Dict]:
    sig = getattr(sched, "_final_sig", None)
    if not sig:
        return []
    try:
        import jax
        import jax.numpy as jnp
        # same key signature convention as Engine._compiled_entry
        key_sig = jax.ShapeDtypeStruct((2,), jnp.uint32)
    except Exception:
        return []
    rows: List[Dict] = []
    idx = 0
    for phase in sched.phases:
        for isl in phase:
            try:
                ins_sig = {n: sig[n] for n in isl.in_names if n in sig}
                ma = isl.jfn.lower(ins_sig, key_sig).compile() \
                    .memory_analysis()
                arg = float(getattr(ma, "argument_size_in_bytes", 0.0))
                tmp = float(getattr(ma, "temp_size_in_bytes", 0.0))
                outb = float(getattr(ma, "output_size_in_bytes", 0.0))
                rows.append({
                    "island": idx, "phase": isl.phase,
                    "ops": len(isl.indices),
                    "argument_bytes": arg, "temp_bytes": tmp,
                    "output_bytes": outb, "peak_bytes": arg + tmp})
            except Exception:
                pass  # one un-lowerable island must not kill the rest
            idx += 1
    return rows


def cost_calibration(engine, program, device_ms_total: Optional[float] = None,
                     dynamic_dim: int = 1,
                     compiled_stats: Optional[Dict] = None) -> Dict:
    """Static-vs-measured cost comparison on the shared island index:
    the analysis cost model's per-island FLOP shares against the
    measured per-island device-time shares (``island_rows``), plus the
    whole-program static FLOP count against XLA's own
    ``compiled_stats`` figure. The Pearson correlation is the headline
    calibration number — it says whether the static model *ranks*
    islands the way the hardware does, which is all the placement
    search needs from it."""
    from ..analysis import cost_model
    out: Dict = {}
    try:
        cost = cost_model.program_cost(program, dynamic_dim=dynamic_dim)
        static_rows = cost_model.island_cost_rows(program, cost)
        out["static_total_flops"] = cost.total_flops
        out["static_total_bytes"] = cost.total_bytes
    except Exception as exc:
        return {"error": f"{type(exc).__name__}: {exc}"}
    measured = island_rows(engine, device_ms_total=device_ms_total)
    by_idx = {r["island"]: r for r in measured
              if r.get("island") is not None}
    xs, ys = [], []
    for r in static_rows:
        m = by_idx.get(r["island"])
        if m is None:
            continue
        t = m.get("device_ms_est", m.get("host_ms"))
        if t is None:
            continue
        xs.append(float(r["flops"]))
        ys.append(float(t))
    out["islands_matched"] = len(xs)
    out["flop_time_correlation"] = cost_model.correlation(xs, ys)
    if compiled_stats:
        xla = float(compiled_stats.get("flops") or 0.0)
        if xla > 0:
            out["xla_flops"] = xla
            out["flops_ratio"] = cost.total_flops / xla
    return out


# ---------------------------------------------------------------------------
# measured device time (on-demand jax.profiler capture)
# ---------------------------------------------------------------------------

def measure_device_time(run_step: Callable[[], object],
                        steps: int = 3, top: int = 10
                        ) -> Optional[Dict]:
    """Capture ``steps`` steps under ``jax.profiler`` and sum the "XLA
    Ops" device lanes (``tools/time_breakdown``). Returns
    {device_ms_per_step, host_ms_per_step, events[:top]} — device
    fields are None on CPU hosts (the chrome trace has no device
    lanes there), host wall time is always measured."""
    out: Dict = {"steps": int(steps)}
    t0 = time.perf_counter()
    trace_path = None
    tmp = tempfile.mkdtemp(prefix="pt_attr_trace_")
    try:
        from ..tools import time_breakdown as tb
        trace_path = tb.trace_step(run_step, steps=steps,
                                   trace_dir=tmp)
    except Exception:
        # profiler unavailable: still measure host wall time
        try:
            for _ in range(int(steps)):
                run_step()
        except Exception:
            return None
    out["host_ms_per_step"] = round(
        (time.perf_counter() - t0) / max(1, int(steps)) * 1e3, 3)
    out["device_ms_per_step"] = None
    if trace_path:
        try:
            from ..tools import time_breakdown as tb
            events = tb.device_events(trace_path)
            total_us = sum(t for _, t, _ in events)
            if total_us > 0:
                out["device_ms_per_step"] = round(
                    total_us / 1e3 / max(1, int(steps)), 3)
                out["events"] = [
                    {"name": n, "us": round(t, 1), "count": c}
                    for n, t, c in events[:top]]
        except Exception:
            pass
    shutil.rmtree(tmp, ignore_errors=True)
    return out


# ---------------------------------------------------------------------------
# the joined report
# ---------------------------------------------------------------------------

def attribute(engine, program, scope, feed, fetch_names,
              block_idx: int = 0, iterations: int = 1,
              profile_steps: int = 0, top: int = 12) -> Dict:
    """One attribution report for an already-run step. Analytic parts
    (cost/memory analysis, HLO category rows, ProgramDesc inventory,
    island host spans) always compute; ``profile_steps > 0``
    additionally captures that many steps under ``jax.profiler`` for
    measured device time, per-island device estimates, and the
    measured-MFU gauge. Never raises — failed sections are absent and
    a top-level "error" key reports a total miss."""
    rep: Dict = {}
    try:
        stats = engine.compiled_stats(program, scope, feed, fetch_names,
                                      block_idx=block_idx,
                                      iterations=iterations)
    except Exception:
        stats = None
    if stats:
        rep["cost"] = {k: stats.get(k)
                       for k in ("flops", "bytes_accessed",
                                 "temp_bytes", "argument_bytes",
                                 "trip_count")
                       if stats.get(k) is not None}
        peak_bytes = (stats.get("temp_bytes") or 0.0) + \
            (stats.get("argument_bytes") or 0.0)
        if peak_bytes:
            rep["hbm_peak_bytes"] = peak_bytes
    # scheduler-aware HBM peak: when FLAGS_op_scheduler split the step,
    # compiled_stats is None (a ScheduledStep has no .lower) and the
    # whole-step gauge used to go stale/unset — the step's footprint is
    # then the max over its islands' own compiled peaks
    mem_rows = island_memory_rows(engine)
    if mem_rows:
        rep["islands_memory"] = mem_rows
        island_peak = max(float(r.get("peak_bytes") or 0.0)
                          for r in mem_rows)
        rep["hbm_peak_bytes"] = max(
            float(rep.get("hbm_peak_bytes") or 0.0), island_peak)
    if rep.get("hbm_peak_bytes"):
        try:
            _metrics.gauge("pt_hbm_peak_bytes").set(
                rep["hbm_peak_bytes"])
        except Exception:
            pass
    hlo = hlo_text(engine, program, scope, feed, fetch_names,
                   block_idx=block_idx, iterations=iterations)
    if hlo:
        try:
            from ..tools import hbm_breakdown as hb
            rows, parsed_total = hb.breakdown(hlo, top=top)
            rep["hbm_rows"] = [
                {"category": c, "bytes": b, "write_bytes": w,
                 "instrs": n} for c, b, w, n, _ in rows]
            rep["hbm_parsed_bytes"] = parsed_total
        except Exception:
            pass
    ops = program_ops(program, block_idx)
    if ops:
        rep["program_ops"] = ops
    device = None
    if profile_steps > 0:
        device = measure_device_time(
            lambda: engine.run(program, scope, None, feed,
                               list(fetch_names)),
            steps=profile_steps)
        if device:
            rep["device"] = device
    dev_ms = (device or {}).get("device_ms_per_step")
    host_ms = (device or {}).get("host_ms_per_step")
    islands = island_rows(engine, device_ms_total=dev_ms)
    if islands:
        rep["islands"] = islands
    if stats and stats.get("flops"):
        # measured MFU over device seconds when the profiler saw the
        # chip; host wall seconds otherwise (labeled, upper-bounds the
        # true step time so this MFU is a lower bound)
        basis_ms = dev_ms or host_ms
        # scanned executables (num_iteration_per_run / PT_MULTI_STEP)
        # count the scan BODY once in cost_analysis; the measured span
        # covers the whole dispatch, so body FLOPs scale by the trip
        # count or the scanned path reports impossibly low MFU
        trip = float(stats.get("trip_count") or 1.0)
        mfu = mfu_estimate(stats["flops"] * trip,
                           (basis_ms or 0.0) / 1e3)
        if mfu is not None:
            rep["mfu_estimate"] = round(mfu, 4)
            rep["mfu_basis"] = "device" if dev_ms else "host_wall"
            try:
                _metrics.gauge("pt_mfu_estimate").set(mfu)
            except Exception:
                pass
    if not rep:
        rep["error"] = "nothing compiled to attribute (eager fallback?)"
    return rep


# ---------------------------------------------------------------------------
# deep-profile trigger (PT_DEEP_PROFILE_EVERY / request_deep_profile)
# ---------------------------------------------------------------------------

_DP = {"steps": 0, "active": None, "remaining": 0, "profiling": False,
       "requested": 0, "seq": 0}


def request_deep_profile(steps: Optional[int] = None) -> None:
    """On-demand trigger: the next observed engine step starts a
    K-step capture (K = ``steps`` or ``PT_DEEP_PROFILE_STEPS``)."""
    _DP["requested"] = int(steps or _dp_steps())


def deep_profile_active() -> bool:
    return _DP["active"] is not None


def _dp_steps() -> int:
    try:
        return max(1, int(os.environ.get("PT_DEEP_PROFILE_STEPS", "3")
                          or 3))
    except ValueError:
        return 3


def deep_profile_tick() -> Optional[str]:
    """Per-step tick from the engine's obs-finish hook (already behind
    ``_HOT``). Starts a capture on the Nth step or an explicit
    request; after K captured steps stops the profiler and returns the
    merged-timeline path (None otherwise). Never raises."""
    try:
        return _deep_profile_tick()
    except Exception:
        _DP["active"], _DP["remaining"] = None, 0
        return None


def _deep_profile_tick() -> Optional[str]:
    st = _DP
    st["steps"] += 1
    if st["active"] is None:
        try:
            every = int(os.environ.get("PT_DEEP_PROFILE_EVERY", "0")
                        or 0)
        except ValueError:
            every = 0
        req = st["requested"]
        if not req and (every <= 0 or st["steps"] % every != 0):
            return None
        st["requested"] = 0
        st["remaining"] = req or _dp_steps()
        st["active"] = tempfile.mkdtemp(prefix="pt_deep_profile_")
        st["profiling"] = False
        try:
            import jax
            jax.profiler.start_trace(st["active"])
            st["profiling"] = True
        except Exception:
            pass  # CPU-only / profiler busy: merge host spans anyway
        return None
    st["remaining"] -= 1
    if st["remaining"] > 0:
        return None
    tmp, st["active"] = st["active"], None
    trace_path = None
    if st["profiling"]:
        try:
            import jax
            jax.profiler.stop_trace()
            trace_path = _newest_trace(tmp)
        except Exception:
            pass
    return _emit_timeline(trace_path, tmp)


def _newest_trace(root: str) -> Optional[str]:
    newest, newest_m = None, -1.0
    for dirpath, _, names in os.walk(root):
        for n in names:
            if n.endswith(".trace.json.gz"):
                p = os.path.join(dirpath, n)
                m = os.path.getmtime(p)
                if m > newest_m:
                    newest, newest_m = p, m
    return newest


def _emit_timeline(trace_path: Optional[str], tmpdir: str
                   ) -> Optional[str]:
    """Merge device events + every span/flight dump in the shared
    flight directory (cross-worker when PT_FLIGHT_DIR is shared) into
    one chrome timeline next to the dumps."""
    try:
        from . import export as _export
        flight_dir = _recorder.default_dir()
        _tracing.dump_spans("deep_profile", directory=flight_dir)
        _recorder.dump("deep_profile", directory=flight_dir)
        inputs = [(os.path.basename(p), p)
                  for p in _tracing.find_span_dumps(flight_dir)]
        inputs.extend((os.path.basename(p), p)
                      for p in _recorder.find_dumps(flight_dir))
        if trace_path:
            inputs.append(("device", trace_path))
        if not inputs:
            return None
        trace = _export.merge_chrome_traces(inputs)
        _DP["seq"] += 1
        out = os.path.join(
            flight_dir,
            f"timeline_{os.getpid()}_{_DP['seq']}.json")
        with open(out, "w") as f:
            json.dump(trace, f)
        try:
            _metrics.counter("pt_deep_profiles_total").inc()
        except Exception:
            pass
        return out
    except Exception:
        return None
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
