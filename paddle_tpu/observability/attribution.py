"""What the observability layer reads off a compiled step, and the
deep-profile trigger.

* :func:`mfu_estimate` / :func:`peak_tflops` — FLOPs per step over
  seconds per step against the chip's dense bf16 peak. The caller
  brings both numbers; nothing here times a step or counts FLOPs
  (``benchmark/`` does, for the cells the driver judges).

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
from typing import Dict, List, Optional

from . import metrics as _metrics
from . import recorder as _recorder
from . import tracing as _tracing

__all__ = ["mfu_estimate", "PEAK_TFLOPS", "peak_tflops",
           "request_deep_profile",
           "deep_profile_tick", "deep_profile_active"]

# Dense bf16 matmul peak TFLOP/s per chip, keyed by the device_kind
# JAX reports (Google Cloud TPU documentation, per-generation system
# architecture pages). A device that is not listed is an error, never
# a default. benchmark/lib/peaks.py holds its own table on purpose:
# the yardstick takes nothing from the program under test.
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
