"""Step flight recorder: a fixed-size ring of per-step span records,
dumped automatically when something dies.

Every PR-5 failure mode (watchdog trip, injected ``PT_FAULT_PLAN``
kill, sticky async-dispatch error, SIGTERM preemption) used to leave
only an exception string; the actual *shape* of the last N steps —
which phase blew up, whether the fast path was still hitting, how deep
the async pipeline was — died with the process. The recorder keeps that
shape in a ring buffer the engine appends to (one dict per step, only
while armed) and :func:`dump` writes it as a JSONL postmortem artifact
read by ``tools/chaos_report.py`` and ``tools/metrics_report.py``.

Arming (all feed :data:`metrics._HOT`, the single hot-path gate):

* telemetry on (``FLAGS_telemetry`` / ``enable_telemetry``);
* a fault plan installed (``PT_FAULT_PLAN`` — chaos runs are armed
  automatically, so the kill's dump always has content);
* a step watchdog constructed (``FLAGS_step_timeout_s > 0``);
* explicit :func:`enable`.

Dump files land in ``$PT_FLIGHT_DIR`` (default
``<tmp>/paddle_tpu_flight``) as ``flight_<pid>_<reason>_<seq>.jsonl``:
a header line (kind=flight_header, reason, engine-counter snapshot)
followed by one line per retained step record, oldest first.
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Dict, List, Optional

from ..core.flags import FLAGS
from . import metrics as _metrics

__all__ = ["FlightRecorder", "flight_recorder", "record_step", "dump",
           "enable", "recording_active", "set_fault_active",
           "set_watchdog_active", "default_dir", "read_dump",
           "find_dumps", "summarize_dumps", "install_sigterm_hook"]

# a step record's phase keys, in the order the phases run (the slots
# of profiler.StepClock); `total_ms` rides beside them
PHASE_KEYS = ("executor_feed_ms", "feed_ms", "trace_ms", "args_ms",
              "rng_ms", "dispatch_ms", "release_ms", "writeback_ms",
              "fetch_ms")

_ENABLED = [False]
_FAULT = [False]
_WATCHDOG = [False]


def recording_active() -> bool:
    return (_ENABLED[0] or _FAULT[0] or _WATCHDOG[0]
            or _metrics.telemetry_active())


def enable(on: bool = True) -> None:
    _ENABLED[0] = bool(on)
    _metrics._recompute_hot()


def set_fault_active(on: bool) -> None:
    """Called by ``distributed.faults.install``: a chaos run arms the
    recorder so the injected failure's dump has the last-N steps."""
    _FAULT[0] = bool(on)
    _metrics._recompute_hot()


def set_watchdog_active(on: bool) -> None:
    """Called by ``resilience.StepWatchdog.__init__``: a watchdog trip
    must always have a postmortem to dump."""
    _WATCHDOG[0] = bool(on)
    _metrics._recompute_hot()


def default_dir() -> str:
    return os.environ.get(
        "PT_FLIGHT_DIR",
        os.path.join(tempfile.gettempdir(), "paddle_tpu_flight"))


class FlightRecorder:
    """Fixed-capacity ring of step-record dicts. Appends are O(1) and
    lock-free (index arithmetic under the GIL); ``snapshot``/``dump``
    take the lock only to get a consistent ordering."""

    def __init__(self, capacity: int = 64):
        self.capacity = max(1, int(capacity))
        self._ring: List[Optional[dict]] = [None] * self.capacity
        self._idx = 0          # total records ever appended
        self._lock = threading.Lock()
        self._dump_seq = 0

    def append(self, rec: dict) -> None:
        self._ring[self._idx % self.capacity] = rec
        self._idx += 1

    def __len__(self) -> int:
        return min(self._idx, self.capacity)

    @property
    def total_appended(self) -> int:
        return self._idx

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self.capacity
            self._idx = 0

    def snapshot(self) -> List[dict]:
        """Retained records, oldest first."""
        with self._lock:
            n, i = min(self._idx, self.capacity), self._idx
            return [self._ring[j % self.capacity]
                    for j in range(i - n, i)]

    def dump(self, reason: str, directory: Optional[str] = None,
             extra: Optional[dict] = None) -> Optional[str]:
        """Write the postmortem JSONL; returns the path, or None when
        the ring is empty (nothing to explain). Never raises — a dump
        is a best-effort artifact on a path that is already failing."""
        records = self.snapshot()
        if not records:
            return None
        try:
            d = directory or default_dir()
            os.makedirs(d, exist_ok=True)
            with self._lock:
                self._dump_seq += 1
                seq = self._dump_seq
            path = os.path.join(
                d, f"flight_{os.getpid()}_{reason}_{seq}.jsonl")
            header = {
                "kind": "flight_header", "version": 1,
                "reason": reason, "pid": os.getpid(),
                "time": time.time(),
                "steps_retained": len(records),
                "steps_total": self.total_appended,
                "counters": _engine_counter_snapshot(),
            }
            if extra:
                header.update(extra)
            with open(path, "w") as f:
                f.write(json.dumps(header) + "\n")
                for r in records:
                    f.write(json.dumps(
                        {"kind": "step", **r},
                        default=_json_fallback) + "\n")
            try:
                _metrics.counter("pt_flight_dumps_total").inc()
            except Exception:
                pass
            return path
        except Exception:
            return None


def _json_fallback(o):
    return repr(o)


def _engine_counter_snapshot() -> Dict[str, float]:
    out: Dict[str, float] = {}
    for eng in list(_metrics._ENGINES):
        for k, v in dict(getattr(eng, "counters", {})).items():
            out[k] = out.get(k, 0) + v
    return out


_RECORDER: Optional[FlightRecorder] = None


def flight_recorder() -> FlightRecorder:
    """The process-wide recorder, sized by
    ``FLAGS_flight_recorder_steps`` at first use."""
    global _RECORDER
    if _RECORDER is None:
        _RECORDER = FlightRecorder(
            int(getattr(FLAGS, "flight_recorder_steps", 64) or 64))
    return _RECORDER


def record_step(rec: dict) -> None:
    """Engine-side sink for one step record. The caller builds ``rec``
    while ``metrics._HOT`` is set, or — armed or not — for a step it
    found slow against its own running median (``"slow": True``; such a
    record always reaches the ring, so a postmortem of an ordinary run
    shows its stalls). Observes the phase histograms when telemetry is
    on and appends to the ring when the recorder is armed."""
    if _metrics.telemetry_active():
        reg = _metrics.default_registry()
        phases = rec.get("phases") or {}
        for key, name in (("feed_ms", "pt_step_feed_seconds"),
                          ("trace_ms", "pt_step_trace_seconds"),
                          ("dispatch_ms", "pt_step_dispatch_seconds"),
                          ("fetch_ms", "pt_step_fetch_seconds"),
                          ("total_ms", "pt_step_total_seconds")):
            v = phases.get(key)
            if v is not None:
                h = reg.get(name)
                if h is not None:
                    h.observe(v / 1e3)
    if recording_active() or rec.get("slow"):
        flight_recorder().append(rec)


def dump(reason: str, directory: Optional[str] = None,
         extra: Optional[dict] = None) -> Optional[str]:
    """Dump the process-wide recorder (no-op on an empty ring). The
    span ring rides along: every postmortem trigger (watchdog,
    injected fault, sticky async error, SIGTERM, skew) leaves both the
    step shapes AND the correlated spans, so straggler attribution
    (tools/chaos_report.py) works on any dump directory."""
    if _RECORDER is None:
        return None
    path = _RECORDER.dump(reason, directory=directory, extra=extra)
    if reason not in ("skew", "deep_profile"):
        # those two call dump_spans themselves (tracing.check_skew /
        # attribution._emit_timeline) — avoid double span dumps
        try:
            from . import tracing
            tracing.dump_spans(reason, directory=directory)
        except Exception:
            pass
    return path


def install_sigterm_hook() -> None:
    """Chain a SIGTERM handler that dumps the flight record before the
    previous disposition runs (CheckpointManager's preemption save
    also dumps on its own path; this is for processes without one).
    Main-thread only (signal semantics); never raises."""
    import signal
    try:
        prev = signal.getsignal(signal.SIGTERM)

        def _handler(signum, frame):
            dump("sigterm")
            if callable(prev):
                prev(signum, frame)
            elif prev == signal.SIG_DFL:
                signal.signal(signum, signal.SIG_DFL)
                os.kill(os.getpid(), signum)

        signal.signal(signal.SIGTERM, _handler)
    except (ValueError, OSError):
        pass  # not the main thread / restricted environment


# ---------------------------------------------------------------------------
# dump-file readers (tools/chaos_report.py, tools/metrics_report.py)
# ---------------------------------------------------------------------------

def read_dump(path: str) -> Dict:
    """Parse one dump file -> {"header": {...}, "records": [...]}."""
    header, records = None, []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            if obj.get("kind") == "flight_header":
                header = obj
            elif obj.get("kind") == "step":
                records.append(obj)
    return {"header": header or {}, "records": records}


def find_dumps(directory: Optional[str] = None) -> List[str]:
    d = directory or default_dir()
    if not os.path.isdir(d):
        return []
    return sorted(os.path.join(d, n) for n in os.listdir(d)
                  if n.startswith("flight_") and n.endswith(".jsonl"))


def summarize_dumps(directory: Optional[str] = None,
                    last_n: int = 8) -> List[Dict]:
    """Per-dump summary (the survival-report ingest format): reason,
    pid, retained-step span, and mean phase latencies over the last N
    records."""
    out = []
    for path in find_dumps(directory):
        try:
            d = read_dump(path)
        except (OSError, ValueError):
            continue
        recs = d["records"][-last_n:]
        steps = [r.get("step") for r in recs
                 if r.get("step") is not None]
        phases: Dict[str, float] = {}
        for key in PHASE_KEYS + ("total_ms",):
            vals = [r["phases"][key] for r in recs
                    if r.get("phases", {}).get(key) is not None]
            if vals:
                phases[key] = round(sum(vals) / len(vals), 3)
        out.append({
            "file": os.path.basename(path),
            "reason": d["header"].get("reason"),
            "pid": d["header"].get("pid"),
            "steps_retained": d["header"].get("steps_retained"),
            "steps_total": d["header"].get("steps_total"),
            "last_step": max(steps) if steps else None,
            "first_step": min(steps) if steps else None,
            "mean_phase_ms": phases,
            # steps the engine found slow, over the whole dump
            "slow_steps": [
                {"step": r.get("step"), "phases": r.get("phases"),
                 "gc": r.get("gc")}
                for r in d["records"] if r.get("slow")],
        })
    return out
