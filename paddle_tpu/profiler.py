"""Profiler: host RAII annotations + device trace + chrome-trace export.

Parity: reference platform/profiler.{h,cc} (RecordEvent :81,
Enable/DisableProfiler :166), CUPTI DeviceTracer -> here jax.profiler
(XPlane/perfetto) captures device timelines, and tools/timeline.py's
chrome://tracing export is served by the same trace directory. Python
surface mirrors fluid.profiler (profiler :225, start_profiler,
stop_profiler, reset_profiler).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import defaultdict, deque
from typing import Deque, Dict, List, Optional

import jax

from .observability import metrics as _obs_metrics
from .observability import recorder as _obs_recorder

__all__ = ["profiler", "start_profiler", "stop_profiler",
           "reset_profiler", "RecordEvent", "cuda_profiler",
           "profiling_active", "set_max_events"]

# Bounded host-event buffer: a week-long run with the profiler left on
# must not grow memory without limit, so old spans fall off the left
# (same policy as the flight recorder's ring).
_MAX_EVENTS_DEFAULT = 100_000
_events: Deque[dict] = deque(maxlen=_MAX_EVENTS_DEFAULT)
_enabled = [False]
# the directory of the session THIS module opened (stop_profiler closes
# only its own); annotations do not look at it — a TraceMe lands in
# whatever session is open, whoever opened it
_trace_dir = [None]


def set_max_events(n: int) -> None:
    """Resize the host-event ring (drops buffered events)."""
    global _events
    _events = deque(_events, maxlen=max(1, int(n)))


def profiling_active() -> bool:
    """True while this module collects host events or holds a device
    trace open, or the observability layer is hot (telemetry enabled or
    the flight recorder armed — ``metrics._HOT``,
    docs/OBSERVABILITY.md). The engine's phase spans (:class:`StepClock`)
    do not ask: they are always emitted."""
    return (_enabled[0] or _trace_dir[0] is not None
            or _obs_metrics._HOT[0])


class RecordEvent:
    """RAII host annotation (reference profiler.h:81)."""

    def __init__(self, name: str):
        self.name = name
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        # a TraceMe with no session open costs under a microsecond, so
        # the span goes to any open session, not only this module's
        self._tc = jax.profiler.TraceAnnotation(self.name)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if _enabled[0]:
            # real thread id: prefetcher / checkpoint-writer / RPC-pool
            # spans must land on their own chrome-trace tracks
            _events.append({"name": self.name, "ts": self._t0 / 1e3,
                            "dur": (t1 - self._t0) / 1e3, "ph": "X",
                            "pid": os.getpid(),
                            "tid": threading.get_native_id()})
        self._tc.__exit__(*exc)
        return False


# ---------------------------------------------------------------------------
# the step's phase spans (docs/TRACING.md, "Profiler spans")
# ---------------------------------------------------------------------------

# One Executor.run is one `pt.step`; inside it these phases follow one
# another, never nested. Index = slot in StepClock.ns.
PHASE_NAMES = ("pt.executor.feed", "pt.engine.feed", "pt.engine.trace",
               "pt.engine.args", "pt.engine.rng", "pt.engine.dispatch",
               "pt.engine.release", "pt.engine.writeback",
               "pt.engine.fetch")
(P_EXECUTOR_FEED, P_FEED, P_TRACE, P_ARGS, P_RNG, P_DISPATCH,
 P_RELEASE, P_WRITEBACK, P_FETCH) = range(len(PHASE_NAMES))
FIRST_DISPATCH = "pt.engine.first_dispatch"
STEP_SPAN = "pt.step"
_NO_STAMPS = (0,) * (2 * len(PHASE_NAMES))
# a flight record's key for each slot: recorder.PHASE_KEYS, same order
assert len(_obs_recorder.PHASE_KEYS) == len(PHASE_NAMES)


class _Phase:
    """One phase of the running step: a TraceAnnotation (a span in any
    open profiler session, on the profiler's clock) and a start and an
    end stamp in the clock's preallocated slots."""

    __slots__ = ("_ns", "_i", "_name", "_tm")

    def __init__(self, ns, i, name):
        self._ns, self._i, self._name = ns, 2 * i, name

    def __enter__(self):
        self._tm = jax.profiler.TraceAnnotation(self._name)
        self._ns[self._i] = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._ns[self._i + 1] = time.perf_counter_ns()
        self._tm.__exit__(*exc)
        return False


class StepClock:
    """Where one thread's running step stamps its phases: no dict, no
    string built, nothing allocated but the span objects. The executor,
    a CompiledProgram and whichever engine runs the step (the
    data-parallel engine is not the executor's own) write the same
    slots, which is why the clock belongs to the thread and not to one
    engine; a step run inside another (a guard's re-execution, a
    multi-step replay, an autotune trial) overwrites the outer one's
    stamps. The engine reads the slots at the end of the step: always
    for the step's total (slow-step detection), and for the whole
    record (:meth:`phases`) when telemetry is on or the step was
    slow."""

    __slots__ = ("ns", "step_ns", "end_ns", "opened", "fast_path",
                 "traced", "sig")

    def __init__(self):
        self.ns = list(_NO_STAMPS)
        self.step_ns = self.end_ns = 0
        # Executor.run began the step and Engine.run has yet to take it
        self.opened = False
        self.fast_path = self.traced = False
        self.sig = None

    def begin_step(self, opened=False):
        self.ns[:] = _NO_STAMPS
        self.opened = opened
        self.fast_path = self.traced = False
        self.sig = None
        self.end_ns = 0
        self.step_ns = time.perf_counter_ns()

    def phase(self, i, name=None):
        return _Phase(self.ns, i, name or PHASE_NAMES[i])

    def end_step(self):
        """Close the step; returns its total in ns."""
        self.end_ns = time.perf_counter_ns()
        return self.end_ns - self.step_ns

    def phases(self):
        """({key: ms}, {key: ms from the step's start}) of the phases
        that ran, keyed as the flight record has them (`feed_ms`, ...;
        the executor's is `executor_feed_ms`)."""
        dur, off = {}, {}
        ns, t0 = self.ns, self.step_ns
        for i, key in enumerate(_obs_recorder.PHASE_KEYS):
            a, b = ns[2 * i], ns[2 * i + 1]
            if a and b >= a:
                dur[key] = (b - a) / 1e6
                off[key] = (a - t0) / 1e6
        dur["total_ms"] = (self.end_ns - t0) / 1e6
        return dur, off


_CLOCKS = threading.local()


def step_clock() -> StepClock:
    """This thread's StepClock."""
    try:
        return _CLOCKS.clock
    except AttributeError:
        clock = _CLOCKS.clock = StepClock()
        return clock


def start_profiler(state="All", tracer_option=None, trace_dir=None):
    _enabled[0] = True
    if trace_dir or state in ("All", "GPU", "TPU"):
        d = trace_dir or "/tmp/paddle_tpu_trace"
        os.makedirs(d, exist_ok=True)
        try:
            jax.profiler.start_trace(d)
            _trace_dir[0] = d
        except Exception:
            _trace_dir[0] = None


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    _enabled[0] = False
    if _trace_dir[0]:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        _trace_dir[0] = None
    # chrome trace export of host events (timeline.py parity)
    if _events and profile_path:
        with open(profile_path + ".chrome_trace.json", "w") as f:
            json.dump({"traceEvents": list(_events)}, f)
    _print_summary(sorted_key)


def reset_profiler():
    _events.clear()


def _print_summary(sorted_key):
    if not _events:
        return
    agg: Dict[str, List[float]] = defaultdict(list)
    for e in _events:
        agg[e["name"]].append(e["dur"])
    rows = [(name, len(ds), sum(ds), min(ds), max(ds),
             sum(ds) / len(ds)) for name, ds in agg.items()]
    if sorted_key in ("total", None):
        rows.sort(key=lambda r: -r[2])
    elif sorted_key == "calls":
        rows.sort(key=lambda r: -r[1])
    elif sorted_key == "max":
        rows.sort(key=lambda r: -r[4])
    print(f"{'Event':<40}{'Calls':>8}{'Total(us)':>14}{'Min':>10}"
          f"{'Max':>10}{'Ave':>10}")
    for name, calls, tot, mn, mx, ave in rows[:50]:
        print(f"{name:<40}{calls:>8}{tot:>14.1f}{mn:>10.1f}"
              f"{mx:>10.1f}{ave:>10.1f}")


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile"):
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*a, **kw):  # name parity; profiles the TPU device
    start_profiler("All")
    try:
        yield
    finally:
        stop_profiler()
