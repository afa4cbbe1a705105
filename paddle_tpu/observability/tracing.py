"""Distributed tracing: correlated spans across trainers and pservers.

The reference Fluid correlates host and device activity with a
profiler + CUPTI DeviceTracer and merges multi-trainer profiles in
``tools/timeline.py``; our rebuild's observability layer (PR 6) stopped
at per-process metrics — flight dumps are per-pid islands with no
cross-worker correlation. This module adds the correlation layer:

* **Deterministic trace ids.** Every step's trace id is
  ``<worker>-<step>`` — derivable from (worker id, step counter), so
  two processes that exchanged RPCs during the same step agree on the
  id without any coordination or randomness.
* **Spans.** One bounded ring of span dicts (``trace``/``span``/
  ``parent``/``name``/``kind``/``worker``/``t0``/``dur_ms`` + an
  ``ann`` annotation dict). The engine derives step/phase/lane spans
  from the obs record it already builds (:func:`finish_step`), the RPC
  layer records client and server spans, async-dispatch fetch handles
  record their materialization waits, and the checkpoint manager its
  background writes.
* **The one-boolean contract** (docs/OBSERVABILITY.md): every recording
  entry point checks ``metrics._HOT[0]`` first and :func:`span` returns
  a shared no-op context manager while it is false — the disabled path
  records zero spans and pays one list-index read.
* **Context propagation.** :func:`current_context` returns a
  builtins-only dict (it must survive the hardened RPC layer's
  restricted unpickler) that callers inject into the ``async_ps``
  message header; the pserver's handler records a server-side span
  whose ``trace``/``parent`` come from that context, so client and
  server spans correlate in one timeline.
* **Skew detection.** Trainers piggyback a step-duration summary on
  every heartbeat; the pserver aggregates them into fleet skew
  (``pt_step_skew_seconds`` + slowest-worker gauges) and piggybacks the
  result on the heartbeat reply, so EVERY worker can compare skew
  against ``PT_SKEW_DUMP_THRESHOLD_S`` and arm a flight + span dump on
  the rising edge — the straggler postmortem exists on all machines,
  not just the slow one.

Span dumps land next to the flight dumps as
``spans_<pid>_<reason>_<seq>.jsonl`` (header line + one span per line)
so ``tools/timeline.py`` and ``tools/chaos_report.py`` ingest them from
the same directory. See docs/TRACING.md.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

import jax

from . import metrics as _metrics
from . import recorder as _recorder

__all__ = ["worker_id", "set_worker", "default_worker", "new_span_id",
           "begin_step", "current_context", "span", "server_span",
           "record_span", "finish_step", "span_buffer",
           "setup_span", "setup_spans", "clear_setup_spans",
           "build_totals", "compile_totals", "reset_setup_totals",
           "note_infer_shapes", "compile_cache_events",
           "spans_snapshot", "clear_spans", "dump_spans",
           "read_span_dump", "find_span_dumps", "note_step_duration",
           "step_summary", "update_skew", "skew_snapshot",
           "observe_skew_reply", "check_skew"]


# ---------------------------------------------------------------------------
# worker identity & span ids
# ---------------------------------------------------------------------------

_WORKER: List[Optional[str]] = [None]


def worker_id() -> str:
    """Stable identity of this process in the fleet: ``PT_WORKER`` env
    override, else ``trainer<PADDLE_TRAINER_ID>``, else ``pid<pid>``
    (standalone runs). Part of every trace id, so it must agree across
    threads of one process. The ``pid`` fallback is nobody's choice and
    is not kept: the `import` set-up span asks before a pserver has
    called :func:`default_worker`."""
    if _WORKER[0] is None:
        w = os.environ.get("PT_WORKER")
        if not w:
            tid = os.environ.get("PADDLE_TRAINER_ID")
            if tid in (None, ""):
                return f"pid{os.getpid()}"
            w = f"trainer{tid}"
        _WORKER[0] = w
    return _WORKER[0]


def set_worker(name: Optional[str]) -> None:
    _WORKER[0] = str(name) if name else None


def default_worker(name: str) -> None:
    """Set the worker id only if nothing chose one yet (the pserver
    labels itself ``ps<port>`` without clobbering an explicit
    ``PT_WORKER``)."""
    if _WORKER[0] is None and not os.environ.get("PT_WORKER") \
            and os.environ.get("PADDLE_TRAINER_ID") in (None, ""):
        _WORKER[0] = str(name)


_SEQ = itertools.count(1)


def new_span_id() -> str:
    return f"{worker_id()}.s{next(_SEQ)}"


# ---------------------------------------------------------------------------
# span ring
# ---------------------------------------------------------------------------

class SpanBuffer:
    """Fixed-capacity ring of span dicts (same shape as the flight
    recorder's ring: O(1) lock-free appends, locked snapshot)."""

    def __init__(self, capacity: int = 4096):
        self.capacity = max(1, int(capacity))
        self._ring: List[Optional[dict]] = [None] * self.capacity
        self._idx = 0
        self._lock = threading.Lock()

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
        with self._lock:
            n, i = min(self._idx, self.capacity), self._idx
            return [self._ring[j % self.capacity]
                    for j in range(i - n, i)]


_BUFFER: Optional[SpanBuffer] = None


def span_buffer() -> SpanBuffer:
    global _BUFFER
    if _BUFFER is None:
        try:
            cap = int(os.environ.get("PT_TRACE_SPANS", "4096") or 4096)
        except ValueError:
            cap = 4096
        _BUFFER = SpanBuffer(cap)
    return _BUFFER


def spans_snapshot() -> List[dict]:
    return span_buffer().snapshot() if _BUFFER is not None else []


def clear_spans() -> None:
    if _BUFFER is not None:
        _BUFFER.clear()


# ---------------------------------------------------------------------------
# per-thread trace context
# ---------------------------------------------------------------------------

_TLS = threading.local()


def begin_step(step) -> Optional[str]:
    """Open the deterministic trace for one engine step on this thread.
    Called by ``Engine.run`` only while ``_HOT`` (the obs record is
    built under the same gate); RPCs, fetch handles and checkpoint
    saves issued during the step inherit this context."""
    if not _metrics._HOT[0]:
        _TLS.ctx = None
        return None
    ctx = {"trace": f"{worker_id()}-{int(step)}", "step": int(step),
           "root": new_span_id(), "stack": []}
    _TLS.ctx = ctx
    return ctx["trace"]


def _ctx() -> Optional[dict]:
    return getattr(_TLS, "ctx", None)


def current_context() -> Optional[Dict[str, str]]:
    """Builtins-only propagation context for the RPC message header
    (str values only — it must pass the restricted unpickler on the
    receiving side). None while tracing is off or outside a step."""
    if not _metrics._HOT[0]:
        return None
    ctx = _ctx()
    if ctx is None:
        return None
    parent = ctx["stack"][-1] if ctx["stack"] else ctx["root"]
    return {"trace": ctx["trace"], "span": parent,
            "worker": worker_id()}


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------

def record_span(name: str, t0: float, dur_ms: float, kind: str = "host",
                trace: Optional[str] = None, span_id: Optional[str] = None,
                parent: Optional[str] = None,
                ann: Optional[dict] = None) -> Optional[dict]:
    """Append one finished span to the ring. Returns the record (so
    callers can parent children under it) or None while tracing is
    off. ``trace``/``parent`` default to the thread's current step
    context."""
    if not _metrics._HOT[0]:
        return None
    ctx = _ctx()
    if trace is None:
        trace = ctx["trace"] if ctx else f"{worker_id()}-detached"
    if parent is None and ctx is not None:
        parent = ctx["stack"][-1] if ctx["stack"] else ctx["root"]
    return _ring_append(_span_record(name, t0, dur_ms, kind, trace,
                                     span_id or new_span_id(), parent,
                                     ann))


def _span_record(name, t0, dur_ms, kind, trace, span_id, parent, ann):
    rec = {"trace": trace, "span": span_id, "parent": parent,
           "name": name, "kind": kind, "worker": worker_id(),
           "t0": round(float(t0), 6), "dur_ms": round(float(dur_ms), 3)}
    if ann:
        rec["ann"] = {k: v for k, v in ann.items() if v is not None}
    return rec


def _ring_append(rec):
    span_buffer().append(rec)
    try:
        _metrics.counter("pt_spans_recorded_total").inc(kind=rec["kind"])
    except Exception:
        pass
    return rec


class _NoopSpan:
    """Shared do-nothing context manager: the cost of ``span(...)``
    with tracing off is one list read + one attribute load."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **kw):
        return self


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "kind", "ann", "sid", "t0", "_pushed")

    def __init__(self, name: str, kind: str, ann: dict):
        self.name = name
        self.kind = kind
        self.ann = ann
        self.sid = new_span_id()
        self.t0 = 0.0
        self._pushed = False

    def annotate(self, **kw):
        self.ann.update(kw)
        return self

    def __enter__(self):
        self.t0 = time.time()
        ctx = _ctx()
        if ctx is not None:
            ctx["stack"].append(self.sid)
            self._pushed = True
        return self

    def __exit__(self, exc_type, exc, tb):
        ctx = _ctx()
        if self._pushed and ctx is not None and ctx["stack"] \
                and ctx["stack"][-1] == self.sid:
            ctx["stack"].pop()
        if exc_type is not None:
            self.ann.setdefault("error", exc_type.__name__)
        record_span(self.name, self.t0,
                    (time.time() - self.t0) * 1e3, kind=self.kind,
                    span_id=self.sid, ann=self.ann)
        return False


def span(name: str, kind: str = "host", **ann):
    """``with span("ckpt_save", kind="ckpt", step=12): ...`` — no-op
    singleton while tracing is off (zero spans recorded)."""
    if not _metrics._HOT[0]:
        return _NOOP
    return _Span(name, kind, ann)


class _ServerSpan:
    """Server-side span adopted from a propagated context: the parent
    is the CLIENT's span id, so the pair correlates across processes
    without touching this thread's local step context."""

    __slots__ = ("name", "kind", "ann", "trace", "parent", "t0")

    def __init__(self, tctx: dict, name: str, kind: str, ann: dict):
        self.name = name
        self.kind = kind
        self.ann = dict(ann)
        self.trace = str(tctx.get("trace") or "")
        self.parent = tctx.get("span")
        w = tctx.get("worker")
        if w:
            self.ann.setdefault("peer", str(w))
        self.t0 = 0.0

    def annotate(self, **kw):
        self.ann.update(kw)
        return self

    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.ann.setdefault("error", exc_type.__name__)
        record_span(self.name, self.t0,
                    (time.time() - self.t0) * 1e3, kind=self.kind,
                    trace=self.trace or None, parent=self.parent,
                    ann=self.ann)
        return False


def server_span(tctx: Optional[dict], name: str, kind: str = "rpc.server",
                **ann):
    """Span correlated to a received propagation context (pserver
    handler side). Falls back to a local span when the message carried
    no context; no-op while tracing is off."""
    if not _metrics._HOT[0]:
        return _NOOP
    if not isinstance(tctx, dict):
        return _Span(name, kind, ann)
    return _ServerSpan(tctx, name, kind, ann)


# ---------------------------------------------------------------------------
# set-up spans: once per executable, recorded whether or not _HOT
# ---------------------------------------------------------------------------

# What a process spends before its first steady step (docs/TRACING.md,
# "Set-up"): `import` (the package), `program_build.backward` /
# `.optimize` / `.amp` (the build passes that have an entry point),
# and per cold `Executor.run` one `cold_run` holding `trace_step`
# (child `trace_step.op_walk`, the abstract walk over the ops'
# lowerings that finds the updated persistables) and `first_dispatch`,
# whose children `first_dispatch.jit_trace` / `.lower` / `.compile` or
# `.cache_load` are what `jax.monitoring` timed inside it (the rest of
# it is the first execution). They occur once per executable, never per
# step, so they are kept without the telemetry switch, and apart from
# the span ring: 4,096 step spans would push them out of it. With _HOT
# set they are mirrored into the ring, so the dumps tools/timeline.py
# reads show them.
_SETUP_MAX = 512
_SETUP: List[dict] = []
# guards the process totals below; a listener call or an appended op
# takes it once
_TOTALS_LOCK = threading.Lock()
FIRST_DISPATCH = "first_dispatch"
_OUTSIDE = "outside"
_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}
# a nested event starts no earlier than the one around it; JAX times
# with time.time(), the arrival is read with perf_counter
_NESTING_SLACK_S = 5e-4
_JIT_PENDING_MAX = 4096


def _no_compiles():
    return {"jit_trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "cache_load_s": 0.0, "cache_retrieval_s": 0.0,
            "cache_hits": 0, "cache_misses": 0}


_COMPILES = {FIRST_DISPATCH: _no_compiles(), _OUTSIDE: _no_compiles()}
_BUILD: Dict[str, list] = {}       # op type -> [calls, seconds]
_INFERRED_S = [0.0]                # seconds over all op types
_LISTENING = [False]


def setup_spans() -> List[dict]:
    """The process's set-up spans, oldest first (record shape of
    :func:`record_span`, ``kind="setup"``)."""
    return list(_SETUP)


def clear_setup_spans() -> None:
    del _SETUP[:]


class _SetupState(threading.local):
    """A thread's set-up state: ``open``, its open set-up spans,
    innermost last; ``jit``, the outermost jit events since the
    innermost `first_dispatch` opened (or ever), newest last, each
    ``[key, start, seconds, function, side]`` on the perf_counter
    clock; ``retrieved``, set between a cache hit's retrieval event and
    the backend-compile event that holds it."""

    def __init__(self):
        self.open, self.jit, self.retrieved = [], [], False


_STATE = _SetupState()


def _append_setup(rec):
    _SETUP.append(rec)
    if len(_SETUP) > _SETUP_MAX:
        del _SETUP[0]
    if _metrics._HOT[0]:
        _ring_append(rec)


class _SetupSpan:
    __slots__ = ("name", "ann", "sid", "parent", "t0", "_p0", "_note",
                 "_outer_jit", "_inferred")

    def __init__(self, name, parent, p0, ann):
        self.name, self.ann, self._p0 = name, ann, p0
        self.parent = parent.sid if parent is not None else None
        self.sid = new_span_id()

    def __enter__(self):
        _listen()
        st = _STATE
        if st.open:
            outer = st.open[-1]
            if self.parent is None:
                self.parent = outer.sid
            if "program" in outer.ann:
                self.ann.setdefault("program", outer.ann["program"])
        st.open.append(self)
        if self.name == FIRST_DISPATCH:
            # the jit events from here to the end are this span's
            self._outer_jit, st.jit = st.jit, []
        self._inferred = _INFERRED_S[0]
        now = time.perf_counter()
        if self._p0 is None:
            self._p0 = now
        self.t0 = time.time() - (now - self._p0)
        # on the profiler's clock too, beside the device's first work
        # (from here on: a span backdated with p0 starts late there)
        self._note = jax.profiler.TraceAnnotation("pt.setup." + self.name)
        self._note.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._note.__exit__(exc_type, exc, tb)
        end = time.perf_counter()
        st = _STATE
        if self in st.open:
            del st.open[st.open.index(self):]
        if exc_type is not None:
            self.ann.setdefault("error", exc_type.__name__)
        inferred = _INFERRED_S[0] - self._inferred
        if inferred:
            # how much of a build pass was shape inference
            self.ann["infer_shapes_s"] = round(inferred, 6)
        if self.name == FIRST_DISPATCH:
            events, st.jit = st.jit, self._outer_jit
            self._record_jit_children(events, end)
        _append_setup(_span_record(
            self.name, self.t0, (end - self._p0) * 1e3, "setup",
            f"{worker_id()}-setup", self.sid, self.parent, self.ann))
        return False

    def _record_jit_children(self, events, end):
        """What the listener heard while this `first_dispatch` was
        open, as child records. It is told a duration and no start: the
        start is the arrival less the duration, held inside the parent
        (JAX times with another clock)."""
        for key, p0, secs, fun, _ in events:
            p0 = min(max(p0, self._p0), end)
            _append_setup(_span_record(
                f"{FIRST_DISPATCH}.{key}", self.t0 + (p0 - self._p0),
                min(secs, end - p0) * 1e3, "setup",
                f"{worker_id()}-setup", new_span_id(), self.sid,
                {"program": self.ann.get("program"), "fun": fun}))


def setup_span(name: str, parent: Optional[_SetupSpan] = None,
               p0: Optional[float] = None, **ann):
    """``with setup_span("trace_step", program=fp) as sp: ...``. The
    parent is ``parent`` or else the set-up span open around it on this
    thread, whose ``program`` annotation the span takes over unless it
    has its own. ``p0`` backdates the start to that ``perf_counter``
    reading (a span whose start is known only later: `import`,
    `cold_run`). Annotations may be added to ``sp.ann`` until the block
    ends."""
    return _SetupSpan(name, parent, p0, ann)


# ---------------------------------------------------------------------------
# set-up counters: shape inference at build, what JAX compiled
# ---------------------------------------------------------------------------

def note_infer_shapes(op_type: str, seconds: float) -> None:
    """One `_infer_op_shapes` call of ``Block.append_op``."""
    with _TOTALS_LOCK:
        rec = _BUILD.get(op_type)
        if rec is None:
            rec = _BUILD[op_type] = [0, 0.0]
        rec[0] += 1
        rec[1] += seconds
        _INFERRED_S[0] += seconds


def build_totals() -> Dict:
    """Calls and seconds of build-time shape inference (`jax.eval_shape`
    of an op's lowering, or its own `infer_shape`) in this process:
    ``{"calls", "seconds", "by_op": {type: {"calls", "seconds"}}}``.
    The forward layers are the user's calls and have no span; this is
    what they, and the build passes, spend per appended op."""
    with _TOTALS_LOCK:
        seconds = _INFERRED_S[0]
        by_op = {t: {"calls": c, "seconds": s}
                 for t, (c, s) in _BUILD.items()}
    return {"calls": sum(r["calls"] for r in by_op.values()),
            "seconds": seconds, "by_op": by_op}


def compile_totals() -> Dict[str, Dict]:
    """What JAX traced, lowered and compiled in this process, as
    ``jax.monitoring`` reported it from the first set-up span on:
    seconds of jit trace, lowering, backend compile (``compile_s`` the
    cache's misses and the requests it was not asked for,
    ``cache_load_s`` its hits; ``cache_retrieval_s`` is the part of
    the latter JAX spent reading and deserialising) and the persistent
    cache's hits and misses, under ``"first_dispatch"`` (arrived while
    one was open on the calling thread: the program's steps) and
    ``"outside"`` (everything else the job compiles: eager helpers, the
    caller's own jits). An event inside another's interval is not
    counted: the seconds never exceed the wall's."""
    _listen()
    with _TOTALS_LOCK:
        return {side: dict(t) for side, t in _COMPILES.items()}


def reset_setup_totals() -> None:
    with _TOTALS_LOCK:
        _BUILD.clear()
        _INFERRED_S[0] = 0.0
        for side in _COMPILES:
            _COMPILES[side] = _no_compiles()


def compile_cache_events():
    """(hits, misses) of JAX's persistent compilation cache so far, as
    ``jax.monitoring`` reports them; counted from the first call or
    set-up span on."""
    totals = compile_totals()
    return tuple(sum(t[k] for t in totals.values())
                 for k in _CACHE_EVENTS.values())


def _side(st):
    return FIRST_DISPATCH if any(
        s.name == FIRST_DISPATCH for s in st.open) else _OUTSIDE


def _on_event(event, **kw):
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        side = _side(_STATE)
        with _TOTALS_LOCK:
            _COMPILES[side][key] += 1


def _on_duration(event, seconds, **kw):
    now = time.perf_counter()
    st = _STATE
    if event == _CACHE_RETRIEVAL:
        # a hit; the backend-compile event around it arrives next
        st.retrieved = True
        with _TOTALS_LOCK:
            _COMPILES[_side(st)]["cache_retrieval_s"] += seconds
        return
    key = _JIT_EVENTS.get(event)
    if key is None:
        return
    if key == "compile" and st.retrieved:
        key, st.retrieved = "cache_load", False
    side, p0, jit = _side(st), now - seconds, st.jit
    with _TOTALS_LOCK:
        # a jitted function called inside another's trace, an eager
        # helper compiled inside it: the outermost event stands for all
        while jit and jit[-1][1] >= p0 - _NESTING_SLACK_S:
            inner = jit.pop()
            _COMPILES[inner[4]][inner[0] + "_s"] -= inner[2]
        _COMPILES[side][key + "_s"] += seconds
    jit.append([key, p0, seconds, kw.get("fun_name"), side])
    if len(jit) > _JIT_PENDING_MAX:
        del jit[:_JIT_PENDING_MAX // 2]


def _listen():
    """Register THE `jax.monitoring` listeners of the program (events:
    the cache's hits and misses; durations: jit trace, lowering, backend
    compile, cache retrieval), once."""
    if _LISTENING[0]:
        return
    _LISTENING[0] = True
    import jax.monitoring
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


# ---------------------------------------------------------------------------
# engine hook: derive step/phase/lane spans from the obs record
# ---------------------------------------------------------------------------



def finish_step(obs: dict) -> None:
    """Close out one step's trace: emit the root step span, one child
    per measured phase, and one grandchild per scheduler-lane island
    span — all derived from the stamps the engine's StepClock took
    (``profiler.py``), so tracing adds no clocks to the hot path. A
    phase span starts where its stamp says: ``obs["phase_t0_ms"]``
    holds each phase's offset from the step's start (a record without
    it, hand-built or from an older dump, has its phases laid end to
    end). Also feeds the step-duration window the heartbeat summaries
    read."""
    ctx = _ctx()
    _TLS.ctx = None
    if not _metrics._HOT[0]:
        return
    step = obs.get("step")
    trace = ctx["trace"] if ctx else f"{worker_id()}-{step}"
    root = ctx["root"] if ctx else new_span_id()
    t0 = float(obs.get("t_host") or time.time())
    phases = obs.get("phases") or {}
    total_ms = float(phases.get("total_ms") or 0.0)
    ann = {k: obs.get(k)
           for k in ("sig", "fast_path", "traced", "comm_plan",
                     "pending_fetches")
           if obs.get(k) is not None}
    ann["step"] = step
    record_span("step", t0, total_ms, kind="step", trace=trace,
                span_id=root, parent=None, ann=ann)
    starts = obs.get("phase_t0_ms")
    off = 0.0
    dispatch_t0, dispatch_sid = t0, root
    for key in _recorder.PHASE_KEYS:
        v = phases.get(key)
        if not v:
            continue
        if starts is not None:
            off = float(starts.get(key) or 0.0)
        rec = record_span(key[:-3], t0 + off / 1e3, float(v),
                          kind="phase", trace=trace, parent=root,
                          ann={"step": step})
        if key == "dispatch_ms" and rec is not None:
            dispatch_t0, dispatch_sid = t0 + off / 1e3, rec["span"]
        off += float(v)
    for lane in obs.get("lanes") or ():
        la = {"step": step, "phase": lane.get("phase"),
              "ops": lane.get("ops"), "island": lane.get("i")}
        if "micro_batch" in lane:
            la["micro_batch"] = lane["micro_batch"]
            name = f"micro_batch:{lane['micro_batch']}"
        else:
            la["lane"] = lane.get("lane")
            name = f"island:{lane.get('i', lane.get('lane'))}"
        record_span(name, dispatch_t0 + float(lane.get("t0_ms") or 0.0)
                    / 1e3, float(lane.get("dur_ms") or 0.0),
                    kind="lane", trace=trace, parent=dispatch_sid,
                    ann=la)
    if total_ms:
        note_step_duration(total_ms / 1e3, step=step)


# ---------------------------------------------------------------------------
# span dumps (next to the flight dumps)
# ---------------------------------------------------------------------------

_DUMP_SEQ = itertools.count(1)


def dump_spans(reason: str, directory: Optional[str] = None,
               extra: Optional[dict] = None) -> Optional[str]:
    """Write the span ring as ``spans_<pid>_<reason>_<seq>.jsonl``
    (header + one span per line). Same contract as the flight
    recorder's dump: best-effort, never raises, None on an empty
    ring."""
    buf = _BUFFER
    if buf is None or len(buf) == 0:
        return None
    try:
        d = directory or _recorder.default_dir()
        os.makedirs(d, exist_ok=True)
        path = os.path.join(
            d, f"spans_{os.getpid()}_{reason}_{next(_DUMP_SEQ)}.jsonl")
        header = {"kind": "span_header", "version": 1, "reason": reason,
                  "pid": os.getpid(), "worker": worker_id(),
                  "time": time.time(), "spans_retained": len(buf),
                  "spans_total": buf.total_appended}
        if extra:
            header.update(extra)
        with open(path, "w") as f:
            f.write(json.dumps(header, default=repr) + "\n")
            for s in buf.snapshot():
                # spans keep their own "kind" (step/phase/rpc.*/...);
                # the header line is the only non-span record
                f.write(json.dumps(s, default=repr) + "\n")
        try:
            _metrics.counter("pt_span_dumps_total").inc()
        except Exception:
            pass
        return path
    except Exception:
        return None


def read_span_dump(path: str) -> Dict:
    """Parse one span dump -> {"header": {...}, "spans": [...]}."""
    header, spans = None, []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            if obj.get("kind") == "span_header":
                header = obj
            else:
                spans.append(obj)
    return {"header": header or {}, "spans": spans}


def find_span_dumps(directory: Optional[str] = None) -> List[str]:
    d = directory or _recorder.default_dir()
    if not os.path.isdir(d):
        return []
    return sorted(os.path.join(d, n) for n in os.listdir(d)
                  if n.startswith("spans_") and n.endswith(".jsonl"))


# ---------------------------------------------------------------------------
# step-duration summaries & fleet skew
# ---------------------------------------------------------------------------

_DUR_LOCK = threading.Lock()
_DURS: List[float] = []
_DUR_WINDOW = 64
_LAST_STEP = [0]


def note_step_duration(seconds: float, step=None) -> None:
    with _DUR_LOCK:
        _DURS.append(float(seconds))
        if len(_DURS) > _DUR_WINDOW:
            _DURS.pop(0)
        if step is not None:
            _LAST_STEP[0] = int(step)


def step_summary() -> Optional[Dict]:
    """Builtins-only step-duration summary for the heartbeat piggyback
    (None before the first observed step — heartbeats then carry no
    summary, exactly the pre-tracing wire shape)."""
    with _DUR_LOCK:
        if not _DURS:
            return None
        srt = sorted(_DURS)
        return {"worker": worker_id(), "step": _LAST_STEP[0],
                "count": len(_DURS),
                "mean_s": round(sum(_DURS) / len(_DURS), 6),
                "p50_s": round(srt[len(srt) // 2], 6),
                "last_s": round(_DURS[-1], 6)}


_LAST_SKEW: List[Optional[dict]] = [None]
_SKEW_ARMED = [False]


def update_skew(summaries: Dict) -> Optional[Dict]:
    """Fleet skew from per-worker summaries ({trainer_id -> summary},
    the pserver's TrainerRegistry store): slowest minus fastest mean
    step duration. Sets ``pt_step_skew_seconds`` and the
    slowest-worker gauge; returns the builtins-only skew dict that
    rides the heartbeat reply (None with fewer than two reporting
    workers)."""
    vals: Dict[str, float] = {}
    for wid, s in (summaries or {}).items():
        if not isinstance(s, dict):
            continue
        m = s.get("mean_s")
        if m is None:
            continue
        vals[str(s.get("worker", wid))] = float(m)
    if len(vals) < 2:
        return None
    slowest = max(vals, key=vals.get)
    fastest = min(vals, key=vals.get)
    skew = vals[slowest] - vals[fastest]
    try:
        _metrics.gauge("pt_step_skew_seconds").set(skew)
        _metrics.gauge("pt_step_slowest_worker_seconds").set(
            vals[slowest], worker=slowest)
    except Exception:
        pass
    rep = {"skew_s": round(skew, 6), "slowest": slowest,
           "slowest_mean_s": round(vals[slowest], 6),
           "fastest": fastest,
           "fastest_mean_s": round(vals[fastest], 6),
           "workers": len(vals)}
    _LAST_SKEW[0] = rep
    check_skew(skew)
    return rep


def skew_snapshot() -> Optional[Dict]:
    return _LAST_SKEW[0]


def check_skew(skew_s) -> bool:
    """Arm a flight + span dump when fleet skew crosses
    ``PT_SKEW_DUMP_THRESHOLD_S`` (0/unset disables). Rising-edge
    debounced: one dump per excursion, re-arming only after skew falls
    back under half the threshold."""
    try:
        thr = float(os.environ.get("PT_SKEW_DUMP_THRESHOLD_S", "0")
                    or 0.0)
    except ValueError:
        return False
    if thr <= 0 or skew_s is None:
        return False
    s = float(skew_s)
    if s >= thr:
        if _SKEW_ARMED[0]:
            return False
        _SKEW_ARMED[0] = True
        extra = {"skew_s": round(s, 6), "threshold_s": thr}
        _recorder.dump("skew", extra=extra)
        dump_spans("skew", extra=extra)
        return True
    if s < thr * 0.5:
        _SKEW_ARMED[0] = False
    return False


def observe_skew_reply(rep) -> None:
    """Heartbeat-reply hook (trainer side): the pserver piggybacks the
    fleet skew it computed; every worker mirrors the gauge locally and
    runs the same dump-threshold check, so the straggler postmortem is
    captured fleet-wide. Tolerates pre-tracing replies ("ok" / None)."""
    if not isinstance(rep, dict):
        return
    skew = rep.get("skew")
    if not isinstance(skew, dict):
        return
    _LAST_SKEW[0] = skew
    s = skew.get("skew_s")
    if s is None:
        return
    try:
        _metrics.gauge("pt_step_skew_seconds").set(float(s))
    except Exception:
        pass
    check_skew(s)
