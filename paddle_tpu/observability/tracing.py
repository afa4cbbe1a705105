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

from . import metrics as _metrics
from . import recorder as _recorder

__all__ = ["worker_id", "set_worker", "default_worker", "new_span_id",
           "begin_step", "current_context", "span", "server_span",
           "record_span", "finish_step", "span_buffer",
           "setup_span", "setup_spans", "clear_setup_spans",
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
    threads of one process."""
    if _WORKER[0] is None:
        w = os.environ.get("PT_WORKER")
        if not w:
            tid = os.environ.get("PADDLE_TRAINER_ID")
            w = f"trainer{tid}" if tid not in (None, "") \
                else f"pid{os.getpid()}"
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

# What a process spends before its first steady step — `trace_step` per
# program (child `trace_step.op_walk`, the abstract walk over the ops'
# lowerings that finds the updated persistables) and `first_dispatch`
# per executable (jit lowering plus XLA compile or persistent-cache
# load). They occur once per executable, never per
# step, so they are kept without the telemetry switch, and apart from
# the span ring: 4,096 step spans would push them out of it. With _HOT
# set they are mirrored into the ring, so the dumps tools/timeline.py
# reads show them.
_SETUP_MAX = 512
_SETUP: List[dict] = []


def setup_spans() -> List[dict]:
    """The process's set-up spans, oldest first (record shape of
    :func:`record_span`, ``kind="setup"``)."""
    return list(_SETUP)


def clear_setup_spans() -> None:
    del _SETUP[:]


class _SetupSpan:
    __slots__ = ("name", "ann", "sid", "parent", "t0", "_p0")

    def __init__(self, name, parent, ann):
        self.name, self.ann = name, ann
        self.parent = parent.sid if parent is not None else None
        self.sid = new_span_id()

    def __enter__(self):
        self.t0 = time.time()
        self._p0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.ann.setdefault("error", exc_type.__name__)
        rec = _span_record(
            self.name, self.t0, (time.perf_counter() - self._p0) * 1e3,
            "setup", f"{worker_id()}-setup", self.sid, self.parent,
            self.ann)
        _SETUP.append(rec)
        if len(_SETUP) > _SETUP_MAX:
            del _SETUP[0]
        if _metrics._HOT[0]:
            _ring_append(rec)
        return False


def setup_span(name: str, parent: Optional[_SetupSpan] = None, **ann):
    """``with setup_span("trace_step", program=fp) as sp: ...``; a child
    passes ``parent=sp``. Annotations may be added to ``sp.ann`` until
    the block ends."""
    return _SetupSpan(name, parent, ann)


_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                 "/jax/compilation_cache/cache_misses": 0}
_CACHE_LISTENING = [False]


def compile_cache_events():
    """(hits, misses) of JAX's persistent compilation cache so far, as
    ``jax.monitoring`` reports them; counted from the first call on."""
    if not _CACHE_LISTENING[0]:
        _CACHE_LISTENING[0] = True
        import jax.monitoring

        def _count(event, **kw):
            if event in _CACHE_EVENTS:
                _CACHE_EVENTS[event] += 1

        jax.monitoring.register_event_listener(_count)
    return tuple(_CACHE_EVENTS.values())


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
