"""Export surfaces for the metrics registry and flight recorder.

Three ways out of the process (docs/OBSERVABILITY.md):

* **Prometheus-style text exposition** (:func:`render_exposition`)
  served by :class:`MetricsServer` over the same length-prefixed
  framing, restricted unpickler, and fault-injection hooks as the
  pserver RPC layer (``distributed/async_ps.py``) — the launch
  supervisor scrapes every trainer with :func:`scrape`. Setting
  ``PT_METRICS_PORT`` starts a per-trainer endpoint automatically at
  ``port + PADDLE_TRAINER_ID`` the first time an Engine registers.
* **JSONL dump files** (:func:`dump_metrics`) — one snapshot per line,
  aggregated fleet-wide by ``tools/metrics_report.py``.
* **chrome-trace merge** (:func:`flight_to_chrome_trace`) — flight
  recorder dumps become per-phase trace lanes for
  ``tools/timeline.py`` next to ``profiler.py`` host spans.

Everything here runs at scrape/dump time only; nothing in this module
is on the step hot path.
"""
from __future__ import annotations

import json
import os
import socket
import threading
import time
from typing import Dict, List, Optional

from . import metrics as _metrics
from . import recorder as _recorder

__all__ = ["render_exposition", "metrics_snapshot", "dump_metrics",
           "read_metrics_dump", "MetricsServer", "scrape",
           "maybe_start_from_env", "flight_to_chrome_trace",
           "spans_to_chrome_trace", "memdump_to_chrome_trace",
           "merge_chrome_traces"]


# ---------------------------------------------------------------------------
# text exposition
# ---------------------------------------------------------------------------

def _escape_label(v: str) -> str:
    return (str(v).replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r'\"'))


def _fmt_labels(labels: Dict[str, str], extra: str = "") -> str:
    parts = [f'{k}="{_escape_label(v)}"'
             for k, v in sorted(labels.items())]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def _fmt_value(v: float) -> str:
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def render_exposition(registry: Optional[
        "_metrics.MetricsRegistry"] = None) -> str:
    """Prometheus text format (version 0.0.4): # HELP / # TYPE headers,
    cumulative ``_bucket{le=...}`` + ``_sum`` + ``_count`` for
    histograms."""
    reg = registry or _metrics.default_registry()
    lines: List[str] = []
    for fam in reg.collect():
        if fam.help:
            lines.append(f"# HELP {fam.name} {fam.help}")
        lines.append(f"# TYPE {fam.name} {fam.type}")
        for labels, value in fam.samples:
            if fam.type == "histogram":
                h = value  # the Histogram object itself
                for bound, cum in h.cumulative():
                    le = "+Inf" if bound == float("inf") \
                        else _fmt_value(bound)
                    le_label = 'le="' + le + '"'
                    lines.append(
                        f"{fam.name}_bucket"
                        f"{_fmt_labels(labels, le_label)} {cum}")
                lines.append(f"{fam.name}_sum"
                             f"{_fmt_labels(labels)}"
                             f" {_fmt_value(h.sum)}")
                lines.append(f"{fam.name}_count"
                             f"{_fmt_labels(labels)} {h.count}")
            else:
                lines.append(f"{fam.name}{_fmt_labels(labels)} "
                             f"{_fmt_value(value)}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# JSON snapshot / dump files
# ---------------------------------------------------------------------------

def metrics_snapshot(registry: Optional[
        "_metrics.MetricsRegistry"] = None) -> Dict[str, dict]:
    """JSON-able {family name -> {type, samples}} snapshot; histograms
    flatten to sum/count/cumulative buckets. This is the ``metrics``
    object in the BENCH json tail and in dump files."""
    reg = registry or _metrics.default_registry()
    out: Dict[str, dict] = {}
    for fam in reg.collect():
        samples = []
        for labels, value in fam.samples:
            if fam.type == "histogram":
                h = value
                samples.append({
                    "labels": labels, "sum": h.sum, "count": h.count,
                    "buckets": [["+Inf" if b == float("inf") else b, c]
                                for b, c in h.cumulative()]})
            else:
                samples.append({"labels": labels, "value": float(value)})
        out[fam.name] = {"type": fam.type, "samples": samples}
    return out


def dump_metrics(directory: Optional[str] = None,
                 registry=None, extra: Optional[dict] = None
                 ) -> Optional[str]:
    """Append one snapshot line to this process's metrics JSONL file
    (``metrics_<pid>.jsonl`` under ``$PT_FLIGHT_DIR`` by default, next
    to the flight dumps so one directory holds a trainer's full
    postmortem). Never raises."""
    try:
        d = directory or _recorder.default_dir()
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"metrics_{os.getpid()}.jsonl")
        try:
            from . import tracing as _tracing
            worker = _tracing.worker_id()
        except Exception:
            worker = None
        line = {"kind": "metrics_snapshot", "pid": os.getpid(),
                "time": time.time(),
                "trainer_id": os.environ.get("PADDLE_TRAINER_ID"),
                "worker": worker,
                "families": metrics_snapshot(registry)}
        if extra:
            line.update(extra)
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
        return path
    except Exception:
        return None


def read_metrics_dump(path: str) -> List[dict]:
    """All snapshot lines from one metrics JSONL file."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            if obj.get("kind") == "metrics_snapshot":
                out.append(obj)
    return out


# ---------------------------------------------------------------------------
# scrape endpoint over the hardened RPC framing
# ---------------------------------------------------------------------------

class MetricsServer:
    """Tiny scrape endpoint reusing the pserver wire protocol
    (length-prefixed pickle, restricted unpickler, bounded message
    size, fault-injection hooks). Messages: ``{"t": "ping"}`` ->
    ``"pong"``, ``{"t": "metrics"}`` -> exposition text, ``{"t":
    "metrics_json"}`` -> :func:`metrics_snapshot` dict, ``{"t":
    "flight"}`` -> current flight-recorder ring snapshot."""

    def __init__(self, port: int, host: str = "127.0.0.1"):
        from ..distributed import async_ps as ps  # lazy: avoid cycle
        self._ps = ps
        self._srv = socket.create_server((host, int(port)))
        self._srv.settimeout(0.2)
        self.host = host
        self.port = self._srv.getsockname()[1]   # resolves port=0
        self.endpoint = f"{host}:{self.port}"
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._serve, name="pt-metrics", daemon=True)

    def start(self) -> "MetricsServer":
        self._thread.start()
        return self

    def _serve(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True).start()

    def _handle(self, conn: socket.socket) -> None:
        try:
            with conn:
                conn.settimeout(10.0)
                msg = self._ps._recv_msg(conn)
                t = msg.get("t") if isinstance(msg, dict) else None
                if t == "ping":
                    self._ps._send_msg(conn, "pong")
                elif t == "metrics":
                    self._ps._send_msg(conn, render_exposition())
                elif t == "metrics_json":
                    self._ps._send_msg(conn, metrics_snapshot())
                elif t == "flight":
                    self._ps._send_msg(
                        conn, _recorder.flight_recorder().snapshot())
                else:
                    self._ps._send_msg(
                        conn, {"err": f"unknown message {t!r}"})
        except (ConnectionError, OSError, ValueError):
            pass

    def stop(self) -> None:
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        if self._thread.is_alive():
            self._thread.join(timeout=2.0)


def scrape(endpoint: str, timeout: float = 10.0,
           as_json: bool = False):
    """One scrape of a trainer's metrics endpoint. Liveness-poll
    semantics: single attempt, no circuit-breaker bookkeeping — a
    monitoring miss must not poison the data-plane health view."""
    from ..distributed import async_ps as ps
    return ps._rpc(endpoint,
                   {"t": "metrics_json" if as_json else "metrics"},
                   timeout=timeout, retries=1, track_health=False)


_SERVER: Optional[MetricsServer] = None
_SERVER_LOCK = threading.Lock()


def maybe_start_from_env() -> Optional[MetricsServer]:
    """Start the process-wide scrape endpoint when ``PT_METRICS_PORT``
    is set (0/unset -> disabled). Multi-trainer launches get distinct
    ports: ``PT_METRICS_PORT + PADDLE_TRAINER_ID``. Idempotent; a bind
    failure (port taken by another process) disables quietly rather
    than killing training."""
    global _SERVER
    with _SERVER_LOCK:
        if _SERVER is not None:
            return _SERVER
        raw = os.environ.get("PT_METRICS_PORT")
        if not raw:
            return None
        try:
            base = int(raw)
        except ValueError:
            return None
        if base <= 0:
            return None
        tid = int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0)
        try:
            _SERVER = MetricsServer(base + tid).start()
        except OSError:
            return None
        return _SERVER


# ---------------------------------------------------------------------------
# chrome-trace merge (tools/timeline.py)
# ---------------------------------------------------------------------------



def flight_to_chrome_trace(path: str) -> List[dict]:
    """Convert one flight-recorder dump into chrome trace events: each
    step's phases render as complete ('X') events, one lane (tid) per
    phase, anchored at the step's host wall time and placed where the
    record's ``phase_t0_ms`` stamps say (back to back where a record
    carries none)."""
    d = _recorder.read_dump(path)
    pid = d["header"].get("pid", 0)
    events: List[dict] = []
    for rec in d["records"]:
        t0 = float(rec.get("t_host") or 0.0) * 1e6  # seconds -> us
        step = rec.get("step")
        phases = rec.get("phases") or {}
        starts = rec.get("phase_t0_ms")
        off = 0.0
        for lane, key in enumerate(_recorder.PHASE_KEYS):
            v = phases.get(key)
            if not v:
                continue
            if starts is not None:
                off = float(starts.get(key) or 0.0) * 1e3
            dur = float(v) * 1e3                    # ms -> us
            args = {"step": step}
            for k in ("sig", "fast_path", "traced", "comm_plan",
                      "pending_fetches"):
                if rec.get(k) is not None:
                    args[k] = rec[k]
            events.append({
                "name": key[:-3], "cat": "flight", "ph": "X",
                "ts": t0 + off, "dur": dur,
                "pid": pid, "tid": lane + 1, "args": args})
            off += dur
    return events


# one lane (tid) per span kind so the timeline groups step roots,
# phases, RPC pairs, fetch waits and ckpt writes
_SPAN_LANES = {"step": 1, "phase": 2, "rpc.client": 3,
               "rpc.server": 4, "fetch": 5, "ckpt": 6}


def spans_to_chrome_trace(path: str) -> List[dict]:
    """Convert one span dump (``spans_<pid>_*.jsonl``,
    docs/TRACING.md) into chrome trace events: complete ('X') events
    anchored at each span's wall time, one lane per span kind, with
    trace/span/parent ids in args so correlated client/server pairs
    are inspectable across merged processes."""
    from . import tracing as _tracing
    d = _tracing.read_span_dump(path)
    pid = d["header"].get("pid", 0)
    events: List[dict] = []
    for s in d["spans"]:
        args = {k: s.get(k)
                for k in ("trace", "span", "parent", "worker")
                if s.get(k) is not None}
        ann = s.get("ann")
        if isinstance(ann, dict):
            args.update(ann)
        kind = s.get("kind", "host")
        events.append({
            "name": s.get("name", "?"), "cat": f"span.{kind}",
            "ph": "X", "ts": float(s.get("t0") or 0.0) * 1e6,
            "dur": max(float(s.get("dur_ms") or 0.0) * 1e3, 1.0),
            "pid": pid,
            "tid": _SPAN_LANES.get(kind, len(_SPAN_LANES) + 1),
            "args": args})
    return events


def memdump_to_chrome_trace(path: str) -> List[dict]:
    """Convert one HBM memory dump (``memdump_<pid>_*.jsonl``,
    docs/MEMORY.md) into chrome trace events rendered as a memory
    lane: a counter ('C') event per owner so the owner breakdown
    graphs as stacked area, one counter for live/tagged/orphan
    totals, plus complete ('X') events for the top live buffers so
    the dump's heaviest allocations are inspectable at the dump
    instant."""
    from . import memory as _memory
    d = _memory.read_memdump(path)
    header = d.get("header") or {}
    census = d.get("census") or {}
    pid = header.get("pid", 0)
    ts = float(census.get("t") or header.get("time") or 0.0) * 1e6
    events: List[dict] = []
    owners = census.get("owners") or {}
    if owners:
        events.append({
            "name": "hbm_owner_bytes", "cat": "memory", "ph": "C",
            "ts": ts, "pid": pid, "tid": 0,
            "args": {o: int((r or {}).get("bytes", 0))
                     for o, r in owners.items()}})
    events.append({
        "name": "hbm_bytes", "cat": "memory", "ph": "C",
        "ts": ts, "pid": pid, "tid": 0,
        "args": {"live": int(census.get("live_bytes") or 0),
                 "tagged": int(census.get("tagged_bytes") or 0),
                 "orphan": int(census.get("orphan_bytes") or 0)}})
    # top buffers: one lane, biggest first; fixed 1ms width — the dump
    # is a snapshot, duration only exists so chrome renders a bar
    for i, b in enumerate(d.get("buffers") or []):
        events.append({
            "name": f"{b.get('owner', '?')}:{b.get('label', '?')}",
            "cat": "memory.buffer", "ph": "X",
            "ts": ts + i * 1e3, "dur": 1e3, "pid": pid, "tid": 1,
            "args": {k: b.get(k)
                     for k in ("owner", "label", "bytes", "shape",
                               "dtype") if b.get(k) is not None}})
    if d.get("donation"):
        events.append({
            "name": "donation", "cat": "memory", "ph": "I",
            "ts": ts, "pid": pid, "tid": 0, "s": "p",
            "args": d["donation"]})
    return events


def _load_trace_events(path: str) -> List[dict]:
    """Events of one timeline input: span/flight/memdump JSONL dumps
    convert, chrome traces (.json / .json.gz, incl. jax.profiler
    output) pass through."""
    base = os.path.basename(path)
    if path.endswith(".jsonl"):
        if base.startswith("spans_"):
            return spans_to_chrome_trace(path)
        if base.startswith("memdump_"):
            return memdump_to_chrome_trace(path)
        return flight_to_chrome_trace(path)
    import gzip
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    if isinstance(data, list):
        return data
    return data.get("traceEvents", [])


def merge_chrome_traces(inputs) -> dict:
    """Merge ``[(name, path)]`` timeline inputs into ONE chrome trace
    dict: every input gets its own pid (named via a process_name
    metadata record) so a 2-trainer + 1-pserver run's span dumps,
    flight dumps and device profiles sit side by side, correlated by
    the trace ids in span args. Unreadable inputs are skipped — a
    postmortem merge must render whatever survived."""
    events: List[dict] = []
    for pid, (name, path) in enumerate(inputs):
        try:
            evs = _load_trace_events(path)
        except Exception:
            continue
        for e in evs:
            if not isinstance(e, dict):
                continue
            e = dict(e)
            e["pid"] = pid
            events.append(e)
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": name}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}
