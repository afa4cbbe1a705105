"""HBM memory observatory: owner-attributed live-buffer census,
OOM/pressure postmortems, and a leak sentinel.

PR 10 attributed device *time* (per-island ms, measured MFU); this
module attributes device *memory*. The framework holds device-resident
state in at least seven places — Scope persistables, the engine
fast-path caches, the ghost-snapshot ring (stability/), pending
async-dispatch steps and fetch handles, checkpoint snapshot copies,
the reader prefetcher's staged batches, and tuning trial snapshots —
and until now none of them answered "who owns the HBM" when a run
OOMs or creeps toward the ceiling.

Design (same shape as recorder.py / tracing.py):

- **Registration is weak and passive.** Buffer-holding subsystems call
  ``track_scope`` / ``track_ghost_ring`` / ``track_snapshot`` /
  ``track_prefetcher`` / ``track_fetch_handle`` once at construction;
  the census *pulls* from the weak sets when it runs, so a tracked
  object pays nothing per step and dies naturally. Engines are
  enumerated through ``metrics._ENGINES`` (already weakly tracked for
  the counter collector) — no new engine-side registration.
- **One-boolean hot gate.** ``Engine._finish_step`` calls
  ``step_tick()`` only while ``metrics._HOT[0]`` is already true, and
  the tick itself re-checks ``census_active()``; with observability
  off the engine performs ZERO census work (``stats()['censuses']``
  stays 0 — tested).
- **Reconciled, not trusted.** Every census diffs the tagged set
  against ``jax.live_arrays()``: bytes nobody claimed are exported as
  owner ``"orphan"`` rather than hidden, and ``coverage_frac`` states
  how much of live HBM the taxonomy explains.
- **Postmortems ride the flight-recorder machinery.** Dumps land next
  to ``flight_*``/``spans_*`` files as
  ``memdump_<pid>_<reason>_<seq>.jsonl`` (reasons: ``oom``,
  ``watermark``, or caller-supplied), with census / top-buffer /
  per-island / donation sections. ``PT_HBM_DUMP_THRESHOLD_FRAC`` arms
  a rising-edge-debounced dump *before* the crash, mirroring
  ``PT_SKEW_DUMP_THRESHOLD_S`` (tracing.check_skew).

Tuning knobs (all env, read per use so tests can flip them):
``PT_HBM_CENSUS_EVERY`` (census cadence in steps, default 1),
``PT_HBM_DUMP_THRESHOLD_FRAC`` (0/unset = watermark off),
``PT_HBM_LIMIT_BYTES`` (device-limit override for hosts whose
``memory_stats()`` has no ``bytes_limit`` — e.g. CPU CI),
``PT_HBM_LEAK_WINDOW`` / ``PT_HBM_LEAK_MIN_BYTES`` (sentinel).
See docs/MEMORY.md for the owner taxonomy and dump format.
"""
from __future__ import annotations

import json
import os
import threading
import time
import warnings
import weakref
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax

from . import metrics as _metrics
from . import recorder as _recorder

__all__ = [
    "track_scope", "track_ghost_ring", "track_snapshot",
    "track_prefetcher", "track_fetch_handle", "track_kv_cache",
    "track_predictor", "note_host_bytes",
    "census", "census_active", "census_enabled", "enable", "step_tick",
    "stats", "reset", "LeakSentinel", "leak_sentinel",
    "check_watermark", "device_limit_bytes", "set_island_attribution",
    "island_attribution", "donation_stats", "dump", "read_memdump",
    "find_memdumps", "is_oom_error", "oom_postmortem",
    "static_plan_report",
]

# ---------------------------------------------------------------------------
# arming
# ---------------------------------------------------------------------------

# census armed explicitly (bench --compare-memory, tests) even when
# full telemetry is off; folded into metrics._recompute_hot so the
# engine builds its obs dict and reaches step_tick()
_ENABLED = [False]


def census_enabled() -> bool:
    return _ENABLED[0]


def census_active() -> bool:
    """True while the per-step census should run: full telemetry on, or
    the census armed explicitly via ``enable(True)``."""
    return _ENABLED[0] or _metrics.telemetry_active()


def enable(on: bool = True) -> None:
    """Arm (or disarm) the per-step census independently of full
    telemetry. Flips the engine's ``_HOT`` gate like
    ``recorder.enable`` does."""
    _ENABLED[0] = bool(on)
    _metrics._recompute_hot()
    if not on and not census_active():
        # engines only replace their tagged feed batch in
        # _finish_step while _HOT, which is now off — release it here
        # so a disarmed census never pins the last step's batch in HBM
        for eng in list(getattr(_metrics, "_ENGINES", ()) or ()):
            if getattr(eng, "_census_feed", None) is not None:
                eng._census_feed = None


# ---------------------------------------------------------------------------
# owner registration (weak, passive)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_SCOPES: "weakref.WeakSet" = weakref.WeakSet()
_GHOST_RINGS: "weakref.WeakSet" = weakref.WeakSet()
_SNAPSHOTS: "weakref.WeakSet" = weakref.WeakSet()
_PREFETCHERS: "weakref.WeakSet" = weakref.WeakSet()
_FETCH_HANDLES: "weakref.WeakSet" = weakref.WeakSet()
_KV_CACHES: "weakref.WeakSet" = weakref.WeakSet()
_PREDICTORS: "weakref.WeakSet" = weakref.WeakSet()
# host-side (non-HBM) byte claims, e.g. tuning trial snapshots: kept
# out of the live_arrays reconciliation, reported separately
_HOST_BYTES: Dict[str, int] = {}


def _track(ws: "weakref.WeakSet", obj: Any) -> None:
    if obj is None:
        return
    try:
        with _LOCK:
            ws.add(obj)
    except TypeError:
        pass  # not weakref-able; owner stays invisible (orphan bytes)


def track_scope(scope) -> None:
    """Tag a Scope's initialized variables (params, opt state, ...) as
    owner ``scope``. Called from the engine cold path."""
    _track(_SCOPES, scope)


def track_ghost_ring(ring) -> None:
    """Tag a stability GhostRing's captured values as ``ghost_ring``."""
    _track(_GHOST_RINGS, ring)


def track_snapshot(snapshot) -> None:
    """Tag a checkpoint Snapshot's shard copies as ``ckpt_snapshot``."""
    _track(_SNAPSHOTS, snapshot)


def track_prefetcher(prefetcher) -> None:
    """Tag a DeviceFeedPrefetcher's staged device batches as
    ``prefetch``."""
    _track(_PREFETCHERS, prefetcher)


def track_fetch_handle(handle) -> None:
    """Tag an async FetchHandle's live payload as ``pending_fetch``."""
    _track(_FETCH_HANDLES, handle)


def track_kv_cache(cache) -> None:
    """Tag a serving PagedKVCache's page slabs as owner ``kv_cache``.
    The cache exposes ``_census_arrays() -> [(label, array)]``
    (inference/serving/kv_cache.py); pages show up in the census,
    watermark dumps, and the leak sentinel like any first-class
    owner."""
    _track(_KV_CACHES, cache)


def track_predictor(pred) -> None:
    """Tag an AnalysisPredictor's device-resident parameters
    (``d_params``/``c_params`` per compiled signature) as owner
    ``predictor`` so inference buffers stop reporting as orphans."""
    _track(_PREDICTORS, pred)


def note_host_bytes(owner: str, nbytes: int) -> None:
    """Claim (or with 0, release) HOST memory for an owner — e.g. the
    autotuner's numpy scope snapshot. Host claims are reported in the
    census but never counted against the ``jax.live_arrays``
    reconciliation (they are not HBM)."""
    with _LOCK:
        if nbytes:
            _HOST_BYTES[str(owner)] = int(nbytes)
        else:
            _HOST_BYTES.pop(str(owner), None)


# ---------------------------------------------------------------------------
# buffer enumeration
# ---------------------------------------------------------------------------

def _arr_live(a) -> bool:
    try:
        if a is None or not hasattr(a, "nbytes"):
            return False
        deleted = getattr(a, "is_deleted", None)
        if deleted is not None and deleted():
            return False
    except Exception:
        return False
    return True


def _iter_owned() -> Iterator[Tuple[str, str, Any]]:
    """Yield ``(owner, label, array)`` for every buffer a registered
    subsystem claims. Order is dedupe priority: the first owner to
    claim an array object keeps it (scope wins over a cache that
    merely aliases a scope-held param)."""
    for scope in list(_SCOPES):
        try:
            names = list(scope.local_var_names())
        except Exception:
            continue
        for n in names:
            try:
                v = scope.find_var(n)
                if v is None or not v.is_initialized():
                    continue
                t = v.get_value()
            except Exception:
                continue
            yield "scope", n, getattr(t, "array", t)
    for ring in list(_GHOST_RINGS):
        for e in list(getattr(ring, "_ring", ()) or ()):
            vals = getattr(e, "values", None) or {}
            step = getattr(e, "step", "?")
            for n, a in vals.items():
                yield "ghost_ring", f"step{step}:{n}", a
    for snap in list(_SNAPSHOTS):
        for e in list(getattr(snap, "entries", ()) or ()):
            name = getattr(e, "name", "?")
            for i, shard in enumerate(getattr(e, "shards", ()) or ()):
                try:
                    _, data = shard
                except Exception:
                    continue
                yield "ckpt_snapshot", f"{name}#{i}", data
    for pf in list(_PREFETCHERS):
        q = getattr(pf, "_live_q", None)
        if q is None:
            continue
        try:
            staged = list(q.queue)  # snapshot; racy by design, best-effort
        except Exception:
            continue
        for bi, item in enumerate(staged):
            if not isinstance(item, dict):
                continue  # stop sentinel / error carrier
            for n, val in item.items():
                yield "prefetch", f"staged{bi}:{n}", getattr(val, "array", val)
    for h in list(_FETCH_HANDLES):
        yield "pending_fetch", str(getattr(h, "_name", "?")), \
            getattr(h, "_value", None)
    for kv in list(_KV_CACHES):
        try:
            entries = list(kv._census_arrays())
        except Exception:
            continue
        for label, a in entries:
            yield "kv_cache", str(label), a
    for pred in list(_PREDICTORS):
        store = getattr(pred, "_param_store", None) or {}
        for si, entry in enumerate(list(store.values())):
            try:
                d_params, c_params = entry
            except Exception:
                continue
            for n, a in dict(d_params).items():
                yield "predictor", f"sig{si}:{n}", a
            for n, a in dict(c_params).items():
                yield "predictor", f"sig{si}:{n}", a
    for eng in list(getattr(_metrics, "_ENGINES", ()) or ()):
        for p in list(getattr(eng, "_pending", ()) or ()):
            yield "pending_step", "nan_flags", getattr(p, "_nan_flags", None)
        for i, a in enumerate(getattr(eng, "_last_updated", ()) or ()):
            yield "engine_updated", f"updated[{i}]", a
        for n, a in (getattr(eng, "_census_feed", None) or {}).items():
            yield "feed", str(n), a


# ---------------------------------------------------------------------------
# census
# ---------------------------------------------------------------------------

_STATS = {"censuses": 0, "dumps": 0, "oom_postmortems": 0}
_LAST_CENSUS: List[Optional[Dict[str, Any]]] = [None]
_OWNER_SERIES_SEEN: set = set()


def census(top_n: int = 8) -> Dict[str, Any]:
    """Walk every registered owner, dedupe claims by array identity,
    reconcile against ``jax.live_arrays()``, export the
    ``pt_hbm_owner_bytes{owner}`` / ``pt_hbm_live_bytes`` gauges, and
    return the full result (owners, top-N buffers, orphan bytes,
    coverage)."""
    t0 = time.perf_counter()
    owners: Dict[str, Dict[str, int]] = {}
    tagged: Dict[int, str] = {}
    buffers: List[Dict[str, Any]] = []
    for owner, label, a in _iter_owned():
        if not isinstance(a, jax.Array) or not _arr_live(a):
            continue
        k = id(a)
        if k in tagged:
            continue
        nb = int(getattr(a, "nbytes", 0) or 0)
        tagged[k] = owner
        rec = owners.setdefault(owner, {"bytes": 0, "count": 0})
        rec["bytes"] += nb
        rec["count"] += 1
        buffers.append({
            "owner": owner, "label": label, "bytes": nb,
            "shape": list(getattr(a, "shape", ()) or ()),
            "dtype": str(getattr(a, "dtype", "?"))})
    live_bytes = 0
    orphan_bytes = 0
    orphan_count = 0
    try:
        live = jax.live_arrays()
    except Exception:
        live = []
    for a in live:
        if not _arr_live(a):
            continue
        nb = int(getattr(a, "nbytes", 0) or 0)
        live_bytes += nb
        if id(a) not in tagged:
            orphan_bytes += nb
            orphan_count += 1
            buffers.append({
                "owner": "orphan", "label": "untagged", "bytes": nb,
                "shape": list(getattr(a, "shape", ()) or ()),
                "dtype": str(getattr(a, "dtype", "?"))})
    tagged_bytes = sum(r["bytes"] for r in owners.values())
    if orphan_count:
        owners["orphan"] = {"bytes": orphan_bytes, "count": orphan_count}
    coverage = ((live_bytes - orphan_bytes) / live_bytes) \
        if live_bytes else 1.0
    buffers.sort(key=lambda b: b["bytes"], reverse=True)
    with _LOCK:
        host_owners = dict(_HOST_BYTES)
    out = {
        "t": time.time(),
        "owners": owners,
        "tagged_bytes": int(tagged_bytes),
        "live_bytes": int(live_bytes),
        "orphan_bytes": int(orphan_bytes),
        "coverage_frac": float(coverage),
        "host_owners": host_owners,
        "top_buffers": buffers[:max(0, int(top_n))],
        "census_ms": (time.perf_counter() - t0) * 1e3,
    }
    _export_gauges(out)
    _LAST_CENSUS[0] = out
    return out


def _export_gauges(c: Dict[str, Any]) -> None:
    try:
        g = _metrics.gauge("pt_hbm_owner_bytes")
        current = set(c["owners"])
        for owner in _OWNER_SERIES_SEEN - current:
            g.set(0.0, owner=owner)  # owner went away: zero, don't lie
        for owner, rec in c["owners"].items():
            g.set(float(rec["bytes"]), owner=owner)
        _OWNER_SERIES_SEEN.update(current)
        _metrics.gauge("pt_hbm_live_bytes").set(float(c["live_bytes"]))
    except Exception:
        pass


def last_census() -> Optional[Dict[str, Any]]:
    return _LAST_CENSUS[0]


def static_plan_report(program, feed_names=None, fetch_names=(),
                       dynamic_dim: int = 1,
                       census_snapshot: Optional[Dict[str, Any]] = None,
                       island_rows: Optional[List[Dict[str, Any]]] = None,
                       ) -> Dict[str, Any]:
    """Calibration hook: run the static HBM planner over ``program``
    and reconcile it against what the observatory actually measured —
    the census (live resident bytes) and, when available, the
    per-island compiled ``memory_analysis`` rows. Takes a fresh census
    when the observatory is armed and no snapshot is passed; otherwise
    reuses ``last_census()``. Returns the plan dict plus the error
    ratios ``analysis.memplan.reconcile`` computes — the number the
    bench ``analysis`` tail and docs/STATIC_ANALYSIS.md's calibration
    table report."""
    from ..analysis import memplan
    plan = memplan.plan_memory(program, feed_names=feed_names,
                               fetch_names=fetch_names,
                               dynamic_dim=dynamic_dim)
    if census_snapshot is None:
        census_snapshot = census() if census_active() else last_census()
    if island_rows is None:
        island_rows = island_attribution() or None
    rec = memplan.reconcile(plan, census=census_snapshot,
                            island_rows=island_rows)
    out = {"plan": plan.to_dict(), "reconcile": rec}
    try:
        err = rec.get("resident_error_ratio")
        if err is not None:
            _metrics.gauge("pt_static_plan_error_ratio").set(float(err))
    except Exception:
        pass
    return out


def stats() -> Dict[str, int]:
    """Process-local observatory counters (``censuses`` proves the
    disabled path did zero census work)."""
    return dict(_STATS)


# ---------------------------------------------------------------------------
# leak sentinel
# ---------------------------------------------------------------------------

def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class LeakSentinel:
    """Diff the census across a sliding step window; an owner whose
    bytes grew monotonically over the whole window by at least
    ``min_bytes`` is a leak suspect (cache past cap, unreleased ghost
    slots, pending-fetch backlog): gauge
    ``pt_hbm_leak_suspect_bytes{owner}`` is set to the window growth
    and a one-shot RuntimeWarning names the owner. Steady or sawtooth
    owners stay silent (gauge 0)."""

    def __init__(self, window: Optional[int] = None,
                 min_bytes: Optional[int] = None):
        if window is None:
            window = _env_int("PT_HBM_LEAK_WINDOW", 8)
        if min_bytes is None:
            min_bytes = _env_int("PT_HBM_LEAK_MIN_BYTES", 1 << 20)
        self.window = max(2, int(window))
        self.min_bytes = max(0, int(min_bytes))
        self._hist: Dict[str, List[int]] = {}
        self._warned: set = set()
        self._flagged: Dict[str, int] = {}

    def feed(self, owner_bytes: Dict[str, int]) -> Dict[str, int]:
        """Record one census's per-owner bytes; returns the currently
        flagged ``{owner: window_growth_bytes}``."""
        for owner in set(self._hist) | set(owner_bytes):
            h = self._hist.setdefault(owner, [])
            h.append(int(owner_bytes.get(owner, 0)))
            if len(h) > self.window:
                del h[:len(h) - self.window]
        flagged: Dict[str, int] = {}
        for owner, h in self._hist.items():
            if len(h) < self.window:
                continue
            growth = h[-1] - h[0]
            if growth >= self.min_bytes and growth > 0 and \
                    all(b >= a for a, b in zip(h, h[1:])):
                flagged[owner] = growth
        try:
            g = _metrics.gauge("pt_hbm_leak_suspect_bytes")
            for owner in self._flagged:
                if owner not in flagged:
                    g.set(0.0, owner=owner)
            for owner, growth in flagged.items():
                g.set(float(growth), owner=owner)
        except Exception:
            pass
        for owner, growth in flagged.items():
            if owner not in self._warned:
                self._warned.add(owner)
                warnings.warn(
                    f"HBM leak suspect: owner {owner!r} grew "
                    f"{growth} bytes monotonically over the last "
                    f"{self.window} censuses "
                    f"(pt_hbm_leak_suspect_bytes; docs/MEMORY.md)",
                    RuntimeWarning, stacklevel=2)
        self._flagged = flagged
        return flagged

    def reset(self) -> None:
        self._hist.clear()
        self._warned.clear()
        self._flagged.clear()


_SENTINEL: List[Optional[LeakSentinel]] = [None]


def leak_sentinel() -> LeakSentinel:
    if _SENTINEL[0] is None:
        _SENTINEL[0] = LeakSentinel()
    return _SENTINEL[0]


# ---------------------------------------------------------------------------
# pressure watermark (rising-edge, mirrors tracing.check_skew)
# ---------------------------------------------------------------------------

_WM_ARMED = [False]


def device_limit_bytes() -> Optional[int]:
    """HBM capacity for watermark fractions: ``PT_HBM_LIMIT_BYTES``
    when set (CPU CI has no real limit), else the default device's
    ``memory_stats()['bytes_limit']`` (TPU/GPU). None = unknown,
    watermark disabled."""
    env = os.environ.get("PT_HBM_LIMIT_BYTES")
    if env:
        try:
            return int(env) or None
        except ValueError:
            return None
    try:
        ms = jax.devices()[0].memory_stats() or {}
        return int(ms.get("bytes_limit", 0)) or None
    except Exception:
        return None


def check_watermark(c: Dict[str, Any]) -> bool:
    """Dump once on the rising edge of live-bytes pressure crossing
    ``PT_HBM_DUMP_THRESHOLD_FRAC`` of the device limit; re-arm only
    after pressure falls below half the threshold (same debounce as
    the step-skew dump)."""
    try:
        thr = float(os.environ.get("PT_HBM_DUMP_THRESHOLD_FRAC", "") or 0.0)
    except ValueError:
        thr = 0.0
    if thr <= 0:
        return False
    limit = device_limit_bytes()
    if not limit:
        return False
    usage = float(c.get("live_bytes", 0)) / float(limit)
    if usage >= thr:
        if _WM_ARMED[0]:
            return False
        _WM_ARMED[0] = True
        dump("watermark", census_snapshot=c,
             extra={"usage_frac": usage, "limit_bytes": limit,
                    "threshold_frac": thr})
        return True
    if usage < thr * 0.5:
        _WM_ARMED[0] = False
    return False


# ---------------------------------------------------------------------------
# per-island attribution cache + donation effectiveness
# ---------------------------------------------------------------------------

_ISLAND_ROWS: List[List[Dict[str, Any]]] = [[]]


def set_island_attribution(rows: List[Dict[str, Any]]) -> None:
    """attribution.island_memory_rows pushes its latest per-island
    memory split here so postmortem dumps carry it without
    recompiling."""
    _ISLAND_ROWS[0] = [dict(r) for r in (rows or [])]


def island_attribution() -> List[Dict[str, Any]]:
    return [dict(r) for r in _ISLAND_ROWS[0]]


def donation_stats() -> Dict[str, Any]:
    """Donation effectiveness over live engines' compiled entries:
    ``alias_size_in_bytes`` (bytes XLA actually reused in-place) over
    ``argument_size_in_bytes``, plus donated/const name counts from
    the fast-path entries. Best-effort; zeros when nothing compiled
    with ``.lower`` (e.g. scheduler-split steps)."""
    out = {"compiled_entries": 0, "argument_bytes": 0, "aliased_bytes": 0,
           "donated_names": 0, "const_names": 0,
           "effectiveness_frac": None}
    try:
        for eng in list(getattr(_metrics, "_ENGINES", ()) or ()):
            for traced in list(getattr(eng, "_cache", {}).values()):
                comp = getattr(traced, "_compiled_cache", None)
                if comp is None:
                    continue
                try:
                    ma = comp.memory_analysis()
                    arg = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
                    ali = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
                except Exception:
                    continue
                out["compiled_entries"] += 1
                out["argument_bytes"] += arg
                out["aliased_bytes"] += ali
            for entries in list((getattr(eng, "_fast", {}) or {}).values()):
                for ent in entries:
                    out["donated_names"] += \
                        len(getattr(ent, "donated_vars", ()) or ())
                    out["const_names"] += \
                        len(getattr(ent, "const_vars", ()) or ())
        if out["argument_bytes"]:
            out["effectiveness_frac"] = \
                out["aliased_bytes"] / out["argument_bytes"]
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------------
# memdump writer / readers (flight-recorder idiom)
# ---------------------------------------------------------------------------

_DUMP_SEQ = [0]
_TOP_N_DUMP = 16


def dump(reason: str, census_snapshot: Optional[Dict[str, Any]] = None,
         extra: Optional[Dict[str, Any]] = None,
         directory: Optional[str] = None) -> Optional[str]:
    """Write ``memdump_<pid>_<reason>_<seq>.jsonl`` next to the flight
    dumps: one ``mem_header`` line, one ``census`` line, top-N
    ``buffer`` lines, per-island ``island`` lines, one ``donation``
    line. Never raises (postmortem paths are already failing);
    returns the path or None."""
    try:
        c = census_snapshot if census_snapshot is not None \
            else census(top_n=_TOP_N_DUMP)
        d = directory or _recorder.default_dir()
        os.makedirs(d, exist_ok=True)
        _DUMP_SEQ[0] += 1
        path = os.path.join(
            d, f"memdump_{os.getpid()}_{reason}_{_DUMP_SEQ[0]}.jsonl")
        header = {"kind": "mem_header", "version": 1, "reason": reason,
                  "pid": os.getpid(), "time": time.time(),
                  "counters": _recorder._engine_counter_snapshot()}
        if extra:
            header.update(extra)
        rows = _ISLAND_ROWS[0]
        if not rows:
            # best-effort refresh: cached on the scheduled step, so
            # this only compiles if nothing attributed islands yet
            try:
                from . import attribution as _attr
                for eng in list(getattr(_metrics, "_ENGINES", ()) or ()):
                    rows = _attr.island_memory_rows(eng)
                    if rows:
                        break
            except Exception:
                rows = []
        with open(path, "w", encoding="utf-8") as f:
            def _w(rec):
                f.write(json.dumps(rec, default=_recorder._json_fallback)
                        + "\n")
            _w(header)
            _w({"kind": "census",
                **{k: v for k, v in c.items() if k != "top_buffers"}})
            for b in c.get("top_buffers", []):
                _w({"kind": "buffer", **b})
            for r in rows or []:
                _w({"kind": "island", **r})
            _w({"kind": "donation", **donation_stats()})
        _STATS["dumps"] += 1
        try:
            _metrics.counter("pt_memdumps_total").inc()
        except Exception:
            pass
        return path
    except Exception:
        return None


def find_memdumps(directory: Optional[str] = None) -> List[str]:
    d = directory or _recorder.default_dir()
    try:
        names = os.listdir(d)
    except OSError:
        return []
    return sorted(os.path.join(d, n) for n in names
                  if n.startswith("memdump_") and n.endswith(".jsonl"))


def read_memdump(path: str) -> Dict[str, Any]:
    """Parse one memdump into
    ``{header, census, buffers[], islands[], donation}``."""
    out: Dict[str, Any] = {"header": None, "census": None, "buffers": [],
                           "islands": [], "donation": None}
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            kind = rec.get("kind")
            if kind == "mem_header":
                out["header"] = rec
            elif kind == "census":
                out["census"] = rec
            elif kind == "buffer":
                out["buffers"].append(rec)
            elif kind == "island":
                out["islands"].append(rec)
            elif kind == "donation":
                out["donation"] = rec
    return out


# ---------------------------------------------------------------------------
# OOM postmortems
# ---------------------------------------------------------------------------

def is_oom_error(exc: BaseException) -> bool:
    """XLA surfaces HBM exhaustion as RESOURCE_EXHAUSTED (text varies
    by backend/version); match on the exception text so wrapped
    EnforceNotMet re-raises still qualify."""
    try:
        s = f"{type(exc).__name__}: {exc}".lower()
    except Exception:
        return False
    return ("resource_exhausted" in s or "resource exhausted" in s
            or "out of memory" in s)


def _find_memdump_tag(exc: BaseException) -> Optional[str]:
    e: Optional[BaseException] = exc
    for _ in range(8):
        if e is None:
            break
        tag = getattr(e, "_pt_memdump", None)
        if tag is not None:
            return tag
        e = getattr(e, "__cause__", None)
    return None


def oom_postmortem(exc: BaseException,
                   where: str = "engine") -> Optional[str]:
    """Write exactly ONE memory postmortem per OOM exception, however
    many catch points see it (engine dispatch, synchronize, async
    materialization): the dump path is tagged onto the exception (and
    its cause chain), so later calls return the existing path. No-op
    for non-OOM errors."""
    if exc is None or not is_oom_error(exc):
        return None
    existing = _find_memdump_tag(exc)
    if existing is not None:
        return existing or None
    path = dump("oom", extra={
        "where": where,
        "error": f"{type(exc).__name__}: {exc}"[:800]})
    tag = path or ""
    e: Optional[BaseException] = exc
    for _ in range(8):
        if e is None:
            break
        try:
            e._pt_memdump = tag
        except Exception:
            pass
        e = getattr(e, "__cause__", None)
    _STATS["oom_postmortems"] += 1
    try:
        _metrics.counter("pt_oom_postmortems_total").inc()
    except Exception:
        pass
    return path


# ---------------------------------------------------------------------------
# per-step tick (called from Engine._finish_step while _HOT)
# ---------------------------------------------------------------------------

_TICK = [0]


def step_tick() -> None:
    """One observatory heartbeat per engine step: census (at
    ``PT_HBM_CENSUS_EVERY`` cadence), gauge export, leak-sentinel
    feed, pressure watermark. Zero work unless ``census_active()``."""
    if not census_active():
        return
    _TICK[0] += 1
    every = _env_int("PT_HBM_CENSUS_EVERY", 1)
    if every > 1 and _TICK[0] % every:
        return
    c = census()
    _STATS["censuses"] += 1
    leak_sentinel().feed(
        {o: int(r["bytes"]) for o, r in c["owners"].items()})
    check_watermark(c)


def reset() -> None:
    """Test isolation: clear tick/dump/sentinel/watermark state and
    host-byte claims. Weak owner sets are cleared too (tracked objects
    re-register on next construction)."""
    _TICK[0] = 0
    _WM_ARMED[0] = False
    _SENTINEL[0] = None
    _ISLAND_ROWS[0] = []
    _LAST_CENSUS[0] = None
    for k in _STATS:
        _STATS[k] = 0
    with _LOCK:
        _HOST_BYTES.clear()
        for ws in (_SCOPES, _GHOST_RINGS, _SNAPSHOTS, _PREFETCHERS,
                   _FETCH_HANDLES, _KV_CACHES, _PREDICTORS):
            ws.clear()
